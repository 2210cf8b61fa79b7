"""Plain float32 reference of a dense decoder: RMSNorm, grouped-query
attention with rotate-half RoPE over the whole head (and RMSNorm on q and
k for qk-norm models), SwiGLU MLP, tied or untied LM head.

It reads the configuration file and the benchmark's weights by the
program's parameter names, and imports nothing of the program. Every
matmul runs in float32 at ``HIGHEST`` precision; the whole forward runs
in one jitted scan over layers, upcasting one layer's weights at a time,
with attention in query blocks, so it fits beside the bfloat16 weights.

``quant="fp8"`` is the control: every projection and the LM head take
both operands through float8 e4m3 (activations scaled per row, weights
per output channel) before the float32 matmul, the step below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QBLOCK = 512        # attention query block
VBLOCK = 32768      # vocabulary block of the LM head
SEQ_BUCKET = 512    # sequences pad to a multiple of this (causal: harmless)
ROW_BUCKET = 256    # compared positions pad to a multiple of this


def _q8(x, axis):
    """Round ``x`` through float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (n, k) @ w (k, m) in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (n, h, dh), rotate-half over the whole head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA. q (n, H, dh), k/v (n, K, dh) -> (n, H*dh)."""
    n, H, dh = q.shape
    K = k.shape[1]
    q = q.reshape(n, K, H // K, dh) * dh ** -0.5
    out = []
    for s in range(0, n, QBLOCK):
        e = min(s + QBLOCK, n)
        qb, kb, vb = q[s:e], k[:e], v[:e]
        sc = jnp.einsum("qkgd,skd->kgqs", qb, kb, precision=HI)
        mask = (jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :])
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("kgqs,skd->qkgd", p, vb, precision=HI))
    return jnp.concatenate(out, 0).reshape(n, H * dh)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _hidden(params, tokens, *, m, quant):
    """Final-normed hidden states (n, d) of one token sequence."""
    n = tokens.shape[0]
    pos = jnp.arange(n)
    x = params["embedding"][tokens].astype(jnp.float32)
    H, K, dh, d = m["H"], m["K"], m["dh"], m["d"]

    def layer(x, lp):
        a, f = lp["attn"], lp["mlp"]
        h = _rms(x, a["ln"], m["eps"])
        q = _mm(h, a["wq"].reshape(d, H * dh), quant).reshape(n, H, dh)
        k = _mm(h, a["wk"].reshape(d, K * dh), quant).reshape(n, K, dh)
        v = _mm(h, a["wv"].reshape(d, K * dh), quant).reshape(n, K, dh)
        if m["qk_norm"]:
            q = _rms(q, a["q_norm"], m["eps"])
            k = _rms(k, a["k_norm"], m["eps"])
        q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
        x = x + _mm(_attention(q, k, v), a["wo"].reshape(H * dh, d), quant)
        h = _rms(x, f["ln"], m["eps"])
        g = _mm(h, f["wg"], quant)
        u = _mm(h, f["wu"], quant)
        return x + _mm(jax.nn.silu(g) * u, f["wd"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_ln"], m["eps"])


@functools.partial(jax.jit, static_argnames=("m",))
def _compare(params, h_ref, h_ctl, served, *, m):
    """Per compared row: the reference's best logit, its logit of the
    served token, and its logit of the token the control puts first
    (``h_ctl`` None: no control)."""
    V = m["V"]
    rows = h_ref.shape[0]
    best = jnp.full((rows,), -jnp.inf)
    at_served = jnp.zeros((rows,))
    ctl_best = jnp.full((rows,), -jnp.inf)
    at_ctl = jnp.zeros((rows,))
    for s in range(0, V, VBLOCK):
        e = min(s + VBLOCK, V)
        if m["tied"]:
            w = params["embedding"][s:e].astype(jnp.float32).T
        else:
            w = params["unembed"][:, s:e].astype(jnp.float32)
        lg = jnp.matmul(h_ref, w, precision=HI)
        best = jnp.maximum(best, lg.max(-1))
        inb = (served >= s) & (served < e)
        got = jnp.take_along_axis(lg, jnp.clip(served - s, 0, e - s - 1)[:, None],
                                  -1)[:, 0]
        at_served = jnp.where(inb, got, at_served)
        if h_ctl is not None:
            lc = _mm(h_ctl, w, True)
            i = lc.argmax(-1)
            mx = lc.max(-1)
            better = mx > ctl_best
            ctl_best = jnp.where(better, mx, ctl_best)
            at_ctl = jnp.where(better, jnp.take_along_axis(lg, i[:, None], -1)[:, 0],
                               at_ctl)
    return best, at_served, at_ctl


def _pad(a, mult, fill=0):
    n = -(-len(a) // mult) * mult
    out = np.full((n,), fill, np.int32)
    out[: len(a)] = a
    return out


def logit_gaps(conf: dict, params, seqs, *, control: bool = False) -> dict:
    """For each ``(prompt, served)`` pair: run the reference over the
    prompt and the served tokens, and at every served token read how far
    its reference logit lies below the reference's best at that position.
    With ``control``, also read that gap for the token the fp8 control
    puts first at the same positions. Returns per-sequence numpy arrays."""
    from bench.families.dense import dims

    m = dims(conf)
    mkey = tuple(sorted(m.items()))
    out = {"gap": [], "control_gap": []}
    for prompt, served in seqs:
        toks = np.asarray(list(prompt) + list(served)[:-1], np.int32)
        n = len(toks)
        padded = jnp.asarray(_pad(toks, SEQ_BUCKET))
        rows = np.arange(len(prompt) - 1, n)           # predict served[j]
        ridx = jnp.asarray(_pad(rows, ROW_BUCKET))
        tgt = jnp.asarray(_pad(np.asarray(served, np.int32), ROW_BUCKET))
        h = _hidden(params, padded, m=_M(mkey), quant=False)[ridx]
        hc = (_hidden(params, padded, m=_M(mkey), quant=True)[ridx]
              if control else None)
        best, at_s, at_c = jax.device_get(
            _compare(params, h, hc, tgt, m=_M(mkey)))
        k = len(served)
        out["gap"].append(np.asarray(best[:k] - at_s[:k]))
        if control:
            out["control_gap"].append(np.asarray(best[:k] - at_c[:k]))
    return out


class _M(dict):
    """Hashable dims for a static jit argument."""

    def __init__(self, items):
        super().__init__(items)
        self._key = tuple(items)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _M) and self._key == other._key


def full_logits(conf: dict, params, tokens, *, quant: bool = False):
    """Logits (n, V) at every position of one sequence; for small sizes
    (the tests), where the whole (n, V) array fits."""
    from bench.families.dense import dims

    m = dims(conf)
    toks = np.asarray(tokens, np.int32)
    h = _hidden(params, jnp.asarray(_pad(toks, SEQ_BUCKET)),
                m=_M(tuple(sorted(m.items()))), quant=quant)[: len(toks)]
    w = (params["embedding"].astype(jnp.float32).T if m["tied"]
         else params["unembed"].astype(jnp.float32))
    if quant:
        return np.asarray(_mm(h, w, True))
    return np.asarray(jnp.matmul(h, w, precision=HI))
