"""Plain float32 reference of an MoE decoder with multi-head latent
attention (DeepSeek-V3's block, as Moonlight-16B-A3B publishes it), for
one chip's share of its experts.

- Attention, in the expanded form of the published modeling: ``q = x
  W_q`` (H heads of nope + rope dims, no q-LoRA); ``[c, k_pe] = x
  W_kv_a``; ``c`` through RMSNorm; ``[k_nope, v] = c W_kv_b``; the keys are
  ``[k_nope, k_pe]`` with the one rope head shared by every head; causal
  softmax scaled by ``(nope + rope)**-0.5``. RoPE rotates the rope dims
  as the published modeling does: viewed as pairs ``(x[2i], x[2i+1])``,
  transposed to evens then odds, then rotated half against half.
- Routing over all ``E`` experts: sigmoid scores; the top k of scores plus
  the correction bias are chosen; their scores, normalized over the k and
  times ``routed_scaling_factor``, weigh them. Only the experts this chip
  holds are computed and added (each token's part from them), as the
  share of an expert-parallel layer; the shared experts and the leading
  dense layer are whole.
- SwiGLU MLPs and experts, RMSNorm, an untied LM head.

It reads the configuration file and the benchmark's weights by the
program's parameter names, and imports nothing of the program. Every
matmul runs in float32 at ``HIGHEST`` precision; the forward runs in one
jitted scan per layer stack, upcasting one layer's weights at a time,
with attention in query blocks and the held experts all at once.

``quant="fp8"`` is the control: every projection, the router, the experts
and the LM head take both operands through float8 e4m3 (activations
scaled per row, weights per output channel) before the float32 matmul,
the step below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import (_M, _compare, _mm, _pad, _q8, _rms,
                                   HI, QBLOCK, ROW_BUCKET, SEQ_BUCKET)


def _rope(x, pos, theta):
    """x (n, h, dr): the published pair layout, then rotate-half."""
    n, h, dr = x.shape
    x = x.reshape(n, h, dr // 2, 2).swapaxes(-1, -2).reshape(n, h, dr)
    half = dr // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale):
    """Causal MHA. q, k (n, H, dq), v (n, H, dv) -> (n, H * dv)."""
    n, H, _ = q.shape
    out = []
    for s in range(0, n, QBLOCK):
        e = min(s + QBLOCK, n)
        sc = jnp.einsum("qhd,shd->hqs", q[s:e] * scale, k[:e], precision=HI)
        mask = jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqs,shd->qhd", p, v[:e], precision=HI))
    return jnp.concatenate(out, 0).reshape(n, -1)


def _mla(a, x, pos, m, quant):
    n, d = x.shape
    H, nope, dr, dv, r = m["H"], m["nope"], m["dr"], m["dv"], m["r"]
    h = _rms(x, a["ln"], m["eps"])
    q = _mm(h, a["wq"].reshape(d, -1), quant).reshape(n, H, nope + dr)
    ckv = _mm(h, a["wkv_a"], quant)
    c = _rms(ckv[:, :r], a["kv_norm"], m["eps"])
    k_pe = _rope(ckv[:, None, r:], pos, m["theta"])
    kv = _mm(c, a["wkv_b"].reshape(r, -1), quant).reshape(n, H, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (n, H, dr))], -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, m["theta"])],
                        -1)
    o = _attention(q, k, kv[..., nope:], (nope + dr) ** -0.5)
    return x + _mm(o, a["wo"].reshape(H * dv, d), quant)


def _swiglu(f, h, quant):
    g = _mm(h, f["wg"], quant)
    u = _mm(h, f["wu"], quant)
    return _mm(jax.nn.silu(g) * u, f["wd"], quant)


def _experts(f, h, m, quant):
    """The held experts' part of the routed MLP, (n, d), and each token's
    routing margin: how far the k-th chosen expert's score (plus bias)
    lies above the best unchosen one's."""
    scores = jax.nn.sigmoid(_mm(h, f["router"], quant))            # (n, E)
    pick, top = jax.lax.top_k(
        scores + f["router_bias"].astype(jnp.float32), m["k"] + 1)
    margin, top = pick[:, -2] - pick[:, -1], top[:, :-1]
    w = jnp.take_along_axis(scores, top, -1)
    if m["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * m["routed_scale"]
    # each token's weight on each held expert (0 where not chosen)
    held = jnp.arange(m["E_held"]) + m["E_first"]
    gate = jnp.sum(jnp.where(top[:, :, None] == held, w[:, :, None], 0.0), 1)
    wg, wu, wd = (f[k].astype(jnp.float32) for k in ("wg", "wu", "wd"))
    if quant:
        wg, wu, wd = _q8(wg, 1), _q8(wu, 1), _q8(wd, 1)
        h = _q8(h, -1)
    g = jnp.einsum("nd,edf->nef", h, wg, precision=HI)
    u = jnp.einsum("nd,edf->nef", h, wu, precision=HI)
    a = jax.nn.silu(g) * u
    if quant:
        a = _q8(a, -1)
    y = jnp.einsum("nef,efd->ned", a, wd, precision=HI)
    return jnp.einsum("ned,ne->nd", y, gate, precision=HI), margin


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _hidden(params, tokens, *, m, quant):
    """Final-normed hidden states (n, d) of one token sequence, and per
    position the least routing margin over the MoE layers."""
    pos = jnp.arange(tokens.shape[0])
    x = params["embedding"][tokens].astype(jnp.float32)

    def dense(x, lp):
        x = _mla(lp["attn"], x, pos, m, quant)
        h = _rms(x, lp["mlp"]["ln"], m["eps"])
        return x + _swiglu(lp["mlp"], h, quant), None

    def moe(x, lp):
        x = _mla(lp["attn"], x, pos, m, quant)
        f = lp["mlp"]
        h = _rms(x, f["ln"], m["eps"])
        y, margin = _experts(f, h, m, quant)
        return x + _swiglu(f["shared"], h, quant) + y, margin

    if "dense_layers" in params:
        x, _ = jax.lax.scan(dense, x, params["dense_layers"])
    x, margins = jax.lax.scan(moe, x, params["moe_layers"])
    return _rms(x, params["final_ln"], m["eps"]), margins.min(0)


def _dims(conf: dict) -> "_M":
    from bench.families.mla_moe import dims

    return _M(tuple(sorted(dims(conf).items())))


def token_gaps(conf: dict, params, seqs, *, control: bool = False) -> dict:
    """For each ``(prompt, served)`` pair: run the reference over the
    prompt and the served tokens, and at every served token read how far
    its reference logit lies below the reference's best at that position.
    With ``control``, also read that gap for the token the fp8 control
    puts first at the same positions. Returns per-sequence numpy arrays."""
    m = _dims(conf)
    cm = _M(tuple(sorted(dict(m, tied=False).items())))
    out = {"gap": [], "control_gap": []}
    for prompt, served in seqs:
        toks = np.asarray(list(prompt) + list(served)[:-1], np.int32)
        padded = jnp.asarray(_pad(toks, SEQ_BUCKET))
        rows = np.arange(len(prompt) - 1, len(toks))     # predict served[j]
        ridx = jnp.asarray(_pad(rows, ROW_BUCKET))
        tgt = jnp.asarray(_pad(np.asarray(served, np.int32), ROW_BUCKET))
        h = _hidden(params, padded, m=m, quant=False)[0][ridx]
        hc = (_hidden(params, padded, m=m, quant=True)[0][ridx]
              if control else None)
        best, at_s, at_c = jax.device_get(_compare(params, h, hc, tgt, m=cm))
        k = len(served)
        out["gap"].append(np.asarray(best[:k] - at_s[:k]))
        if control:
            out["control_gap"].append(np.asarray(best[:k] - at_c[:k]))
    return out


def logit_gaps(conf: dict, params, seqs, *, control: bool = False) -> dict:
    """The check's reading of each ``(prompt, served)`` pair: the mean of
    its served tokens' gaps (:func:`token_gaps`), given at each of them,
    so that the check's largest is the worst request's mean gap.

    The mean, and not each token's gap, because a routed token's experts
    are decided by top-k over 64 close scores in each of 26 layers: where
    the reference decides one by less than bfloat16 rounds the router's
    input, a bfloat16 program takes the other expert, and the random
    weights carry that difference on through the layers after it. So the
    worst single token of a bfloat16 run lies about as far below the
    reference's best as the worst of an fp8 run (1.88 against 2.45 on a
    TPU v5e, same seed), while over a request's tokens the bfloat16 program
    puts the reference's best first almost everywhere and fp8 does not
    (worst request's mean 0.089 against 0.584). A fault of the program (a
    wrong page, mask, scale or expert) moves most tokens of the requests it
    touches, and so their mean."""
    got = token_gaps(conf, params, seqs, control=control)
    return {k: [np.full(g.shape, g.mean()) for g in v]
            for k, v in got.items()}


def router_margins(conf: dict, params, tokens) -> np.ndarray:
    """Per position of one sequence, the least routing margin over the MoE
    layers (see ``_experts``): where it is within rounding, a program in
    lower precision may route that token to another expert."""
    toks = np.asarray(tokens, np.int32)
    _, margin = _hidden(params, jnp.asarray(_pad(toks, SEQ_BUCKET)),
                        m=_dims(conf), quant=False)
    return np.asarray(margin[: len(toks)])


def full_logits(conf: dict, params, tokens, *, quant: bool = False):
    """Logits (n, V) at every position of one sequence; for small sizes
    (the tests), where the whole (n, V) array fits."""
    toks = np.asarray(tokens, np.int32)
    h = _hidden(params, jnp.asarray(_pad(toks, SEQ_BUCKET)), m=_dims(conf),
                quant=quant)[0][: len(toks)]
    w = params["unembed"].astype(jnp.float32)
    if quant:
        return np.asarray(_mm(h, w, True))
    return np.asarray(jnp.matmul(h, w, precision=HI))
