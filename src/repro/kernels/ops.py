"""Jit'd kernel wrappers with backend dispatch.

Three backends per op:

- ``xla``              — memory-efficient pure-XLA implementation (default;
  used by the multi-pod dry-run so ``cost_analysis`` sees real FLOPs).
- ``pallas``           — the TPU Pallas kernel (target hardware).
- ``pallas_interpret`` — the Pallas kernel executed with ``interpret=True``
  (CPU correctness validation).

The XLA implementations are *algorithmically identical* to the Pallas kernels
(online-softmax flash blocks, chunked scans) so the roofline derived from the
dry-run reflects the kernelized execution. ``ref.py`` holds the simple oracles
both are tested against.

The process-wide default backend is resolved on first use, never at
import: ``REPRO_KERNEL_BACKEND`` when set (how CI runs the whole test suite
once per backend without touching test code), else ``pallas`` when JAX's
default backend is a TPU and ``xla`` elsewhere. ``use_backend`` overrides
it per scope. The scope is part of every ``jax.jit`` cache key, so a
function traced under one backend is retraced, not reused, under another.
On a TPU no op runs in interpret mode.
"""

from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.parallel import partition, tracing

_BACKENDS = ("xla", "pallas", "pallas_interpret")

# None = the process default; any other value is a use_backend() scope
_SCOPE = jax.make_user_context(default_value=None)

NEG_INF = -1e30


@functools.cache
def default_backend() -> str:
    name = os.environ.get("REPRO_KERNEL_BACKEND")
    if name is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if name not in _BACKENDS:
        raise ValueError(
            f"REPRO_KERNEL_BACKEND={name!r}: expected one of {_BACKENDS}"
        )
    return name


def current_backend() -> str:
    name = _SCOPE.value or default_backend()
    if name == "pallas_interpret" and jax.default_backend() == "tpu":
        raise RuntimeError(
            "kernel backend 'pallas_interpret' runs Pallas kernels in "
            "interpret mode, which is for CPU validation only; use 'pallas' "
            "on a TPU"
        )
    return name


@contextlib.contextmanager
def use_backend(name: str):
    """Context manager selecting the kernel backend ("xla", "pallas", "pallas_interpret")."""
    assert name in _BACKENDS, name
    with _SCOPE(name):
        yield


def _pallas(name: str):
    """Lazily import a Pallas kernel module."""
    import importlib

    return importlib.import_module(f"repro.kernels.{name}")


def _kernel_axes(batch: int, *heads: int):
    """Axes of the installed activation mesh that a kernel's batch dim and
    head dims split over (None keeps the dim whole; both None without a
    mesh): batch over the data axes, heads over ``model`` only when every
    head count divides it, so each shard keeps whole GQA groups."""
    mesh = partition.current_mesh()
    if mesh is None:
        return None, None
    data = partition.data_axes(mesh)
    b = data if data and batch % partition.axis_size(mesh, data) == 0 else None
    m = ("model" if "model" in mesh.axis_names
         and all(h % mesh.shape["model"] == 0 for h in heads) else None)
    return b, m


def _per_shard(kernel, args, in_specs, out_specs):
    """Call a Pallas kernel once per shard of the installed activation mesh
    (:func:`repro.parallel.partition.activation_sharding`): XLA cannot
    partition a Mosaic kernel by itself. Without a mesh, one plain call."""
    mesh = partition.current_mesh()
    if mesh is None:
        return kernel(*args)
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    b = current_backend()
    if b == "xla":
        return ref.rmsnorm(x, w, eps)
    mod = _pallas("rmsnorm")
    kernel = functools.partial(mod.rmsnorm, eps=eps,
                               interpret=(b == "pallas_interpret"))
    spec = P(_kernel_axes(x.shape[0])[0], *(None,) * (x.ndim - 1))
    return _per_shard(kernel, (x, w), (spec, P()), spec)


# ---------------------------------------------------------------------------
# Flash attention (training / prefill)
# ---------------------------------------------------------------------------


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, K, D)
    v: jax.Array,  # (B, Sk, K, Dv)
    *,
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
) -> jax.Array:
    """Blocked causal (or full) GQA attention -> (B, Sq, H, Dv), scores
    scaled by ``D**-0.5``."""
    b = current_backend()
    if b == "xla":
        return _flash_attention_xla(
            q, k, v, causal=causal, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
        )
    mod = _pallas("flash_attention")
    kernel = functools.partial(
        mod.flash_attention, causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        interpret=(b == "pallas_interpret"),
    )
    bt, m = _kernel_axes(q.shape[0], q.shape[2], k.shape[2])
    spec = P(bt, None, m, None)
    return _per_shard(kernel, (q, k, v), (spec, spec, spec), spec)


def _flash_attention_xla(q, k, v, *, causal, q_offset, block_q, block_k):
    """Blocked online-softmax attention in pure XLA.

    vmapped over query blocks, lax.scan over key/value blocks; f32 softmax
    statistics; memory per device is O(block_q * block_k) per (batch, head).
    v's head size may differ from q's and k's.
    """
    B, Sq, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scale = D ** -0.5

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    # pad sequence dims to block multiples (padded keys masked out)
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq = (Sq + pq) // bq
    nk = (Sk + pk) // bk

    qb = q.reshape(B, nq, bq, H, D).transpose(1, 0, 2, 3, 4)  # (nq,B,bq,H,D)

    def per_q_block(qi, qblk):
        qf = qblk.astype(jnp.float32) * scale

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk = jax.lax.dynamic_slice_in_dim(k, ki * bk, bk, 1)
            vblk = jax.lax.dynamic_slice_in_dim(v, ki * bk, bk, 1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, kblk.astype(jnp.float32))
            kpos = ki * bk + jnp.arange(bk)
            valid = kpos < Sk
            if causal:
                qpos = qi * bq + jnp.arange(bq) + q_offset
                valid = valid[None, :] & (kpos[None, :] <= qpos[:, None])
                s = jnp.where(valid[None, None], s, NEG_INF)
            else:
                s = jnp.where(valid[None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, H, bq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk),
                                      unroll=tracing.scan_unroll())
        out = acc / jnp.maximum(l, 1e-30)[..., None]         # (B,H,bq,D)
        return out.transpose(0, 2, 1, 3)                      # (B,bq,H,D)

    out = jax.vmap(per_q_block, in_axes=(0, 0), out_axes=0)(jnp.arange(nq), qb)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, Sq + pq, H, Dv)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (single token vs KV cache)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,        # (B, H, D)
    k: jax.Array,        # (B, S, K, D)
    v: jax.Array,        # (B, S, K, D)
    lengths: jax.Array,  # (B,) int32
    *,
    scale: float | None = None,   # of the scores; None: D**-0.5
) -> jax.Array:
    b = current_backend()
    if b == "xla":
        return _decode_attention_xla(q, k, v, lengths, scale=scale)
    mod = _pallas("decode_attention")
    kernel = functools.partial(mod.decode_attention,
                               interpret=(b == "pallas_interpret"),
                               scale=scale)
    bt, m = _kernel_axes(q.shape[0], q.shape[1], k.shape[2])
    kv = P(bt, None, m, None)
    return _per_shard(kernel, (q, k, v, lengths),
                      (P(bt, m, None), kv, kv, P(bt)), P(bt, m, None))


def _decode_attention_xla(q, k, v, lengths, *, scale=None):
    B, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, K, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32))
    mask = jnp.arange(S)[None, :] < lengths[:, None]          # (B,S)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode attention (single token vs paged KV cache)
# ---------------------------------------------------------------------------


def paged_decode_attention(
    q: jax.Array,           # (B, H, D)
    k_pages: jax.Array,     # (n_pages, P, K, D) — shared page pool
    v_pages: jax.Array,     # (n_pages, P, K, D)
    page_table: jax.Array,  # (B, max_pages) int32
    lengths: jax.Array,     # (B,) int32
    *,
    scale: float | None = None,   # of the scores; None: D**-0.5
) -> jax.Array:
    b = current_backend()
    if b == "xla":
        return _paged_decode_attention_xla(q, k_pages, v_pages, page_table,
                                           lengths, scale=scale)
    return _paged_decode_pallas(q, k_pages, v_pages, page_table, lengths,
                                interpret=(b == "pallas_interpret"),
                                scale=scale)


def _paged_decode_pallas(q, k_pages, v_pages, page_table, lengths, *,
                         interpret, scale=None):
    """The paged flash-decode kernel, split per shard under a mesh: lanes
    over the data axes, query heads and the pool's kv heads over
    ``model`` (page ids stay global, so the pool's page dim never
    splits)."""
    mod = _pallas("paged_decode_attention")
    kernel = functools.partial(mod.paged_decode_attention,
                               interpret=interpret, scale=scale)
    bt, m = _kernel_axes(q.shape[0], q.shape[1], k_pages.shape[2])
    pool = P(None, None, m, None)
    return _per_shard(kernel, (q, k_pages, v_pages, page_table, lengths),
                      (P(bt, m, None), pool, pool, P(bt, None), P(bt)),
                      P(bt, m, None))


def _paged_decode_attention_xla(q, k_pages, v_pages, page_table, lengths, *,
                                scale=None):
    """Pure-XLA paged decode: gather each sequence's pages through the same
    page table the Pallas kernel prefetches, then run the masked-softmax
    decode path. The gather is a transient — the resident cache stays paged."""
    B, H, D = q.shape
    K = k_pages.shape[2]
    k = k_pages[page_table].reshape(B, -1, K, D)
    v = v_pages[page_table].reshape(B, -1, K, D)
    return _decode_attention_xla(q, k, v, lengths, scale=scale)


# ---------------------------------------------------------------------------
# Paged verify attention (speculative-draft window vs paged KV cache)
# ---------------------------------------------------------------------------


def paged_verify_attention(
    q: jax.Array,           # (B, W, H, D) — W verify positions per sequence
    k_pages: jax.Array,     # (n_pages, P, K, D) — shared page pool
    v_pages: jax.Array,     # (n_pages, P, K, D)
    page_table: jax.Array,  # (B, max_pages) int32
    positions: jax.Array,   # (B,) int32 — cache position of query 0 per seq
) -> jax.Array:
    """Causal multi-query paged decode for speculative verification: query
    ``j`` of lane ``b`` attends over the first ``positions[b] + j + 1``
    cache entries. One call verifies a whole draft window instead of W
    sequential decode steps. Tested against
    :func:`repro.kernels.ref.paged_verify_attention`."""
    b = current_backend()
    if b == "xla":
        return _paged_verify_attention_xla(q, k_pages, v_pages, page_table,
                                           positions)
    # Pallas backends: fold the window into the batch dim and reuse the
    # paged flash-decode kernel — per-query causality is exactly a
    # per-lane length (positions[b] + j + 1), which is the kernel's
    # masking contract.
    B, W, H, D = q.shape
    lengths = (positions[:, None] + jnp.arange(W)[None, :] + 1).reshape(-1)
    out = _paged_decode_pallas(
        q.reshape(B * W, H, D), k_pages, v_pages,
        jnp.repeat(page_table, W, axis=0), lengths.astype(jnp.int32),
        interpret=(b == "pallas_interpret"),
    )
    return out.reshape(B, W, H, D)


def _paged_verify_attention_xla(q, k_pages, v_pages, page_table, positions):
    """Pure-XLA paged verify: gather the pages through the table, then one
    masked softmax with a per-query causal length. The gather is a
    transient — the resident cache stays paged."""
    B, W, H, D = q.shape
    K = k_pages.shape[2]
    k = _expand_kv(k_pages[page_table].reshape(B, -1, K, D), H)
    v = _expand_kv(v_pages[page_table].reshape(B, -1, K, D), H)
    S = k.shape[1]
    scale = D ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
        k.astype(jnp.float32),
    )
    kpos = jnp.arange(S)[None, None, :]
    qend = positions[:, None, None] + jnp.arange(W)[None, :, None] + 1
    mask = kpos < qend                                         # (B, W, S)
    s = jnp.where(mask[:, None], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged cross attention (query block vs paged encoder-output cache)
# ---------------------------------------------------------------------------


def paged_cross_attention(
    q: jax.Array,           # (B, C, H, D) — C query positions per sequence
    k_pages: jax.Array,     # (n_pages, P, K, D) — shared page pool
    v_pages: jax.Array,     # (n_pages, P, K, D)
    page_table: jax.Array,  # (B, max_pages) int32
    lengths: jax.Array,     # (B,) int32 — valid cross positions per sequence
) -> jax.Array:
    """Non-causal attention of a query block over a paged cross-attention
    (encoder-output) region: the enc-dec decode step (C = 1) and chunked
    prefill (C = chunk) both read the encoder pages through this one op.
    Tested against :func:`repro.kernels.ref.paged_cross_attention`."""
    b = current_backend()
    if b == "xla":
        return _paged_cross_attention_xla(q, k_pages, v_pages, page_table,
                                          lengths)
    # Pallas backends: fold the query positions into the batch dim and
    # reuse the paged flash-decode kernel — "one query, length-masked,
    # non-causal over paged KV" is exactly its contract, and every folded
    # lane shares its sequence's page table and length.
    B, C, H, D = q.shape
    out = _paged_decode_pallas(
        q.reshape(B * C, H, D), k_pages, v_pages,
        jnp.repeat(page_table, C, axis=0), jnp.repeat(lengths, C, axis=0),
        interpret=(b == "pallas_interpret"),
    )
    return out.reshape(B, C, H, D)


def _paged_cross_attention_xla(q, k_pages, v_pages, page_table, lengths):
    """Pure-XLA paged cross attention: gather the pages through the table,
    then one masked non-causal softmax. The gather is a transient — the
    resident encoder cache stays paged."""
    B, C, H, D = q.shape
    K = k_pages.shape[2]
    k = _expand_kv(k_pages[page_table].reshape(B, -1, K, D), H)
    v = _expand_kv(v_pages[page_table].reshape(B, -1, K, D), H)
    S = k.shape[1]
    scale = D ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
        k.astype(jnp.float32),
    )
    mask = jnp.arange(S)[None, :] < lengths[:, None]          # (B, S)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Grouped matmul over held experts (dropless MoE)
# ---------------------------------------------------------------------------


def moe_experts(lhs: jax.Array, wg: jax.Array, wu: jax.Array,
                wd: jax.Array, group_sizes: jax.Array,
                layer: jax.Array | None = None) -> jax.Array:
    """Each row's expert MLP, ``(silu(x @ wg[g]) * (x @ wu[g])) @ wd[g]``,
    for the rows of ``lhs (M, d)`` sorted by group (``group_sizes (G,)``)
    -> (M, d) in ``lhs``'s dtype, f32 inside; rows past the last group are
    zero. The weights are ``(G, ., .)`` or layer stacks ``(L, G, ., .)``
    with ``layer`` their index, read in place.

    Under a mesh whose ``model`` axis divides G, the experts split over
    ``model`` as the weights are laid out: each shard runs its own groups'
    rows through its own experts, its other rows zero, and the shards'
    outputs are summed, so no shard gathers another's expert weights."""
    mesh = partition.current_mesh()
    G = wg.shape[-3]
    if (mesh is None or "model" not in mesh.axis_names
            or G % mesh.shape["model"]):
        return _experts(lhs, wg, wu, wd, group_sizes, layer)
    stacked = layer is not None

    def body(lhs, wg, wu, wd, sizes, layer):
        n = wg.shape[-3]
        first = jax.lax.axis_index("model") * n
        start = jnp.sum(jnp.where(jnp.arange(G) < first, sizes, 0))
        local = jax.lax.dynamic_slice_in_dim(sizes, first, n)
        out = _experts(jnp.roll(lhs, -start, 0), wg, wu, wd, local,
                       layer if stacked else None)
        return jax.lax.psum(jnp.roll(out, start, 0), "model")

    w = P(*(None,) * (wg.ndim - 3), "model", None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(P(), w, w, w, P(), P()),
                         out_specs=P(), check_vma=False)(
        lhs, wg, wu, wd, group_sizes, _layer_arg(layer))


def _experts(lhs, wg, wu, wd, group_sizes, layer):
    """:func:`moe_experts` on one device: the grouped matmul kernels
    (``moe_gmm_swiglu``, then ``moe_gmm``), or ``jax.lax.ragged_dot`` as
    their XLA twin."""
    if current_backend() == "xla":
        g, u = (_ragged_dot(lhs, _layer_of(w, layer), group_sizes)
                for w in (wg, wu))
        h = (g * jax.nn.sigmoid(g) * u).astype(lhs.dtype)
        return _ragged_dot(h, _layer_of(wd, layer), group_sizes
                           ).astype(lhs.dtype)
    mod = _pallas("moe_gmm")
    interpret = current_backend() == "pallas_interpret"
    h = mod.moe_gmm_swiglu(lhs, wg, wu, group_sizes, layer,
                           interpret=interpret)
    out = mod.moe_gmm(h, wd, group_sizes, layer, interpret=interpret)
    # the kernels leave rows of no group unwritten: select, never multiply
    rows = jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(rows[:, None], out, jnp.zeros((), out.dtype))


def _layer_of(w: jax.Array, layer) -> jax.Array:
    return w if layer is None else jax.lax.dynamic_index_in_dim(
        w, layer, 0, keepdims=False)


def _layer_arg(layer) -> jax.Array:
    return jnp.asarray(0 if layer is None else layer, jnp.int32)


def _ragged_dot(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Causal depthwise conv (Mamba front conv)
# ---------------------------------------------------------------------------


def causal_conv1d(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
                  state: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv along seq. x (B,S,C), w (W,C).

    ``state`` (B, W-1, C), if given, supplies left context (decode/chunking).
    """
    B, S, C = x.shape
    W = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = jax.lax.conv_general_dilated(
        xp.astype(jnp.float32),
        w.astype(jnp.float32)[:, None, :],           # (W, 1, C)
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=C,
    )
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Mamba1 selective scan (chunked)
# ---------------------------------------------------------------------------


def selective_scan(
    x: jax.Array,    # (B, S, Di)
    dt: jax.Array,   # (B, S, Di)
    A: jax.Array,    # (Di, N)
    Bm: jax.Array,   # (B, S, N)
    C: jax.Array,    # (B, S, N)
    D: jax.Array,    # (Di,)
    h0: jax.Array | None = None,
    *,
    chunk: int = 256,
    compute_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    b = current_backend()
    if b in ("pallas", "pallas_interpret"):
        mod = _pallas("selective_scan")
        kernel = functools.partial(mod.selective_scan, chunk=chunk,
                                   interpret=(b == "pallas_interpret"))
        if h0 is None:
            h0 = jnp.zeros((x.shape[0], *A.shape), jnp.float32)
        bt, m = _kernel_axes(x.shape[0], x.shape[2])
        chans = P(bt, None, m)
        seq = P(bt, None, None)
        state = P(bt, m, None)
        return _per_shard(
            kernel, (x, dt, A, Bm, C, D, h0),
            (chans, chans, P(m, None), seq, seq, P(m), state),
            (chans, state),
        )
    return _selective_scan_xla(x, dt, A, Bm, C, D, h0, chunk=chunk,
                               compute_dtype=compute_dtype)


def _selective_scan_xla(x, dt, A, Bm, C, D, h0, *, chunk,
                        compute_dtype=jnp.float32):
    """Chunked scan: lax.scan over chunks, associative scan within a chunk.

    Keeps the (B, c, Di, N) expanded state tensor to one chunk at a time —
    the same blocking as the Pallas kernel.
    """
    B, S, Di = x.shape
    N = A.shape[1]
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // c
    if h0 is None:
        h0 = jnp.zeros((B, Di, N), jnp.float32)

    Af = A.astype(jnp.float32)

    def to_chunks(t):
        return t.reshape(B, nc, c, *t.shape[2:]).swapaxes(0, 1)

    cd = compute_dtype
    xs = (to_chunks(x.astype(cd)), to_chunks(dt.astype(cd)),
          to_chunks(Bm.astype(cd)), to_chunks(C.astype(cd)))

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2

    def chunk_step(h, inp):
        xc, dtc, Bc, Cc = inp                                  # (B,c,·)
        # the big (B,c,Di,N) intermediates carry ``compute_dtype``; the
        # inter-chunk state stays f32 for stability
        dA = jnp.exp(dtc.astype(jnp.float32)[..., None]
                     * Af[None, None]).astype(cd)              # (B,c,Di,N)
        dBx = (dtc * xc)[..., None] * Bc[:, :, None, :]        # (B,c,Di,N)
        aa, bb = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
        hs = aa.astype(jnp.float32) * h[:, None] + bb.astype(jnp.float32)
        yc = jnp.einsum("bcdn,bcn->bcd", hs, Cc.astype(jnp.float32))
        return hs[:, -1], yc

    hT, ys = jax.lax.scan(chunk_step, h0.astype(jnp.float32), xs,
                          unroll=tracing.scan_unroll())
    y = ys.swapaxes(0, 1).reshape(B, Sp, Di)[:, :S]
    y = y + D.astype(jnp.float32)[None, None] * x.astype(jnp.float32)[:, :S]
    return y.astype(x.dtype), hT


def selective_scan_step(
    x: jax.Array,   # (B, Di) — one token
    dt: jax.Array,  # (B, Di)
    A: jax.Array,   # (Di, N)
    Bm: jax.Array,  # (B, N)
    C: jax.Array,   # (B, N)
    D: jax.Array,   # (Di,)
    h: jax.Array,   # (B, Di, N) f32
) -> tuple[jax.Array, jax.Array]:
    """Single decode step of the Mamba1 recurrence."""
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf[..., None] * A.astype(jnp.float32)[None])
    dBx = (dtf * xf)[..., None] * Bm.astype(jnp.float32)[:, None, :]
    h_new = dA * h + dBx
    y = jnp.einsum("bdn,bn->bd", h_new, C.astype(jnp.float32))
    y = y + D.astype(jnp.float32)[None] * xf
    return y.astype(x.dtype), h_new


# ---------------------------------------------------------------------------
# Mamba2 SSD (chunked matmul form)
# ---------------------------------------------------------------------------


def ssd(
    x: jax.Array,    # (B, S, Hs, P)
    dt: jax.Array,   # (B, S, Hs)
    A: jax.Array,    # (Hs,)
    Bm: jax.Array,   # (B, S, N)
    C: jax.Array,    # (B, S, N)
    D: jax.Array,    # (Hs,)
    h0: jax.Array | None = None,
    *,
    chunk: int = 256,
) -> tuple[jax.Array, jax.Array]:
    b = current_backend()
    if b in ("pallas", "pallas_interpret"):
        mod = _pallas("ssd")
        kernel = functools.partial(mod.ssd, chunk=chunk,
                                   interpret=(b == "pallas_interpret"))
        if h0 is None:
            h0 = jnp.zeros((*x.shape[:1], *x.shape[2:], Bm.shape[-1]),
                           jnp.float32)
        bt, m = _kernel_axes(x.shape[0], x.shape[2])
        seq = P(bt, None, None)
        state = P(bt, m, None, None)
        return _per_shard(
            kernel, (x, dt, A, Bm, C, D, h0),
            (P(bt, None, m, None), P(bt, None, m), P(m), seq, seq, P(m),
             state),
            (P(bt, None, m, None), state),
        )
    return _ssd_xla(x, dt, A, Bm, C, D, h0, chunk=chunk)


def _ssd_xla(x, dt, A, Bm, C, D, h0, *, chunk):
    """Chunked SSD: quadratic-within-chunk matmuls + inter-chunk recurrence.

    This is the TPU-native (MXU) adaptation of Mamba2: all heavy ops are
    einsums over (chunk × chunk) or (chunk × state) tiles.
    """
    B, S, Hs, P = x.shape
    N = Bm.shape[-1]
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // c
    if h0 is None:
        h0 = jnp.zeros((B, Hs, P, N), jnp.float32)
    Af = A.astype(jnp.float32)

    def to_chunks(t):
        return t.reshape(B, nc, c, *t.shape[2:]).swapaxes(0, 1)

    xs = (to_chunks(x.astype(jnp.float32)), to_chunks(dt.astype(jnp.float32)),
          to_chunks(Bm.astype(jnp.float32)), to_chunks(C.astype(jnp.float32)))

    def chunk_step(h, inp):
        xc, dtc, Bc, Cc = inp                     # (B,c,Hs,P) (B,c,Hs) (B,c,N)
        da = dtc * Af[None, None]                 # (B,c,Hs)  log-decay increments
        l = jnp.cumsum(da, axis=1)                # (B,c,Hs)  inclusive
        # intra-chunk: Y[i] += sum_{j<=i} exp(l_i - l_j) * (C_i·B_j) dt_j x_j
        g = jnp.einsum("bin,bjn->bij", Cc, Bc)    # (B,c,c) shared across heads
        ldiff = l[:, :, None, :] - l[:, None, :, :]          # (B,i,j,Hs)
        ii = jnp.arange(c)
        causal = (ii[:, None] >= ii[None, :])[None, :, :, None]
        decay = jnp.where(causal, jnp.exp(ldiff), 0.0)       # (B,i,j,Hs)
        m = g[..., None] * decay * dtc[:, None]              # (B,i,j,Hs)
        y_intra = jnp.einsum("bijh,bjhp->bihp", m, xc)
        # inter-chunk: contribution of carried state
        y_inter = jnp.einsum("bin,bhpn,bih->bihp", Cc, h, jnp.exp(l))
        # next carried state
        rev = jnp.exp(l[:, -1:, :] - l)                      # exp(l_last - l_j)
        s_chunk = jnp.einsum("bjh,bjn,bjhp->bhpn", rev * dtc, Bc, xc)
        h_new = jnp.exp(l[:, -1])[:, :, None, None] * h + s_chunk
        return h_new, y_intra + y_inter

    hT, ys = jax.lax.scan(chunk_step, h0.astype(jnp.float32), xs,
                          unroll=tracing.scan_unroll())
    y = ys.swapaxes(0, 1).reshape(B, Sp, Hs, P)[:, :S]
    y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)[:, :S]
    return y.astype(x.dtype), hT


def ssd_step(
    x: jax.Array,   # (B, Hs, P)
    dt: jax.Array,  # (B, Hs)
    A: jax.Array,   # (Hs,)
    Bm: jax.Array,  # (B, N)
    C: jax.Array,   # (B, N)
    D: jax.Array,   # (Hs,)
    h: jax.Array,   # (B, Hs, P, N) f32
) -> tuple[jax.Array, jax.Array]:
    """Single decode step of the Mamba2 recurrence."""
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    da = jnp.exp(dtf * A.astype(jnp.float32)[None])          # (B,Hs)
    dbx = jnp.einsum("bh,bhp,bn->bhpn", dtf, xf, Bm.astype(jnp.float32))
    h_new = da[..., None, None] * h + dbx
    y = jnp.einsum("bhpn,bn->bhp", h_new, C.astype(jnp.float32))
    y = y + D.astype(jnp.float32)[None, :, None] * xf
    return y.astype(x.dtype), h_new
