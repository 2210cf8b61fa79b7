"""Flash-decode Pallas kernel: one query token vs a long KV cache.

Decode attention is bandwidth-bound (the whole cache is streamed once per
token), so the kernel's job is to consume the cache in VMEM-sized chunks
with online-softmax statistics and never materialize the (H, S) score
matrix. Tiling: grid ``(batch, num_k_blocks)``; all ``H`` query heads of
one sequence ride in a single ``(H, D)`` tile (tiny), each k-block streams
a ``(bk, K, D)`` cache tile, and per-head statistics carry in VMEM scratch
across k-blocks. GQA is computed by reshaping H into (K, G) groups inside
the kernel — again no head expansion in HBM.

Per-sequence valid ``lengths`` (scalar-prefetched into SMEM) mask the
cache tail; blocks entirely past ``lengths[b]`` are skipped with
``pl.when`` (a decode over a 32k cache at length 1k does 1/32 of the block
iterations' work).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,   # scalar prefetch (B,) int32
    q_ref,     # (1, H, D)
    k_ref,     # (1, bk, K, D)
    v_ref,     # (1, bk, K, D)
    o_ref,     # (1, H, D)
    m_ref,     # scratch (H,)
    l_ref,     # scratch (H,)
    acc_ref,   # scratch (H, D)
    *,
    block_k: int,
    scale: float,
):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    length = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * block_k < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (H, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, K, D)
        v = v_ref[0].astype(jnp.float32)
        H, D = q.shape
        bk, K, _ = k.shape
        G = H // K
        qg = q.reshape(K, G, D)
        # s[k, g, s] = qg[k,g,:] · k[s,k,:]
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,)))
        )                                                  # (K, G, bk)
        kpos = ki * block_k + jax.lax.iota(jnp.int32, bk)
        valid = kpos < length
        s = jnp.where(valid[None, None, :], s, NEG_INF)
        s = s.reshape(H, bk)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])                    # (H, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        pg = p.reshape(K, G, bk)
        # o[k, g, d] = Σ_s pg[k,g,s] v[s,k,d]
        og = jax.lax.dot_general(
            pg, v, (((2,), (0,)), ((0,), (1,)))
        )                                                  # (K, G, D)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + og.reshape(H, D)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jax.Array,        # (B, H, D)
    k: jax.Array,        # (B, S, K, D)
    v: jax.Array,        # (B, S, K, D)
    lengths: jax.Array,  # (B,) int32
    *,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    # block_k 128: at 256 the f32 working copies of an 8-kv-head tile
    # overflow v5e's 16 MiB scoped VMEM (phi4-mini widths)
    B, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    scale = D ** -0.5

    bk = min(block_k, S)
    pk = (-S) % bk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nk = (S + pk) // bk

    kernel = functools.partial(_decode_kernel, block_k=bk, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, ki, lens: (b, 0, 0)),
            pl.BlockSpec((1, bk, K, D), lambda b, ki, lens: (b, ki, 0, 0)),
            pl.BlockSpec((1, bk, K, D), lambda b, ki, lens: (b, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, ki, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
