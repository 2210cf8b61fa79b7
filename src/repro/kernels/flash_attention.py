"""Causal GQA flash attention (training/prefill) as a Pallas TPU kernel.

Tiling: grid ``(batch, q_heads, num_q_blocks, num_k_blocks)``. The last
grid dim iterates sequentially on TPU, so the online-softmax statistics
``(m, l)`` and the output accumulator live in VMEM scratch and carry
across k-blocks; the final k-block writes the normalized tile. GQA is
expressed in the k/v index maps (query head ``h`` reads kv head
``h // q_per_kv``) — no materialized head expansion, which is the memory
win over the XLA fallback.

Causal blocks that are entirely masked are skipped with ``pl.when``
(their flops never execute — the kernel does ~half the work of the dense
score matrix). V's head size may differ from Q's and K's (latent attention
expands 192-wide keys beside 128-wide values). Block shapes default to
512×512 tiles of ``(seq, head_dim)``
— MXU-aligned (128 multiples) and ≤ ~4 MiB of VMEM at f32 for d ≤ 256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,    # (1, 1, bq, d)
    k_ref,    # (1, 1, bk, d)
    v_ref,    # (1, 1, bk, dv)
    o_ref,    # (1, 1, bq, dv)
    m_ref,    # scratch (bq,)
    l_ref,    # scratch (bq,)
    acc_ref,  # scratch (bq, dv)
    *,
    causal: bool,
    q_offset: int,
    sk: int,
    block_q: int,
    block_k: int,
    scale: float,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = qi * block_q + jax.lax.iota(jnp.int32, block_q) + q_offset
    kpos = ki * block_k + jax.lax.iota(jnp.int32, block_k)

    # skip fully-masked blocks (strictly above the causal diagonal)
    run = jnp.logical_or(
        not causal, ki * block_k <= qi * block_q + block_q - 1 + q_offset
    )

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))
        )  # (bq, bk)
        valid = kpos[None, :] < sk
        if causal:
            valid = jnp.logical_and(valid, kpos[None, :] <= qpos[:, None])
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ()))
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, K, D)
    v: jax.Array,  # (B, Sk, K, Dv)
    *,
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, D = q.shape
    Sk, K, Dv = k.shape[1], k.shape[2], v.shape[-1]
    assert H % K == 0, (H, K)
    q_per_kv = H // K
    scale = D ** -0.5

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pq = (-Sq) % bq
    pk = (-Sk) % bk

    # (B, S, H, D) -> (B, H, S, D) tiles
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = (Sq + pq) // bq
    nk = (Sk + pk) // bk

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        q_offset=q_offset,
        sk=Sk,
        block_q=bq,
        block_k=bk,
        scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, bk, D),
                lambda b, h, qi, ki: (b, h // q_per_kv, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, bk, Dv),
                lambda b, h, qi, ki: (b, h // q_per_kv, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = jnp.swapaxes(out, 1, 2)  # (B, Sq+pq, H, D)
    if pq:
        out = out[:, :Sq]
    return out
