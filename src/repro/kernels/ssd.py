"""Mamba2 SSD Pallas kernel (chunked matmul / state-space-duality form).

The MXU-native formulation: within a chunk of ``c`` tokens the output is a
masked (c × c) matmul (``C_i·B_j`` Gram matrix × decay mask), and chunks
are stitched by a (P × N) carried state per head — so the heavy ops are
all dots on MXU-aligned tiles, not elementwise recurrences. Grid
``(batch, heads, seq_chunks)``; the ``(P, N)`` state carries in VMEM
scratch across the sequential chunk dim.

Per chunk and head:
  y_intra[i] = Σ_{j≤i} exp(l_i - l_j)·(C_i·B_j)·dt_j·x_j      (c×c dot)
  y_inter[i] = exp(l_i) · C_i · h                              (c×N dot)
  h' = exp(l_last)·h + Σ_j exp(l_last - l_j)·dt_j·B_j ⊗ x_j    (N×c · c×P)

with l = cumsum(dt·A) the per-head log-decay within the chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    A_ref,    # scalar prefetch (Hs,) f32
    D_ref,    # scalar prefetch (Hs,) f32
    x_ref,    # (1, 1, c, P)
    dtc_ref,  # (1, 1, c, 1) f32 — dt as a column
    dtr_ref,  # (1, 1, 1, c) f32 — the same dt as a row
    B_ref,    # (1, c, N)
    C_ref,    # (1, c, N)
    h0_ref,   # (1, 1, P, N)
    y_ref,    # (1, 1, c, P) out
    hT_ref,   # (1, 1, P, N) out
    h_ref,    # scratch (P, N)
    *,
    chunk: int,
):
    head = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (c, P)
    a = A_ref[head]
    da_col = dtc_ref[0, 0] * a                   # (c, 1)
    dt_row = dtr_ref[0, 0]                       # (1, c)
    Bm = B_ref[0].astype(jnp.float32)            # (c, N)
    C = C_ref[0].astype(jnp.float32)             # (c, N)

    # inclusive cumsum l = cumsum(dt·a), as a column and as a row, by
    # masked reductions (VPU/XLU work, no scan primitive in the kernel)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ii >= jj
    l_col = jnp.sum(jnp.where(causal, dt_row * a, 0.0), axis=1,
                    keepdims=True)                             # (c, 1)
    l_row = jnp.sum(jnp.where(ii <= jj, da_col, 0.0), axis=0,
                    keepdims=True)                             # (1, c)
    # intra-chunk: masked decay Gram matmul
    g = jax.lax.dot_general(C, Bm, (((1,), (1,)), ((), ())))   # (c, c)
    decay = jnp.where(causal, jnp.exp(l_col - l_row), 0.0)
    m = g * decay * dt_row                                     # (c, c)
    y_intra = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())))  # (c, P)
    # inter-chunk: carried state contribution
    h = h_ref[...]
    y_inter = jnp.exp(l_col) * jax.lax.dot_general(
        C, h, (((1,), (1,)), ((), ()))
    )                                                          # (c, P)
    y = y_intra + y_inter + D_ref[head] * x
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # next state: h' = exp(l_last) h + Σ_j w_j x_j ⊗ B_j,  w_j = exp(l_last-l_j) dt_j
    l_last = l_col[chunk - 1:, :]                              # (1, 1)
    w = jnp.exp(l_last - l_col) * dtc_ref[0, 0]                # (c, 1)
    s = jax.lax.dot_general(
        x * w, Bm, (((0,), (0,)), ((), ()))
    )                                                          # (P, N)
    h_ref[...] = jnp.exp(l_last) * h + s

    @pl.when(ci == nc - 1)
    def _finish():
        hT_ref[0, 0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
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
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, S, Hs, P = x.shape
    N = Bm.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((B, Hs, P, N), jnp.float32)

    c = min(chunk, S)
    ps = (-S) % c
    if ps:
        x = jnp.pad(x, ((0, 0), (0, ps), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, ps), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, ps), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, ps), (0, 0)))
    Sp = S + ps
    ncs = Sp // c

    # head-major layout: every block's trailing two dims are whole
    # (c, P) / (c, 1) / (1, c) tiles, as the TPU's (8, 128) tiling needs
    xt = jnp.swapaxes(x, 1, 2)                       # (B, Hs, Sp, P)
    dtt = jnp.swapaxes(dt, 1, 2).astype(jnp.float32)  # (B, Hs, Sp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hs, ncs),
        in_specs=[
            pl.BlockSpec((1, 1, c, P), lambda b, h, ci, *_: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, c, 1), lambda b, h, ci, *_: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, 1, c), lambda b, h, ci, *_: (b, h, 0, ci)),
            pl.BlockSpec((1, c, N), lambda b, h, ci, *_: (b, ci, 0)),
            pl.BlockSpec((1, c, N), lambda b, h, ci, *_: (b, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ci, *_: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, P), lambda b, h, ci, *_: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ci, *_: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    y, hT = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=c),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hs, Sp, P), x.dtype),
            jax.ShapeDtypeStruct((B, Hs, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(A.astype(jnp.float32), D.astype(jnp.float32), xt, dtt[..., None],
      dtt[:, :, None, :], Bm, C, h0)
    return jnp.swapaxes(y, 1, 2)[:, :S], hT
