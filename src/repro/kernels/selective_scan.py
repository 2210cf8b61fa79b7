"""Mamba1 selective-scan Pallas kernel (chunked recurrence).

TPU adaptation: the recurrence ``h_t = dA_t·h_{t-1} + dB_t·x_t`` is
processed in VMEM-resident chunks — grid ``(batch, channel_blocks,
seq_chunks)``, where the sequence dim iterates sequentially and the
carried state lives in VMEM scratch across chunk steps. Inside a chunk
the recurrence steps token by token (``fori_loop``) over an ``(N, bc)``
state tile: the state dim on sublanes, 128 channels on lanes, so every
step is a few full-vreg VPU ops and HBM sees each input once. ``B`` and
``C`` arrive as ``(N, 1)`` columns per token, which broadcast across the
channel lanes without a transpose in the kernel. (An in-chunk
associative scan does not lower to Mosaic: "Invalid type" at 256 steps,
"interior padding" at 50.)

Layouts at the call boundary follow the XLA fallback in
``repro.kernels.ops`` so the two paths are drop-in interchangeable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(
    x_ref,    # (1, c, bc) f32
    dt_ref,   # (1, c, bc) f32
    At_ref,   # (N, bc) — A transposed
    B_ref,    # (1, c, N, 1) — one column per token
    C_ref,    # (1, c, N, 1)
    D_ref,    # (1, bc)
    h0_ref,   # (1, N, bc)
    y_ref,    # (1, c, bc) f32 out
    hT_ref,   # (1, N, bc) out (final state)
    h_ref,    # scratch (N, bc) — carried state
    *,
    chunk: int,
):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    At = At_ref[...].astype(jnp.float32)
    D = D_ref[...].astype(jnp.float32)

    def step(t, h):
        dt = dt_ref[0, pl.ds(t, 1), :]                      # (1, bc)
        x = x_ref[0, pl.ds(t, 1), :]
        h = (jnp.exp(At * dt) * h
             + B_ref[0, t].astype(jnp.float32) * (dt * x))  # (N, bc)
        y = jnp.sum(h * C_ref[0, t].astype(jnp.float32), axis=0,
                    keepdims=True)                          # (1, bc)
        y_ref[0, pl.ds(t, 1), :] = y + D * x
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == nc - 1)
    def _finish():
        hT_ref[0] = h


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_channels", "interpret")
)
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
    block_channels: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, S, Di = x.shape
    N = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((B, Di, N), jnp.float32)

    c = min(chunk, S)
    bc = min(block_channels, Di)
    ps = (-S) % c
    pc = (-Di) % bc
    f32 = jnp.float32
    xs, dts = x.astype(f32), dt.astype(f32)
    if ps:
        # padded timesteps: dt=0 -> dA=1, dBx=0 (identity transitions)
        xs = jnp.pad(xs, ((0, 0), (0, ps), (0, 0)))
        dts = jnp.pad(dts, ((0, 0), (0, ps), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, ps), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, ps), (0, 0)))
    if pc:
        xs = jnp.pad(xs, ((0, 0), (0, 0), (0, pc)))
        dts = jnp.pad(dts, ((0, 0), (0, 0), (0, pc)))
        A = jnp.pad(A, ((0, pc), (0, 0)))
        D = jnp.pad(D, ((0, pc),))
        h0 = jnp.pad(h0, ((0, 0), (0, pc), (0, 0)))
    Sp, Dp = S + ps, Di + pc
    ncs, ncb = Sp // c, Dp // bc

    y, hT = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=c),
        grid=(B, ncb, ncs),
        in_specs=[
            pl.BlockSpec((1, c, bc), lambda b, cb, ci: (b, ci, cb)),
            pl.BlockSpec((1, c, bc), lambda b, cb, ci: (b, ci, cb)),
            pl.BlockSpec((N, bc), lambda b, cb, ci: (0, cb)),
            pl.BlockSpec((1, c, N, 1), lambda b, cb, ci: (b, ci, 0, 0)),
            pl.BlockSpec((1, c, N, 1), lambda b, cb, ci: (b, ci, 0, 0)),
            pl.BlockSpec((1, bc), lambda b, cb, ci: (0, cb)),
            pl.BlockSpec((1, N, bc), lambda b, cb, ci: (b, 0, cb)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, bc), lambda b, cb, ci: (b, ci, cb)),
            pl.BlockSpec((1, N, bc), lambda b, cb, ci: (b, 0, cb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, Dp), f32),
            jax.ShapeDtypeStruct((B, N, Dp), f32),
        ],
        scratch_shapes=[pltpu.VMEM((N, bc), f32)],
        interpret=interpret,
    )(xs, dts, A.T, Bm[..., None], C[..., None], D.reshape(1, Dp),
      jnp.swapaxes(h0, 1, 2))
    return (y[:, :S, :Di].astype(x.dtype),
            jnp.swapaxes(hT, 1, 2)[:, :Di])
