"""Grouped matmul over the experts a chip holds (dropless MoE), as a Pallas
TPU kernel.

``lhs (M, K)`` holds the routed rows sorted by expert: rows
``[offsets[g], offsets[g + 1])`` go to expert ``g``, and rows past the last
group belong to no expert held here. ``rhs (G, K, N)`` holds each expert's
weight, or ``rhs (L, G, K, N)`` every layer's, of which ``layer`` is used:
a layer scan hands the kernel its whole stack and the layer's index, so
the layer's experts are never copied out of the stack before the kernel
reads them. ``moe_gmm`` gives ``lhs[rows of g] @ rhs[g]``;
``moe_gmm_swiglu`` takes two weights and gives ``silu(x @ wg) * (x @ wu)``
from one pass over the rows, the expert MLP's first half.

Tiling follows megablox (``jax.experimental.pallas.ops.tpu.megablox``):
the grid is ``(n tiles, visits, k tiles)``, where a visit is one
``(m tile, expert)`` pair whose rows meet, listed by scalar prefetch. An
empty expert gets no visit, so its weight is never read; an m tile that
two experts share is visited once for each, consecutively, and each visit
stores only its own rows of the output tile. Output rows that no held
expert owns are left unwritten: callers mask them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

# contraction rows a step takes, and the bytes of one weight block
_TILE_K = 512
_BLOCK_BYTES = 3 * 2**19


def _tile(n: int, cap: int) -> int:
    """The largest multiple of 128 dividing ``n`` and at most ``cap``; the
    whole of ``n`` where none does (a block may span a full dim)."""
    for t in range(min(cap, n) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def weight_tiles(K: int, N: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn) of a weight block: ``_TILE_K`` contraction rows where they
    divide ``K`` in tiles of 256 or more, else all of ``K``; then the
    widest ``tn`` that keeps the block within ``_BLOCK_BYTES``."""
    tk = _tile(K, _TILE_K)
    if tk < 256:
        tk = K
    return tk, _tile(N, max(128, _BLOCK_BYTES // (tk * itemsize)))


def row_tile(m: int) -> int:
    """Rows per m tile for ``m`` routed rows; callers pad ``m`` to it."""
    return 128 if m >= 128 else -(-m // 16) * 16


def _kernel(offsets, group_ids, m_tile_ids, layer, lhs_ref, *refs, n_rhs,
            k_tiles, tm, tn, swiglu):
    rhs_refs, out_ref, accs = refs[:n_rhs], refs[n_rhs], refs[n_rhs + 1:]
    visit, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    x = lhs_ref[...]
    for acc, w in zip(accs, rhs_refs):
        acc[...] += jnp.dot(x, w[...], preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _store():
        g = group_ids[visit]
        rows = (jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
                + m_tile_ids[visit] * tm)
        mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
        if swiglu:
            gate = accs[0][...]
            val = gate * jax.nn.sigmoid(gate) * accs[1][...]
        else:
            val = accs[0][...]
        out_ref[...] = jnp.where(mine, val, out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def _gmm(lhs, rhss, group_sizes, layer, *, swiglu, interpret):
    M, K = lhs.shape
    if rhss[0].ndim == 3:
        rhss, layer = tuple(w[None] for w in rhss), 0
    G, _, N = rhss[0].shape[1:]
    tm = row_tile(M)
    assert M % tm == 0, (M, tm)
    tk, tn = weight_tiles(K, N, rhss[0].dtype.itemsize)
    k_tiles, n_tiles = K // tk, N // tn
    meta, visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=M, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=G,
        visit_empty_groups=False)

    def lhs_map(n, v, k, offsets, gids, mids, layer):
        return mids[v], k

    def rhs_map(n, v, k, offsets, gids, mids, layer):
        return layer[0], gids[v], k, n

    def out_map(n, v, k, offsets, gids, mids, layer):
        return mids[v], n

    kernel = functools.partial(_kernel, n_rhs=len(rhss), k_tiles=k_tiles,
                               tm=tm, tn=tn, swiglu=swiglu)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles, visits, k_tiles),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map)]
            + [pl.BlockSpec((None, None, tk, tn), rhs_map)] * len(rhss),
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * len(rhss),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*meta, jnp.reshape(layer, (1,)).astype(jnp.int32), lhs, *rhss)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(lhs, rhs, group_sizes, layer=None, *, interpret: bool = False):
    """``lhs (M, K)`` rows grouped by ``group_sizes (G,)`` times ``rhs
    (G, K, N)`` (or ``rhs[layer]`` of an ``(L, G, K, N)`` stack), per
    group, in ``lhs``'s dtype with f32 accumulation."""
    return _gmm(lhs, (rhs,), group_sizes, layer, swiglu=False,
                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm_swiglu(lhs, wg, wu, group_sizes, layer=None, *,
                   interpret: bool = False):
    """``silu(lhs @ wg[g]) * (lhs @ wu[g])`` per group ``g``: the gate and
    up projections of the held experts in one pass over the rows (weights
    stacked by layer as in :func:`moe_gmm`)."""
    return _gmm(lhs, (wg, wu), group_sizes, layer, swiglu=True,
                interpret=interpret)
