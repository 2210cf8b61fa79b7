"""Paged flash-decode Pallas kernel: one query token vs a paged KV cache.

Same bandwidth-bound problem as ``decode_attention`` but the cache lives in
a shared page pool: ``k_pages/v_pages (n_pages, page, K, D)`` hold fixed-size
pages owned by many sequences, and ``page_table (B, max_pages)`` maps each
sequence's logical page index to a physical page id.

Tiling: a ragged walk over multi-page blocks. The grid is ``(B,)``; the
pools stay wherever XLA placed them (``pl.ANY``) and the lengths and the
page table arrive by *scalar prefetch*
(:class:`pltpu.PrefetchScalarGridSpec`). Lane ``b`` loops over
``cdiv(lengths[b], block)`` blocks of ``pages_per_block`` pages, so no grid
step or block is spent past a lane's length, and a lane of length 0 does no
block and writes zeros. A block's pages are copied by one DMA per page into
a double-buffered VMEM block, and pages past the lane's last one are not
copied; the next block's copy (or, in a lane's last block, the next
non-empty lane's first) runs while the current block computes. The block
is computed a chunk of ``_CHUNK_PAGES`` pages at a time, over the chunks
that hold the lane's tokens only.

A page's rows are (token, kv head) pairs, so a chunk is a plain
``(tokens * K, D)`` matrix: one score matmul of all H query heads against
it, with the pairs of another kv head masked out (GQA without head
expansion in HBM), and one value matmul. q, K and V enter the MXU in their
stored dtype with f32 accumulation; the scale (``D**-0.5`` unless given)
applies to the f32 scores; the online-softmax statistics and the accumulator are f32 and carry
in VMEM across the lane's blocks; the probabilities stay f32 for the value
matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# a block spans about this many tokens, and its double-buffered K and V
# copies stay inside this much VMEM
_BLOCK_TOKENS = 512
_BLOCK_VMEM_BYTES = 8 * 2**20
# pages per compute chunk: a block computes only the chunks that hold the
# lane's tokens
_CHUNK_PAGES = 2


def pages_per_block(page_size: int, max_pages: int, k_heads: int,
                    head_dim: int, itemsize: int) -> int:
    """Pages per block: about ``_BLOCK_TOKENS`` tokens, no more pages than
    a lane's table holds, a whole number of chunks, and two buffers each of
    K and V within ``_BLOCK_VMEM_BYTES``."""
    page_bytes = page_size * k_heads * head_dim * itemsize
    fit = _BLOCK_VMEM_BYTES // (4 * page_bytes)
    ppb = min(pl.cdiv(_BLOCK_TOKENS, page_size), fit,
              pl.cdiv(max_pages, _CHUNK_PAGES) * _CHUNK_PAGES)
    return max(_CHUNK_PAGES, ppb // _CHUNK_PAGES * _CHUNK_PAGES)


def _paged_decode_kernel(
    len_ref,   # scalar prefetch (B,) int32
    tab_ref,   # scalar prefetch (B * max_pages,) int32
    q_ref,     # (1, H, D)
    bias_ref,  # (H, chunk * K): 0 where a query head meets its own kv
               #   head, NEG_INF elsewhere
    k_hbm,     # (n_pages, P * K, D), any memory: a page's rows are
               #   (token, kv head)
    v_hbm,     # (n_pages, P * K, D), any memory
    o_ref,     # (1, H, D)
    k_buf,     # scratch (2, ppb * P * K, D): double-buffered block
    v_buf,     # scratch (2, ppb * P * K, D)
    sems,      # scratch DMA semaphores (2, 2): [K or V, buffer]
    nxt,       # scratch SMEM (2,): buffer of this lane's first block, and
               #   whether an earlier lane already started its copy
    m_ref,     # scratch (H, 1)
    l_ref,     # scratch (H, 1)
    acc_ref,   # scratch (H, D)
    *,
    page_size: int,
    k_heads: int,
    max_pages: int,
    scale: float,
):
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    P, K = page_size, k_heads
    rows = P * K                       # buffer rows per page
    ppb = k_buf.shape[1] // rows
    block = ppb * P                    # tokens per block
    chunk = _CHUNK_PAGES * P           # tokens per chunk

    @pl.when(b == 0)
    def _first_lane():
        nxt[0] = 0
        nxt[1] = 0
        # a chunk's pages past the lane's last keep what the buffer held:
        # make that finite, since p = 0 times a NaN is still NaN
        v_buf[...] = jnp.zeros_like(v_buf)

    def lane_pages(lane):
        return jnp.minimum(pl.cdiv(len_ref[lane], P), max_pages)

    def page_copies(lane, blk, slot):
        """(valid, K copy, V copy) for each page of the block."""
        first = blk * ppb
        n_valid = lane_pages(lane) - first
        for j in range(ppb):
            valid = j < n_valid
            entry = lane * max_pages + jnp.minimum(first + j, max_pages - 1)
            page = jnp.where(valid, tab_ref[entry], 0)
            yield valid, *(
                pltpu.make_async_copy(
                    hbm.at[page], buf.at[slot, pl.ds(j * rows, rows)],
                    sems.at[kv, slot])
                for kv, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf))))

    def start(lane, blk, slot):
        for valid, kc, vc in page_copies(lane, blk, slot):
            @pl.when(valid)
            def _():
                kc.start()
                vc.start()

    def wait(lane, blk, slot):
        for valid, kc, vc in page_copies(lane, blk, slot):
            @pl.when(valid)
            def _():
                kc.wait()
                vc.wait()

    length = len_ref[b]
    n_blocks = pl.cdiv(length, block)
    slot0 = nxt[0]
    # the next lane with work, or n_lanes: its first block is copied
    # while this lane's last block computes
    nxt_lane = jax.lax.fori_loop(
        b + 1, n_lanes,
        lambda c, n: jnp.where((n == n_lanes) & (len_ref[c] > 0), c, n),
        n_lanes)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((n_blocks > 0) & (nxt[1] == 0))
    def _start_first():
        start(b, 0, slot0)

    q = q_ref[0]                                           # (H, D)

    def compute(slot, i, c):
        """Online-softmax update over chunk c of the lane's block i."""
        t0 = i * block + c * chunk                         # its first token
        r0 = pl.multiple_of(c * chunk * K, chunk * K)
        k = k_buf[slot, pl.ds(r0, chunk * K)]
        v = v_buf[slot, pl.ds(r0, chunk * K)]
        # s[h, t * K + k] = q[h, :] . k[t, k, :], kept where k is h's head
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias_ref[...]                          # (H, chunk * K)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < (length - t0) * K, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    def body(i, _):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch_block():
            start(b, i + 1, 1 - slot)

        @pl.when((i + 1 == n_blocks) & (nxt_lane < n_lanes))
        def _prefetch_lane():
            start(nxt_lane, 0, 1 - slot)

        wait(b, i, slot)
        n_chunks = pl.cdiv(jnp.minimum(length - i * block, block), chunk)
        jax.lax.fori_loop(0, n_chunks, lambda c, _: compute(slot, i, c), None)

    jax.lax.fori_loop(0, n_blocks, body, None)

    @pl.when(n_blocks > 0)
    def _hand_over():
        nxt[0] = (slot0 + n_blocks) % 2
        nxt[1] = (nxt_lane < n_lanes).astype(jnp.int32)

    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def paged_decode_attention(
    q: jax.Array,           # (B, H, D)
    k_pages: jax.Array,     # (n_pages, P, K, D)
    v_pages: jax.Array,     # (n_pages, P, K, D)
    page_table: jax.Array,  # (B, max_pages) int32 — physical page ids
    lengths: jax.Array,     # (B,) int32 — valid tokens per sequence
    *,
    interpret: bool = False,
    scale: float | None = None,  # of the scores; None: D**-0.5
) -> jax.Array:
    B, H, D = q.shape
    n_pages, P, K, _ = k_pages.shape
    assert H % K == 0, (H, K)
    max_pages = page_table.shape[1]
    ppb = pages_per_block(P, max_pages, K, D, k_pages.dtype.itemsize)
    rows = ppb * P * K
    chunk_rows = _CHUNK_PAGES * P * K
    head = jnp.arange(H) // (H // K)
    bias = jnp.where(jnp.arange(chunk_rows)[None, :] % K == head[:, None],
                     0.0, NEG_INF).astype(jnp.float32)

    kernel = functools.partial(_paged_decode_kernel, page_size=P, k_heads=K,
                               max_pages=max_pages,
                               scale=D ** -0.5 if scale is None else scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, lens, tab: (b, 0, 0)),
            pl.BlockSpec((H, chunk_rows), lambda b, lens, tab: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, lens, tab: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, D), k_pages.dtype),
            pltpu.VMEM((2, rows, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        # lanes run in order: a lane's last block starts the next lane's copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        page_table.astype(jnp.int32).reshape(-1),
        q,
        bias,
        k_pages.reshape(n_pages, P * K, D),
        v_pages.reshape(n_pages, P * K, D),
    )
