"""Paged KV cache: pool invariants, kernel equivalence, engine parity.

- :class:`PagePool` alloc/free invariants (disjointness, exhaustion,
  accounting, snapshot restore);
- paged decode attention vs the ``ref.py`` oracle in both ``xla`` and
  ``pallas_interpret`` backends;
- the paged engine matching dense-engine outputs token-for-token where
  dense bucketing is exact, and matching an exact unpadded-prefill
  reference where it is not (chunked prefill is exact at any length);
- snapshot → restore round-trip mid-generation with paging enabled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REDUCED
from repro.kernels import ops, paged_decode_attention, ref
from repro.models import get_model
from repro.serving.engine import ServeEngine
from repro.serving.kvcache import PagePool, pages_needed

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# PagePool invariants
# ---------------------------------------------------------------------------


def test_pool_alloc_free_invariants():
    pool = PagePool(16)
    assert pool.available == 15  # page 0 reserved
    a = pool.alloc(5)
    b = pool.alloc(7)
    assert 0 not in a + b
    assert len(set(a) & set(b)) == 0
    assert pool.available == 3
    assert pool.outstanding == 12
    assert pool.alloc(4) is None          # exhausted: no side effects
    assert pool.available == 3
    pool.free(a)
    assert pool.available == 8
    c = pool.alloc(8)
    assert len(set(c) & set(b)) == 0      # b still owned
    pool.free(b)
    pool.free(c)
    assert pool.available == 15
    assert pool.outstanding == 0


def test_pool_double_free_rejected():
    pool = PagePool(4)
    pages = pool.alloc(2)
    pool.free(pages)
    with pytest.raises(AssertionError):
        pool.free(pages)


def test_pool_restore():
    pool = PagePool(8)
    pool.alloc(3)
    free = list(pool._free)
    other = PagePool(8)
    other.restore(free)
    assert other.available == pool.available
    assert other.outstanding == pool.outstanding


def test_pages_needed():
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2
    assert pages_needed(0, 16) == 1  # at least one page


# ---------------------------------------------------------------------------
# Kernel: paged decode attention vs oracle
# ---------------------------------------------------------------------------


def _paged_case(b, h, k, d, page, max_pages, n_pages, dtype, lens=None):
    q = jnp.asarray(RNG.standard_normal((b, h, d)), dtype)
    kp = jnp.asarray(RNG.standard_normal((n_pages, page, k, d)), dtype)
    vp = jnp.asarray(RNG.standard_normal((n_pages, page, k, d)), dtype)
    ids = RNG.permutation(np.arange(1, n_pages))[: b * max_pages]
    table = ids.reshape(b, max_pages)
    if lens is None:
        lens = RNG.integers(1, max_pages * page + 1, b)
    else:
        # as the engine leaves it: entries past a lane's chain hold page 0
        chain = -(-np.asarray(lens) // page)
        table = np.where(np.arange(max_pages) < chain[:, None], table, 0)
    return (q, kp, vp, jnp.asarray(table, jnp.int32),
            jnp.asarray(lens, jnp.int32))


def _block_tokens(page, max_pages, k, d, dtype):
    """Tokens per block of the Pallas kernel at these shapes."""
    return page * paged_decode_attention.pages_per_block(
        page, max_pages, k, d, jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize(
    "b,h,k,d,page,max_pages,n_pages,edges",
    [pytest.param(2, 4, 2, 16, 8, 4, 12, False, id="2-4-2-16-8-4-12"),
     pytest.param(3, 8, 8, 32, 16, 3, 16, False, id="3-8-8-32-16-3-16"),
     pytest.param(1, 16, 2, 64, 8, 5, 8, False, id="1-16-2-64-8-5-8"),
     # GQA groups of 3 and 4 over 64-token pages and tables of 10 pages,
     # not a whole number of blocks: lanes one below, at and one above a
     # block boundary, inside the first page, and the whole table
     pytest.param(6, 6, 2, 16, 64, 10, 64, True, id="gqa3-edges"),
     pytest.param(6, 8, 2, 16, 64, 10, 64, True, id="gqa4-edges")],
)
def test_paged_decode_attention(b, h, k, d, page, max_pages, n_pages, edges,
                                backend, dtype):
    lens = None
    if edges:
        block = _block_tokens(page, max_pages, k, d, dtype)
        assert max_pages * page > block and max_pages % (block // page)
        lens = [block - 1, block, block + 1, 1, page // 2,
                max_pages * page]
    q, kp, vp, table, lens = _paged_case(b, h, k, d, page, max_pages,
                                         n_pages, dtype, lens)
    want = ref.paged_decode_attention(q, kp, vp, table, lens)
    with ops.use_backend(backend):
        got = ops.paged_decode_attention(q, kp, vp, table, lens)
    tol = dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


def test_paged_decode_attention_zero_length_lane():
    q, kp, vp, table, lens = _paged_case(2, 4, 2, 16, 8, 4, 12, jnp.float32)
    lens = lens.at[0].set(0)  # inactive slot: output must be zeros, not NaN
    with ops.use_backend("pallas_interpret"):
        got = ops.paged_decode_attention(q, kp, vp, table, lens)
    assert np.allclose(np.asarray(got)[0], 0.0)
    assert not np.any(np.isnan(np.asarray(got)))
    # empty lanes beside full ones over several blocks: a full lane's last
    # block starts the copy of the next lane with work, past the empty ones
    full = 10 * 64
    q, kp, vp, table, lens = _paged_case(
        6, 6, 2, 16, 64, 10, 64, jnp.float32, [0, full, 0, 0, full, 1])
    with ops.use_backend("pallas_interpret"):
        got = np.asarray(ops.paged_decode_attention(q, kp, vp, table, lens))
    want = np.asarray(ref.paged_decode_attention(q, kp, vp, table, lens))
    assert not np.any(np.isnan(got))
    np.testing.assert_array_equal(got[[0, 2, 3]], 0.0)
    np.testing.assert_allclose(got[[1, 4, 5]], want[[1, 4, 5]],
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Engine parity + lifecycle
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qwen():
    cfg = REDUCED["qwen3-8b"]
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


def _paged_engine(model, params, n_slots=2, **kw):
    kw.setdefault("max_seq", 96)
    kw.setdefault("page_size", 16)
    kw.setdefault("prefill_chunk", 32)
    return ServeEngine(model, params, n_slots=n_slots, paged=True, **kw)


def test_paged_matches_dense_token_for_token(qwen):
    """Power-of-two prompts: dense bucketing is exact, so the two engines
    must agree on every generated token."""
    cfg, model, params = qwen
    prompts = _prompts(cfg, [32, 64, 32, 64], seed=3)
    dense = ServeEngine(model, params, n_slots=2, max_seq=96, paged=False)
    paged = _paged_engine(model, params)
    for p in prompts:
        dense.submit(p, max_new_tokens=5)
        paged.submit(p, max_new_tokens=5)
    dd = sorted(dense.run(300), key=lambda r: r.req_id)
    pd = sorted(paged.run(300), key=lambda r: r.req_id)
    assert [r.generated for r in pd] == [r.generated for r in dd]


def test_chunked_prefill_exact_at_any_length(qwen):
    """Chunked prefill takes the true final prompt position (regression for
    the bucketed first-token bug) and pads nothing the model can see: the
    continuation equals an exact unpadded prefill + decode at every prompt
    length, including lengths that cross chunk boundaries."""
    cfg, model, params = qwen
    from repro.serving.kvcache import expand_prefill_cache

    def exact(p, n_new):
        logits, cache = jax.jit(model.prefill)(
            params, {"tokens": jnp.asarray([p], jnp.int32)}
        )
        out = [int(jnp.argmax(logits[0]))]
        cache = expand_prefill_cache(cache, model.init_cache(1, 96))
        dec = jax.jit(model.decode_step)
        pos = len(p)
        for _ in range(n_new - 1):
            lg, cache = dec(params, cache, {
                "tokens": jnp.asarray([[out[-1]]], jnp.int32),
                "positions": jnp.asarray([pos], jnp.int32),
            })
            out.append(int(jnp.argmax(lg[0])))
            pos += 1
        return out

    prompts = _prompts(cfg, [5, 11, 33, 40], seed=4)
    eng = _paged_engine(model, params)
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run(300)
    for r, p in zip(reqs, prompts):
        assert r.generated == exact(p, 4), len(p)


def test_paged_engine_frees_pages_on_completion(qwen):
    cfg, model, params = qwen
    eng = _paged_engine(model, params, n_slots=2)
    usable = eng.pool.available
    reqs = [eng.submit(p, max_new_tokens=4)
            for p in _prompts(cfg, [8, 8, 8, 8], seed=5)]
    eng.step()
    assert eng.pool.outstanding > 0
    eng.run(300)
    assert all(r.done for r in reqs)
    assert eng.pool.available == usable
    assert eng.pool.outstanding == 0
    assert np.all(eng.page_table == 0)  # all rows back to the scratch page


def test_paged_pool_exhaustion_queues_requests(qwen):
    """An undersized pool must queue, not corrupt: every request still
    completes with the same tokens as an uncontended engine."""
    cfg, model, params = qwen
    prompts = _prompts(cfg, [32, 32, 32, 32], seed=6)
    big = _paged_engine(model, params, n_slots=2)
    small = _paged_engine(model, params, n_slots=2, n_pages=4)  # 3 usable
    for p in prompts:
        big.submit(p, max_new_tokens=5)
        small.submit(p, max_new_tokens=5)
    bd = sorted(big.run(400), key=lambda r: r.req_id)
    sd = sorted(small.run(400), key=lambda r: r.req_id)
    assert len(sd) == len(prompts)
    assert [r.generated for r in sd] == [r.generated for r in bd]


def test_paged_snapshot_restore_resumes_identically(qwen):
    """Mid-generation paged snapshot restored on a 'substitute host' must
    produce the same continuations (ad hoc continuity, paper §III-D)."""
    cfg, model, params = qwen
    prompts = _prompts(cfg, [8, 24, 40, 12], seed=7)

    ref_eng = _paged_engine(model, params)
    for p in prompts:
        ref_eng.submit(p, max_new_tokens=8)
    ref_done = sorted(ref_eng.run(400), key=lambda r: r.req_id)

    eng = _paged_engine(model, params)
    for p in prompts:
        eng.submit(p, max_new_tokens=8)
    for _ in range(3):
        eng.step()
    blob = eng.snapshot()
    eng2 = _paged_engine(model, params)
    eng2.restore(blob)
    done2 = sorted(eng2.run(400), key=lambda r: r.req_id)

    assert [r.generated for r in done2] == [r.generated for r in ref_done]
    # allocator state survived: finish everything, pool fully drains
    assert eng2.pool.outstanding == 0
    assert np.all(eng2.page_table == 0)


def test_paged_dense_snapshot_mode_mismatch_rejected(qwen):
    cfg, model, params = qwen
    paged = _paged_engine(model, params)
    blob = paged.snapshot()
    dense = ServeEngine(model, params, n_slots=2, max_seq=96, paged=False)
    with pytest.raises(AssertionError):
        dense.restore(blob)


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "zamba2-1.2b"])
def test_stateful_families_paged_serve(arch):
    """Chunked prefill writes recurrent state in place (dt=0 pad identity);
    paged serving of SSM/hybrid families completes and is deterministic."""
    cfg = REDUCED[arch]
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    outs = []
    for _ in range(2):
        eng = ServeEngine(model, params, n_slots=2, max_seq=64, paged=True,
                          page_size=16, prefill_chunk=16)
        reqs = [eng.submit(p, max_new_tokens=4)
                for p in _prompts(cfg, [6, 18, 9], seed=8)]
        done = sorted(eng.run(300), key=lambda r: r.req_id)
        assert len(done) == 3
        outs.append([tuple(r.generated) for r in done])
    assert outs[0] == outs[1]
