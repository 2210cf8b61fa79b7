"""A whole benchmark run on the CPU, the look for a chip skipped, with
the timed path broken underneath: ``correct`` has to come out false for
each fault a serving cell on one chip can have, and true unbroken."""

import bench_tiny
import jax.numpy as jnp
import pytest


def state_unchanged(engine):
    """Every step returns its cache as it got it: no K/V is ever written."""
    decode, chunk = engine._decode_paged, engine._prefill_chunk

    def d(params, cache, batch):
        return decode(params, cache, batch)[0], cache

    def c(params, cache, batch, **kw):
        return chunk(params, cache, batch, **kw)[0], cache

    engine._decode_paged, engine._prefill_chunk = d, c


def token_altered(engine):
    """Decode picks token 3 whatever the logits say."""
    decode = engine._decode_paged

    def d(params, cache, batch):
        logits, cache = decode(params, cache, batch)
        return logits.at[:, 3].set(jnp.inf), cache

    engine._decode_paged = d


def half_lanes_unwritten(engine):
    """Decode lanes in the upper half of the batch never write their K/V:
    the step returns the cache the lower half alone wrote."""
    decode = engine._decode_paged

    def d(params, cache, batch):
        logits, _ = decode(params, cache, batch)
        half = batch["page_table"].shape[0] // 2
        lower = dict(batch, page_table=batch["page_table"].at[half:].set(0))
        return logits, decode(params, cache, lower)[1]

    engine._decode_paged = d


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # arrivals fast enough that every lane of the batch serves
    busy = dict(bench_tiny.TINY_CHAT, arrivals={"rate_per_s": 60.0})
    return bench_tiny.make_root(tmp_path_factory.mktemp("root"), traffic=busy)


@pytest.mark.parametrize("fault", [None, state_unchanged, token_altered,
                                   half_lanes_unwritten],
                         ids=["sound", "state_unchanged", "token_altered",
                              "half_lanes_unwritten"])
def test_correct_follows_the_timed_path(root, fault):
    out = bench_tiny.run(root, seconds=2.0, fault=fault)
    gap = out["checks"]["logit_gap_max"]
    assert out["attempted"] > 0 and list(out["checks"]) == ["logit_gap_max"]
    assert out["correct"] is (fault is None), gap
    assert (gap["value"] <= gap["limit"]) is (fault is None)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_a_backlog_window_counts_every_token_it_makes(tmp_path, seed):
    """The batch tier's window opens on requests the ramp left in flight
    and queued: their tokens made in the window count, as every
    request's do, and no token made before it."""
    import time

    from bench import harness

    backlog = {"kind": "backlog", "depth": 4, "block": 16, "ramp": "turnover",
               "prompt": dict(bench_tiny.TINY_CHAT["prompt"]),
               "output": dict(bench_tiny.TINY_CHAT["output"], median=12)}
    root = bench_tiny.make_root(tmp_path, traffic=backlog)
    diag: dict = {}
    out = harness.run("tiny-chat", seed, 1.5, False, t_start=time.monotonic(),
                      root=root, require_chip=False, diag=diag)
    assert out["correct"] is True
    assert diag["tokens"] == diag["engine_tokens"] > 0
    rate = out["metrics"]["output_tokens_per_s"]["value"]
    assert rate == pytest.approx(diag["engine_tokens"] / diag["window_s"])
