"""The one traffic generator: a seed always gives the same requests,
another seed other token ids, and every seed the same schedule of sizes
and due times, so that the load does not change with the seed."""

import json

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import loadgen

MIXES = sorted(p.stem for p in (bench_tiny.ROOT / "bench" / "traffic").glob("*.json"))


def _drive(mix: dict, seed: int, seconds: float = 60.0):
    """Every arrival the mix makes over the window, each answered at once
    with ``max_new`` tokens (sessions need answers to make later turns)."""
    src = loadgen.source(mix, seed, seconds, vocab=1000)
    out = []
    t = 0.0
    while t < seconds:
        for a in src.ready(t, 0):
            out.append(a)
            if mix["kind"] != "backlog":
                src.finished(a, [7] * a.max_new, t)
        if mix["kind"] == "backlog" and len(out) >= 200:
            break
        t += 0.05
    return out


def _load(name):
    return json.loads((bench_tiny.ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = _drive(_load(name), 2**31 + 11), _drive(_load(name), 2**31 + 11)
    assert [(x.due, x.prompt, x.max_new) for x in a] == \
        [(x.due, x.prompt, x.max_new) for x in b]
    assert len(a) > 10


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_requests_same_work(name):
    mix = _load(name)
    a, b = _drive(mix, 5), _drive(mix, 2**32 + 5)
    assert [x.prompt for x in a] != [x.prompt for x in b]
    # the same schedule: sizes and due times in the same order
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in b]
    if mix["kind"] == "open_loop":
        assert len(a) == round(mix["arrivals"]["rate_per_s"] * 60)
    if mix["kind"] == "sessions":
        sa, sb = loadgen.source(mix, 5, 60, 1000), loadgen.source(mix, 9, 60, 1000)
        assert sa.sessions == sb.sessions and sa.system != sb.system


def test_order_seed_reorders_the_same_sizes():
    mix = _load("chat")
    a = _drive(mix, 5)
    b = _drive(dict(mix, order_seed=1), 5)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)


def test_sizes_are_quantiles_within_bounds():
    rng = loadgen.rng_for(1, "x")
    spec = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 32, "max": 1536}
    v = loadgen.sizes(spec, 201, rng)
    assert v.min() >= 32 and v.max() <= 1536
    assert sorted(v)[100] == 512          # the middle quantile is the median


def test_on_off_puts_the_share_in_the_on_halves():
    spec = {"rate_per_s": 10.0, "on_off": {"period_s": 4.0, "on_share": 0.8}}
    t = loadgen.arrival_times(spec, 40.0, loadgen.rng_for(3, "x"))
    on = ((t % 4.0) < 2.0).mean()
    assert abs(on - 0.8) < 0.03 and len(t) == 400


def test_sessions_grow_prompts_and_regenerate():
    mix = _load("sessions")
    arr = _drive(mix, 17)
    by = {}
    for a in arr:
        by.setdefault(a.session, []).append(a)
    regen = 0
    for turns in by.values():
        for prev, nxt in zip(turns, turns[1:]):
            if nxt.meta.get("regenerated"):
                regen += 1
                assert nxt.prompt == prev.prompt
            else:
                assert nxt.prompt[: len(prev.prompt)] == prev.prompt
                assert len(nxt.prompt) <= mix["max_prompt"]
        assert len(turns[0].prompt) > mix["system"]["tokens"]
    assert regen > 0
