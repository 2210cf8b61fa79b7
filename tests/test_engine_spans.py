"""The serving engine's host spans and host-side counters.

- ``engine.*`` spans land in a profiler trace, the step's phases nested
  inside ``engine.step``, a prefill chunk carrying its request's id;
- on a busy mix (slots blocked by page pressure, prompts prefilling
  across steps) the slot counters account for every slot of every
  decode step, the host clock counters stay inside the loop's wall
  time, and all of them behave like the engine's other counters under
  ``reset_stats``, ``snapshot`` and ``restore``.

Token parity with the engine as it was is held by the existing greedy
reference tests (``test_paged.py``, ``test_scheduler.py``).
"""

import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import REDUCED
from repro.models import get_model
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import SchedulerConfig

STEP_PHASES = ("engine.schedule", "engine.prefill_chunk", "engine.first_token",
               "engine.upload", "engine.decode", "engine.readback",
               "engine.commit")
COUNTERS = ("decode_steps", "prefill_slot_steps", "blocked_slot_steps",
            "host_gap_ns", "caller_ns", "queue_wait_ns", "queue_waits")


@pytest.fixture(scope="module")
def qwen():
    cfg = REDUCED["qwen3-8b"]
    model = get_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _busy_engine(model, params):
    """Four slots but pages for two or three requests, and a token budget
    that spreads a prompt over several steps."""
    return ServeEngine(model, params, paged=True, n_slots=4, max_seq=128,
                       page_size=16, n_pages=13, prefill_chunk=16,
                       scheduler=SchedulerConfig(token_budget=20))


def _prompts(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, int(k)).tolist()
            for k in rng.integers(20, 60, n)]


def test_spans_nest_inside_the_step_and_name_their_request(qwen, tmp_path):
    cfg, model, params = qwen
    eng = _busy_engine(model, params)
    for p in _prompts(cfg, 3, seed=0):          # compile outside the trace
        eng.submit(p, max_new_tokens=3)
    eng.run(200)
    with jax.profiler.trace(str(tmp_path)):
        reqs = [eng.submit(p, max_new_tokens=3)
                for p in _prompts(cfg, 6, seed=1)]
        eng.run(200)
    assert all(r.done for r in reqs)
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    steps = [s for s in spans if s[0] == "engine.step"]
    names = {s[0] for s in spans}
    assert set(STEP_PHASES) | {"engine.submit", "engine.step"} <= names
    for name, t0, t1, _ in spans:
        if name in STEP_PHASES:
            assert any(s0 <= t0 and t1 <= s1 for _, s0, s1, _ in steps), name
    ids = {r.req_id for r in reqs}
    submits = [m["req"] for n, _, _, m in spans if n == "engine.submit"]
    assert sorted(submits) == sorted(ids)
    chunks = [m for n, _, _, m in spans if n == "engine.prefill_chunk"]
    assert {m["req"] for m in chunks} == ids
    for r in reqs:
        mine = sorted(m["offset"] for m in chunks if m["req"] == r.req_id)
        assert mine[0] == 0 and sum(m["valid"] for m in chunks
                                    if m["req"] == r.req_id) == len(r.prompt)
    decodes = [m["lanes"] for n, _, _, m in spans if n == "engine.decode"]
    assert decodes and all(1 <= k <= eng.n_slots for k in decodes)


def test_counters_account_for_every_slot_of_every_decode_step(qwen):
    cfg, model, params = qwen
    eng = _busy_engine(model, params)
    eng.submit(_prompts(cfg, 1, seed=2)[0], max_new_tokens=2)
    eng.run(50)                                  # compile
    eng.reset_stats()
    assert all(eng.stats[k] == 0 for k in COUNTERS)
    seen = []                                    # slot states at dispatch
    decode = eng._decode_paged

    def watch(params, cache, batch):
        empty = eng.slot_req.count(None)
        seen.append((len(eng.prefilling), int(np.sum(eng.slot_hold > 0)),
                     empty if eng.queue else 0, 0 if eng.queue else empty))
        return decode(params, cache, batch)

    eng._decode_paged = watch
    prompts = _prompts(cfg, 10, seed=3)
    t0 = time.perf_counter_ns()
    reqs, decoded = [], 0
    for i in range(400):
        if i % 2 == 0 and prompts:               # arrivals between steps
            reqs.append(eng.submit(prompts.pop(), max_new_tokens=8))
        if not eng.pending() and not prompts:
            break
        before = dict(eng.stats)
        n_seen = len(seen)
        lanes = eng.step()
        d = {k: eng.stats[k] - before[k] for k in COUNTERS}
        if lanes:
            decoded += 1
            assert len(seen) == n_seen + 1
            prefilling, held, blocked, idle = seen[-1]
            assert d["decode_steps"] == 1
            assert d["prefill_slot_steps"] == prefilling
            assert d["blocked_slot_steps"] == blocked
            assert lanes + prefilling + held + blocked + idle == eng.n_slots
        else:
            assert d["decode_steps"] == d["prefill_slot_steps"] == 0
    wall = time.perf_counter_ns() - t0
    assert all(r.done for r in reqs) and len(reqs) == 10
    s = eng.stats
    assert s["decode_steps"] == decoded
    assert s["blocked_slot_steps"] > 0 and s["prefill_slot_steps"] > 0
    assert s["queue_waits"] == len(reqs)
    for k in ("host_gap_ns", "caller_ns", "queue_wait_ns"):
        assert 0 <= s[k] < wall * (len(reqs) if k == "queue_wait_ns" else 1)
    assert s["host_gap_ns"] > 0 and s["caller_ns"] > 0

    clone = _busy_engine(model, params)
    clone.restore(eng.snapshot())
    assert {k: clone.stats[k] for k in COUNTERS} == \
        {k: s[k] for k in COUNTERS}


def test_an_empty_engine_counts_no_host_time(qwen):
    """Time spent with nothing pending is waiting for work: neither the
    caller's time nor a host gap."""
    cfg, model, params = qwen
    eng = _busy_engine(model, params)
    eng.submit(_prompts(cfg, 1, seed=4)[0], max_new_tokens=2)
    eng.run(50)
    eng.reset_stats()
    time.sleep(0.5)
    eng.submit(_prompts(cfg, 1, seed=5)[0], max_new_tokens=2)
    t0 = time.perf_counter_ns()
    eng.run(50)
    wall = time.perf_counter_ns() - t0
    assert wall < 0.5e9
    assert eng.stats["caller_ns"] + eng.stats["host_gap_ns"] < wall
    assert eng.stats["queue_waits"] == 1
    assert eng.stats["queue_wait_ns"] < wall
