"""The per-layer metrics read from the engine's own counters: each reads
a number in a traced run of a tiny cell, gives None where its
denominator is 0 or the program lacks the counter, and the slot means
of a backlog account for every slot of the batch."""

import bench_tiny
import pytest

METRICS = [("host.gap_ms_per_step.serve", "ms"),
           ("host.gap_ms_per_step.batch", "ms"),
           ("host.caller_ms_per_step.serve", "ms"),
           ("host.caller_ms_per_step.batch", "ms"),
           ("scheduler.queue_wait_ms_mean.serve", "ms"),
           ("scheduler.prefill_slots_mean.batch", "slots"),
           ("scheduler.blocked_slots_mean.batch", "slots")]
NAMES = [n for n, _ in METRICS]
COUNTERS = ("decode_steps", "prefill_slot_steps", "blocked_slot_steps",
            "host_gap_ns", "caller_ns", "queue_wait_ns", "queue_waits")
BACKLOG = {"kind": "backlog", "depth": 4, "block": 16, "ramp": "turnover",
           "prompt": dict(bench_tiny.TINY_CHAT["prompt"], median=60),
           "output": dict(bench_tiny.TINY_CHAT["output"], median=12)}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced run of a backlog on a pool that holds about three of the
    four lanes' requests."""
    config = dict(bench_tiny.TINY_DENSE,
                  engine=dict(bench_tiny.TINY_DENSE["engine"], n_pages=17))
    per_layer = METRICS + [("scheduler.decode_lanes_mean.batch", "lanes")]
    root = bench_tiny.make_root(tmp_path_factory.mktemp("root"),
                                config=config, traffic=BACKLOG,
                                per_layer=per_layer)
    return bench_tiny.run(root, seconds=2.0, trace=True)


@pytest.mark.parametrize("name", NAMES)
def test_each_metric_reads_a_number_in_a_traced_run(traced, name):
    assert traced["correct"], traced["checks"]
    assert traced["metrics"][name]["value"] >= 0
    assert traced["metrics"][name]["unit"] == dict(METRICS)[name]


def test_slot_means_of_a_backlog_add_up_to_the_batch(traced):
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert m["scheduler.blocked_slots_mean.batch"] > 0
    total = (m["scheduler.decode_lanes_mean.batch"]
             + m["scheduler.prefill_slots_mean.batch"]
             + m["scheduler.blocked_slots_mean.batch"])
    assert abs(total - bench_tiny.TINY_DENSE["engine"]["n_slots"]) < 0.3


@pytest.mark.parametrize("stats", [dict.fromkeys(COUNTERS, 0), {}],
                         ids=["zero", "no_counters"])
@pytest.mark.parametrize("name", NAMES)
def test_a_reader_gives_none_without_a_denominator(name, stats):
    from bench import catalog

    ctx = {"stats": dict(stats, prefill_tokens=0),
           "conf": bench_tiny.TINY_DENSE}
    assert catalog.Catalog().reader(name)(ctx) is None
