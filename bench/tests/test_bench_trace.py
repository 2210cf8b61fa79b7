"""The trace reduction: by hand on a made-up trace, and on a small trace
recorded on a TPU v5 lite (one phi4-chat engine step with a prefill chunk
and a decode) against sums taken straight from its records."""

import gzip
import json
from pathlib import Path

import bench_tiny  # noqa: F401
import pytest

from bench import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur, kernel=False):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "kernel": kernel}


def test_op_names_from_hlo_text():
    assert tr.op_name("%paged_decode_attention.9 = bf16[16,24,128]{2,1,0} "
                      "custom-call(s32[16]{0} %a)") == "paged_decode_attention"
    assert tr.op_name("%bitcast_add_fusion.5 = bf16[16,1,3072] fusion(..)") \
        == "bitcast_add_fusion"
    assert tr.base_name("jit__unknown(15388027131515875373)") == "jit__unknown"


def test_hand_made_trace():
    recs = [
        ev(DEV, "XLA Modules", "jit__unknown", 100, 500),
        ev(DEV, "XLA Ops", "while", 100, 400),
        ev(DEV, "XLA Ops", "fusion", 100, 100),
        ev(DEV, "XLA Ops", "flash_attention", 250, 250, kernel=True),
        ev(DEV, "XLA Ops", "fusion", 550, 50),          # 500..550 idle
        ev(DEV, "XLA Modules", "jit__argmax", 800, 100),
        ev(DEV, "XLA Ops", "argmax", 800, 100),          # 600..800 idle
        ev(HOST, "python3", "bench.step", 0, 1000),
        ev(HOST, "python3", "bench.readback", 590, 300),
    ]
    r = tr.reduce(recs, 0, 1000)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: 100..500, 550..600, 800..900
    assert r["busy_s"] == pytest.approx(550e-9)
    assert r["ops"] == pytest.approx({"fusion": 150e-9, "flash_attention": 250e-9,
                                      "argmax": 100e-9})
    assert r["programs"] == pytest.approx({"jit__unknown[flash_attention]": 500e-9,
                                           "jit__argmax[]": 100e-9})
    # gaps 0..100 and 500..550 in bench.step only; 600..800 and 900..1000
    # in the inner readback span (mid 700 and 950 fall in 590..890? 950 no)
    assert r["idle"] == pytest.approx({"bench.step": (100 + 50 + 100) * 1e-9,
                                       "bench.readback": 200e-9})
    assert tr.kernel_seconds(r, ["flash"]) == pytest.approx(250e-9)


def test_window_clips_events():
    recs = [ev(DEV, "XLA Ops", "fusion", 0, 100), ev(DEV, "XLA Ops", "fusion", 150, 100)]
    r = tr.reduce(recs, 50, 200)
    assert r["busy_s"] == pytest.approx(100e-9)
    assert r["idle"] == pytest.approx({"no host span": 50e-9})


def test_recorded_tpu_trace():
    path = Path(__file__).parent / "data" / "trace_phi4_chat.json.gz"
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    recs, t0, t1 = d["events"], d["t0_ns"], d["t1_ns"]
    r = tr.reduce(recs, t0, t1)
    ops = [x for x in recs if x["line"] == "XLA Ops"]
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(r["idle"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    def inside(name):
        return sum(min(x["start_ns"] + x["dur_ns"], t1) - max(x["start_ns"], t0)
                   for x in ops if x["name"] == name) * 1e-9
    assert tr.kernel_seconds(r, ["paged_decode"]) == pytest.approx(
        inside("paged_decode_attention"))
    assert tr.kernel_seconds(r, ["flash"]) == pytest.approx(inside("flash_attention"))
    progs = r["programs"]
    assert any("paged_decode_attention" in p for p in progs)
    assert any("flash_attention" in p and "paged" not in p for p in progs)
    # the engine's step programs take most of the busy time
    step = tr.program_seconds(r, ["paged_decode_attention", "flash_attention"])
    assert 0.9 * r["busy_s"] < step <= r["busy_s"] * 1.0001
    assert "while" not in r["ops"]
    b = tr.breakdown(r)
    assert len(b["device_ops"]) == 10 and b["idle_gaps"][0][1] > 0


def test_device_readers_find_their_programs_in_the_recorded_trace():
    """Every device-trace reader of the serving cells reads a number, at
    most 100%, from the recorded trace and calls of the shape it held."""
    from bench import catalog, peaks

    path = Path(__file__).parent / "data" / "trace_phi4_chat.json.gz"
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    cat = catalog.Catalog()
    conf = cat.config("phi4-mini-3.8b")
    ctx = {"conf": conf, "family": catalog.family("dense"),
           "peaks": peaks.lookup("TPU v5 lite"), "chips": 1,
           "trace": tr.reduce(d["events"], d["t0_ns"], d["t1_ns"]),
           "calls": [("decode", [600] * 13, 13), ("prefill", 512, 256, False)],
           "roofline": cat.roofline}
    names = [m["name"] for m in cat.metrics("per_layer", "phi4-chat")
             if m["source"] == "device_trace"]
    assert len(names) == 5
    for name in names:
        v = cat.reader(name)(ctx)
        assert v is not None and 0 < v <= 100, (name, v)


def test_a_chunk_is_final_unless_its_slot_goes_on_from_its_end():
    from bench.harness import mark_final

    calls = [("prefill", 0, 256, 3), ("decode", [5, 9], 2),
             ("prefill", 256, 100, 3), ("prefill", 0, 256, 4),
             ("prefill", 0, 64, 3)]
    assert mark_final(calls) == [
        ("prefill", 0, 256, False), ("decode", [5, 9], 2),
        ("prefill", 256, 100, True), ("prefill", 0, 256, True),
        ("prefill", 0, 64, True)]
