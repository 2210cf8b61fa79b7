"""A cell, its configuration, its traffic mix and a per-layer metric
defined only in new files: the harness runs it without an edit, and the
result line keeps to the contract's shape."""

import json
import subprocess
import sys

import bench_tiny

READER = '''
"""Requests the cell admitted in the window."""


def read(ctx):
    return len(ctx["queue_waits_ms"]) or None
'''
SESSIONS = {
    "kind": "sessions", "shares_prefixes": True,
    "starts": {"rate_per_s": 3.0, "on_off": {"period_s": 1.0, "on_share": 0.8}},
    "system": {"count": 2, "tokens": 32, "zipf_s": 1.0},
    "turns": {"dist": "uniform", "min": 2, "max": 3},
    "user": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2, "max": 20},
    "answer": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 12},
    "think_s": {"min": 0.05, "max": 0.2}, "regenerate_share": 0.3,
    "max_prompt": 120,
}


def test_a_new_cell_from_files_only(tmp_path):
    root = bench_tiny.make_root(
        tmp_path, traffic=SESSIONS, cell="tiny-sessions",
        per_layer=[("extra.admitted", "count"), ("cache.prefix_hit_share", "%")],
        metric_files={"extra.admitted": READER})
    out = bench_tiny.run(root, cell="tiny-sessions", seconds=3.0, trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["extra.admitted"]["value"] > 0
    assert out["metrics"]["cache.prefix_hit_share"]["value"] > 0
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device",
                        "breakdown", "checks"}
    assert list(out)[-1] == "checks"
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(out["device"])


def test_no_tpu_no_result(tmp_path):
    """On a machine with no TPU the command exits non-zero and prints no
    result line."""
    p = subprocess.run(
        [sys.executable, str(bench_tiny.ROOT / "bench" / "run.py"),
         "--workload", "phi4-chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "TMPDIR": str(tmp_path)})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.startswith("{")
    json.dumps(p.returncode)
