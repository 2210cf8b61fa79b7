"""Benchmark entry point: one section per paper table/figure + the
framework's own performance tables.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--csv PATH]

Sections:
- reliability  — paper §IV completion-rate replay (30 hosts, traces)
- performance  — paper §IV ad hoc vs dedicated makespan
- snapshot     — §III-D placement quality + snapshot costs
- straggler    — interference mitigation (low-interference rule)
- kernel       — kernel micro-benchmarks
- roofline     — per-cell roofline terms from dry-run artifacts
- serving      — paged vs dense serving engine + copy-on-write prefix
                 sharing vs the non-shared paged path + multi-host page
                 spill under churn + vlm paged serving (BENCH_SERVING;
                 also written machine-readably to BENCH_SERVING.json at
                 the repo root so the perf trajectory is tracked across
                 PRs — run `python -m benchmarks.serving_bench
                 --prefix-share`, `--spill` or `--vlm-paged` for one
                 scenario alone; REPRO_BENCH_TINY=1 shrinks everything
                 for the CI smoke job)
- batch        — verified batch-inference tier under seeded churn:
                 workunit replication + hash-quorum validation + re-issue
                 (the ``batch-churn`` rows of BENCH_SERVING.json; run
                 `python -m benchmarks.batch_bench --batch-churn`
                 standalone)
- cell         — elastic tensor-parallel serving cell under seeded churn:
                 re-shard on host loss + snapshot restore + teacher-forced
                 mid-stream replay + priority shedding (the ``cell-churn``
                 row of BENCH_SERVING.json; run
                 `python -m benchmarks.cell_bench --cell-churn` standalone)
- latency      — iteration-level continuous batching under a deep
                 heavy-tailed queue: p50/p99 TTFT and inter-token latency
                 on a simulated clock, token-for-token parity vs the
                 synchronous reference, plus an overload pressure phase
                 exercising preemption and shedding (the ``latency`` row
                 of BENCH_SERVING.json; run
                 `python -m benchmarks.latency_bench` standalone)
"""

import argparse
import csv
import sys


SECTIONS = ["reliability", "performance", "snapshot", "straggler",
            "kernel", "roofline", "serving", "batch", "cell", "latency"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=SECTIONS)
    ap.add_argument("--csv", default=None)
    args = ap.parse_args()

    rows: list[dict] = []
    failed: list[str] = []
    sections = [args.only] if args.only else SECTIONS
    for name in sections:
        print("\n" + "=" * 72)
        print(f"== {name}")
        print("=" * 72)
        try:
            if name == "reliability":
                from benchmarks import reliability_bench as m
            elif name == "performance":
                from benchmarks import performance_bench as m
            elif name == "snapshot":
                from benchmarks import snapshot_bench as m
            elif name == "straggler":
                from benchmarks import straggler_bench as m
            elif name == "kernel":
                from benchmarks import kernel_bench as m
            elif name == "roofline":
                from benchmarks import roofline_bench as m
            elif name == "serving":
                from benchmarks import serving_bench as m
            elif name == "batch":
                from benchmarks import batch_bench as m
            elif name == "cell":
                from benchmarks import cell_bench as m
            elif name == "latency":
                from benchmarks import latency_bench as m
            m.main(rows)
        except Exception as e:  # run the other sections, fail at the end
            print(f"SECTION FAILED: {name}: {type(e).__name__}: {e}")
            import traceback
            traceback.print_exc()
            failed.append(name)

    if args.csv:
        keys = sorted({k for r in rows for k in r})
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=keys)
            w.writeheader()
            w.writerows(rows)
        print(f"\nwrote {len(rows)} rows to {args.csv}")
    if failed:
        sys.exit(f"{len(failed)} section(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
