#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: run its mix at each of
several rates in one process and print what each sustained.

    python3 bench/sweep.py --workload phi4-chat --key arrivals.rate_per_s \\
        --rates 2,3,4,5 --seconds 30 --seed 1

One JSON line per rate: offered and delivered tokens per second, TTFT and
ITL (p50, p95), requests due, finished and still queued at the close.
The knee is the highest rate whose queue does not grow through the
window; a cell's mix then fixes its rate at about four fifths of it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--key", required=True, help="dotted path of the rate in the mix")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    for rate in (float(r) for r in args.rates.split(",")):
        diag: dict = {}
        out = harness.run(args.workload, args.seed, args.seconds, False,
                          t_start=time.monotonic(), root=ROOT,
                          mix_override={args.key: rate}, diag=diag)
        print(json.dumps({"rate": rate, "attempted": out["attempted"],
                          "correct": out["correct"], **diag}), flush=True)


if __name__ == "__main__":
    main()
