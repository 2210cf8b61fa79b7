#!/usr/bin/env python3
"""Readings for a cell's check limit, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds a,b,c...

For each seed: one run of the cell at its own load for ``--seconds``
(its own weights, engine and traffic), then the widest logit gap of the
served tokens against the float32 reference (the program's reading) and
of the tokens the fp8 control puts first at the same positions (the
control's reading), each judged by the configuration's limit. Prints one
JSON line per seed and a summary: the largest program reading and the
smallest control reading, the two ends the limit is set between. Exits
non-zero unless every seed's program reads correct and every seed's
control reads not correct.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    prog, ctl, sound = [], [], True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(args.workload, seed, args.seconds, False,
                          t_start=time.monotonic(), root=ROOT, control=True)
        p = out["checks"]["logit_gap_max"]["value"]
        c = out["control"]["logit_gap_max"]
        prog.append(p)
        ctl.append(c)
        sound &= out["correct"] and not out["control"]["correct"]
        print(json.dumps({"seed": seed, "program": p, "control": c,
                          "program_correct": out["correct"],
                          "control_correct": out["control"]["correct"],
                          "attempted": out["attempted"],
                          "metrics": out["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(prog),
                      "program_max": max(prog), "control_min": min(ctl),
                      "separated": sound, "program": prog, "control": ctl}),
          flush=True)
    if not sound:
        raise SystemExit(1)

if __name__ == "__main__":
    main()
