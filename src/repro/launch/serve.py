"""End-to-end serving driver (CLI).

Stands up a serving cloudlet: a :class:`~repro.serving.engine.ServeEngine`
guest processes a batch of requests with continuous batching; an optional
mid-stream failure snapshots the engine, restores it on another host, and
generation resumes deterministically (greedy sampling).

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b \\
        --requests 12 --max-new 16 [--fail-after 5]

``--full`` serves the published widths and depth instead of the reduced
config. Params are drawn in bf16, the dtype the model computes in; on a
TPU the Pallas kernels run by default.
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at one fixed path and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
    itself), else ``<repo>/.jax_cache``. A fixed path is what lets a later
    process find the programs an earlier one compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path is None:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_engine(arch: str, *, full: bool, slots: int, max_seq: int,
                 seed: int = 0):
    """The served model's engine, as the CLI builds it: params drawn from
    ``seed`` in bf16 (the dtype the model computes in), then a paged,
    continuously batching :class:`ServeEngine`."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get
    from repro.models import get_model
    from repro.serving.engine import ServeEngine

    model = get_model(get(arch, reduced=not full))
    params = model.init(jax.random.key(seed), jnp.bfloat16)
    return ServeEngine(model, params, n_slots=slots, max_seq=max_seq)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--fail-after", type=int, default=None,
                    help="kill the serving host after N engine steps")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import numpy as np

    from repro.kernels import ops
    from repro.serving.engine import ServeEngine

    use_compile_cache()
    engine = build_engine(args.arch, full=args.full, slots=args.slots,
                          max_seq=args.max_seq, seed=args.seed)
    cfg = engine.model.cfg
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size, args.prompt_len).tolist()
        engine.submit(prompt, max_new_tokens=args.max_new)
    dev = jax.devices()[0]
    print(f"serving {args.requests} requests on {args.arch} "
          f"({args.slots} slots) on {dev.platform} {dev.device_kind}, "
          f"kernels: {ops.current_backend()}")

    if args.fail_after is None:
        done = engine.run()
    else:
        for _ in range(args.fail_after):
            engine.step()
        print(f"-- host failure after {args.fail_after} steps: snapshotting, "
              f"restoring on substitute host --")
        blob = engine.snapshot()          # P2P replica (paper §III-D)
        engine2 = ServeEngine(engine.model, engine.params,
                              n_slots=args.slots, max_seq=args.max_seq)
        engine2.restore(blob)             # restore on the receiver
        done = engine2.run()

    for r in sorted(done, key=lambda r: r.req_id)[:6]:
        print(f"  req {r.req_id}: prompt {r.prompt[:4]}... -> {r.generated}")
    print(f"{len(done)}/{args.requests} requests completed")


if __name__ == "__main__":
    main()
