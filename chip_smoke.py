#!/usr/bin/env python3
"""Smoke test: serve phi4-mini-3.8b at its published size on a TPU.

    python chip_smoke.py [--seed N]      # one chip: the ServeEngine path
    python chip_smoke.py --four-chips    # four chips: the elastic cell

One chip: the engine that ``python -m repro.launch.serve --full`` builds
(bf16 params drawn from ``--seed``, paged KV, continuous batching, chunked
prefill, the Pallas kernels) serves 8 requests of 300-1200 prompt tokens
and 32 new tokens each. One request re-asks a page-aligned prefix of
another, so it is admitted as a whole-prompt prefix hit whose last page is
copied on write. Checks: every request completes with 32 tokens, every
logit the engine computes is finite, the COW copy ran, and one decode step
with every lane active gives the same logits under the Pallas kernels as
under their XLA twins, within ``REL_TOL``.

Four chips: the elastic tensor-parallel cell with ``materialize=True``:
4 hosts x 1 chip, model_parallel 2, so params and the paged KV pool live
on a real (2, 2) mesh. One host crashes mid-decode; the cell must shrink
its mesh, resume from its snapshot and complete every committed stream.
Its first decode step is compared against the one-chip engine in this
process, within ``REL_TOL``.

Everything runs in this one process, which holds the chip(s). The compile
cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
Timings printed here are smoke timings, not benchmark numbers. The last
line is ``{"ok": true, "device": {...}}``; any failure exits non-zero
before it, and so does a machine where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "phi4-mini-3.8b"
N_REQUESTS, SLOTS, MAX_SEQ, MAX_NEW = 8, 8, 2048, 32
PROMPT_LO, PROMPT_HI = 300, 1200
# the four-chip cell: 4 slots of 512 positions, 4 prompts of 64-255 tokens
CELL_SLOTS, CELL_MAX_SEQ, CELL_LO, CELL_HI = 4, 512, 64, 255
# bf16 tolerance on one decode step's logits, per lane, as a relative L2
# error. Both sides read the same cache, so only the step's own kernels
# differ, by a bf16 rounding (2^-8) here and there, and the spread grows
# like sqrt(depth) * 2^-8: 0.009 at 2 layers and 0.022-0.027 at 32 layers
# in interpret mode on a CPU. A wrong page, mask or head mapping moves
# the logits by O(1).
REL_TOL = 0.1


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def nbytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def prompts(seed: int, vocab: int, n: int, lo: int, hi: int,
            page: int) -> list[list[int]]:
    """``n`` prompts of ``lo..hi`` tokens. Prompt 0 is ``hi`` long and
    prompt 1 is a page-aligned prefix of it, i.e. a whole-prompt hit on
    prompt 0's pages."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = [hi, 0, *rng.integers(lo, hi + 1, n - 2)]
    out = [rng.integers(1, vocab, int(k)).tolist() for k in lens]
    pages = int(rng.integers(-(-lo // page), hi // page + 1))
    out[1] = out[0][: pages * page]
    return out


def step_logits(engine, tokens=None):
    """Logits of one decode step on the engine's current state (fed
    ``tokens`` in place of its last tokens, if given), through the
    engine's own jitted step; the engine itself is left unchanged."""
    import jax.numpy as jnp

    tokens = engine.last_token if tokens is None else tokens
    batch = {
        "tokens": jnp.asarray(tokens)[:, None],
        "positions": jnp.asarray(engine.lengths),
        "page_table": jnp.asarray(engine.page_table),
    }
    logits, _ = engine._decode_paged(engine.params, engine.cache, batch)
    return logits


def compare(got, want, lanes) -> dict:
    """Per-lane relative L2 error, max |difference| and greedy-token
    agreement of two (lanes, vocab) logit arrays."""
    import numpy as np

    got = np.asarray(got, np.float32)[lanes]
    want = np.asarray(want, np.float32)[lanes]
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          "non-finite logits in the parity step")
    rel = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    return {
        "lanes": len(lanes),
        "max_rel_l2": float(rel.max()),
        "max_abs": float(np.abs(got - want).max()),
        "greedy_agree": int((got.argmax(-1) == want.argmax(-1)).sum()),
    }


def watch_finite(engine, flags: list) -> None:
    """Record, for every step the engine runs, whether its logits were
    all finite (device-side; read once at the end)."""
    import jax.numpy as jnp

    decode, prefill = engine._decode_paged, engine._prefill_chunk

    def checked_decode(params, cache, batch):
        logits, cache = decode(params, cache, batch)
        flags.append(jnp.isfinite(logits).all())
        return logits, cache

    def checked_prefill(params, cache, batch, **kw):
        logits, cache = prefill(params, cache, batch, **kw)
        flags.append(jnp.isfinite(logits).all())
        return logits, cache

    engine._decode_paged, engine._prefill_chunk = checked_decode, checked_prefill


def serve_one_chip(seed: int) -> dict:
    import numpy as np

    from repro.kernels import ops
    from repro.launch.serve import build_engine

    engine = build_engine(ARCH, full=True, slots=SLOTS, max_seq=MAX_SEQ,
                          seed=seed)
    flags: list = []
    watch_finite(engine, flags)
    ps = prompts(seed, engine.model.cfg.vocab_size, N_REQUESTS, PROMPT_LO,
                 PROMPT_HI, engine.page_size)
    reqs = [engine.submit(p, max_new_tokens=MAX_NEW) for p in ps]
    # run until every request is admitted and prefilled: the next decode
    # step is the first one with every lane active
    while engine.queue or engine.prefilling:
        engine.step()
    lanes = [r.slot for r in reqs if not r.done]
    check(len(lanes) == len(reqs), "a request finished before the parity step")
    got = step_logits(engine)
    with ops.use_backend("xla"):
        want = step_logits(engine)
    parity = compare(got, want, lanes)
    del got, want
    engine.run()

    check(all(r.done and len(r.generated) == MAX_NEW for r in reqs),
          f"not every request completed {MAX_NEW} tokens: "
          f"{[len(r.generated) for r in reqs]}")
    check(bool(np.all([bool(f) for f in flags])),
          "the engine computed non-finite logits")
    check(engine.stats["cow_copies"] >= 1 and engine.stats["prefix_hits"] >= 1,
          f"the copy-on-write prefix hit did not run: {engine.stats}")
    check(parity["max_rel_l2"] <= REL_TOL,
          f"pallas vs xla logits differ: {parity} (tolerance {REL_TOL})")
    return {
        "backend": ops.current_backend(),
        "param_bytes": nbytes(engine.params),
        "pool_bytes": nbytes(engine.cache),
        "prompt_lens": [len(p) for p in ps],
        "tokens": sum(len(r.generated) for r in reqs),
        "steps": engine.steps,
        "cow_copies": engine.stats["cow_copies"],
        "prefix_hit_tokens": engine.stats["prefix_hit_tokens"],
        "parity": parity,
    }


def cell_four_chips(seed: int) -> dict:
    """The elastic cell on a real (2, 2) mesh, one host crashing
    mid-decode, against a one-chip engine with the same params."""
    import jax
    import numpy as np

    from repro.core.faults import FaultEvent, FaultPlan
    from repro.core.server import AdHocServer
    from repro.core.simulation import SimClock
    from repro.launch.serve import build_engine
    from repro.parallel.partition import activation_sharding
    from repro.serving.batch import make_engine_factory
    from repro.serving.cell import ElasticServeCell

    kw = dict(n_slots=CELL_SLOTS, max_seq=CELL_MAX_SEQ)
    one = build_engine(ARCH, full=True, slots=CELL_SLOTS,
                       max_seq=CELL_MAX_SEQ, seed=seed)
    model = one.model
    ps = prompts(seed, model.cfg.vocab_size, 4, CELL_LO, CELL_HI,
                 one.page_size)
    # the same engine configuration (synchronous admission) as the cell's
    ref = make_engine_factory(model, one.params, **kw)("one-chip")
    del one
    reqs = [ref.submit(p, max_new_tokens=MAX_NEW) for p in ps]
    ref._admit()
    want = step_logits(ref)
    ref_state = (ref.lengths.copy(), ref.page_table.copy(),
                 ref.last_token.copy())
    ref.run()
    ref_streams = [list(r.generated) for r in reqs]
    # the cell lays out from a host copy; nothing stays on the first chip
    params_host = jax.device_get(ref.params)
    del ref

    srv = AdHocServer(failure_timeout=6.0)
    srv.create_cloudlet("cell", ARCH)
    for i in range(4):
        srv.register_host(f"h{i}", 0.0, cloudlets=["cell"])
    cell = ElasticServeCell(
        srv, "cell", model, params_host, engine_kwargs=kw,
        model_parallel=2, devices_per_host=1, target_hosts=4, min_hosts=1,
        slots_per_host=2, decode_step_s=1.0, step_deadline_s=4.0,
        snapshot_every_s=3.0, materialize=True,
    )
    creqs = [cell.submit(p, max_new_tokens=MAX_NEW) for p in ps]
    clock = SimClock()
    cell.step(clock)                      # formation: place on the mesh
    formed = cell.grid
    check(formed == (2, 2), f"cell formed on grid {formed}, not (2, 2)")
    eng = cell.engine
    with activation_sharding(cell.mesh):
        eng._admit()                      # what the cell's first step runs
        # the same inputs as the one-chip step: a near-tie may pick a
        # different first token on the mesh, which is not a kernel error
        got = step_logits(eng, tokens=ref_state[2])
    check((eng.lengths == ref_state[0]).all()
          and (eng.page_table == ref_state[1]).all(),
          "the cell admitted its requests unlike the one-chip engine")
    parity = compare(got, want, list(range(len(ps))))
    del got, want
    victim = cell.cell_hosts[1]
    plan = FaultPlan([FaultEvent(at=clock.now() + 6.0, kind="crash",
                                 host=victim)])
    summary = cell.run(clock, fault_plan=plan, max_ticks=500)
    streams = [list(r.committed) for r in creqs]

    check(summary["requests_done"] == len(ps),
          f"cell completed {summary['requests_done']}/{len(ps)}: {summary}")
    check(all(len(s) == MAX_NEW for s in streams),
          f"incomplete committed streams: {[len(s) for s in streams]}")
    check(victim not in summary["hosts"] and summary["resharded"] >= 1,
          f"the crash did not re-shard the cell: {summary}")
    check(summary["grid"][0] * summary["grid"][1] < 4,
          f"the mesh did not shrink after the crash: {summary['grid']}")
    check(parity["max_rel_l2"] <= REL_TOL,
          f"mesh vs one-chip logits differ: {parity} (tolerance {REL_TOL})")
    return {
        "grid_formed": list(formed),
        "grid_after_crash": list(summary["grid"]),
        "crashed": victim,
        "resharded": summary["resharded"],
        "resumed_from_snapshot": summary["resumed_from_snapshot"],
        "tokens_replayed": summary["tokens_replayed"],
        "forced_mismatches": summary["forced_mismatches"],
        "parity": parity,
        "streams_equal_to_one_chip": sum(
            s == r for s, r in zip(streams, ref_streams)),
        "tokens": sum(len(s) for s in streams),
        "param_bytes": nbytes(params_host),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the elastic cell on a 2x2 mesh")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    if args.four_chips and len(devices) != 4:
        sys.exit(f"chip_smoke: --four-chips needs 4 chips, found {len(devices)}")
    from bench.harness import CompileClock
    from repro.launch.serve import use_compile_cache

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print("device:", json.dumps(device))
    print("compile cache:", use_compile_cache())
    compiles = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        report = cell_four_chips(args.seed)
    else:
        report = serve_one_chip(args.seed)
        check(report["backend"] == "pallas",
              f"kernel backend {report['backend']!r} on a TPU")
    wall = time.perf_counter() - t0
    for k, v in report.items():
        print(f"{k}: {json.dumps(v)}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use (chip 0): {stats.get('peak_bytes_in_use')} "
          f"of {stats.get('bytes_limit')}")
    print(f"smoke timing, not a benchmark: wall {wall:.1f} s, compile "
          f"{compiles.seconds:.1f} s, persistent-cache hits "
          f"{compiles.cache_hits}, tokens generated {report['tokens']}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
