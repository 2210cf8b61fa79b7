"""One run of one cell: set-up, the measured window, with ``trace`` the
per-layer readings, and the check of what the window served against the
plain reference.

The window drives ``ServeEngine.step()`` from this loop. Before each step
every request whose due time has passed is submitted (open loop); each
token is timed on the host clock when the step that produced it returns,
which is after the step's token readback. TTFT counts from the due time.
"""

from __future__ import annotations

import gc
import glob
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from bench import catalog, loadgen, peaks as peak_table, trace_reduce

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class CompileClock:
    """Seconds the XLA/TPU compiler runs and programs loaded from the
    persistent cache, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def programs(self) -> int:
        return self.compiles + self.cache_hits


@dataclass
class Served:
    arrival: loadgen.Arrival
    req: object
    due: float
    n: int = 0                   # tokens served so far
    base: int = 0                # ... of which before the window opened
    carried: bool = False        # submitted before the window (the ramp)
    slot: int | None = None
    t_first: float | None = None
    t_last: float | None = None
    t_admit: float | None = None
    gaps: list = field(default_factory=list)


class Probe:
    """Host spans around the engine's two jitted steps, for the traced run
    only (the end-to-end run leaves the engine as it is), and a record of
    each call: a decode's lanes and their context lengths, a prefill
    chunk's slot, offset and valid tokens. Both are read from the call's
    own arguments once ``step()`` has returned, since reading a device
    array inside the step would wait for the device."""

    def __init__(self, engine):
        import jax

        self.engine = engine
        self.TA = jax.profiler.TraceAnnotation
        self.on = False
        self.calls: list[tuple] = []
        self.chunk_slots: list[tuple[float, int]] = []   # this step's chunks
        self._args: list[tuple] = []
        self._readback = None
        decode, chunk = engine._decode_paged, engine._prefill_chunk

        def decode_w(params, cache, batch):
            self._args.append(("decode", batch))
            with self.TA("bench.decode"):
                out = decode(params, cache, batch)
            if self._readback is None:
                self._readback = self.TA("bench.readback")
                self._readback.__enter__()
            return out

        def chunk_w(params, cache, batch, **kw):
            self._args.append(("prefill", batch, kw["offset"],
                               time.perf_counter()))
            with self.TA("bench.prefill_chunk"):
                return chunk(params, cache, batch, **kw)

        engine._decode_paged, engine._prefill_chunk = decode_w, chunk_w

    def step(self):
        with self.TA("bench.step"):
            n = self.engine.step()
            if self._readback is not None:
                self._readback.__exit__(None, None, None)
                self._readback = None
        args, self._args = self._args, []
        self.chunk_slots = []
        for a in args:
            if a[0] == "decode":
                pos = np.asarray(a[1]["positions"])
                table = np.asarray(a[1]["page_table"])
                # a lane whose page-table row is all scratch (page 0) is
                # idle or still prefilling, and computes nothing needed
                lanes = [int(p) + 1 for p, row in zip(pos, table) if row.any()]
                call = ("decode", lanes, len(lanes))
            else:
                _, batch, off, t = a
                slot = int(np.asarray(batch["slot"]))
                self.chunk_slots.append((t, slot))
                call = ("prefill", off, int(np.asarray(batch["valid"])), slot)
            if self.on:
                self.calls.append(call)
        return n


def mark_final(calls: list[tuple]) -> list[tuple]:
    """``("prefill", offset, valid, slot)`` records as ``("prefill",
    offset, valid, final)``: a chunk is its prompt's last unless a later
    chunk of the same slot starts where it ends."""
    out = []
    for i, c in enumerate(calls):
        if c[0] == "prefill":
            _, off, n, slot = c
            more = any(d[0] == "prefill" and d[3] == slot and d[1] == off + n
                       for d in calls[i + 1:])
            c = ("prefill", off, n, not more)
        out.append(c)
    return out


def warm_up(engine, mix: dict, vocab: int) -> None:
    """Compile or load every program the window runs, through the engine's
    public calls: a prompt as long as the mix's longest reaches every
    prefill chunk offset, then decodes and reads back its tokens. Where
    the mix shares prefixes, prompts that share each page-aligned prefix
    of that one start their suffix at every page boundary, and the same
    prompt asked again takes the whole-prompt hit's copy on write."""
    longest = mix["max_prompt"] if "max_prompt" in mix else mix["prompt"]["max"]
    rng = np.random.default_rng(0)
    base = rng.integers(1, vocab, longest).tolist()
    _drain(engine, [base])
    if mix.get("shares_prefixes"):
        P = engine.page_size
        _drain(engine, [base[:k] + rng.integers(1, vocab, longest - k).tolist()
                        for k in range(P, longest, P)] + [base])


def _drain(engine, prompts: list[list[int]]) -> None:
    for p in prompts:
        engine.submit(p, max_new_tokens=2)
    while engine.pending():
        engine.step()


def pct(values, q: float) -> float | None:
    """The ``q``-th percentile (0-100), linear between order statistics."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root=catalog.ROOT, require_chip: bool = True,
        fault=None, keep_trace: str | None = None,
        control: bool = False, mix_override: dict | None = None,
        diag: dict | None = None) -> dict:
    import jax

    cat = catalog.Catalog(root)
    cell = cat.cell(cell_name)
    conf = cat.config(cell["config"])
    mix = cat.traffic(cell["traffic"])
    for path, v in (mix_override or {}).items():   # the knee sweep's rates
        *head, last = path.split(".")
        node = mix
        for k in head:
            node = node[k]
        node[last] = v
    fam = catalog.family(conf["family"])
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            raise SystemExit(f"bench: JAX found no TPU (platform "
                             f"{dev.platform!r}); refusing to measure")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"bench: {cell_name} needs {cell['chips']} chips,"
                             f" found {len(devices)}")
        peaks = peak_table.lookup(dev.device_kind)
    else:
        peaks = peak_table.PEAKS.get(dev.device_kind)
    devices = devices[: cell["chips"]]
    clock = CompileClock()

    # ---- set-up: weights, engine, warm-up (and the batch tier's ramp)
    params = fam.make_params(conf, seed)
    jax.block_until_ready(params)
    engine = fam.build_engine(conf, params)
    from repro.kernels import ops

    backend = ops.current_backend()
    if require_chip and backend != "pallas":
        raise SystemExit(f"bench: kernel backend {backend!r} on a TPU")
    vocab = conf["vocab_size"]
    warm_up(engine, mix, vocab)
    src = loadgen.source(mix, seed, seconds, vocab)
    carried = _ramp(engine, src) if mix.get("ramp") == "turnover" else []
    if fault is not None:
        fault(engine)
    probe = Probe(engine) if trace else None
    step = probe.step if probe else engine.step
    stats0 = dict(engine.stats)
    made0 = _made(engine)
    compile_s = clock.seconds
    programs0 = clock.programs()
    t_open = time.perf_counter()
    setup_s = time.monotonic() - t_start
    say(f"bench: {cell_name} seed {seed}: set-up {setup_s:.3f} s "
        f"(compile {compile_s:.3f} s, {clock.compiles} compiles, "
        f"{clock.cache_hits} cache loads), window {seconds} s, trace {int(trace)}")

    # ---- the window
    live = [Served(a, r, a.due, n=len(r.generated), base=len(r.generated),
                   carried=True, slot=r.slot) for a, r in carried]
    served = list(live)
    late: list[float] = []
    lanes: list[int] = []
    tr = _Tracer(seconds, keep_trace) if trace else None
    while True:
        now = time.perf_counter() - t_open
        if now >= seconds:
            break
        if tr is not None:
            tr.tick(now, engine, probe)
        for a in src.ready(now, len(engine.queue)):
            req = engine.submit(a.prompt, max_new_tokens=a.max_new)
            late.append(now - a.due)
            s = Served(a, req, a.due)
            live.append(s)
            served.append(s)
        if engine.pending():
            n = step()
            t = time.perf_counter() - t_open
            if n:
                lanes.append(n)
            for s in list(live):
                if s.slot is None:
                    s.slot = s.req.slot
                if (probe is not None and s.t_admit is None
                        and s.slot is not None and not s.carried):
                    # the request's first prefill chunk on its slot
                    s.t_admit = next((c - t_open for c, slot
                                      in probe.chunk_slots
                                      if slot == s.slot), None)
                k = len(s.req.generated)
                if k > s.n:
                    if s.t_last is not None:
                        s.gaps.append(t - s.t_last)
                    elif not s.carried:
                        s.t_first = t
                    s.t_last, s.n = t, k
                if s.req.done:
                    live.remove(s)
                    src.finished(s.arrival, list(s.req.generated), t)
        else:
            nxt = src.next_due()
            wait = 0.001 if nxt is None else min(max(nxt - now, 0.0), 0.005)
            time.sleep(wait)
    t_close = time.perf_counter() - t_open
    if tr is not None:
        tr.stop(engine, probe)
        tr.reduce()
    programs_in_window = clock.programs() - programs0
    stats = {k: engine.stats[k] - stats0.get(k, 0) for k in engine.stats}
    say(f"bench: generator lateness: max {max(late, default=0.0) * 1e3:.3f} ms,"
        f" p95 {(pct(late, 95) or 0.0) * 1e3:.3f} ms over {len(late)} submits")
    mem = [d.memory_stats() or {} for d in devices]
    memory_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)

    # ---- end-to-end numbers
    # every token of the window, the carried requests' too; TTFT of the
    # requests due in it
    tokens = sum(s.n - s.base for s in served)
    ttft = [((s.t_first if s.t_first is not None else t_close) - s.due) * 1e3
            for s in served if not s.carried]
    itl = [g * 1e3 for s in served for g in s.gaps]
    e2e = {
        "ttft_p95_ms": pct(ttft, 95),
        "itl_p95_ms": pct(itl, 95),
        "output_tokens_per_s": tokens / t_close,
        "setup_s": setup_s,
    }
    say(f"bench: window {t_close:.3f} s: {len(served)} requests, "
        f"{sum(s.req.done for s in served)} finished, {tokens} tokens, "
        f"ttft p50 {pct(ttft, 50)} ms, itl p50 {pct(itl, 50)} ms, "
        f"memory peak {memory_peak} B")

    if diag is not None:
        diag.update(e2e, tokens=tokens, engine_tokens=_made(engine) - made0,
                    finished=sum(s.req.done for s in served),
                    queued=len(engine.queue), window_s=t_close,
                    ttft_p50_ms=pct(ttft, 50), itl_p50_ms=pct(itl, 50),
                    late_max_ms=max(late, default=0.0) * 1e3)

    # ---- per-layer readings (traced run)
    layer: dict = {}
    extra_device: dict = {}
    breakdown = None
    if trace:
        ctx = {
            "conf": conf, "family": fam, "peaks": peaks, "mix": mix,
            "setup_compile_s": compile_s,
            "programs_in_window": programs_in_window,
            "queue_waits_ms": [(s.t_admit - s.due) * 1e3 for s in served
                               if s.t_admit is not None],
            "decode_lanes": lanes, "stats": stats,
            "trace": tr.reduced, "calls": mark_final(tr.calls),
            "roofline": cat.roofline, "chips": len(devices),
        }
        for m in cat.metrics("per_layer", cell_name):
            v = cat.reader(m["name"])(ctx)
            if v is not None:
                layer[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if tr.reduced is not None:
            extra_device = {"busy_s": tr.reduced["busy_s"],
                            "window_s": tr.reduced["window_s"]}
            breakdown = trace_reduce.breakdown(tr.reduced)

    # ---- the check, once the window has closed and the peak is read
    done = [s for s in served if s.req.done]
    failed = sum(bool(getattr(s.req, "shed", False)) for s in served)
    del engine, probe, step
    gc.collect()
    checks = check(conf, params, done, seed, served_total=len(served),
                   control=control)
    correct = bool(checks["ok"]) and failed == 0

    metrics = {}
    if trace:
        metrics = layer
    else:
        for m in cat.metrics("end_to_end", cell_name):
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak),
              **extra_device}
    out = {"correct": correct, "attempted": len(served), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["control"] = checks["control"]
    out["checks"] = checks["numbers"]
    for name, v in checks["numbers"].items():
        say(f"check {name}: {v['value']} (limit {v['limit']})")
    return out


def _made(engine) -> int:
    """Tokens the engine has produced for all its requests so far."""
    return sum(len(r.generated) for r in engine.requests.values())


def _ramp(engine, src) -> list[tuple]:
    """The batch tier's set-up: keep the backlog full until every slot has
    finished one request, so the window opens on a turned-over batch.
    Returns the requests still queued or in flight, with their arrivals:
    the window serves them on."""
    turned: set[int] = set()
    live = {}
    while len(turned) < engine.n_slots:
        for a in src.ready(0.0, len(engine.queue)):
            r = engine.submit(a.prompt, max_new_tokens=a.max_new)
            live[r.req_id] = (a, r)
        slots = {rid: r.slot for rid, (_, r) in live.items()}
        engine.step()
        for rid, (_, r) in list(live.items()):
            if r.done:
                if slots.get(rid) is not None:
                    turned.add(slots[rid])
                del live[rid]
    return list(live.values())


class _Tracer:
    """A profiler trace of the window's last few seconds (at most 6, at
    most 30% of the window), started between two steps and stopped once
    the window has closed: stopping writes the trace, which takes seconds
    and would stall the window's later requests."""

    def __init__(self, seconds: float, keep: str | None):
        self.t0 = seconds - min(6.0, seconds * 0.3)
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.keep = keep
        self.state = "before"
        self.reduced = None
        self.calls: list = []
        self.span = None

    def tick(self, now, engine, probe) -> None:
        import jax

        if self.state == "before" and now >= self.t0:
            jax.block_until_ready(engine.cache)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # host spans, not every call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            probe.on = True
            self.state = "on"

    def stop(self, engine, probe) -> None:
        import jax

        if self.state != "on":
            return
        jax.block_until_ready(engine.cache)
        probe.on = False
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "stopped"
        self.calls = list(probe.calls)

    def reduce(self) -> None:
        """Read the trace once the window has closed."""
        if self.state != "stopped":
            return
        self.state = "done"
        path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                         recursive=True)
        try:
            recs = trace_reduce.events(path[0])
            win = [r for r in recs if r["name"] == "bench.window"]
            t0 = win[0]["start_ns"]
            t1 = t0 + win[0]["dur_ns"]
            self.reduced = trace_reduce.reduce(recs, t0, t1)
            if self.keep:
                import json

                os.makedirs(self.keep, exist_ok=True)
                with open(os.path.join(self.keep, "events.json"), "w") as f:
                    json.dump({"t0_ns": t0, "t1_ns": t1, "events": recs}, f)
                with open(os.path.join(self.keep, "survey.json"), "w") as f:
                    json.dump(trace_reduce.survey(path[0]), f, indent=1)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def check(conf: dict, params, done: list[Served], seed: int, *,
          served_total: int, control: bool = False) -> dict:
    """Compare a sample of the finished requests with the plain reference:
    the widest gap by which a served token's reference logit lies below
    the reference's best at its position (``pick_sample``). With
    ``control``, also the same gap for the tokens the reference's fp8
    control puts first, judged by the same limit (for setting the limit;
    the benchmark's runs never do this)."""
    limit = conf["check"]["logit_gap_max"]
    sample = pick_sample(done, seed, conf["check"].get("min_tokens", 400))
    worst = ctl = None
    if sample:
        ref = catalog.reference(conf["family"])
        seqs = [(s.arrival.prompt, list(s.req.generated)) for s in sample]
        t0 = time.perf_counter()
        got = ref.logit_gaps(conf, params, seqs, control=control)
        ref_s = time.perf_counter() - t0
        gaps = got["gap"]
        worst = float(max(g.max() for g in gaps))
        if control:
            ctl = float(max(g.max() for g in got["control_gap"]))
        slots = len({s.slot for s in sample} - {None})
        say(f"bench: compared {sum(len(g) for g in gaps)} served tokens of "
            f"{len(sample)} requests from {slots} slots ({served_total} "
            f"served in the window); the reference took {ref_s:.3f} s")
    ok = worst is not None and limit is not None and worst <= limit
    ctl_ok = ctl is not None and limit is not None and ctl <= limit
    return {"ok": ok, "control": {"logit_gap_max": ctl, "correct": ctl_ok},
            "numbers": {"logit_gap_max": {"value": worst, "limit": limit}}}


def pick_sample(done: list[Served], seed: int, min_tokens: int) -> list[Served]:
    """The finished request with the most served tokens, one finished
    request (drawn from the seed) of every other slot that finished one,
    then others in seeded order until ``min_tokens`` served tokens are
    covered: every lane that served is checked, on every seed."""
    if not done:
        return []
    rng = loadgen.rng_for(seed, "sample")
    longest = max(done, key=lambda s: (len(s.req.generated), -s.req.req_id))
    out = [longest]
    by_slot: dict = {}
    for s in done:
        by_slot.setdefault(s.slot, []).append(s)
    for slot in sorted(by_slot, key=lambda k: -1 if k is None else k):
        if slot != longest.slot:
            group = by_slot[slot]
            out.append(group[int(rng.integers(len(group)))])
    picked = {id(s) for s in out}
    rest = [s for s in done if id(s) not in picked]
    total = sum(len(s.req.generated) for s in out)
    for i in rng.permutation(len(rest)):
        if total >= min_tokens:
            break
        out.append(rest[i])
        total += len(rest[i].req.generated)
    return out
