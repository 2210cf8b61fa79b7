"""Reduce a profiler trace to the numbers the per-layer metrics read.

``events(path)`` flattens an ``.xplane.pb`` into plain records;
``reduce(records, t0_ns, t1_ns)`` works on those records only, so the
tests check it on a small recorded trace. Inside the traced window
``[t0_ns, t1_ns]`` it gives:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device (averaged over the devices), and ``window_s``;
- ``programs``: device seconds per jitted program (``XLA Modules``),
  each labelled by its name and the custom-call kernels that ran inside
  it (``jit__unknown[flash_attention,rmsnorm]``), since jitting a partial
  leaves every program of the engine named ``jit__unknown``;
- ``ops``: device seconds per operation (``XLA Ops``), numbered suffixes
  merged (``fusion.12`` -> ``fusion``), control flow (``while``,
  ``conditional``, ``call``) left out because its body's ops are counted;
- ``idle``: idle device seconds per host span (``bench.*``) that the gap
  falls in, the innermost span at the gap's middle.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


CONTROL_FLOW = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """The short name of a device op from its event name, which on a TPU
    is the op's HLO text: ``%paged_decode_attention.9 = bf16[..] ...`` ->
    ``paged_decode_attention``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def events(path: str) -> list[dict]:
    """Records of the device ops and modules and of the benchmark's host
    spans, with ``start_ns``/``dur_ns`` on the trace's common clock. Ops
    carry their short name and whether they are a custom call (a kernel)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not dev and not e.name.startswith(SPAN_PREFIX):
                    continue
                rec = {"plane": plane.name, "line": line.name, "name": e.name,
                       "start_ns": float(e.start_ns),
                       "dur_ns": float(e.duration_ns)}
                if dev and line.name == OPS_LINE:
                    rec["name"] = op_name(e.name)
                    rec["kernel"] = " custom-call(" in e.name
                elif dev:
                    rec["name"] = base_name(e.name)
                out.append(rec)
    return out


def survey(path: str, per_line: int = 5) -> list[dict]:
    """Every plane and line of a trace with its event count and a few
    events with their stats: what to read before matching names."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(evs),
                        "sample": [{"name": e.name, "start_ns": e.start_ns,
                                    "dur_ns": e.duration_ns,
                                    "stats": {k: str(v) for k, v in
                                              dict(e.stats).items()}}
                                   for e in evs[:per_line]]})
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``; a module's ``jit_f(3)`` -> ``jit_f``."""
    return re.sub(r"(\.\d+)+$|\(\d+\)$", "", name)


def reduce(records: list[dict], t0_ns: float, t1_ns: float) -> dict:
    window = max(t1_ns - t0_ns, 1.0)

    def clip(r):
        s = max(r["start_ns"], t0_ns)
        e = min(r["start_ns"] + r["dur_ns"], t1_ns)
        return (s, e) if e > s else None

    per_dev: dict[str, list] = defaultdict(list)
    ops: dict[str, float] = defaultdict(float)
    kernels: dict[str, list] = defaultdict(list)     # plane -> (start, name)
    modules = []
    spans = []
    for r in records:
        iv = clip(r)
        if iv is None:
            continue
        if r["plane"].startswith(DEVICE_PREFIX):
            if r["line"] == OPS_LINE:
                per_dev[r["plane"]].append(iv)
                if r["name"] not in CONTROL_FLOW:
                    ops[r["name"]] += (iv[1] - iv[0]) * 1e-9
                if r.get("kernel"):
                    kernels[r["plane"]].append((iv[0], r["name"]))
            else:
                modules.append((r["plane"], iv, r["name"]))
        else:
            spans.append((iv[0], iv[1], r["name"]))
    programs: dict[str, float] = defaultdict(float)
    for plane, ks in kernels.items():
        ks.sort()
    for plane, (s, e), name in modules:
        ks = kernels.get(plane, [])
        inside = {n for t, n in ks[bisect.bisect_left(ks, (s, "")):
                                  bisect.bisect_left(ks, (e, ""))]}
        programs[f"{name}[{','.join(sorted(inside))}]"] += (e - s) * 1e-9
    busy_by_dev = {d: _union(iv) for d, iv in per_dev.items()}
    n_dev = max(len(busy_by_dev), 1)
    busy = sum(e - s for u in busy_by_dev.values() for s, e in u) / n_dev
    # idle gaps, on the first device, attributed to the innermost host span
    idle: dict[str, float] = defaultdict(float)
    gaps = []
    if busy_by_dev:
        u = busy_by_dev[sorted(busy_by_dev)[0]]
        edges = [t0_ns] + [x for iv in u for x in iv] + [t1_ns]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            inside = [sp for sp in spans if sp[0] <= mid <= sp[1]]
            name = (min(inside, key=lambda sp: sp[1] - sp[0])[2]
                    if inside else "no host span")
            idle[name] += (e - s) * 1e-9
            gaps.append(((e - s) * 1e-9, name))
    return {
        "window_s": window * 1e-9,
        "busy_s": busy * 1e-9,
        "devices": n_dev,
        "ops": dict(ops),
        "programs": dict(programs),
        "idle": dict(idle),
        "longest_gaps": sorted(gaps, reverse=True)[:10],
    }


def kernel_seconds(reduced: dict, names: list[str]) -> float:
    """Device seconds of the ops whose name contains one of ``names``."""
    return sum(s for op, s in reduced["ops"].items()
               if any(n in op for n in names))


def program_seconds(reduced: dict, names: list[str]) -> float:
    return sum(s for p, s in reduced["programs"].items()
               if any(n in p for n in names))


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(reduced["idle"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
