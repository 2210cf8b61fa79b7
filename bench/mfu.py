"""Shares of the chip's peak that the per-layer readers compute from the
traced window's recorded calls and device times. None where the trace
holds nothing to read, never 0."""

from __future__ import annotations

from bench import trace_reduce


def _ready(ctx) -> bool:
    return ctx["trace"] is not None and ctx["peaks"] is not None


def program_share(ctx, kind: str, programs: list[str]):
    if not _ready(ctx):
        return None
    fam, conf = ctx["family"], ctx["conf"]
    flops = sum(fam.call_flops(conf, c) for c in ctx["calls"] if c[0] == kind)
    secs = trace_reduce.program_seconds(ctx["trace"], programs)
    if not flops or not secs:
        return None
    return 100.0 * flops / secs / ctx["peaks"]["bf16_flops_per_s"]


def window_share(ctx):
    if not _ready(ctx):
        return None
    fam, conf = ctx["family"], ctx["conf"]
    flops = sum(fam.call_flops(conf, c) for c in ctx["calls"])
    if not flops:
        return None
    return (100.0 * flops / ctx["trace"]["window_s"]
            / (ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]))


def roofline_share(ctx, kernel: str):
    """Least time the chip could take for the kernel's recorded work (the
    larger of FLOPs over peak FLOP/s and bytes over peak bandwidth, call
    by call) over the kernel's device time."""
    if not _ready(ctx):
        return None
    rl = ctx["roofline"](kernel)
    m = ctx["family"].dims(ctx["conf"])
    pf = ctx["peaks"]["bf16_flops_per_s"]
    pb = ctx["peaks"]["hbm_bytes_per_s"]
    ideal = 0.0
    for c in ctx["calls"]:
        if c[0] == rl.CALL:
            f, b = rl.work(c, m)
            ideal += max(f / pf, b / pb)
    secs = trace_reduce.kernel_seconds(ctx["trace"], rl.EVENTS)
    if not ideal or not secs:
        return None
    return 100.0 * ideal / secs
