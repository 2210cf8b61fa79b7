"""The held experts' grouped matmuls' roofline time over their device
time in the traced window, in percent. Each traced call of a kind counts
that kind's mean experts read and rows per call over the window, from the
program's own counters (``roofline/moe_gmm.py``), so the bytes follow the
routing the weights and traffic gave; decode steps and prefill chunks are
summed against the same traced kernel time. None where the program
counted none of a kind the trace ran."""
from bench import trace_reduce


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    rl = ctx["roofline"]("moe_gmm")
    m = ctx["family"].dims(ctx["conf"])
    pf = ctx["peaks"]["bf16_flops_per_s"]
    pb = ctx["peaks"]["hbm_bytes_per_s"]
    ideal = 0.0
    for kind in rl.CALLS:
        n = sum(1 for c in ctx["calls"] if c[0] == kind)
        if not n:
            continue
        mean = rl.per_call(ctx["stats"], kind)
        if mean is None:
            return None
        f, b = rl.work(*mean, m)
        ideal += n * max(f / pf, b / pb)
    secs = trace_reduce.kernel_seconds(ctx["trace"], rl.EVENTS)
    if not ideal or not secs:
        return None
    return 100.0 * ideal / secs
