"""Milliseconds per decode step in which the engine had nothing on the
chip: from a blocking readback to its next prefill chunk or decode
dispatch, while requests were pending (``engine.stats``)."""
from bench import counters


def read(ctx):
    return counters.ratio(ctx, "host_gap_ns", "decode_steps", 1e-6)
