"""Mean slots per decode step left empty while requests queued, after
the admission scan: page pressure or the scan limit (``engine.stats``)."""
from bench import counters


def read(ctx):
    return counters.ratio(ctx, "blocked_slot_steps", "decode_steps")
