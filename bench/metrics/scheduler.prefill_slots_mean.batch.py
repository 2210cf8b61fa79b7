"""Mean slots per decode step bound to a request whose prefill has not
finished (``engine.stats``)."""
from bench import counters


def read(ctx):
    return counters.ratio(ctx, "prefill_slot_steps", "decode_steps")
