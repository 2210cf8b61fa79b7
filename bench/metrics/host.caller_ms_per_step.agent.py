"""Milliseconds per decode step spent outside the engine: from one
``step()``'s return to the next one's entry, while requests were pending
(the serving loop, ``submit()``; ``engine.stats``)."""
from bench import counters


def read(ctx):
    return counters.ratio(ctx, "caller_ns", "decode_steps", 1e-6)
