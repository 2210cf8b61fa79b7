"""Mean milliseconds from ``submit()`` to the request's first prefill
chunk dispatch (admission, for a whole-prompt hit), over the requests
whose prefill began in the window (``engine.stats``)."""
from bench import counters


def read(ctx):
    return counters.ratio(ctx, "queue_wait_ns", "queue_waits", 1e-6)
