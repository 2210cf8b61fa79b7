"""95th percentile of due time -> the request's first prefill chunk
dispatched, host clock, over the requests that got one in the window."""
import numpy as np


def read(ctx):
    w = ctx["queue_waits_ms"]
    return float(np.percentile(w, 95)) if w else None
