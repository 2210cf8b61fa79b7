"""100 * (1 - busy / window) of the traced window: busy is the union of
the intervals in which an operation ran on the device."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else 100.0 * (1.0 - t["busy_s"] / t["window_s"])
