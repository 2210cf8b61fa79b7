"""Per-layer readings from the engine's own counters (``engine.stats``,
their change over the measured window, ``ctx["stats"]``)."""


def ratio(ctx, num: str, den: str, scale: float = 1.0):
    """``scale * stats[num] / stats[den]``, or None where the denominator
    is 0 or the program has no such counter."""
    s = ctx["stats"]
    return scale * s[num] / s[den] if s.get(den) else None
