"""Programs compiled or loaded from the persistent cache inside the
window (jax.monitoring); a warm-up that covers the traffic leaves 0."""


def read(ctx):
    return ctx["programs_in_window"]
