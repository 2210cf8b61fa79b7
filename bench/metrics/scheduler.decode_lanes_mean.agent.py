"""Mean active decode lanes over the window's decode steps (the count
``ServeEngine.step()`` returns)."""


def read(ctx):
    lanes = ctx["decode_lanes"]
    return sum(lanes) / len(lanes) if lanes else None
