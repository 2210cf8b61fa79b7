"""Model FLOPs of every token stepped in the traced window over the
window, as a share of the chips' peak."""
from bench import mfu


def read(ctx):
    return mfu.window_share(ctx)
