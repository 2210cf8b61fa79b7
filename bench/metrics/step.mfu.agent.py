"""Model FLOPs of this chip's share for every token stepped in the traced
window, over the window, as a share of the chip's peak."""
from bench import mfu


def read(ctx):
    return mfu.window_share(ctx)
