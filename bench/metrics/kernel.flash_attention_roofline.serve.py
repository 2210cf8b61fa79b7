"""The prefill flash attention kernel's roofline time over its device
time in the traced window, in percent."""
from bench import mfu


def read(ctx):
    return mfu.roofline_share(ctx, "flash_attention")
