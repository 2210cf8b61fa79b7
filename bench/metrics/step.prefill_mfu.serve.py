"""Model FLOPs of the prefill chunks in the traced window over the
prefill programs' device time (the programs that ran the flash kernel),
as a share of the chip's peak."""
from bench import mfu


def read(ctx):
    return mfu.program_share(ctx, "prefill", ["flash_attention"])
