"""Model FLOPs of the decode lanes in the traced window over the decode
program's device time (the programs that ran the paged decode kernel),
as a share of the chip's peak."""
from bench import mfu


def read(ctx):
    return mfu.program_share(ctx, "decode", ["paged_decode_attention"])
