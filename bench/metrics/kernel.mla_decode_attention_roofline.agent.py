"""The paged decode kernel's roofline time over the latent pool (latent
attention's absorbed decode) over its device time in the traced window,
in percent."""
from bench import mfu


def read(ctx):
    return mfu.roofline_share(ctx, "mla_decode_attention")
