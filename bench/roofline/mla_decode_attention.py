"""Operations and bytes latent-attention decode needs per engine decode
call (the paged decode kernel over the latent pool, one kv head): for
every active lane and layer, H absorbed queries against the lane's valid
context, scores over the ``latent`` (c and k_pe) dims and values over the
``r`` dims of c. Each cache row is counted once, ``latent`` bfloat16
values, though the kernel reads the pool as keys and as values; q in and
the output out per lane."""

# substrings of the kernel's device op name in the trace
EVENTS = ["paged_decode"]
CALL = "decode"


def work(call: tuple, m: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode call over all layers. ``m`` holds the
    family dims: L layers, H heads, ``latent`` row width, ``r`` latent
    rank."""
    L, H, lat, r = m["L"], m["H"], m["latent"], m["r"]
    flops = by = 0.0
    for ctx in call[1]:
        flops += H * ctx * (2.0 * lat + 2.0 * r)
        by += ctx * lat * 2 + 2 * H * lat * 2
    return L * flops, L * by
