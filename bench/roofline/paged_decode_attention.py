"""Operations and bytes the paged decode attention kernel needs per
engine decode call: for every active lane and layer, one query of H heads
against the lane's valid context (not the table's ``max_pages``)."""

# substrings of the kernel's device op name in the trace
EVENTS = ["paged_decode"]
CALL = "decode"


def work(call: tuple, m: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode call over all layers. ``m`` holds the
    family dims: L layers, H query heads, K kv heads, dh head size."""
    L, H, K, dh = m["L"], m["H"], m["K"], m["dh"]
    flops = by = 0.0
    for ctx in call[1]:
        flops += 4.0 * H * dh * ctx                # q.k and p.v
        by += 2 * ctx * K * dh * 2 + 2 * H * dh * 2  # K,V pages; q in, o out
    return L * flops, L * by
