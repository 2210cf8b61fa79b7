"""Operations and bytes of the held experts' grouped matmuls
(``moe_gmm_swiglu``, then ``moe_gmm``) from what the program counted it
gave them: per kind of engine call, decode or prefill chunk, the calls,
the (MoE layer, held expert) pairs some row chose, whose weights the
kernels had to read, and the rows they computed (the paged cache's expert
counters, ``moe_<kind>_{calls,hits,rows}`` in ``engine.stats``; idle
lanes and a chunk's padding are rows too, as the kernels compute them).
Each hit expert's three weights (gate, up, down: ``d f`` each) are read
once, in bfloat16; each row takes ``6 d f`` FLOPs and goes in (``d``),
through the SwiGLU (``f`` out and back in) and out again (``d``)."""

# substrings of the kernels' device op names in the trace
EVENTS = ["moe_gmm"]
CALLS = ("decode", "prefill")


def per_call(stats: dict, kind: str) -> tuple[float, float] | None:
    """Mean (hits, rows) of one call of ``kind`` over the window, or None
    where the program counted no such call."""
    n = stats.get(f"moe_{kind}_calls")
    if not n:
        return None
    return stats[f"moe_{kind}_hits"] / n, stats[f"moe_{kind}_rows"] / n


def work(hits: float, rows: float, m: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of ``hits`` experts read and ``rows`` rows computed;
    ``m`` holds the family dims (d, expert width f)."""
    d, f = m["d"], m["f"]
    return rows * 6.0 * d * f, (hits * 3 * d * f + rows * (2 * d + 2 * f)) * 2
