"""Operations and bytes the flash attention kernel needs per prefill
chunk: ``valid`` queries at ``offset..offset+valid`` over the causal
context gathered from the slot's pages (padding rows count nothing)."""

EVENTS = ["flash"]
CALL = "prefill"


def work(call: tuple, m: dict) -> tuple[float, float]:
    _, off, n, _ = call
    L, H, K, dh = m["L"], m["H"], m["K"], m["dh"]
    keys = n * off + n * (n + 1) / 2               # causal (query, key) pairs
    flops = 4.0 * H * dh * keys
    by = (2 * n * H * dh + 2 * (off + n) * K * dh) * 2
    return L * flops, L * by
