"""Share of prompt tokens served from shared pages over the window:
100 * shared / (computed + shared), from the engine's counters."""


def read(ctx):
    s = ctx["stats"]
    total = s["prefill_tokens"] + s["prefill_tokens_shared"]
    return 100.0 * s["prefill_tokens_shared"] / total if total else None
