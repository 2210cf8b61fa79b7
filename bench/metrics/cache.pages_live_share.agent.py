"""Pool pages held by live requests, as a share of the pool, over the
window's paged decode steps (``live_page_steps`` over ``decode_steps``
times ``n_pages``), in percent; None where the engine has no such
counter."""


def read(ctx):
    s = ctx["stats"]
    if "live_page_steps" not in s or not s.get("decode_steps"):
        return None
    pages = ctx["conf"]["engine"]["n_pages"]
    return 100.0 * s["live_page_steps"] / (s["decode_steps"] * pages)
