"""Distinct query rows per kernel launch: requests answered ok that
neither hit the cache nor were collapsed into a duplicate, over
launches: ``(serve.ok - cache_hits - dedup_collapsed) / launches``."""


def read(ctx):
    c = ctx["counters"]
    if not c["launches"]:
        return None
    return (c["ok"] - c["cache_hits"] - c["dedup_collapsed"]) / c["launches"]
