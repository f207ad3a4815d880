"""Share of admitted requests the scheduler answered from its LRU
result cache, in %: ``serve.cache_hits / serve.submitted``."""


def read(ctx):
    c = ctx["counters"]
    return 100.0 * c["cache_hits"] / c["submitted"] if c["submitted"] else None
