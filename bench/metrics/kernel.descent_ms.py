"""Device time per run of the rule-search descent program
(``kernels/rule_search.py`` ``rule_search_fused`` on the plain layout,
``rule_search_span`` on the compressed one), in ms: the program's
events on the trace's module line, averaged."""

PATTERN = r"rule_search_(fused|span)"


def read(ctx):
    s = ctx.get("trace")
    if s is None:
        return None
    evs = s.matching(PATTERN, line="XLA Modules")
    if not evs:
        return None
    return sum(e.dur_ns for e in evs) / len(evs) / 1e6
