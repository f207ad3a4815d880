"""The ops layer's host time with the chip idle, in ms per launch: the
idle time under the program's ``ops.<op>.<phase>`` annotations
(``kernels/ops.py``: host preparation and uploads, ``top_k``'s eager
prefix descent, the dispatch, the gathers after it), which never nest
in one another, over the window's launches.  A program without the
annotations reads nothing."""
PREFIX = "bench.step/ops."


def read(ctx):
    s = ctx.get("trace")
    launches = ctx["counters"]["launches"]
    if s is None or not launches:
        return None
    idle = [v for k, v in s.idle_by_host.items() if k.startswith(PREFIX)]
    return sum(idle) * 1e3 / launches if idle else None
