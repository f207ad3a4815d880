"""The scheduler's own host time with the chip idle, in ms per launch:
the idle time under the program's ``serve.step`` annotation less that
under the ``serve.engine.*`` ones nested in it (batch forming, the
cache, dedup, pads, the device-to-host pull of ``serve.fetch``), over
the window's launches.  A program without the annotations reads
nothing."""
STEP = "bench.step/serve.step"
ENGINE = "bench.step/serve.engine."


def read(ctx):
    s = ctx.get("trace")
    launches = ctx["counters"]["launches"]
    if s is None or not launches or STEP not in s.idle_by_host:
        return None
    engine = sum(v for k, v in s.idle_by_host.items()
                 if k.startswith(ENGINE))
    return (s.idle_by_host[STEP] - engine) * 1e3 / launches
