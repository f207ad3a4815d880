"""Share of the traced window in which no operation ran on the chip, in
%: 1 - (union of device-op intervals) / window."""


def read(ctx):
    s = ctx.get("trace")
    return None if s is None else 100.0 * s.idle_share
