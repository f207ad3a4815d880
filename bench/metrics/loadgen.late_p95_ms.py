"""95th percentile of how late the load generator sent each request
(send time minus due time), in ms: a starved generator, not a slow
server, when this is high."""
from benchlib import openloop


def read(ctx):
    late = openloop.late_ms(ctx["records"])
    return openloop.percentile(late, 95) if late.size else None
