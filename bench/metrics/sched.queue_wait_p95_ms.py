"""95th percentile (nearest rank) of the scheduler's queue wait, in ms:
``Response.queue_ms``, from a request's submit to the time its batch
formed, over the requests answered ok.  The scheduler serves one bucket
(op and kwargs) per step, so a request can wait behind steps of other
buckets; a program whose responses carry no ``queue_ms`` reads
nothing."""
import numpy as np

from benchlib import openloop


def read(ctx):
    rec = ctx["records"]
    waits = [getattr(r, "queue_ms", None)
             for r, s in zip(rec.responses, rec.status) if s == "ok"]
    if not waits or any(w is None for w in waits):
        return None
    return openloop.percentile(np.asarray(waits, np.float64), 95)
