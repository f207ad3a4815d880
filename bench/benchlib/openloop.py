"""The open loop: send each request at its due time, step the server,
and time every request from its due time to its answer.

The server is single-threaded (``TrieScheduler.step`` launches one
batch and returns the responses it completed), so the loop alternates:
submit everything that has come due, then step once; with nothing
pending it sleeps until the next due time.  A request that came due
while a step ran is sent when the step returns, and its wait counts in
its latency.  After the window closes nothing more is sent; the loop
steps on until every sent request has its answer (deadlines bound it).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import numpy as np

FAILED = ("failed", "invalid", "raised")   # faults; timeouts and sheds
                                          # are load: they miss limits


@dataclasses.dataclass
class Records:
    due: np.ndarray        # s from the window's start
    sent: np.ndarray       # s; nan when never sent
    done: np.ndarray       # s; nan when never answered
    status: list           # per request: ok / timeout / shed / failed ...
    deadline_ms: np.ndarray
    responses: list        # the server's Response (None when none)
    seconds: float         # the window's length
    wall_s: float = 0.0    # window start to the last answer


def _null(_name):
    return contextlib.nullcontext()


def run_window(server, reqs, seconds: float, *, clock=time.perf_counter,
               sleep=time.sleep, annotate=_null,
               queue_full=(), invalid=()) -> Records:
    """Drive ``server`` (``submit``/``step``/``pending``) with ``reqs``
    (sorted by ``due_s``).  ``queue_full`` and ``invalid`` are the
    exception types ``submit`` raises for a shed and a malformed
    request; ``annotate(name)`` wraps each submit burst, step and wait
    (a trace annotation in traced runs)."""
    n = len(reqs)
    due = np.array([r.due_s for r in reqs], np.float64)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    status = ["unsent"] * n
    responses = [None] * n
    owner = {}
    i = 0
    t0 = clock()
    while True:
        now = clock() - t0
        if i < n and due[i] <= now:
            with annotate("bench.submit"):
                while i < n and due[i] <= now:
                    r = reqs[i]
                    try:
                        h = server.submit(r.op, r.payload, r.kwargs,
                                          deadline_ms=r.deadline_ms,
                                          tenant=r.tenant)
                    except queue_full:
                        status[i] = "shed"
                    except invalid:
                        status[i] = "invalid"
                    else:
                        owner[h.id] = i
                        status[i] = "pending"
                    sent[i] = clock() - t0
                    i += 1
                    now = sent[i - 1]
        if server.pending:
            with annotate("bench.step"):
                out = server.step()
            t = clock() - t0
            for resp in out:
                j = owner.pop(resp.id)
                done[j] = t
                status[j] = resp.status
                responses[j] = resp
            continue
        if i >= n:
            break
        wait = due[i] - (clock() - t0)
        if wait > 0:
            with annotate("bench.wait"):
                sleep(wait)
    wall = clock() - t0
    for j in owner.values():       # never answered: should not happen
        status[j] = "raised"
    return Records(due, sent, done, status,
                   np.array([r.deadline_ms for r in reqs], np.float64),
                   responses, float(seconds), wall)


def latencies_ms(rec: Records) -> np.ndarray:
    """Due time to answer, in ms; ``inf`` for every request that was not
    answered ok (timed out, shed, failed) — it misses every limit."""
    ok = np.array([s == "ok" for s in rec.status])
    lat = (rec.done - rec.due) * 1e3
    return np.where(ok & np.isfinite(lat), lat, np.inf)


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile over all values (``inf`` included)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    k = max(int(math.ceil(q / 100.0 * v.size)) - 1, 0)
    return float(v[k])


def goodput_qps(rec: Records) -> float:
    """Requests answered ok within their deadline (from due time), over
    the whole window."""
    lat = latencies_ms(rec)
    return float(np.count_nonzero(lat <= rec.deadline_ms) / rec.seconds)


def late_ms(rec: Records) -> np.ndarray:
    """How late the generator sent each request, in ms."""
    s = rec.sent - rec.due
    return s[np.isfinite(s)] * 1e3


def n_failed(rec: Records) -> int:
    return sum(s in FAILED for s in rec.status)
