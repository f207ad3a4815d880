"""The readers of the program's own spans: ``sched.host_ms`` and
``ops.host_ms`` from ``serve.*`` and ``ops.*`` annotations nested in a
``bench.step`` of a small recorded trace, and
``sched.queue_wait_p95_ms`` from the responses' ``queue_ms``."""
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import harness, openloop, tracereduce  # noqa: E402
from benchlib.tracereduce import Event  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1_000_000


def ev(plane, line, name, start_ms, dur_ms):
    return Event(plane, line, name, int(start_ms * MS), int(dur_ms * MS))


def host(name, start_ms, end_ms):
    return ev(HOST, "main", name, start_ms, end_ms - start_ms)


def recorded():
    """One 100 ms step of a ``top_k`` launch; the chip is busy 42-80 ms
    (the kernel, while the host expands and fetches), idle otherwise."""
    return [
        host("bench.step", 0, 100),
        host("serve.step", 1, 99),
        host("serve.batch_form", 2, 5),
        host("serve.dedup_cache", 5, 8),
        host("serve.launch", 10, 70),
        host("serve.engine.top_k_rules_batch", 12, 60),
        host("ops.top_k.prep", 13, 20),
        host("DevicePut", 15, 17),
        host("ops.top_k.prefix_ranges", 20, 40),
        host("PjitFunction(add)", 22, 25),
        host("ops.top_k.kernel", 40, 45),
        host("ops.top_k.expand", 45, 50),
        host("serve.merge", 70, 95),
        host("serve.fetch", 71, 90),
        ev(DEV, "XLA Ops", "%rank.1 = f32[8] custom-call()", 42, 38),
        ev(DEV, "XLA Modules", "jit_rank(3)", 42, 38),
    ]


def ctx_of(events, launches=2):
    return {"trace": tracereduce.summarize(events),
            "counters": {"launches": launches}}


def read(metric, ctx):
    return harness.metric_reader(metric).read(ctx)


def test_spans_reach_the_idle_breakdown():
    s = tracereduce.summarize(recorded())
    idle = s.idle_by_host
    # idle: [0, 42) and [80, 100); under serve.step [1, 42) + [80, 99)
    assert idle["bench.step/serve.step"] == pytest.approx(0.060)
    assert idle["bench.step/serve.engine.top_k_rules_batch"] == \
        pytest.approx(0.030)
    assert idle["bench.step/ops.top_k.prefix_ranges"] == pytest.approx(0.020)
    assert idle["bench.step/ops.top_k.kernel"] == pytest.approx(0.002)
    assert "bench.step/ops.top_k.expand" not in idle      # chip busy
    assert idle["bench.step/serve.fetch"] == pytest.approx(0.010)
    gaps = [k for k, _ in s.breakdown()["idle_gaps"]]
    assert any(k.startswith("bench.step/ops.") for k in gaps)
    assert any(k.startswith("bench.step/serve.") for k in gaps)


@pytest.mark.parametrize("launches, sched_ms, ops_ms", [
    (1, 30.0, 29.0),
    (2, 15.0, 14.5),
])
def test_host_ms_per_launch(launches, sched_ms, ops_ms):
    ctx = ctx_of(recorded(), launches)
    # step idle 60 ms less the engine's 30 ms
    assert read("sched.host_ms", ctx) == pytest.approx(sched_ms)
    # prep 7 + prefix_ranges 20 + kernel 2 (expand ran on a busy chip)
    assert read("ops.host_ms", ctx) == pytest.approx(ops_ms)


def test_host_ms_reads_nothing_without_spans_or_launches():
    bare = [e for e in recorded()
            if not e.name.startswith(("serve.", "ops."))]
    for metric in ("sched.host_ms", "ops.host_ms"):
        assert read(metric, ctx_of(bare)) is None
        assert read(metric, ctx_of(recorded(), launches=0)) is None
        assert read(metric, {"trace": None,
                             "counters": {"launches": 3}}) is None


def records(responses, status):
    n = len(status)
    return openloop.Records(
        due=np.zeros(n), sent=np.zeros(n), done=np.ones(n),
        status=status, deadline_ms=np.full(n, 1e3), responses=responses,
        seconds=1.0)


def test_queue_wait_p95_over_ok_responses():
    waits = [3.0, 120.0, 7.5, 41.0, 0.25, 88.0, 12.0, 64.0]
    responses = [SimpleNamespace(queue_ms=w) for w in waits]
    status = ["ok"] * len(waits)
    # a timeout that never reached a batch, one that did, and a shed
    responses += [SimpleNamespace(queue_ms=math.nan),
                  SimpleNamespace(queue_ms=500.0), None]
    status += ["timeout", "timeout", "shed"]
    got = read("sched.queue_wait_p95_ms", {"records": records(responses,
                                                              status)})
    assert got == openloop.percentile(np.array(waits), 95) == 120.0


def test_queue_wait_reads_nothing_without_queue_ms():
    responses = [SimpleNamespace(latency_ms=5.0), None]
    rec = records(responses, ["ok", "shed"])
    assert read("sched.queue_wait_p95_ms", {"records": rec}) is None
    rec = records([None], ["shed"])
    assert read("sched.queue_wait_p95_ms", {"records": rec}) is None
