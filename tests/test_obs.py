"""Observability layer: metrics correctness, span-tree shape, exporter
round-trips, and the disabled-mode no-op contract.

The replay-driven cases run the real serve loop on a ``VirtualClock``
(deterministic discrete-event time), so every span-duration assertion
here is exact — the tracer reads the scheduler's own clock.
"""
import json
import math

import numpy as np
import pytest

from repro.arm.datasets import paper_example_db
from repro.core.array_trie import FrozenTrie
from repro.core.builder import build_trie_of_rules
from repro.obs import (
    NULL_INSTRUMENT,
    NULL_SPAN,
    Histogram,
    MetricsRegistry,
    Observability,
    Tracer,
    merge_snapshots,
    metrics_text,
    quantile_from_snapshot,
    spans_to_trace_events,
    write_trace,
)
from repro.serve import (
    STAT_KEYS,
    FaultInjector,
    FaultyEngine,
    ResilientTrieEngine,
    TrieQueryEngine,
    TrieScheduler,
    VirtualClock,
    zipfian_workload,
)


@pytest.fixture(scope="module")
def fz():
    return FrozenTrie.freeze(
        build_trie_of_rules(paper_example_db(), 0.25).trie
    )


@pytest.fixture(scope="module")
def replicated(fz):
    return TrieQueryEngine(fz, mode="replicated")


def traced_sched(engine, **kw):
    engine.obs = None            # module-scoped engine: rebind per test
    obs = Observability(tracing=True)
    clock = VirtualClock()
    sched = TrieScheduler(engine, clock=clock, obs=obs, **kw)
    return sched, obs, clock


# ----------------------------------------------------------------------
# histograms vs exact oracles
# ----------------------------------------------------------------------
def test_histogram_quantiles_vs_numpy_oracle():
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=2.0, sigma=1.5, size=4000)
    h = Histogram("lat")
    for v in samples:
        h.observe(v)
    s = np.sort(samples)
    g = h.growth
    for q in (0.01, 0.10, 0.25, 0.50, 0.75, 0.90, 0.99):
        est = h.quantile(q)
        # the histogram's own definition of the q-quantile: smallest
        # order statistic with cumulative count >= q*n.  Estimate and
        # oracle share a bucket, so the ratio is bounded by one growth.
        exact = s[max(math.ceil(q * len(s)) - 1, 0)]
        assert exact / g <= est <= exact * g, (q, est, exact)
        # numpy's interpolated percentile uses a slightly different rank
        # convention; two buckets of slack absorbs it
        ref = float(np.percentile(samples, q * 100))
        assert ref / g**2 <= est <= ref * g**2, (q, est, ref)
    assert h.quantile(0.0) == pytest.approx(s[0], rel=1e-12)
    assert h.quantile(1.0) == pytest.approx(s[-1], rel=1e-12)
    assert h.mean == pytest.approx(float(samples.mean()))


def test_histogram_underflow_negative_nan():
    h = Histogram("x", lo=1.0)
    for v in (0.25, 0.75, -1.0, float("nan")):
        h.observe(v)
    assert h.count == 2                    # negative + NaN ignored
    assert h.counts[0] == 2                # both land in [0, lo)
    assert 0.25 <= h.quantile(0.5) <= 0.75


def test_histogram_snapshot_merge_matches_union():
    rng = np.random.default_rng(11)
    a, b = rng.exponential(5.0, 500), rng.exponential(50.0, 500)
    ha, hb, hu = Histogram("m"), Histogram("m"), Histogram("m")
    for v in a:
        ha.observe(v)
    for v in b:
        hb.observe(v)
    for v in np.concatenate([a, b]):
        hu.observe(v)
    ha.merge_snapshot(hb.snapshot())
    assert ha.count == hu.count
    assert ha.total == pytest.approx(hu.total)
    for q in (0.1, 0.5, 0.9, 0.99):
        assert ha.quantile(q) == pytest.approx(hu.quantile(q))
    with pytest.raises(ValueError):
        ha.merge_snapshot(Histogram("m", lo=1.0).snapshot())


def test_registry_labels_and_snapshot_merge():
    m = MetricsRegistry()
    m.counter("req", tenant="a").inc(3)
    m.counter("req", tenant="b").inc()
    # label order never splits an instrument
    assert m.counter("x", a="1", b="2") is m.counter("x", b="2", a="1")
    assert m.value("req", tenant="a") == 3
    assert m.label_values("req", "tenant") == ["a", "b"]
    m.histogram("lat", tenant="a").observe(10.0)
    m2 = MetricsRegistry()
    m2.counter("req", tenant="a").inc(4)
    m2.histogram("lat", tenant="a").observe(1000.0)
    merged = merge_snapshots([m.snapshot(), m2.snapshot()])
    assert merged["counters"]['req{tenant="a"}'] == 7
    hs = merged["histograms"]['lat{tenant="a"}']
    assert hs["count"] == 2
    assert quantile_from_snapshot(hs, 1.0) == pytest.approx(1000.0)
    text = metrics_text(merged)
    assert 'req{tenant="a"} 7' in text.splitlines()


# ----------------------------------------------------------------------
# disabled-mode no-op contract
# ----------------------------------------------------------------------
def test_disabled_registry_and_tracer_are_noops():
    m = MetricsRegistry(enabled=False)
    assert m.counter("a") is NULL_INSTRUMENT
    assert m.gauge("b") is NULL_INSTRUMENT
    assert m.histogram("c") is NULL_INSTRUMENT
    m.counter("a").inc()
    m.histogram("c").observe(5.0)
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    tr = Tracer(enabled=False)
    sp = tr.start("root")
    assert sp is NULL_SPAN
    with tr.span("scoped", parent=sp) as inner:
        assert inner is NULL_SPAN
        inner.attrs["x"] = 1           # vanishes by design
    tr.end(sp, status="ok")
    assert tr.spans == [] and inner.attrs == {}


def test_disabled_scheduler_records_nothing(fz, replicated):
    replicated.obs = None
    obs = Observability(metrics=MetricsRegistry(enabled=False),
                        tracer=Tracer(enabled=False))
    sched = TrieScheduler(replicated, clock=VirtualClock(), obs=obs)
    for w in zipfian_workload(fz, 10, seed=5):
        sched.submit(w["op"], w["payload"], w["kwargs"], tenant=w["tenant"])
    out = sched.drain()
    assert all(r.status == "ok" for r in out)
    assert obs.tracer.spans == []
    assert obs.metrics.snapshot()["counters"] == {}
    # the stats property still answers (all-zero null counters)
    assert set(sched.stats) == set(STAT_KEYS)


def test_stats_preseeded_on_fresh_scheduler(fz, replicated):
    replicated.obs = None
    sched = TrieScheduler(replicated, clock=VirtualClock())
    assert sched.stats == {k: 0 for k in STAT_KEYS}
    assert {"inserted", "refreezes"} <= set(sched.stats)


# ----------------------------------------------------------------------
# span tree under a deterministic replay
# ----------------------------------------------------------------------
def test_span_tree_well_formed_under_replay(fz, replicated):
    sched, obs, clock = traced_sched(replicated, max_batch=8)
    wl = zipfian_workload(fz, 24, seed=9)
    for w in wl:
        sched.submit(w["op"], w["payload"], w["kwargs"], tenant=w["tenant"])
    out = sched.drain()
    assert all(r.status == "ok" for r in out)
    spans = obs.tracer.spans
    assert obs.tracer.finished() == spans          # nothing left open
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        assert s.parent_id == -1 or s.parent_id in by_id
        assert s.duration_s >= 0
        if s.parent_id in by_id:                   # nested in the parent
            p = by_id[s.parent_id]
            assert p.start_s - 1e-9 <= s.start_s
            assert s.end_s <= p.end_s + 1e-9
    roots = [s for s in spans if s.name == "request"]
    assert len(roots) == len(wl)
    stages = {"admit", "queue", "serve", "respond"}
    for root in roots:
        kids = [s for s in spans if s.parent_id == root.span_id]
        assert {k.name for k in kids} <= stages
        # stage spans are contiguous: child durations sum to the root,
        # which matches the reported end-to-end latency (VirtualClock
        # time on both sides, so only float add-order slack)
        child_sum = sum(k.duration_s for k in kids)
        assert child_sum == pytest.approx(root.duration_s, abs=1e-9)
        lat = sched.responses[root.attrs["req"]].latency_ms
        assert root.duration_s * 1e3 == pytest.approx(lat, abs=1e-6)


def test_trace_capacity_drops_instead_of_growing(fz, replicated):
    replicated.obs = None
    obs = Observability(tracer=Tracer(enabled=True, capacity=5))
    sched = TrieScheduler(replicated, clock=VirtualClock(), obs=obs)
    for w in zipfian_workload(fz, 10, seed=5):
        sched.submit(w["op"], w["payload"], w["kwargs"])
    out = sched.drain()
    assert all(r.status == "ok" for r in out)      # behavior unaffected
    assert len(obs.tracer.spans) == 5
    assert obs.tracer.dropped > 0


# ----------------------------------------------------------------------
# exporter
# ----------------------------------------------------------------------
def test_perfetto_export_round_trip(fz, replicated, tmp_path):
    sched, obs, _ = traced_sched(replicated, max_batch=8)
    for w in zipfian_workload(fz, 16, seed=4):
        sched.submit(w["op"], w["payload"], w["kwargs"], tenant=w["tenant"])
    sched.drain()
    path = tmp_path / "trace.json"
    write_trace(str(path), obs.tracer.finished())
    doc = json.loads(path.read_text())             # valid JSON on disk
    assert doc["displayTimeUnit"] == "ms"
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(events) == len(obs.tracer.finished())
    assert all(
        a["ts"] <= b["ts"] for a, b in zip(events, events[1:])
    )
    assert all(e["dur"] >= 0 for e in events)
    # every span's payload survives: ids + attrs in args
    ids = {e["args"]["span_id"] for e in events}
    assert len(ids) == len(events)
    assert {e["args"]["parent_id"] for e in events} <= ids | {-1}
    # request-owned spans ride request tracks, step machinery tid 1
    req_tids = {e["tid"] for e in events if e["name"] == "request"}
    assert req_tids and 1 not in req_tids
    assert {e["tid"] for e in events if e["name"] == "step"} == {1}
    assert any(m["name"] == "process_name" for m in meta)


def test_export_skips_open_spans():
    tr = Tracer(enabled=True)
    done = tr.start("done", parent=False)
    tr.end(done)
    tr.start("open", parent=False)                 # never ended
    doc = spans_to_trace_events(tr.spans)
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert names == ["done"]


# ----------------------------------------------------------------------
# failover counters + span (shard-kill regression sequence)
# ----------------------------------------------------------------------
def test_shard_kill_emits_counter_sequence_and_failover_span(fz):
    primary = TrieQueryEngine(fz, mode="sharded")
    clock = VirtualClock()
    inj = FaultInjector().fail_nth_launch(1, shard=0)
    res = ResilientTrieEngine(FaultyEngine(primary, inj, clock=clock))
    obs = Observability(tracing=True)
    sched = TrieScheduler(res, clock=clock, obs=obs, max_batch=8)
    wl = zipfian_workload(fz, 12, seed=11)
    for w in wl:
        sched.submit(w["op"], w["payload"], w["kwargs"])
    out = sched.drain()
    assert all(r.status == "ok" for r in out)
    assert res.failovers == 1
    # ordered health events and their counter mirror agree
    assert res.health.events == [
        {"kind": "failure", "shard": 0},
        {"kind": "dead", "shard": 0},
    ]
    m = obs.metrics
    assert m.value("serve.shard_events", kind="failure", shard=0) == 1
    assert m.value("serve.shard_events", kind="dead", shard=0) == 1
    assert m.value(
        "serve.failover", labels={"from": "sharded", "to": "replicated"}
    ) == 1
    # the failover span annotates the transition and nests in a launch
    fspans = [s for s in obs.tracer.finished() if s.name == "failover"]
    assert len(fspans) == 1
    assert fspans[0].attrs["from"] == "sharded"
    assert fspans[0].attrs["to"] == "replicated"
    by_id = {s.span_id: s for s in obs.tracer.spans}
    anc = fspans[0]
    seen = set()
    while anc.parent_id in by_id and anc.span_id not in seen:
        seen.add(anc.span_id)
        anc = by_id[anc.parent_id]
        if anc.name == "launch":
            break
    assert anc.name == "launch"


# ----------------------------------------------------------------------
# per-tenant labels
# ----------------------------------------------------------------------
def test_per_tenant_labels_cover_workload(fz, replicated):
    sched, obs, _ = traced_sched(replicated, max_batch=8)
    wl = zipfian_workload(fz, 20, seed=3)
    for w in wl:
        sched.submit(w["op"], w["payload"], w["kwargs"], tenant=w["tenant"])
    out = sched.drain()
    m = obs.metrics
    tenants = sorted({w["tenant"] for w in wl})
    assert m.label_values("serve.admitted", "tenant") == tenants
    assert m.label_values("serve.latency_ms", "tenant") == tenants
    admitted = sum(
        c.value for c in m.counters_named("serve.admitted")
    )
    assert admitted == len(wl)
    observed = sum(
        h.count for h in m.histograms_named("serve.latency_ms")
    )
    assert observed == len(out)
    by_status = sum(
        c.value for c in m.counters_named("serve.requests")
    )
    assert by_status == len(out)


# ----------------------------------------------------------------------
# spans in the jax.profiler trace: serve.* over ops.*, on one thread
# ----------------------------------------------------------------------
ENGINE_OP = {"rule_search": "rule_search_batch", "top_k": "top_k_rules_batch",
             "rules_with": "rules_with"}


@pytest.fixture(scope="module", params=[True, False],
                ids=["tracing", "no_tracing"])
def profiled_steps(request, fz, replicated, tmp_path_factory):
    """Annotations of the loop thread while a small scheduler serves one
    batch of each op under ``jax.profiler``, with the in-memory tracer
    on or off: ``[(name, start_ns, end_ns)]`` sorted by start."""
    import jax
    from jax.profiler import ProfileData

    replicated.obs = None
    sched = TrieScheduler(replicated, clock=VirtualClock(),
                          obs=Observability(tracing=request.param))
    wl = zipfian_workload(fz, 30, seed=3)
    for w in wl:                              # compile outside the trace
        sched.submit(w["op"], w["payload"], w["kwargs"])
    sched.drain()
    sched = TrieScheduler(replicated, clock=VirtualClock(), cache_size=0,
                          obs=Observability(tracing=request.param))
    for w in wl:
        sched.submit(w["op"], w["payload"], w["kwargs"])
    out_dir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(out_dir))
    try:
        out = sched.drain()
    finally:
        jax.profiler.stop_trace()
    assert all(r.ok for r in out)
    pb = sorted(out_dir.rglob("*.xplane.pb"))[-1]
    lines = []
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events
                   if e.name.startswith(("serve.", "ops."))]
            if evs:
                lines.append(sorted(evs, key=lambda e: e[1]))
    assert len(lines) == 1, "annotations on more than one thread"
    return lines[0]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _parents(ev, events, prefix):
    return [o for o in events if o is not ev and o[0].startswith(prefix)
            and _inside(ev, o)]


def test_profiler_trace_nests_serve_and_ops_spans(profiled_steps):
    evs = profiled_steps
    names = {e[0] for e in evs}
    assert {"serve.step", "serve.batch_form", "serve.dedup_cache",
            "serve.launch", "serve.merge", "serve.fetch"} <= names
    for op, eop in ENGINE_OP.items():
        assert f"serve.engine.{eop}" in names
        assert {f"ops.{op}.prep", f"ops.{op}.kernel"} <= names
    assert "ops.top_k.prefix_ranges" in names
    # serve.step ⊃ serve.launch ⊃ serve.engine.* ⊃ ops.*
    for ev in evs:
        if ev[0] == "serve.launch":
            assert _parents(ev, evs, "serve.step")
        elif ev[0].startswith("serve.engine."):
            assert _parents(ev, evs, "serve.launch")
        elif ev[0].startswith("ops."):
            eng = _parents(ev, evs, "serve.engine.")
            assert len(eng) == 1
            assert _parents(eng[0], evs, "serve.launch")
        elif ev[0] == "serve.fetch":
            assert _parents(ev, evs, "serve.merge")


def test_ops_annotations_never_nest(profiled_steps):
    ops_evs = [e for e in profiled_steps if e[0].startswith("ops.")]
    assert ops_evs
    for ev in ops_evs:
        assert not _parents(ev, ops_evs, "ops.")
    # each op's phases run in order: prep and descent before its kernel,
    # expand after it
    order = {"prep": 0, "prefix_ranges": 1, "kernel": 2, "expand": 3}
    for eng in (e for e in profiled_steps
                if e[0].startswith("serve.engine.")):
        phases = [order[e[0].rsplit(".", 1)[1]] for e in ops_evs
                  if _inside(e, eng)]
        assert phases == sorted(phases)


def test_tracer_span_annotates_when_disabled(tmp_path):
    import jax
    from jax.profiler import ProfileData

    tr = Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("outer", op="x"):
            with tr.span("inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert tr.spans == []                          # list stays empty
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    names = [e.name for p in ProfileData.from_file(str(pb)).planes
             for ln in p.lines for e in ln.events]
    assert names.count("serve.outer") == 1
    assert names.count("serve.inner") == 1


# ----------------------------------------------------------------------
# queue wait: submit to the time the request's batch formed
# ----------------------------------------------------------------------
def test_queue_ms_is_submit_to_batch_formed(fz, replicated):
    replicated.obs = None
    clock = VirtualClock()
    sched = TrieScheduler(replicated, clock=clock)
    h = sched.submit("rules_with", 0, {"k": 3})
    clock.advance(0.005)
    (r,) = sched.step()
    assert r.ok and not r.cache_hit
    assert r.queue_ms == pytest.approx(5.0, abs=1e-9)
    # a cache hit is batched too, and gets its own wait
    h2 = sched.submit("rules_with", 0, {"k": 3})
    clock.advance(0.002)
    (r2,) = sched.step()
    assert r2.ok and r2.cache_hit and h2.id != h.id
    assert r2.queue_ms == pytest.approx(2.0, abs=1e-9)


def test_queue_ms_waits_behind_other_buckets(fz, replicated):
    """One bucket per step: a request of another op waits a step."""
    replicated.obs = None
    clock = VirtualClock()
    sched = TrieScheduler(replicated, clock=clock, cache_size=0)
    sched.submit("rules_with", 0, {"k": 3})
    sched.submit("top_k", [], {"k": 3})
    clock.advance(0.001)
    (first,) = sched.step()
    clock.advance(0.004)
    (second,) = sched.step()
    assert first.op == "rules_with" and second.op == "top_k"
    assert first.queue_ms == pytest.approx(1.0, abs=1e-9)
    assert second.queue_ms == pytest.approx(5.0, abs=1e-9)
    assert second.latency_ms == pytest.approx(5.0, abs=1e-9)


def test_queue_ms_nan_when_never_batched(fz, replicated):
    replicated.obs = None
    clock = VirtualClock()
    sched = TrieScheduler(replicated, clock=clock, max_pending=1,
                          shed_policy="drop_oldest")
    sched.submit("rules_with", 0, {"k": 3}, deadline_ms=1.0)
    sched.submit("rules_with", 1, {"k": 3}, deadline_ms=1.0)   # sheds
    clock.advance(0.002)                                        # expires
    out = sched.step()
    statuses = sorted(r.status for r in sched.responses.values())
    assert statuses == ["shed", "timeout"] and len(out) == 1
    assert all(math.isnan(r.queue_ms) for r in sched.responses.values())
    # nothing was batched, so the histogram saw nothing
    m = sched.obs.metrics
    assert sum(h.count for h in m.histograms_named(
        "serve.queue_wait_ms")) == 0


def test_queue_wait_histogram_counts_each_batched_request_once(
        fz, replicated):
    replicated.obs = None
    clock = VirtualClock()
    sched = TrieScheduler(replicated, clock=clock, max_batch=4)
    wl = zipfian_workload(fz, 40, seed=11)
    for w in wl:
        sched.submit(w["op"], w["payload"], w["kwargs"],
                     deadline_ms=math.inf)
        clock.advance(0.0005)
    out = sched.drain()
    assert len(out) == len(wl) and all(r.ok for r in out)
    m = sched.obs.metrics
    by_op = {}
    for h in m.histograms_named("serve.queue_wait_ms"):
        by_op[dict(h.labels)["op"]] = h
    for op in {w["op"] for w in wl}:
        want = sorted(r.queue_ms for r in out if r.op == op)
        h = by_op[op]
        assert h.count == len(want)
        assert h.total == pytest.approx(sum(want))
        assert h.max == pytest.approx(max(want))
    assert sum(h.count for h in by_op.values()) == len(wl)
