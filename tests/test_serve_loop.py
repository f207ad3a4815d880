"""Fault-path coverage for the resilient serve loop.

Everything runs on a ``VirtualClock`` — deadlines, backoff schedules and
injected latency are deterministic discrete-event time, so every assert
here is bit-reproducible.  The multi-shard degradation lanes re-run
under the multi-device tier (``make test-multidevice``), where the
sharded primary really spans 8 simulated devices.
"""
import math
import random

import numpy as np
import pytest

import jax

from repro.arm.datasets import paper_example_db
from repro.core.array_trie import FrozenTrie
from repro.core.builder import build_trie_of_rules
from repro.distributed.trie_sharding import (
    ShardFailure,
    mask_dead_shards,
    shard_device_trie,
)
from repro.kernels.ops import (
    InvalidQueryError,
    TransientBackendError,
    dedup_query_rows,
    is_retryable,
)
from repro.launch.mesh import make_trie_mesh
from repro.serve import (
    FaultInjector,
    FaultyEngine,
    QueueFull,
    ResilientTrieEngine,
    RetryPolicy,
    ShardHealth,
    TrieQueryEngine,
    TrieScheduler,
    VirtualClock,
    zipfian_workload,
)


def needs_devices(p):
    return pytest.mark.skipif(
        jax.device_count() < p,
        reason=f"needs {p} devices (run under XLA_FLAGS="
               f"--xla_force_host_platform_device_count=8)",
    )


@pytest.fixture(scope="module")
def fz():
    return FrozenTrie.freeze(
        build_trie_of_rules(paper_example_db(), 0.25).trie
    )


@pytest.fixture(scope="module")
def replicated(fz):
    return TrieQueryEngine(fz, mode="replicated")


def make_sched(engine, **kw):
    clock = kw.pop("clock", None) or VirtualClock()
    return TrieScheduler(engine, clock=clock, **kw), clock


# ----------------------------------------------------------------------
# happy path + cache/dedup parity
# ----------------------------------------------------------------------
def test_workload_drains_clean(fz, replicated):
    sched, _ = make_sched(replicated, max_batch=8)
    for w in zipfian_workload(fz, 30, seed=3):
        sched.submit(w["op"], w["payload"], w["kwargs"], tenant=w["tenant"])
    out = sched.drain()
    assert len(out) == 30
    assert all(r.status == "ok" for r in out)
    assert sched.pending == 0
    # zipfian traffic must exercise the whole-query dedup
    assert sched.stats["dedup_collapsed"] > 0
    assert sched.stats["launches"] < 30


def test_cache_hit_bit_parity(fz, replicated):
    sched, _ = make_sched(replicated)
    r1 = sched.submit("top_k", [0], {"k": 4, "metric": "lift"})
    miss = sched.drain()[0]
    assert not miss.cache_hit
    r2 = sched.submit("top_k", [0], {"k": 4, "metric": "lift"})
    hit = sched.drain()[0]
    assert hit.cache_hit and hit.backend == "cache"
    for key in miss.result:
        np.testing.assert_array_equal(miss.result[key], hit.result[key])
    assert sched.stats["cache_hits"] == 1
    assert r1.key == r2.key


def test_batched_responses_match_direct_ops(fz, replicated):
    sched, _ = make_sched(replicated, max_batch=16)
    wl = [w for w in zipfian_workload(fz, 24, seed=5)
          if w["op"] == "rule_search"][:6]
    reqs = [sched.submit(w["op"], w["payload"], w["kwargs"]) for w in wl]
    out = {r.id: r for r in sched.drain()}
    direct = replicated.rule_search_batch(
        [tuple(w["payload"]) for w in wl]
    )
    for i, req in enumerate(reqs):
        got = out[req.id]
        assert got.status == "ok"
        for key in ("found", "node", "support", "confidence", "lift"):
            np.testing.assert_array_equal(
                np.asarray(direct[key])[i], got.result[key],
            )


# ----------------------------------------------------------------------
# backpressure
# ----------------------------------------------------------------------
def test_backpressure_rejects_beyond_max_pending(fz, replicated):
    sched, _ = make_sched(replicated, max_pending=4)
    for i in range(4):
        sched.submit("rules_with", 1, {"k": 4})
    with pytest.raises(QueueFull):
        sched.submit("rules_with", 2, {"k": 4})
    assert sched.stats["shed"] == 1
    # the queue itself is intact and drains
    assert all(r.status == "ok" for r in sched.drain())


def test_backpressure_drop_oldest_policy(fz, replicated):
    sched, _ = make_sched(
        replicated, max_pending=2, shed_policy="drop_oldest",
    )
    first = sched.submit("rules_with", 1, {"k": 4})
    sched.submit("rules_with", 2, {"k": 4})
    sched.submit("rules_with", 3, {"k": 4})   # evicts `first`
    shed = sched.responses[first.id]
    assert shed.status == "shed"
    assert sched.pending == 2
    assert all(r.status == "ok" for r in sched.drain())


# ----------------------------------------------------------------------
# deadlines
# ----------------------------------------------------------------------
def test_deadline_expiry_returns_timeout_not_hang(fz, replicated):
    sched, clock = make_sched(replicated)
    r = sched.submit("rules_with", 1, {"k": 4}, deadline_ms=50.0)
    clock.advance(0.2)                     # 200ms > 50ms budget
    out = sched.drain()
    assert sched.responses[r.id].status == "timeout"
    assert sched.responses[r.id].result is None
    assert [o.id for o in out] == [r.id]


def test_shaper_refuses_deadline_busting_bucket(fz, replicated):
    sched, clock = make_sched(replicated)
    # teach the predictor this bucket (2 unique rows) costs 100ms
    sched.predictor.observe(("rules_with", ("any", 4, "lift", 1)), 2, 0.1)
    tight = sched.submit(
        "rules_with", 1, {"k": 4, "metric": "lift"}, deadline_ms=10.0
    )
    roomy = sched.submit(
        "rules_with", 2, {"k": 4, "metric": "lift"}, deadline_ms=1e4
    )
    sched.drain()
    # the 10ms request can never survive a 100ms launch: Timeout NOW,
    # and it must not have blocked its batchmate
    assert sched.responses[tight.id].status == "timeout"
    assert sched.responses[roomy.id].status == "ok"


# ----------------------------------------------------------------------
# retry/backoff determinism
# ----------------------------------------------------------------------
def test_retry_schedule_deterministic_under_seeded_clock(fz, replicated):
    def run(seed):
        clock = VirtualClock()
        inj = FaultInjector().fail_transient(1).fail_transient(2)
        eng = ResilientTrieEngine(
            FaultyEngine(replicated, inj, clock=clock)
        )
        sched = TrieScheduler(
            eng, clock=clock, seed=seed,
            retry_policy=RetryPolicy(max_retries=3, base_ms=10.0),
        )
        sched.submit("rules_with", 1, {"k": 4})
        out = sched.drain()
        return out[0], clock.now()

    r1, t1 = run(seed=7)
    r2, t2 = run(seed=7)
    assert r1.status == r2.status == "ok"
    assert r1.retries == r2.retries == 2
    assert t1 == t2                       # same virtual backoff timeline
    # and the timeline is exactly the policy's seeded schedule
    expect = RetryPolicy(max_retries=3, base_ms=10.0).schedule_ms(
        random.Random(7)
    )
    assert t1 == pytest.approx(sum(expect[:2]) / 1e3)
    _, t3 = run(seed=8)
    assert t3 != t1                       # jitter really is seed-driven


def test_retry_exhaustion_fails_request(fz, replicated):
    clock = VirtualClock()
    inj = FaultInjector()
    for n in range(1, 6):
        inj.fail_transient(n)
    eng = ResilientTrieEngine(FaultyEngine(replicated, inj, clock=clock))
    sched = TrieScheduler(
        eng, clock=clock,
        retry_policy=RetryPolicy(max_retries=2, base_ms=1.0),
    )
    r = sched.submit("rules_with", 1, {"k": 4})
    sched.drain()
    assert sched.responses[r.id].status == "failed"
    assert "transient" in sched.responses[r.id].error


def test_error_taxonomy_classification():
    assert is_retryable(TransientBackendError("x"))
    assert not is_retryable(InvalidQueryError("x"))
    assert not is_retryable(ShardFailure(0))
    assert is_retryable(RuntimeError("RESOURCE_EXHAUSTED: pool"))
    assert not is_retryable(RuntimeError("segfault"))


# what the TPU compiler raises for a kernel over its VMEM budget (the
# first, verbatim from a v5e compile of an oversized rank tile) and for a
# program over the device's memory: RESOURCE_EXHAUSTED, but permanent
COMPILE_REFUSALS = (
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space vmem. Used 175.87M of 128.00M vmem. "
    "Exceeded vmem capacity by 47.87M.",
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space hbm. Used 20.5G of 15.75G hbm.",
)


@pytest.mark.parametrize("msg", COMPILE_REFUSALS)
def test_compile_refusal_surfaces_at_once(fz, msg):
    assert not is_retryable(RuntimeError(msg))
    eng = TrieQueryEngine(fz, mode="replicated")
    calls = []

    def refuse(*args, **kwargs):
        calls.append(1)
        raise RuntimeError(msg)

    eng.rules_with = refuse
    sched = TrieScheduler(
        eng, clock=VirtualClock(),
        retry_policy=RetryPolicy(max_retries=3, base_ms=1.0),
    )
    r = sched.submit("rules_with", 1, {"k": 4})
    sched.drain()
    resp = sched.responses[r.id]
    assert resp.status == "failed" and resp.retries == 0
    assert len(calls) == 1


# ----------------------------------------------------------------------
# poison-query isolation
# ----------------------------------------------------------------------
def test_poison_query_does_not_fail_batchmates(fz, replicated):
    clock = VirtualClock()
    inj = FaultInjector().poison_payload(
        lambda p: 1 in np.asarray(p).ravel().tolist(), times=10,
    )
    eng = ResilientTrieEngine(FaultyEngine(replicated, inj, clock=clock))
    sched = TrieScheduler(eng, clock=clock, max_batch=8)
    poisoned = sched.submit("rules_with", 1, {"k": 4})
    clean = sched.submit("rules_with", 2, {"k": 4})
    sched.drain()
    assert sched.responses[poisoned.id].status == "invalid"
    assert sched.responses[clean.id].status == "ok"


# ----------------------------------------------------------------------
# shard failure: failover + degradation
# ----------------------------------------------------------------------
def test_shard_failure_fails_over_bit_correct_in_flight(fz, replicated):
    """A killed shard mid-launch: every in-flight request completes with
    answers bit-identical to the replicated engine's."""
    primary = TrieQueryEngine(fz, mode="sharded")   # P=1 mesh off-CI
    clock = VirtualClock()
    inj = FaultInjector().fail_nth_launch(1, shard=0)
    res = ResilientTrieEngine(FaultyEngine(primary, inj, clock=clock))
    sched = TrieScheduler(res, clock=clock, max_batch=8)
    wl = zipfian_workload(fz, 12, seed=11)
    reqs = [sched.submit(w["op"], w["payload"], w["kwargs"]) for w in wl]
    out = sched.drain()
    # zero dropped in-flight requests
    assert len(out) == len(reqs)
    assert all(r.status == "ok" for r in out)
    assert not any(r.degraded for r in out)
    assert res.backend == "replicated"
    assert res.failovers == 1
    assert res.health.dead == {0}
    # bit-parity against the replicated oracle for every response
    for w, req in zip(wl, reqs):
        got = sched.responses[req.id]
        if w["op"] == "rule_search":
            oracle = replicated.rule_search_batch([tuple(w["payload"])])
        elif w["op"] == "top_k":
            oracle = replicated.top_k_rules_batch(
                [w["payload"]], w["kwargs"]["k"],
                metric=w["kwargs"]["metric"],
            )
        else:
            oracle = replicated.rules_with(
                [w["payload"]], **w["kwargs"]
            )
        for key, v in oracle.items():
            np.testing.assert_array_equal(
                np.asarray(v)[0], got.result[key]
            )


@needs_devices(2)
def test_degraded_mode_flags_and_filters(fz, replicated):
    """With replicated fallback disallowed, a killed shard demotes to a
    masked plan: responses carry ``degraded=True`` and ranked answers
    are exactly the full answers filtered of the dead shard's range."""
    primary = TrieQueryEngine(
        fz, mesh=make_trie_mesh(2), mode="sharded"
    )
    clock = VirtualClock()
    inj = FaultInjector().fail_nth_launch(1, shard=1)
    res = ResilientTrieEngine(
        FaultyEngine(primary, inj, clock=clock),
        allow_replicated_fallback=False,
    )
    sched = TrieScheduler(res, clock=clock)
    k = 8
    req = sched.submit("top_k", [], {"k": k})
    out = sched.drain()
    assert len(out) == 1 and out[0].status == "ok"
    assert out[0].degraded and out[0].backend == "degraded"
    # filtered-oracle: degraded live rules == full rules minus the dead
    # shard's DFS range, in the same rank order
    lo, hi = primary.plan.ranges[1]
    full = replicated.top_k_rules_batch([[]], k * 2)
    dfs = np.asarray(fz.dfs_order)
    full_nodes = [
        n for n in np.asarray(full["node"])[0]
        if n >= 0 and not lo <= dfs[n] < hi
    ]
    got_nodes = [n for n in out[0].result["node"] if n >= 0]
    assert got_nodes == full_nodes[: len(got_nodes)]
    # degraded results never enter the cache
    assert sched.cache_len == 0


@needs_devices(2)
def test_mask_dead_shards_validation(fz):
    plan = shard_device_trie(fz, make_trie_mesh(2))
    with pytest.raises(ValueError, match="out of range"):
        mask_dead_shards(plan, [9])
    with pytest.raises(ValueError, match="all"):
        mask_dead_shards(plan, [0, 1])
    assert mask_dead_shards(plan, []) is plan


def test_shard_health_straggler_demotion():
    """The shared StragglerDetector EWMA (``distributed.health``, the
    training-side implementation reused verbatim): after a clean
    baseline, sustained per-shard latency flags the shard slow and
    (with ``demote_slow``) kills it."""
    health = ShardHealth(2, demote_slow=True)
    health.record_launch(0, 0.0)
    health.record_launch(1, 0.0)          # baseline EWMA for both shards
    for _ in range(4):
        health.record_launch(0, 0.0)
        health.record_launch(1, 0.25)     # sustained straggle on shard 1
    assert 1 in health.slow
    assert health.dead == {1}
    assert not health.healthy
    assert health.dead_shards() == (1,)
    assert 0 not in health.slow


def test_faulty_engine_feeds_straggler_probe(fz, replicated):
    """Slow-shard injection charges the virtual clock AND trains the
    per-shard health probe through ``FaultyEngine``."""
    clock = VirtualClock()
    health = ShardHealth(1)
    inj = FaultInjector()
    eng = FaultyEngine(replicated, inj, clock=clock, health=health)
    eng.rules_with([1], k=4)              # clean baseline launch
    inj.slow_shard(0, 0.25)
    for _ in range(4):
        eng.rules_with([1], k=4)
    assert 0 in health.slow
    assert clock.now() == pytest.approx(4 * 0.25)  # latency charged


# ----------------------------------------------------------------------
# satellite: rule_search_batch dedup bit-parity at high duplication
# ----------------------------------------------------------------------
def test_rule_search_batch_dedup_bit_parity(fz):
    wl = [w for w in zipfian_workload(fz, 200, seed=13, s=1.6)
          if w["op"] == "rule_search"]
    pairs = [tuple(map(tuple, w["payload"])) for w in wl]
    uniq = sorted(set(pairs))
    assert len(pairs) >= 40
    assert len(uniq) < len(pairs) // 2            # heavy duplication
    from repro.kernels import ops

    batched = ops.rule_search_batch(fz, pairs)
    # oracle: one launch per UNIQUE pair (no cross-row dedup possible),
    # then every duplicate row must scatter back bit-identically
    oracle = {
        pair: {
            key: np.asarray(v)[0]
            for key, v in ops.rule_search_batch(fz, [pair]).items()
        }
        for pair in uniq
    }
    for i, pair in enumerate(pairs):
        for key in ("found", "node", "support", "confidence", "lift"):
            np.testing.assert_array_equal(
                oracle[pair][key], np.asarray(batched[key])[i],
                err_msg=f"row {i} key {key}",
            )


def test_dedup_query_rows_roundtrip():
    rng = np.random.RandomState(3)
    base = rng.randint(0, 5, size=(4, 3)).astype(np.int32)
    al = rng.randint(1, 3, size=(4,)).astype(np.int32)
    picks = rng.randint(0, 4, size=(64,))
    q, a = base[picks], al[picks]
    uq, ual, inv = dedup_query_rows(q, a)
    assert inv is not None
    assert uq.shape[0] & (uq.shape[0] - 1) == 0     # pow2 padded
    np.testing.assert_array_equal(uq[inv], q)
    np.testing.assert_array_equal(ual[inv], a)


# ----------------------------------------------------------------------
# satellite: typed validation per op
# ----------------------------------------------------------------------
class TestValidation:
    def test_unknown_metric(self, fz):
        from repro.kernels import ops

        with pytest.raises(InvalidQueryError, match="nope"):
            ops.top_k_rules(fz, k=2, metric="nope")
        with pytest.raises(InvalidQueryError, match="nope"):
            ops.top_k_rules_batch(fz, [[0]], k=2, metric="nope")
        with pytest.raises(InvalidQueryError, match="nope"):
            ops.rules_with(fz, [0], k=2, metric="nope")

    def test_bad_k(self, fz):
        from repro.kernels import ops

        for bad in (0, -3, 2.5, True):
            with pytest.raises(InvalidQueryError, match=repr(bad)):
                ops.top_k_rules(fz, k=bad)
            with pytest.raises(InvalidQueryError, match=repr(bad)):
                ops.rules_with(fz, [0], k=bad)

    def test_none_entries_named_in_error(self, fz):
        from repro.kernels import ops

        with pytest.raises(InvalidQueryError, match="None"):
            ops.rules_with(fz, [1, None], k=2)
        with pytest.raises(InvalidQueryError, match="None"):
            ops.top_k_rules_batch(fz, [[1, None]], k=2)
        with pytest.raises(InvalidQueryError, match="None"):
            ops.rule_search_batch(fz, [(None, [1])])

    def test_malformed_pair(self, fz):
        from repro.kernels import ops

        with pytest.raises(InvalidQueryError, match="pair"):
            ops.rule_search_batch(fz, [(1, 2, 3)])

    def test_strict_rejects_out_of_vocab(self, fz, replicated):
        from repro.kernels import ops

        n_items = int(np.asarray(fz.item_offsets).shape[0]) - 1
        with pytest.raises(InvalidQueryError, match=str(n_items + 17)):
            ops.rules_with(fz, [n_items + 17], k=2, strict=True)
        # lenient default: absent item answers empty, unchanged contract
        out = ops.rules_with(fz, [n_items + 17], k=2)
        assert not (np.asarray(out["node"]) >= 0).any()

    def test_scheduler_admission_rejects_invalid(self, fz, replicated):
        sched, _ = make_sched(replicated)
        with pytest.raises(InvalidQueryError):
            sched.submit("rules_with", None, {"k": 4})
        with pytest.raises(InvalidQueryError):
            sched.submit("top_k", [None], {"k": 4})
        with pytest.raises(InvalidQueryError):
            sched.submit("bogus_op", 1, {})
        assert sched.stats["invalid"] == 3
        assert sched.pending == 0          # nothing poisoned the queue
