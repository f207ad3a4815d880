"""The open loop's arithmetic and the traffic generator's invariants."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import mixgen, openloop  # noqa: E402


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class Resp:
    def __init__(self, rid, status, op="top_k"):
        self.id, self.status, self.op, self.result = rid, status, op, {}


class FakeServer:
    """Answers every pending request per step, taking ``step_s``; ids in
    ``statuses`` get that status instead of ok."""

    def __init__(self, clock, step_s, statuses=None, full_after=None):
        self.clock, self.step_s = clock, step_s
        self.statuses = statuses or {}
        self.full_after = full_after
        self.q, self.next = [], 0

    class Full(Exception):
        pass

    @property
    def pending(self):
        return len(self.q)

    def submit(self, op, payload, kwargs, deadline_ms, tenant):
        if self.full_after is not None and self.next >= self.full_after:
            self.next += 1
            raise self.Full()
        h = Resp(self.next, "pending")
        self.next += 1
        self.q.append(h)
        return h

    def step(self):
        self.clock.t += self.step_s
        out = [Resp(h.id, self.statuses.get(h.id, "ok")) for h in self.q]
        self.q = []
        return out


def reqs_at(dues, deadline=100.0):
    return [mixgen.Request(d, "top_k", [1], {}, "t0", deadline)
            for d in dues]


def test_latency_runs_from_the_due_time():
    clk = FakeClock()
    srv = FakeServer(clk, step_s=0.010)
    # two requests due together: both wait for one 10 ms step
    rec = openloop.run_window(srv, reqs_at([0.0, 0.0, 0.5]), 1.0,
                              clock=clk, sleep=clk.sleep)
    lat = openloop.latencies_ms(rec)
    assert lat == pytest.approx([10.0, 10.0, 10.0])
    assert np.all(openloop.late_ms(rec) == 0.0)


def test_a_stall_counts_against_later_requests():
    clk = FakeClock()
    srv = FakeServer(clk, step_s=0.300)
    # the second request is due while the first step runs; it is sent
    # late and its latency counts the wait from its due time
    rec = openloop.run_window(srv, reqs_at([0.0, 0.1]), 1.0, clock=clk,
                              sleep=clk.sleep)
    lat = openloop.latencies_ms(rec)
    assert lat == pytest.approx([300.0, 500.0])
    assert openloop.late_ms(rec) == pytest.approx([0.0, 200.0])


def test_misses_count_as_infinite_and_goodput_spans_the_window():
    clk = FakeClock()
    srv = FakeServer(clk, step_s=0.010, statuses={1: "timeout"},
                     full_after=3)
    rec = openloop.run_window(srv, reqs_at([0.0, 0.2, 0.4, 0.6]), 2.0,
                              clock=clk, sleep=clk.sleep,
                              queue_full=FakeServer.Full)
    lat = openloop.latencies_ms(rec)
    assert rec.status == ["ok", "timeout", "ok", "shed"]
    assert np.isinf(lat[1]) and np.isinf(lat[3])
    assert openloop.percentile(lat, 50) == pytest.approx(10.0)
    assert np.isinf(openloop.percentile(lat, 95))
    # two answered within deadline, over the whole 2 s window
    assert openloop.goodput_qps(rec) == pytest.approx(1.0)
    assert openloop.n_failed(rec) == 0


def test_an_answer_after_its_deadline_is_no_goodput():
    clk = FakeClock()
    srv = FakeServer(clk, step_s=0.150)
    rec = openloop.run_window(srv, reqs_at([0.0], deadline=100.0), 1.0,
                              clock=clk, sleep=clk.sleep)
    assert openloop.latencies_ms(rec)[0] == pytest.approx(150.0)
    assert openloop.goodput_qps(rec) == 0.0


def test_percentile_is_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert openloop.percentile(v, 95) == 95.0
    assert openloop.percentile(v, 50) == 50.0


@pytest.mark.parametrize("burst", [None, {"period_s": 5.0, "length_s": 0.5,
                                          "factor": 3.0}])
def test_arrivals_fixed_count_and_gaps_per_seed(burst):
    arr = {"rate_qps": 200.0}
    if burst:
        arr["burst"] = burst
    a = mixgen.arrival_times(arr, 10.0, np.random.default_rng(1))
    b = mixgen.arrival_times(arr, 10.0, np.random.default_rng(2))
    assert a.size == b.size == 2000
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 10.0
    if burst is None:
        assert np.sort(np.diff(a)) == pytest.approx(np.sort(np.diff(b)),
                                                    abs=1e-3)
    else:
        # three times the mean rate inside the bursts (1 s of 10)
        inside = np.mod(a, 5.0) < 0.5
        assert inside.sum() == pytest.approx(3 * 200.0, rel=0.02)


def test_zipf_ranks_are_the_same_multiset_for_every_seed():
    a = mixgen.zipf_ranks(5000, 300, 1.1, np.random.default_rng(1))
    b = mixgen.zipf_ranks(5000, 300, 1.1, np.random.default_rng(9))
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert np.bincount(a)[0] > np.bincount(a)[50]


def _view():
    # a toy store: paths of depth 1..4 over 10 items, canonical order
    paths = np.full((9, 4), -1, np.int32)
    rows = [(), (0,), (1,), (0, 2), (0, 2, 5), (0, 2, 5, 7), (1, 3),
            (1, 3, 4), (1, 3, 4, 9)]
    for i, p in enumerate(rows):
        paths[i, : len(p)] = p
    depth = np.array([len(p) for p in rows])
    return mixgen.StoreView(paths, depth, np.arange(10), 10)


MIX = {
    "zipf_s": 1.1, "tenants": 4, "deadlines_ms": [50, 200, 1000],
    "arrivals": {"rate_qps": 100.0},
    "ops": [
        {"op": "rule_search", "share": 0.5, "pool": 4, "depth": [3, 4],
         "absent_share": 0.25},
        {"op": "top_k", "share": 0.3, "pool": 3, "prefix_len": [1, 2],
         "k": 10, "metrics": ["confidence", "lift"]},
        {"op": "rules_with", "share": 0.2, "pool": 10,
         "role": "antecedent", "k": 10, "metrics": ["lift"]},
    ],
}


def test_generator_is_deterministic_by_seed_with_fixed_work():
    view = _view()
    a, _ = mixgen.generate(MIX, view, 2.0, seed=5)
    b, _ = mixgen.generate(MIX, view, 2.0, seed=5)
    c, _ = mixgen.generate(MIX, view, 2.0, seed=6)
    key = lambda rs: [(r.due_s, r.op, str(r.payload), r.tenant)  # noqa
                      for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    count = lambda rs: sorted(r.op for r in rs)  # noqa: E731
    assert count(a) == count(c) and len(a) == 200
    assert sorted(r.deadline_ms for r in a) == sorted(
        r.deadline_ms for r in c)
    for r in a:
        if r.op == "rule_search":
            ant, con = r.payload
            assert ant and con and not set(ant) & set(con)
        elif r.op == "top_k":
            assert 1 <= len(r.payload) <= 2


def test_a_mix_changes_only_what_it_gives_over_its_base(tmp_path):
    base = dict(MIX, name="base")
    (tmp_path / "base.json").write_text(json.dumps(base))
    (tmp_path / "fast.json").write_text(json.dumps(
        {"base": "base", "name": "fast", "deadlines_ms": [5000],
         "arrivals": {"burst": {"period_s": 5.0, "length_s": 0.5,
                                "factor": 3.0}}}))
    fast = mixgen.load_mix(tmp_path, "fast")
    assert fast["name"] == "fast" and fast["deadlines_ms"] == [5000]
    # a dict is merged one level down: the base's rate stays
    assert fast["arrivals"] == {"rate_qps": 100.0, "burst": {
        "period_s": 5.0, "length_s": 0.5, "factor": 3.0}}
    assert fast["ops"] == MIX["ops"] and "base" not in fast
    assert mixgen.load_mix(tmp_path, "base") == base


def test_a_mix_may_not_be_its_own_base(tmp_path):
    (tmp_path / "a.json").write_text(json.dumps({"base": "b"}))
    (tmp_path / "b.json").write_text(json.dumps({"base": "a"}))
    with pytest.raises(ValueError):
        mixgen.load_mix(tmp_path, "a")


def test_committed_mixes_share_the_recs_parameters():
    traffic = Path(mixgen.__file__).resolve().parents[1] / "traffic"
    recs = mixgen.load_mix(traffic, "recs")
    sat = mixgen.load_mix(traffic, "sat")
    assert sat["ops"] == recs["ops"] and sat["zipf_s"] == recs["zipf_s"]
    assert sat["deadlines_ms"] == [50, 200, 1000]
