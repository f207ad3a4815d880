"""The basket generator and the plain reference, at tiny sizes."""
import importlib.util
import itertools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib.ruletable import RuleTable  # noqa: E402


def load(rel, name):
    spec = importlib.util.spec_from_file_location(name, BENCH / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


baskets_store = load("stores/baskets.py", "t_store_baskets")
baskets_ref = load("refs/baskets.py", "t_ref_baskets")


def tiny(name, **over):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(over)
    return cfg


def test_generators_are_deterministic_by_seed():
    rcfg = tiny("retail", n_baskets=500, n_items=120)
    x = baskets_store.generate_baskets(rcfg, 4)
    y = baskets_store.generate_baskets(rcfg, 4)
    z = baskets_store.generate_baskets(rcfg, 5)
    assert all(np.array_equal(u, v) for u, v in zip(x, y))
    assert not np.array_equal(x[1], z[1])


def test_retail_store_holds_the_published_count():
    # the configuration's baskets, mined by the plain reference at the
    # configuration's minsup, hold the source's count of frequent
    # sequences to within 5%
    cfg = tiny("retail")
    indptr, items = baskets_store.generate_baskets(cfg, cfg["data_seed"])
    assert indptr.size - 1 == cfg["n_baskets"]
    assert items.max() < cfg["n_items"]
    t = baskets_ref.reference({
        "indptr": indptr, "items": items, "n_items": cfg["n_items"],
        "minsup": cfg["minsup"], "max_len": cfg["max_len"]})
    n_seq = t.parent.size - 1
    assert abs(n_seq / cfg["published_sequences"] - 1) < 0.05, n_seq


def test_baskets_are_sorted_and_distinct():
    indptr, items = baskets_store.generate_baskets(
        tiny("retail", n_baskets=800, n_items=150), 9)
    assert indptr.size == 801 and np.all(np.diff(indptr) >= 1)
    for b in np.split(items, indptr[1:-1]):
        assert np.all(np.diff(b) > 0)
    assert items.min() >= 0 and items.max() < 150


def test_reference_miner_matches_brute_force():
    rng = np.random.default_rng(0)
    baskets = [sorted(rng.choice(8, size=rng.integers(1, 5),
                                 replace=False).tolist())
               for _ in range(60)]
    indptr = np.concatenate([[0], np.cumsum([len(b) for b in baskets])])
    data = {"indptr": indptr, "items": np.concatenate(baskets),
            "n_items": 8, "minsup": 0.1, "max_len": 3}
    t = baskets_ref.reference(data)
    want = {}
    for size in (1, 2, 3):
        for s in itertools.combinations(range(8), size):
            c = sum(set(s) <= set(b) for b in baskets)
            if c >= 0.1 * 60:
                want[frozenset(s)] = c
    got = {frozenset(t.path(r)): t.count[r] for r in range(1, t.parent.size)}
    assert got == want
    # canonical order: rank ascends along each path
    for r in range(1, t.parent.size):
        ranks = [t.rank[i] for i in t.path(r)]
        assert ranks == sorted(ranks)


def test_rule_table_answers():
    # root, a(0), b(1), a/b, a/b/c(2); counts over n = 100
    t = RuleTable([-1, 0, 0, 1, 3], [-1, 0, 1, 1, 2],
                  [100, 60, 50, 40, 20], 100, np.arange(3), 3)
    r = t.rule([0], [1])
    assert r["found"] and r["confidence"] == pytest.approx(40 / 60)
    assert r["lift"] == pytest.approx((40 / 60) / 0.5)
    # the antecedent's items must precede the consequent's: b -> a is
    # the row (b, a), no path of the table
    assert t.rule([1], [0])["found"] is False
    r = t.rule([0], [1, 2])      # compound: conf over the consequent
    assert r["confidence"] == pytest.approx(20 / 60)
    assert r["lift"] == 0.0      # the path b/c is not in the table
    assert sorted(t.subtree([0]).tolist()) == [1, 3, 4]
    assert sorted(t.holding(1, "antecedent").tolist()) == [4]
    assert sorted(t.holding(1, "consequent").tolist()) == [2, 3]
    rows, vals = t.top(t.subtree([0]), "support", 2)
    assert rows.tolist() == [1, 3] and vals[0] == pytest.approx(0.6)
