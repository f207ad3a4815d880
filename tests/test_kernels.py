"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Every Pallas kernel is swept over shapes (incl. non-tile-multiple sizes,
which exercise the padding paths) and dtypes, and asserted allclose against
``ref.py``.

Random-trie builders and mined fixtures come from ``tests/conftest.py``.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.array_trie import csr_offsets_from_edges
from repro.kernels.ref import (
    rule_search_fused_ref,
    rule_search_ref,
    support_count_ref,
    trie_reduce_ref,
)
from repro.kernels.support_count import support_count_pallas
from repro.kernels.rule_search import (
    rule_search_fused,
    rule_search_pallas,
)
from repro.kernels.trie_reduce import trie_reduce_pallas
from repro.kernels.ops import (
    dense_from_bitmaps,
    members_from_candidates,
    rule_search,
    support_count,
)


# ----------------------------------------------------------------------
# support_count
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "t,i,c", [(8, 5, 3), (100, 40, 17), (256, 128, 128), (301, 169, 200),
              (1024, 333, 65)]
)
@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.bfloat16, jnp.float32])
def test_support_count_sweep(t, i, c, dtype):
    rng = np.random.RandomState(t * 1000 + i + c)
    tx = (rng.rand(t, i) < 0.2).astype(np.float32)
    member = np.zeros((c, i), np.float32)
    lengths = np.zeros((c,), np.int32)
    for row in range(c):
        k = rng.randint(1, min(5, i) + 1)
        items = rng.choice(i, size=k, replace=False)
        member[row, items] = 1.0
        lengths[row] = k

    out = support_count_pallas(
        jnp.asarray(tx, dtype), jnp.asarray(member, dtype),
        jnp.asarray(lengths), interpret=True,
    )
    ref = support_count_ref(
        jnp.asarray(tx), jnp.asarray(member), jnp.asarray(lengths)
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # and against brute-force truth
    truth = np.array(
        [
            int(((tx @ member[j]) == lengths[j]).sum())
            for j in range(c)
        ],
        np.int32,
    )
    np.testing.assert_array_equal(np.asarray(out), truth)


def test_support_count_padding_rows_ignored():
    tx = jnp.ones((4, 3), jnp.float32)
    member = jnp.zeros((2, 3), jnp.float32)
    lengths = jnp.array([-1, -1], jnp.int32)  # padding sentinel rows
    out = support_count_pallas(tx, member, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), [0, 0])


def test_members_from_candidates():
    cands = jnp.array([[0, 2, -1], [1, -1, -1]], jnp.int32)
    m = members_from_candidates(cands, 4)
    np.testing.assert_array_equal(
        np.asarray(m), [[1, 0, 1, 0], [0, 1, 0, 0]]
    )


def test_dense_from_bitmaps_roundtrip():
    from repro.arm.transactions import TransactionDB

    rng = np.random.RandomState(7)
    txs = [
        set(rng.choice(20, size=rng.randint(1, 8), replace=False))
        for _ in range(67)
    ]
    db = TransactionDB(txs, n_items=20)
    dense = dense_from_bitmaps(db.item_bitmaps)
    assert dense.shape[1] == 20
    for tid, t in enumerate(txs):
        row = set(np.nonzero(dense[tid])[0].tolist())
        assert row == set(t)


def test_support_count_op_equals_db():
    from repro.arm.datasets import paper_example_db

    db = paper_example_db()
    cands, lens = db.candidate_matrix(
        [(5, 2), (5, 2, 0), (1,), (0, 12)], 3
    )
    out = support_count(cands, lens, item_bitmaps=db.item_bitmaps)
    truth = [db.itemset_count(tuple(c[c >= 0])) for c in np.asarray(cands)]
    np.testing.assert_array_equal(np.asarray(out), truth)


# ----------------------------------------------------------------------
# rule_search
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "n_nodes,n_items,q,width",
    [(5, 4, 3, 2), (50, 12, 40, 5), (200, 30, 129, 7), (512, 64, 256, 4)],
)
def test_rule_search_sweep(n_nodes, n_items, q, width, random_trie):
    rng = np.random.RandomState(n_nodes + q)
    arrs = random_trie(rng, n_nodes, n_items, max_children=4)
    queries = rng.randint(-1, n_items, size=(q, width)).astype(np.int32)
    ant_len = rng.randint(0, width + 1, size=(q,)).astype(np.int32)

    args = [
        jnp.asarray(arrs[k])
        for k in (
            "edge_parent", "edge_item", "edge_child",
            "edge_conf", "edge_sup", "edge_lift",
        )
    ]
    out = rule_search_pallas(
        *args, jnp.asarray(queries), jnp.asarray(ant_len), interpret=True
    )
    ref = rule_search_ref(*args, jnp.asarray(queries), jnp.asarray(ant_len))
    np.testing.assert_array_equal(
        np.asarray(out["found"]), np.asarray(ref["found"])
    )
    np.testing.assert_array_equal(
        np.asarray(out["node"]), np.asarray(ref["node"])
    )
    for k in ("support", "confidence", "node_lift"):
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(ref[k]), rtol=1e-6
        )


@pytest.mark.parametrize(
    "n_nodes,n_items,q,width",
    [(5, 4, 3, 2), (50, 12, 40, 5), (200, 30, 129, 7), (512, 64, 256, 4)],
)
def test_rule_search_fused_sweep(n_nodes, n_items, q, width, random_trie):
    """CSR bucket descent ≡ layout-agnostic full-table reference (incl.
    the compound lift it assembles)."""
    rng = np.random.RandomState(n_nodes + q)
    arrs = random_trie(rng, n_nodes, n_items, max_children=4)
    queries = rng.randint(-1, n_items, size=(q, width)).astype(np.int32)
    ant_len = rng.randint(0, width + 1, size=(q,)).astype(np.int32)
    offsets, max_fanout = csr_offsets_from_edges(
        arrs["edge_parent"], n_nodes
    )

    args = [
        jnp.asarray(arrs[k])
        for k in (
            "edge_item", "edge_child",
            "edge_conf", "edge_sup", "edge_lift",
        )
    ]
    out = rule_search_fused(
        jnp.asarray(offsets), *args,
        jnp.asarray(queries), jnp.asarray(ant_len),
        max_fanout=max_fanout,
    )
    ref = rule_search_fused_ref(
        jnp.asarray(arrs["edge_parent"]), *args,
        jnp.asarray(queries), jnp.asarray(ant_len),
    )
    np.testing.assert_array_equal(
        np.asarray(out["found"]), np.asarray(ref["found"])
    )
    np.testing.assert_array_equal(
        np.asarray(out["node"]), np.asarray(ref["node"])
    )
    for k in ("support", "confidence", "lift"):
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(ref[k]), rtol=1e-6
        )


def test_rule_search_walks_real_trie(paper_db, mined, frozen):
    """End-to-end: kernel answers == pointer trie answers on real data."""
    from repro.core.builder import build_flat_table

    res = mined(0.3)
    _, rules, _ = build_flat_table(paper_db, res.itemsets)
    fz = frozen(0.3)
    q, al = fz.canonicalize_queries(
        [r.antecedent for r in rules], [r.consequent for r in rules]
    )
    out = rule_search(fz, q, al)
    for i, r in enumerate(rules):
        assert bool(out["found"][i])
        np.testing.assert_allclose(
            float(out["support"][i]), r.metrics.support, rtol=1e-5
        )
        np.testing.assert_allclose(
            float(out["confidence"][i]), r.metrics.confidence, rtol=1e-5
        )
        np.testing.assert_allclose(
            float(out["lift"][i]), r.metrics.lift, rtol=1e-4
        )


# ----------------------------------------------------------------------
# trie_reduce
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 7, 100, 8192, 8193, 20000])
def test_trie_reduce_sweep(n):
    rng = np.random.RandomState(n)
    sup = rng.rand(n).astype(np.float32)
    conf = rng.rand(n).astype(np.float32)
    depth = rng.randint(0, 5, size=(n,)).astype(np.int32)
    out = trie_reduce_pallas(
        jnp.asarray(sup), jnp.asarray(conf), jnp.asarray(depth),
        interpret=True,
    )
    ref = trie_reduce_ref(
        jnp.asarray(sup), jnp.asarray(conf), jnp.asarray(depth)
    )
    for a, b in zip(out, ref):
        if np.isfinite(float(b)):
            np.testing.assert_allclose(float(a), float(b), rtol=1e-5)


def test_trie_reduce_empty_trie_guarded():
    """N=0 must not trace a zero-grid pallas_call and must report zeros
    (not -inf) in every slot — kernel and oracle agree."""
    z = jnp.zeros((0,), jnp.float32)
    zi = jnp.zeros((0,), jnp.int32)
    out = trie_reduce_pallas(z, z, zi, interpret=True)
    ref = trie_reduce_ref(z, z, zi)
    for a, b in zip(out, ref):
        assert float(a) == 0.0 and float(b) == 0.0


def test_trie_reduce_all_padding_max_not_inf():
    """A live array whose rows are ALL padding/root (depth <= 0) used to
    leave the max-confidence accumulator at its -inf init value."""
    rng = np.random.RandomState(3)
    sup = jnp.asarray(rng.rand(17).astype(np.float32))
    conf = jnp.asarray(rng.rand(17).astype(np.float32))
    depth = jnp.zeros((17,), jnp.int32)
    out = trie_reduce_pallas(sup, conf, depth, interpret=True)
    ref = trie_reduce_ref(sup, conf, depth)
    for a, b in zip(out, ref):
        assert float(a) == 0.0 and float(b) == 0.0
    # and through the public op (mean_conf must not be NaN/-inf)
    from repro.core.array_trie import FrozenTrie
    from repro.core.trie import TrieOfRules
    from repro.kernels.ops import trie_reduce

    agg = trie_reduce(FrozenTrie.freeze(TrieOfRules()).device_arrays())
    assert float(agg["n_rules"]) == 0.0
    assert float(agg["confidence_max"]) == 0.0
    assert float(agg["mean_conf"]) == 0.0
