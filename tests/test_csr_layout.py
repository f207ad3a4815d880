"""CSR child-bucket layout tests.

- ``child_offsets`` round-trips against the pointer trie's children,
- the fused single-launch kernel ≡ the jnp CSR oracle ≡ the layout-agnostic
  full-table reference, on random tries including compound consequents,
  absent rules, and all-padding query rows,
- the CSR jnp oracle ≡ the seed full-table binary-search oracle,
- empty-trie degenerate cases return all-not-found without tracing a
  zero-chunk kernel.

Trie/query builders and mined fixtures come from ``tests/conftest.py``
(shared with the DFS, kernel, and batched-query suites).
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.array_trie import (
    batched_rule_search,
    child_lookup,
    csr_offsets_from_edges,
)
from repro.kernels.ops import edge_metric_arrays, rule_search
from repro.kernels.ref import rule_search_fused_ref
from repro.kernels.rule_search import (
    rule_search_fused,
    rule_search_pallas,
)


# ----------------------------------------------------------------------
# CSR offsets round-trip against the pointer trie
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "minsup",
    [pytest.param(0.2, marks=pytest.mark.slow), 0.3, 0.5],
)
def test_child_offsets_roundtrip_pointer_trie(minsup, mined, frozen):
    res = mined(minsup)
    fz = frozen(minsup)
    co = fz.child_offsets
    assert co.shape == (fz.n_nodes + 1,)
    assert co[0] == 0 and co[-1] == fz.n_edges
    assert (np.diff(co) >= 0).all()
    assert fz.max_fanout == (np.diff(co).max() if fz.n_edges else 0)
    # each bucket is exactly the node's children, item-sorted
    for nid in range(fz.n_nodes):
        lo, hi = int(co[nid]), int(co[nid + 1])
        bucket_items = fz.edge_item[lo:hi]
        bucket_children = fz.edge_child[lo:hi]
        assert (fz.edge_parent[lo:hi] == nid).all()
        assert (np.diff(bucket_items) > 0).all()  # sorted, unique
        expect = {
            int(fz.node_item[c])
            for c in np.nonzero(fz.node_parent == nid)[0]
        }
        assert set(bucket_items.tolist()) == expect
        for it, ch in zip(bucket_items, bucket_children):
            assert fz.node_parent[ch] == nid and fz.node_item[ch] == it
    # pointer round-trip: descending via CSR child_lookup reproduces every
    # pointer-trie path and its Step-3 metrics
    dt = fz.device_arrays()
    for path, pnode in res.trie.all_paths():
        node = jnp.zeros((1,), jnp.int32)
        for it in path:
            node = child_lookup(dt, node, jnp.full((1,), it, jnp.int32))
            assert int(node[0]) >= 0
        nid = int(node[0])
        np.testing.assert_allclose(
            float(fz.confidence[nid]), pnode.confidence, rtol=1e-6
        )
        np.testing.assert_allclose(
            float(fz.support[nid]), pnode.support, rtol=1e-6
        )


# ----------------------------------------------------------------------
# CSR child_lookup ≡ seed full-table binary search
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_nodes,n_items", [(2, 3), (60, 9), (400, 40)])
def test_child_lookup_csr_matches_seed(n_nodes, n_items, random_trie,
                                       device_trie):
    rng = np.random.RandomState(n_nodes * 7 + n_items)
    arrs = random_trie(rng, n_nodes, n_items)
    dt_csr = device_trie(arrs, csr=True)
    dt_seed = device_trie(arrs, csr=False)
    # valid parents, invalid parents, absent items all covered
    parents = jnp.asarray(
        rng.randint(-2, n_nodes + 2, size=(256,)), jnp.int32
    )
    items = jnp.asarray(rng.randint(-1, n_items + 2, size=(256,)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(child_lookup(dt_csr, parents, items)),
        np.asarray(child_lookup(dt_seed, parents, items)),
    )


@pytest.mark.parametrize("n_nodes,n_items,q,width", [(80, 10, 60, 6)])
def test_oracle_csr_matches_seed_search(n_nodes, n_items, q, width,
                                        random_trie, device_trie, query_mix):
    rng = np.random.RandomState(5)
    arrs = random_trie(rng, n_nodes, n_items)
    queries, ant_len = query_mix(rng, arrs, q, width)
    qj, alj = jnp.asarray(queries), jnp.asarray(ant_len)
    out_csr = batched_rule_search(device_trie(arrs, csr=True), qj, alj)
    out_seed = batched_rule_search(device_trie(arrs, csr=False), qj, alj)
    for k in out_csr:
        np.testing.assert_array_equal(
            np.asarray(out_csr[k]), np.asarray(out_seed[k]), err_msg=k
        )


# ----------------------------------------------------------------------
# bucket descent ≡ jnp oracle ≡ full-table reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "n_nodes,n_items,q,width",
    [(5, 4, 9, 3), (60, 8, 48, 5), (300, 24, 130, 7), (700, 150, 200, 4)],
)
def test_fused_kernel_parity(n_nodes, n_items, q, width, random_trie,
                             device_trie, query_mix):
    rng = np.random.RandomState(n_nodes + q)
    arrs = random_trie(rng, n_nodes, n_items, max_children=9)
    queries, ant_len = query_mix(rng, arrs, q, width)
    qj, alj = jnp.asarray(queries), jnp.asarray(ant_len)

    edge_args = [
        jnp.asarray(arrs[k]) for k in ("edge_item", "edge_child")
    ]
    emetrics = [
        jnp.asarray(arrs[col])[jnp.asarray(arrs["edge_child"])]
        for col in ("confidence", "support", "lift")
    ]
    out = rule_search_fused(
        jnp.asarray(arrs["child_offsets"]), *edge_args, *emetrics,
        qj, alj, max_fanout=arrs["max_fanout"],
    )
    ref = rule_search_fused_ref(
        jnp.asarray(arrs["edge_parent"]), *edge_args, *emetrics, qj, alj
    )
    oracle = batched_rule_search(device_trie(arrs, csr=True), qj, alj)
    for k in ("found", "node"):
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(ref[k]), err_msg=k
        )
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(oracle[k]), err_msg=k
        )
    for k in ("support", "confidence", "lift"):
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(ref[k]), rtol=1e-6, err_msg=k
        )
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(oracle[k]), rtol=1e-6, err_msg=k
        )
    # all-padding rows (every 3rd) must be not-found with zeroed metrics
    pad_rows = np.asarray(queries >= 0).sum(axis=1) == 0
    assert not np.asarray(out["found"])[pad_rows].any()
    assert (np.asarray(out["node"])[pad_rows] == -1).all()
    assert (np.asarray(out["lift"])[pad_rows] == 0).all()


def test_fused_kernel_hub_bucket_chunked_sweep(query_mix):
    """A hub root (fanout 300) — the bucket binary search over a wide
    bucket must stay bit-identical to the full-table reference (the
    low-minsup production shape: many frequent 1-items)."""
    root_fanout = 300
    parent = [-1]
    item = [-1]
    edges = []
    for it in range(root_fanout):  # root's hub bucket
        nid = len(parent)
        parent.append(0)
        item.append(it)
        edges.append((0, it, nid))
    hub_children = list(range(1, 51))
    for p in hub_children:  # depth-2 layer under the first 50 children
        for it in (0, 150, 299):
            nid = len(parent)
            parent.append(p)
            item.append(it)
            edges.append((p, it, nid))
    edges.sort()
    e = np.array(edges, np.int32)
    n_nodes = len(parent)
    rng = np.random.RandomState(7)
    offsets, max_fanout = csr_offsets_from_edges(e[:, 0], n_nodes)
    assert max_fanout == root_fanout  # the hub is the widest bucket
    arrs = {
        "node_parent": np.asarray(parent, np.int32),
        "node_item": np.asarray(item, np.int32),
        "node_depth": np.where(np.asarray(parent) == 0, 1, 2).astype(
            np.int32
        ),
        "confidence": (rng.rand(n_nodes) * 0.9 + 0.05).astype(np.float32),
        "support": (rng.rand(n_nodes) * 0.9 + 0.05).astype(np.float32),
        "lift": (rng.rand(n_nodes) * 2).astype(np.float32),
        "edge_parent": e[:, 0].copy(), "edge_item": e[:, 1].copy(),
        "edge_child": e[:, 2].copy(),
        "child_offsets": offsets, "max_fanout": max_fanout,
    }
    queries, ant_len = query_mix(rng, arrs, 96, 4)
    qj, alj = jnp.asarray(queries), jnp.asarray(ant_len)
    emetrics = [
        jnp.asarray(arrs[col])[jnp.asarray(arrs["edge_child"])]
        for col in ("confidence", "support", "lift")
    ]
    out = rule_search_fused(
        jnp.asarray(offsets), jnp.asarray(arrs["edge_item"]),
        jnp.asarray(arrs["edge_child"]), *emetrics,
        qj, alj, max_fanout=max_fanout,
    )
    ref = rule_search_fused_ref(
        jnp.asarray(arrs["edge_parent"]), jnp.asarray(arrs["edge_item"]),
        jnp.asarray(arrs["edge_child"]), *emetrics, qj, alj,
    )
    assert np.asarray(out["found"]).any()  # hub paths actually resolved
    for k in ("found", "node"):
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(ref[k]), err_msg=k
        )
    for k in ("support", "confidence", "lift"):
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(ref[k]), rtol=1e-6, err_msg=k
        )


def test_ops_rule_search_single_launch_matches_oracle(frozen, query_mix):
    """The public op (fused path) against the oracle on real mined data."""
    fz = frozen(0.25)
    rng = np.random.RandomState(3)
    queries, ant_len = query_mix(
        rng,
        {
            "node_item": fz.node_item, "node_parent": fz.node_parent,
            "edge_item": fz.edge_item,
        },
        90, 6,
    )
    out = rule_search(fz, queries, ant_len)
    oracle = batched_rule_search(
        fz.device_arrays(), jnp.asarray(queries), jnp.asarray(ant_len)
    )
    for k in ("found", "node"):
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(oracle[k]), err_msg=k
        )
    for k in ("support", "confidence", "lift"):
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(oracle[k]), rtol=1e-6, err_msg=k
        )


# ----------------------------------------------------------------------
# empty trie: guards instead of zero-chunk kernels
# ----------------------------------------------------------------------
def test_empty_trie_freeze_and_metric_arrays(empty_frozen):
    fz = empty_frozen
    assert fz.n_nodes == 1 and fz.n_edges == 0
    np.testing.assert_array_equal(fz.child_offsets, [0, 0])
    assert fz.max_fanout == 0
    edges = edge_metric_arrays(fz)  # empty gathers must not raise
    assert edges["edge_conf"].shape == (0,)
    assert edges["max_fanout"] == 0


def test_empty_trie_search_all_not_found(empty_frozen):
    fz = empty_frozen
    queries = np.array([[0, 1], [-1, -1], [2, -1]], np.int32)
    ant_len = np.array([1, 0, 0], np.int32)
    for out in (
        rule_search(fz, queries, ant_len),
        batched_rule_search(
            fz.device_arrays(), jnp.asarray(queries), jnp.asarray(ant_len)
        ),
    ):
        assert not np.asarray(out["found"]).any()
        assert (np.asarray(out["node"]) == -1).all()
        for k in ("support", "confidence", "lift"):
            assert (np.asarray(out[k]) == 0).all()


def test_empty_edge_table_kernels_guarded():
    empty_i = jnp.zeros((0,), jnp.int32)
    empty_f = jnp.zeros((0,), jnp.float32)
    queries = jnp.asarray([[0, 1, -1]], jnp.int32)
    al = jnp.asarray([1], jnp.int32)
    out = rule_search_pallas(
        empty_i, empty_i, empty_i, empty_f, empty_f, empty_f,
        queries, al, interpret=True,
    )
    assert not bool(out["found"][0]) and int(out["node"][0]) == -1
    out = rule_search_fused(
        jnp.asarray([0, 0], jnp.int32), empty_i, empty_i,
        empty_f, empty_f, empty_f, queries, al, max_fanout=0,
    )
    assert not bool(out["found"][0]) and int(out["node"][0]) == -1
    assert float(out["lift"][0]) == 0.0


def test_zero_width_queries_guarded(frozen):
    fz = frozen(0.3)
    out = rule_search(
        fz, np.zeros((4, 0), np.int32), np.zeros((4,), np.int32)
    )
    assert not np.asarray(out["found"]).any()


def test_device_trie_pytree_roundtrip(random_trie, device_trie):
    rng = np.random.RandomState(0)
    arrs = random_trie(rng, 30, 6)
    dt = device_trie(arrs, csr=True)
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(dt)
    dt2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert dt2.max_fanout == dt.max_fanout
    assert dt2.max_postings == dt.max_postings
    np.testing.assert_array_equal(
        np.asarray(dt2.child_offsets), np.asarray(dt.child_offsets)
    )
    np.testing.assert_array_equal(
        np.asarray(dt2.item_offsets), np.asarray(dt.item_offsets)
    )
