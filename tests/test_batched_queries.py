"""Batched multi-query engine tests.

Every batched op must be BIT-identical to its looped single-query
counterpart (tie order included) and to its jnp oracle:

- ``rules_with`` (consequent / antecedent / any roles): kernel ≡ oracle ≡
  pointer-trie ``rules_with_item`` enumeration; absent items, duplicate
  queries, Q=0, k > matches,
- ``top_k_rules_batch`` ≡ Q ``top_k_rules`` calls, incl. absent and
  empty prefixes,
- ``rule_search_batch`` ≡ Q single ``rule_search`` calls on ragged
  (A, C) pairs,
- everything on BOTH construction engines (``pointer`` freeze and
  ``arrays`` build) — the indexes must answer identically.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.synthetic import synthetic_csr_trie
from repro.kernels.item_index import ROLES, rules_with_pallas
from repro.kernels.metrics_inkernel import RANK_METRICS
from repro.kernels.ops import (
    item_rank_arrays,
    prefix_ranges,
    rule_search,
    rule_search_batch,
    rules_with,
    top_k_rules,
    top_k_rules_batch,
)
from repro.kernels.rank import topk_rank_batch_pallas
from repro.kernels.ref import rules_with_ref, topk_rank_batch_ref


def _assert_same(a, b, keys, msg=""):
    for k in keys:
        np.testing.assert_array_equal(
            np.asarray(a[k]), np.asarray(b[k]), err_msg=f"{msg} {k}"
        )


# ----------------------------------------------------------------------
# rules_with: kernel ≡ oracle ≡ pointer enumeration, all roles
# ----------------------------------------------------------------------
@pytest.mark.parametrize("role", ROLES)
@pytest.mark.parametrize(
    "metric",
    [
        m if m in ("confidence", "conviction")
        else pytest.param(m, marks=pytest.mark.slow)
        for m in RANK_METRICS
    ],
)
def test_rules_with_kernel_matches_oracle(role, metric, frozen):
    fz = frozen(0.2)
    n_items = fz.item_offsets.shape[0] - 1
    # absent item (n_items+5), negative item, duplicates — all included
    items = [0, 1, n_items + 5, 1, -3, max(n_items - 1, 0)]
    out_k = rules_with(fz, items, role=role, k=6, metric=metric)
    out_o = rules_with(
        fz, items, role=role, k=6, metric=metric, use_kernel=False
    )
    _assert_same(
        out_k, out_o, ("values", "node", "pos"), f"{role}/{metric}"
    )


@pytest.mark.parametrize("role", ROLES)
def test_rules_with_matches_pointer_enumeration(role, mined, frozen):
    """Semantic ground truth: the returned rule set per item equals the
    pointer trie's per-node path-walk enumeration, and values rank the
    metric column descending."""
    from collections import deque

    res = mined(0.2)
    fz = frozen(0.2)
    bfs = {id(res.trie.root): 0}
    q = deque([res.trie.root])
    while q:
        node = q.popleft()
        for child in sorted(node.children.values(), key=lambda c: c.item):
            bfs[id(child)] = len(bfs)
            q.append(child)
    n_items = fz.item_offsets.shape[0] - 1
    items = list(range(n_items))
    k = fz.n_nodes  # k > any match count: full enumeration per item
    out = rules_with(fz, items, role=role, k=k, metric="confidence")
    nodes = np.asarray(out["node"])
    vals = np.asarray(out["values"])
    for qi, it in enumerate(items):
        got = {int(x) for x in nodes[qi] if x >= 0}
        want = {
            bfs[id(nd)] for nd in res.trie.rules_with_item(it, role)
        }
        assert got == want, (role, it)
        live = vals[qi][nodes[qi] >= 0]
        assert (np.diff(live) <= 0).all()  # descending scores
        np.testing.assert_allclose(
            live, fz.confidence[nodes[qi][nodes[qi] >= 0]], rtol=0
        )
        # k > matches: the tail is exactly (-inf, -1)
        assert (vals[qi][nodes[qi] < 0] == -np.inf).all()


def test_rules_with_duplicate_queries_identical_rows(frozen):
    fz = frozen(0.25)
    out = rules_with(fz, [2, 2, 2], role="any", k=4)
    for key in ("values", "node", "pos"):
        col = np.asarray(out[key])
        np.testing.assert_array_equal(col[0], col[1], err_msg=key)
        np.testing.assert_array_equal(col[1], col[2], err_msg=key)


def test_rules_with_absent_item_and_q0(frozen):
    fz = frozen(0.25)
    n_items = fz.item_offsets.shape[0] - 1
    out = rules_with(fz, [n_items + 17], role="any", k=3)
    assert (np.asarray(out["values"]) == -np.inf).all()
    assert (np.asarray(out["node"]) == -1).all()
    # consequent role too (the posting fast path)
    out = rules_with(fz, [-1], role="consequent", k=3)
    assert (np.asarray(out["node"]) == -1).all()
    # Q = 0: empty result, no kernel trace
    out = rules_with(fz, [], role="antecedent", k=3)
    assert np.asarray(out["values"]).shape == (0, 3)


def test_rules_with_consequent_two_paths_agree(frozen):
    """The consequent role has two independent implementations: the
    posting-range fast path (rank kernel over posting-ordered columns)
    and the membership kernel with role='consequent'.  Same nodes, same
    values, same order."""
    fz = frozen(0.2)
    arrays = item_rank_arrays(fz)
    items = [0, 1, 3, 99]
    fast = rules_with(
        fz, items, role="consequent", k=5, metric="lift", arrays=arrays
    )
    from repro.kernels.ops import _posting_slices

    plos, phis, qitems = _posting_slices(arrays["item_offsets"], items)
    vals, pos = rules_with_pallas(
        arrays["support"], arrays["confidence"], arrays["lift"],
        arrays["depth"], arrays["node_item"],
        arrays["post_lo"], arrays["post_hi"],
        jnp.asarray(plos), jnp.asarray(phis), jnp.asarray(qitems),
        k=5, metric="lift", role="consequent",
        max_postings=arrays["max_postings"], interpret=True,
    )
    node = np.where(
        np.asarray(pos) >= 0,
        np.asarray(arrays["dfs_to_node"])[np.maximum(np.asarray(pos), 0)],
        -1,
    )
    np.testing.assert_array_equal(np.asarray(fast["values"]), np.asarray(vals))
    np.testing.assert_array_equal(np.asarray(fast["node"]), node)


def test_rules_with_min_depth_excludes_pseudo_rules(frozen):
    """min_depth=2 drops depth-1 nodes (empty antecedent) from the
    consequent role's answers."""
    fz = frozen(0.2)
    items = list(range(fz.item_offsets.shape[0] - 1))
    out = rules_with(
        fz, items, role="consequent", k=fz.n_nodes, min_depth=2
    )
    nodes = np.asarray(out["node"])
    live = nodes[nodes >= 0]
    assert (fz.node_depth[live] >= 2).all()


def test_rules_with_rejects_bad_args(frozen):
    fz = frozen(0.25)
    with pytest.raises(ValueError, match="role"):
        rules_with(fz, [0], role="subject")
    with pytest.raises(ValueError, match="metric"):
        rules_with(fz, [0], metric="novelty")


# ----------------------------------------------------------------------
# batched segmented rank kernel vs its oracle on raw ranges
# ----------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 10, 100])
def test_topk_rank_batch_kernel_oracle_parity(k):
    arrs = synthetic_csr_trie(3_000, seed=11)
    d2n = arrs["dfs_to_node"]
    cols = tuple(
        jnp.asarray(arrs[c][d2n])
        for c in ("support", "confidence", "lift", "node_depth")
    )
    n = arrs["node_parent"].shape[0]
    los = jnp.asarray([0, 7, 2_500, 100, 0, n], jnp.int32)
    his = jnp.asarray([n, 2_000, 2_501, 100, 1, n], jnp.int32)
    for metric in ("confidence", "conviction"):
        kv, kp = topk_rank_batch_pallas(
            *cols, los, his, k=k, metric=metric, interpret=True
        )
        rv, rp = topk_rank_batch_ref(*cols, los, his, k=k, metric=metric)
        np.testing.assert_array_equal(np.asarray(kv), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))


@pytest.mark.slow
def test_rules_with_pallas_matches_ref_on_synthetic():
    """Raw membership ranking vs the searchsorted reference on an
    irregular synthetic trie, every role, with ties (deep tie coverage:
    the fast job's role×metric sweep runs on the mined trie instead)."""
    arrs = synthetic_csr_trie(5_000, seed=9)
    rng = np.random.RandomState(1)
    # quantize to force score ties across tile boundaries
    for c in ("support", "confidence", "lift"):
        arrs[c] = (rng.randint(0, 5, size=arrs[c].shape) / 5.0).astype(
            np.float32
        )
    d2n = arrs["dfs_to_node"]
    sup, conf, lif = (
        jnp.asarray(arrs[c][d2n])
        for c in ("support", "confidence", "lift")
    )
    dep = jnp.asarray(arrs["node_depth"][d2n])
    nit = jnp.asarray(arrs["node_item"][d2n])
    post_lo = jnp.asarray(
        arrs["dfs_order"][arrs["item_nodes"]], jnp.int32
    )
    io = arrs["item_offsets"]
    # per-item sorted subtree ends
    lo_np = np.asarray(post_lo)
    hi_np = lo_np + arrs["subtree_size"][arrs["item_nodes"]]
    seg = np.repeat(np.arange(io.shape[0] - 1), np.diff(io))
    n = arrs["node_parent"].shape[0]
    post_hi = jnp.asarray(
        hi_np[np.argsort(seg * (n + 1) + hi_np, kind="stable")], jnp.int32
    )
    items = np.array([0, 1, 2, 5, 7], np.int64)
    plos = jnp.asarray(io[items], jnp.int32)
    phis = jnp.asarray(io[items + 1], jnp.int32)
    items_j = jnp.asarray(items, jnp.int32)
    for role in ROLES:
        for k in (10, 100):
            kv, kp = rules_with_pallas(
                sup, conf, lif, dep, nit, post_lo, post_hi,
                plos, phis, items_j,
                k=k, metric="support", role=role,
                max_postings=arrs["max_postings"], interpret=True,
            )
            rv, rp = rules_with_ref(
                sup, conf, lif, dep, nit, post_lo, post_hi,
                plos, phis, items_j, k=k, metric="support", role=role,
            )
            np.testing.assert_array_equal(
                np.asarray(kv), np.asarray(rv), err_msg=f"{role} k={k}",
            )
            np.testing.assert_array_equal(
                np.asarray(kp), np.asarray(rp), err_msg=f"{role} k={k}",
            )


# ----------------------------------------------------------------------
# top_k_rules_batch ≡ looped top_k_rules
# ----------------------------------------------------------------------
@pytest.mark.parametrize("metric", RANK_METRICS)
def test_top_k_rules_batch_matches_looped(metric, frozen):
    fz = frozen(0.25)
    prefixes = [
        (int(fz.item_order[0]),),
        (),                          # empty prefix = whole trie
        (123456,),                   # absent prefix = empty range
        (int(fz.item_order[1]),),
        (int(fz.item_order[0]),),    # duplicate query
    ]
    out = top_k_rules_batch(fz, prefixes, 7, metric)
    for qi, p in enumerate(prefixes):
        single = top_k_rules(fz, 7, metric, prefix=(p if p else None))
        for key in ("values", "node", "dfs_pos"):
            np.testing.assert_array_equal(
                np.asarray(out[key])[qi], np.asarray(single[key]),
                err_msg=f"{metric} q={qi} {key}",
            )


def test_top_k_rules_batch_oracle_parity(frozen):
    fz = frozen(0.2)
    prefixes = [(int(fz.item_order[0]),), (), (987654,)]
    out_k = top_k_rules_batch(fz, prefixes, 5, "lift")
    out_o = top_k_rules_batch(fz, prefixes, 5, "lift", use_kernel=False)
    _assert_same(out_k, out_o, ("values", "node", "dfs_pos"))


def test_top_k_rules_batch_matrix_minus_one_is_padding(frozen):
    """The top_k_rules_batch ENTRY POINT must preserve an already-padded
    [Q, P] matrix end to end (the serve scheduler launches exactly this
    shape) — a normalization layer that list()-ifies it turns the -1
    padding into literal absent items and every padded row goes empty."""
    fz = frozen(0.25)
    it = int(fz.item_order[0])
    mat = np.array([[it, -1, -1], [-1, -1, -1]], np.int32)
    out_m = top_k_rules_batch(fz, mat, 5, "confidence")
    out_r = top_k_rules_batch(fz, [(it,), ()], 5, "confidence")
    _assert_same(out_m, out_r, ("values", "node", "dfs_pos"))


def test_top_k_rules_batch_q0(frozen):
    fz = frozen(0.25)
    out = top_k_rules_batch(fz, [], 4, "confidence")
    assert np.asarray(out["values"]).shape == (0, 4)


def test_prefix_negative_item_is_absent(frozen):
    """A negative item id in a RAGGED prefix means 'not in the trie' — it
    must not be silently dropped as padding (empty range, not whole
    trie)."""
    fz = frozen(0.25)
    los, his, nodes = prefix_ranges(fz, [(-1,), (-5,)])
    assert (np.asarray(los) == np.asarray(his)).all()
    assert (np.asarray(nodes) == -1).all()
    out = top_k_rules(fz, 4, "confidence", prefix=(-1,))
    assert (np.asarray(out["node"]) == -1).all()


def test_prefix_matrix_minus_one_is_padding(frozen):
    """In an already-padded [Q, P] prefix MATRIX, -1 is padding (the
    repo-wide query-matrix convention): a padded row must resolve the
    same range as its ragged unpadded form."""
    fz = frozen(0.25)
    it = int(fz.item_order[0])
    mat = np.array([[it, -1, -1], [-1, -1, -1]], np.int32)
    m_los, m_his, m_nodes = prefix_ranges(fz, mat)
    r_los, r_his, r_nodes = prefix_ranges(fz, [(it,), ()])
    np.testing.assert_array_equal(np.asarray(m_los), np.asarray(r_los))
    np.testing.assert_array_equal(np.asarray(m_his), np.asarray(r_his))
    np.testing.assert_array_equal(np.asarray(m_nodes), np.asarray(r_nodes))
    # all-padding row = empty prefix = whole trie
    assert (int(m_los[1]), int(m_his[1])) == (0, fz.n_nodes)


def test_rule_search_batch_device_trie_needs_arrays(frozen):
    """Ragged pairs against a DeviceTrie: a clear ValueError, not an
    AttributeError (canonicalization is host-side FrozenTrie state)."""
    dt = frozen(0.25).device_arrays()
    with pytest.raises(ValueError, match="FrozenTrie"):
        rule_search_batch(dt, [((0,), (1,))])


def test_prefix_ranges_resolution(frozen):
    fz = frozen(0.25)
    it = int(fz.item_order[0])
    los, his, nodes = prefix_ranges(fz, [(it,), (), (424242,)])
    (nid,) = [
        i for i in range(fz.n_nodes)
        if fz.node_parent[i] == 0 and fz.node_item[i] == it
    ]
    assert int(nodes[0]) == nid
    assert int(los[0]) == int(fz.dfs_order[nid])
    assert int(his[0]) - int(los[0]) == int(fz.subtree_size[nid])
    # empty prefix: root, whole trie
    assert int(nodes[1]) == 0
    assert (int(los[1]), int(his[1])) == (0, fz.n_nodes)
    # absent prefix: empty range, node -1
    assert int(nodes[2]) == -1
    assert int(los[2]) == int(his[2])


# ----------------------------------------------------------------------
# rule_search_batch ≡ looped rule_search
# ----------------------------------------------------------------------
def test_rule_search_batch_matches_looped(paper_db, mined, frozen):
    from repro.arm.rulegen import prefix_split_rules

    res = mined(0.2)
    fz = frozen(0.2)
    rules = prefix_split_rules(res.itemsets, paper_db)
    pairs = [(r.antecedent, r.consequent) for r in rules]
    pairs.append(((99, 98), (97,)))      # absent rule
    pairs.append(pairs[0])               # duplicate query
    out = rule_search_batch(fz, pairs)
    # the looped equivalent: one single-pair canonicalize + launch each.
    # Spot-check a mix of rows (first/mid/absent/duplicate) rather than
    # all Q — each looped launch is a full interpret-mode kernel run.
    spot = sorted({0, 1, len(rules) // 2, len(pairs) - 2, len(pairs) - 1})
    for qi in spot:
        a, c = pairs[qi]
        single = rule_search_batch(fz, [(a, c)])
        for key in ("found", "node", "support", "confidence", "lift"):
            np.testing.assert_array_equal(
                np.asarray(out[key])[qi: qi + 1], np.asarray(single[key]),
                err_msg=f"q={qi} {key}",
            )
    # and the found rows carry the pointer-trie metrics
    for qi, r in enumerate(rules):
        assert bool(out["found"][qi])
        m = res.trie.search_rule(r.antecedent, r.consequent)
        np.testing.assert_allclose(
            float(out["confidence"][qi]), m.confidence, rtol=1e-5
        )
    assert not bool(out["found"][len(rules)])


def test_rule_search_batch_array_inputs_and_q0(frozen):
    fz = frozen(0.25)
    out = rule_search_batch(fz, [])
    assert np.asarray(out["found"]).shape == (0,)
    # padded-matrix entry point delegates to the same fused launch
    queries = np.array([[0, 1, -1], [-1, -1, -1]], np.int32)
    al = np.array([1, 0], np.int32)
    out = rule_search_batch(fz, queries, ant_len=al)
    ref = rule_search(fz, queries, al)
    _assert_same(out, ref, ("found", "node", "support", "confidence", "lift"))
    # Q=0 with explicit arrays
    out = rule_search(fz, np.zeros((0, 3), np.int32), np.zeros(0, np.int32))
    assert np.asarray(out["found"]).shape == (0,)


# ----------------------------------------------------------------------
# arrays-engine parity: the batched ops answer identically on the
# array-native index
# ----------------------------------------------------------------------
def test_batched_ops_pointer_vs_arrays_engine(mined):
    res = mined(0.2, engine="both")
    from repro.core.array_trie import FrozenTrie

    fz_ptr = FrozenTrie.freeze(res.trie)
    fz_arr = res.frozen
    items = [0, 1, 5, 2]
    for role in ROLES:
        a = rules_with(fz_ptr, items, role=role, k=6, metric="leverage")
        b = rules_with(fz_arr, items, role=role, k=6, metric="leverage")
        _assert_same(a, b, ("values", "node", "pos"), role)
    prefixes = [(int(fz_ptr.item_order[0]),), ()]
    a = top_k_rules_batch(fz_ptr, prefixes, 5, "confidence")
    b = top_k_rules_batch(fz_arr, prefixes, 5, "confidence")
    _assert_same(a, b, ("values", "node", "dfs_pos"))
