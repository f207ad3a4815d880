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
- ``prefix_ranges``' jitted descent ≡ the host mirror
  (``host_prefix_ranges``) ≡ the eager column walk, on both layouts,
  compiled once per launch shape,
- each op's jitted ``expand`` (``_expand_rows`` / ``_node_ids`` /
  ``_gather_rows``) ≡ the eager gathers it replaced, compiled once per
  launch shape, with the rank kernel still launched as its own program,
- everything on BOTH construction engines (``pointer`` freeze and
  ``arrays`` build) — the indexes must answer identically.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import monitoring

from repro.core.array_trie import (
    canonical_prefix_rows,
    child_lookup,
    compressed_step,
)
from repro.core.synthetic import (
    frozen_from_arrays,
    synthetic_chain_trie,
    synthetic_csr_trie,
)
from repro.distributed.trie_sharding import host_prefix_ranges
from repro.kernels import ops, rank
from repro.kernels.item_index import ROLES, rules_with_pallas
from repro.kernels.metrics_inkernel import RANK_METRICS
from repro.kernels.ops import (
    _descend_ranges,
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


# ----------------------------------------------------------------------
# prefix_ranges: the jitted descent ≡ the host mirror ≡ the eager walk
# ----------------------------------------------------------------------
def _eager_prefix_ranges(dt, mat):
    """A column-by-column eager descent (one dispatch per step), the
    layout-aware oracle for the jitted ``_descend_ranges``."""
    q, width = mat.shape
    if dt.layout == "compressed":
        n = dt.subtree_size.shape[0]
        pos = jnp.zeros((q,), jnp.int32)
        rem = jnp.zeros((q,), jnp.int32)
        ctail = jnp.zeros((q,), jnp.int32)
        okm = jnp.ones((q,), bool)
        for c in range(width):
            col = jnp.asarray(mat[:, c])
            p2, r2, t2, hit = compressed_step(dt, pos, rem, ctail, col)
            active = (col != -1) & okm
            okm = jnp.where(active, hit, okm)
            adv = active & hit
            pos = jnp.where(adv, p2, pos)
            rem = jnp.where(adv, r2, rem)
            ctail = jnp.where(adv, t2, ctail)
        los = jnp.where(okm, pos, 0)
        his = jnp.minimum(jnp.where(okm, pos + dt.subtree_size[pos], 0), n)
        return los, his, jnp.where(okm, dt.dfs_to_node[pos], -1)
    n = dt.dfs_order.shape[0]
    nodes = jnp.zeros((q,), jnp.int32)
    for c in range(width):
        col = jnp.asarray(mat[:, c])
        nodes = jnp.where(col != -1, child_lookup(dt, nodes, col), nodes)
    ok = nodes >= 0
    nid = jnp.maximum(nodes, 0)
    los = jnp.where(ok, dt.dfs_order[nid], 0)
    his = jnp.minimum(jnp.where(ok, los + dt.subtree_size[nid], 0), n)
    return los, his, jnp.where(ok, nodes, -1)


@pytest.fixture(scope="module")
def descent_stores(frozen):
    """``{store: (FrozenTrie, DeviceTrie)}``: a chain-heavy synthetic
    trie in both layouts (its paths are not frequency-sorted, so its
    FrozenTrie carries no ``item_rank`` and rows descend as given), and
    the mined paper trie, whose ``item_rank`` canonicalizes shuffled
    prefixes."""
    arrs = synthetic_chain_trie(2000, chain_fraction=0.75, seed=3)
    fz = dataclasses.replace(frozen_from_arrays(arrs), item_rank=None)
    assert fz.span_fraction() >= 0.5
    dtc = fz.device_arrays(layout="compressed")
    assert dtc.layout == "compressed"
    paper = frozen(0.2)
    return {
        "plain": (fz, fz.device_arrays(layout="plain")),
        "compressed": (fz, dtc),
        "mined": (paper, paper.device_arrays()),
    }


def _path(fz, nid):
    items = []
    while nid > 0:
        items.append(int(fz.node_item[nid]))
        nid = int(fz.node_parent[nid])
    return items[::-1]


def _descent_matrix(rng, fz, q, width=8):
    """``[q, width]`` prefix rows cycling through every row kind: real
    path prefixes cut at any depth (mid-span on the compressed layout),
    the scheduler's ``-2 - i`` pad rows, empty rows, out-of-vocab ids and
    live negative ids after a real item."""
    mat = np.full((q, width), -1, np.int32)
    for i in range(q):
        kind = i % 6
        path = _path(fz, int(rng.randint(1, fz.n_nodes)))[:width]
        if kind in (0, 1):
            row = path[: rng.randint(1, len(path) + 1)] if kind == 0 else path
            if fz.item_rank is not None:  # canonicalization reorders it
                row = list(rng.permutation(row))
        elif kind == 2:
            row = [-2 - i]
        elif kind == 3:
            row = []
        elif kind == 4:
            row = path[:1] + [10 ** 6]
        else:
            row = path[:1] + [-5]
        mat[i, : len(row)] = row
    return mat


@pytest.mark.parametrize("q", [1, 2, 4, 8, 16, 32, 64])
@pytest.mark.parametrize("store", ["plain", "compressed", "mined"])
def test_prefix_ranges_jitted_matches_mirror(store, q, descent_stores):
    fz, dt = descent_stores[store]
    rng = np.random.RandomState(q)
    mat = _descent_matrix(rng, fz, q)
    # ragged rows: -1 is a literal item there (remapped to -9, absent)
    ragged = [list(r[r != -1]) for r in mat]
    for i in range(0, q, 3):
        ragged[i] = ragged[i] + [-1]
    for prefixes in (ragged, mat):
        got = [np.asarray(a) for a in prefix_ranges(fz, prefixes, dt=dt)]
        want = host_prefix_ranges(fz, prefixes)
        rows = canonical_prefix_rows(prefixes, fz.item_rank)
        cmat = np.full((q, max(max(map(len, rows)), 1)), -1, np.int32)
        for i, r in enumerate(rows):
            cmat[i, : len(r)] = r
        eager = [np.asarray(a) for a in _eager_prefix_ranges(dt, cmat)]
        for name, g, w, e in zip(("los", "his", "nodes"), got, want, eager):
            assert g.dtype == np.int32, name
            np.testing.assert_array_equal(g, w, err_msg=f"{name} mirror")
            np.testing.assert_array_equal(g, e, err_msg=f"{name} eager")
    los, his, nodes = got  # the matrix form's
    if q >= 8:
        # the kinds resolved as they should: pads, out-of-vocab and live
        # negatives to [0, 0), empty rows to [0, N), real paths found
        kinds = np.arange(q) % 6
        dead = np.isin(kinds, (2, 4, 5))
        assert (los[dead] == 0).all() and (his[dead] == 0).all()
        assert (nodes[dead] == -1).all()
        assert (los[kinds == 3] == 0).all()
        assert (his[kinds == 3] == fz.n_nodes).all()
        if fz.item_rank is None:
            assert (nodes[kinds <= 1] >= 0).all()
    if store == "compressed" and q >= 32:
        # some found rows end mid-span: on a single-child, non-root node
        cc = np.bincount(fz.node_parent[1:], minlength=fz.n_nodes)
        hit = nodes[nodes > 0]
        assert (cc[hit] == 1).any()


def _count_compiles():
    """``(compiles, stop)``: the backend compiles seen until ``stop()``."""
    compiles = []
    live = [True]

    def listen(event, duration, **_):
        if live[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    monitoring.register_event_duration_secs_listener(listen)
    return compiles, lambda: live.__setitem__(0, False)


def test_prefix_ranges_compiles_once_per_shape(descent_stores):
    """Two launches of one ``(Q, P)`` shape with different contents run
    one compiled program: the second compiles nothing."""
    fz, dt = descent_stores["compressed"]
    compiles, stop = _count_compiles()
    try:
        rng = np.random.RandomState(7)
        a = _descent_matrix(rng, fz, 5, width=7)
        b = _descent_matrix(rng, fz, 5, width=7)
        assert not (a == b).all()
        before = _descend_ranges._cache_size()
        ra = [np.asarray(x) for x in prefix_ranges(fz, a, dt=dt)]
        first = len(compiles)
        rb = [np.asarray(x) for x in prefix_ranges(fz, b, dt=dt)]
        assert len(compiles) == first
        assert _descend_ranges._cache_size() == before + 1
        assert any((x != y).any() for x, y in zip(ra, rb))
    finally:
        stop()


# ----------------------------------------------------------------------
# expand: the jitted gathers after each launch ≡ the eager ones they
# replaced (test-only copies below, verbatim), one program per shape
# ----------------------------------------------------------------------
def _eager_expand_rows(vals, pos, inv, back):
    vals = vals[inv]
    pos = pos[inv]
    if back.shape[0] == 0:
        node = jnp.full_like(pos, -1)
    else:
        node = jnp.where(pos >= 0, back[jnp.maximum(pos, 0)], -1)
    return vals, pos, node


def _eager_node_ids(pos, back):
    return jnp.where(pos >= 0, back[jnp.maximum(pos, 0)], -1)


def _eager_gather_rows(out, inv):
    return {key: v[inv] for key, v in out.items()}


def _assert_bitwise(got, want, keys, msg=""):
    for key in keys:
        g, w = np.asarray(got[key]), np.asarray(want[key])
        assert g.dtype == w.dtype, f"{msg} {key}"
        np.testing.assert_array_equal(g, w, err_msg=f"{msg} {key}")


def _expand_store(descent_stores, store):
    """``(trie, n_items)``: the plain layout as its FrozenTrie, the
    compressed one as its DeviceTrie, the mined paper trie frozen."""
    fz, dt = descent_stores[store]
    trie = dt if store == "compressed" else fz
    return trie, fz.item_offsets.shape[0] - 1


def _expand_items(rng, n_items, q):
    """``q`` items cycling through every kind a launch meets: a few hot
    items (duplicate-heavy), any item, absent ids past the vocabulary or
    negative, and the scheduler's ``-2 - i`` pad items."""
    hot = rng.randint(0, n_items, size=3)
    items = []
    for i in range(q):
        kind = i % 4
        if kind == 0:
            items.append(int(hot[rng.randint(0, 3)]))
        elif kind == 1:
            items.append(int(rng.randint(0, n_items)))
        elif kind == 2:
            items.append(n_items + 5 if i % 8 == 2 else -3)
        else:
            items.append(-2 - i)
    return items


@pytest.mark.parametrize("q", [1, 2, 4, 8, 16, 32, 64])
@pytest.mark.parametrize("role", ROLES)
@pytest.mark.parametrize("store", ["plain", "compressed", "mined"])
def test_rules_with_expand_matches_eager(
    store, role, q, descent_stores, monkeypatch
):
    trie, n_items = _expand_store(descent_stores, store)
    items = _expand_items(np.random.RandomState(q), n_items, q)
    # k above every match count on the small mined trie: (-inf, -1) tails
    k = descent_stores["mined"][0].n_nodes + 3 if store == "mined" else 10
    arrays = item_rank_arrays(trie)
    got = rules_with(trie, items, role=role, k=k, arrays=arrays)
    monkeypatch.setattr(ops, "_expand_rows", _eager_expand_rows)
    want = rules_with(trie, items, role=role, k=k, arrays=arrays)
    _assert_bitwise(got, want, ("values", "node", "pos"), f"{store}/{role}")
    node = np.asarray(got["node"])
    assert node.shape == (q, k)
    dead = np.array([it < 0 or it >= n_items for it in items])
    assert (node[dead] == -1).all()
    if store == "mined":
        assert (node[:, -3:] == -1).all()


@pytest.mark.parametrize("role", ROLES)
def test_rules_with_expand_oracle_path_matches_eager(
    role, descent_stores, monkeypatch
):
    """The ``use_kernel=False`` oracle path runs the same expand."""
    trie, n_items = _expand_store(descent_stores, "plain")
    items = _expand_items(np.random.RandomState(5), n_items, 12)
    got = rules_with(trie, items, role=role, k=6, use_kernel=False)
    monkeypatch.setattr(ops, "_expand_rows", _eager_expand_rows)
    want = rules_with(trie, items, role=role, k=6, use_kernel=False)
    _assert_bitwise(got, want, ("values", "node", "pos"), role)


@pytest.mark.parametrize("q", [1, 8, 64])
@pytest.mark.parametrize("store", ["plain", "compressed", "mined"])
def test_top_k_rules_batch_expand_matches_eager(
    store, q, descent_stores, monkeypatch
):
    fz, dt = descent_stores[store]
    trie = dt if store == "compressed" else fz
    mat = _descent_matrix(np.random.RandomState(q), fz, q)
    got = top_k_rules_batch(trie, mat, 7, "lift")
    monkeypatch.setattr(ops, "_node_ids", _eager_node_ids)
    want = top_k_rules_batch(trie, mat, 7, "lift")
    _assert_bitwise(got, want, ("values", "node", "dfs_pos"), store)


@pytest.mark.parametrize("dups", [True, False])
@pytest.mark.parametrize("store", ["plain", "compressed"])
def test_rule_search_batch_expand_matches_eager(
    store, dups, descent_stores, monkeypatch
):
    """With duplicate rows the batch descends its unique rows and
    ``_gather_rows`` scatters them back; with none (Q a power of two)
    no expand runs at all."""
    fz, dt = descent_stores[store]
    rng = np.random.RandomState(3)
    rows = []
    for _ in range(8):
        path = _path(fz, int(rng.randint(1, fz.n_nodes)))[:6]
        rows.append(path + [-1] * (6 - len(path)))
    queries = np.array(rows, np.int32)
    ant_len = np.array(
        [max(int((r >= 0).sum()) - 1, 0) for r in queries], np.int32
    )
    if dups:
        pick = np.array([0, 1, 0, 2, 1, 0, 3, 3, 2])
        queries, ant_len = queries[pick], ant_len[pick]
    calls = []
    jitted = ops._gather_rows

    def spy(out, inv):
        calls.append(inv.shape)
        return jitted(out, inv)

    monkeypatch.setattr(ops, "_gather_rows", spy)
    got = rule_search_batch(dt, queries, ant_len=ant_len)
    assert len(calls) == (1 if dups else 0)
    monkeypatch.setattr(ops, "_gather_rows", _eager_gather_rows)
    want = rule_search_batch(dt, queries, ant_len=ant_len)
    keys = ("found", "node", "support", "confidence", "lift")
    assert sorted(got) == sorted(want) == sorted(keys)
    _assert_bitwise(got, want, keys, store)
    assert np.asarray(got["found"]).all()


def test_expand_compiles_once_per_shape(descent_stores):
    """A second launch of one ``(U_pad, Q, k)`` shape with other contents
    compiles nothing; each op's expand holds one program for it."""
    fz, dt = descent_stores["compressed"]
    n_items = fz.item_offsets.shape[0] - 1
    arrays = item_rank_arrays(dt)
    compiles, stop = _count_compiles()
    try:
        # distinct items: U_pad == Q, as on the served path
        before = ops._expand_rows._cache_size()
        a = rules_with(dt, [0, 1, 2, -2], role="any", k=9, arrays=arrays)
        first, progs = len(compiles), ops._expand_rows._cache_size()
        assert progs - before <= 1
        b = rules_with(dt, [3, 4, n_items - 1, -5], role="any", k=9,
                       arrays=arrays)
        assert len(compiles) == first
        assert ops._expand_rows._cache_size() == progs
        assert (np.asarray(a["node"]) != np.asarray(b["node"])).any()

        mats = [_descent_matrix(np.random.RandomState(s), fz, 4, width=5)
                for s in (1, 2)]
        top_k_rules_batch(dt, mats[0], 9, "support")
        first, progs = len(compiles), ops._node_ids._cache_size()
        top_k_rules_batch(dt, mats[1], 9, "support")
        assert len(compiles) == first
        assert ops._node_ids._cache_size() == progs
    finally:
        stop()


@pytest.mark.parametrize("store", ["plain", "compressed"])
def test_rank_kernel_stays_its_own_program(store, descent_stores, monkeypatch):
    """``rank_roofline`` reads the rank kernel inside the program of
    ``rank._topk_rank_batch_impl``: a ``rules_with`` launch (both of its
    paths on the plain layout) and a ``top_k`` launch still dispatch that
    program themselves, with concrete operands, never traced inside an
    outer program."""
    fz, dt = descent_stores[store]
    trie = dt if store == "compressed" else fz
    calls = []
    impl = rank._topk_rank_batch_impl

    def spy(support, *args, **kw):
        calls.append(isinstance(support, jax.core.Tracer))
        return impl(support, *args, **kw)

    monkeypatch.setattr(rank, "_topk_rank_batch_impl", spy)
    for role in ROLES:
        rules_with(trie, [0, 1, -2, -3], role=role, k=5)
    top_k_rules_batch(trie, _descent_matrix(
        np.random.RandomState(0), fz, 4), 5)
    assert calls == [False] * (len(ROLES) + 1)
