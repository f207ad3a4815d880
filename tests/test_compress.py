"""Path-compressed (Patricia) layout + quantized metric columns.

Three layers of guarantees, mirroring the layout's contract:

* **structure** — the chain-run detector and the compressed encoding
  round-trip exactly (``expand_edges`` reproduces the plain edge table in
  DFS-position space) on mined, chain-heavy, random, and degenerate
  tries;
* **bit-parity** — every batched op (rule search, segmented top-k,
  item-scoped membership in all roles, prefix ranges, traversal reduce)
  over an UNQUANTIZED compressed trie is bit-identical (tie order
  included) to the plain layout, single-device and sharded at
  P ∈ {1, 2, 8} (multi-P lanes skip below their device count, and the
  multidevice CI tier re-runs the module with 8 host devices);
* **bounded error** — quantized columns reconstruct within documented
  bounds: int32 support counts ≤ 1/(2·n_tx) + 1 ulp, bf16 relative
  error ≤ 2^-8, int8 absolute error ≤ scale/2 (conviction is excluded
  from the quantized guarantees: its 1/(1-conf) pole amplifies any
  confidence rounding unboundedly near conf → 1).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.array_trie import (
    FrozenTrie,
    batched_rule_search,
    chain_spans,
    compress_pos_space,
    compressed_descend,
    quantize_metric_columns,
    reconstruct_paths,
    traverse_reduce,
)
from repro.core.synthetic import (
    device_trie_from_arrays,
    frozen_from_arrays,
    mixed_queries,
    random_csr_trie,
)
from repro.core.trie import TrieOfRules
from repro.kernels import ops
from repro.kernels.ref import rule_search_span_ref
from repro.kernels.rule_search import rule_search_span

METRICS = ("confidence", "lift", "support", "conviction")
ROLES = ("any", "antecedent", "consequent")


def _frozen(arrs) -> FrozenTrie:
    return frozen_from_arrays(arrs)


def _pair(arrs, **quant):
    """(plain DeviceTrie, compressed DeviceTrie) over one arrays dict."""
    return (
        device_trie_from_arrays(arrs),
        device_trie_from_arrays(arrs, layout="compressed", **quant),
    )


def _queries(arrs, q=24, width=7, seed=0):
    rng = np.random.RandomState(seed)
    qs, al = mixed_queries(rng, arrs, q, width)
    return jnp.asarray(qs), jnp.asarray(al)


def assert_all_ops_bitwise(dtp, dtc, arrs, seed=0):
    """Every batched op, plain vs compressed, assert_array_equal."""
    q, al = _queries(arrs, seed=seed)
    rp = ops.rule_search(dtp, q, al)
    rc = ops.rule_search(dtc, q, al)
    for k in rp:
        np.testing.assert_array_equal(
            np.asarray(rp[k]), np.asarray(rc[k]), err_msg=f"rule_search {k}"
        )
    for metric in METRICS:
        tp = ops.top_k_rules(dtp, 6, metric=metric)
        tc = ops.top_k_rules(dtc, 6, metric=metric)
        np.testing.assert_array_equal(
            np.asarray(tp["values"]), np.asarray(tc["values"]),
            err_msg=f"top_k {metric}",
        )
        np.testing.assert_array_equal(
            np.asarray(tp["node"]), np.asarray(tc["node"]),
            err_msg=f"top_k {metric} nodes",
        )
    ei = arrs["edge_item"]
    first = int(ei[0]) if ei.size else 0
    prefixes = [[], [first], [9999], [first, first + 1]]
    bp = ops.top_k_rules_batch(dtp, prefixes, 5)
    bc = ops.top_k_rules_batch(dtc, prefixes, 5)
    for k in ("values", "node"):
        np.testing.assert_array_equal(
            np.asarray(bp[k]), np.asarray(bc[k]), err_msg=f"batch {k}"
        )
    items = [0, 1, 2, first, 9999, 1]
    for role in ROLES:
        wp = ops.rules_with(dtp, items, role=role, k=5)
        wc = ops.rules_with(dtc, items, role=role, k=5)
        for k in ("values", "node"):
            np.testing.assert_array_equal(
                np.asarray(wp[k]), np.asarray(wc[k]),
                err_msg=f"rules_with {role} {k}",
            )
    tr_p, tr_c = ops.trie_reduce(dtp), ops.trie_reduce(dtc)
    for k in tr_p:
        # retiling-free here, but the compressed launch pads node columns
        # to the span kernel's geometry — sums stay within the documented
        # 1e-6 reassociation bound, count/max are exact
        np.testing.assert_allclose(
            np.asarray(tr_p[k]), np.asarray(tr_c[k]), rtol=1e-6,
            err_msg=f"trie_reduce {k}",
        )


# ----------------------------------------------------------------------
# detector + encoding structure
# ----------------------------------------------------------------------
class TestChainDetector:
    def test_hand_built_runs(self):
        #      pos: 0  1  2  3  4  5  6
        # children: 2  1  1  0  2  0  0   (chain 1->2 ending at 3)
        cc = np.array([2, 1, 1, 0, 2, 0, 0])
        is_span, run_end = chain_spans(cc)
        np.testing.assert_array_equal(
            is_span, [False, True, True, False, False, False, False]
        )
        assert run_end[1] == 3 and run_end[2] == 3

    def test_root_single_child_is_not_a_span(self):
        is_span, _ = chain_spans(np.array([1, 1, 0]))
        assert not is_span[0] and is_span[1]

    def test_empty(self):
        is_span, run_end = chain_spans(np.zeros((0,), np.int64))
        assert is_span.shape == (0,) and run_end.shape == (0,)

    def test_span_fraction_matches_detector(self, chain_trie):
        arrs = chain_trie(1200, chain_fraction=0.8)
        fz = _frozen(arrs)
        cc = np.diff(arrs["child_offsets"])[
            np.asarray(arrs["dfs_to_node"], np.int64)
        ]
        is_span, _ = chain_spans(cc)
        assert fz.span_fraction() == pytest.approx(
            is_span.sum() / fz.n_edges
        )


class TestRoundTrip:
    @pytest.mark.parametrize("cf", [0.0, 0.5, 1.0])
    def test_expand_edges_reproduces_plain_table(self, chain_trie, cf):
        arrs = chain_trie(600, chain_fraction=cf)
        fz = _frozen(arrs)
        ct = fz.compress()
        par, items, child = ct.expand_edges()
        dfs = np.asarray(fz.dfs_order, np.int64)
        want = np.zeros((fz.n_nodes,), np.int64)
        want_it = np.zeros((fz.n_nodes,), np.int64)
        want[dfs[fz.edge_child]] = dfs[np.asarray(fz.edge_parent, np.int64)]
        want_it[dfs[fz.edge_child]] = fz.edge_item
        np.testing.assert_array_equal(par, want[1:])
        np.testing.assert_array_equal(items, want_it[1:])
        np.testing.assert_array_equal(child, np.arange(1, fz.n_nodes))

    def test_compress_pos_space_counts(self, chain_trie):
        arrs = chain_trie(800, chain_fraction=0.9)
        fz = _frozen(arrs)
        ct = fz.compress()
        assert ct.n_edges == fz.n_edges
        assert ct.n_compressed_edges < fz.n_edges
        assert ct.span_fraction == pytest.approx(fz.span_fraction())
        # every span step is accounted for exactly once
        assert (
            ct.n_compressed_edges + int(np.sum(ct.edge_span))
            == fz.n_edges
        )

    def test_compressed_bytes_shrink_on_chains(self, chain_trie):
        arrs = chain_trie(2000, chain_fraction=0.9)
        dtp, dtc = _pair(arrs)
        assert dtc.nbytes() < dtp.nbytes()

    def test_mined_engines_agree_compressed(self, frozen, mined):
        fz = frozen()
        fz2 = mined(engine="arrays").frozen
        a, b = fz.compress(), fz2.compress()
        for name in ("edge_item", "edge_pos", "edge_span", "edge_tail",
                     "child_offsets", "item_pos"):
            np.testing.assert_array_equal(
                getattr(a, name), getattr(b, name), err_msg=name
            )


# ----------------------------------------------------------------------
# bit-parity: single device, all ops x all fixtures
# ----------------------------------------------------------------------
class TestBitParity:
    def test_mined_paper_trie(self, frozen):
        fz = frozen()
        dtp = fz.device_arrays()
        dtc = fz.device_arrays(layout="compressed")
        arrs = {
            "node_item": np.asarray(fz.node_item),
            "node_parent": np.asarray(fz.node_parent),
            "edge_item": np.asarray(fz.edge_item),
        }
        assert_all_ops_bitwise(dtp, dtc, arrs)

    @pytest.mark.parametrize("cf", [0.0, 0.6, 1.0])
    def test_chain_heavy(self, chain_trie, cf):
        arrs = chain_trie(1500, chain_fraction=cf)
        dtp, dtc = _pair(arrs)
        assert_all_ops_bitwise(dtp, dtc, arrs, seed=int(cf * 10))

    def test_random_irregular(self):
        rng = np.random.RandomState(11)
        arrs = random_csr_trie(rng, 700, 30)
        dtp, dtc = _pair(arrs)
        assert_all_ops_bitwise(dtp, dtc, arrs, seed=2)

    def test_kernel_matches_span_ref_and_core_oracle(self, chain_trie):
        arrs = chain_trie(900, chain_fraction=0.8)
        dtc = device_trie_from_arrays(arrs, layout="compressed")
        q, al = _queries(arrs, seed=4)
        out = rule_search_span(
            dtc.child_offsets, dtc.edge_item, dtc.edge_child,
            dtc.edge_span, dtc.edge_tail, dtc.node_item,
            dtc.support, dtc.confidence, dtc.lift, q, al,
            max_fanout=dtc.max_fanout,
        )
        ref = rule_search_span_ref(
            dtc.edge_parent, dtc.edge_item, dtc.edge_child,
            dtc.edge_span, dtc.edge_tail, dtc.node_item,
            dtc.support, dtc.confidence, dtc.lift, q, al,
        )
        for k in out:
            np.testing.assert_array_equal(
                np.asarray(out[k]), np.asarray(ref[k]), err_msg=k
            )
        core = batched_rule_search(dtc, q, al)
        for k in ("found", "support", "confidence", "lift"):
            np.testing.assert_array_equal(
                np.asarray(out[k]), np.asarray(core[k]), err_msg=k
            )

    def test_auto_layout_picks_compressed_on_chains(self, chain_trie):
        arrs = chain_trie(1000, chain_fraction=0.9)
        fz = _frozen(arrs)
        assert fz.device_arrays(layout="auto").layout == "compressed"
        rng = np.random.RandomState(3)
        branchy = random_csr_trie(rng, 400, 8)
        assert (
            _frozen(branchy).device_arrays(layout="auto").layout == "plain"
        )

    def test_traverse_reduce_and_descend(self, chain_trie):
        arrs = chain_trie(800, chain_fraction=0.7)
        dtp, dtc = _pair(arrs)
        a, b = traverse_reduce(dtp), traverse_reduce(dtc)
        for k in a:
            np.testing.assert_allclose(
                np.asarray(a[k]), np.asarray(b[k]), rtol=1e-6, err_msg=k
            )
        q, _ = _queries(arrs, seed=5)
        pos, ok = compressed_descend(dtc, q)
        # cross-check against the plain bucket descent via rule_search
        # (which additionally reports all-padding rows as not-found)
        al = jnp.zeros((q.shape[0],), jnp.int32)
        plain = ops.rule_search(dtp, q, al)
        pos, ok = np.asarray(pos), np.asarray(ok)
        found = ok & (pos > 0)
        got = np.asarray(dtc.dfs_to_node)[np.maximum(pos, 0)]
        np.testing.assert_array_equal(found, np.asarray(plain["found"]))
        np.testing.assert_array_equal(
            np.where(found, got, -1), np.asarray(plain["node"])
        )


# ----------------------------------------------------------------------
# degenerates
# ----------------------------------------------------------------------
class TestDegenerates:
    def test_empty_trie(self, empty_frozen):
        dtc = empty_frozen.device_arrays(layout="compressed")
        out = ops.rule_search(
            dtc, jnp.asarray([[0, 1, -1]], jnp.int32),
            jnp.asarray([1], jnp.int32),
        )
        assert not bool(out["found"][0])
        tk = ops.top_k_rules(dtc, 4)
        assert np.all(np.asarray(tk["node"]) == -1)
        ops.trie_reduce(dtc)

    def test_single_chain_trie(self):
        # root -> 0 -> 1 -> 2 -> 3: one maximal run, one compressed edge
        t = TrieOfRules()
        for depth in range(1, 5):
            leaf = t.insert(tuple(range(depth)))
            leaf.support, leaf.confidence, leaf.lift = 0.5, 0.5, 1.0
        fz = FrozenTrie.freeze(t)
        ct = fz.compress()
        assert ct.n_compressed_edges == 1
        assert int(ct.edge_span[0]) == 3
        dtc = ct.device_arrays()
        # the full path lands on the run tail; the prefix lands mid-span
        # (interior positions stay addressable through the node columns);
        # a diverging path misses
        q = jnp.asarray(
            [[0, 1, 2, 3], [0, 1, -1, -1], [0, 2, -1, -1]], jnp.int32
        )
        al = jnp.asarray([2, 1, 1], jnp.int32)
        out = ops.rule_search(dtc, q, al)
        np.testing.assert_array_equal(
            np.asarray(out["found"]), [True, True, False]
        )
        # compound confidence chains the per-step 0.5 along the consequent
        np.testing.assert_allclose(
            np.asarray(out["confidence"])[:2], [0.25, 0.5]
        )

    def test_empty_queries_and_zero_width(self, chain_trie):
        arrs = chain_trie(300)
        dtc = device_trie_from_arrays(arrs, layout="compressed")
        out = ops.rule_search(
            dtc, jnp.zeros((0, 3), jnp.int32), jnp.zeros((0,), jnp.int32)
        )
        assert out["found"].shape == (0,)
        out = ops.rule_search(
            dtc, jnp.zeros((2, 0), jnp.int32), jnp.zeros((2,), jnp.int32)
        )
        assert not np.any(np.asarray(out["found"]))

    def test_reconstruct_paths_rejects_compressed(self, chain_trie):
        dtc = device_trie_from_arrays(chain_trie(300), layout="compressed")
        with pytest.raises(ValueError):
            reconstruct_paths(dtc, jnp.asarray([1], jnp.int32), 8)


# ----------------------------------------------------------------------
# quantized columns: bounded reconstruction error
# ----------------------------------------------------------------------
class TestQuantized:
    N_TX = 4000

    def test_int32_support_counts_are_exact(self, chain_trie):
        arrs = chain_trie(800)
        sup = np.round(
            np.asarray(arrs["support"], np.float64) * self.N_TX
        ) / self.N_TX
        arrs = dict(arrs, support=sup.astype(np.float32))
        dtq = device_trie_from_arrays(
            arrs, layout="compressed", quantize=True,
            n_transactions=self.N_TX,
        )
        assert dtq.support.dtype == jnp.int32
        # counts / n_tx reconstructs the exact ratio to 1 ulp
        got = np.asarray(dtq.support, np.float64) / self.N_TX
        want = sup[np.asarray(arrs["dfs_to_node"], np.int64)]
        np.testing.assert_allclose(got, want, rtol=1.2e-7)

    @pytest.mark.parametrize("columns", ["bf16", "int8"])
    def test_column_error_bounds(self, chain_trie, columns):
        arrs = chain_trie(800)
        sup = np.asarray(arrs["support"], np.float32)
        conf = np.asarray(arrs["confidence"], np.float32)
        lift = np.asarray(arrs["lift"], np.float32)
        sq, cq, lq, n_tx, cs, ls = quantize_metric_columns(
            sup, conf, lift, self.N_TX, columns
        )
        if columns == "bf16":
            err = np.abs(np.asarray(cq, np.float32) - conf) / conf
            assert err.max() <= 2 ** -8
        else:
            err = np.abs(np.asarray(cq, np.float32) * cs - conf)
            assert err.max() <= cs / 2 + 1e-7
            err_l = np.abs(np.asarray(lq, np.float32) * ls - lift)
            assert err_l.max() <= ls / 2 + 1e-7

    @pytest.mark.parametrize("columns", ["bf16", "int8"])
    def test_ops_within_documented_bounds(self, chain_trie, columns):
        arrs = chain_trie(1000, chain_fraction=0.7)
        dtp = device_trie_from_arrays(arrs)
        dtq = device_trie_from_arrays(
            arrs, layout="compressed", quantize=True,
            n_transactions=self.N_TX, columns=columns,
        )
        q, al = _queries(arrs, seed=7)
        rp = ops.rule_search(dtp, q, al)
        rq = ops.rule_search(dtq, q, al)
        # structure is exact — only metric VALUES are approximate
        np.testing.assert_array_equal(
            np.asarray(rp["found"]), np.asarray(rq["found"])
        )
        np.testing.assert_array_equal(
            np.asarray(rp["node"]), np.asarray(rq["node"])
        )
        m = np.asarray(rp["found"])
        rtol = 2e-2 if columns == "bf16" else 6e-2
        for k in ("support", "confidence", "lift"):
            np.testing.assert_allclose(
                np.asarray(rq[k])[m], np.asarray(rp[k])[m], rtol=rtol,
                err_msg=k,
            )
        # rank order survives for support (exact counts): same winners
        tp = ops.top_k_rules(dtp, 5, metric="support")
        tq = ops.top_k_rules(dtq, 5, metric="support")
        np.testing.assert_array_equal(
            np.asarray(tp["node"]), np.asarray(tq["node"])
        )
        tr_p, tr_q = ops.trie_reduce(dtp), ops.trie_reduce(dtq)
        np.testing.assert_allclose(
            np.asarray(tr_q["support_sum"]),
            np.asarray(tr_p["support_sum"]), rtol=1e-4,
        )

    def test_kernel_bitwise_vs_ref_on_quantized(self, chain_trie):
        """Quantized columns: the KERNEL still matches its oracle bitwise
        (both dequantize identically in fp32) — the error is purely in
        the stored values, never in the computation."""
        arrs = chain_trie(700)
        dtq = device_trie_from_arrays(
            arrs, layout="compressed", quantize=True,
            n_transactions=self.N_TX,
        )
        q, al = _queries(arrs, seed=9)
        dq = dict(
            n_transactions=dtq.n_transactions,
            confidence_scale=dtq.confidence_scale,
            lift_scale=dtq.lift_scale,
        )
        out = rule_search_span(
            dtq.child_offsets, dtq.edge_item, dtq.edge_child,
            dtq.edge_span, dtq.edge_tail, dtq.node_item,
            dtq.support, dtq.confidence, dtq.lift, q, al,
            max_fanout=dtq.max_fanout, **dq,
        )
        ref = rule_search_span_ref(
            dtq.edge_parent, dtq.edge_item, dtq.edge_child,
            dtq.edge_span, dtq.edge_tail, dtq.node_item,
            dtq.support, dtq.confidence, dtq.lift, q, al, **dq,
        )
        for k in out:
            np.testing.assert_array_equal(
                np.asarray(out[k]), np.asarray(ref[k]), err_msg=k
            )


# ----------------------------------------------------------------------
# sharded parity at P in {1, 2, 8}
# ----------------------------------------------------------------------
SHARD_COUNTS = (1, 2, 8)


def needs_devices(p):
    return pytest.mark.skipif(
        jax.device_count() < p,
        reason=f"needs {p} devices (run under XLA_FLAGS="
               f"--xla_force_host_platform_device_count=8)",
    )


@pytest.mark.parametrize(
    "p", [pytest.param(p, marks=needs_devices(p)) for p in SHARD_COUNTS]
)
class TestShardedCompressed:
    def _fixture(self, chain_trie):
        arrs = chain_trie(1200, chain_fraction=0.7, seed=2)
        return arrs, _frozen(arrs)

    def test_rule_search_bitwise(self, chain_trie, p):
        from repro.distributed.trie_sharding import (
            shard_device_trie, sharded_rule_search_batch,
        )
        from repro.launch.mesh import make_trie_mesh

        arrs, fz = self._fixture(chain_trie)
        plan = shard_device_trie(
            fz, make_trie_mesh(p), layout="compressed"
        )
        q, al = _queries(arrs, seed=3)
        want = ops.rule_search(fz.device_arrays(), q, al)
        got = sharded_rule_search_batch(plan, np.asarray(q), np.asarray(al))
        for k in want:
            np.testing.assert_array_equal(
                np.asarray(want[k]), np.asarray(got[k]), err_msg=k
            )

    def test_rank_and_membership_bitwise(self, chain_trie, p):
        from repro.distributed.trie_sharding import (
            shard_device_trie, sharded_rules_with,
            sharded_top_k_rules_batch,
        )
        from repro.launch.mesh import make_trie_mesh

        arrs, fz = self._fixture(chain_trie)
        plan = shard_device_trie(
            fz, make_trie_mesh(p), layout="compressed"
        )
        dtp = fz.device_arrays()
        first = int(arrs["edge_item"][0])
        prefixes = [[], [first], [9999]]
        want = ops.top_k_rules_batch(dtp, prefixes, 5)
        got = sharded_top_k_rules_batch(plan, prefixes, 5)
        for k in ("values", "node"):
            np.testing.assert_array_equal(
                np.asarray(want[k]), np.asarray(got[k]), err_msg=k
            )
        items = [0, 1, first, 9999]
        for role in ROLES:
            w = ops.rules_with(dtp, items, role=role, k=4)
            g = sharded_rules_with(plan, items, role=role, k=4)
            for k in ("values", "node"):
                np.testing.assert_array_equal(
                    np.asarray(w[k]), np.asarray(g[k]),
                    err_msg=f"{role} {k}",
                )

    def test_quantized_sharded_matches_single_device_quantized(
        self, chain_trie, p
    ):
        from repro.distributed.trie_sharding import (
            shard_device_trie, sharded_rule_search_batch,
        )
        from repro.launch.mesh import make_trie_mesh

        arrs, fz = self._fixture(chain_trie)
        plan = shard_device_trie(
            fz, make_trie_mesh(p), layout="compressed",
            quantize=True, n_transactions=4000,
        )
        dtq = fz.device_arrays(
            layout="compressed", quantize=True, n_transactions=4000
        )
        q, al = _queries(arrs, seed=3)
        want = ops.rule_search(dtq, q, al)
        got = sharded_rule_search_batch(plan, np.asarray(q), np.asarray(al))
        for k in want:
            np.testing.assert_array_equal(
                np.asarray(want[k]), np.asarray(got[k]), err_msg=k
            )

    def test_masked_compressed_plan_degrades(self, chain_trie, p):
        if p < 2:
            pytest.skip("masking needs >= 2 shards")
        from repro.distributed.trie_sharding import (
            mask_dead_shards, shard_device_trie,
            sharded_rule_search_batch,
        )
        from repro.launch.mesh import make_trie_mesh

        arrs, fz = self._fixture(chain_trie)
        plan = shard_device_trie(
            fz, make_trie_mesh(p), layout="compressed"
        )
        deg = mask_dead_shards(plan, [p - 1])
        q, al = _queries(arrs, seed=3)
        base = sharded_rule_search_batch(plan, np.asarray(q), np.asarray(al))
        got = sharded_rule_search_batch(deg, np.asarray(q), np.asarray(al))
        bf = np.asarray(base["found"])
        gf = np.asarray(got["found"])
        assert gf.sum() <= bf.sum()
        assert not np.any(gf & ~bf)

    # -- dead-shard masking x compressed spans -------------------------
    # The span pool is the layout's danger zone for masking: a dead
    # shard's interior (path-compressed) nodes live ONLY in its s_*
    # position-space columns, and a descent can land mid-span.  These
    # cases pin the whole masked surface: masked-plain and
    # masked-compressed must stay bitwise twins for every op, dead-routed
    # descents must return the not-found contract, and live-shard rows
    # must be untouched.

    def _span_paths(self, fz):
        """Rule paths ending mid-span: interior nodes of single-child
        runs (parent fan-out 1 AND own fan-out 1, depth >= 2)."""
        co = np.asarray(fz.child_offsets)
        fan = co[1:] - co[:-1]
        parent = np.asarray(fz.node_parent)
        depth = np.asarray(fz.node_depth)
        mid = np.nonzero(
            (depth >= 2) & (fan == 1) & (fan[parent] == 1)
        )[0]
        item = np.asarray(fz.node_item)

        def path(n):
            out = []
            while n != 0:
                out.append(int(item[n]))
                n = int(parent[n])
            return out[::-1]

        return [path(n) for n in mid[:48]]

    def _routes(self, fz, ranges, heads):
        """Owning shard per depth-1 head item (-1 when absent)."""
        co = np.asarray(fz.child_offsets)
        ei = np.asarray(fz.edge_item)
        ec = np.asarray(fz.edge_child)
        dfs = np.asarray(fz.dfs_order)
        lo, hi = int(co[0]), int(co[1])
        out = []
        for it in heads:
            j = int(np.searchsorted(ei[lo:hi], it))
            node = (
                int(ec[lo + j])
                if j < hi - lo and int(ei[lo + j]) == it else -1
            )
            pos = int(dfs[node]) if node > 0 else -1
            s = -1
            for si, (rlo, rhi) in enumerate(ranges):
                if pos >= 0 and rlo <= pos < rhi:
                    s = si
            out.append(s)
        return np.asarray(out)

    def test_masked_plain_compressed_bitwise(self, chain_trie, p):
        """Every batched op over a dead-shard-masked plan: plain and
        compressed layouts answer bit-identically (tie order included),
        including descents landing mid-span inside the DEAD shard."""
        if p < 2:
            pytest.skip("masking needs >= 2 shards")
        from repro.distributed.trie_sharding import (
            mask_dead_shards, shard_device_trie,
            sharded_rule_search_batch, sharded_rules_with,
            sharded_top_k_rules_batch,
        )
        from repro.launch.mesh import make_trie_mesh

        arrs, fz = self._fixture(chain_trie)
        mesh = make_trie_mesh(p)
        pp = shard_device_trie(fz, mesh, layout="plain")
        pc = shard_device_trie(fz, mesh, layout="compressed")
        paths = self._span_paths(fz)
        pairs = [
            (s[: max(1, len(s) // 2)], s[max(1, len(s) // 2):])
            for s in paths if len(s) >= 2
        ]
        q, al = fz.canonicalize_queries(
            [a for a, _ in pairs], [c for _, c in pairs]
        )
        q, al = np.asarray(q), np.asarray(al)
        first = int(arrs["edge_item"][0])
        prefixes = [[], [first], [9999]]
        items = [0, 1, 2, first, 9999]
        for dead in ([0], [p - 1], [0, p - 1]):
            if len(dead) >= p:
                continue
            dp = mask_dead_shards(pp, dead)
            dc = mask_dead_shards(pc, dead)
            rp = sharded_rule_search_batch(dp, q, al)
            rc = sharded_rule_search_batch(dc, q, al)
            for k in rp:
                np.testing.assert_array_equal(
                    np.asarray(rp[k]), np.asarray(rc[k]),
                    err_msg=f"dead={dead} rule_search {k}",
                )
            for metric in METRICS:
                tp = sharded_top_k_rules_batch(dp, prefixes, 6,
                                               metric=metric)
                tc = sharded_top_k_rules_batch(dc, prefixes, 6,
                                               metric=metric)
                for k in ("values", "node"):
                    np.testing.assert_array_equal(
                        np.asarray(tp[k]), np.asarray(tc[k]),
                        err_msg=f"dead={dead} top_k {metric} {k}",
                    )
            for role in ROLES:
                wp = sharded_rules_with(dp, items, role=role, k=5)
                wc = sharded_rules_with(dc, items, role=role, k=5)
                for k in ("values", "node"):
                    np.testing.assert_array_equal(
                        np.asarray(wp[k]), np.asarray(wc[k]),
                        err_msg=f"dead={dead} rules_with {role} {k}",
                    )

    def test_masked_midspan_dead_vs_live_rows(self, chain_trie, p):
        """Mid-span landings split by routing: a descent into the dead
        shard returns the not-found contract (False / -1 / 0.0); a row
        whose antecedent AND consequent both route to live shards is
        bit-identical to the unmasked plan."""
        if p < 2:
            pytest.skip("masking needs >= 2 shards")
        from repro.distributed.trie_sharding import (
            mask_dead_shards, shard_device_trie,
            sharded_rule_search_batch,
        )
        from repro.launch.mesh import make_trie_mesh

        arrs, fz = self._fixture(chain_trie)
        plan = shard_device_trie(
            fz, make_trie_mesh(p), layout="compressed"
        )
        paths = self._span_paths(fz)
        pairs = [
            (s[: max(1, len(s) // 2)], s[max(1, len(s) // 2):])
            for s in paths if len(s) >= 2
        ]
        q, al = fz.canonicalize_queries(
            [a for a, _ in pairs], [c for _, c in pairs]
        )
        q, al = np.asarray(q), np.asarray(al)
        ant_route = self._routes(fz, plan.ranges, q[:, 0])
        con_head = q[np.arange(len(q)), al]
        con_route = self._routes(fz, plan.ranges, con_head)
        # kill the shard most mid-span landings route to, so the dead
        # set is guaranteed to receive descents
        hit, counts = np.unique(
            ant_route[ant_route >= 0], return_counts=True
        )
        dead = [int(hit[np.argmax(counts)])]
        deg = mask_dead_shards(plan, dead)
        full = sharded_rule_search_batch(plan, q, al)
        got = sharded_rule_search_batch(deg, q, al)
        dead_rows = np.isin(ant_route, dead)
        live_rows = ~dead_rows & ~np.isin(con_route, dead)
        assert dead_rows.any(), "fixture routed nothing to the dead shard"
        assert live_rows.any(), "fixture routed nothing to live shards"
        gf = np.asarray(got["found"])
        assert not gf[dead_rows].any()
        np.testing.assert_array_equal(
            np.asarray(got["node"])[dead_rows], -1
        )
        for k in ("support", "confidence", "lift"):
            np.testing.assert_array_equal(
                np.asarray(got[k])[dead_rows], 0.0, err_msg=f"dead {k}"
            )
        for k in full:
            np.testing.assert_array_equal(
                np.asarray(got[k])[live_rows],
                np.asarray(full[k])[live_rows], err_msg=f"live {k}",
            )

    def test_masked_quantized_compressed(self, chain_trie, p):
        """Masking composes with the quantized span pool: dead-shard
        rows still blank to the not-found contract and the masked plan
        matches the masked UNQUANTIZED plan's found/node columns."""
        if p < 2:
            pytest.skip("masking needs >= 2 shards")
        from repro.distributed.trie_sharding import (
            mask_dead_shards, shard_device_trie,
            sharded_rule_search_batch,
        )
        from repro.launch.mesh import make_trie_mesh

        arrs, fz = self._fixture(chain_trie)
        mesh = make_trie_mesh(p)
        pc = shard_device_trie(fz, mesh, layout="compressed")
        pq = shard_device_trie(
            fz, mesh, layout="compressed",
            quantize=True, n_transactions=4000,
        )
        paths = self._span_paths(fz)
        pairs = [
            (s[: max(1, len(s) // 2)], s[max(1, len(s) // 2):])
            for s in paths if len(s) >= 2
        ]
        q, al = fz.canonicalize_queries(
            [a for a, _ in pairs], [c for _, c in pairs]
        )
        q, al = np.asarray(q), np.asarray(al)
        dead = [p - 1]
        gc = sharded_rule_search_batch(mask_dead_shards(pc, dead), q, al)
        gq = sharded_rule_search_batch(mask_dead_shards(pq, dead), q, al)
        for k in ("found", "node"):
            np.testing.assert_array_equal(
                np.asarray(gc[k]), np.asarray(gq[k]), err_msg=k
            )
        nf = ~np.asarray(gq["found"])
        for k in ("support", "confidence", "lift"):
            np.testing.assert_array_equal(
                np.asarray(gq[k])[nf], 0.0, err_msg=k
            )


# ----------------------------------------------------------------------
# the int8 gradient-compression helpers, wired into the encoder
# ----------------------------------------------------------------------
class TestInt8Compression:
    def test_quantize_round_trip_bound(self):
        from repro.distributed.compression import (
            dequantize_int8, quantize_int8,
        )

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(257).astype(np.float32) * 3)
        q, scale = quantize_int8(x)
        assert q.dtype == jnp.int8
        err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
        assert err.max() <= float(scale) / 2 + 1e-7

    def test_zero_input_is_stable(self):
        from repro.distributed.compression import (
            dequantize_int8, quantize_int8,
        )

        q, scale = quantize_int8(jnp.zeros((8,), jnp.float32))
        assert float(scale) > 0
        np.testing.assert_array_equal(
            np.asarray(dequantize_int8(q, scale)), np.zeros(8)
        )

    def test_error_feedback_residual_identity(self):
        from repro.distributed.compression import (
            ErrorFeedbackInt8, dequantize_int8, quantize_int8,
        )

        rng = np.random.RandomState(1)
        grads = {"w": jnp.asarray(rng.randn(64).astype(np.float32))}
        ef = ErrorFeedbackInt8()
        res = ef.init(grads)
        np.testing.assert_array_equal(np.asarray(res["w"]), np.zeros(64))
        dq, res2 = ef.compress(grads, res)
        # dq + residual' == grads + residual (nothing is lost, only delayed)
        np.testing.assert_allclose(
            np.asarray(dq["w"]) + np.asarray(res2["w"]),
            np.asarray(grads["w"]), rtol=1e-6,
        )
        # second step folds the carried residual in
        dq2, _ = ef.compress(grads, res2)
        q, s = quantize_int8(grads["w"] + res2["w"])
        np.testing.assert_array_equal(
            np.asarray(dq2["w"]), np.asarray(dequantize_int8(q, s))
        )

    def test_compressed_psum_single_device(self):
        from repro.distributed.compression import compressed_psum
        from repro.launch.mesh import make_trie_mesh

        mesh = make_trie_mesh(1)
        x = jnp.asarray(np.linspace(-2, 2, 128, dtype=np.float32))
        out = compressed_psum(x, "data", mesh)
        q_err = float(jnp.max(jnp.abs(x))) / 127.0
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(x), atol=q_err / 2 + 1e-7
        )

    def test_encoder_int8_columns_use_same_scale_convention(self):
        from repro.distributed.compression import quantize_int8

        rng = np.random.RandomState(2)
        conf = rng.rand(300).astype(np.float32)
        lift = (rng.rand(300) * 2).astype(np.float32)
        sup = rng.rand(300).astype(np.float32)
        _, cq, lq, _, cs, ls = quantize_metric_columns(
            sup, conf, lift, 1000, "int8"
        )
        wq, ws = quantize_int8(jnp.asarray(conf))
        np.testing.assert_array_equal(np.asarray(cq), np.asarray(wq))
        assert cs == pytest.approx(float(ws))
        assert ls == pytest.approx(float(lift.max()) / 127.0)
