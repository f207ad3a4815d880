"""Sharded multi-device Trie of Rules: subtree-range partitioning +
shard_map-aware batched query engine.

The serving north star is one frozen trie answering batched queries from
many devices' worth of traffic; the structure that makes this clean is the
DFS-contiguous relabeling (``array_trie.dfs_layout``): every depth-1
subtree (a root child and everything under it) is ONE contiguous DFS
position range, and those ranges tile ``[1, N)`` consecutively.  Subtree
ranges are therefore the natural shard boundary — the same observation
that drives distribution of the mining structure (not the miner) in the
Hadoop Apriori literature and the memory partitioning of hybrid tries.

``shard_device_trie`` cuts the trie into P contiguous DFS ranges by greedy
bin-packing over the depth-1 ``subtree_size`` metadata
(``FrozenTrie.depth1_subtrees``; pointer oracle
``TrieOfRules.depth1_subtree_sizes``), then builds a ``ShardedDeviceTrie``
pytree whose leaves are ``[P, ...]`` stacks placed with
``NamedSharding(mesh, P("data"))`` over the 1-D trie mesh
(``launch.mesh.make_trie_mesh``) — each device holds:

* its DFS slice of the metric/depth/item columns (the rank + membership
  kernels' inputs),
* its slice of the posting lists, co-partitioned by item IN LOCAL DFS
  COORDINATES (legal because shards are unions of whole depth-1 subtrees,
  so every posting's subtree range is shard-local — the laminar
  range-count never needs a remote posting),
* a relabeled local edge table + CSR buckets for the fused rule-search
  descent.  The root and its (item-sorted) bucket are the replicated hub:
  every local trie keeps local id 0 = the global root, with the root
  bucket restricted to the shard's own depth-1 children — a query's first
  item routes it to exactly ONE shard, which is what makes the found-
  winner merge exact.

Two small ``[N]``/``[E]`` int32 back-map tables (DFS position → node id,
posting index → node id) stay replicated; everything metric- or
edge-sized is sharded.

The three batched query ops then run under ``shard_map``: every device
executes the UNCHANGED single-device Pallas kernel over its local range
and the per-device k-best lists / search verdicts merge with

* a k-best ``all_gather`` + static fold through ``rank.rank_merge`` (the
  same (value desc, pos asc) rank-scatter the in-kernel ``kbest_update``
  uses), for the ranked ops — positions are globalized before the merge,
  and because shard ranges ascend in DFS order the merged tie order is
  bit-identical to the single-device kernel;
* a found-winner select for ``rule_search`` — at most one shard can
  complete a descent — plus a max-merge of the consequent-path Support
  (the descent's ``con_support`` output) so compound-consequent lift
  (paper Eq. 1-4) is re-assembled globally even when the consequent path
  lives on a different shard than the rule's main path.

All of this is CI-testable on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the multi-device
tier: ``make test-multidevice``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.array_trie import (
    AUTO_COMPRESS_SPAN_FRACTION,
    FrozenTrie,
    canonical_prefix_rows,
    compress_pos_space,
    sanitize_query_items,
)
from repro.kernels.item_index import ROLES, rules_with_pallas
from repro.kernels.metrics_inkernel import RANK_METRICS, compound_lift
# ops only imports THIS module lazily (inside its dispatch helper), so a
# module-scope import back into it is cycle-safe — and keeps the
# interpret-mode heuristic in exactly one place.
from repro.kernels.ops import (
    InvalidQueryError,
    TrieQueryError,
    _interpret,
    dedup_query_rows,
)
from repro.kernels.rank import LANE, rank_merge, topk_rank_batch_pallas
from repro.kernels.rule_search import rule_search_fused, rule_search_span

_BIG = 2**30


class ShardFailure(TrieQueryError):
    """A specific trie shard is unhealthy (raised by fault injection or a
    real per-device launch failure).  Deliberately NOT retryable under
    ``kernels.ops.is_retryable`` — re-launching on the same sharded
    backend hits the same dead shard; the serve loop's ``ShardHealth``
    handles it by demoting to the replicated backend or to a
    dead-shard-masked degraded plan (``mask_dead_shards``)."""

    def __init__(self, shard: int, message: str = ""):
        self.shard = int(shard)
        super().__init__(
            message or f"shard {self.shard} failed"
        )


def _shard_map(fn, mesh: Mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


# ----------------------------------------------------------------------
# partitioning: greedy contiguous bin-packing over depth-1 subtree sizes
# ----------------------------------------------------------------------
def _greedy_bounds(sizes: Sequence[int], n_shards: int) -> List[Tuple[int, int]]:
    m = len(sizes)
    bounds: List[Tuple[int, int]] = []
    i = 0
    remaining = int(np.sum(sizes)) if m else 0
    for b in range(n_shards):
        bins_left = n_shards - b
        if i >= m or remaining <= 0:
            bounds.append((i, i))
            continue
        target = remaining / bins_left
        acc = 0
        j = i
        while j < m:
            nxt = int(sizes[j])
            overshoot = (acc + nxt) - target
            if (
                acc > 0 and bins_left > 1 and overshoot > 0
                and overshoot > target - acc
            ):
                break
            acc += nxt
            j += 1
            if acc >= target:
                break
        bounds.append((i, j))
        remaining -= acc
        i = j
    if i < m:
        lo, _ = bounds[-1]
        bounds[-1] = (lo, m)
    return bounds


def _bin_loads(sizes: Sequence[int], bounds: Sequence[Tuple[int, int]]):
    return [int(np.sum(sizes[a:b])) if b > a else 0 for a, b in bounds]


def plan_shard_bounds(
    sizes: Sequence[int],
    n_shards: int,
    hub_buckets=None,
    c: Optional[float] = None,
    prev_bounds: Optional[Sequence[Tuple[int, int]]] = None,
    drift: Optional[float] = None,
) -> List[Tuple[int, int]]:
    """Greedy contiguous partition of depth-1 subtrees into ``n_shards``
    bins.

    ``sizes`` are the subtree sizes in DFS order; bin ``b`` receives the
    contiguous run ``sizes[a_b:a_{b+1}]``.  Each bin fills toward the
    running ideal ``remaining / bins_left`` and closes at the cut nearest
    that target: the next subtree is still taken when overshooting by it
    beats stopping short (and always when the bin is empty — a single
    giant subtree must land somewhere).  Trailing bins may come out empty
    when there are fewer subtrees than shards; leftovers (a final
    oversized run) fold into the last bin.

    ``prev_bounds`` + ``drift`` gate REBALANCING on load drift: when a
    previous partition of the same subtree list is still within
    ``(1 + drift)`` of the fresh plan's max load, it is returned
    unchanged — streaming folds then keep their resident shard layout
    (no re-upload churn) until the delta actually skews the load.

    ``hub_buckets`` + ``c`` trigger HUB REFINEMENT: when the plan's max
    load exceeds ``c * ideal`` because one bin is a single hub subtree,
    the planner recurses ONE level into that hub's child buckets
    (``hub_buckets`` maps subtree index -> its depth-2 bucket sizes) and
    re-plans over the refined unit list.  The return then becomes
    ``(bounds, units)`` where ``units[u] = (subtree, bucket)`` (bucket
    ``-1`` = the hub node itself, whole subtrees keep bucket ``-1``) and
    ``bounds`` indexes ``units`` — cuts may land INSIDE a refined hub.
    ``shard_device_trie`` cannot realize interior cuts yet (its local
    relabeling and posting co-partition assume whole depth-1 subtrees;
    spine replication is the recorded follow-on), so refined plans feed
    load accounting, insert routing, and the streaming bench — not the
    device layout.
    """
    bounds = _greedy_bounds(sizes, n_shards)
    if prev_bounds is not None and drift is not None:
        prev = [tuple(map(int, b)) for b in prev_bounds]
        valid = (
            len(prev) == n_shards
            and prev[0][0] == 0
            and all(b[1] == nb[0] for b, nb in zip(prev, prev[1:]))
            and (prev[-1][1] == len(sizes))
        )
        if valid:
            prev_max = max(_bin_loads(sizes, prev), default=0)
            new_max = max(_bin_loads(sizes, bounds), default=0)
            if prev_max <= (1.0 + float(drift)) * new_max:
                return prev
    if hub_buckets is None or c is None:
        return bounds
    total = int(np.sum(sizes)) if len(sizes) else 0
    ideal = total / max(n_shards, 1)
    loads = _bin_loads(sizes, bounds)
    units: List[Tuple[int, int]] = [(t, -1) for t in range(len(sizes))]
    refined: List[int] = []
    if loads and max(loads) > c * ideal:
        b = int(np.argmax(loads))
        a, e = bounds[b]
        if e - a == 1 and len(hub_buckets.get(a, ())) > 0:
            refined.append(a)
    if not refined:
        return bounds
    r_sizes: List[int] = []
    r_units: List[Tuple[int, int]] = []
    for t, sz in enumerate(sizes):
        if t in refined:
            buckets = list(hub_buckets[t])
            r_sizes.append(1)             # the hub node itself
            r_units.append((t, -1))
            for bi, bsz in enumerate(buckets):
                r_sizes.append(int(bsz))
                r_units.append((t, bi))
        else:
            r_sizes.append(int(sz))
            r_units.append((t, -1))
    return _greedy_bounds(r_sizes, n_shards), r_units


def shard_dfs_ranges(
    frozen: FrozenTrie,
    n_shards: int,
    prev_ranges: Optional[Sequence[Tuple[int, int]]] = None,
    drift: Optional[float] = None,
) -> List[Tuple[int, int]]:
    """P contiguous DFS ranges tiling ``[0, N)``, cut at depth-1 subtree
    boundaries (shard 0 additionally absorbs the root at position 0).

    ``prev_ranges`` + ``drift`` pass through to ``plan_shard_bounds``'s
    drift gate (ranges convert to subtree bounds when they still align
    with the current trie's depth-1 boundaries): a staggered streaming
    re-freeze that barely moved the load keeps its previous cuts.
    """
    _kids, _los, sizes = frozen.depth1_subtrees()
    cum = np.concatenate([[0], np.cumsum(sizes, dtype=np.int64)])
    prev_bounds = None
    if prev_ranges is not None and drift is not None:
        pb: List[Tuple[int, int]] = []
        edges = [0] + [int(hi) for _, hi in prev_ranges]
        ok = len(prev_ranges) == n_shards
        for lo_e, hi_e in zip(edges, edges[1:]):
            a = int(np.searchsorted(1 + cum, max(lo_e, 1)))
            b = int(np.searchsorted(1 + cum, max(hi_e, 1)))
            if (
                a >= len(cum) or 1 + int(cum[a]) != max(lo_e, 1)
                or b >= len(cum) or 1 + int(cum[b]) != max(hi_e, 1)
            ):
                ok = False       # old cut no longer on a subtree boundary
                break
            pb.append((a, b))
        if ok:
            prev_bounds = pb
    bounds = plan_shard_bounds(
        sizes, n_shards, prev_bounds=prev_bounds, drift=drift
    )
    ranges: List[Tuple[int, int]] = []
    for d, (a, b) in enumerate(bounds):
        lo = 1 + int(cum[a])
        hi = 1 + int(cum[b])
        if d == 0:
            lo = 0
        ranges.append((lo, hi))
    return ranges


def hub_child_buckets(frozen: FrozenTrie) -> Dict[int, List[int]]:
    """Depth-2 bucket sizes per depth-1 subtree (subtree index in DFS
    order -> its children's subtree sizes) — the one-level recursion
    input for ``plan_shard_bounds`` hub refinement."""
    kids, _los, _sizes = frozen.depth1_subtrees()
    co = np.asarray(frozen.child_offsets)
    ec = np.asarray(frozen.edge_child)
    sub = np.asarray(frozen.subtree_size)
    out: Dict[int, List[int]] = {}
    for t, v in enumerate(kids):
        lo, hi = int(co[v]), int(co[v + 1])
        if hi > lo:
            out[t] = [int(sub[ec[j]]) for j in range(lo, hi)]
    return out


# ----------------------------------------------------------------------
# the sharded device structure
# ----------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclass
class ShardedDeviceTrie:
    """Device-side view of a P-way sharded frozen trie.

    Every leaf is a ``[P, ...]`` stack sharded over the ``("data",)`` mesh
    axis (leading dim = shard), except the two ``g_*`` back-map tables,
    which are replicated (they are gather-only id translations).  Static
    metadata rides in the pytree aux so jitted callers specialize on it.
    """

    # DFS-ordered node columns, shard slices (padding: 0 / depth -1 /
    # item -2 — never selected, never matched)
    support: jax.Array        # f32 [P, L]
    confidence: jax.Array     # f32 [P, L]
    lift: jax.Array           # f32 [P, L]
    depth: jax.Array          # int32 [P, L]
    node_item: jax.Array      # int32 [P, L]
    dfs_base: jax.Array       # int32 [P] global DFS start of the slice
    dfs_len: jax.Array        # int32 [P] live length of the slice
    # item-inverted index, co-partitioned by item, LOCAL DFS coordinates
    post_lo: jax.Array        # int32 [P, W] subtree starts (asc per item)
    post_hi: jax.Array        # int32 [P, W] subtree ends (sorted per item)
    p_support: jax.Array      # f32 [P, W] posting-ordered metric columns
    p_confidence: jax.Array   # f32 [P, W]
    p_lift: jax.Array         # f32 [P, W]
    p_depth: jax.Array        # int32 [P, W]
    # relabeled local subforest (root = local id 0) for the fused descent
    child_offsets: jax.Array  # int32 [P, CO] local CSR buckets
    edge_item: jax.Array      # int32 [P, E'] (pad -7)
    edge_child: jax.Array     # int32 [P, E'] local child ids (pad -1)
    edge_conf: jax.Array      # f32 [P, E']
    edge_sup: jax.Array       # f32 [P, E']
    edge_lift: jax.Array      # f32 [P, E']
    l2g: jax.Array            # int32 [P, NL] local node id -> global id
    # compressed layout only: span edge columns + position-space node
    # columns with the replicated-root slot at local position 0 (the span
    # descent reads metrics off nodes, not edges).  [P, 1] dummies when
    # the plan is plain (and vice versa for the edge metric columns).
    edge_pos: jax.Array       # int32 [P, E'] child LOCAL DFS position
    edge_span: jax.Array      # int32 [P, E'] interior steps after child
    edge_tail: jax.Array      # int32 [P, E'] local compressed tail id
    s_item: jax.Array         # int32 [P, NL] (pad -2)
    s_support: jax.Array      # f32|int32|bf16 [P, NL]
    s_confidence: jax.Array   # f32|bf16|int8 [P, NL]
    s_lift: jax.Array         # f32|bf16|int8 [P, NL]
    # replicated back-map tables (global position/posting -> node id)
    g_dfs_to_node: jax.Array  # int32 [N]
    g_item_nodes: jax.Array   # int32 [E]
    # static
    n_shards: int = 1
    max_fanout: int = 0       # max local bucket width across shards
    max_postings: int = 0     # global longest posting list
    layout: str = "plain"
    n_transactions: int = 0   # compressed quantization statics
    confidence_scale: float = 1.0
    lift_scale: float = 1.0

    _LEAVES = (
        "support", "confidence", "lift", "depth", "node_item",
        "dfs_base", "dfs_len",
        "post_lo", "post_hi",
        "p_support", "p_confidence", "p_lift", "p_depth",
        "child_offsets", "edge_item", "edge_child",
        "edge_conf", "edge_sup", "edge_lift", "l2g",
        "edge_pos", "edge_span", "edge_tail",
        "s_item", "s_support", "s_confidence", "s_lift",
        "g_dfs_to_node", "g_item_nodes",
    )

    def tree_flatten(self):
        return (
            tuple(getattr(self, f) for f in self._LEAVES),
            (
                self.n_shards, self.max_fanout, self.max_postings,
                self.layout, self.n_transactions,
                self.confidence_scale, self.lift_scale,
            ),
        )

    @classmethod
    def tree_unflatten(cls, aux, fields):
        return cls(
            *fields, n_shards=aux[0], max_fanout=aux[1],
            max_postings=aux[2], layout=aux[3], n_transactions=aux[4],
            confidence_scale=aux[5], lift_scale=aux[6],
        )

    def _dequant(self) -> Dict:
        return {
            "n_transactions": self.n_transactions,
            "confidence_scale": self.confidence_scale,
            "lift_scale": self.lift_scale,
        }


@dataclass
class ShardPlan:
    """Host-side companion of a ``ShardedDeviceTrie``.

    Carries the mesh, the DFS cut points, and the small host tables the
    query wrappers need BEFORE anything touches a device: per-shard
    posting offsets (slicing each query's posting window per shard) and
    the global posting base per (shard, item) that globalizes local
    posting positions ahead of the k-best merge.  ``frozen`` stays
    referenced for host-side canonicalization and the prefix descent.
    """

    mesh: Mesh
    trie: ShardedDeviceTrie
    frozen: FrozenTrie
    ranges: Tuple[Tuple[int, int], ...]
    local_item_offsets: np.ndarray   # int64 [P, I+1]
    gbase: np.ndarray                # int64 [P, I]

    @property
    def n_shards(self) -> int:
        return self.trie.n_shards


def shard_device_trie(
    frozen: FrozenTrie,
    mesh: Mesh,
    layout: str = "plain",
    quantize: bool = False,
    n_transactions: int = 0,
    columns: str = "bf16",
    prev_ranges: Optional[Sequence[Tuple[int, int]]] = None,
    drift: Optional[float] = None,
) -> ShardPlan:
    """Partition ``frozen`` over every device on ``mesh``'s ``data`` axis.

    Returns the host-side :class:`ShardPlan`; its ``.trie`` is the
    device-sharded :class:`ShardedDeviceTrie`.  The three batched query
    ops in ``kernels.ops`` accept the plan wherever they accept a
    ``DeviceTrie`` and produce bit-identical results.

    ``layout``/``quantize``/``n_transactions``/``columns`` mirror
    ``FrozenTrie.device_arrays``: with ``layout="compressed"`` every
    shard carries a path-compressed span pool covering exactly its own
    depth-1 subtrees (chains never cross a subtree boundary, so the
    per-shard ``compress_pos_space`` run reproduces the global span set
    restricted to the shard), and the metric columns may be quantized
    with GLOBAL scales so per-shard dequantization is bit-identical to
    the single-device compressed trie.

    ``prev_ranges`` + ``drift`` rebalance only on load drift (see
    ``plan_shard_bounds``): a streaming re-freeze that barely moved the
    depth-1 load keeps the previous cut points.
    """
    if layout not in ("plain", "compressed", "auto"):
        raise ValueError(f"unknown layout {layout!r}")
    if layout == "auto":
        layout = (
            "compressed"
            if frozen.span_fraction() >= AUTO_COMPRESS_SPAN_FRACTION
            else "plain"
        )
    comp = (
        frozen.compress(
            quantize=quantize, n_transactions=n_transactions,
            columns=columns,
        )
        if layout == "compressed"
        else None
    )
    n_shards = int(mesh.shape["data"])
    ranges = shard_dfs_ranges(
        frozen, n_shards, prev_ranges=prev_ranges, drift=drift
    )
    n = frozen.n_nodes
    dfs = np.asarray(frozen.dfs_order, np.int64)
    sub = np.asarray(frozen.subtree_size, np.int64)
    d2n = np.asarray(frozen.dfs_to_node, np.int64)

    # --- DFS-ordered column slices -----------------------------------
    # (the compressed path reuses the possibly-quantized position-space
    # columns so every shard slice carries the same stored values — and
    # therefore the same dequantized values — as the global encoding)
    cols = {
        "support": (
            comp.support_pos if comp is not None
            else np.asarray(frozen.support, np.float32)[d2n]
        ),
        "confidence": (
            comp.confidence_pos if comp is not None
            else np.asarray(frozen.confidence, np.float32)[d2n]
        ),
        "lift": (
            comp.lift_pos if comp is not None
            else np.asarray(frozen.lift, np.float32)[d2n]
        ),
        "depth": np.asarray(frozen.node_depth, np.int32)[d2n],
        "node_item": np.asarray(frozen.node_item, np.int32)[d2n],
    }
    fills = {
        "support": 0.0, "confidence": 0.0, "lift": 0.0,
        "depth": -1, "node_item": -2,
    }
    lens = [hi - lo for lo, hi in ranges]
    lpad = max(max(lens), 1)

    def stack_slices(col, fill):
        out = np.full((n_shards, lpad), fill, col.dtype)
        for d, (lo, hi) in enumerate(ranges):
            out[d, : hi - lo] = col[lo:hi]
        return out

    stacked = {k: stack_slices(v, fills[k]) for k, v in cols.items()}
    dfs_base = np.array([lo for lo, _ in ranges], np.int32)
    dfs_len = np.array(lens, np.int32)

    # --- posting lists, co-partitioned by item -----------------------
    item_offsets = np.asarray(frozen.item_offsets, np.int64)
    item_nodes = np.asarray(frozen.item_nodes, np.int64)
    n_items = item_offsets.shape[0] - 1
    e = item_nodes.shape[0]
    post_item = np.repeat(
        np.arange(n_items, dtype=np.int64), np.diff(item_offsets)
    )
    post_dfs = dfs[item_nodes] if e else np.zeros((0,), np.int64)
    # postings are (item, dfs)-sorted, so one composite-key searchsorted
    # finds every shard's slice of every item's posting list at once
    key = post_item * (n + 1) + post_dfs
    item_keys = np.arange(n_items, dtype=np.int64) * (n + 1)
    starts = np.searchsorted(
        key, item_keys[None, :] + np.array([r[0] for r in ranges])[:, None]
    )
    ends = np.searchsorted(
        key, item_keys[None, :] + np.array([r[1] for r in ranges])[:, None]
    )
    counts = ends - starts                       # [P, I]
    local_item_offsets = np.zeros((n_shards, n_items + 1), np.int64)
    np.cumsum(counts, axis=1, out=local_item_offsets[:, 1:])
    wpad = max(int(counts.sum(axis=1).max()) if n_items else 0, 1)

    # the compressed layout has no posting-ordered metric columns (its
    # consequent role runs through the membership kernel over the node
    # columns — the rank-path memory win), so those shrink to dummies
    ppad = 1 if comp is not None else wpad
    post = {
        "post_lo": np.full((n_shards, wpad), _BIG, np.int32),
        "post_hi": np.full((n_shards, wpad), _BIG, np.int32),
        "p_support": np.zeros((n_shards, ppad), np.float32),
        "p_confidence": np.zeros((n_shards, ppad), np.float32),
        "p_lift": np.zeros((n_shards, ppad), np.float32),
        "p_depth": np.full((n_shards, ppad), -1, np.int32),
    }
    nsup = np.asarray(frozen.support, np.float32)
    nconf = np.asarray(frozen.confidence, np.float32)
    nlift = np.asarray(frozen.lift, np.float32)
    ndep = np.asarray(frozen.node_depth, np.int32)
    for d, (lo, hi) in enumerate(ranges):
        sel = (post_dfs >= lo) & (post_dfs < hi)
        ln = item_nodes[sel]                     # item-major, DFS-minor
        w = ln.shape[0]
        sp_lo = (dfs[ln] - lo).astype(np.int64)
        sp_hi = sp_lo + sub[ln]
        # per-item ascending subtree ends (the membership kernel's second
        # binary-search side) — same composite-key sort as the
        # single-device item_rank_arrays
        seg = post_item[sel]
        order = np.argsort(seg * (n + 1) + sp_hi, kind="stable")
        post["post_lo"][d, :w] = sp_lo
        post["post_hi"][d, :w] = sp_hi[order]
        if comp is None:
            post["p_support"][d, :w] = nsup[ln]
            post["p_confidence"][d, :w] = nconf[ln]
            post["p_lift"][d, :w] = nlift[ln]
            post["p_depth"][d, :w] = ndep[ln]

    # --- relabeled local subforests for the fused descent -------------
    edge_parent = np.asarray(frozen.edge_parent, np.int64)
    edge_item = np.asarray(frozen.edge_item, np.int64)
    edge_child = np.asarray(frozen.edge_child, np.int64)
    child_dfs = dfs[edge_child] if edge_child.size else np.zeros(
        (0,), np.int64
    )
    cc_pos = None
    if comp is not None:
        cc_all = np.diff(np.asarray(frozen.child_offsets, np.int64))
        cc_pos = cc_all[d2n] if d2n.size else cc_all
    locals_: List[Dict[str, np.ndarray]] = []
    for d, (lo, hi) in enumerate(ranges):
        start_pos = max(lo, 1)
        n_loc = max(hi - start_pos, 0)
        sel = (child_dfs >= start_pos) & (child_dfs < hi)
        ep, ei, ec = edge_parent[sel], edge_item[sel], edge_child[sel]
        # local id 0 = the (replicated) global root; in-shard nodes take
        # 1 + their offset inside the shard's DFS range — parents are
        # always root or in-shard because shards are whole depth-1
        # subtrees
        lp = np.where(ep == 0, 0, dfs[ep] - start_pos + 1)
        lc = dfs[ec] - start_pos + 1
        loc: Dict[str, np.ndarray] = {
            "l2g": np.concatenate(
                [[0], d2n[start_pos:hi]]
            ).astype(np.int64),
        }
        if comp is not None:
            # per-shard path compression in LOCAL position space.  The
            # local slice preserves the global DFS order, and every
            # non-root local node keeps its global child count, so
            # chain_spans sees exactly the global span set restricted to
            # this shard (chains never cross a depth-1 boundary: the
            # last position of a subtree is one of its leaves).
            cc_loc = np.concatenate([
                [int(np.count_nonzero(lp == 0))],
                cc_pos[start_pos:hi],
            ])
            c = compress_pos_space(cc_loc, lp, ei, lc)
            loc.update({
                "co": c["child_offsets"].astype(np.int64),
                "ei": c["edge_item"].astype(np.int64),
                "epos": c["edge_pos"].astype(np.int64),
                "espan": c["edge_span"].astype(np.int64),
                "etail": c["edge_tail"].astype(np.int64),
                "fan": int(c["max_fanout"]),
            })
        else:
            order = np.lexsort((ei, lp))
            lp, ei, lc, ec = lp[order], ei[order], lc[order], ec[order]
            cnt = np.bincount(lp, minlength=n_loc + 1)
            offsets = np.zeros((n_loc + 2,), np.int64)
            np.cumsum(cnt, out=offsets[1:])
            loc.update({
                "co": offsets,
                "ei": ei, "lc": lc,
                "ecf": nconf[ec], "esp": nsup[ec], "elf": nlift[ec],
                "fan": int(cnt.max()) if cnt.size else 0,
            })
        locals_.append(loc)
    co_pad = max(loc["co"].shape[0] for loc in locals_)
    e_pad = max(max(loc["ei"].shape[0] for loc in locals_), 1)
    nl_pad = max(loc["l2g"].shape[0] for loc in locals_)
    # plain and compressed subforests populate disjoint edge-column
    # families; the other family stays a [P, 1] dummy leaf
    pw = e_pad if comp is None else 1
    cw = e_pad if comp is not None else 1
    sw = nl_pad if comp is not None else 1
    edges = {
        "child_offsets": np.zeros((n_shards, co_pad), np.int32),
        "edge_item": np.full((n_shards, e_pad), -7, np.int32),
        "edge_child": np.full((n_shards, pw), -1, np.int32),
        "edge_conf": np.zeros((n_shards, pw), np.float32),
        "edge_sup": np.zeros((n_shards, pw), np.float32),
        "edge_lift": np.zeros((n_shards, pw), np.float32),
        "l2g": np.full((n_shards, nl_pad), -1, np.int32),
        "edge_pos": np.full((n_shards, cw), -1, np.int32),
        "edge_span": np.zeros((n_shards, cw), np.int32),
        "edge_tail": np.zeros((n_shards, cw), np.int32),
    }
    # position-space node columns for the span descent: the replicated
    # root at local position 0 followed by the shard's DFS slice
    scols = {
        "s_item": np.full((n_shards, sw), -2, np.int32),
        "s_support": np.zeros(
            (n_shards, sw), cols["support"].dtype
        ),
        "s_confidence": np.zeros(
            (n_shards, sw), cols["confidence"].dtype
        ),
        "s_lift": np.zeros((n_shards, sw), cols["lift"].dtype),
    }
    for d, loc in enumerate(locals_):
        co = loc["co"]
        edges["child_offsets"][d, : co.shape[0]] = co
        edges["child_offsets"][d, co.shape[0]:] = co[-1]
        w = loc["ei"].shape[0]
        edges["edge_item"][d, :w] = loc["ei"]
        if comp is not None:
            edges["edge_pos"][d, :w] = loc["epos"]
            edges["edge_span"][d, :w] = loc["espan"]
            edges["edge_tail"][d, :w] = loc["etail"]
            lo, hi = ranges[d]
            start_pos = max(lo, 1)
            nl = 1 + max(hi - start_pos, 0)
            for name, src in (
                ("s_item", cols["node_item"]),
                ("s_support", cols["support"]),
                ("s_confidence", cols["confidence"]),
                ("s_lift", cols["lift"]),
            ):
                scols[name][d, 0] = src[0]
                scols[name][d, 1:nl] = src[start_pos:hi]
        else:
            edges["edge_child"][d, :w] = loc["lc"]
            edges["edge_conf"][d, :w] = loc["ecf"]
            edges["edge_sup"][d, :w] = loc["esp"]
            edges["edge_lift"][d, :w] = loc["elf"]
        edges["l2g"][d, : loc["l2g"].shape[0]] = loc["l2g"]
    max_fanout = max(max(loc["fan"] for loc in locals_), 1)

    # --- device placement --------------------------------------------
    shd = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())

    def put(a):
        return jax.device_put(jnp.asarray(a), shd)

    trie = ShardedDeviceTrie(
        support=put(stacked["support"]),
        confidence=put(stacked["confidence"]),
        lift=put(stacked["lift"]),
        depth=put(stacked["depth"]),
        node_item=put(stacked["node_item"]),
        dfs_base=put(dfs_base),
        dfs_len=put(dfs_len),
        post_lo=put(post["post_lo"]),
        post_hi=put(post["post_hi"]),
        p_support=put(post["p_support"]),
        p_confidence=put(post["p_confidence"]),
        p_lift=put(post["p_lift"]),
        p_depth=put(post["p_depth"]),
        child_offsets=put(edges["child_offsets"]),
        edge_item=put(edges["edge_item"]),
        edge_child=put(edges["edge_child"]),
        edge_conf=put(edges["edge_conf"]),
        edge_sup=put(edges["edge_sup"]),
        edge_lift=put(edges["edge_lift"]),
        l2g=put(edges["l2g"]),
        edge_pos=put(edges["edge_pos"]),
        edge_span=put(edges["edge_span"]),
        edge_tail=put(edges["edge_tail"]),
        s_item=put(scols["s_item"]),
        s_support=put(scols["s_support"]),
        s_confidence=put(scols["s_confidence"]),
        s_lift=put(scols["s_lift"]),
        g_dfs_to_node=jax.device_put(
            jnp.asarray(d2n, jnp.int32), repl
        ),
        g_item_nodes=jax.device_put(
            jnp.asarray(item_nodes, jnp.int32), repl
        ),
        n_shards=n_shards,
        max_fanout=max_fanout,
        max_postings=int(frozen.max_postings),
        layout=layout,
        n_transactions=comp.n_transactions if comp is not None else 0,
        confidence_scale=(
            comp.confidence_scale if comp is not None else 1.0
        ),
        lift_scale=comp.lift_scale if comp is not None else 1.0,
    )
    return ShardPlan(
        mesh=mesh,
        trie=trie,
        frozen=frozen,
        ranges=tuple(ranges),
        local_item_offsets=local_item_offsets,
        gbase=starts.astype(np.int64),
    )


# ----------------------------------------------------------------------
# degraded plans: answering around dead shards
# ----------------------------------------------------------------------
# every [P, ...] leaf's padding value — masking a shard's rows with its
# own padding convention makes the dead shard indistinguishable from an
# empty one: rank ops return nothing from its DFS range, posting windows
# come back empty, and descents routed to it report found=False.
_MASK_FILLS = {
    "support": 0.0, "confidence": 0.0, "lift": 0.0,
    "depth": -1, "node_item": -2,
    "dfs_len": 0,
    "post_lo": _BIG, "post_hi": _BIG,
    "p_support": 0.0, "p_confidence": 0.0, "p_lift": 0.0, "p_depth": -1,
    "child_offsets": 0, "edge_item": -7, "edge_child": -1,
    "edge_conf": 0.0, "edge_sup": 0.0, "edge_lift": 0.0, "l2g": -1,
    "edge_pos": -1, "edge_span": 0, "edge_tail": 0,
    "s_item": -2, "s_support": 0, "s_confidence": 0, "s_lift": 0,
}


def mask_dead_shards(
    plan: ShardPlan, dead: Sequence[int]
) -> ShardPlan:
    """A DEGRADED copy of ``plan`` with the listed shards' data blanked.

    The masked plan still answers every batched op without error, but
    each dead shard's DFS range, posting lists, and subforest simply
    vanish: ranked results silently exclude its rules and descents whose
    first item routes to it return ``found=False``.  This is the partial-
    answer fallback the serve loop's ``ShardHealth`` selects when the
    replicated backend is unavailable; callers must surface the loss
    explicitly (the scheduler stamps ``degraded=True`` on every response
    answered through a masked plan).

    Host-side and allocation-only — the original plan (and its device
    buffers) is untouched, so recovery is just "resume using the old
    plan".  Raises ``ValueError`` when ``dead`` names an out-of-range
    shard or would kill ALL shards (no data left to answer from).
    """
    dead_set = sorted({int(d) for d in dead})
    p = plan.n_shards
    bad = [d for d in dead_set if not 0 <= d < p]
    if bad:
        raise ValueError(
            f"dead shard ids {bad} out of range for {p}-shard plan"
        )
    if not dead_set:
        return plan
    if len(dead_set) == p:
        raise ValueError(
            f"masking all {p} shards leaves nothing to answer from"
        )
    st = plan.trie
    shd = NamedSharding(plan.mesh, P("data"))
    masked = {}
    for name in ShardedDeviceTrie._LEAVES:
        arr = getattr(st, name)
        if name not in _MASK_FILLS:       # replicated tables / dfs_base
            masked[name] = arr
            continue
        host = np.array(arr)              # gather + copy
        host[dead_set] = _MASK_FILLS[name]
        masked[name] = jax.device_put(jnp.asarray(host), shd)
    trie = ShardedDeviceTrie(
        **masked,
        n_shards=st.n_shards,
        max_fanout=st.max_fanout,
        max_postings=st.max_postings,
        layout=st.layout,
        n_transactions=st.n_transactions,
        confidence_scale=st.confidence_scale,
        lift_scale=st.lift_scale,
    )
    local_item_offsets = plan.local_item_offsets.copy()
    local_item_offsets[dead_set] = 0
    gbase = plan.gbase.copy()
    gbase[dead_set] = 0
    return ShardPlan(
        mesh=plan.mesh,
        trie=trie,
        frozen=plan.frozen,
        ranges=plan.ranges,
        local_item_offsets=local_item_offsets,
        gbase=gbase,
    )


# ----------------------------------------------------------------------
# k-best merge (the static rank-merge over all-gathered device lists)
# ----------------------------------------------------------------------
def merge_kbest(vals: jax.Array, pos: jax.Array, k: int):
    """Fold P per-device k-best lists ``[P, Q, k]`` into the global
    ``[Q, k]`` via ``rank.rank_merge`` — the same (value desc, pos asc)
    rank scatter the in-kernel ``kbest_update`` uses, so the merged tie
    order matches ``jax.lax.top_k`` exactly.  Positions must be GLOBAL
    (distinct across devices) before merging."""
    p = vals.shape[0]
    kpad = k + (-k % LANE)
    v = jnp.pad(
        vals, ((0, 0), (0, 0), (0, kpad - k)), constant_values=-jnp.inf
    )
    q = jnp.pad(pos, ((0, 0), (0, 0), (0, kpad - k)), constant_values=-1)
    merge = jax.vmap(
        lambda a, b, c, d: rank_merge(a, b, c, d, kpad)
    )
    mv, mp = v[0], q[0]
    for d in range(1, p):
        mv, mp = merge(mv, mp, v[d], q[d])
    return mv[:, :k], mp[:, :k]


def _take_back(table: jax.Array, pos: jax.Array) -> jax.Array:
    if table.shape[0] == 0:
        return jnp.full_like(pos, -1)
    return jnp.where(pos >= 0, table[jnp.maximum(pos, 0)], -1)


# ----------------------------------------------------------------------
# host-side prefix descent (query prep without touching devices)
# ----------------------------------------------------------------------
def host_prefix_ranges(
    frozen: FrozenTrie, prefixes
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Numpy mirror of ``kernels.ops.prefix_ranges`` (same
    canonicalization — ``array_trie.canonical_prefix_rows``, the ONE
    shared normalization — same CSR bucket descent, same [Q,P]-matrix vs
    ragged padding semantics) so the sharded engine resolves antecedent
    prefixes to global DFS ranges without uploading the global edge
    table.  Integer-for-integer identical to the device descent."""
    co = np.asarray(frozen.child_offsets, np.int64)
    ei = np.asarray(frozen.edge_item, np.int64)
    ec = np.asarray(frozen.edge_child, np.int64)
    dfs = np.asarray(frozen.dfs_order, np.int64)
    sub = np.asarray(frozen.subtree_size, np.int64)
    n = frozen.n_nodes
    rows = canonical_prefix_rows(prefixes, frozen.item_rank)
    q = len(rows)
    los = np.zeros((q,), np.int32)
    his = np.zeros((q,), np.int32)
    nodes = np.zeros((q,), np.int32)
    for i, its in enumerate(rows):
        node = 0
        for it in its:
            lo_e, hi_e = int(co[node]), int(co[node + 1])
            j = lo_e + int(np.searchsorted(ei[lo_e:hi_e], it))
            if j < hi_e and ei[j] == it:
                node = int(ec[j])
            else:
                node = -1
                break
        if node >= 0:
            los[i] = dfs[node]
            his[i] = min(int(dfs[node] + sub[node]), n)
            nodes[i] = node
        else:
            nodes[i] = -1
    return los, his, nodes


# ----------------------------------------------------------------------
# shard_map-aware batched ops (each device runs the unchanged kernels)
# ----------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "k", "metric", "min_depth", "interpret",
        "n_transactions", "confidence_scale", "lift_scale",
    ),
)
def _topk_ranges_sharded(
    st: ShardedDeviceTrie, los, his,
    *, mesh, k, metric, min_depth, interpret,
    n_transactions=0, confidence_scale=1.0, lift_scale=1.0,
):
    n_shards = int(mesh.shape["data"])

    def fn(sup, conf, lif, dep, base, length, los, his):
        b = base[0]
        ln = length[0]
        ll = jnp.clip(los - b, 0, ln)
        hh = jnp.clip(his - b, 0, ln)
        v, p = topk_rank_batch_pallas(
            sup[0], conf[0], lif[0], dep[0], ll, hh,
            k=k, metric=metric, min_depth=min_depth, interpret=interpret,
            n_transactions=n_transactions,
            confidence_scale=confidence_scale, lift_scale=lift_scale,
        )
        p = jnp.where(p >= 0, p + b, -1)
        if n_shards == 1:
            # single-shard mesh: the local list IS the global answer —
            # skip the collective + merge (static, so it compiles away)
            return v, p
        return merge_kbest(
            jax.lax.all_gather(v, "data"),
            jax.lax.all_gather(p, "data"),
            k,
        )

    ps, pr = P("data"), P()
    return _shard_map(
        fn, mesh, in_specs=(ps,) * 6 + (pr, pr), out_specs=(pr, pr)
    )(
        st.support, st.confidence, st.lift, st.depth,
        st.dfs_base, st.dfs_len, los, his,
    )


def sharded_top_k_rules_batch(
    plan: ShardPlan, prefixes, k: int,
    metric: str = "confidence", min_depth: int = 1,
) -> Dict[str, jax.Array]:
    """Sharded form of ``ops.top_k_rules_batch``: per-device segmented
    ranking over the local DFS slice + k-best all-gather/rank-merge.
    Bit-identical (tie order included) to the single-device op."""
    if metric not in RANK_METRICS:
        raise InvalidQueryError(
            f"metric {metric!r} not in {RANK_METRICS}"
        )
    # the exact input normalization of the single-device op: a [Q, P]
    # matrix stays a matrix (its -1 entries are padding under the
    # repo-wide query-matrix convention — list()-ing it would turn them
    # into literal absent items), everything else becomes Q ragged rows
    if not isinstance(prefixes, np.ndarray):
        prefixes = list(prefixes)
    if len(prefixes) == 0:
        kk = max(int(k), 0)
        return {
            "values": jnp.zeros((0, kk), jnp.float32),
            "node": jnp.zeros((0, kk), jnp.int32),
            "dfs_pos": jnp.zeros((0, kk), jnp.int32),
        }
    los, his, _nodes = host_prefix_ranges(plan.frozen, prefixes)
    vals, pos = _topk_ranges_sharded(
        plan.trie, jnp.asarray(los), jnp.asarray(his),
        mesh=plan.mesh, k=int(k), metric=metric,
        min_depth=int(min_depth), interpret=_interpret(),
        **plan.trie._dequant(),
    )
    node = _take_back(plan.trie.g_dfs_to_node, pos)
    return {"values": vals, "node": node, "dfs_pos": pos}


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "k", "metric", "min_depth", "role", "max_postings",
        "interpret", "layout",
        "n_transactions", "confidence_scale", "lift_scale",
    ),
)
def _rules_with_sharded(
    st: ShardedDeviceTrie, plos, phis, gdelta, qitems,
    *, mesh, k, metric, min_depth, role, max_postings, interpret,
    layout="plain", n_transactions=0, confidence_scale=1.0,
    lift_scale=1.0,
):
    ps, pr = P("data"), P()
    n_shards = int(mesh.shape["data"])
    # compressed plans carry no posting-ordered metric columns, so the
    # consequent role runs through the membership ranking below (a pure
    # node_item == qitem self-hit), mirroring the single-device dispatch
    if role == "consequent" and layout != "compressed":
        def fn(psup, pconf, plif, pdep, plos, phis, gdelta):
            v, p = topk_rank_batch_pallas(
                psup[0], pconf[0], plif[0], pdep[0], plos[0], phis[0],
                k=k, metric=metric, min_depth=min_depth,
                interpret=interpret,
            )
            # local posting index -> GLOBAL posting index before merging
            p = jnp.where(p >= 0, p + gdelta[0][:, None], -1)
            if n_shards == 1:
                return v, p
            return merge_kbest(
                jax.lax.all_gather(v, "data"),
                jax.lax.all_gather(p, "data"),
                k,
            )

        return _shard_map(
            fn, mesh, in_specs=(ps,) * 7, out_specs=(pr, pr)
        )(
            st.p_support, st.p_confidence, st.p_lift, st.p_depth,
            plos, phis, gdelta,
        )

    def fn(sup, conf, lif, dep, nit, sp_lo, sp_hi, base, plos, phis, qi):
        v, p = rules_with_pallas(
            sup[0], conf[0], lif[0], dep[0], nit[0],
            sp_lo[0], sp_hi[0], plos[0], phis[0], qi,
            k=k, metric=metric, min_depth=min_depth, role=role,
            max_postings=max_postings, interpret=interpret,
            n_transactions=n_transactions,
            confidence_scale=confidence_scale, lift_scale=lift_scale,
        )
        # local DFS position -> global DFS position before merging
        p = jnp.where(p >= 0, p + base[0], -1)
        if n_shards == 1:
            return v, p
        return merge_kbest(
            jax.lax.all_gather(v, "data"),
            jax.lax.all_gather(p, "data"),
            k,
        )

    return _shard_map(
        fn, mesh, in_specs=(ps,) * 10 + (pr,), out_specs=(pr, pr)
    )(
        st.support, st.confidence, st.lift, st.depth, st.node_item,
        st.post_lo, st.post_hi, st.dfs_base, plos, phis, qitems,
    )


def _sharded_posting_slices(plan: ShardPlan, items):
    """[P, Q] posting slices per shard + [P, Q] global-index deltas +
    sanitized [Q] item ids (absent items -> empty slices, id -1 — the
    sanitize step is ``array_trie.sanitize_query_items``, shared with
    the single-device ``ops._posting_slices``)."""
    offsets = plan.local_item_offsets
    valid, safe, qitems = sanitize_query_items(
        items, offsets.shape[1] - 1
    )
    plos = np.where(valid[None, :], offsets[:, safe], 0).astype(np.int32)
    phis = np.where(
        valid[None, :], offsets[:, safe + 1], 0
    ).astype(np.int32)
    gdelta = np.where(
        valid[None, :], plan.gbase[:, safe] - plos, 0
    ).astype(np.int32)
    return plos, phis, gdelta, qitems


def sharded_rules_with(
    plan: ShardPlan, items, role: str = "any", k: int = 10,
    metric: str = "confidence", min_depth: int = 1,
) -> Dict[str, jax.Array]:
    """Sharded form of ``ops.rules_with``: each device answers over its
    co-partitioned posting lists / DFS slice, then k-best merge.
    Bit-identical (tie order included) to the single-device op."""
    if role not in ROLES:
        raise InvalidQueryError(f"role {role!r} not in {ROLES}")
    if metric not in RANK_METRICS:
        raise InvalidQueryError(
            f"metric {metric!r} not in {RANK_METRICS}"
        )
    plos, phis, gdelta, qitems = _sharded_posting_slices(plan, items)
    q = qitems.shape[0]
    if q == 0:
        kk = max(int(k), 0)
        z = jnp.zeros((0, kk), jnp.int32)
        return {
            "values": jnp.zeros((0, kk), jnp.float32),
            "node": z, "pos": z,
        }
    # duplicate-item dedup, mirroring the single-device op: identical
    # sanitized items yield bit-identical rows, so the shard_map launch
    # (and its per-query posting windows) runs over U unique items
    # (power-of-two padded with absent-item rows, bounding the compiled
    # launch shapes) and the inverse map expands the merged rows back
    from repro.kernels.ops import _pad_pow2_rows

    _, first, inv = np.unique(
        qitems, return_index=True, return_inverse=True
    )
    plos_u, phis_u, qitems_u = _pad_pow2_rows(
        plos[:, first], phis[:, first], qitems[first], axis=1
    )
    gdelta_u = np.pad(
        gdelta[:, first],
        [(0, 0), (0, qitems_u.shape[0] - first.shape[0])],
    )
    vals, pos = _rules_with_sharded(
        plan.trie, jnp.asarray(plos_u),
        jnp.asarray(phis_u), jnp.asarray(gdelta_u),
        jnp.asarray(qitems_u),
        mesh=plan.mesh, k=int(k), metric=metric,
        min_depth=int(min_depth), role=role,
        max_postings=plan.trie.max_postings, interpret=_interpret(),
        layout=plan.trie.layout, **plan.trie._dequant(),
    )
    inv_j = jnp.asarray(inv, jnp.int32)
    vals = vals[inv_j]
    pos = pos[inv_j]
    # compressed consequent answers come back as DFS positions (the
    # membership ranking's coordinate), like every other role there
    back = (
        plan.trie.g_item_nodes
        if role == "consequent" and plan.trie.layout != "compressed"
        else plan.trie.g_dfs_to_node
    )
    return {"values": vals, "node": _take_back(back, pos), "pos": pos}


@functools.partial(jax.jit, static_argnames=("mesh", "max_fanout"))
def _rule_search_sharded(
    st: ShardedDeviceTrie, queries, ant_len, *, mesh, max_fanout,
):
    n_shards = int(mesh.shape["data"])

    def fn(co, ei, ec, ecf, esp, elf, l2g, queries, ant_len):
        out = rule_search_fused(
            co[0], ei[0], ec[0], ecf[0], esp[0], elf[0],
            queries, ant_len, max_fanout=max_fanout,
        )
        l2g1 = l2g[0]
        node_g = jnp.where(
            out["node"] > 0,
            l2g1[jnp.clip(out["node"], 0, l2g1.shape[0] - 1)],
            -1,
        )
        if n_shards == 1:
            # single-shard mesh: the whole trie is local, so the
            # descent's compound lift is already the global answer — no
            # collective, no re-select
            return (
                out["found"], node_g, out["confidence"],
                out["support"], out["lift"],
            )
        gather = functools.partial(jax.lax.all_gather, axis_name="data")
        found_all = gather(out["found"])          # [P, Q]
        # at most ONE shard can complete a descent (the first query item
        # routes to exactly one depth-1 subtree), so the merge is a
        # found-winner select; all-False rows pick shard 0, whose outputs
        # already carry the not-found contract values (0 / -1 / False)
        win = jnp.argmax(found_all.astype(jnp.int32), axis=0)

        def take(a):
            return jnp.take_along_axis(gather(a), win[None, :], axis=0)[0]

        found = jnp.any(found_all, axis=0)
        node = take(node_g)
        conf = take(out["confidence"])
        sup = take(out["support"])
        nlift = take(out["lift"])
        # the consequent-only walk may succeed on a DIFFERENT shard than
        # the main path; merge its Support (nonzero on <= 1 shard) and
        # re-run the Eq. 1-4 select globally.  For single-item
        # consequents the winning shard's lift IS the node lift, which
        # is exactly what compound_lift's single branch reads.
        csup = jnp.max(gather(out["con_support"]), axis=0)
        seq_len = jnp.sum((queries >= 0).astype(jnp.int32), axis=1)
        single = (seq_len - ant_len) == 1
        lift = compound_lift(found, single, nlift, conf, csup)
        return found, node, conf, sup, lift

    ps, pr = P("data"), P()
    return _shard_map(
        fn, mesh, in_specs=(ps,) * 7 + (pr, pr),
        out_specs=(pr,) * 5,
    )(
        st.child_offsets, st.edge_item, st.edge_child,
        st.edge_conf, st.edge_sup, st.edge_lift, st.l2g,
        queries, ant_len,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "max_fanout",
        "n_transactions", "confidence_scale", "lift_scale",
    ),
)
def _rule_search_sharded_span(
    st: ShardedDeviceTrie, queries, ant_len,
    *, mesh, max_fanout,
    n_transactions=0, confidence_scale=1.0, lift_scale=1.0,
):
    """Compressed-layout twin of ``_rule_search_sharded``: every device
    runs the span-aware descent over its local span pool, then
    the identical found-winner merge + global Eq. 1-4 re-assembly (local
    DFS positions translate through the shard's ``l2g`` row, whose index
    space coincides with local position space)."""
    n_shards = int(mesh.shape["data"])

    def fn(co, ei, epos, espan, etail, nit, sup, conf, lif, l2g,
           queries, ant_len):
        out = rule_search_span(
            co[0], ei[0], epos[0], espan[0], etail[0],
            nit[0], sup[0], conf[0], lif[0],
            queries, ant_len, max_fanout=max_fanout,
            n_transactions=n_transactions,
            confidence_scale=confidence_scale, lift_scale=lift_scale,
        )
        l2g1 = l2g[0]
        node_g = jnp.where(
            out["pos"] > 0,
            l2g1[jnp.clip(out["pos"], 0, l2g1.shape[0] - 1)],
            -1,
        )
        if n_shards == 1:
            return (
                out["found"], node_g, out["confidence"],
                out["support"], out["lift"],
            )
        gather = functools.partial(jax.lax.all_gather, axis_name="data")
        found_all = gather(out["found"])          # [P, Q]
        win = jnp.argmax(found_all.astype(jnp.int32), axis=0)

        def take(a):
            return jnp.take_along_axis(gather(a), win[None, :], axis=0)[0]

        found = jnp.any(found_all, axis=0)
        node = take(node_g)
        conf_o = take(out["confidence"])
        sup_o = take(out["support"])
        nlift = take(out["lift"])
        csup = jnp.max(gather(out["con_support"]), axis=0)
        seq_len = jnp.sum((queries >= 0).astype(jnp.int32), axis=1)
        single = (seq_len - ant_len) == 1
        lift = compound_lift(found, single, nlift, conf_o, csup)
        return found, node, conf_o, sup_o, lift

    ps, pr = P("data"), P()
    return _shard_map(
        fn, mesh, in_specs=(ps,) * 10 + (pr, pr),
        out_specs=(pr,) * 5,
    )(
        st.child_offsets, st.edge_item, st.edge_pos, st.edge_span,
        st.edge_tail, st.s_item, st.s_support, st.s_confidence,
        st.s_lift, st.l2g,
        queries, ant_len,
    )


def sharded_rule_search_batch(
    plan: ShardPlan, queries, ant_len=None,
) -> Dict[str, jax.Array]:
    """Sharded form of ``ops.rule_search_batch``: every device runs the
    fused CSR descent over its local subforest (replicated-root hub
    bucket restricted to its own depth-1 children), then a found-winner
    merge + global compound-lift re-assembly.  Bit-identical per row to
    the single-device op."""
    if ant_len is None:
        pairs = list(queries)
        if not pairs:
            queries = np.zeros((0, 1), np.int32)
            ant_len = np.zeros((0,), np.int32)
        else:
            ants = [p[0] for p in pairs]
            cons = [p[1] for p in pairs]
            queries, ant_len = plan.frozen.canonicalize_queries(ants, cons)
    queries = np.asarray(queries, np.int32)
    ant_len = np.asarray(ant_len, np.int32)
    q, width = queries.shape
    if q == 0 or width == 0 or plan.frozen.n_edges == 0:
        z = jnp.zeros((q,), jnp.float32)
        return {
            "found": jnp.zeros((q,), bool),
            "node": jnp.full((q,), -1, jnp.int32),
            "support": z, "confidence": z, "lift": z,
        }
    # whole-query dedup, same helper as the single-device op: skewed
    # serving traffic descends each unique canonical row once per shard
    queries, ant_len, inv = dedup_query_rows(queries, ant_len)
    if plan.trie.layout == "compressed":
        found, node, conf, sup, lift = _rule_search_sharded_span(
            plan.trie, jnp.asarray(queries), jnp.asarray(ant_len),
            mesh=plan.mesh, max_fanout=plan.trie.max_fanout,
            **plan.trie._dequant(),
        )
    else:
        found, node, conf, sup, lift = _rule_search_sharded(
            plan.trie, jnp.asarray(queries), jnp.asarray(ant_len),
            mesh=plan.mesh, max_fanout=plan.trie.max_fanout,
        )
    out = {
        "found": found, "node": node,
        "support": sup, "confidence": conf, "lift": lift,
    }
    if inv is None:
        return out
    inv_j = jnp.asarray(inv)
    return {key: v[inv_j] for key, v in out.items()}
