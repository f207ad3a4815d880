"""Frozen Trie of Rules — TPU-native structure-of-arrays / CSR encoding.

This is the hardware adaptation of the paper's data structure (DESIGN.md §2):
a trie as flat arrays

    node_item / node_parent / node_depth          int32[N]
    support / confidence / lift                   float32[N]   (metric columns)
    edge_parent / edge_item / edge_child          int32[E]     (sorted lex)
    child_offsets                                 int32[N+1]   (CSR buckets)
    dfs_order / subtree_size / dfs_to_node        int32[N]     (DFS layout)
    item_offsets / item_nodes                     int32[I+1]/[E] (item index)

``child_offsets`` is the CSR row index over the lex-sorted edge table: node
``p``'s outgoing edges occupy ``edge_*[child_offsets[p]:child_offsets[p+1]]``,
item-sorted within the bucket (the array analogue of the modified FP-tree
header table, arXiv:1504.07018).  ``max_fanout`` — the widest bucket — is
precomputed at freeze time and bounds every per-step scan.

Every paper operation becomes a vectorized array program:

    rule search   — batched root→down descent; each step is a binary search
                    *inside the active node's child bucket* (O(log fanout),
                    not O(log E)) via the CSR offsets,
    top-N         — ``jax.lax.top_k`` over a metric column,
    traversal     — full-column reductions over the node arrays,
    compound conf — segment-product of confidences along the walked path
                    (paper Eq. 1-4).

Node ids are assigned in BFS order at freeze time so level-order traversal is
contiguous.  On top of that, freeze emits a DFS pre-order relabeling
(``dfs_order``: node id -> pre-order position, ``subtree_size``: node id ->
subtree node count, ``dfs_to_node``: the inverse permutation), following the
DFS-contiguous relabeling of memory-efficient trie mining
(arXiv:2202.06834): every antecedent-prefix subtree is the contiguous
position range ``[dfs_order[v], dfs_order[v] + subtree_size[v])``, which is
what the segmented top-k rank kernel (``repro.kernels.rank``) masks to.

``item_offsets`` / ``item_nodes`` form the item-inverted index — the array
analog of the FP-tree header table extended to a full posting-list layout:
item ``i``'s posting list ``item_nodes[item_offsets[i]:item_offsets[i+1]]``
holds every node whose consequent is ``i``, in DFS position order.  The
DFS sort makes each posting entry's subtree range directly intersectable
with the DFS relabeling, so "rules with item ``i`` in the antecedent" is a
laminar range-count over posting subtree ranges (``kernels.item_index``),
never a per-node path walk.

The same CSR bucket descent runs inside the fused Pallas kernel
(``repro.kernels.rule_search``); this module is the jnp reference/production
path for CPU/GPU/TPU-without-kernel.  A ``DeviceTrie`` with
``child_offsets=None`` falls back to the seed full-table lexicographic
binary search (kept for comparison benchmarks).

Two construction engines emit this encoding:

* ``FrozenTrie.freeze(pointer_trie)`` — the per-node BFS walk over the
  paper-faithful ``trie.TrieOfRules``; kept as the parity oracle.
* ``core.build_arrays.build_frozen_trie`` — the array-native production
  path: vectorized prefix dedup straight from the canonical sequence
  matrix plus one batched Step-3 annotation pass (no Python-per-node
  work); bit-identical to ``freeze`` by construction and by test.

Path-compressed (Patricia) layout (PR 8)
----------------------------------------

Rule tries are chain-heavy: similar rules overlay into long single-child
antecedent runs, and the plain layout spends a full node row (CSR bucket,
edge triple, DFS pair) on every link.  ``FrozenTrie.compress`` collapses
every maximal single-child run into a *span* and re-bases the whole
layout on DFS positions:

* a node with exactly one child (and not the root) is a **span
  position**; in DFS pre-order its only child sits at the very next
  position, so each maximal run is a contiguous DFS interval — the
  "span item pool" is literally a slice of the DFS-ordered
  ``node_item`` column, shared with the membership kernel for free;
* only run heads/tails and branching nodes (``children != 1``) keep CSR
  rows: the compressed edge table carries ``(item, child DFS position,
  span length, tail compressed-id)`` so descent matches a span's item
  subsequence with O(1) column probes instead of bucket scans;
* interior nodes keep just their metric tuple — the DFS-ordered metric
  columns, stored ONCE and scanned directly by the rank / reduce /
  membership kernels (no per-op gathered copies).

``compress(quantize=True)`` additionally narrows the metric columns:
support becomes exact int32 transaction counts (fp32 ratio
reconstructed in-kernel as ``count / n_transactions``), confidence and
lift become bf16 (or int8 via ``distributed.compression.quantize_int8``
with a per-column fp32 scale).  Unquantized compressed results are
bit-identical to plain; quantized error bounds are documented on
``kernels.metrics_inkernel.dequantize_metrics``.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .metrics import Item
from .trie import TrieNode, TrieOfRules

NO_NODE = np.int32(-1)


def canonical_prefix_rows(prefixes, item_rank=None) -> List[List[int]]:
    """Normalize Q antecedent prefixes into frequency-sorted item rows.

    The ONE implementation behind both prefix-resolution paths — the
    device descent (``kernels.ops.prefix_ranges``) and the host descent
    (``distributed.trie_sharding.host_prefix_ranges``) — whose
    integer-for-integer agreement the sharded/single bit-parity contract
    rests on.

    In an already-padded ``[Q, P]`` MATRIX, ``-1`` entries are padding
    (the repo-wide query-matrix convention) and are dropped per row; in
    ragged sequences every element is a literal item, so ``-1`` there is
    remapped off the padding sentinel (to ``-9``) and reads as "not in
    the trie", exactly like any other absent item.  Items sort by
    ``(frequency rank, item)`` when an ``item_rank`` table is given;
    unknown items rank last.
    """
    as_matrix = isinstance(prefixes, np.ndarray) and prefixes.ndim == 2
    rows: List[List[int]] = []
    for p in prefixes:
        if as_matrix:
            its = [int(it) for it in np.asarray(p).reshape(-1) if it != -1]
        else:
            its = [
                int(it) if int(it) != -1 else -9
                for it in np.asarray(p).reshape(-1)
            ]
        if item_rank is not None:
            nr = int(np.asarray(item_rank).shape[0])
            its.sort(
                key=lambda it: (
                    int(item_rank[it]) if 0 <= it < nr else 1 << 30, it
                )
            )
        rows.append(its)
    return rows


def sanitize_query_items(
    items, n_items: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Absent-item sanitization shared by every posting-slice resolver.

    Returns ``(valid bool[Q], safe int64[Q], qitems int32[Q])``: items
    outside ``[0, n_items)`` are invalid (they resolve to empty posting
    slices), ``safe`` is the clipped index usable against any
    ``[n_items(+1)]``-sized offsets table, and ``qitems`` carries the
    sanitized id ``-1`` (matched by no node) for invalid entries.  Both
    the single-device resolver (``kernels.ops._posting_slices``) and the
    per-shard one (``trie_sharding._sharded_posting_slices``) go through
    THIS function — the sharded==single bit-parity contract for
    absent-item queries rests on the two agreeing integer-for-integer.
    """
    items = np.asarray(list(items), np.int64).reshape(-1)
    valid = (items >= 0) & (items < n_items)
    safe = np.clip(items, 0, max(n_items - 1, 0))
    qitems = np.where(valid, items, -1).astype(np.int32)
    return valid, safe, qitems


def item_tables(item_order) -> Tuple[np.ndarray, np.ndarray]:
    """Frequency-order lookup tables shared by both construction engines.

    ``item_order`` is the rank→item list (``TransactionDB.frequency_order``
    / ``TrieOfRules._rank`` sorted by rank).  Returns ``(item_order
    int32[n], item_rank int32[max_item+1])`` where unknown items map to a
    huge rank, exactly as ``TrieOfRules.canonical`` treats them.
    """
    item_order = np.asarray(list(item_order), dtype=np.int32)
    max_item = int(item_order.max()) if item_order.size else 0
    item_rank = np.full(
        (max_item + 1,), np.iinfo(np.int32).max // 2, dtype=np.int32
    )
    item_rank[item_order] = np.arange(item_order.size, dtype=np.int32)
    return item_order, item_rank


def csr_offsets_from_edges(
    edge_parent: np.ndarray, n_nodes: int
) -> Tuple[np.ndarray, int]:
    """CSR row index over a (parent, item)-sorted edge table.

    Returns ``(child_offsets int32[N+1], max_fanout)`` where node ``p``'s
    bucket is ``[child_offsets[p], child_offsets[p+1])``.
    """
    counts = np.bincount(
        np.asarray(edge_parent, dtype=np.int64), minlength=n_nodes
    )
    offsets = np.zeros((n_nodes + 1,), dtype=np.int32)
    np.cumsum(counts, out=offsets[1:])
    max_fanout = int(counts.max()) if counts.size else 0
    return offsets, max_fanout


def item_index_arrays(
    node_item: np.ndarray,
    dfs_order: np.ndarray,
    n_items: int,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Item-inverted index: the CSR header-table analog over the nodes.

    Groups every non-root node id by its consequent item (``node_item``)
    and sorts each group by DFS position, so item ``i``'s posting list is
    ``item_nodes[item_offsets[i]:item_offsets[i+1]]`` — every rule with
    consequent ``i``, in DFS position order.  Because the trie is
    DFS-contiguous, each posting entry's subtree range
    ``[dfs_order[v], dfs_order[v] + subtree_size[v])`` is directly
    range-intersectable with any prefix scope, and the DFS sort makes the
    per-item subtree starts ascending — which is what the
    antecedent-membership binary search (``kernels.item_index``) needs.

    Returns ``(item_offsets int32[I+1], item_nodes int32[E], max_postings)``
    where ``E = N - 1`` (every non-root node posts exactly once) and
    ``max_postings`` is the longest posting list (bounds in-kernel binary
    searches, like ``max_fanout`` bounds bucket scans).
    """
    node_item = np.asarray(node_item, np.int64)
    dfs_order = np.asarray(dfs_order, np.int64)
    nids = np.nonzero(node_item >= 0)[0]
    items = node_item[nids]
    order = np.lexsort((dfs_order[nids], items))
    item_nodes = nids[order].astype(np.int32)
    counts = np.bincount(items, minlength=max(n_items, 0))
    offsets = np.zeros((counts.shape[0] + 1,), np.int32)
    np.cumsum(counts, out=offsets[1:])
    max_postings = int(counts.max()) if counts.size else 0
    return offsets, item_nodes, max_postings


def dfs_layout(
    node_parent: np.ndarray,
    node_depth: np.ndarray,
    edge_parent: np.ndarray,
    edge_child: np.ndarray,
    child_offsets: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """DFS pre-order relabeling of a frozen trie (vectorized, host-side).

    Children are visited in CSR bucket order (item-sorted), so the DFS
    position order is deterministic.  Returns

        dfs_order     int32[N]  node id -> pre-order position (root = 0)
        subtree_size  int32[N]  node id -> |subtree(node)| (incl. itself)
        dfs_to_node   int32[N]  pre-order position -> node id (inverse perm)

    and guarantees node ``v``'s subtree occupies exactly the contiguous
    position range ``[dfs_order[v], dfs_order[v] + subtree_size[v])``.

    Vectorized per depth level instead of a per-node stack walk:
    subtree sizes accumulate bottom-up level by level, and a node's
    pre-order position is ``pos(parent) + 1 + sum(subtree sizes of earlier
    siblings)`` where the sibling sum is an exclusive segmented cumsum over
    the CSR buckets.  Level membership comes from one stable depth argsort
    (O(N log N) total), so chain-shaped tries stay linear-ish rather than
    O(N * max_depth).
    """
    node_parent = np.asarray(node_parent, np.int64)
    node_depth = np.asarray(node_depth, np.int64)
    edge_parent = np.asarray(edge_parent, np.int64)
    edge_child = np.asarray(edge_child, np.int64)
    child_offsets = np.asarray(child_offsets, np.int64)
    n = node_parent.shape[0]
    empty = np.zeros((0,), np.int32)
    if n == 0:
        return empty, empty, empty

    max_depth = int(node_depth.max()) if n else 0
    # node ids grouped by depth: by_depth[bounds[d]:bounds[d+1]] = level d
    by_depth = np.argsort(node_depth, kind="stable")
    bounds = np.searchsorted(
        node_depth[by_depth], np.arange(max_depth + 2)
    )

    subtree_size = np.ones((n,), np.int64)
    for d in range(max_depth, 0, -1):
        nids = by_depth[bounds[d]:bounds[d + 1]]
        np.add.at(subtree_size, node_parent[nids], subtree_size[nids])

    # Exclusive prefix of subtree sizes within each CSR bucket = the number
    # of pre-order slots consumed by a child's earlier siblings.
    sizes = subtree_size[edge_child]
    cum = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    earlier_siblings = cum - cum[child_offsets[edge_parent]]

    # edges grouped by child depth, for the top-down position sweep
    e_depth = node_depth[edge_child]
    e_by_depth = np.argsort(e_depth, kind="stable")
    e_bounds = np.searchsorted(
        e_depth[e_by_depth], np.arange(max_depth + 2)
    )
    pos = np.zeros((n,), np.int64)
    for d in range(1, max_depth + 1):
        eids = e_by_depth[e_bounds[d]:e_bounds[d + 1]]
        pos[edge_child[eids]] = (
            pos[edge_parent[eids]] + 1 + earlier_siblings[eids]
        )
    dfs_to_node = np.zeros((n,), np.int32)
    dfs_to_node[pos] = np.arange(n, dtype=np.int32)
    return (
        pos.astype(np.int32),
        subtree_size.astype(np.int32),
        dfs_to_node,
    )


# ----------------------------------------------------------------------
# path compression (Patricia spans) — PR 8
# ----------------------------------------------------------------------
# layout="auto" compresses when at least this fraction of edges sit on
# single-child chains (below it the span machinery buys little and plain
# keeps the parent-pointer extras like reconstruct_paths).
AUTO_COMPRESS_SPAN_FRACTION = 0.5


def chain_spans(child_counts_pos: np.ndarray):
    """Level-free vectorized chain-run detector in DFS-position space.

    ``child_counts_pos[p]`` is the child count of the node at DFS
    position ``p``.  A position is a *span position* when its node has
    exactly one child and is not the root: its single child occupies the
    very next pre-order position, so every maximal single-child run is a
    contiguous interval of span positions and detection is one boolean
    scan — no per-level loop, no pointer jumping.

    Returns ``(is_span bool[N], run_end int64[N])`` where ``run_end[p]``
    is the first non-span position at or after ``p`` (the run's tail
    node for any span position ``p``); equivalently the run starting at
    span position ``p`` covers ``run_end[p] - p`` interior steps before
    landing on its tail.
    """
    cc = np.asarray(child_counts_pos, np.int64)
    n = cc.shape[0]
    is_span = cc == 1
    if n:
        is_span[0] = False  # the root always keeps its CSR row
    idx = np.arange(n, dtype=np.int64)
    # suffix-min of non-span positions = first non-span at/after p.  A
    # span position always has a non-span tail after it (the last DFS
    # position is a leaf), so the N sentinel never escapes for spans.
    nonspan = np.where(~is_span, idx, n)
    run_end = np.minimum.accumulate(nonspan[::-1])[::-1] if n else nonspan
    return is_span, run_end


def compress_pos_space(
    child_counts_pos: np.ndarray,
    edge_parent_pos: np.ndarray,
    edge_item: np.ndarray,
    edge_child_pos: np.ndarray,
):
    """Core of the compressed encoding, shared by the whole-trie path and
    the per-shard path (``distributed.trie_sharding``): everything is in
    DFS-position space, where local ids and pre-order positions coincide.

    Only edges whose parent keeps a CSR row survive; each surviving edge
    records its child's DFS position, the number of span (single-child
    interior) steps that follow it, and the compressed id of the run's
    tail — the node whose CSR bucket continues the descent.

    Returns a dict with ``is_span``, ``cnode_of_pos`` (DFS position →
    compressed id, valid at non-span positions), ``child_offsets``
    (int32[Nc+1]), ``edge_parent`` (compressed parent ids), ``edge_item``,
    ``edge_pos`` (child DFS position), ``edge_span``, ``edge_tail`` and
    ``max_fanout``.
    """
    cc = np.asarray(child_counts_pos, np.int64)
    ep = np.asarray(edge_parent_pos, np.int64)
    ei = np.asarray(edge_item, np.int64)
    ec = np.asarray(edge_child_pos, np.int64)
    is_span, run_end = chain_spans(cc)
    cnode_of_pos = np.cumsum(~is_span) - 1
    n_cnodes = int(cnode_of_pos[-1]) + 1 if cc.shape[0] else 0

    keep = ~is_span[ep] if ep.size else np.zeros((0,), bool)
    kp = cnode_of_pos[ep[keep]]
    ki = ei[keep]
    kc = ec[keep]
    order = np.lexsort((ki, kp))  # bucket-major, item-sorted inside
    kp, ki, kc = kp[order], ki[order], kc[order]
    span = np.where(is_span[kc], run_end[kc] - kc, 0)
    tail = cnode_of_pos[kc + span]

    counts = np.bincount(kp, minlength=max(n_cnodes, 0))
    offsets = np.zeros((n_cnodes + 1,), np.int32)
    np.cumsum(counts, out=offsets[1:])
    return {
        "is_span": is_span,
        "cnode_of_pos": cnode_of_pos.astype(np.int32),
        "child_offsets": offsets,
        "edge_parent": kp.astype(np.int32),
        "edge_item": ki.astype(np.int32),
        "edge_pos": kc.astype(np.int32),
        "edge_span": span.astype(np.int32),
        "edge_tail": tail.astype(np.int32),
        "max_fanout": int(counts.max()) if counts.size else 0,
    }


def quantize_metric_columns(
    support: np.ndarray,
    confidence: np.ndarray,
    lift: np.ndarray,
    n_transactions: int = 0,
    columns: str = "bf16",
):
    """Column quantization pass for the compressed layout.

    * ``support`` → exact int32 transaction counts when
      ``n_transactions`` is known (the fp32 ratio is reconstructed
      in-kernel by ``metrics_inkernel.dequantize_metrics``), else bf16;
    * ``confidence`` / ``lift`` → bf16 (default) or int8 through
      ``distributed.compression.quantize_int8`` (per-column fp32 scale).

    Returns ``(support_q, confidence_q, lift_q, n_transactions,
    confidence_scale, lift_scale)``.
    """
    if columns not in ("bf16", "int8"):
        raise ValueError(f"unknown quantized column dtype {columns!r}")
    bf16 = jnp.bfloat16
    if n_transactions and n_transactions > 0:
        counts = np.rint(
            np.asarray(support, np.float64) * float(n_transactions)
        ).astype(np.int32)
        sup_q = counts
    else:
        n_transactions = 0
        sup_q = np.asarray(support, np.float32).astype(bf16)
    conf_scale = lift_scale = 1.0
    if columns == "int8":
        # wire through the gradient-compression helpers (same encoding,
        # same scale convention) rather than re-deriving the math here
        from repro.distributed.compression import quantize_int8

        cq, cs = quantize_int8(jnp.asarray(confidence, jnp.float32))
        lq, ls = quantize_int8(jnp.asarray(lift, jnp.float32))
        conf_q = np.asarray(cq)
        lift_q = np.asarray(lq)
        conf_scale = float(cs)
        lift_scale = float(ls)
    else:
        conf_q = np.asarray(confidence, np.float32).astype(bf16)
        lift_q = np.asarray(lift, np.float32).astype(bf16)
    return sup_q, conf_q, lift_q, int(n_transactions), conf_scale, lift_scale


def _sorted_posting_bounds(
    item_offsets: np.ndarray,
    item_nodes: np.ndarray,
    dfs_order: np.ndarray,
    subtree_size: np.ndarray,
):
    """Posting subtree ranges in DFS coordinates: ``post_lo`` in posting
    order (ascending per item by the DFS sort), ``post_hi`` re-sorted
    ascending within each item segment — the two monotone arrays the
    membership kernel's laminar range count binary-searches."""
    nodes = np.asarray(item_nodes, np.int64)
    dfs = np.asarray(dfs_order, np.int64)
    sub = np.asarray(subtree_size, np.int64)
    n = int(dfs.shape[0])
    lo = dfs[nodes]
    hi = lo + sub[nodes]
    seg = np.repeat(
        np.arange(item_offsets.shape[0] - 1, dtype=np.int64),
        np.diff(item_offsets),
    )
    order = np.argsort(seg * (n + 1) + hi, kind="stable")
    return lo.astype(np.int32), hi[order].astype(np.int32)


@dataclass
class CompressedTrie:
    """Path-compressed frozen layout, host-side (DFS-position space).

    Node-axis arrays (``*_pos``) are indexed by DFS pre-order position —
    span interiors keep only their metric tuple here; structural rows
    exist only for the ``child_offsets``/``edge_*`` compressed CSR over
    run heads, tails, and branching nodes.  ``device_arrays`` reuses the
    ``DeviceTrie`` container with ``layout="compressed"``: the node
    columns carry the position-space arrays, ``edge_child`` carries child
    DFS *positions*, and ``edge_span``/``edge_tail`` drive the span-aware
    descent.
    """

    item_pos: np.ndarray        # int32[N]  DFS-ordered consequent items
    depth_pos: np.ndarray       # int32[N]
    subtree_pos: np.ndarray     # int32[N]  subtree sizes, DFS order
    dfs_to_node: np.ndarray     # int32[N]  position -> original node id
    support_pos: np.ndarray     # f32|int32|bf16[N]
    confidence_pos: np.ndarray  # f32|bf16|int8[N]
    lift_pos: np.ndarray        # f32|bf16|int8[N]
    child_offsets: np.ndarray   # int32[Nc+1] compressed CSR
    edge_parent: np.ndarray     # int32[Ec]  compressed parent ids
    edge_item: np.ndarray       # int32[Ec]  first item of the edge
    edge_pos: np.ndarray        # int32[Ec]  child DFS position
    edge_span: np.ndarray       # int32[Ec]  interior steps after the child
    edge_tail: np.ndarray       # int32[Ec]  compressed id of the run tail
    max_fanout: int
    item_offsets: np.ndarray    # int32[I+1] posting buckets
    post_lo: np.ndarray         # int32[E]   posting DFS starts
    post_hi: np.ndarray         # int32[E]   posting DFS ends (sorted/item)
    max_postings: int
    n_transactions: int = 0     # 0 = support column not count-encoded
    confidence_scale: float = 1.0
    lift_scale: float = 1.0

    @property
    def n_nodes(self) -> int:
        return int(self.item_pos.shape[0])

    @property
    def n_edges(self) -> int:
        """Logical (uncompressed) edge count."""
        return self.n_nodes - 1 if self.n_nodes else 0

    @property
    def n_compressed_edges(self) -> int:
        return int(self.edge_item.shape[0])

    @property
    def span_fraction(self) -> float:
        """Fraction of logical edges absorbed into spans."""
        e = self.n_edges
        return 1.0 - self.n_compressed_edges / e if e else 0.0

    def nbytes(self) -> int:
        """Total bytes of the device-resident layout (all leaves)."""
        return sum(
            np.asarray(a).nbytes
            for a in (
                self.item_pos, self.depth_pos, self.subtree_pos,
                self.dfs_to_node, self.support_pos, self.confidence_pos,
                self.lift_pos, self.child_offsets, self.edge_parent,
                self.edge_item, self.edge_pos, self.edge_span,
                self.edge_tail, self.item_offsets, self.post_lo,
                self.post_hi,
            )
        )

    def expand_edges(self):
        """Round-trip check: re-expand spans into the full edge set.

        Returns ``(parent_pos, item, child_pos)`` for every logical edge
        in child-position order — compare against the plain layout's edge
        table mapped through ``dfs_order``.  Every position inside a span
        is the child of the position directly before it (the pre-order
        chain property the encoding rests on); run heads attach to their
        compressed parent's position.
        """
        n = self.n_nodes
        parents = np.full((n,), -1, np.int64)
        in_span_tail = np.zeros((n,), bool)
        ec = np.asarray(self.edge_pos, np.int64)
        es = np.asarray(self.edge_span, np.int64)
        # positions covered by a span (interiors' children + the tail):
        # child of position p is p+1 for every p in [edge_pos, edge_pos+span)
        for c, s in zip(ec, es):
            for q in range(c, c + s):
                parents[q + 1] = q
                in_span_tail[q + 1] = True
        # compressed-node positions in compressed-id order = the non-span,
        # non-tail-of-chain structural rows: recover from the CSR ownership
        is_cnode = np.ones((n,), bool)
        for c, s in zip(ec, es):
            is_cnode[c:c + s] = False
        cpos = np.nonzero(is_cnode)[0]
        for j, c in enumerate(ec):
            parents[c] = cpos[int(self.edge_parent[j])]
        child = np.arange(1, n, dtype=np.int64)
        return parents[1:], np.asarray(self.item_pos, np.int64)[1:], child

    def device_arrays(self) -> "DeviceTrie":
        return DeviceTrie(
            node_item=jnp.asarray(self.item_pos),
            node_parent=jnp.zeros((0,), jnp.int32),
            node_depth=jnp.asarray(self.depth_pos),
            support=jnp.asarray(self.support_pos),
            confidence=jnp.asarray(self.confidence_pos),
            lift=jnp.asarray(self.lift_pos),
            edge_parent=jnp.asarray(self.edge_parent),
            edge_item=jnp.asarray(self.edge_item),
            edge_child=jnp.asarray(self.edge_pos),
            child_offsets=jnp.asarray(self.child_offsets),
            max_fanout=self.max_fanout,
            dfs_order=None,
            subtree_size=jnp.asarray(self.subtree_pos),
            dfs_to_node=jnp.asarray(self.dfs_to_node),
            item_offsets=jnp.asarray(self.item_offsets),
            item_nodes=None,
            max_postings=self.max_postings,
            edge_span=jnp.asarray(self.edge_span),
            edge_tail=jnp.asarray(self.edge_tail),
            post_lo=jnp.asarray(self.post_lo),
            post_hi=jnp.asarray(self.post_hi),
            layout="compressed",
            n_transactions=self.n_transactions,
            confidence_scale=self.confidence_scale,
            lift_scale=self.lift_scale,
        )


@dataclass
class FrozenTrie:
    """Immutable SoA trie; arrays are numpy on host, moved to jnp lazily."""

    node_item: np.ndarray      # int32[N], root = -1
    node_parent: np.ndarray    # int32[N], root = -1
    node_depth: np.ndarray     # int32[N]
    support: np.ndarray        # float32[N]
    confidence: np.ndarray     # float32[N]
    lift: np.ndarray           # float32[N]
    edge_parent: np.ndarray    # int32[E] sorted by (parent, item)
    edge_item: np.ndarray      # int32[E]
    edge_child: np.ndarray     # int32[E]
    item_order: np.ndarray     # int32[n_items] frequency rank -> item
    item_rank: np.ndarray      # int32[max_item+1] item -> frequency rank
    child_offsets: Optional[np.ndarray] = None  # int32[N+1] CSR buckets
    max_fanout: int = 0        # widest child bucket (bounds per-step scans)
    dfs_order: Optional[np.ndarray] = None     # int32[N] node -> DFS pos
    subtree_size: Optional[np.ndarray] = None  # int32[N] node -> |subtree|
    dfs_to_node: Optional[np.ndarray] = None   # int32[N] DFS pos -> node
    item_offsets: Optional[np.ndarray] = None  # int32[I+1] posting buckets
    item_nodes: Optional[np.ndarray] = None    # int32[E] DFS-sorted postings
    max_postings: int = 0      # longest posting list (bounds index searches)

    def __post_init__(self):
        if self.child_offsets is None:
            self.child_offsets, self.max_fanout = csr_offsets_from_edges(
                self.edge_parent, self.node_item.shape[0]
            )
        if self.dfs_order is None:
            self.dfs_order, self.subtree_size, self.dfs_to_node = dfs_layout(
                self.node_parent, self.node_depth,
                self.edge_parent, self.edge_child, self.child_offsets,
            )
        if self.item_offsets is None:
            # Both construction engines land here (freeze and the
            # array-native build share this constructor), so the inverted
            # index is part of the frozen layout, not an opt-in.
            n_items = max(
                int(self.item_rank.shape[0]),
                int(self.node_item.max(initial=-1)) + 1,
            )
            self.item_offsets, self.item_nodes, self.max_postings = (
                item_index_arrays(self.node_item, self.dfs_order, n_items)
            )

    @property
    def n_nodes(self) -> int:
        return int(self.node_item.shape[0])

    @property
    def n_edges(self) -> int:
        return int(self.edge_parent.shape[0])

    @property
    def max_depth(self) -> int:
        return int(self.node_depth.max()) if self.n_nodes > 1 else 0

    # ------------------------------------------------------------------
    # freeze
    # ------------------------------------------------------------------
    @classmethod
    def freeze(cls, trie: TrieOfRules) -> "FrozenTrie":
        """BFS-number the pointer trie into flat arrays."""
        nodes: List[TrieNode] = [trie.root]
        ids = {id(trie.root): 0}
        q = deque([trie.root])
        while q:
            node = q.popleft()
            for child in sorted(node.children.values(), key=lambda c: c.item):
                ids[id(child)] = len(nodes)
                nodes.append(child)
                q.append(child)
        n = len(nodes)
        node_item = np.full((n,), -1, dtype=np.int32)
        node_parent = np.full((n,), -1, dtype=np.int32)
        node_depth = np.zeros((n,), dtype=np.int32)
        support = np.zeros((n,), dtype=np.float32)
        confidence = np.zeros((n,), dtype=np.float32)
        lift = np.zeros((n,), dtype=np.float32)
        edges: List[Tuple[int, int, int]] = []
        for i, node in enumerate(nodes):
            node_item[i] = node.item
            node_depth[i] = node.depth
            support[i] = node.support
            confidence[i] = node.confidence
            lift[i] = node.lift
            if node.parent is not None:
                node_parent[i] = ids[id(node.parent)]
            for child in node.children.values():
                edges.append((i, child.item, ids[id(child)]))
        edges.sort()
        e = np.array(edges, dtype=np.int32).reshape(-1, 3)
        rank_pairs = sorted(trie._rank.items(), key=lambda kv: kv[1])
        item_order, item_rank = item_tables([it for it, _ in rank_pairs])
        return cls(
            node_item=node_item,
            node_parent=node_parent,
            node_depth=node_depth,
            support=support,
            confidence=confidence,
            lift=lift,
            edge_parent=e[:, 0].copy() if e.size else np.zeros(0, np.int32),
            edge_item=e[:, 1].copy() if e.size else np.zeros(0, np.int32),
            edge_child=e[:, 2].copy() if e.size else np.zeros(0, np.int32),
            item_order=item_order,
            item_rank=item_rank,
        )

    # ------------------------------------------------------------------
    # host-side helpers
    # ------------------------------------------------------------------
    def canonicalize_queries(
        self,
        antecedents: Sequence[Sequence[Item]],
        consequents: Sequence[Sequence[Item]],
        max_len: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Pack (A, C) query pairs into the padded item matrix + ant lengths.

        Items inside A and inside C are frequency-sorted independently and
        concatenated — exactly the pointer implementation's canonical form.
        """
        def rank(it: int) -> int:
            if 0 <= it < self.item_rank.shape[0]:
                return int(self.item_rank[it])
            return 1 << 30

        rows: List[List[int]] = []
        ant_lens: List[int] = []
        for a, c in zip(antecedents, consequents):
            sa = sorted(a, key=lambda it: (rank(it), it))
            sc = sorted(c, key=lambda it: (rank(it), it))
            rows.append(list(sa) + list(sc))
            ant_lens.append(len(sa))
        width = max_len or max((len(r) for r in rows), default=1)
        mat = np.full((len(rows), width), -1, dtype=np.int32)
        for i, r in enumerate(rows):
            if len(r) > width:
                raise ValueError("query longer than max_len")
            mat[i, : len(r)] = r
        return mat, np.array(ant_lens, dtype=np.int32)

    def device_arrays(
        self,
        layout: str = "plain",
        quantize: bool = False,
        n_transactions: int = 0,
        columns: str = "bf16",
    ) -> "DeviceTrie":
        """Move the frozen layout to device.

        ``layout``: ``"plain"`` (default, the historical encoding),
        ``"compressed"`` (path-compressed spans, see ``compress``), or
        ``"auto"`` — compressed when at least
        ``AUTO_COMPRESS_SPAN_FRACTION`` of the edges sit on single-child
        chains (rule tries usually qualify), plain otherwise.  The
        quantization knobs only apply to the compressed layout.
        """
        if layout not in ("plain", "compressed", "auto"):
            raise ValueError(f"unknown layout {layout!r}")
        if layout == "auto":
            layout = (
                "compressed"
                if self.span_fraction() >= AUTO_COMPRESS_SPAN_FRACTION
                else "plain"
            )
        if layout == "compressed":
            return self.compress(
                quantize=quantize, n_transactions=n_transactions,
                columns=columns,
            ).device_arrays()
        return DeviceTrie(
            node_item=jnp.asarray(self.node_item),
            node_parent=jnp.asarray(self.node_parent),
            node_depth=jnp.asarray(self.node_depth),
            support=jnp.asarray(self.support),
            confidence=jnp.asarray(self.confidence),
            lift=jnp.asarray(self.lift),
            edge_parent=jnp.asarray(self.edge_parent),
            edge_item=jnp.asarray(self.edge_item),
            edge_child=jnp.asarray(self.edge_child),
            child_offsets=jnp.asarray(self.child_offsets),
            max_fanout=self.max_fanout,
            dfs_order=jnp.asarray(self.dfs_order),
            subtree_size=jnp.asarray(self.subtree_size),
            dfs_to_node=jnp.asarray(self.dfs_to_node),
            item_offsets=jnp.asarray(self.item_offsets),
            item_nodes=jnp.asarray(self.item_nodes),
            max_postings=self.max_postings,
        )

    def span_fraction(self) -> float:
        """Fraction of edges absorbed into spans by path compression:
        non-root nodes with exactly one child, over all edges."""
        if self.n_edges == 0:
            return 0.0
        cc = np.diff(np.asarray(self.child_offsets, np.int64))
        chain = int(np.count_nonzero(cc[1:] == 1))
        return chain / self.n_edges

    def compress(
        self,
        quantize: bool = False,
        n_transactions: int = 0,
        columns: str = "bf16",
    ) -> CompressedTrie:
        """Path-compress into the Patricia span layout (DFS-position
        space; module docstring has the memory model).

        ``quantize=True`` narrows the metric columns — pass the mining
        DB's ``n_transactions`` to store support as exact int32 counts
        (error ≤ 2 ulp after in-kernel ratio reconstruction), and pick
        ``columns`` in ``{"bf16", "int8"}`` for confidence/lift.
        Both construction engines land here: ``freeze`` and
        ``build_arrays.build_frozen_trie`` produce bit-identical frozen
        arrays, so their compressed encodings coincide too.
        """
        dfs = np.asarray(self.dfs_order, np.int64)
        d2n = np.asarray(self.dfs_to_node, np.int64)
        cc = np.diff(np.asarray(self.child_offsets, np.int64))
        comp = compress_pos_space(
            cc[d2n] if d2n.size else cc,
            dfs[self.edge_parent] if self.n_edges else self.edge_parent,
            self.edge_item,
            dfs[self.edge_child] if self.n_edges else self.edge_child,
        )
        sup = np.asarray(self.support, np.float32)[d2n]
        conf = np.asarray(self.confidence, np.float32)[d2n]
        lift = np.asarray(self.lift, np.float32)[d2n]
        conf_scale = lift_scale = 1.0
        n_tx = 0
        if quantize:
            sup, conf, lift, n_tx, conf_scale, lift_scale = (
                quantize_metric_columns(
                    sup, conf, lift, n_transactions, columns
                )
            )
        post_lo, post_hi = _sorted_posting_bounds(
            self.item_offsets, self.item_nodes,
            self.dfs_order, self.subtree_size,
        )
        return CompressedTrie(
            item_pos=np.asarray(self.node_item, np.int32)[d2n],
            depth_pos=np.asarray(self.node_depth, np.int32)[d2n],
            subtree_pos=np.asarray(self.subtree_size, np.int32)[d2n],
            dfs_to_node=np.asarray(self.dfs_to_node, np.int32),
            support_pos=sup,
            confidence_pos=conf,
            lift_pos=lift,
            child_offsets=comp["child_offsets"],
            edge_parent=comp["edge_parent"],
            edge_item=comp["edge_item"],
            edge_pos=comp["edge_pos"],
            edge_span=comp["edge_span"],
            edge_tail=comp["edge_tail"],
            max_fanout=comp["max_fanout"],
            item_offsets=np.asarray(self.item_offsets, np.int32),
            post_lo=post_lo,
            post_hi=post_hi,
            max_postings=self.max_postings,
            n_transactions=n_tx,
            confidence_scale=conf_scale,
            lift_scale=lift_scale,
        )

    def depth1_subtrees(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Shard metadata: the root's child subtrees in DFS order.

        Returns ``(child_ids, dfs_los, sizes)`` — for each depth-1 node
        (root bucket order = item-sorted = DFS order) its node id, its
        subtree's DFS start position, and its subtree size.  Because the
        layout is DFS-contiguous, these subtrees tile ``[1, N)`` with
        consecutive ranges ``[dfs_los[t], dfs_los[t] + sizes[t])`` — the
        natural shard boundaries the multi-device partitioner
        (``repro.distributed.trie_sharding``) bin-packs into contiguous
        DFS ranges.  The pointer-trie parity oracle is
        ``TrieOfRules.depth1_subtree_sizes``.
        """
        lo, hi = int(self.child_offsets[0]), int(self.child_offsets[1])
        kids = self.edge_child[lo:hi].astype(np.int64)
        order = np.argsort(self.dfs_order[kids], kind="stable")
        kids = kids[order]
        return (
            kids.astype(np.int32),
            self.dfs_order[kids].astype(np.int32),
            self.subtree_size[kids].astype(np.int32),
        )

    def path_items(self, node_id: int) -> Tuple[Item, ...]:
        items: List[int] = []
        nid = int(node_id)
        while nid > 0:
            items.append(int(self.node_item[nid]))
            nid = int(self.node_parent[nid])
        return tuple(reversed(items))


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceTrie:
    """The on-device view (a pytree of jnp arrays).

    ``child_offsets`` is the CSR row index over the edge table; ``None``
    selects the seed full-table binary-search path.  ``max_fanout`` is
    static metadata (pytree aux) so jitted callers can size the bucket
    search at trace time.  ``dfs_order`` / ``subtree_size`` /
    ``dfs_to_node`` carry the DFS-contiguous relabeling consumed by the
    segmented top-k rank path (``None`` on tries frozen without one).
    ``item_offsets`` / ``item_nodes`` carry the item-inverted index
    (posting lists by consequent item, DFS-sorted) consumed by the
    item-scoped batched query ops; ``max_postings`` is its static
    metadata companion (pytree aux alongside ``max_fanout``).

    ``layout`` (static aux) selects the encoding the batched ops and
    kernels descend:

    * ``"plain"`` — the historical node-id-space encoding above.
    * ``"compressed"`` — path-compressed spans (``CompressedTrie``):
      node-axis columns are DFS-position-indexed, ``edge_child`` holds
      child DFS *positions*, ``edge_span``/``edge_tail`` drive the
      span-aware descent, ``post_lo``/``post_hi`` are the precomputed
      posting subtree ranges (``item_nodes``/``node_parent`` are absent),
      and the metric columns may be quantized — ``n_transactions`` /
      ``confidence_scale`` / ``lift_scale`` (static aux) parameterize
      the in-kernel fp32 reconstruction.
    """

    node_item: jax.Array
    node_parent: jax.Array
    node_depth: jax.Array
    support: jax.Array
    confidence: jax.Array
    lift: jax.Array
    edge_parent: jax.Array
    edge_item: jax.Array
    edge_child: jax.Array
    child_offsets: Optional[jax.Array] = None
    max_fanout: int = 0
    dfs_order: Optional[jax.Array] = None
    subtree_size: Optional[jax.Array] = None
    dfs_to_node: Optional[jax.Array] = None
    item_offsets: Optional[jax.Array] = None
    item_nodes: Optional[jax.Array] = None
    max_postings: int = 0
    edge_span: Optional[jax.Array] = None   # int32[Ec] compressed only
    edge_tail: Optional[jax.Array] = None   # int32[Ec] compressed only
    post_lo: Optional[jax.Array] = None     # int32[E]  compressed only
    post_hi: Optional[jax.Array] = None     # int32[E]  compressed only
    layout: str = "plain"
    n_transactions: int = 0
    confidence_scale: float = 1.0
    lift_scale: float = 1.0

    def tree_flatten(self):
        fields = (
            self.node_item, self.node_parent, self.node_depth,
            self.support, self.confidence, self.lift,
            self.edge_parent, self.edge_item, self.edge_child,
            self.child_offsets,
            self.dfs_order, self.subtree_size, self.dfs_to_node,
            self.item_offsets, self.item_nodes,
            self.edge_span, self.edge_tail, self.post_lo, self.post_hi,
        )
        return fields, (
            self.max_fanout, self.max_postings, self.layout,
            self.n_transactions, self.confidence_scale, self.lift_scale,
        )

    @classmethod
    def tree_unflatten(cls, aux, fields):
        (max_fanout, max_postings, layout,
         n_transactions, confidence_scale, lift_scale) = aux
        return cls(
            *fields[:9], child_offsets=fields[9], max_fanout=max_fanout,
            dfs_order=fields[10], subtree_size=fields[11],
            dfs_to_node=fields[12],
            item_offsets=fields[13], item_nodes=fields[14],
            max_postings=max_postings,
            edge_span=fields[15], edge_tail=fields[16],
            post_lo=fields[17], post_hi=fields[18],
            layout=layout, n_transactions=n_transactions,
            confidence_scale=confidence_scale, lift_scale=lift_scale,
        )

    def nbytes(self) -> int:
        """Device-resident bytes across all present leaves — the number
        the compressed-layout bench compares (plain vs compressed)."""
        leaves, _ = self.tree_flatten()
        return sum(
            int(a.size) * a.dtype.itemsize for a in leaves if a is not None
        )


# ----------------------------------------------------------------------
# vectorized operations (the jnp oracle shared with the Pallas kernels)
# ----------------------------------------------------------------------
def _lex_binary_search(
    edge_parent: jax.Array,
    edge_item: jax.Array,
    qp: jax.Array,
    qi: jax.Array,
    n_steps: int,
) -> jax.Array:
    """Lower-bound index of (qp, qi) in the lex-sorted edge table.

    ``qp``/``qi`` are arbitrary-shaped int32; returns same-shaped indices.
    A fixed ``n_steps = ceil(log2(E))+1`` iteration count keeps this
    trace-friendly (and is the exact loop the Pallas kernel runs in VMEM).
    """
    e = edge_parent.shape[0]
    lo = jnp.zeros_like(qp)
    hi = jnp.full_like(qp, e)
    for _ in range(n_steps):
        mid = (lo + hi) // 2
        midc = jnp.minimum(mid, e - 1)
        p = edge_parent[midc]
        i = edge_item[midc]
        less = (p < qp) | ((p == qp) & (i < qi))
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(less, hi, mid)
    return lo


def _n_search_steps(n_edges: int) -> int:
    n = max(int(n_edges), 1)
    return int(np.ceil(np.log2(n + 1))) + 1


def bucket_edge_lookup(
    child_offsets: jax.Array,
    edge_item: jax.Array,
    max_fanout: int,
    parents: jax.Array,
    items: jax.Array,
) -> jax.Array:
    """Batched CSR-bucket lower-bound: the *edge index* of
    ``(parents, items)``, -1 where no such edge.

    The binary search is confined to the parent's child bucket —
    ``O(log max_fanout)`` steps instead of ``O(log E)`` — with a fixed
    iteration count from the static ``max_fanout`` so it stays
    trace-friendly.  Shared by the plain descent (``child_lookup``
    returns ``edge_child`` at this index) and the compressed descent
    (which also needs ``edge_span``/``edge_tail`` at the same index).
    """
    e = edge_item.shape[0]
    if e == 0:
        return jnp.full_like(parents, -1)
    n = child_offsets.shape[0] - 1
    p_ok = (parents >= 0) & (parents < n)
    p = jnp.clip(parents, 0, n - 1)
    lo = child_offsets[p]
    bucket_hi = child_offsets[p + 1]
    hi = bucket_hi
    for _ in range(_n_search_steps(max(max_fanout, 1))):
        mid = (lo + hi) // 2
        midc = jnp.minimum(mid, e - 1)
        less = edge_item[midc] < items
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(less, hi, mid)
    loc = jnp.minimum(lo, e - 1)
    found = p_ok & (lo < bucket_hi) & (edge_item[loc] == items)
    return jnp.where(found, loc, -1)


def child_lookup(
    trie: DeviceTrie, parents: jax.Array, items: jax.Array
) -> jax.Array:
    """Batched child id for (parent, item); -1 where no such edge.

    With CSR ``child_offsets`` this is ``bucket_edge_lookup`` plus an
    ``edge_child`` gather.  Without them (seed layout) it falls back to
    the full-table lexicographic search.
    """
    e = trie.edge_parent.shape[0]
    if e == 0:
        return jnp.full_like(parents, -1)
    if trie.child_offsets is None:
        idx = _lex_binary_search(
            trie.edge_parent, trie.edge_item, parents, items,
            _n_search_steps(e),
        )
        idxc = jnp.minimum(idx, e - 1)
        found = (
            (idx < e)
            & (trie.edge_parent[idxc] == parents)
            & (trie.edge_item[idxc] == items)
        )
        return jnp.where(found, trie.edge_child[idxc], -1)

    j = bucket_edge_lookup(
        trie.child_offsets, trie.edge_item, trie.max_fanout, parents, items
    )
    return jnp.where(j >= 0, trie.edge_child[jnp.maximum(j, 0)], -1)


def _dequantized_columns(trie: DeviceTrie):
    """fp32 view of the three metric columns, honoring quantization.

    Same math as ``kernels.metrics_inkernel.dequantize_metrics`` (kept
    local: core must not depend on the kernels package — the compound-
    lift select below has the same duplication note).  fp32 columns pass
    through untouched, so the unquantized compressed layout stays
    bit-identical to plain through this function.
    """
    def col(a, scale):
        if a.dtype == jnp.float32:
            return a
        if a.dtype == jnp.int8:
            return a.astype(jnp.float32) * jnp.float32(scale)
        return a.astype(jnp.float32)

    sup = trie.support
    if sup.dtype == jnp.int32:
        sup = sup.astype(jnp.float32) / jnp.float32(
            max(int(trie.n_transactions), 1)
        )
    elif sup.dtype != jnp.float32:
        sup = sup.astype(jnp.float32)
    return (
        sup,
        col(trie.confidence, trie.confidence_scale),
        col(trie.lift, trie.lift_scale),
    )


def span_step(
    child_offsets: jax.Array,
    edge_item: jax.Array,
    edge_child: jax.Array,
    edge_span: jax.Array,
    edge_tail: jax.Array,
    node_item: jax.Array,
    max_fanout: int,
    pos: jax.Array,
    rem: jax.Array,
    ctail: jax.Array,
    items: jax.Array,
):
    """One item-consumption step of the span-aware descent, over the
    compressed columns themselves (``edge_child`` holds child DFS
    positions).

    State per query column: ``pos`` (current DFS position), ``rem``
    (span steps left before the next CSR node), ``ctail`` (compressed id
    of the run tail — the node whose bucket continues the descent once
    ``rem`` hits 0).  Inside a span (``rem > 0``) the next pre-order
    position IS the single child, so the probe is one gather of the
    DFS-ordered item column; at a CSR node it is a bucket binary search.
    Returns the advanced ``(pos, rem, ctail, hit)`` — callers gate the
    state update on their own activity mask.
    """
    n = node_item.shape[0]
    in_span = rem > 0
    nxt = jnp.minimum(pos + 1, n - 1)
    span_hit = in_span & (node_item[nxt] == items)
    j = bucket_edge_lookup(child_offsets, edge_item, max_fanout, ctail, items)
    edge_hit = (~in_span) & (j >= 0)
    jc = jnp.maximum(j, 0)
    if edge_child.shape[0]:
        e_pos = edge_child[jc]
        e_span = edge_span[jc]
        e_tail = edge_tail[jc]
    else:  # single-node trie: no edges to gather from
        e_pos = e_span = e_tail = jnp.zeros_like(pos)
    pos = jnp.where(span_hit, pos + 1, jnp.where(edge_hit, e_pos, pos))
    rem = jnp.where(span_hit, rem - 1, jnp.where(edge_hit, e_span, rem))
    ctail = jnp.where(edge_hit, e_tail, ctail)
    return pos, rem, ctail, span_hit | edge_hit


def compressed_step(
    trie: DeviceTrie,
    pos: jax.Array,
    rem: jax.Array,
    ctail: jax.Array,
    items: jax.Array,
):
    """``span_step`` over a compressed ``DeviceTrie``."""
    return span_step(
        trie.child_offsets, trie.edge_item, trie.edge_child,
        trie.edge_span, trie.edge_tail, trie.node_item, trie.max_fanout,
        pos, rem, ctail, items,
    )


def compressed_descend(trie: DeviceTrie, queries: jax.Array):
    """Resolve padded item rows to DFS positions on a compressed trie.

    queries: int32[Q, L] frequency-canonical rows, -1 padded.  Returns
    ``(pos int32[Q], found bool[Q])`` — the position of the node spelling
    the full row (root for all-padding rows).  The compressed analog of
    a ``child_lookup`` fold; ``ops.prefix_ranges`` builds subtree ranges
    from it via the position-space ``subtree_size``.
    """
    q = queries.shape[0]

    def step(carry, items):
        pos, rem, ctail, ok = carry
        active = (items >= 0) & ok
        pos2, rem2, ctail2, hit = compressed_step(trie, pos, rem, ctail, items)
        ok = jnp.where(active, hit, ok)
        adv = active & hit
        pos = jnp.where(adv, pos2, pos)
        rem = jnp.where(adv, rem2, rem)
        ctail = jnp.where(adv, ctail2, ctail)
        return (pos, rem, ctail, ok), None

    z = jnp.zeros((q,), jnp.int32)
    (pos, _, _, ok), _ = jax.lax.scan(
        step, (z, z, z, jnp.ones((q,), bool)), queries.T
    )
    return pos, ok


def _batched_rule_search_compressed(
    trie: DeviceTrie, queries: jax.Array, ant_len: jax.Array
):
    """Span-aware twin of the plain ``batched_rule_search`` scan below.

    Identical per-column confidence-product order and Eq. 1-4 lift
    select, so unquantized results are bit-identical to plain; the
    ``node`` output maps back to original ids via ``dfs_to_node``.
    """
    q, width = queries.shape
    sup_col, conf_col, lift_col = _dequantized_columns(trie)

    def step(carry, col):
        pos, rem, ctail, conf, ok = carry
        item, cpos = col
        active = (item >= 0) & ok
        pos2, rem2, ctail2, hit = compressed_step(trie, pos, rem, ctail, item)
        ok = jnp.where(active, hit, ok)
        adv = active & hit
        in_consequent = cpos >= ant_len
        conf = jnp.where(
            adv & in_consequent, conf * conf_col[pos2], conf
        )
        pos = jnp.where(adv, pos2, pos)
        rem = jnp.where(adv, rem2, rem)
        ctail = jnp.where(adv, ctail2, ctail)
        return (pos, rem, ctail, conf, ok), None

    z = jnp.zeros((q,), jnp.int32)
    ok0 = jnp.ones((q,), bool)
    cols = (queries.T, jnp.arange(width, dtype=jnp.int32)[:, None]
            * jnp.ones((1, q), jnp.int32))
    (pos, _, _, conf, ok), _ = jax.lax.scan(
        step, (z, z, z, jnp.ones((q,), jnp.float32), ok0), cols
    )

    def cstep(carry, col):
        cp, rem, ctail, cok = carry
        item, colp = col
        active = (item >= 0) & (colp >= ant_len) & cok
        p2, r2, t2, hit = compressed_step(trie, cp, rem, ctail, item)
        cok = jnp.where(active, hit, cok)
        adv = active & hit
        cp = jnp.where(adv, p2, cp)
        rem = jnp.where(adv, r2, rem)
        ctail = jnp.where(adv, t2, ctail)
        return (cp, rem, ctail, cok), None

    (cpos, _, _, cok), _ = jax.lax.scan(cstep, (z, z, z, ok0), cols)
    con_support = jnp.where(cok & (cpos > 0), sup_col[cpos], 0.0)

    found = ok & (pos > 0)
    sup = jnp.where(found, sup_col[pos], 0.0)
    conf = jnp.where(found, conf, 0.0)
    seq_len = jnp.sum(queries >= 0, axis=1).astype(jnp.int32)
    single = (seq_len - ant_len) == 1
    node_lift = jnp.where(found, lift_col[pos], 0.0)
    lift = jnp.where(
        single,
        node_lift,
        jnp.where(con_support > 0, conf / con_support, 0.0),
    )
    lift = jnp.where(found, lift, 0.0)
    return {
        "found": found,
        "support": sup,
        "confidence": conf,
        "lift": lift,
        "node": jnp.where(found, trie.dfs_to_node[pos], -1),
    }


@partial(jax.jit, static_argnames=())
def batched_rule_search(
    trie: DeviceTrie, queries: jax.Array, ant_len: jax.Array
):
    """Search Q rules at once.

    queries: int32[Q, L] frequency-ordered item rows, -1 padded
             (antecedent items first, consequent items after — the paper's
             canonical rule layout).
    ant_len: int32[Q] antecedent length per row.

    Returns dict with:
      found        bool[Q]    rule present as a trie path
      support      f32[Q]     Support of the full sequence (paper: node sup)
      confidence   f32[Q]     compound Confidence (Eq. 1-4 product)
      lift         f32[Q]     compound conf / Support(consequent path)
      node         int32[Q]   final consequent node id (-1 if absent)
    """
    if trie.layout == "compressed":
        return _batched_rule_search_compressed(trie, queries, ant_len)
    q, width = queries.shape

    def step(carry, col):
        node, conf, ok, ant_node = carry
        item, pos = col
        active = (item >= 0) & ok
        child = child_lookup(trie, node, item)
        ok = jnp.where(active, child >= 0, ok)
        node_next = jnp.where(active & (child >= 0), child, node)
        in_consequent = pos >= ant_len
        child_conf = jnp.where(
            child >= 0, trie.confidence[jnp.maximum(child, 0)], 0.0
        )
        conf = jnp.where(
            active & in_consequent & (child >= 0), conf * child_conf, conf
        )
        ant_node = jnp.where(
            active & (pos == ant_len - 1) & (child >= 0), child, ant_node
        )
        return (node_next, conf, ok, ant_node), None

    node0 = jnp.zeros((q,), jnp.int32)
    conf0 = jnp.ones((q,), jnp.float32)
    ok0 = jnp.ones((q,), bool)
    ant0 = jnp.zeros((q,), jnp.int32)   # root: Support(∅)=1 ⇒ conf chain ok
    cols = (queries.T, jnp.arange(width, dtype=jnp.int32)[:, None]
            * jnp.ones((1, q), jnp.int32))
    (node, conf, ok, _ant), _ = jax.lax.scan(
        step, (node0, conf0, ok0, ant0), cols
    )

    # Consequent-path support for lift: walk the consequent items from root.
    def cstep(carry, col):
        cnode, cok = carry
        item, pos = col
        active = (item >= 0) & (pos >= ant_len) & cok
        child = child_lookup(trie, cnode, item)
        cok = jnp.where(active, child >= 0, cok)
        cnode = jnp.where(active & (child >= 0), child, cnode)
        return (cnode, cok), None

    (cnode, cok), _ = jax.lax.scan(
        cstep, (node0, ok0), cols
    )
    con_support = jnp.where(
        cok & (cnode > 0), trie.support[jnp.maximum(cnode, 0)], 0.0
    )

    found = ok & (node > 0)
    sup = jnp.where(found, trie.support[jnp.maximum(node, 0)], 0.0)
    conf = jnp.where(found, conf, 0.0)
    # Single-item consequent: the final node's Step-3 lift IS the rule lift
    # (conf == node confidence there).  Compound consequents divide by the
    # consequent-path Support when that path exists in the trie.  Same
    # Eq. 1-4 select as kernels/metrics_inkernel.compound_lift (kept local:
    # core must not depend on the kernels package).
    seq_len = jnp.sum(queries >= 0, axis=1).astype(jnp.int32)
    single = (seq_len - ant_len) == 1
    node_lift = jnp.where(found, trie.lift[jnp.maximum(node, 0)], 0.0)
    lift = jnp.where(
        single,
        node_lift,
        jnp.where(con_support > 0, conf / con_support, 0.0),
    )
    lift = jnp.where(found, lift, 0.0)
    return {
        "found": found,
        "support": sup,
        "confidence": conf,
        "lift": lift,
        "node": jnp.where(found, node, -1),
    }


@partial(jax.jit, static_argnames=("n", "min_depth"))
def top_n_nodes(
    trie: DeviceTrie, metric: jax.Array, n: int, min_depth: int = 1
):
    """Top-N rules by a metric column; nodes above ``min_depth`` only
    (use min_depth=2 to exclude empty-antecedent pseudo-rules)."""
    masked = jnp.where(trie.node_depth >= min_depth, metric, -jnp.inf)
    vals, ids = jax.lax.top_k(masked, n)
    return vals, ids


@jax.jit
def traverse_reduce(trie: DeviceTrie):
    """The traversal benchmark op: visit every rule once and reduce its
    metrics (sum/max/count over the node columns).

    Layout-agnostic: the compressed columns are a DFS permutation of the
    plain ones, so counts and maxes are bitwise identical; fp32 sums
    reassociate (documented 1e-6 allclose contract, same as the autotune
    ``reduce_bn`` relaxation).  Quantized columns reconstruct to fp32
    first.
    """
    sup_col, conf_col, _ = _dequantized_columns(trie)
    mask = trie.node_depth > 0
    n = jnp.sum(mask)
    sup = jnp.where(mask, sup_col, 0.0)
    conf = jnp.where(mask, conf_col, 0.0)
    return {
        "n_rules": n,
        "support_sum": jnp.sum(sup),
        # all-padding tries report 0.0, not the -inf mask sentinel
        # (same contract as the trie_reduce kernel's empty guard)
        "confidence_max": jnp.where(
            n > 0, jnp.max(jnp.where(mask, conf_col, -jnp.inf)), 0.0
        ),
        "mean_conf": jnp.sum(conf) / jnp.maximum(n, 1),
    }


def reconstruct_paths(
    trie: DeviceTrie, node_ids: jax.Array, max_depth: int
) -> jax.Array:
    """Vectorized parent-pointer walk: int32[Q, max_depth] item matrix
    (left-padded with -1) for each node id.

    Plain layout only: the compressed encoding drops ``node_parent``
    (query results already carry original node ids via ``dfs_to_node``;
    reconstruct paths host-side from the FrozenTrie, or keep a plain
    DeviceTrie for this op).
    """
    if trie.layout == "compressed":
        raise ValueError(
            "reconstruct_paths needs the plain layout's parent pointers; "
            "compressed tries drop node_parent — reconstruct from the "
            "host FrozenTrie (path_items) instead"
        )
    def step(carry, _):
        nid = carry
        item = jnp.where(nid > 0, trie.node_item[jnp.maximum(nid, 0)], -1)
        parent = jnp.where(
            nid > 0, trie.node_parent[jnp.maximum(nid, 0)], nid
        )
        return parent, item

    _, items_rev = jax.lax.scan(
        step, node_ids, None, length=max_depth
    )
    return items_rev.T[:, ::-1]
