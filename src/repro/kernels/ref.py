"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel is allclose-tested
against (``tests/test_kernels.py`` sweeps shapes/dtypes in interpret mode).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .metrics_inkernel import compound_lift, dequantize_metrics, rank_score


# ----------------------------------------------------------------------
# support_count — mining Step 1 hot loop (MXU formulation)
# ----------------------------------------------------------------------
def support_count_ref(
    dense_tx: jax.Array,      # {u}int8/bf16/f32 [T, I] 0/1 membership
    member: jax.Array,        # same dtype   [C, I] candidate membership
    lengths: jax.Array,       # int32 [C]  (|itemset|; -1 for padding rows)
) -> jax.Array:
    """counts[c] = |{t : candidate c ⊆ transaction t}|.

    A transaction contains the itemset iff ⟨tx_row, member_row⟩ == |itemset|
    — the matmul formulation that runs on the MXU (DESIGN.md §2).
    """
    s = jnp.dot(
        dense_tx.astype(jnp.float32),
        member.astype(jnp.float32).T,
        preferred_element_type=jnp.float32,
    )  # [T, C]
    hits = s == lengths.astype(jnp.float32)[None, :]
    return jnp.sum(hits, axis=0).astype(jnp.int32)


# ----------------------------------------------------------------------
# rule_search — batched trie descent (paper Fig. 8-10 operation)
# ----------------------------------------------------------------------
def rule_search_ref(
    edge_parent: jax.Array,   # int32 [E]   (pad = -7, never matches)
    edge_item: jax.Array,     # int32 [E]
    edge_child: jax.Array,    # int32 [E]
    edge_conf: jax.Array,     # f32   [E]  confidence of the child node
    edge_sup: jax.Array,      # f32   [E]  support of the child node
    edge_lift: jax.Array,     # f32   [E]  lift of the child node
    queries: jax.Array,       # int32 [Q, L]  (-1 padded)
    ant_len: jax.Array,       # int32 [Q]
) -> Dict[str, jax.Array]:
    """Walk each query root→down by matching (node, item) against the full
    edge table (the broadcast-compare semantics of the TPU kernel).

    Returns found/node/support/confidence/node_lift per query; compound
    lift is assembled by the ops wrapper from a second consequent-only walk.
    """
    q, width = queries.shape
    node = jnp.zeros((q,), jnp.int32)
    ok = jnp.ones((q,), bool)
    conf = jnp.ones((q,), jnp.float32)
    sup = jnp.zeros((q,), jnp.float32)
    nlift = jnp.zeros((q,), jnp.float32)

    for s in range(width):
        item = queries[:, s]
        active = (item >= 0) & ok
        qp = jnp.where(active, node, -9)
        match = (edge_parent[None, :] == qp[:, None]) & (
            edge_item[None, :] == item[:, None]
        )  # [Q, E]
        child = jnp.max(
            jnp.where(match, edge_child[None, :], -1), axis=1
        )
        e_conf = jnp.max(jnp.where(match, edge_conf[None, :], 0.0), axis=1)
        e_sup = jnp.max(jnp.where(match, edge_sup[None, :], 0.0), axis=1)
        e_lift = jnp.max(jnp.where(match, edge_lift[None, :], 0.0), axis=1)
        hit = child >= 0
        ok = jnp.where(active, hit, ok)
        node = jnp.where(active & hit, child, node)
        in_cons = s >= ant_len
        conf = jnp.where(active & hit & in_cons, conf * e_conf, conf)
        sup = jnp.where(active & hit, e_sup, sup)
        nlift = jnp.where(active & hit, e_lift, nlift)

    found = ok & (node > 0)
    return {
        "found": found,
        "node": jnp.where(found, node, -1),
        "support": jnp.where(found, sup, 0.0),
        "confidence": jnp.where(found, conf, 0.0),
        "node_lift": jnp.where(found, nlift, 0.0),
    }


def rule_search_fused_ref(
    edge_parent: jax.Array,   # int32 [E]
    edge_item: jax.Array,     # int32 [E]
    edge_child: jax.Array,    # int32 [E]
    edge_conf: jax.Array,     # f32   [E]
    edge_sup: jax.Array,      # f32   [E]
    edge_lift: jax.Array,     # f32   [E]
    queries: jax.Array,       # int32 [Q, L]  (-1 padded)
    ant_len: jax.Array,       # int32 [Q]
) -> Dict[str, jax.Array]:
    """Ground truth for the fused CSR kernel: full metrics in one pass,
    compound lift included (main walk + root-anchored consequent walk).

    Deliberately layout-agnostic — full-table matching, no CSR — so it
    cross-checks the bucket descent against independent logic.
    """
    main = rule_search_ref(
        edge_parent, edge_item, edge_child,
        edge_conf, edge_sup, edge_lift, queries, ant_len,
    )
    width = queries.shape[1]
    cols = jnp.arange(width, dtype=jnp.int32)[None, :]
    cons_q = jnp.where(cols >= ant_len[:, None], queries, -1)
    cons = rule_search_ref(
        edge_parent, edge_item, edge_child,
        edge_conf, edge_sup, edge_lift,
        cons_q, jnp.zeros_like(ant_len),
    )
    seq_len = jnp.sum(queries >= 0, axis=1).astype(jnp.int32)
    single = (seq_len - ant_len) == 1
    return {
        "found": main["found"],
        "node": main["node"],
        "support": main["support"],
        "confidence": main["confidence"],
        "lift": compound_lift(
            main["found"], single, main["node_lift"],
            main["confidence"], cons["support"],
        ),
    }


def rule_search_span_ref(
    edge_parent: jax.Array,   # int32 [Ec] COMPRESSED parent ids
    edge_item: jax.Array,     # int32 [Ec]
    edge_pos: jax.Array,      # int32 [Ec] child DFS position (run head)
    edge_span: jax.Array,     # int32 [Ec] interior steps to the run tail
    edge_tail: jax.Array,     # int32 [Ec] run tail's compressed id
    node_item: jax.Array,     # int32 [N]  item per DFS position
    support: jax.Array,       # f32|int32 [N] position-indexed
    confidence: jax.Array,    # f32|bf16|int8 [N]
    lift: jax.Array,          # f32|bf16|int8 [N]
    queries: jax.Array,       # int32 [Q, L]  (-1 padded)
    ant_len: jax.Array,       # int32 [Q]
    *,
    n_transactions: int = 0,
    confidence_scale: float = 1.0,
    lift_scale: float = 1.0,
) -> Dict[str, jax.Array]:
    """Ground truth for the COMPRESSED-layout span descent: the same
    ``(pos, rem, ctail)`` state machine, but CSR-node steps match against
    the FULL compressed edge table (broadcast compare on the compressed
    parent-id column) instead of a bucket binary search — independent
    logic for the part the descent optimizes.  Metric columns dequantize
    through the same shared ``dequantize_metrics``, so fp32 inputs keep
    the oracle bit-identical to the span descent AND to the plain fused
    pair."""
    q, width = queries.shape
    n = node_item.shape[0]
    if edge_parent.shape[0] == 0 or width == 0:
        z = jnp.zeros((q,), jnp.float32)
        return {
            "found": jnp.zeros((q,), bool),
            "pos": jnp.full((q,), -1, jnp.int32),
            "support": z, "confidence": z, "lift": z, "con_support": z,
        }
    sup_col, conf_col, lift_col = dequantize_metrics(
        support, confidence, lift,
        n_transactions, confidence_scale, lift_scale,
    )

    def walk(qs, al, track_conf):
        pos = jnp.zeros((q,), jnp.int32)
        rem = jnp.zeros((q,), jnp.int32)
        ctail = jnp.zeros((q,), jnp.int32)
        ok = jnp.ones((q,), bool)
        conf = jnp.ones((q,), jnp.float32)
        for s in range(width):
            item = qs[:, s]
            active = (item >= 0) & ok
            in_span = rem > 0
            nxt = jnp.minimum(pos + 1, n - 1)
            span_hit = in_span & (node_item[nxt] == item)
            qp = jnp.where(active & ~in_span, ctail, -9)
            match = (edge_parent[None, :] == qp[:, None]) & (
                edge_item[None, :] == item[:, None]
            )  # [Q, Ec]
            sel_pos = jnp.max(
                jnp.where(match, edge_pos[None, :], -1), axis=1
            )
            sel_span = jnp.max(
                jnp.where(match, edge_span[None, :], 0), axis=1
            )
            sel_tail = jnp.max(
                jnp.where(match, edge_tail[None, :], 0), axis=1
            )
            edge_hit = (~in_span) & (sel_pos >= 0)
            hit = span_hit | edge_hit
            pos2 = jnp.where(
                span_hit, pos + 1, jnp.where(edge_hit, sel_pos, pos)
            )
            rem2 = jnp.where(
                span_hit, rem - 1, jnp.where(edge_hit, sel_span, rem)
            )
            ok = jnp.where(active, hit, ok)
            adv = active & hit
            if track_conf:
                conf = jnp.where(
                    adv & (s >= al), conf * conf_col[pos2], conf
                )
            pos = jnp.where(adv, pos2, pos)
            rem = jnp.where(adv, rem2, rem)
            ctail = jnp.where(adv & edge_hit, sel_tail, ctail)
        return pos, conf, ok

    pos, conf, ok = walk(queries, ant_len, True)
    cols = jnp.arange(width, dtype=jnp.int32)[None, :]
    cons_q = jnp.where(cols >= ant_len[:, None], queries, -1)
    cpos, _, cok = walk(cons_q, jnp.zeros_like(ant_len), False)
    con_sup = jnp.where(cok & (cpos > 0), sup_col[cpos], 0.0)

    found = ok & (pos > 0)
    conf = jnp.where(found, conf, 0.0)
    seq_len = jnp.sum(queries >= 0, axis=1).astype(jnp.int32)
    single = (seq_len - ant_len) == 1
    return {
        "found": found,
        "pos": jnp.where(found, pos, -1),
        "support": jnp.where(found, sup_col[pos], 0.0),
        "confidence": conf,
        "lift": compound_lift(
            found, single, jnp.where(found, lift_col[pos], 0.0),
            conf, con_sup,
        ),
        "con_support": con_sup,
    }


# ----------------------------------------------------------------------
# trie_reduce — full-ruleset traversal reductions (the 8× traversal op)
# ----------------------------------------------------------------------
def trie_reduce_ref(
    support: jax.Array,       # f32|int32 [N]
    confidence: jax.Array,    # f32|bf16|int8 [N]
    depth: jax.Array,         # int32 [N]  (root=0 and padding<0 masked out)
    *,
    n_transactions: int = 0,
    confidence_scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(n_rules, Σ support, max confidence, Σ confidence) over real nodes.

    Degenerate tries (N == 0 or all-padding) reduce to all-zeros — the max
    slot is 0.0, not -inf, so downstream consumers never see a poisoned
    sentinel (mirrors the kernel's empty-trie guard).  Quantized columns
    (compressed layout) widen through the shared ``dequantize_metrics``.
    """
    if support.shape[0] == 0:
        z = jnp.float32(0.0)
        return z, z, z, z
    # lift is unused by this reduction: pass confidence as a stand-in.
    support, confidence, _ = dequantize_metrics(
        support, confidence, confidence,
        n_transactions, confidence_scale, confidence_scale,
    )
    mask = depth > 0
    n = jnp.sum(mask).astype(jnp.float32)
    sup_sum = jnp.sum(jnp.where(mask, support, 0.0))
    conf_max = jnp.where(
        n > 0, jnp.max(jnp.where(mask, confidence, -jnp.inf)), 0.0
    )
    conf_sum = jnp.sum(jnp.where(mask, confidence, 0.0))
    return n, sup_sum, conf_max, conf_sum


# ----------------------------------------------------------------------
# topk_rank — segmented ranked extraction over the DFS-contiguous layout
# ----------------------------------------------------------------------
def topk_rank_ref(
    support: jax.Array,     # f32 [N] DFS-ordered
    confidence: jax.Array,  # f32 [N] DFS-ordered
    lift: jax.Array,        # f32 [N] DFS-ordered
    depth: jax.Array,       # int32 [N] DFS-ordered
    lo,                     # int32 scalar: DFS range start (inclusive)
    hi,                     # int32 scalar: DFS range end (exclusive)
    *,
    k: int,
    metric: str = "confidence",
    min_depth: int = 1,
    n_transactions: int = 0,
    confidence_scale: float = 1.0,
    lift_scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Ground truth for the segmented top-k kernel: ``jax.lax.top_k`` over
    the masked score vector (scores from the SAME ``rank_score`` the kernel
    runs in VMEM, so values are bit-identical; ``lax.top_k`` breaks ties by
    lower index, which the kernel's min-position extraction replicates).
    Empty slots — k beyond the live-rule count — are ``(-inf, -1)``.
    """
    n = support.shape[0]
    if n == 0 or k <= 0:
        return (
            jnp.full((max(k, 0),), -jnp.inf, jnp.float32),
            jnp.full((max(k, 0),), -1, jnp.int32),
        )
    score = rank_score(
        metric,
        *dequantize_metrics(
            support, confidence, lift,
            n_transactions, confidence_scale, lift_scale,
        ),
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    lo = jnp.maximum(jnp.asarray(lo, jnp.int32), 0)
    hi = jnp.minimum(jnp.asarray(hi, jnp.int32), n)
    valid = (pos >= lo) & (pos < hi) & (depth >= min_depth)
    masked = jnp.where(valid, score, -jnp.inf)
    if k > n:
        masked = jnp.pad(masked, (0, k - n), constant_values=-jnp.inf)
    vals, idx = jax.lax.top_k(masked, k)
    idx = jnp.where(vals > -jnp.inf, idx.astype(jnp.int32), -1)
    return vals, idx


def topk_rank_batch_ref(
    support: jax.Array,     # f32 [N] DFS-ordered
    confidence: jax.Array,  # f32 [N] DFS-ordered
    lift: jax.Array,        # f32 [N] DFS-ordered
    depth: jax.Array,       # int32 [N] DFS-ordered
    los: jax.Array,         # int32 [Q]
    his: jax.Array,         # int32 [Q]
    *,
    k: int,
    metric: str = "confidence",
    min_depth: int = 1,
    n_transactions: int = 0,
    confidence_scale: float = 1.0,
    lift_scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Ground truth for the BATCHED segmented top-k: ``lax.top_k`` over a
    ``[Q, N]`` masked score matrix (each row its own ``[lo, hi)`` range).
    Row-for-row identical to Q ``topk_rank_ref`` calls."""
    n = support.shape[0]
    q = los.shape[0]
    if n == 0 or k <= 0 or q == 0:
        return (
            jnp.full((q, max(k, 0)), -jnp.inf, jnp.float32),
            jnp.full((q, max(k, 0)), -1, jnp.int32),
        )
    score = rank_score(
        metric,
        *dequantize_metrics(
            support, confidence, lift,
            n_transactions, confidence_scale, lift_scale,
        ),
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    los = jnp.maximum(jnp.asarray(los, jnp.int32), 0)[:, None]
    his = jnp.minimum(jnp.asarray(his, jnp.int32), n)[:, None]
    valid = (
        (pos[None, :] >= los) & (pos[None, :] < his)
        & (depth[None, :] >= min_depth)
    )
    masked = jnp.where(valid, score[None, :], -jnp.inf)
    if k > n:
        masked = jnp.pad(
            masked, ((0, 0), (0, k - n)), constant_values=-jnp.inf
        )
    vals, idx = jax.lax.top_k(masked, k)
    idx = jnp.where(vals > -jnp.inf, idx.astype(jnp.int32), -1)
    return vals, idx


# ----------------------------------------------------------------------
# rules_with — item-scoped ranked extraction via the inverted index
# ----------------------------------------------------------------------
def rules_with_ref(
    support: jax.Array,     # f32 [N] DFS-ordered
    confidence: jax.Array,  # f32 [N] DFS-ordered
    lift: jax.Array,        # f32 [N] DFS-ordered
    depth: jax.Array,       # int32 [N] DFS-ordered
    node_item: jax.Array,   # int32 [N] DFS-ordered consequent items
    post_lo: jax.Array,     # int32 [E] posting subtree starts
    post_hi: jax.Array,     # int32 [E] posting subtree ends (sorted/item)
    plos: jax.Array,        # int32 [Q]
    phis: jax.Array,        # int32 [Q]
    items: jax.Array,       # int32 [Q]
    *,
    k: int,
    metric: str = "confidence",
    min_depth: int = 1,
    role: str = "any",
    n_transactions: int = 0,
    confidence_scale: float = 1.0,
    lift_scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Ground truth for the membership ranking: the same laminar
    range-count (``searchsorted`` on the posting slice) as a dense [Q, N]
    membership matrix, then batched ``lax.top_k``.  Bit-identical to
    ``item_index.rules_with_pallas`` including tie order."""
    n = support.shape[0]
    q = plos.shape[0]
    if n == 0 or k <= 0 or q == 0:
        return (
            jnp.full((q, max(k, 0)), -jnp.inf, jnp.float32),
            jnp.full((q, max(k, 0)), -1, jnp.int32),
        )
    score = rank_score(
        metric,
        *dequantize_metrics(
            support, confidence, lift,
            n_transactions, confidence_scale, lift_scale,
        ),
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    self_hit = node_item[None, :] == jnp.asarray(items, jnp.int32)[:, None]
    if role == "consequent":
        member = self_hit
    else:
        # Laminar range count per (query, node) via numpy searchsorted on
        # each query's posting slice — independent of the op's
        # difference-array count.  This reference is never
        # jitted, so the slice bounds are concrete.
        arr_lo = np.asarray(post_lo)
        arr_hi = np.asarray(post_hi)
        pos_np = np.arange(n)
        rows = []
        for qi in range(q):
            plo, phi = int(plos[qi]), int(phis[qi])
            rows.append(
                np.searchsorted(arr_lo[plo:phi], pos_np, side="right")
                - np.searchsorted(arr_hi[plo:phi], pos_np, side="right")
            )
        cnt = jnp.asarray(np.stack(rows).astype(np.int32))
        if role == "antecedent":
            member = (cnt - self_hit.astype(jnp.int32)) > 0
        elif role == "any":
            member = cnt > 0
        else:
            raise ValueError(f"unknown role {role!r}")
    valid = member & (depth[None, :] >= min_depth)
    masked = jnp.where(valid, score[None, :], -jnp.inf)
    if k > n:
        masked = jnp.pad(
            masked, ((0, 0), (0, k - n)), constant_values=-jnp.inf
        )
    vals, idx = jax.lax.top_k(masked, k)
    idx = jnp.where(vals > -jnp.inf, idx.astype(jnp.int32), -1)
    return vals, idx
