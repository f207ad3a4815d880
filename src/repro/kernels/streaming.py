"""Frozen+delta k-best merging: StreamingTrie answers for the batched ops.

Every batched op in ``kernels.ops`` accepts a
``core.delta_trie.StreamingTrie`` and lands here: the frozen side runs
the op's normal kernel path (single-device arrays or the ShardPlan),
the delta side ranks the overlay entries with the same
``metrics_inkernel.rank_score``, and the two k-best lists fold through
the public ``rank.rank_merge`` — the exact merge primitive the sharded
engine already folds shards with — in REBUILT DFS coordinates, so the
result is bit-identical (tie order included) to running the op on a
from-scratch rebuild of frozen+delta.

Coordinate plumbing (all precomputed per epoch by the overlay):

* frozen k-best positions remap monotonically (``p -> p + shift[p]``),
  preserving each row's (value desc, pos asc) invariant, so the two
  inputs of ``rank_merge`` are both internally sorted as it requires;
* stale frozen copies of UPDATED rules never reach the merge — their
  depth column is patched to ``-1`` (single-device: patched rank
  arrays; sharded: the plan is built from a depth-masked FrozenTrie),
  which the rank kernels' ``depth >= min_depth`` filter drops for any
  ``min_depth >= 0`` while leaving descent structure untouched;
* node ids come from ``r2n`` (rebuilt position -> rebuilt BFS id), and
  the consequent-role posting contract from the rebuilt posting tables
  — both exactly what the rebuild would emit;
* rule search needs no ranking: the frozen kernel answers as-is except
  on rows whose path (or consequent path) touches a modified rule —
  those recompute host-side in np.float32 mirroring the descent's
  scan-order arithmetic, with the final Eq. 1-4 lift select running
  through the shared jnp ``compound_lift`` (the same outside-the-kernel
  re-select the sharded merge uses, proven bit-identical in its tests).

Import shape: this module is only ever imported lazily from inside the
``ops`` dispatch functions, so the ``from . import ops`` below always
sees a fully-initialized module (no cycle at import time).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.array_trie import canonical_prefix_rows, sanitize_query_items
from . import ops
from .metrics_inkernel import compound_lift, rank_score
from .rank import LANE, rank_merge


def _kpad(k: int) -> int:
    return int(k) + (-int(k) % LANE)


def _base(stream, base=None):
    """The frozen side of the merge: an explicit override (the resilient
    engine's dead-masked plan), else the stream's plan, else the frozen
    trie itself."""
    if base is not None:
        return base
    plan = stream.shard_plan()
    return plan if plan is not None else stream.frozen


def _ov_device(stream) -> Dict[str, jax.Array]:
    """Overlay columns on device, cached for the epoch."""
    ov = stream.overlay()
    dev = ov.cache.get("device")
    if dev is None:
        dev = {
            "pos": jnp.asarray(ov.pos, jnp.int32),
            "shift": jnp.asarray(ov.shift, jnp.int32),
            "old2new": jnp.asarray(ov.old2new, jnp.int32),
            "r2n": jnp.asarray(ov.r2n, jnp.int32),
            "post_index": jnp.asarray(ov.post_index, jnp.int32),
            "post_nodes": jnp.asarray(ov.post_nodes, jnp.int32),
        }
        ov.cache["device"] = dev
    return dev


def _delta_scores(stream, metric: str) -> jax.Array:
    """rank_score over the delta metric columns, cached per metric —
    the SAME scoring expression the rank kernels evaluate."""
    ov = stream.overlay()
    key = ("score", metric)
    s = ov.cache.get(key)
    if s is None:
        s = rank_score(
            metric,
            jnp.asarray(ov.support),
            jnp.asarray(ov.confidence),
            jnp.asarray(ov.lift),
        ).astype(jnp.float32)
        ov.cache[key] = s
    return s


def _rank_arrays(stream) -> Dict[str, jax.Array]:
    """Single-device rank columns with updated nodes' depth masked to -1
    (the stale-copy suppression), cached for the epoch."""
    ov = stream.overlay()
    arrs = ov.cache.get("rank_arrays")
    if arrs is None:
        arrs = ops.dfs_rank_arrays(stream.frozen)
        if ov.masked_nodes.size:
            dfs = np.asarray(stream.frozen.dfs_order)
            depth = np.array(arrs["depth"])
            depth[dfs[ov.masked_nodes]] = -1
            arrs["depth"] = jnp.asarray(depth, jnp.int32)
        ov.cache["rank_arrays"] = arrs
    return arrs


def _item_arrays(stream) -> Dict[str, jax.Array]:
    """Single-device inverted-index columns with updated nodes' depth
    masked to -1 in BOTH the DFS-ordered and posting-ordered blocks."""
    ov = stream.overlay()
    arrs = ov.cache.get("item_arrays")
    if arrs is None:
        arrs = ops.item_rank_arrays(stream.frozen)
        if ov.masked_nodes.size:
            dfs = np.asarray(stream.frozen.dfs_order)
            depth = np.array(arrs["depth"])
            depth[dfs[ov.masked_nodes]] = -1
            arrs["depth"] = jnp.asarray(depth, jnp.int32)
            pdepth = np.array(arrs["p_depth"])
            hit = np.isin(np.asarray(stream.frozen.item_nodes),
                          ov.masked_nodes)
            pdepth[hit] = -1
            arrs["p_depth"] = jnp.asarray(pdepth, jnp.int32)
        ov.cache["item_arrays"] = arrs
    return arrs


def _delta_topk(scores: jax.Array, dpos: jax.Array, kpad: int):
    """Per-query k-best over the delta entries: ``scores`` is [Q, D]
    with -inf at non-matching entries, ``dpos`` the [D] merge positions
    (ascending per query by construction, so ``lax.top_k``'s
    lower-index-first tie rule IS the (value desc, pos asc) order)."""
    d = scores.shape[1]
    if d < kpad:
        scores = jnp.pad(
            scores, ((0, 0), (0, kpad - d)), constant_values=-jnp.inf
        )
        dpos = jnp.pad(dpos, (0, kpad - d), constant_values=-1)
    vals, idx = jax.lax.top_k(scores, kpad)
    pos = jnp.where(vals > -jnp.inf, dpos[idx], -1)
    return vals, pos


def _merge(fvals, fpos, dvals, dpos, k: int):
    """rank_merge the frozen and delta k-best lists (both [Q, *] in
    rebuilt positions) and slice back to k columns."""
    kpad = _kpad(k)
    pad = kpad - fvals.shape[1]
    if pad:
        fvals = jnp.pad(
            fvals, ((0, 0), (0, pad)), constant_values=-jnp.inf
        )
        fpos = jnp.pad(fpos, ((0, 0), (0, pad)), constant_values=-1)
    mv, mp = jax.vmap(
        lambda av, ap, tv, tp: rank_merge(av, ap, tv, tp, kpad)
    )(fvals, fpos.astype(jnp.int32), dvals, dpos.astype(jnp.int32))
    return mv[:, :k], mp[:, :k]


def _prefix_match(stream, prefixes) -> np.ndarray:
    """bool [Q, D]: does delta entry d's path start with prefix q?
    Canonicalization mirrors ``prefix_ranges`` (only -1 is padding;
    other invalid items match nothing, like any absent item)."""
    ov = stream.overlay()
    rows = canonical_prefix_rows(prefixes, stream.frozen.item_rank)
    q = len(rows)
    wp = max((len(r) for r in rows), default=0)
    pm = np.full((q, max(wp, 1)), -1, np.int64)
    for i, r in enumerate(rows):
        pm[i, : len(r)] = r
    paths = ov.paths.astype(np.int64)
    w = paths.shape[1]
    if pm.shape[1] > w:
        paths = np.pad(
            paths, ((0, 0), (0, pm.shape[1] - w)), constant_values=-1
        )
    paths = paths[:, : pm.shape[1]]
    return np.all(
        (pm[:, None, :] == -1) | (pm[:, None, :] == paths[None, :, :]),
        axis=2,
    )


# ----------------------------------------------------------------------
# ranked ops
# ----------------------------------------------------------------------
def streaming_top_k_rules_batch(
    stream, prefixes, k: int, metric: str = "confidence",
    min_depth: int = 1, base=None,
) -> Dict[str, jax.Array]:
    """top_k_rules_batch over frozen+delta (inputs pre-validated by the
    ops dispatch)."""
    fb = _base(stream, base)
    if stream.is_identity:
        return ops.top_k_rules_batch(
            fb, prefixes, k, metric=metric, min_depth=min_depth
        )
    kwargs = {}
    if ops._as_shard_plan(fb) is None:
        kwargs["arrays"] = _rank_arrays(stream)
    fout = ops.top_k_rules_batch(
        fb, prefixes, k, metric=metric, min_depth=min_depth, **kwargs
    )
    if len(prefixes) == 0:
        return fout
    ov = stream.overlay()
    dev = _ov_device(stream)

    fpos = fout["dfs_pos"]
    live = fpos >= 0
    rpos = jnp.where(
        live, fpos + dev["shift"][jnp.maximum(fpos, 0)], -1
    )

    match = _prefix_match(stream, prefixes)
    match &= ov.depth[None, :] >= int(min_depth)
    scores = jnp.where(
        jnp.asarray(match), _delta_scores(stream, metric)[None, :],
        -jnp.inf,
    )
    dvals, dpos = _delta_topk(scores, dev["pos"], _kpad(k))
    vals, pos = _merge(fout["values"], rpos, dvals, dpos, int(k))
    node = jnp.where(pos >= 0, dev["r2n"][jnp.maximum(pos, 0)], -1)
    return {"values": vals, "node": node, "dfs_pos": pos}


def streaming_top_k_rules(
    stream, k: int, metric: str = "confidence", prefix=None,
    min_depth: int = 1, base=None,
) -> Dict[str, jax.Array]:
    """Q=1 slice of the batched form (identical merge path)."""
    out = streaming_top_k_rules_batch(
        stream, [prefix if prefix is not None else []], k,
        metric=metric, min_depth=min_depth, base=base,
    )
    return {key: v[0] for key, v in out.items()}


def streaming_rules_with(
    stream, items, role: str = "any", k: int = 10,
    metric: str = "confidence", min_depth: int = 1,
    strict: bool = False, base=None,
) -> Dict[str, jax.Array]:
    """rules_with over frozen+delta.  ``pos`` keeps the op contract in
    REBUILT coordinates: the rebuilt posting index for the (plain
    layout) consequent role, the rebuilt DFS position otherwise."""
    fb = _base(stream, base)
    if not isinstance(items, np.ndarray):
        items = list(items)
    if stream.is_identity:
        return ops.rules_with(
            fb, items, role=role, k=k, metric=metric,
            min_depth=min_depth, strict=strict,
        )
    kwargs = {}
    sharded = ops._as_shard_plan(fb) is not None
    if not sharded:
        kwargs["arrays"] = _item_arrays(stream)
    fout = ops.rules_with(
        fb, items, role=role, k=k, metric=metric, min_depth=min_depth,
        strict=strict, **kwargs,
    )
    qitems = np.asarray(items, np.int64).reshape(-1)
    if qitems.shape[0] == 0:
        return fout
    ov = stream.overlay()
    dev = _ov_device(stream)
    n_items = int(stream.frozen.item_rank.shape[0])
    _, _, qit = sanitize_query_items(qitems, n_items)
    qit = np.asarray(qit, np.int64).reshape(-1)

    # streaming bases are plain-layout (enforced at StreamingTrie
    # construction), so the consequent role always takes the
    # posting-index fast path — single-device AND sharded (see
    # _rules_with_sharded) rank it over posting indices
    consequent_fast = role == "consequent"

    # delta membership per role
    paths = ov.paths
    plen = ov.path_len
    cols = np.arange(paths.shape[1])
    in_path = cols[None, :] < plen[:, None]
    is_last = cols[None, :] == (plen[:, None] - 1)
    eq = paths[None, :, :] == qit[:, None, None]     # [Q, D, W]
    if role == "consequent":
        match = np.any(eq & is_last[None, :, :], axis=2)
    elif role == "antecedent":
        match = np.any(eq & (in_path & ~is_last)[None, :, :], axis=2)
    else:
        match = np.any(eq & in_path[None, :, :], axis=2)
    match &= ov.depth[None, :] >= int(min_depth)

    if consequent_fast:
        # merge in rebuilt POSTING coordinates (the kernel's tie key on
        # this path); entry posting indices are ascending in entry order
        # per item, and cross-item entries are masked out per query
        dmerge = dev["post_index"][dev["r2n"][dev["pos"]]]
        fpos = fout["pos"]
        live = fpos >= 0
        old_post = jnp.asarray(
            np.asarray(stream.frozen.item_nodes), jnp.int32
        )
        if old_post.shape[0] == 0:
            # delta-only stream: the frozen base has no postings, so
            # every frozen lane is already dead (nothing to gather)
            rpos = jnp.full_like(fpos, -1)
        else:
            rpos = jnp.where(
                live,
                dev["post_index"][
                    dev["old2new"][old_post[jnp.maximum(fpos, 0)]]
                ],
                -1,
            )
        back = dev["post_nodes"]
    else:
        dmerge = dev["pos"]
        fpos = fout["pos"]
        live = fpos >= 0
        rpos = jnp.where(
            live, fpos + dev["shift"][jnp.maximum(fpos, 0)], -1
        )
        back = dev["r2n"]

    scores = jnp.where(
        jnp.asarray(match), _delta_scores(stream, metric)[None, :],
        -jnp.inf,
    )
    dvals, dpos = _delta_topk(scores, dmerge, _kpad(k))
    vals, pos = _merge(fout["values"], rpos, dvals, dpos, int(k))
    node = jnp.where(pos >= 0, back[jnp.maximum(pos, 0)], -1)
    return {"values": vals, "node": node, "pos": pos}


# ----------------------------------------------------------------------
# rule search
# ----------------------------------------------------------------------
def _affected_rows(stream, qmat: np.ndarray, ant_len: np.ndarray):
    """Rows whose result can differ from the frozen answer: some prefix
    of the full path, or the consequent path itself, is a modified rule."""
    ov = stream.overlay()
    mod = ov.modified
    aff = np.zeros((qmat.shape[0],), bool)
    for i in range(qmat.shape[0]):
        row = qmat[i]
        items = tuple(int(x) for x in row[row >= 0])
        if not items:
            continue
        al = int(ant_len[i])
        if any(items[:j] in mod for j in range(1, len(items) + 1)):
            aff[i] = True
        elif items[al:] in mod:
            aff[i] = True
    return aff


def streaming_rule_search_batch(
    stream, queries, ant_len=None, strict: bool = False, base=None,
) -> Dict[str, jax.Array]:
    """rule_search_batch over frozen+delta.

    The frozen kernel answers every row (its descent structure is
    untouched by the overlay); rows touching a modified rule recompute
    from the union host-side, mirroring the descent's scan-order
    f32 arithmetic, with the Eq. 1-4 lift select through the shared jnp
    ``compound_lift``.  Node ids remap old -> rebuilt everywhere.
    """
    fb = _base(stream, base)
    if stream.is_identity:
        return ops.rule_search_batch(
            fb, queries, ant_len, strict=strict
        )
    fz = stream.frozen
    if ant_len is None and not isinstance(queries, np.ndarray):
        pairs = list(queries)
        ops.validate_rule_pairs(
            pairs, "rule_search_batch", item_rank=fz.item_rank,
            strict=strict,
        )
        if not pairs:
            return ops.rule_search_batch(fb, np.zeros((0, 1), np.int32),
                                         np.zeros((0,), np.int32))
        queries, ant_len = fz.canonicalize_queries(
            [p[0] for p in pairs], [p[1] for p in pairs]
        )
    qmat = np.asarray(queries)
    al = np.asarray(ant_len)
    out = ops.rule_search_batch(fb, qmat, al)
    dev = _ov_device(stream)
    node = out["node"]
    node = jnp.where(node >= 0, dev["old2new"][jnp.maximum(node, 0)], node)

    aff = _affected_rows(stream, qmat, al)
    if not aff.any():
        return {**out, "node": node}

    q = qmat.shape[0]
    c_found = np.zeros((q,), bool)
    c_node = np.full((q,), -1, np.int32)
    c_sup = np.zeros((q,), np.float32)
    c_conf = np.zeros((q,), np.float32)
    c_nlift = np.zeros((q,), np.float32)
    c_consup = np.zeros((q,), np.float32)
    c_single = np.zeros((q,), bool)
    for i in np.nonzero(aff)[0]:
        row = qmat[i]
        items = tuple(int(x) for x in row[row >= 0])
        a = int(al[i])
        full = stream.lookup(items)
        if full is None:
            continue  # absent from the union: all-zero row stands
        # scan-order f32 product over the consequent steps, exactly the
        # kernel's conf accumulation
        conf = np.float32(1.0)
        for j in range(a + 1, len(items) + 1):
            conf = np.float32(conf * np.float32(stream.lookup(items[:j])[1]))
        cons = items[a:]
        cm = stream.lookup(cons) if cons else None
        c_found[i] = True
        c_node[i] = stream.node_of(items)
        c_sup[i] = np.float32(full[0])
        c_conf[i] = conf
        c_nlift[i] = np.float32(full[2])
        c_consup[i] = np.float32(cm[0]) if cm is not None else 0.0
        c_single[i] = (len(items) - a) == 1
    c_lift = compound_lift(
        jnp.asarray(c_found), jnp.asarray(c_single),
        jnp.asarray(c_nlift), jnp.asarray(c_conf),
        jnp.asarray(c_consup),
    )
    aj = jnp.asarray(aff)
    return {
        "found": jnp.where(aj, jnp.asarray(c_found), out["found"]),
        "node": jnp.where(aj, jnp.asarray(c_node), node),
        "support": jnp.where(aj, jnp.asarray(c_sup), out["support"]),
        "confidence": jnp.where(
            aj, jnp.asarray(c_conf * c_found), out["confidence"]
        ),
        "lift": jnp.where(aj, c_lift, out["lift"]),
    }
