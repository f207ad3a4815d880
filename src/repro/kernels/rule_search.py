"""Batched Trie-of-Rules descent (the paper's search op).

Three entry points share this module:

``rule_search_fused`` — the production path on the plain layout.  The
edge table is laid out in CSR child buckets (``array_trie.FrozenTrie.
freeze``): node ``p``'s outgoing edges are contiguous at
``child_offsets[p]:child_offsets[p+1]``, item-sorted.  Each descent step
binary-searches only the active node's bucket
(``array_trie.bucket_edge_lookup``, ``O(log max_fanout)`` gathers per
query), and the consequent-only walk needed for compound lift (paper
Eq. 1-4) runs in the SAME scan, so a full-metric search is one jitted
program returning found/node/support/confidence/lift plus the
consequent-path Support (``con_support`` — the sharded engine merges it
across devices before re-assembling compound lift globally).

``rule_search_span`` — its compressed-layout (path-compressed span)
twin.  The node axis is DFS pre-order position and maximal single-child
runs are spans: kept edges carry ``(item, head position, interior step
count, run-tail compressed id)`` and span interiors occupy NO bucket.
The per-query state is ``(pos, rem, ctail)`` (``array_trie.span_step``).
Metric columns are POSITION-indexed and may be quantized (int32 support
counts / bf16 / int8); they widen once through
``metrics_inkernel.dequantize_metrics``, so the unquantized fp32 path
stays bit-identical to the plain descent.

Both descents are XLA gather loops over the L query columns, not Pallas
kernels: a descent reads a few entries per step from tables of E edges,
so the tables stay in HBM at any trie size (a VMEM-resident edge table
caps the trie at a few hundred thousand edges, and Pallas on TPU refuses
gathers from 1-D vectors outright).

``rule_search_pallas`` — the seed full-sweep kernel, kept as the
benchmark baseline and as the fallback when no CSR offsets are
available.  It broadcast-compares every query against the ENTIRE
lex-sorted edge table at every step (O(E) compares per step, streamed
through VMEM in BE-wide chunks), and returns per-node metrics only;
compound lift needs a second consequent-only invocation by the ops
wrapper.

Metrics ride ON THE EDGES in the plain layout (edge_conf/edge_sup/
edge_lift are the child node's Step-3 annotations gathered at freeze
time) and on the DFS-ordered node columns in the compressed layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.array_trie import bucket_edge_lookup, span_step

from .metrics_inkernel import compound_lift, dequantize_metrics

BQ = 128    # queries per tile (full-sweep kernel)
BE = 2048   # edge-table chunk per compare sweep (full-sweep kernel)


def _make_kernel(width: int, n_chunks: int):
    def kernel(
        q_ref, al_ref,
        ep_ref, ei_ref, ec_ref, econf_ref, esup_ref, elift_ref,
        node_ref, ok_ref, conf_ref, sup_ref, lift_ref,
    ):
        bq = q_ref.shape[0]
        node = jnp.zeros((bq,), jnp.int32)
        ok = jnp.ones((bq,), jnp.bool_)
        conf = jnp.ones((bq,), jnp.float32)
        sup = jnp.zeros((bq,), jnp.float32)
        nlift = jnp.zeros((bq,), jnp.float32)
        ant_len = al_ref[...][:, 0]

        for s in range(width):
            item = q_ref[...][:, s]
            active = (item >= 0) & ok
            qp = jnp.where(active, node, -9)

            child = jnp.full((bq,), -1, jnp.int32)
            e_conf = jnp.zeros((bq,), jnp.float32)
            e_sup = jnp.zeros((bq,), jnp.float32)
            e_lift = jnp.zeros((bq,), jnp.float32)
            for ch in range(n_chunks):
                sl = (0, pl.dslice(ch * BE, BE))
                ep = ep_ref[sl]
                ei = ei_ref[sl]
                ec = ec_ref[sl]
                cf = econf_ref[sl]
                sp = esup_ref[sl]
                lf = elift_ref[sl]
                match = (ep[None, :] == qp[:, None]) & (
                    ei[None, :] == item[:, None]
                )
                child = jnp.maximum(
                    child,
                    jnp.max(jnp.where(match, ec[None, :], -1), axis=1),
                )
                e_conf = jnp.maximum(
                    e_conf,
                    jnp.max(jnp.where(match, cf[None, :], 0.0), axis=1),
                )
                e_sup = jnp.maximum(
                    e_sup,
                    jnp.max(jnp.where(match, sp[None, :], 0.0), axis=1),
                )
                e_lift = jnp.maximum(
                    e_lift,
                    jnp.max(jnp.where(match, lf[None, :], 0.0), axis=1),
                )

            hit = child >= 0
            ok = jnp.where(active, hit, ok)
            node = jnp.where(active & hit, child, node)
            in_cons = s >= ant_len
            conf = jnp.where(active & hit & in_cons, conf * e_conf, conf)
            sup = jnp.where(active & hit, e_sup, sup)
            nlift = jnp.where(active & hit, e_lift, nlift)

        found = ok & (node > 0)
        node_ref[...] = jnp.where(found, node, -1)[:, None]
        ok_ref[...] = found.astype(jnp.int32)[:, None]
        conf_ref[...] = jnp.where(found, conf, 0.0)[:, None]
        sup_ref[...] = jnp.where(found, sup, 0.0)[:, None]
        lift_ref[...] = jnp.where(found, nlift, 0.0)[:, None]

    return kernel


def _all_not_found(q: int, lift_key: str) -> dict:
    """Result dict for degenerate searches (empty trie / zero-width query)."""
    z = jnp.zeros((q,), jnp.float32)
    return {
        "found": jnp.zeros((q,), bool),
        "node": jnp.full((q,), -1, jnp.int32),
        "confidence": z,
        "support": z,
        lift_key: z,
    }


@functools.partial(jax.jit, static_argnames=("interpret",))
def rule_search_pallas(
    edge_parent: jax.Array,   # int32 [E]
    edge_item: jax.Array,     # int32 [E]
    edge_child: jax.Array,    # int32 [E]
    edge_conf: jax.Array,     # f32 [E]
    edge_sup: jax.Array,      # f32 [E]
    edge_lift: jax.Array,     # f32 [E]
    queries: jax.Array,       # int32 [Q, L]
    ant_len: jax.Array,       # int32 [Q]
    interpret: bool = False,
):
    q, width = queries.shape
    e = edge_parent.shape[0]
    if e == 0 or width == 0:
        # Nothing to descend into: every rule is absent.  Returning here
        # avoids tracing a zero-chunk kernel over an empty edge table.
        return _all_not_found(q, "node_lift")
    qp = -q % BQ
    epad = -e % BE

    queries_p = jnp.pad(
        queries.astype(jnp.int32), ((0, qp), (0, 0)), constant_values=-1
    )
    al_p = jnp.pad(ant_len.astype(jnp.int32), (0, qp)).reshape(-1, 1)

    def pad_e(a, fill):
        return jnp.pad(a, (0, epad), constant_values=fill).reshape(1, -1)

    ep = pad_e(edge_parent.astype(jnp.int32), -7)
    ei = pad_e(edge_item.astype(jnp.int32), -7)
    ec = pad_e(edge_child.astype(jnp.int32), -1)
    ecf = pad_e(edge_conf.astype(jnp.float32), 0.0)
    esp = pad_e(edge_sup.astype(jnp.float32), 0.0)
    elf = pad_e(edge_lift.astype(jnp.float32), 0.0)

    qq = queries_p.shape[0]
    ee = ep.shape[1]
    n_chunks = ee // BE
    grid = (qq // BQ,)
    edge_spec = pl.BlockSpec((1, ee), lambda qi: (0, 0))
    out_specs = [
        pl.BlockSpec((BQ, 1), lambda qi: (qi, 0)) for _ in range(5)
    ]
    out_shapes = [
        jax.ShapeDtypeStruct((qq, 1), jnp.int32),
        jax.ShapeDtypeStruct((qq, 1), jnp.int32),
        jax.ShapeDtypeStruct((qq, 1), jnp.float32),
        jax.ShapeDtypeStruct((qq, 1), jnp.float32),
        jax.ShapeDtypeStruct((qq, 1), jnp.float32),
    ]
    node, okv, conf, sup, nlift = pl.pallas_call(
        _make_kernel(width, n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BQ, width), lambda qi: (qi, 0)),
            pl.BlockSpec((BQ, 1), lambda qi: (qi, 0)),
            edge_spec, edge_spec, edge_spec,
            edge_spec, edge_spec, edge_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(queries_p, al_p, ep, ei, ec, ecf, esp, elf)
    return {
        "found": okv[:q, 0].astype(bool),
        "node": node[:q, 0],
        "confidence": conf[:q, 0],
        "support": sup[:q, 0],
        "node_lift": nlift[:q, 0],
    }


# ----------------------------------------------------------------------
# plain layout: CSR bucket descent + consequent walk + compound lift
# ----------------------------------------------------------------------
def _columns(queries):
    """Scan inputs: one (item column, column index) pair per step."""
    q, width = queries.shape
    steps = jnp.broadcast_to(
        jnp.arange(width, dtype=jnp.int32)[:, None], (width, q)
    )
    return queries.astype(jnp.int32).T, steps


@functools.partial(jax.jit, static_argnames=("max_fanout",))
def rule_search_fused(
    child_offsets: jax.Array,  # int32 [N+1] CSR buckets over the edge table
    edge_item: jax.Array,      # int32 [E] item-sorted within each bucket
    edge_child: jax.Array,     # int32 [E]
    edge_conf: jax.Array,      # f32 [E]
    edge_sup: jax.Array,       # f32 [E]
    edge_lift: jax.Array,      # f32 [E]
    queries: jax.Array,        # int32 [Q, L]
    ant_len: jax.Array,        # int32 [Q]
    *,
    max_fanout: int = 0,       # static: widest bucket (bounds the search)
):
    """Rule search with full paper metrics (compound lift included): CSR
    bucket descent + consequent-only walk in one scan over the columns."""
    q, width = queries.shape
    e = edge_item.shape[0]
    if e == 0 or width == 0:
        out = _all_not_found(q, "lift")
        out["con_support"] = jnp.zeros((q,), jnp.float32)
        return out
    ant_len = ant_len.astype(jnp.int32)
    ecf = edge_conf.astype(jnp.float32)
    esp = edge_sup.astype(jnp.float32)
    elf = edge_lift.astype(jnp.float32)

    def lookup(nodes, items):
        j = bucket_edge_lookup(
            child_offsets, edge_item, max_fanout, nodes, items
        )
        jc = jnp.maximum(j, 0)
        return j >= 0, edge_child[jc], jc

    def step(carry, col):
        node, ok, conf, sup, nlift, cnode, cok, csup = carry
        item, s = col
        has_item = item >= 0
        in_cons = s >= ant_len

        active = has_item & ok
        hit, child, j = lookup(jnp.where(active, node, 0), item)
        ok = jnp.where(active, hit, ok)
        adv = active & hit
        node = jnp.where(adv, child, node)
        conf = jnp.where(adv & in_cons, conf * ecf[j], conf)
        sup = jnp.where(adv, esp[j], sup)
        nlift = jnp.where(adv, elf[j], nlift)

        # root-anchored consequent-only walk (Eq. 1-4 lift denominator)
        c_active = has_item & in_cons & cok
        chit, cchild, cj = lookup(jnp.where(c_active, cnode, 0), item)
        cok = jnp.where(c_active, chit, cok)
        cadv = c_active & chit
        cnode = jnp.where(cadv, cchild, cnode)
        csup = jnp.where(cadv, esp[cj], csup)
        return (node, ok, conf, sup, nlift, cnode, cok, csup), None

    zi = jnp.zeros((q,), jnp.int32)
    zf = jnp.zeros((q,), jnp.float32)
    ones = jnp.ones((q,), jnp.bool_)
    init = (zi, ones, jnp.ones((q,), jnp.float32), zf, zf, zi, ones, zf)
    (node, ok, conf, sup, nlift, cnode, cok, csup), _ = jax.lax.scan(
        step, init, _columns(queries)
    )
    found = ok & (node > 0)
    seq_len = jnp.sum((queries >= 0).astype(jnp.int32), axis=1)
    single = (seq_len - ant_len) == 1
    con_sup = jnp.where(cok & (cnode > 0), csup, 0.0)
    return {
        "found": found,
        "node": jnp.where(found, node, -1),
        "confidence": jnp.where(found, conf, 0.0),
        "support": jnp.where(found, sup, 0.0),
        "lift": compound_lift(found, single, nlift, conf, con_sup),
        # Support of the consequent-only root walk (0 where that path is
        # absent) — consumed by the sharded cross-device lift merge.
        "con_support": con_sup,
    }


# ----------------------------------------------------------------------
# compressed layout: span-aware descent + consequent walk
# ----------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=(
        "max_fanout", "n_transactions", "confidence_scale", "lift_scale",
    ),
)
def rule_search_span(
    child_offsets: jax.Array,  # int32 [Nc+1] compressed CSR buckets
    edge_item: jax.Array,      # int32 [Ec] item-sorted within each bucket
    edge_pos: jax.Array,       # int32 [Ec] child DFS position (run head)
    edge_span: jax.Array,      # int32 [Ec] interior steps to the run tail
    edge_tail: jax.Array,      # int32 [Ec] run tail's compressed id
    node_item: jax.Array,      # int32 [N] item per DFS position
    support: jax.Array,        # f32|int32 [N] (int32 = transaction counts)
    confidence: jax.Array,     # f32|bf16|int8 [N]
    lift: jax.Array,           # f32|bf16|int8 [N]
    queries: jax.Array,        # int32 [Q, L]
    ant_len: jax.Array,        # int32 [Q]
    *,
    max_fanout: int = 0,       # static: widest compressed bucket
    n_transactions: int = 0,   # static: int32-support denominator
    confidence_scale: float = 1.0,   # static: int8 column scale
    lift_scale: float = 1.0,         # static: int8 column scale
):
    """Rule search on the COMPRESSED layout (span-aware descent + fused
    consequent walk + compound lift).  The ``pos`` output is a DFS
    position — the ops wrapper maps it to an original node id via
    ``dfs_to_node``."""
    q, width = queries.shape
    e = edge_item.shape[0]
    if e == 0 or width == 0:
        out = _all_not_found(q, "lift")
        out["pos"] = out.pop("node")
        out["con_support"] = jnp.zeros((q,), jnp.float32)
        return out
    ant_len = ant_len.astype(jnp.int32)
    node_item = node_item.astype(jnp.int32)
    sup_col, conf_col, lift_col = dequantize_metrics(
        support, confidence, lift,
        n_transactions, confidence_scale, lift_scale,
    )

    def advance(pos, rem, ctail, items):
        return span_step(
            child_offsets, edge_item, edge_pos, edge_span, edge_tail,
            node_item, max_fanout, pos, rem, ctail, items,
        )

    def step(carry, col):
        pos, rem, ctail, ok, conf, cpos, crem, cctail, cok = carry
        item, s = col
        has_item = item >= 0
        in_cons = s >= ant_len

        active = has_item & ok
        pos2, rem2, ctail2, hit = advance(
            pos, rem, jnp.where(active, ctail, 0), item
        )
        ok = jnp.where(active, hit, ok)
        adv = active & hit
        conf = jnp.where(adv & in_cons, conf * conf_col[pos2], conf)
        pos = jnp.where(adv, pos2, pos)
        rem = jnp.where(adv, rem2, rem)
        ctail = jnp.where(adv, ctail2, ctail)

        c_active = has_item & in_cons & cok
        cp2, cr2, ct2, chit = advance(
            cpos, crem, jnp.where(c_active, cctail, 0), item
        )
        cok = jnp.where(c_active, chit, cok)
        cadv = c_active & chit
        cpos = jnp.where(cadv, cp2, cpos)
        crem = jnp.where(cadv, cr2, crem)
        cctail = jnp.where(cadv, ct2, cctail)
        return (pos, rem, ctail, ok, conf, cpos, crem, cctail, cok), None

    zi = jnp.zeros((q,), jnp.int32)
    ones = jnp.ones((q,), jnp.bool_)
    init = (zi, zi, zi, ones, jnp.ones((q,), jnp.float32),
            zi, zi, zi, ones)
    (pos, _, _, ok, conf, cpos, _, _, cok), _ = jax.lax.scan(
        step, init, _columns(queries)
    )
    found = ok & (pos > 0)
    seq_len = jnp.sum((queries >= 0).astype(jnp.int32), axis=1)
    single = (seq_len - ant_len) == 1
    con_sup = jnp.where(cok & (cpos > 0), sup_col[cpos], 0.0)
    conf = jnp.where(found, conf, 0.0)
    return {
        "found": found,
        "pos": jnp.where(found, pos, -1),
        "confidence": conf,
        "support": jnp.where(found, sup_col[pos], 0.0),
        "lift": compound_lift(
            found, single, jnp.where(found, lift_col[pos], 0.0),
            conf, con_sup,
        ),
        "con_support": con_sup,
    }
