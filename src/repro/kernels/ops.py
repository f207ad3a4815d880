"""Public jit'd wrappers over the Pallas kernels and XLA descents.

Each op compiles its Pallas kernels on a TPU and runs them in interpret
mode everywhere else (interpret mode executes the kernel body as plain
jnp, which is how the kernels are validated on CPU hosts), and composes
kernels into the paper-level semantics (e.g. compound-consequent lift =
two descents, Eq. 1-4).  ``REPRO_FORCE_INTERPRET`` overrides the choice
off-TPU (see ``interpret_mode``); a TPU always compiles.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.array_trie import (
    DeviceTrie,
    canonical_prefix_rows,
    child_lookup,
    compressed_descend,
    sanitize_query_items,
)

from .item_index import ROLES, rules_with_pallas
from .metrics_inkernel import RANK_METRICS, compound_lift, rank_score
from .rank import topk_rank_batch_pallas, topk_rank_pallas
from .ref import rules_with_ref, topk_rank_batch_ref, topk_rank_ref
from .support_count import support_count_pallas
from .rule_search import (
    rule_search_fused,
    rule_search_pallas,
    rule_search_span,
)
from .trie_reduce import trie_reduce_pallas
from .tuning import launch_pad

_TRUTHY = frozenset({"1", "true", "yes", "on", "interpret"})
_FALSY = frozenset({"0", "false", "no", "off", "compiled"})
_interpret_cache: dict = {}


def interpret_mode() -> bool:
    """Whether ops launch their Pallas kernels in interpret mode.

    Default: interpret everywhere but TPU (interpret mode executes the
    kernel body as plain jnp — how the kernels run on CPU CI).  The
    ``REPRO_FORCE_INTERPRET`` env var overrides the backend sniff off
    the TPU: truthy values (1/true/yes/on/interpret) force interpret,
    falsy values (0/false/no/off/compiled) force compiled.  On a TPU a
    truthy value is an error: it would silently time the interpreter.
    The decision is cached per (env value, backend), so flipping the env
    var mid-process takes effect on the next op call.
    """
    env = os.environ.get("REPRO_FORCE_INTERPRET")
    backend = jax.default_backend()
    key = (env, backend)
    hit = _interpret_cache.get(key)
    if hit is not None:
        return hit
    if env is not None and env.strip():
        val = env.strip().lower()
        if val in _TRUTHY:
            if backend == "tpu":
                raise ValueError(
                    f"REPRO_FORCE_INTERPRET={env!r} on a TPU: the kernels "
                    f"always compile there; unset it"
                )
            mode = True
        elif val in _FALSY:
            mode = False
        else:
            raise ValueError(
                f"REPRO_FORCE_INTERPRET={env!r} not understood; use one "
                f"of {sorted(_TRUTHY)} or {sorted(_FALSY)}"
            )
    else:
        mode = backend != "tpu"
    _interpret_cache[key] = mode
    return mode


# Back-compat alias: distributed.trie_sharding (and older call sites)
# import the pre-override name.
_interpret = interpret_mode


# ----------------------------------------------------------------------
# profiler annotations: ``ops.<op>.<phase>``
# ----------------------------------------------------------------------
from repro.obs.trace import scope as _scope  # noqa: E402


def _phase(op: str, phase: str, **attrs):
    """The ``ops.<op>.<phase>`` annotation (``repro.obs.trace.scope``)
    around one phase of an op, so a ``jax.profiler`` trace splits its
    host time.  Phases are siblings, never nested: ``prep`` (host numpy
    work and uploads before the launch), ``prefix_ranges`` (``top_k``'s
    host canonicalization and the dispatch of its jitted descent),
    ``kernel`` (the dispatch of the jitted or Pallas program; ``rows``
    its launch rows) and ``expand`` (the dispatch of the jitted gathers
    after it, back to query rows and node ids: ``_expand_rows``,
    ``_node_ids`` or ``_gather_rows``, one program per launch shape)."""
    return _scope(f"ops.{op}.{phase}", **attrs)


# ----------------------------------------------------------------------
# error taxonomy (the serve loop's retryable-vs-fatal classification)
# ----------------------------------------------------------------------
class TrieQueryError(Exception):
    """Base of the trie-query error taxonomy."""


class InvalidQueryError(TrieQueryError, ValueError):
    """Malformed query input — fatal for the offending request, never
    retried.  Subclasses ``ValueError`` so existing ``except ValueError``
    call sites (and tests) keep working."""


class TransientBackendError(TrieQueryError):
    """Transient backend/launch failure — safe to retry with backoff."""


# runtime-error message markers that indicate a transient backend
# condition (allocator pressure, collective flakes) rather than a bug;
# matched case-sensitively against gRPC/XLA status phrases
_RETRYABLE_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "out of memory",
)

# markers of a refusal by the TPU compiler (a kernel over its VMEM
# budget, a program over the device's memory): the status reads
# RESOURCE_EXHAUSTED, but the same program is refused on every retry
_COMPILE_MARKERS = (
    "compile permanent error",
    "memory space vmem",
)


def is_retryable(exc: BaseException) -> bool:
    """Retryable-vs-fatal classification for the serve loop's retry path.

    ``TransientBackendError`` (and subclasses) is retryable by
    construction; ``InvalidQueryError`` never is, and neither is
    ``distributed.trie_sharding.ShardFailure`` (re-launching on the same
    backend hits the same dead shard — the resilience ladder demotes
    instead of retrying).  Anything else is classified by message:
    backend ``RuntimeError``s carrying a transient status phrase
    (RESOURCE_EXHAUSTED / UNAVAILABLE / ...) retry, except refusals by
    the compiler (``_COMPILE_MARKERS``: a VMEM overflow reads
    RESOURCE_EXHAUSTED too, but no retry can fit it); all other errors
    are treated as bugs and surface immediately.
    """
    if isinstance(exc, InvalidQueryError):
        return False
    if isinstance(exc, TransientBackendError):
        return True
    msg = str(exc)
    if any(marker in msg for marker in _COMPILE_MARKERS):
        return False
    return any(marker in msg for marker in _RETRYABLE_MARKERS)


# ----------------------------------------------------------------------
# typed input validation (InvalidQueryError instead of XLA shape errors)
# ----------------------------------------------------------------------
_UNKNOWN_RANK = np.iinfo(np.int32).max // 2   # item_tables' unknown-item rank


def _validate_k(k, op: str) -> int:
    if isinstance(k, bool) or not isinstance(k, (int, np.integer)):
        raise InvalidQueryError(
            f"{op}: k must be a positive int, got {k!r}"
        )
    if int(k) <= 0:
        raise InvalidQueryError(
            f"{op}: k must be positive, got {int(k)}"
        )
    return int(k)


def _scalar_item_ok(it) -> bool:
    if isinstance(it, bool) or it is None:
        return False
    if isinstance(it, (int, np.integer)):
        return True
    # 0-d integer arrays (numpy or jax scalars) count as item ids too
    shape = getattr(it, "shape", None)
    if shape == ():
        return bool(np.issubdtype(np.asarray(it).dtype, np.integer))
    return False


def validate_items(
    items, op: str = "rules_with",
    n_items: Optional[int] = None, strict: bool = False,
) -> list:
    """Typed validation of a flat item-id list (``rules_with`` input).

    Always rejects ``None`` and non-integer entries with the offending
    value and index.  With ``strict=True`` (the serve loop's admission
    contract) ids outside ``[0, n_items)`` also raise; the default keeps
    the documented lenient semantics, where absent/negative ids resolve
    to empty result rows.
    """
    seq = list(items)
    try:
        arr = np.asarray(seq)
    except (ValueError, TypeError):
        arr = np.asarray(seq, dtype=object)
    if arr.ndim != 1:
        raise InvalidQueryError(
            f"{op}: expected a flat list of item ids, got shape "
            f"{arr.shape}"
        )
    if arr.size and not np.issubdtype(arr.dtype, np.integer):
        offender_i, offender = next(
            ((i, v) for i, v in enumerate(seq) if not _scalar_item_ok(v)),
            (0, seq[0]),
        )
        raise InvalidQueryError(
            f"{op}: query item at index {offender_i} is {offender!r}; "
            f"expected an integer item id"
        )
    if strict and n_items is not None and arr.size:
        bad = np.where((arr < 0) | (arr >= n_items))[0]
        if bad.size:
            i = int(bad[0])
            raise InvalidQueryError(
                f"{op}: item id {int(arr[i])} at index {i} outside the "
                f"vocabulary [0, {n_items})"
            )
    return seq


def _strict_vocab_check(its, qi: int, op: str, item_rank) -> None:
    nr = int(np.asarray(item_rank).shape[0])
    for it in its:
        v = int(it)
        if not (0 <= v < nr) or int(item_rank[v]) >= _UNKNOWN_RANK:
            raise InvalidQueryError(
                f"{op}: item id {v} in query {qi} is not in the trie's "
                f"vocabulary"
            )


def validate_prefixes(
    prefixes, op: str = "top_k_rules_batch",
    item_rank=None, strict: bool = False,
) -> None:
    """Typed validation of Q ragged antecedent prefixes.

    ``None`` prefixes and ``None``/non-integer entries inside a prefix
    raise with the offending value; ``strict=True`` additionally rejects
    ids outside the trie's item vocabulary (requires ``item_rank``).
    An already-padded ``[Q, P]`` integer matrix passes wholesale (its
    ``-1`` entries are padding by the repo-wide convention).
    """
    if isinstance(prefixes, np.ndarray) and prefixes.ndim == 2:
        if not np.issubdtype(prefixes.dtype, np.integer):
            raise InvalidQueryError(
                f"{op}: prefix matrix must be integer-typed, got "
                f"{prefixes.dtype}"
            )
        return
    for qi, p in enumerate(list(prefixes)):
        if p is None:
            raise InvalidQueryError(f"{op}: prefix {qi} is None")
        its = list(np.asarray(p).reshape(-1)) if not isinstance(
            p, (list, tuple)
        ) else list(p)
        for i, it in enumerate(its):
            if not _scalar_item_ok(it):
                raise InvalidQueryError(
                    f"{op}: entry {i} of prefix {qi} is {it!r}; "
                    f"expected an integer item id"
                )
        if strict and item_rank is not None:
            _strict_vocab_check(its, qi, op, item_rank)


def validate_rule_pairs(
    pairs, op: str = "rule_search_batch",
    item_rank=None, strict: bool = False,
) -> None:
    """Typed validation of ragged (antecedent, consequent) query pairs."""
    for qi, pair in enumerate(pairs):
        if pair is None or len(pair) != 2:
            raise InvalidQueryError(
                f"{op}: query {qi} is {pair!r}; expected an "
                f"(antecedent, consequent) pair"
            )
        for side_name, side in zip(("antecedent", "consequent"), pair):
            if side is None:
                raise InvalidQueryError(
                    f"{op}: {side_name} of query {qi} is None; expected "
                    f"a sequence of integer item ids"
                )
            its = list(side)
            for i, it in enumerate(its):
                if not _scalar_item_ok(it):
                    raise InvalidQueryError(
                        f"{op}: entry {i} of the {side_name} of query "
                        f"{qi} is {it!r}; expected an integer item id"
                    )
            if strict and item_rank is not None:
                _strict_vocab_check(its, qi, op, item_rank)


def dedup_query_rows(queries, ant_len):
    """Whole-query dedup for canonical ``[Q, L]`` search rows.

    Equal (row, ant_len) queries descend identically, so the kernel only
    needs the unique rows — skewed serving traffic otherwise re-descends
    duplicates Q times.  Returns ``(uq, ual, inv)`` where ``inv`` scatters
    unique-row results back to the original Q rows, or
    ``(queries, ant_len, None)`` when every row is already unique AND the
    count already equals its launch pad (the original launch path, no
    extra padding).  The unique count otherwise pads up to
    ``tuning.launch_pad`` (next pow2, floored at the active config's
    ``launch_pad_floor``) with all-padding rows (item -1, ant_len 0 —
    found False by construction):
    a serving stream of arbitrary batch sizes then hits a BOUNDED set of
    compiled launch shapes instead of recompiling per distinct Q.
    """
    q = np.asarray(queries)
    al = np.asarray(ant_len)
    if q.shape[0] <= 1:
        return q, al, None
    key = np.concatenate(
        [q.astype(np.int64), al.astype(np.int64)[:, None]], axis=1
    )
    uniq, inv = np.unique(key, axis=0, return_inverse=True)
    inv = np.asarray(inv).reshape(-1)
    u = uniq.shape[0]
    upad = launch_pad(u)
    if u == q.shape[0] and upad == u:
        return q, al, None
    uq = np.full((upad, q.shape[1]), -1, np.int32)
    ual = np.zeros((upad,), np.int32)
    uq[:u] = uniq[:, :-1]
    ual[:u] = uniq[:, -1]
    return uq, ual, inv.astype(np.int32)


def _as_shard_plan(trie):
    """The ShardPlan when ``trie`` is one, else None (lazy import: the
    distributed package imports kernel submodules, so importing it at
    module scope would cycle through ``repro.kernels.__init__``)."""
    from repro.distributed.trie_sharding import ShardPlan

    return trie if isinstance(trie, ShardPlan) else None


def _as_streaming(trie):
    """The StreamingTrie when ``trie`` is one, else None (same lazy
    isinstance dispatch as ``_as_shard_plan``; the streaming merge
    helpers live in ``kernels.streaming``)."""
    from repro.core.delta_trie import StreamingTrie

    return trie if isinstance(trie, StreamingTrie) else None


# ----------------------------------------------------------------------
# support counting
# ----------------------------------------------------------------------
def members_from_candidates(
    candidates: jax.Array, n_items: int
) -> jax.Array:
    """[C, K] padded item lists → [C, I] 0/1 membership.

    A row-indexed scatter-max, NOT a one-hot sum: annotation batches reach
    C ≈ 1e5+ nodes, where materializing the [C, K, I] one-hot would cost
    gigabytes; the scatter peaks at the [C, I] output itself.
    """
    c, k = candidates.shape
    valid = candidates >= 0
    safe = jnp.where(valid, candidates, 0)
    rows = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[:, None], (c, k))
    member = jnp.zeros((c, n_items), jnp.float32)
    return member.at[rows, safe].max(valid.astype(jnp.float32))


def support_count(
    candidates,            # int32 [C, K] padded with -1
    lengths,               # int32 [C]; <= 0 marks padding rows (count 0)
    item_bitmaps=None,     # uint32 [I, W] vertical layout (TransactionDB)
    dense_tx=None,         # or [T, I] 0/1 dense transactions
) -> jax.Array:
    """Counts for every candidate itemset against the transaction DB.

    The in-kernel match test compares against the number of DISTINCT
    items per row (recomputed from the 0/1 membership), so candidate rows
    with repeated items — e.g. duplicate-item trie paths — count their
    item SET, matching the bitmap AND semantics.
    """
    if dense_tx is None:
        if item_bitmaps is None:
            raise ValueError("need item_bitmaps or dense_tx")
        dense_tx = dense_from_bitmaps(np.asarray(item_bitmaps))
    dense_tx = jnp.asarray(dense_tx)
    candidates = jnp.asarray(candidates, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    member = members_from_candidates(candidates, dense_tx.shape[1])
    distinct = jnp.sum(member, axis=1).astype(jnp.int32)
    eff_len = jnp.where(lengths > 0, distinct, -1)
    return support_count_pallas(
        dense_tx, member, eff_len, interpret=_interpret()
    )


def dense_from_bitmaps(item_bitmaps: np.ndarray) -> np.ndarray:
    """uint32 [I, W] vertical bitmaps → uint8 [T, I] dense membership."""
    i, w = item_bitmaps.shape
    bits = np.unpackbits(
        item_bitmaps.view(np.uint8).reshape(i, w, 4), axis=-1, bitorder="little"
    )  # [I, W, 32]
    return bits.reshape(i, w * 32).T.astype(np.uint8)


def annotate_candidates(
    candidates,            # int32 [C, K] node root-path items, -1 padded
    lengths,               # int32 [C] path depths
    node_parent,           # int32 [C] parent node id per node (0 = root)
    node_item,             # int32 [C] consequent item per node
    item_counts,           # int/float [n_items] absolute item frequencies
    n_transactions: int,
    item_bitmaps=None,     # uint32 [I, W] vertical layout (TransactionDB)
    dense_tx=None,         # or [T, I] 0/1 dense transactions
) -> Dict[str, jax.Array]:
    """Step-3 batched trie annotation: every node metric in one pass.

    Node ids are BFS/depth-major (``FrozenTrie`` numbering, root = 0), so
    row ``i`` describes node ``i + 1``.  Supports come from ONE
    ``support_count`` kernel launch over the whole candidate matrix
    (``[T,I]@[C,I]^T`` on the MXU) — replacing the pointer pipeline's N
    per-node popcount calls — and the Confidence/Lift columns are pure
    array ops against the parent supports via ``node_parent`` gathers.
    Leverage and conviction are derived with the same shared
    ``metrics_inkernel.rank_score`` math the rank kernel uses.
    """
    counts = support_count(candidates, lengths, item_bitmaps, dense_tx)
    n_tx = jnp.maximum(jnp.float32(n_transactions), 1.0)
    sup = counts.astype(jnp.float32) / n_tx
    # parent-support gather; virtual root slot = Support(∅) = 1.0
    sup_full = jnp.concatenate([jnp.ones((1,), jnp.float32), sup])
    psup = sup_full[jnp.asarray(node_parent, jnp.int32)]
    conf = jnp.where(
        psup > 0, sup / jnp.where(psup > 0, psup, 1.0), 0.0
    )
    isup = (
        jnp.asarray(item_counts, jnp.float32)[
            jnp.asarray(node_item, jnp.int32)
        ] / n_tx
    )
    lift = jnp.where(
        isup > 0, conf / jnp.where(isup > 0, isup, 1.0), 0.0
    )
    return {
        "support": sup,
        "confidence": conf,
        "lift": lift,
        "leverage": rank_score("leverage", sup, conf, lift),
        "conviction": rank_score("conviction", sup, conf, lift),
    }


# ----------------------------------------------------------------------
# trie search
# ----------------------------------------------------------------------
def _dequant_statics(src) -> Dict:
    """The static dequantization params (``metrics_inkernel``) carried by
    a compressed trie or one of the arrays dicts below; fp32 no-op
    defaults otherwise.  Plumbed into every rank/membership/reduce launch
    so quantized columns widen in-kernel."""
    get = src.get if isinstance(src, dict) else (
        lambda k, d: getattr(src, k, d)
    )
    return {
        "n_transactions": int(get("n_transactions", 0)),
        "confidence_scale": float(get("confidence_scale", 1.0)),
        "lift_scale": float(get("lift_scale", 1.0)),
    }


def edge_metric_arrays(trie) -> Dict[str, jax.Array]:
    """Edge-annotated metrics: child-node metrics gathered onto edges once
    at freeze time, so the kernel needs no per-step metric gathers
    (DeviceTrie or FrozenTrie accepted).

    Also carries the CSR child-bucket index (``child_offsets`` +
    ``max_fanout``) when the trie has one; the bucket descent needs it,
    and the full-sweep kernel ignores it.

    COMPRESSED tries return the span-table form instead (marked with
    ``"layout": "compressed"``): the compressed CSR + span edge columns
    and the POSITION-indexed (possibly quantized) node metric columns —
    no edge metric gathers exist on this layout at all, which is a large
    part of its memory win.
    """
    if getattr(trie, "layout", "plain") == "compressed":
        return {
            "layout": "compressed",
            "child_offsets": jnp.asarray(trie.child_offsets, jnp.int32),
            "edge_item": jnp.asarray(trie.edge_item, jnp.int32),
            "edge_pos": jnp.asarray(trie.edge_child, jnp.int32),
            "edge_span": jnp.asarray(trie.edge_span, jnp.int32),
            "edge_tail": jnp.asarray(trie.edge_tail, jnp.int32),
            "node_item": jnp.asarray(trie.node_item, jnp.int32),
            "support": jnp.asarray(trie.support),
            "confidence": jnp.asarray(trie.confidence),
            "lift": jnp.asarray(trie.lift),
            "dfs_to_node": jnp.asarray(trie.dfs_to_node, jnp.int32),
            "max_fanout": int(getattr(trie, "max_fanout", 0)),
            **_dequant_statics(trie),
        }
    child = jnp.asarray(trie.edge_child, jnp.int32)
    safe_child = jnp.maximum(child, 0)  # E == 0 → empty gather stays valid
    offsets = getattr(trie, "child_offsets", None)
    return {
        "edge_parent": jnp.asarray(trie.edge_parent, jnp.int32),
        "edge_item": jnp.asarray(trie.edge_item, jnp.int32),
        "edge_child": child,
        "edge_conf": jnp.asarray(trie.confidence)[safe_child],
        "edge_sup": jnp.asarray(trie.support)[safe_child],
        "edge_lift": jnp.asarray(trie.lift)[safe_child],
        "child_offsets": (
            None if offsets is None else jnp.asarray(offsets, jnp.int32)
        ),
        "max_fanout": int(getattr(trie, "max_fanout", 0)),
    }


@jax.jit
def _pos_to_node(found, pos, dfs_to_node):
    """Span-kernel DFS position → original node id (-1 where not found),
    jitted so the compressed path's post-map is one dispatch."""
    return jnp.where(found, dfs_to_node[jnp.maximum(pos, 0)], -1)


# ----------------------------------------------------------------------
# expand: the gathers after a launch, one jitted program per launch shape
# ----------------------------------------------------------------------
def _map_nodes(pos, back):
    """Kernel position → node id through the ``back`` map (``dfs_to_node``
    or a posting list's ``item_nodes``), -1 on empty slots.  An empty map
    (a trie with no rules) is resolved when the program is traced."""
    if back.shape[0] == 0:
        return jnp.full_like(pos, -1)
    return jnp.where(pos >= 0, back[jnp.maximum(pos, 0)], -1)


# the ranked ops' node-id map as one dispatch
_node_ids = jax.jit(_map_nodes)


@jax.jit
def _expand_rows(vals, pos, inv, back):
    """``rules_with``'s expand in one dispatch: the unique launch rows
    back to the Q query rows through the inverse map ``inv``, and their
    positions to node ids.  Integer gathers and selects only, so the
    rows are bit-identical to the same expressions run eagerly."""
    vals = vals[inv]
    pos = pos[inv]
    return vals, pos, _map_nodes(pos, back)


@jax.jit
def _gather_rows(out, inv):
    """``rule_search_batch``'s expand in one dispatch: every column of the
    result dict (a pytree) back to the query rows through ``inv``."""
    return jax.tree.map(lambda v: v[inv], out)


def rule_search(
    trie,                  # DeviceTrie / FrozenTrie
    queries,               # int32 [Q, L] canonical rows (-1 padded)
    ant_len,               # int32 [Q]
    edges: Optional[Dict[str, jax.Array]] = None,
) -> Dict[str, jax.Array]:
    """Batched rule search with full paper metrics (compound lift incl.).

    With a CSR child-bucket index this is ONE jitted descent (bucket
    binary search + consequent walk + Eq. 1-4 lift, an XLA gather loop).
    Without one (seed layout) it falls back to two full-sweep launches.

    ``trie`` may also be a ``core.delta_trie.StreamingTrie`` — the
    frozen kernel then answers over the base and rows touching a
    modified rule recompute from the union, bit-identical to a
    from-scratch rebuild (``kernels.streaming``).
    """
    stream = _as_streaming(trie)
    if stream is not None:
        if edges is not None:
            raise ValueError(
                "streaming rule_search ignores precomputed edges= — the "
                "stream owns its (epoch-versioned) base residency; drop "
                "the argument"
            )
        from .streaming import streaming_rule_search_batch

        return streaming_rule_search_batch(stream, queries, ant_len)
    with _phase("rule_search", "prep"):
        if edges is None:
            edges = edge_metric_arrays(trie)
        queries = jnp.asarray(queries, jnp.int32)
        ant_len = jnp.asarray(ant_len, jnp.int32)

    if queries.shape[0] == 0:
        # Q == 0: nothing to search; avoid tracing a zero-grid kernel.
        z = jnp.zeros((0,), jnp.float32)
        return {
            "found": jnp.zeros((0,), bool),
            "node": jnp.zeros((0,), jnp.int32),
            "support": z, "confidence": z, "lift": z,
        }

    rows = queries.shape[0]
    if edges.get("layout") == "compressed":
        with _phase("rule_search", "kernel", rows=rows):
            out = rule_search_span(
                edges["child_offsets"], edges["edge_item"],
                edges["edge_pos"], edges["edge_span"], edges["edge_tail"],
                edges["node_item"], edges["support"], edges["confidence"],
                edges["lift"], queries, ant_len,
                max_fanout=edges["max_fanout"],
                n_transactions=edges["n_transactions"],
                confidence_scale=edges["confidence_scale"],
                lift_scale=edges["lift_scale"],
            )
        # The span descent reports DFS positions; the op-level contract is
        # original node ids (same dict shape as the plain paths).
        with _phase("rule_search", "expand"):
            node = _pos_to_node(out["found"], out["pos"],
                                edges["dfs_to_node"])
        return {
            "found": out["found"],
            "node": node,
            "support": out["support"],
            "confidence": out["confidence"],
            "lift": out["lift"],
        }

    if edges.get("child_offsets") is not None:
        with _phase("rule_search", "kernel", rows=rows):
            out = rule_search_fused(
                edges["child_offsets"], edges["edge_item"],
                edges["edge_child"], edges["edge_conf"], edges["edge_sup"],
                edges["edge_lift"], queries, ant_len,
                max_fanout=edges["max_fanout"],
            )
        # con_support is descent plumbing for the sharded merge, not part
        # of the op-level contract (keeps single/sharded dicts identical)
        return {k: v for k, v in out.items() if k != "con_support"}

    interp = _interpret()
    with _phase("rule_search", "kernel", rows=rows):
        full = rule_search_pallas(
            edges["edge_parent"], edges["edge_item"], edges["edge_child"],
            edges["edge_conf"], edges["edge_sup"], edges["edge_lift"],
            queries, ant_len, interpret=interp,
        )
    # Consequent-only walk for compound lift (Eq. 1-4): keep consequent
    # columns, blank the antecedent, walk from the root.
    with _phase("rule_search", "prep"):
        width = queries.shape[1]
        cols = jnp.arange(width, dtype=jnp.int32)[None, :]
        cons_q = jnp.where(cols >= ant_len[:, None], queries, -1)
    with _phase("rule_search", "kernel", rows=rows):
        cons = rule_search_pallas(
            edges["edge_parent"], edges["edge_item"], edges["edge_child"],
            edges["edge_conf"], edges["edge_sup"], edges["edge_lift"],
            cons_q, jnp.zeros_like(ant_len), interpret=interp,
        )
    with _phase("rule_search", "expand"):
        seq_len = jnp.sum(queries >= 0, axis=1).astype(jnp.int32)
        single = (seq_len - ant_len) == 1
        lift = compound_lift(
            full["found"], single, full["node_lift"],
            full["confidence"], cons["support"],
        )
    return {
        "found": full["found"],
        "node": full["node"],
        "support": full["support"],
        "confidence": full["confidence"],
        "lift": lift,
    }


# ----------------------------------------------------------------------
# ranked extraction (segmented top-k over the DFS-contiguous layout)
# ----------------------------------------------------------------------
def dfs_rank_arrays(trie) -> Dict[str, jax.Array]:
    """DFS-ordered rank columns + the DFS relabeling, gathered once.

    ``trie`` is a DeviceTrie or FrozenTrie carrying the DFS layout from
    ``FrozenTrie.freeze`` / ``array_trie.dfs_layout``.  Pass the result
    back via ``top_k_rules(..., arrays=...)`` to amortize the gathers
    across repeated ranked queries on the same trie.

    On the COMPRESSED layout the node axis already IS DFS pre-order, so
    the columns are direct (possibly quantized) views with NO gathers —
    no fp32 duplicate of the quantized storage ever materializes, which
    is the rank-path half of the layout's memory win.  The dict carries
    the dequant statics for the kernel launches.
    """
    d2n = getattr(trie, "dfs_to_node", None)
    if d2n is None:
        raise ValueError(
            "trie has no DFS layout (dfs_to_node is None); freeze it with "
            "FrozenTrie.freeze or compute array_trie.dfs_layout first"
        )
    d2n = jnp.asarray(d2n, jnp.int32)
    if getattr(trie, "layout", "plain") == "compressed":
        return {
            "support": jnp.asarray(trie.support),
            "confidence": jnp.asarray(trie.confidence),
            "lift": jnp.asarray(trie.lift),
            "depth": jnp.asarray(trie.node_depth, jnp.int32),
            "subtree_size": jnp.asarray(trie.subtree_size, jnp.int32),
            "dfs_to_node": d2n,
            **_dequant_statics(trie),
        }
    return {
        "support": jnp.asarray(trie.support)[d2n],
        "confidence": jnp.asarray(trie.confidence)[d2n],
        "lift": jnp.asarray(trie.lift)[d2n],
        "depth": jnp.asarray(trie.node_depth, jnp.int32)[d2n],
        "dfs_order": jnp.asarray(trie.dfs_order, jnp.int32),
        "subtree_size": jnp.asarray(trie.subtree_size, jnp.int32),
        "dfs_to_node": d2n,
    }


def top_k_rules(
    trie,                                   # DeviceTrie / FrozenTrie
    k: int,
    metric: str = "confidence",
    prefix: Optional[Sequence[int]] = None,
    min_depth: int = 1,
    arrays: Optional[Dict[str, jax.Array]] = None,
    use_kernel: bool = True,
) -> Dict[str, jax.Array]:
    """Top-k rules by an interestingness metric, whole-trie or under an
    antecedent prefix.

    ``metric`` is one of ``RANK_METRICS`` (support/confidence/lift/
    leverage/conviction — leverage and conviction are derived in-kernel
    from the stored columns, see ``metrics_inkernel.rank_score``).

    ``prefix`` — items of an antecedent prefix — scopes the ranking to
    the rules whose path starts with that prefix: the CSR bucket descent
    resolves the prefix node, whose subtree is ONE contiguous DFS range
    ``[dfs_order[v], dfs_order[v] + subtree_size[v])`` by construction.
    A prefix absent from the trie yields an empty range (all slots
    ``(-inf, -1)``).  Items are canonicalized to frequency order when the
    trie carries an ``item_rank`` table (FrozenTrie does).

    Returns ``{"values" f32[k], "node" int32[k], "dfs_pos" int32[k]}``
    in ``jax.lax.top_k`` order; slots past the live-rule count are
    ``(-inf, -1)``.  The kernel path and the ``use_kernel=False`` jnp
    oracle are bit-identical.
    """
    if metric not in RANK_METRICS:
        raise InvalidQueryError(
            f"metric {metric!r} not in {RANK_METRICS}"
        )
    _validate_k(k, "top_k_rules")
    if prefix is not None:
        validate_prefixes(
            [prefix], "top_k_rules",
            item_rank=getattr(trie, "item_rank", None),
        )
    stream = _as_streaming(trie)
    if stream is not None:
        if arrays is not None or not use_kernel:
            raise ValueError(
                "streaming top_k_rules supports neither arrays= (the "
                "stream owns its epoch-versioned residency) nor "
                "use_kernel=False (the jnp oracle takes no delta)"
            )
        from .streaming import streaming_top_k_rules

        return streaming_top_k_rules(
            stream, k, metric=metric, prefix=prefix, min_depth=min_depth
        )
    if arrays is None:
        arrays = dfs_rank_arrays(trie)
    n = arrays["support"].shape[0]
    if prefix is None:
        lo = jnp.int32(0)
        hi = jnp.int32(n)
    else:
        # The Q=1 slice of the batched resolution: ONE canonicalization +
        # descent implementation for single and batched prefix queries.
        los, his, _nodes = prefix_ranges(
            trie, [prefix], dt=_cached_device_trie(trie, arrays)
        )
        lo, hi = los[0], his[0]
    rank_fn = (
        functools.partial(topk_rank_pallas, interpret=_interpret())
        if use_kernel else topk_rank_ref
    )
    with _phase("top_k", "kernel", rows=1):
        vals, pos = rank_fn(
            arrays["support"], arrays["confidence"], arrays["lift"],
            arrays["depth"], lo, hi,
            k=int(k), metric=metric, min_depth=int(min_depth),
            **_dequant_statics(arrays),
        )
    with _phase("top_k", "expand"):
        node_ids = _node_ids(pos, arrays["dfs_to_node"])
    return {"values": vals, "node": node_ids, "dfs_pos": pos}


# ----------------------------------------------------------------------
# batched multi-query engine (item-inverted index + segmented ranges)
# ----------------------------------------------------------------------
def _cached_device_trie(trie, arrays: Optional[Dict] = None):
    """The descent's DeviceTrie, cached in the arrays dict so repeat
    queries with ``arrays=`` don't re-upload the trie columns."""
    if isinstance(trie, DeviceTrie):
        return trie
    if arrays is None:
        return trie.device_arrays()
    dt = arrays.get("_device_trie")
    if dt is None:
        dt = trie.device_arrays()
        arrays["_device_trie"] = dt
    return dt


def item_rank_arrays(trie) -> Dict[str, jax.Array]:
    """Inverted-index query arrays, gathered once per trie.

    ``trie`` is a DeviceTrie or FrozenTrie carrying the item-inverted
    index (``item_offsets`` / ``item_nodes``) plus the DFS layout.
    Returns the DFS-ordered metric/item columns, the posting subtree
    ranges (``post_lo`` ascending per item by construction; ``post_hi``
    sorted per item here, so both sides of the laminar range count are
    binary-searchable), and posting-ordered metric columns for the
    consequent-role fast path.  Pass the result back via
    ``rules_with(..., arrays=...)`` to amortize across repeated queries.

    The COMPRESSED layout stores the posting subtree bounds precomputed
    (``CompressedTrie.device_arrays``) and its columns are already
    DFS-ordered, so everything is a direct view; it has NO posting-node
    array (``item_nodes``) and hence no posting-ordered column block —
    ``rules_with`` routes the consequent role through the membership
    kernel (pure ``node_item`` self-hit, no posting arrays touched)
    instead of the posting-range fast path.
    """
    offsets = getattr(trie, "item_offsets", None)
    if offsets is None:
        raise ValueError(
            "trie has no item-inverted index (item_offsets is None); "
            "freeze it with FrozenTrie / build_frozen_trie first"
        )
    offsets = np.asarray(offsets)
    if getattr(trie, "layout", "plain") == "compressed":
        return {
            "support": jnp.asarray(trie.support),
            "confidence": jnp.asarray(trie.confidence),
            "lift": jnp.asarray(trie.lift),
            "depth": jnp.asarray(trie.node_depth, jnp.int32),
            "node_item": jnp.asarray(trie.node_item, jnp.int32),
            "post_lo": jnp.asarray(trie.post_lo, jnp.int32),
            "post_hi": jnp.asarray(trie.post_hi, jnp.int32),
            "item_offsets": offsets,   # host: query slicing is scalar
            "dfs_to_node": jnp.asarray(trie.dfs_to_node, jnp.int32),
            "max_postings": int(getattr(trie, "max_postings", 0)),
            **_dequant_statics(trie),
        }
    item_nodes = np.asarray(trie.item_nodes)
    dfs_order = np.asarray(trie.dfs_order)
    subtree = np.asarray(trie.subtree_size)
    d2n = np.asarray(trie.dfs_to_node)
    n = dfs_order.shape[0]
    post_lo = dfs_order[item_nodes].astype(np.int64)
    post_hi_raw = post_lo + subtree[item_nodes].astype(np.int64)
    # per-item ascending subtree ends: one global composite-key argsort
    # (segment id majors the key) instead of a per-item sort loop
    seg = np.repeat(
        np.arange(offsets.shape[0] - 1, dtype=np.int64), np.diff(offsets)
    )
    order = np.argsort(seg * (n + 1) + post_hi_raw, kind="stable")
    post_hi = post_hi_raw[order]
    sup = np.asarray(trie.support)
    conf = np.asarray(trie.confidence)
    lift = np.asarray(trie.lift)
    depth = np.asarray(trie.node_depth)
    nitem = np.asarray(trie.node_item)
    max_postings = (
        int(np.diff(offsets).max()) if offsets.shape[0] > 1 else 0
    )
    return {
        "support": jnp.asarray(sup[d2n]),
        "confidence": jnp.asarray(conf[d2n]),
        "lift": jnp.asarray(lift[d2n]),
        "depth": jnp.asarray(depth[d2n], jnp.int32),
        "node_item": jnp.asarray(nitem[d2n], jnp.int32),
        "post_lo": jnp.asarray(post_lo, jnp.int32),
        "post_hi": jnp.asarray(post_hi, jnp.int32),
        "item_offsets": offsets,       # host: query slicing is scalar
        "item_nodes": jnp.asarray(item_nodes, jnp.int32),
        "dfs_to_node": jnp.asarray(d2n, jnp.int32),
        "max_postings": max_postings,
        # posting-ordered columns: the consequent-role fast path ranks a
        # contiguous posting range of these with the segmented kernel
        "p_support": jnp.asarray(sup[item_nodes]),
        "p_confidence": jnp.asarray(conf[item_nodes]),
        "p_lift": jnp.asarray(lift[item_nodes]),
        "p_depth": jnp.asarray(depth[item_nodes], jnp.int32),
    }


def _pad_pow2_rows(plos, phis, qitems, axis: int = 0) -> tuple:
    """Pad deduped query rows up to the next power of two with
    absent-item queries (empty slice [0, 0), item id -1) so kernel
    launch shapes stay bucketed (at most log2(Q) compiled variants)."""
    u = qitems.shape[0]
    u_pad = launch_pad(u)
    if u_pad == u:
        return plos, phis, qitems
    pad = u_pad - u
    widths = [(0, 0)] * plos.ndim
    widths[axis] = (0, pad)
    return (
        np.pad(plos, widths),
        np.pad(phis, widths),
        np.pad(qitems, (0, pad), constant_values=-1),
    )


def _posting_slices(offsets: np.ndarray, items) -> tuple:
    """Per-query posting slice [plo, phi) + sanitized item ids.

    Items outside ``[0, I)`` (absent from the universe) get the empty
    slice and item id -1 (matched by no node) — the sanitize step is
    ``array_trie.sanitize_query_items``, shared with the sharded
    resolver."""
    valid, safe, qitems = sanitize_query_items(
        items, offsets.shape[0] - 1
    )
    plos = np.where(valid, offsets[safe], 0).astype(np.int32)
    phis = np.where(valid, offsets[safe + 1], 0).astype(np.int32)
    return plos, phis, qitems


def rules_with(
    trie,                                   # DeviceTrie / FrozenTrie
    items,                                  # int sequence [Q]
    role: str = "any",
    k: int = 10,
    metric: str = "confidence",
    min_depth: int = 1,
    arrays: Optional[Dict[str, jax.Array]] = None,
    use_kernel: bool = True,
    strict: bool = False,
) -> Dict[str, jax.Array]:
    """Top-k rules involving each queried item, Q items in ONE launch.

    ``role`` selects where the item must appear: ``"consequent"`` (the
    node's own item — its posting list, ranked via the segmented rank
    kernel over a contiguous posting range), ``"antecedent"`` (a strict
    ancestor carries it — DFS-subtree-range membership over the posting
    subtree ranges, no path walk), or ``"any"`` (either).

    Returns ``{"values" f32[Q, k], "node" int32[Q, k], "pos" int32[Q, k]}``
    rows in ``jax.lax.top_k`` order, empty slots ``(-inf, -1)``.
    ``pos`` is the in-kernel position (posting index for the consequent
    role, DFS position otherwise); ``node`` is always the node id.
    Absent items, duplicate items, and k beyond the match count are all
    well-defined (empty slices / repeated rows / ``(-inf, -1)`` tails).

    ``trie`` may also be a ``distributed.trie_sharding.ShardPlan`` — the
    query then runs shard_map-distributed over the plan's mesh (each
    device answering over its co-partitioned posting lists, k-best
    all-gather + rank-merge), bit-identical to this single-device form.
    """
    if role not in ROLES:
        raise InvalidQueryError(f"role {role!r} not in {ROLES}")
    if metric not in RANK_METRICS:
        raise InvalidQueryError(f"metric {metric!r} not in {RANK_METRICS}")
    _validate_k(k, "rules_with")
    stream = _as_streaming(trie)
    if stream is not None:
        if arrays is not None or not use_kernel:
            raise ValueError(
                "streaming rules_with supports neither arrays= (the "
                "stream owns its epoch-versioned residency) nor "
                "use_kernel=False (the jnp oracle takes no delta)"
            )
        from .streaming import streaming_rules_with

        return streaming_rules_with(
            stream, items, role=role, k=k, metric=metric,
            min_depth=min_depth, strict=strict,
        )
    plan = _as_shard_plan(trie)
    if plan is not None:
        if arrays is not None or not use_kernel:
            raise ValueError(
                "sharded rules_with supports neither arrays= (the plan "
                "already owns its device residency) nor use_kernel=False "
                "(the jnp oracle is single-device only)"
            )
        with _phase("rules_with", "prep"):
            items = validate_items(
                items, "rules_with",
                n_items=plan.local_item_offsets.shape[1] - 1, strict=strict,
            )
        from repro.distributed.trie_sharding import sharded_rules_with

        with _phase("rules_with", "kernel", rows=len(items)):
            return sharded_rules_with(
                plan, items, role=role, k=k, metric=metric,
                min_depth=min_depth,
            )
    with _phase("rules_with", "prep"):
        if arrays is None:
            arrays = item_rank_arrays(trie)
        items = validate_items(
            items, "rules_with",
            n_items=arrays["item_offsets"].shape[0] - 1, strict=strict,
        )
        plos, phis, qitems = _posting_slices(arrays["item_offsets"], items)
        # Duplicate-item dedup: identical (sanitized) items produce
        # bit-identical result rows, and the membership mask gathers a
        # [Q, ~max_postings] posting window per query — running the
        # launch over the U unique items bounds that at [U, ...] and cuts
        # compute on skewed traffic; rows expand back via the inverse map
        # afterwards.  (Every absent item sanitizes to -1, so they dedup
        # together too.)  U pads up to a power of two so a serving stream
        # of fixed-Q batches with varying duplicate multiplicity hits a
        # bounded set of compiled launch shapes instead of one trace per
        # distinct unique-count; the pad rows are absent-item queries
        # (empty slice, item -1) that no inverse-map entry ever reads.
        _, first, inv = np.unique(
            qitems, return_index=True, return_inverse=True
        )
        plos, phis, qitems = _pad_pow2_rows(
            plos[first], phis[first], qitems[first]
        )
        plos_j = jnp.asarray(plos)
        phis_j = jnp.asarray(phis)
        qitems_j = jnp.asarray(qitems)
        inv_j = jnp.asarray(inv, jnp.int32)
    rows = plos.shape[0]
    if role == "consequent" and "p_support" in arrays:
        rank_fn = (
            functools.partial(topk_rank_batch_pallas, interpret=_interpret())
            if use_kernel else topk_rank_batch_ref
        )
        with _phase("rules_with", "kernel", rows=rows):
            vals, pos = rank_fn(
                arrays["p_support"], arrays["p_confidence"],
                arrays["p_lift"], arrays["p_depth"],
                plos_j, phis_j,
                k=int(k), metric=metric, min_depth=int(min_depth),
            )
        back = arrays["item_nodes"]
    else:
        # The compressed layout has no posting-ordered column block, so
        # its consequent role also runs here: the membership mask's
        # consequent test is a pure node_item self-hit (the posting
        # arrays are operands but never read), and postings are
        # DFS-sorted, so the node order matches the fast path's.
        member_fn = (
            functools.partial(rules_with_pallas, interpret=_interpret())
            if use_kernel else rules_with_ref
        )
        with _phase("rules_with", "kernel", rows=rows):
            vals, pos = member_fn(
                arrays["support"], arrays["confidence"], arrays["lift"],
                arrays["depth"], arrays["node_item"],
                arrays["post_lo"], arrays["post_hi"],
                plos_j, phis_j, qitems_j,
                k=int(k), metric=metric, min_depth=int(min_depth),
                role=role,
                **({"max_postings": arrays["max_postings"]}
                   if use_kernel else {}),
                **_dequant_statics(arrays),
            )
        back = arrays["dfs_to_node"]
    with _phase("rules_with", "expand"):
        vals, pos, node = _expand_rows(vals, pos, inv_j, back)
    return {"values": vals, "node": node, "pos": pos}


def prefix_ranges(
    trie,                                   # DeviceTrie / FrozenTrie
    prefixes,                               # ragged item seqs or [Q, P]
    dt: Optional[DeviceTrie] = None,        # pre-uploaded descent arrays
) -> tuple:
    """Resolve Q antecedent prefixes to DFS ranges in one batched descent.

    Prefixes are canonicalized to frequency order on the host when the
    trie carries an ``item_rank`` table and packed into one int32
    ``[Q, P]`` matrix (a matrix input keeps its width ``P``), which
    ``_descend_ranges`` walks root-down in one jitted program — the CSR
    ``child_lookup`` on the plain layout, the span-aware
    ``compressed_descend`` on the compressed one — all queries at once.
    Absent prefixes (invalid item ids included) resolve to the empty
    range ``[0, 0)``; empty prefixes to the whole trie ``[0, N)``.

    In an already-padded ``[Q, P]`` MATRIX, ``-1`` entries are padding
    (the repo-wide query-matrix convention) and are dropped per row; in
    ragged sequences every element is a literal item, so a negative id
    there reads as "not in the trie" (empty range), exactly like any
    other absent item.  (Normalization itself lives in
    ``array_trie.canonical_prefix_rows``, shared with the host descent
    the sharded engine resolves prefixes through.)

    Returns ``(los int32[Q], his int32[Q], nodes int32[Q])``.
    """
    with _phase("top_k", "prefix_ranges"):
        rows = canonical_prefix_rows(
            prefixes, getattr(trie, "item_rank", None)
        )
        width = max((len(r) for r in rows), default=0)
        if isinstance(prefixes, np.ndarray) and prefixes.ndim == 2:
            # a matrix keeps its own width, so one launch shape is one
            # program however long its prefixes happen to be
            width = max(width, prefixes.shape[1])
        mat = np.full((len(rows), max(width, 1)), -1, np.int32)
        for i, r in enumerate(rows):
            mat[i, : len(r)] = r
        if dt is None:
            dt = _cached_device_trie(trie)
        return _descend_ranges(dt, mat)


@jax.jit
def _descend_ranges(dt: DeviceTrie, mat: jax.Array):
    """``prefix_ranges``' descent: one program per ``(Q, P, layout)``.

    ``mat`` is the canonical int32 ``[Q, P]`` prefix matrix.  Only ``-1``
    is padding; any other negative id is a live item that no edge
    carries, so its row resolves to the empty range.  A ``lax.scan``
    folds the columns root-down (``compressed_descend`` on the compressed
    layout), then the subtree gathers close each row's range (``his``
    clamped to ``N``, ``nodes`` -1 where absent).
    """
    if dt.layout == "compressed":
        pos, ok = compressed_descend(dt, mat)
        # Positions ARE DFS indices on this layout, so the subtree range
        # is [pos, pos + subtree_size[pos]) with no dfs_order gather.
        n = dt.subtree_size.shape[0]
        los = jnp.where(ok, pos, 0).astype(jnp.int32)
        his = jnp.where(ok, pos + dt.subtree_size[pos], 0).astype(jnp.int32)
        return los, jnp.minimum(his, n), jnp.where(
            ok, dt.dfs_to_node[pos], -1
        )

    def step(nodes, col):
        return jnp.where(col != -1, child_lookup(dt, nodes, col), nodes), None

    nodes, _ = jax.lax.scan(step, jnp.zeros(mat.shape[:1], jnp.int32), mat.T)
    n = dt.dfs_order.shape[0]
    ok = nodes >= 0
    nid = jnp.maximum(nodes, 0)
    los = jnp.where(ok, dt.dfs_order[nid], 0).astype(jnp.int32)
    his = jnp.where(ok, los + dt.subtree_size[nid], 0).astype(jnp.int32)
    return los, jnp.minimum(his, n), jnp.where(ok, nodes, -1)


def top_k_rules_batch(
    trie,                                   # DeviceTrie / FrozenTrie
    prefixes,                               # Q antecedent prefixes
    k: int,
    metric: str = "confidence",
    min_depth: int = 1,
    arrays: Optional[Dict[str, jax.Array]] = None,
    use_kernel: bool = True,
    strict: bool = False,
) -> Dict[str, jax.Array]:
    """Top-k rules under EACH of Q antecedent prefixes, one launch total.

    The batched form of ``top_k_rules``: the Q prefixes resolve to Q
    DFS-contiguous ``[lo, hi)`` subtree ranges (``prefix_ranges``) that
    one ``topk_rank_batch_pallas`` call ranks simultaneously — replacing
    Q separate kernel launches.  Row-for-row bit-identical to looping
    ``top_k_rules`` (tie order included).

    Returns ``{"values" f32[Q, k], "node" int32[Q, k],
    "dfs_pos" int32[Q, k]}``.

    ``trie`` may also be a ``distributed.trie_sharding.ShardPlan`` — the
    Q rankings then run shard_map-distributed (host-side prefix descent,
    per-device range-clipped kernels, k-best all-gather + rank-merge),
    bit-identical to this single-device form.
    """
    if metric not in RANK_METRICS:
        raise InvalidQueryError(f"metric {metric!r} not in {RANK_METRICS}")
    _validate_k(k, "top_k_rules_batch")
    plan = _as_shard_plan(trie)
    item_rank = getattr(
        plan.frozen if plan is not None else trie, "item_rank", None
    )
    with _phase("top_k", "prep"):
        if not isinstance(prefixes, np.ndarray):
            # normalize once (generators included)
            prefixes = list(prefixes)
        validate_prefixes(
            prefixes, "top_k_rules_batch", item_rank=item_rank,
            strict=strict,
        )
    stream = _as_streaming(trie)
    if stream is not None:
        if arrays is not None or not use_kernel:
            raise ValueError(
                "streaming top_k_rules_batch supports neither arrays= "
                "(the stream owns its epoch-versioned residency) nor "
                "use_kernel=False (the jnp oracle takes no delta)"
            )
        from .streaming import streaming_top_k_rules_batch

        return streaming_top_k_rules_batch(
            stream, prefixes, k, metric=metric, min_depth=min_depth
        )
    if plan is not None:
        if arrays is not None or not use_kernel:
            raise ValueError(
                "sharded top_k_rules_batch supports neither arrays= (the "
                "plan already owns its device residency) nor "
                "use_kernel=False (the jnp oracle is single-device only)"
            )
        from repro.distributed.trie_sharding import (
            sharded_top_k_rules_batch,
        )

        with _phase("top_k", "kernel", rows=len(prefixes)):
            return sharded_top_k_rules_batch(
                plan, prefixes, k, metric=metric, min_depth=min_depth,
            )
    if arrays is None:
        with _phase("top_k", "prep"):
            arrays = dfs_rank_arrays(trie)
    if len(prefixes) == 0:
        return {
            "values": jnp.zeros((0, max(int(k), 0)), jnp.float32),
            "node": jnp.zeros((0, max(int(k), 0)), jnp.int32),
            "dfs_pos": jnp.zeros((0, max(int(k), 0)), jnp.int32),
        }
    los, his, _nodes = prefix_ranges(
        trie, prefixes, dt=_cached_device_trie(trie, arrays)
    )
    rank_fn = (
        functools.partial(topk_rank_batch_pallas, interpret=_interpret())
        if use_kernel else topk_rank_batch_ref
    )
    with _phase("top_k", "kernel", rows=len(prefixes)):
        vals, pos = rank_fn(
            arrays["support"], arrays["confidence"], arrays["lift"],
            arrays["depth"], los, his,
            k=int(k), metric=metric, min_depth=int(min_depth),
            **_dequant_statics(arrays),
        )
    with _phase("top_k", "expand"):
        node_ids = _node_ids(pos, arrays["dfs_to_node"])
    return {"values": vals, "node": node_ids, "dfs_pos": pos}


def rule_search_batch(
    trie,                                   # DeviceTrie / FrozenTrie
    queries,                                # (A, C) pairs or [Q, L] rows
    ant_len=None,                           # int32 [Q] with array queries
    edges: Optional[Dict[str, jax.Array]] = None,
    strict: bool = False,
) -> Dict[str, jax.Array]:
    """Search Q rules in ONE descent.

    The serving-side batched entry: ``queries`` is either a sequence of
    ``(antecedent, consequent)`` item-sequence pairs — canonicalized and
    packed host-side via ``FrozenTrie.canonicalize_queries`` — or an
    already-canonical padded ``[Q, L]`` row matrix with ``ant_len``.
    Either way the whole batch dedups to its UNIQUE canonical rows
    host-side (``dedup_query_rows`` — skewed serving traffic otherwise
    re-descends duplicates), descends in one jitted program (the CSR
    bucket descent), and scatters results back to the original Q rows.
    Bit-identical per row to looping ``rule_search`` over the queries.

    ``trie`` may also be a ``distributed.trie_sharding.ShardPlan`` — the
    batch then descends shard_map-distributed (each device's descent
    over its local subforest, found-winner merge + global compound-lift
    re-assembly), bit-identical to this single-device form.

    Or a ``core.delta_trie.StreamingTrie`` — frozen kernel + host
    recompute of rows touching modified rules (``kernels.streaming``),
    bit-identical to a from-scratch rebuild of frozen+delta.
    """
    stream = _as_streaming(trie)
    if stream is not None:
        if edges is not None:
            raise ValueError(
                "streaming rule_search_batch ignores precomputed edges= "
                "— the stream owns its (epoch-versioned) base residency; "
                "drop the argument"
            )
        from .streaming import streaming_rule_search_batch

        return streaming_rule_search_batch(
            stream, queries, ant_len, strict=strict
        )
    plan = _as_shard_plan(trie)
    if ant_len is None and not isinstance(queries, np.ndarray):
        with _phase("rule_search", "prep"):
            queries = list(queries)
            validate_rule_pairs(
                queries, "rule_search_batch",
                item_rank=getattr(
                    plan.frozen if plan is not None else trie,
                    "item_rank", None,
                ),
                strict=strict,
            )
    if plan is not None:
        if edges is not None:
            raise ValueError(
                "sharded rule_search_batch ignores precomputed edges= — "
                "the plan already owns its (relabeled, sharded) edge "
                "residency; drop the argument"
            )
        from repro.distributed.trie_sharding import (
            sharded_rule_search_batch,
        )

        n_q = (queries.shape[0] if isinstance(queries, np.ndarray)
               else len(queries))
        with _phase("rule_search", "kernel", rows=n_q):
            return sharded_rule_search_batch(plan, queries, ant_len)
    if ant_len is None:
        canonicalize = getattr(trie, "canonicalize_queries", None)
        if canonicalize is None:
            raise ValueError(
                "ragged (antecedent, consequent) queries need a FrozenTrie "
                "(canonicalize_queries lives host-side); for a DeviceTrie "
                "pass an already-canonical [Q, L] matrix plus ant_len"
            )
        pairs = list(queries)
        if not pairs:
            return rule_search(
                trie, np.zeros((0, 1), np.int32), np.zeros((0,), np.int32),
                edges=edges,
            )
    with _phase("rule_search", "prep"):
        if ant_len is None:
            queries, ant_len = canonicalize(
                [p[0] for p in pairs], [p[1] for p in pairs]
            )
        uq, ual, inv = dedup_query_rows(queries, ant_len)
        inv_j = None if inv is None else jnp.asarray(inv)
    out = rule_search(trie, uq, ual, edges=edges)
    if inv_j is None:
        return out
    with _phase("rule_search", "expand"):
        return _gather_rows(out, inv_j)


# ----------------------------------------------------------------------
# traversal reduction
# ----------------------------------------------------------------------
def trie_reduce(trie) -> Dict[str, jax.Array]:
    dq = _dequant_statics(trie)
    n_nodes = int(trie.support.shape[0])
    with _phase("trie_reduce", "kernel", rows=n_nodes):
        n, sup_sum, conf_max, conf_sum = trie_reduce_pallas(
            jnp.asarray(trie.support),
            jnp.asarray(trie.confidence),
            jnp.asarray(trie.node_depth),
            interpret=_interpret(),
            n_transactions=dq["n_transactions"],
            confidence_scale=dq["confidence_scale"],
        )
    return {
        "n_rules": n,
        "support_sum": sup_sum,
        "confidence_max": conf_max,
        "mean_conf": conf_sum / jnp.maximum(n, 1.0),
    }
