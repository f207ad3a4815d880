"""Pallas TPU kernel: segmented top-k rule ranking over the DFS layout.

The paper positions sorting as "the base for many knowledge discovery
methods"; this kernel is the ranked-extraction counterpart of the fused
rule search.  It streams the DFS-ordered node metric columns through VMEM
in ``BN``-node tiles and maintains a k-best (value, DFS position) buffer
across grid steps:

    per tile i:  score[t]  = rank_score(metric, sup[t], conf[t], lift[t])
                 score[t] := -inf outside [lo, hi) or below min_depth
                 c         = |{t : score[t] > current kth-best}|
                 if c > 0:  extract the tile's top-min(c, k) by iterative
                            max+mask (c is SMALL once the buffer warms up),
                            then rank-merge the two sorted k-lists with one
                            (kpad x kpad) comparison matrix

Because the trie is DFS-contiguous (``array_trie.dfs_layout``), an
antecedent-prefix subtree is exactly one ``[lo, hi)`` position range, so a
prefix-scoped ranked query masks (and mostly *skips* — the ``c > 0`` guard
fails for every tile outside the range) instead of gathering.  The full
ranking is the ``[0, N)`` range of the same kernel.

The kernel is natively BATCHED (``topk_rank_batch_pallas``): the grid is
``(Q, n_tiles)`` with one k-best buffer row per query, so Q segmented
rankings — Q analyst prefixes, or Q posting-list ranges from the
item-inverted index — cost ONE launch instead of Q.  The single-range
``topk_rank_pallas`` is its Q=1 slice.

The in-kernel score math lives in ``metrics_inkernel.rank_score`` — the ONE
implementation shared with the jnp oracle (``ref.topk_rank_ref``), keeping
kernel and oracle bit-identical per element.  Tie-breaking replicates
``jax.lax.top_k``: equal values rank by ascending position (the iterative
extraction takes the min position among maxima; merged lists are ordered by
(value desc, position asc)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .metrics_inkernel import dequantize_metrics, metric_pad_dtype, rank_score
from .tuning import get_kernel_config

BN = 8192    # default nodes per tile (tunable: KernelConfig.rank_bn)
LANE = 128   # lane width: k-buffer padding granularity
_BIG = 2**30  # plain int: pallas kernels may not close over jnp constants


def _iota(n: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (n,), 0)


def rank_merge(av, ap, tv, tp, kpad: int):
    """Merge two internally-sorted (value desc, pos asc) kpad-lists into
    the top-kpad of their union via rank scatter (one comparison matrix
    each way; ranks over the union are a permutation, so every output slot
    is hit by exactly one element).

    Pure ``jnp`` with no refs, so it runs both inside a Pallas kernel body
    (``kbest_update``) and as a plain array op — the sharded query engine
    (``repro.distributed.trie_sharding``) folds per-device k-best lists
    through it after the all-gather, which is what keeps the multi-device
    merge bit-identical (tie order included) to the single-device kernels.
    Live positions must be distinct between the two lists."""
    lane = _iota(kpad)
    # -inf padding entries get unique, largest tie keys so the order stays
    # strictly total (live positions are distinct by construction: the
    # buffer holds earlier tiles' positions, the tile batch later ones).
    apk = jnp.where(av > -jnp.inf, ap, _BIG + lane)
    tpk = jnp.where(tv > -jnp.inf, tp, _BIG + kpad + lane)

    def precedes(v1, p1, v2, p2):
        return (v1 > v2) | ((v1 == v2) & (p1 < p2))

    rank_a = lane + jnp.sum(
        precedes(tv[:, None], tpk[:, None], av[None, :], apk[None, :])
        .astype(jnp.int32), axis=0,
    )
    rank_t = lane + jnp.sum(
        precedes(av[:, None], apk[:, None], tv[None, :], tpk[None, :])
        .astype(jnp.int32), axis=0,
    )
    hit_a = lane[:, None] == rank_a[None, :]
    hit_t = lane[:, None] == rank_t[None, :]
    nv = jnp.maximum(
        jnp.max(jnp.where(hit_a, av[None, :], -jnp.inf), axis=1),
        jnp.max(jnp.where(hit_t, tv[None, :], -jnp.inf), axis=1),
    )
    np_ = jnp.maximum(
        jnp.max(jnp.where(hit_a, ap[None, :], -1), axis=1),
        jnp.max(jnp.where(hit_t, tp[None, :], -1), axis=1),
    )
    return nv, jnp.where(nv > -jnp.inf, np_, -1)


def kbest_update(vals_ref, pos_ref, score, pos, k: int, kpad: int):
    """Fold one tile's masked scores into the (value, position) k-best
    buffer refs — the incremental-extraction + rank-merge step shared by
    every segmented ranking kernel (this module and
    ``kernels.item_index``).

    Strictly-greater entry test: an equal-valued tile entry has a larger
    position than every buffered entry, so it loses the tie and can never
    displace — tiles that cannot improve the buffer (incl. every tile
    fully outside the query's range) skip the merge.
    """
    kth = vals_ref[0, k - 1]
    c = jnp.sum((score > kth).astype(jnp.int32))

    @pl.when(c > 0)
    def _merge():
        lane = _iota(kpad)
        cc = jnp.minimum(c, k)

        def body(state):
            j, cand, tv, tp = state
            m = jnp.max(cand)
            sel = jnp.min(jnp.where(cand == m, pos, _BIG))
            tv = jnp.where(lane == j, m, tv)
            tp = jnp.where(lane == j, sel, tp)
            cand = jnp.where(pos == sel, -jnp.inf, cand)
            return j + 1, cand, tv, tp

        _, _, tv, tp = jax.lax.while_loop(
            lambda s: s[0] < cc,
            body,
            (
                jnp.int32(0),
                score,
                jnp.full((kpad,), -jnp.inf, jnp.float32),
                jnp.full((kpad,), -1, jnp.int32),
            ),
        )
        nv, np_ = rank_merge(
            vals_ref[...][0], pos_ref[...][0], tv, tp, kpad
        )
        vals_ref[...] = nv[None, :]
        pos_ref[...] = np_[None, :]


def _make_kernel(k: int, kpad: int, metric: str, min_depth: int,
                 block_n: int, n_transactions: int,
                 confidence_scale: float, lift_scale: float,
                 masked: bool):
    def kernel(params_ref, sup_ref, conf_ref, lift_ref, depth_ref, *refs):
        # grid = (Q, n_tiles): queries outer, DFS tiles inner, so each
        # query's k-best buffer accumulates across its own tile sweep.
        # Per-query refs (params, member, outputs) arrive as [1, lanes]:
        # the query axis is squeezed out of their blocks.
        member_ref, vals_ref, pos_ref = refs if masked else (None, *refs)
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            vals_ref[...] = jnp.full_like(vals_ref[...], -jnp.inf)
            pos_ref[...] = jnp.full_like(pos_ref[...], -1)

        lo = params_ref[0, 0]
        hi = params_ref[0, 1]
        # Quantized columns (compressed layout) ride their narrow storage
        # dtype through HBM->VMEM and widen here, per tile.
        sup, conf, lift = dequantize_metrics(
            sup_ref[...][0], conf_ref[...][0], lift_ref[...][0],
            n_transactions, confidence_scale, lift_scale,
        )
        depth = depth_ref[...][0]
        pos = _iota(block_n) + i * block_n
        score = rank_score(metric, sup, conf, lift)
        valid = (pos >= lo) & (pos < hi) & (depth >= min_depth)
        if masked:
            valid = valid & (member_ref[...][0] != 0)
        score = jnp.where(valid, score, -jnp.inf)
        kbest_update(vals_ref, pos_ref, score, pos, k, kpad)

    return kernel


def topk_rank_batch_pallas(
    support: jax.Array,     # f32 [N] DFS-ordered
    confidence: jax.Array,  # f32 [N] DFS-ordered
    lift: jax.Array,        # f32 [N] DFS-ordered
    depth: jax.Array,       # int32 [N] DFS-ordered
    los: jax.Array,         # int32 [Q]: DFS range starts (inclusive)
    his: jax.Array,         # int32 [Q]: DFS range ends (exclusive)
    *,
    k: int,
    metric: str = "confidence",
    min_depth: int = 1,
    interpret: bool = False,
    block_n: int | None = None,
    n_transactions: int = 0,
    confidence_scale: float = 1.0,
    lift_scale: float = 1.0,
    member: jax.Array | None = None,   # [Q, N] per-query node filter
):
    """Top-k of EVERY DFS range ``[los[q], his[q])`` in one launch.

    The batched form of the segmented ranking: one grid dimension over
    queries (each with its own k-best buffer row), one over DFS tiles —
    Q prefix-scoped rankings cost one ``pallas_call`` instead of Q.
    Returns ``(values f32[Q, k], positions int32[Q, k])``, each row in
    ``jax.lax.top_k`` order with ``(-inf, -1)`` empty slots.

    Quantized metric columns (compressed layout: int32 support counts,
    bf16/int8 confidence/lift) stay narrow through VMEM and widen
    in-kernel via the static dequant params, which default to the fp32
    no-op.

    ``member`` (optional, nonzero = eligible) further restricts each
    query to a node subset — how ``item_index.rules_with_pallas`` ranks
    its item-membership sets with this same kernel.

    ``block_n`` (nodes per tile) resolves from the active per-backend
    ``KernelConfig`` when None — resolution happens in this thin
    un-jitted shim so a table change is never baked into a stale trace.
    """
    if block_n is None:
        block_n = get_kernel_config().rank_bn
    return _topk_rank_batch_impl(
        support, confidence, lift, depth, los, his, member,
        k=k, metric=metric, min_depth=min_depth, interpret=interpret,
        block_n=int(block_n),
        n_transactions=int(n_transactions),
        confidence_scale=float(confidence_scale),
        lift_scale=float(lift_scale),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "metric", "min_depth", "interpret", "block_n",
        "n_transactions", "confidence_scale", "lift_scale",
    ),
)
def _topk_rank_batch_impl(
    support, confidence, lift, depth, los, his, member=None,
    *, k, metric, min_depth, interpret, block_n,
    n_transactions, confidence_scale, lift_scale,
):
    n = support.shape[0]
    q = los.shape[0]
    if n == 0 or k <= 0 or q == 0:
        # Nothing to rank: avoid tracing a zero-grid kernel.
        return (
            jnp.full((q, max(k, 0)), -jnp.inf, jnp.float32),
            jnp.full((q, max(k, 0)), -1, jnp.int32),
        )
    kpad = k + (-k % LANE)
    npad = -n % block_n

    def pad(a, fill, dtype):
        return jnp.pad(
            a.astype(dtype), (0, npad), constant_values=fill
        ).reshape(1, -1)

    sup = pad(support, 0, metric_pad_dtype(support))
    conf = pad(confidence, 0, metric_pad_dtype(confidence))
    lif = pad(lift, 0, metric_pad_dtype(lift))
    dep = pad(depth, -1, jnp.int32)
    # Clamping hi to N keeps every padding lane outside [lo, hi).
    los = jnp.maximum(jnp.asarray(los, jnp.int32), 0)
    his = jnp.minimum(jnp.asarray(his, jnp.int32), n)
    # Per-query operands are [Q, 1, lanes] with the query axis squeezed
    # out of each block: a (1, lanes) block of a [Q, lanes] array would
    # break the TPU's (8, 128) block-tiling rule for any Q > 1.
    params = jnp.zeros((q, 1, LANE), jnp.int32)
    params = params.at[:, 0, 0].set(los).at[:, 0, 1].set(his)

    nn = sup.shape[1]
    grid = (q, nn // block_n)
    col_spec = pl.BlockSpec((1, block_n), lambda qi, i: (0, i))
    row_spec = pl.BlockSpec(
        (pl.Squeezed(), 1, LANE), lambda qi, i: (qi, 0, 0)
    )
    out_spec = pl.BlockSpec(
        (pl.Squeezed(), 1, kpad), lambda qi, i: (qi, 0, 0)
    )
    in_specs = [row_spec, col_spec, col_spec, col_spec, col_spec]
    operands = [params, sup, conf, lif, dep]
    if member is not None:
        in_specs.append(pl.BlockSpec(
            (pl.Squeezed(), 1, block_n), lambda qi, i: (qi, 0, i)
        ))
        operands.append(jnp.pad(
            member.astype(jnp.int32), ((0, 0), (0, npad))
        ).reshape(q, 1, -1))
    vals, pos = pl.pallas_call(
        _make_kernel(
            k, kpad, metric, min_depth, block_n,
            n_transactions, confidence_scale, lift_scale,
            member is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((q, 1, kpad), jnp.float32),
            jax.ShapeDtypeStruct((q, 1, kpad), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return vals[:, 0, :k], pos[:, 0, :k]


def topk_rank_pallas(
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
    interpret: bool = False,
    block_n: int | None = None,
    n_transactions: int = 0,
    confidence_scale: float = 1.0,
    lift_scale: float = 1.0,
):
    """Top-k (scores, DFS positions) of the rules in DFS range ``[lo, hi)``.

    The Q=1 slice of ``topk_rank_batch_pallas`` (same kernel, same tie
    order).  Returns ``(values f32[k], positions int32[k])`` sorted by
    (value desc, position asc) — ``jax.lax.top_k`` order — with empty
    slots (k exceeds the live-rule count) as ``(-inf, -1)``.
    """
    vals, pos = topk_rank_batch_pallas(
        support, confidence, lift, depth,
        jnp.asarray(lo, jnp.int32).reshape(1),
        jnp.asarray(hi, jnp.int32).reshape(1),
        k=k, metric=metric, min_depth=min_depth, interpret=interpret,
        block_n=block_n, n_transactions=n_transactions,
        confidence_scale=confidence_scale, lift_scale=lift_scale,
    )
    return vals[0], pos[0]
