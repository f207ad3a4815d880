"""Batched item-scoped top-k over the inverted index.

The paper frames the trie as a *knowledge extraction* structure; the
analyst queries it answers are rarely one rule at a time — they are "every
rule with consequent *c*", "every rule involving item *i*", ranked.  The
item-inverted index (``array_trie.item_index_arrays``) makes those
answerable without walking paths:

* posting list ``item_nodes[item_offsets[i]:item_offsets[i+1]]`` = every
  node (= rule) whose CONSEQUENT is ``i``, in DFS position order;
* a node's ANTECEDENT contains ``i`` iff some strict ancestor carries
  ``i`` — i.e. iff the node's DFS position falls inside a posting entry's
  subtree range.  Subtree ranges of one item's postings form a laminar
  family (nested or disjoint), so "how many ranges contain position p" is

      |{u : subtree_lo[u] <= p}| - |{u : subtree_hi[u] <= p}|

  — the prefix sum of a difference array that has +1 at every posting
  subtree start and -1 at every end.  No per-node root-path walk, ever.

``rules_with_pallas`` runs Q item queries in ONE ranking launch.  The
membership test is plain XLA (``item_membership``): each query's posting
slice is gathered into a ``max_postings``-wide window, scattered into a
``[Q, N + 1]`` difference array and prefix-summed — the posting arrays
stay in HBM at any trie size, and no kernel gathers from a 1-D vector
(which the TPU's Pallas lowering refuses).  The ``[Q, N]`` membership
mask then rides into the segmented rank kernel
(``rank.topk_rank_batch_pallas``) as a per-query node filter, so the
ranking, tie order and k-best merge are exactly the prefix top-k's.

The consequent-only role needs no range counting (membership is just
``node_item == item``); ``kernels.ops.rules_with`` routes it through the
posting-ordered columns + ``rank.topk_rank_batch_pallas`` instead (a
contiguous posting-range scan) on the plain layout.  Both paths return
identical node order for overlapping queries (postings are DFS-sorted),
which the tests assert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .rank import topk_rank_batch_pallas

ROLES = ("consequent", "antecedent", "any")


@functools.partial(jax.jit, static_argnames=("role", "max_postings"))
def item_membership(
    node_item: jax.Array,   # int32 [N] DFS-ordered consequent items
    post_lo: jax.Array,     # int32 [E] posting subtree starts (asc/item)
    post_hi: jax.Array,     # int32 [E] posting subtree ends (sorted/item)
    plos: jax.Array,        # int32 [Q] posting-slice start per query
    phis: jax.Array,        # int32 [Q] posting-slice end per query
    items: jax.Array,       # int32 [Q] queried item per query
    *,
    role: str,
    max_postings: int,
) -> jax.Array:
    """bool ``[Q, N]``: does DFS position ``p`` hold a rule involving
    query ``q``'s item in ``role``?

    ``max_postings`` (static) MUST bound every queried slice length
    (``item_rank_arrays`` emits it); a shorter window truncates the
    slice.  Positions are counted with a difference array per query —
    +1 at each posting subtree start, -1 at each end, prefix-summed —
    so the laminar range count is exact integer arithmetic."""
    n = node_item.shape[0]
    q = plos.shape[0]
    items = jnp.asarray(items, jnp.int32)
    self_hit = node_item[None, :] == items[:, None]
    if role == "consequent":
        return self_hit
    e = post_lo.shape[0]
    if e == 0:
        cnt = jnp.zeros((q, n), jnp.int32)
    else:
        w = max(int(max_postings), 1)
        widx = jnp.asarray(plos, jnp.int32)[:, None] + jnp.arange(
            w, dtype=jnp.int32
        )[None, :]
        live = widx < jnp.asarray(phis, jnp.int32)[:, None]
        safe = jnp.clip(widx, 0, e - 1)
        # out-of-slice lanes scatter to column N + 1: past the end, dropped
        lo = jnp.where(live, post_lo[safe], n + 1)
        hi = jnp.where(live, post_hi[safe], n + 1)
        rows = jnp.broadcast_to(
            jnp.arange(q, dtype=jnp.int32)[:, None], (q, w)
        )
        diff = jnp.zeros((q, n + 1), jnp.int32)
        diff = diff.at[rows, lo].add(1, mode="drop")
        diff = diff.at[rows, hi].add(-1, mode="drop")
        cnt = jnp.cumsum(diff[:, :n], axis=1)
    if role == "antecedent":
        # strict ancestors only: the node's own posting entry always
        # contains its own position — subtract it back out
        return (cnt - self_hit.astype(jnp.int32)) > 0
    return cnt > 0          # "any": consequent or anywhere on the path


def rules_with_pallas(
    support: jax.Array,     # f32 [N] DFS-ordered
    confidence: jax.Array,  # f32 [N] DFS-ordered
    lift: jax.Array,        # f32 [N] DFS-ordered
    depth: jax.Array,       # int32 [N] DFS-ordered
    node_item: jax.Array,   # int32 [N] DFS-ordered consequent items
    post_lo: jax.Array,     # int32 [E] posting subtree starts (asc/item)
    post_hi: jax.Array,     # int32 [E] posting subtree ends (sorted/item)
    plos: jax.Array,        # int32 [Q] posting-slice start per query
    phis: jax.Array,        # int32 [Q] posting-slice end per query
    items: jax.Array,       # int32 [Q] queried item per query
    *,
    k: int,
    metric: str = "confidence",
    min_depth: int = 1,
    role: str = "any",
    max_postings: int = 0,
    interpret: bool = False,
    block_n: int | None = None,
    n_transactions: int = 0,
    confidence_scale: float = 1.0,
    lift_scale: float = 1.0,
):
    """Top-k (scores, DFS positions) of the rules involving each queried
    item, for Q queries in ONE launch.

    ``role`` decides membership: ``"consequent"`` (node item equals the
    query item), ``"antecedent"`` (a strict ancestor carries it), or
    ``"any"``.  Rows follow ``jax.lax.top_k`` order with ``(-inf, -1)``
    empty slots.  Absent items are expressed as empty posting slices
    (``plos[q] == phis[q]``) plus an item id no node carries.
    ``max_postings`` must bound every queried slice (see
    ``item_membership``); ``block_n`` is the rank kernel's tile.
    """
    if role not in ROLES:
        raise ValueError(f"role {role!r} not in {ROLES}")
    n = support.shape[0]
    q = plos.shape[0]
    member = item_membership(
        node_item, post_lo, post_hi, plos, phis, items,
        role=role, max_postings=int(max_postings),
    )
    return topk_rank_batch_pallas(
        support, confidence, lift, depth,
        jnp.zeros((q,), jnp.int32), jnp.full((q,), n, jnp.int32),
        k=k, metric=metric, min_depth=min_depth, interpret=interpret,
        block_n=block_n, n_transactions=n_transactions,
        confidence_scale=confidence_scale, lift_scale=lift_scale,
        member=member,
    )
