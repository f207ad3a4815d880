"""Device programs for the Trie-of-Rules hot spots (Pallas kernels where
a kernel pays, XLA where the op is a few gathers per step).

- ``support_count``      mining Step 1: MXU matmul support counting
- ``rule_search``        paper Fig. 8-10: batched CSR bucket trie descent
                         (an XLA gather loop; the tables stay in HBM)
- ``rule_search_batch``  Q ragged rules canonicalized + searched in ONE
                         descent (the serving-side batched entry)
- ``trie_reduce``        paper traversal: masked column reductions
- ``top_k_rules``        segmented ranked extraction over the
                         DFS-contiguous layout (whole-trie or
                         antecedent-prefix subtree), scoring with any
                         ``RANK_METRICS`` measure in-kernel
- ``top_k_rules_batch``  Q prefix-scoped rankings in ONE launch
- ``rules_with``         item-scoped ranked extraction via the
                         item-inverted index (consequent / antecedent /
                         any role), Q items in ONE launch

The shared Eq. 1-4 / interestingness math lives in ``metrics_inkernel`` —
one implementation for every kernel AND its jnp oracle (``ref``).

Static launch knobs (tile sizes, the launch-pad floor) resolve at op-dispatch time from the per-backend
``tuning.KernelConfig`` registry — committed tables under
``benchmarks/tuning/`` (regenerate with ``make autotune``), historical
constants when no table exists.

The three batched ops are shard_map-aware: handed a
``repro.distributed.trie_sharding.ShardPlan`` instead of a trie, each
runs distributed over the plan's ``("data",)`` mesh (per-device kernels
over local DFS ranges + bit-identical k-best / found-winner merges).
"""
from .item_index import ROLES
from .metrics_inkernel import RANK_METRICS
from .tuning import (
    KernelConfig,
    get_kernel_config,
    launch_pad,
    set_kernel_config,
    tuning_overrides,
)
from .ops import (
    InvalidQueryError,
    TransientBackendError,
    TrieQueryError,
    dedup_query_rows,
    dense_from_bitmaps,
    dfs_rank_arrays,
    edge_metric_arrays,
    interpret_mode,
    is_retryable,
    item_rank_arrays,
    members_from_candidates,
    prefix_ranges,
    rule_search,
    rule_search_batch,
    rules_with,
    support_count,
    top_k_rules,
    top_k_rules_batch,
    trie_reduce,
)

__all__ = [
    "RANK_METRICS",
    "ROLES",
    "InvalidQueryError",
    "KernelConfig",
    "TransientBackendError",
    "TrieQueryError",
    "dedup_query_rows",
    "get_kernel_config",
    "interpret_mode",
    "is_retryable",
    "launch_pad",
    "set_kernel_config",
    "tuning_overrides",
    "dense_from_bitmaps",
    "dfs_rank_arrays",
    "edge_metric_arrays",
    "item_rank_arrays",
    "members_from_candidates",
    "prefix_ranges",
    "rule_search",
    "rule_search_batch",
    "rules_with",
    "support_count",
    "top_k_rules",
    "top_k_rules_batch",
    "trie_reduce",
]
