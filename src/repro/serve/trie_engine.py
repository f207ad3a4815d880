"""Trie query serving: one front door over the replicated and sharded
engines.

``TrieQueryEngine`` owns a frozen trie's device residency and routes the
three batched query ops (``rule_search_batch`` / ``top_k_rules_batch`` /
``rules_with``) to one of two bit-identical backends:

* ``"replicated"`` — the whole trie on one device (a ``DeviceTrie`` plus
  the memoized gather dicts), queries run as single-device one-launch
  kernels.  Right for small tries and single-device hosts: no collective
  latency, no partitioning work.
* ``"sharded"`` — the trie partitioned into contiguous DFS subtree
  ranges across the ``("data",)`` mesh
  (``distributed.trie_sharding.shard_device_trie``), queries run under
  ``shard_map`` with k-best/found-winner merges.  Right when the trie
  outgrows one device's memory or its tile sweep dominates latency —
  each device scans ``~N/P`` nodes per ranked query.

``layout`` picks the frozen encoding both backends hold: ``"plain"``,
``"compressed"`` (path-compressed spans) or ``"auto"`` (compressed when
most edges sit on single-child chains — ``FrozenTrie.device_arrays``).

``mode="auto"`` picks sharded exactly when there is more than one device
to shard over AND the trie clears ``shard_threshold_nodes`` (default
64Ki nodes — below that the per-launch tile sweep is a handful of tiles
and the all-gather merge would dominate).  Both backends answer through
the SAME ``kernels.ops`` entry points and are bit-identical (tie order
included), so routing is purely a performance decision.

The engine also fronts a ``core.delta_trie.StreamingTrie`` — a frozen
base plus a mutable delta overlay.  Queries then run through
``kernels.streaming`` (frozen+delta k-best merges, bit-identical to a
from-scratch rebuild), ``insert`` absorbs new/updated rules, and
``maybe_refreeze`` runs the staggered fold.  ``epoch`` exposes the
stream's trie-version counter (bumps on every insert and refreeze) so
callers — the scheduler's result cache above all — can tell whether a
cached answer predates the current trie contents.  ``frozen`` and
``plan`` are properties for this reason: a refreeze swaps the frozen
base, and the engine must never serve a query half over the old trie
and half over the new one.
"""
from __future__ import annotations

from contextlib import nullcontext
from typing import Dict, Optional, Sequence

import jax

from repro.core.array_trie import FrozenTrie
from repro.core.delta_trie import StreamingTrie
from repro.kernels import ops as trie_ops

DEFAULT_SHARD_THRESHOLD = 1 << 16   # nodes


class TrieQueryEngine:
    """Serving front door for one frozen Trie of Rules."""

    def __init__(
        self,
        frozen,                 # FrozenTrie | StreamingTrie
        mesh=None,
        mode: str = "auto",
        shard_threshold_nodes: int = DEFAULT_SHARD_THRESHOLD,
        plan=None,
        layout: str = "plain",
    ):
        if mode not in ("auto", "replicated", "sharded"):
            raise ValueError(
                f"mode {mode!r} not in ('auto', 'replicated', 'sharded')"
            )
        # set by the resilience layer / scheduler; when present, each op
        # runs under an ``engine.<op>`` span on the shared tracer
        self.obs = None
        self.stream = None
        self.layout = layout
        if isinstance(frozen, StreamingTrie):
            self.stream = frozen
            frozen = None
        self._frozen = frozen
        self._plan = None
        self._stream_sharded = False
        self._dt = None
        self._edges = None
        self._dfs_arrays = None
        self._item_arrays = None
        if plan is not None:
            # pre-built (possibly dead-shard-masked) ShardPlan injection:
            # the resilience layer's degraded engines hand their masked
            # plan straight in, skipping the (re)partitioning work.  With
            # a stream the injected plan overrides the stream's own —
            # delta merges keep running over the masked residency.
            self._plan = plan
            self.mesh = plan.mesh
            return
        if mode != "replicated" and mesh is None and jax.device_count() > 1:
            from repro.launch.mesh import make_trie_mesh

            mesh = make_trie_mesh()
        n_dev = int(mesh.shape["data"]) if mesh is not None else 1
        sharded = mode == "sharded" or (
            mode == "auto"
            and n_dev > 1
            and self.frozen.n_nodes >= shard_threshold_nodes
        )
        if self.stream is not None:
            if sharded:
                if self.stream.mesh is not None:
                    mesh = self.stream.mesh
                else:
                    if mesh is None:
                        from repro.launch.mesh import make_trie_mesh

                        mesh = make_trie_mesh()
                    # the engine owns residency: hand the stream its mesh
                    # before any plan is cached
                    self.stream.mesh = mesh
                self._stream_sharded = True
            self.mesh = mesh
            return
        if sharded:
            if mesh is None:
                from repro.launch.mesh import make_trie_mesh

                mesh = make_trie_mesh()
            from repro.distributed.trie_sharding import shard_device_trie

            self._plan = shard_device_trie(frozen, mesh, layout=layout)
        self.mesh = mesh

    # ------------------------------------------------------------------
    @property
    def frozen(self) -> FrozenTrie:
        """The current frozen base — re-read per call because a refreeze
        swaps it (the stream's epoch says which version answered)."""
        if self.stream is not None:
            return self.stream.frozen
        return self._frozen

    @property
    def plan(self):
        if self.stream is not None and self._plan is None:
            return (
                self.stream.shard_plan() if self._stream_sharded else None
            )
        return self._plan

    @property
    def epoch(self) -> int:
        """Trie-version counter (0 for a plain frozen engine): bumps on
        every insert and refreeze, so any result cache keyed on it can
        never return a pre-insert row for a post-insert trie."""
        return self.stream.epoch if self.stream is not None else 0

    @property
    def backend(self) -> str:
        return "sharded" if self.plan is not None else "replicated"

    @property
    def n_shards(self) -> int:
        return self.plan.n_shards if self.plan is not None else 1

    def _device_trie(self):
        if self._dt is None:
            self._dt = self.frozen.device_arrays(layout=self.layout)
        return self._dt

    def _span(self, name: str, **attrs):
        """Engine-level trace span (no-op context when obs is unset).
        Parents under the tracer's current scoped span — the scheduler's
        ``launch`` span when called from the serve loop."""
        if self.obs is None:
            return nullcontext()
        return self.obs.tracer.span(
            name, backend=self.backend, shards=self.n_shards, **attrs
        )

    def _stream_base(self):
        """Residency override handed to ``kernels.streaming``: an
        injected (dead-shard-masked) plan wins; a replicated engine over
        a mesh-bearing stream pins the frozen base instead of the
        stream's plan; ``None`` lets the stream route itself (queries
        then go through the validating ``kernels.ops`` dispatch)."""
        if self._plan is not None:
            return self._plan
        if self.stream.mesh is not None and not self._stream_sharded:
            return self.stream.frozen
        return None

    # ------------------------------------------------------------------
    # streaming mutation surface
    # ------------------------------------------------------------------
    def insert(self, sequences, support, confidence, lift) -> int:
        """Absorb inserted/updated rules into the delta overlay (bumps
        ``epoch``).  Requires a ``StreamingTrie``-backed engine."""
        if self.stream is None:
            raise TypeError(
                "insert requires a StreamingTrie-backed engine; build "
                "one with TrieQueryEngine(StreamingTrie(frozen), ...)"
            )
        return self.stream.insert(sequences, support, confidence, lift)

    def maybe_refreeze(self) -> Optional[int]:
        """Run one staggered fold step if the delta is over threshold;
        returns the folded depth-1 item (None when nothing folded).  The
        serve loop calls this between launches, so the frozen-base swap
        is atomic w.r.t. in-flight queries."""
        if self.stream is None:
            return None
        return self.stream.maybe_refreeze()

    # ------------------------------------------------------------------
    # the three batched ops (thin routing over kernels.ops)
    # ------------------------------------------------------------------
    def rule_search_batch(self, queries, ant_len=None) -> Dict:
        with self._span("engine.rule_search_batch", n=len(queries)):
            return self._rule_search_batch(queries, ant_len)

    def _rule_search_batch(self, queries, ant_len=None) -> Dict:
        if self.stream is not None:
            base = self._stream_base()
            if base is None:
                return trie_ops.rule_search_batch(
                    self.stream, queries, ant_len
                )
            from repro.kernels.streaming import streaming_rule_search_batch

            return streaming_rule_search_batch(
                self.stream, queries, ant_len, base=base
            )
        if self.plan is not None:
            return trie_ops.rule_search_batch(self.plan, queries, ant_len)
        if self._edges is None:
            self._edges = trie_ops.edge_metric_arrays(self._device_trie())
        # the FrozenTrie keeps ragged-pair canonicalization host-side
        return trie_ops.rule_search_batch(
            self.frozen, queries, ant_len, edges=self._edges
        )

    def top_k_rules_batch(
        self, prefixes, k: int, metric: str = "confidence",
        min_depth: int = 1,
    ) -> Dict:
        with self._span("engine.top_k_rules_batch", n=len(prefixes), k=k):
            return self._top_k_rules_batch(
                prefixes, k, metric=metric, min_depth=min_depth
            )

    def _top_k_rules_batch(
        self, prefixes, k: int, metric: str = "confidence",
        min_depth: int = 1,
    ) -> Dict:
        if self.stream is not None:
            base = self._stream_base()
            if base is None:
                return trie_ops.top_k_rules_batch(
                    self.stream, prefixes, k, metric=metric,
                    min_depth=min_depth,
                )
            from repro.kernels.streaming import streaming_top_k_rules_batch

            return streaming_top_k_rules_batch(
                self.stream, prefixes, k, metric=metric,
                min_depth=min_depth, base=base,
            )
        if self.plan is not None:
            return trie_ops.top_k_rules_batch(
                self.plan, prefixes, k, metric=metric, min_depth=min_depth
            )
        if self._dfs_arrays is None:
            self._dfs_arrays = trie_ops.dfs_rank_arrays(self._device_trie())
            self._dfs_arrays["_device_trie"] = self._device_trie()
        return trie_ops.top_k_rules_batch(
            self.frozen, prefixes, k, metric=metric, min_depth=min_depth,
            arrays=self._dfs_arrays,
        )

    def rules_with(
        self, items: Sequence[int], role: str = "any", k: int = 10,
        metric: str = "confidence", min_depth: int = 1,
    ) -> Dict:
        with self._span("engine.rules_with", n=len(items), k=k):
            return self._rules_with(
                items, role=role, k=k, metric=metric, min_depth=min_depth
            )

    def _rules_with(
        self, items: Sequence[int], role: str = "any", k: int = 10,
        metric: str = "confidence", min_depth: int = 1,
    ) -> Dict:
        if self.stream is not None:
            base = self._stream_base()
            if base is None:
                return trie_ops.rules_with(
                    self.stream, items, role=role, k=k, metric=metric,
                    min_depth=min_depth,
                )
            from repro.kernels.streaming import streaming_rules_with

            return streaming_rules_with(
                self.stream, items, role=role, k=k, metric=metric,
                min_depth=min_depth, base=base,
            )
        if self.plan is not None:
            return trie_ops.rules_with(
                self.plan, items, role=role, k=k, metric=metric,
                min_depth=min_depth,
            )
        if self._item_arrays is None:
            self._item_arrays = trie_ops.item_rank_arrays(
                self._device_trie()
            )
        return trie_ops.rules_with(
            self.frozen, items, role=role, k=k, metric=metric,
            min_depth=min_depth, arrays=self._item_arrays,
        )


def make_trie_engine(
    frozen,
    mesh=None,
    mode: str = "auto",
    shard_threshold_nodes: int = DEFAULT_SHARD_THRESHOLD,
    layout: str = "plain",
) -> TrieQueryEngine:
    """Factory alias (mirrors the ``make_*_step`` serving constructors).
    ``frozen`` may be a ``FrozenTrie`` or a ``StreamingTrie``."""
    return TrieQueryEngine(
        frozen, mesh=mesh, mode=mode,
        shard_threshold_nodes=shard_threshold_nodes, layout=layout,
    )
