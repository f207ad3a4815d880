#!/usr/bin/env python3
"""Drive the trie serve path once on TPU chips, at real sizes, and check
every answer against the repo's plain references.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # four chips: the sharded engine only

One chip runs the path users run — mine -> ``build_trie_of_rules(engine=
"arrays")`` -> ``FrozenTrie`` -> ``TrieQueryEngine`` -> ``TrieScheduler``:

  a  device  the first device is a TPU and the kernels compile there
  b  build   Online Retail (synthetic: 18,000 baskets over 3,600 items)
             mined at the paper's minsup 0.002; Step 3 counts supports
             with the ``support_count`` kernel; the trie must equal the
             host build bit for bit, its metrics to fp32 rounding
  c  serve   a few hundred mixed rule_search / top_k / rules_with
             requests through the scheduler, then an insert into a
             streaming engine and a query that sees it
  d  scale   a 2^20-node trie (plain layout) and a 2^20-node chain-heavy
             trie (compressed layout): the same ops plus ``trie_reduce``

``--chips 4`` runs only the sharded engine over a 4-device mesh on the
2^20-node trie and compares it, bit for bit, with a one-chip replicated
engine in the same process.

References are the ``repro.kernels.ref`` oracles and the host descent,
evaluated on the host CPU backend, independent of the device path.  Each
phase prints one line with its sizes, compile seconds and run seconds.
Any non-ok response, mismatch or exception exits non-zero; on success
the last line of stdout is the JSON device record.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.arm.datasets import online_retail_db  # noqa: E402
from repro.core.build_arrays import build_frozen_trie  # noqa: E402
from repro.core.builder import build_trie_of_rules  # noqa: E402
from repro.core.delta_trie import StreamingTrie  # noqa: E402
from repro.core.synthetic import (  # noqa: E402
    frozen_from_arrays,
    synthetic_chain_trie,
    synthetic_csr_trie,
)
from repro.distributed.trie_sharding import host_prefix_ranges  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_trie_mesh  # noqa: E402
from repro.serve import TrieQueryEngine, TrieScheduler  # noqa: E402

RETAIL_MINSUP = 0.002        # the paper's Online Retail operating point
SCALE_NODES = 1 << 20
K = 10
SEED = 0
STRUCTURE = (
    "node_item", "node_parent", "node_depth", "edge_parent", "edge_item",
    "edge_child", "child_offsets", "dfs_order", "subtree_size",
    "dfs_to_node", "item_offsets", "item_nodes",
)


class SmokeError(AssertionError):
    pass


def expect(cond, what: str) -> None:
    if not cond:
        raise SmokeError(what)


# ----------------------------------------------------------------------
# timing: wall time split into XLA backend compiles (JAX's own compile
# events) and the rest
# ----------------------------------------------------------------------
class CompileClock:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def timed(self, fn):
        """``(result, compile_s, run_s)`` of ``fn()``."""
        c0, t0 = self.seconds, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        comp = self.seconds - c0
        return out, comp, max(wall - comp, 0.0)


def report(phase: str, compile_s: float, run_s: float, **sizes) -> None:
    fields = " ".join(f"{k}={v}" for k, v in sizes.items())
    print(f"phase={phase} {fields} compile_s={compile_s:.3f} "
          f"run_s={run_s:.3f}", flush=True)


# ----------------------------------------------------------------------
# references on the host CPU backend
# ----------------------------------------------------------------------
def on_host(fn, *args, **kwargs):
    with jax.default_device(jax.devices("cpu")[0]):
        return jax.device_get(fn(*args, **kwargs))


def ref_rule_search(fz, pairs):
    rows, al = fz.canonicalize_queries(
        [a for a, _ in pairs], [c for _, c in pairs]
    )
    ec = fz.edge_child
    return on_host(
        ref.rule_search_fused_ref, fz.edge_parent, fz.edge_item, ec,
        fz.confidence[ec], fz.support[ec], fz.lift[ec], rows, al,
    )


def _dfs_columns(fz):
    d2n = fz.dfs_to_node
    return (fz.support[d2n], fz.confidence[d2n], fz.lift[d2n],
            fz.node_depth[d2n])


def ref_top_k(fz, prefixes, k, metric):
    los, his, _ = host_prefix_ranges(fz, prefixes)
    vals, pos = on_host(
        ref.topk_rank_batch_ref, *_dfs_columns(fz),
        los.astype(np.int32), his.astype(np.int32), k=k, metric=metric,
    )
    node = np.where(pos >= 0, fz.dfs_to_node[np.maximum(pos, 0)], -1)
    return {"values": vals, "node": node, "dfs_pos": pos}


def ref_rules_with(fz, items, role, k, metric):
    with jax.default_device(jax.devices("cpu")[0]):
        arrs = ops.item_rank_arrays(fz)
    offsets = np.asarray(fz.item_offsets)
    items = np.asarray(items, np.int64)
    vals, pos = on_host(
        ref.rules_with_ref, arrs["support"], arrs["confidence"],
        arrs["lift"], arrs["depth"], arrs["node_item"],
        arrs["post_lo"], arrs["post_hi"],
        offsets[items].astype(np.int32), offsets[items + 1].astype(np.int32),
        items.astype(np.int32), k=k, metric=metric, role=role,
    )
    node = np.where(pos >= 0, fz.dfs_to_node[np.maximum(pos, 0)], -1)
    return {"values": vals, "node": node}


def ref_trie_reduce(fz):
    return on_host(
        ref.trie_reduce_ref, fz.support, fz.confidence, fz.node_depth
    )


def check_rows(resps, want, what):
    """Every response ok, and each row equal to the reference row:
    integers exactly, floats to fp32 rounding (the device divides where
    compound lift needs it)."""
    for i, r in enumerate(resps):
        expect(r.ok, f"{what}[{i}]: status={r.status} error={r.error}")
        for key, ref_col in want.items():
            got = np.asarray(r.result[key])
            exp = np.asarray(ref_col[i])
            if np.issubdtype(exp.dtype, np.floating):
                ok = np.allclose(got, exp, rtol=1e-6, atol=0.0)
            else:
                ok = np.array_equal(got, exp)
            expect(ok, f"{what}[{i}] {key}: got {got} want {exp}")


# ----------------------------------------------------------------------
# traffic
# ----------------------------------------------------------------------
def canonical_nodes(fz, limit: int) -> np.ndarray:
    """Nodes whose root path is in canonical (frequency-rank) order —
    every node of a mined trie; on a synthetic trie with identity ranks,
    the ascending paths.  Only these are findable through the
    scheduler, which canonicalizes every query."""
    co, ei, ec = fz.child_offsets, fz.edge_item, fz.edge_child
    rank = np.asarray(fz.item_rank, np.int64)
    nodes, last = np.zeros(1, np.int64), np.full(1, -1, np.int64)
    out = []
    while nodes.size and sum(map(len, out)) < limit:
        starts, counts = co[nodes], co[nodes + 1] - co[nodes]
        first = np.repeat(np.cumsum(counts) - counts, counts)
        idx = np.repeat(starts, counts) + np.arange(counts.sum()) - first
        keep = rank[ei[idx]] > np.repeat(last, counts)
        nodes, last = ec[idx[keep]], rank[ei[idx[keep]]]
        out.append(nodes)
    return np.concatenate(out)[:limit] if out else np.zeros(0, np.int64)


def make_traffic(fz, rng, n_search, n_topk, n_items, roles):
    """Mixed requests: real rules (and absent ones), prefix rankings over
    real prefixes, item rankings in every role."""
    nodes = canonical_nodes(fz, 1 << 16)
    expect(nodes.size > 0, "no canonical nodes to query")
    n_vocab = int(np.asarray(fz.item_offsets).shape[0]) - 1
    reqs = []
    for i in range(n_search):
        if i % 4 == 3:   # an absent rule
            items = rng.choice(n_vocab, size=3, replace=False).tolist()
            pair = (items[:2], items[2:])
        else:
            path = list(fz.path_items(int(rng.choice(nodes))))
            a = int(rng.randint(0, len(path)))
            pair = (path[:a], path[a:])
        reqs.append(("rule_search", pair, {}))
    for i in range(n_topk):
        if i == 0:
            prefix = []
        else:
            path = list(fz.path_items(int(rng.choice(nodes))))
            prefix = path[: 1 + i % 2]
        metric = ("confidence", "lift", "support")[i % 3]
        reqs.append(("top_k", prefix, {"k": K, "metric": metric}))
    for role in roles:
        for it in rng.choice(n_vocab, size=min(n_items, n_vocab),
                             replace=False):
            reqs.append(
                ("rules_with", int(it), {"role": role, "k": K,
                                         "metric": "lift"})
            )
    return reqs


MAX_PENDING = 1024   # the whole request mix is admitted up front


def scheduler(engine) -> TrieScheduler:
    return TrieScheduler(engine, max_pending=MAX_PENDING)


def serve(sched, reqs):
    handles = [sched.submit(op, payload, kw) for op, payload, kw in reqs]
    sched.drain()
    return [sched.responses[h.id] for h in handles]


def check_against_reference(fz, reqs, resps, what):
    """Group requests by op and kwargs and compare each group's rows with
    the reference evaluated for that group."""
    groups = {}
    for i, (op, payload, kw) in enumerate(reqs):
        groups.setdefault((op, tuple(sorted(kw.items()))), []).append(i)
    for (op, kw), idx in groups.items():
        kw = dict(kw)
        payloads = [reqs[i][1] for i in idx]
        rs = [resps[i] for i in idx]
        tag = f"{what}/{op}{kw or ''}"
        if op == "rule_search":
            want = ref_rule_search(fz, payloads)
            check_rows(rs, {k: want[k] for k in (
                "found", "node", "support", "confidence", "lift")}, tag)
        elif op == "top_k":
            check_rows(rs, ref_top_k(fz, payloads, kw["k"], kw["metric"]),
                       tag)
        else:
            check_rows(rs, ref_rules_with(
                fz, payloads, kw["role"], kw["k"], kw["metric"]), tag)


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def phase_device(chips: int):
    devs = jax.devices()
    expect(devs[0].platform == "tpu",
           f"first device is {devs[0].platform!r}, not a TPU")
    expect(len(devs) >= chips, f"{chips} chips asked, {len(devs)} visible")
    forced = os.environ.get("REPRO_FORCE_INTERPRET", "").strip().lower()
    expect(forced in ("", "0", "false", "no", "off", "compiled"),
           f"REPRO_FORCE_INTERPRET={forced!r} would interpret on a TPU")
    expect(not ops.interpret_mode(), "kernels would run in interpret mode")
    jax.devices("cpu")  # the references run there
    report("device", 0.0, 0.0, platform=devs[0].platform,
           kind=repr(devs[0].device_kind), visible=len(devs), used=chips)
    return devs


def phase_build(clock):
    db = online_retail_db()
    res, comp, run = clock.timed(lambda: build_trie_of_rules(
        db, RETAIL_MINSUP, miner="fpgrowth", engine="arrays",
        use_kernel=True,
    ))
    fz = res.frozen
    host, _, _ = build_frozen_trie(db, res.sequences, use_kernel=False)
    for name in STRUCTURE:
        expect(np.array_equal(getattr(fz, name), getattr(host, name)),
               f"build: {name} differs from the host build")
    for name in ("support", "confidence", "lift"):
        got, want = getattr(fz, name), getattr(host, name)
        expect(np.allclose(got, want, rtol=1e-6, atol=1e-7),
               f"build: {name} off by {np.abs(got - want).max()}")
    report("build", comp, run, transactions=db.n_transactions,
           items=db.n_items, minsup=RETAIL_MINSUP,
           sequences=len(res.sequences), nodes=fz.n_nodes,
           mine_s=f"{res.mine_seconds:.3f}",
           annotate_s=f"{res.annotate_seconds:.3f}")
    return fz


def phase_serve(clock, fz):
    rng = np.random.RandomState(SEED)
    reqs = make_traffic(fz, rng, n_search=128, n_topk=96, n_items=64,
                        roles=ops.ROLES)
    sched = scheduler(TrieQueryEngine(fz, mode="replicated"))
    resps, comp, run = clock.timed(lambda: serve(sched, reqs))
    check_against_reference(fz, reqs, resps, "serve")

    # a streaming engine: a new rule is invisible, inserted, then found
    node = int(canonical_nodes(fz, 1 << 16)[-1])
    path = list(fz.path_items(node))
    rank = np.asarray(fz.item_rank)
    children = set(fz.edge_item[fz.child_offsets[node]:
                                fz.child_offsets[node + 1]].tolist())
    new = next(int(it) for it in np.asarray(fz.item_order)[::-1]
               if rank[it] > max(rank[p] for p in path)
               and int(it) not in children)
    metrics = (np.float32(0.0125), np.float32(0.375), np.float32(1.5))
    ssched = scheduler(TrieQueryEngine(StreamingTrie(fz), mode="replicated"))

    def insert_round():
        before = serve(ssched, [("rule_search", (path, [new]), {})])[0]
        ins = serve(ssched, [("insert", (path + [new], *metrics), {})])[0]
        after = serve(ssched, [("rule_search", (path, [new]), {})])[0]
        return before, ins, after

    (before, ins, after), icomp, irun = clock.timed(insert_round)
    expect(before.ok and not bool(before.result["found"]),
           f"insert: rule {path}->{new} found before its insert")
    expect(ins.ok, f"insert: status={ins.status} error={ins.error}")
    expect(after.ok and bool(after.result["found"]),
           "insert: the inserted rule is not found")
    for key, want in zip(("support", "confidence", "lift"), metrics):
        expect(np.float32(after.result[key]) == want,
               f"insert: {key}={after.result[key]} want {want}")
    counts = {op: sum(r[0] == op for r in reqs)
              for op in ("rule_search", "top_k", "rules_with")}
    report("serve", comp + icomp, run + irun, requests=len(reqs),
           **counts, launches=sched.stats["launches"], inserts=1,
           nodes=fz.n_nodes)


def phase_scale(clock, name, arrs, want_layout):
    fz = frozen_from_arrays(arrs)
    rng = np.random.RandomState(SEED + 1)
    reqs = make_traffic(fz, rng, n_search=64, n_topk=64, n_items=64,
                        roles=ops.ROLES)

    def run():
        engine = TrieQueryEngine(fz, mode="replicated", layout="auto")
        dt = engine._device_trie()
        resps = serve(scheduler(engine), reqs)
        return dt, resps, jax.device_get(ops.trie_reduce(dt))

    (dt, resps, agg), comp, run_s = clock.timed(run)
    expect(dt.layout == want_layout,
           f"{name}: layout {dt.layout!r}, want {want_layout!r}")
    check_against_reference(fz, reqs, resps, name)
    n, sup_sum, conf_max, conf_sum = ref_trie_reduce(fz)
    expect(agg["n_rules"] == n and agg["confidence_max"] == conf_max,
           f"{name}: trie_reduce count/max {agg} vs {(n, conf_max)}")
    expect(np.allclose([agg["support_sum"], agg["mean_conf"] * n],
                       [sup_sum, conf_sum], rtol=1e-5),
           f"{name}: trie_reduce sums {agg} vs {(sup_sum, conf_sum)}")
    report(name, comp, run_s, nodes=fz.n_nodes, layout=dt.layout,
           requests=len(reqs), found=sum(
               bool(r.result.get("found", False)) for r in resps))


def phase_sharded(clock, chips):
    arrs = synthetic_csr_trie(SCALE_NODES - 1)
    fz = frozen_from_arrays(arrs)
    rng = np.random.RandomState(SEED + 2)
    reqs = make_traffic(fz, rng, n_search=64, n_topk=64, n_items=64,
                        roles=ops.ROLES)
    single = scheduler(TrieQueryEngine(fz, mode="replicated"))
    want = serve(single, reqs)

    def run():
        engine = TrieQueryEngine(fz, mesh=make_trie_mesh(chips),
                                 mode="sharded")
        sched = scheduler(engine)
        return engine, sched, serve(sched, reqs)

    (engine, sched, got), comp, run_s = clock.timed(run)
    expect(engine.n_shards == chips, f"n_shards={engine.n_shards}")
    expect(engine.backend == "sharded", f"backend={engine.backend}")
    expect(sched.engine.failovers == 0,
           f"{sched.engine.failovers} failover(s)")
    for i, (g, w) in enumerate(zip(got, want)):
        expect(g.ok and w.ok, f"sharded[{i}]: {g.status} / {w.status}")
        expect(g.backend in ("sharded", "cache") and not g.degraded,
               f"sharded[{i}]: answered by {g.backend}")
        for key in w.result:
            expect(np.array_equal(np.asarray(g.result[key]),
                                  np.asarray(w.result[key])),
                   f"sharded[{i}] {reqs[i][0]} {key}: {g.result[key]} "
                   f"vs one chip {w.result[key]}")
    report("sharded", comp, run_s, nodes=fz.n_nodes, shards=chips,
           requests=len(reqs), launches=sched.stats["launches"])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = parser.parse_args(argv)
    enable_compile_cache()
    try:
        devs = phase_device(args.chips)
        clock = CompileClock()
        if args.chips == 1:
            fz = phase_build(clock)
            phase_serve(clock, fz)
            phase_scale(clock, "scale_plain",
                        synthetic_csr_trie(SCALE_NODES - 1), "plain")
            phase_scale(clock, "scale_compressed",
                        synthetic_chain_trie(SCALE_NODES - 1),
                        "compressed")
        else:
            phase_sharded(clock, args.chips)
    except Exception:  # noqa: BLE001 - any failure fails the smoke
        traceback.print_exc()
        print("chip_smoke: FAILED", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
