"""One run of one cell: build the store, warm up, run the open loop,
check the answers against the plain reference, print the result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names a configuration (``bench/configs/<config>.json``, whose ``store``
names ``bench/stores/<kind>.py`` and its reference
``bench/refs/<kind>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``); each per-layer metric is read by
``bench/metrics/<metric>.py``, or, where a quantity is split by the
end-to-end metric it moves (``device.idle_share.sat``) and the part has
no file of its own, by the reader of the name with its last dotted part
taken off.  Adding a deployment, a mix or a metric adds files and
manifest entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
import traceback
from pathlib import Path

import numpy as np

from benchlib import compare, mixgen, openloop

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
PER_OP_SAMPLE = 150


class NoChip(RuntimeError):
    """The devices JAX found cannot run the cell."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """``(manifest, cell, config, mix)`` of the named cell."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfgs = {c["name"]: c for c in manifest["configs"]}
    cfg = json.loads((ROOT / cfgs[cell["config"]]["file"]).read_text())
    mix = mixgen.load_mix(BENCH / "traffic", cell["traffic"])
    return manifest, cell, cfg, mix


def metric_reader(name: str):
    """The reader module of per-layer metric ``name``."""
    stem = name
    while not (BENCH / "metrics" / f"{stem}.py").exists():
        if "." not in stem:
            raise FileNotFoundError(f"no reader for metric {name!r}")
        stem = stem.rsplit(".", 1)[0]
    return load_module(BENCH / "metrics" / f"{stem}.py",
                       "bench_metric_" + stem.replace(".", "_"))


def check_devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (first device: {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def node_paths(fz):
    """``[N, max_depth]`` root paths of every node of a frozen trie and
    the depths, read off its parent and item arrays."""
    parent = np.asarray(fz.node_parent, np.int64)
    item = np.asarray(fz.node_item, np.int64)
    depth = np.asarray(fz.node_depth, np.int64)
    width = max(int(depth.max()), 1)
    mat = np.full((parent.size, width), -1, np.int32)
    cur = np.arange(parent.size)
    for _ in range(width):
        d = depth[cur]
        ok = d > 0
        mat[np.flatnonzero(ok), d[ok] - 1] = item[cur[ok]]
        cur = np.where(ok, parent[cur], 0)
    return mat, depth


def make_scheduler(engine, cfg: dict, **over):
    from repro.serve import TrieScheduler

    kw = dict(cfg["scheduler"])
    kw.update(over)
    engine.obs = None        # bind the new scheduler's registry
    return TrieScheduler(engine, **kw)


def warm_sizes(op: str, largest: int) -> list:
    """Batch sizes that reach every launch shape of a bucket.  Ranked ops
    (``top_k``, ``rules_with``) launch on rows padded to a power of two,
    so one size per power suffices; ``rule_search`` also gathers its
    results back to the unpadded row count, so every size is a shape."""
    if op == "rule_search":
        return list(range(1, largest + 1))
    sizes = {1, 2, largest}
    p = 2
    while p + 1 <= largest:
        sizes.add(p + 1)
        p *= 2
    return sorted(n for n in sizes if n <= largest)


def warm_up(engine, cfg, mix, pools) -> int:
    """Launch every shape the window can: each bucket (op and kwargs) at
    the batch sizes ``warm_sizes`` names, on distinct queries, with the
    result cache off so that each launch has that many rows.  The window
    reports how many programs compiled inside it, which should be 0."""
    max_batch = int(cfg["scheduler"]["max_batch"])
    buckets = {}
    for spec, pool in zip(mix["ops"], pools):
        for payload, kw in pool:
            sig = (spec["op"], tuple(sorted(kw.items())))
            lst = buckets.setdefault(sig, [])
            if len(lst) < max_batch:
                lst.append((spec["op"], payload, kw))
    sched = make_scheduler(engine, cfg, cache_size=0,
                           max_pending=max_batch)
    launches = 0
    for (op, _), lst in buckets.items():
        for n in warm_sizes(op, len(lst)):
            hs = [sched.submit(o, p, kw) for o, p, kw in lst[:n]]
            sched.drain()
            launches += 1
            for h in hs:
                r = sched.responses.pop(h.id)
                if not r.ok:
                    raise RuntimeError(f"warm-up {r.op}: {r.status} "
                                       f"{r.error}")
    return launches


def e2e_metrics(rec, names) -> dict:
    lat = openloop.latencies_ms(rec)
    vals = {"p50_ms": ("ms", openloop.percentile(lat, 50)),
            "p95_ms": ("ms", openloop.percentile(lat, 95)),
            "goodput_qps": ("req/s", openloop.goodput_qps(rec))}
    out = {}
    for n in names:
        if n not in vals:
            continue
        if not math.isfinite(vals[n][1]):
            missed = float(np.mean(np.isinf(lat)))
            raise ValueError(
                f"{n} is undefined: {missed:.1%} of the requests missed "
                f"every limit, so the offered rate is above the knee")
        out[n] = {"value": vals[n][1], "unit": vals[n][0]}
    return out


def metrics_for(manifest, cell_name, section) -> list:
    out = []
    for m in manifest[section]:
        if "workloads" not in m or cell_name in m["workloads"]:
            out.append(m)
    return out


class Prepared:
    """A cell made ready to measure: the store built through the
    program's path, the engine with the store on the device, and what
    the traffic generator may read of the store."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def prepare(name: str, *, require_tpu: bool = True,
            cfg_over=None, mix_over=None) -> Prepared:
    manifest, cell, cfg, mix = load_cell(name)
    if cfg_over:
        cfg = {**cfg, **cfg_over}
    if mix_over:
        mix = {**mix, **mix_over}
    import jax

    from benchlib.compiles import CompileClock

    if require_tpu:
        devs = check_devices(jax, int(cell["chips"]))
    else:
        devs = jax.devices()
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program goes to the persistent cache, however fast it
    # compiled, so that a second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock(jax.monitoring)
    from repro.serve import TrieQueryEngine

    kind = cfg["store"]
    store_mod = load_module(BENCH / "stores" / f"{kind}.py",
                            f"bench_store_{kind}")
    t0 = time.perf_counter()
    # the store is the deployment's fixed data set; the run's seed draws
    # only its traffic
    store = store_mod.build(cfg, int(cfg["data_seed"]))
    fz = store.frozen
    engine = TrieQueryEngine(fz, **cfg["engine"])
    dt = engine._device_trie()
    jax.block_until_ready(dt.support)
    paths, depth = node_paths(fz)
    view = mixgen.StoreView(paths, depth, store.popular_items,
                            int(np.asarray(fz.item_offsets).size - 1))
    return Prepared(
        jax=jax, manifest=manifest, cell=cell, cfg=cfg, mix=mix,
        devs=devs, cache_dir=cache_dir, clock=clock, kind=kind,
        store=store, fz=fz, engine=engine, layout=dt.layout, paths=paths,
        depth=depth, view=view,
        build_s=time.perf_counter() - t0)


def measure(p: Prepared, reqs, seconds: float, trace_dir=None):
    """One open-loop window over a fresh scheduler (empty cache);
    ``trace_dir`` traces it.  Returns ``(records, counters, compiles
    inside the window)``."""
    from repro.kernels.ops import InvalidQueryError
    from repro.serve import QueueFull

    jax = p.jax
    sched = make_scheduler(p.engine, p.cfg)
    annotate = openloop._null
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    before = p.clock.count
    try:
        rec = openloop.run_window(
            sched, reqs, seconds, annotate=annotate,
            queue_full=QueueFull, invalid=InvalidQueryError)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return rec, sched.stats, p.clock.count - before


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, cfg_over=None,
             mix_over=None, log=None, control: bool = False) -> dict:
    """One run; returns the result object (the last stdout line).
    ``control`` also reads the control: the reference itself, its values
    rounded to bfloat16, in the server's place on the same sample."""
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    p = prepare(name, require_tpu=require_tpu, cfg_over=cfg_over,
                mix_over=mix_over)
    reqs, pools = mixgen.generate(p.mix, p.view, seconds, seed)
    t1 = time.perf_counter()
    launches = warm_up(p.engine, p.cfg, p.mix, pools)
    t_warm = time.perf_counter() - t1
    trace_dir = None
    if trace:
        import shutil

        trace_dir = ROOT / ".bench_trace" / name
        if trace_dir.exists():
            shutil.rmtree(trace_dir)
    setup_compiles = p.clock.count
    setup_s = time.perf_counter() - t_start
    log(f"setup store={p.kind} nodes={p.fz.n_nodes} layout={p.layout} "
        f"build_s={p.build_s:.3f} warmup_s={t_warm:.3f} "
        f"warmup_launches={launches} requests={len(reqs)} "
        f"cache_dir={p.cache_dir} setup_compiles={setup_compiles} "
        f"setup_compile_s={p.clock.seconds:.3f} "
        f"info={json.dumps(p.store.info)}")
    rec, stats, in_window = measure(p, reqs, seconds, trace_dir)
    log(f"window requests={len(reqs)} wall_s={rec.wall_s:.3f} "
        f"compiles_in_window={in_window} counters={json.dumps(stats)}")
    dev = p.devs[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(p.devs), "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": len(reqs),
              "failed": openloop.n_failed(rec), "metrics": {},
              "device": device}
    if trace:
        from benchlib import tracereduce

        summary = tracereduce.summarize(tracereduce.load_events(trace_dir))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
        ctx = {"records": rec, "counters": stats, "trace": summary,
               "device_kind": dev.device_kind}
        for m in metrics_for(p.manifest, name, "per_layer"):
            v = metric_reader(m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
    else:
        names = [m["name"] for m in metrics_for(p.manifest, name,
                                                "end_to_end")]
        result["metrics"] = e2e_metrics(rec, names)
        if "setup_s" in names:
            result["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
    # the program's device state goes before the reference runs
    p.engine = None
    t2 = time.perf_counter()
    ref_mod = load_module(BENCH / "refs" / f"{p.kind}.py",
                          f"bench_ref_{p.kind}")
    truth = compare.Reference(ref_mod.reference(p.store.data))
    sizes = [_size(r) for r in reqs]
    idx = compare.sample(rec, sizes, PER_OP_SAMPLE, seed)
    items = [(reqs[i].op, reqs[i].payload, reqs[i].kwargs,
              compare.served_answer(reqs[i].op, rec.responses[i].result,
                                    p.paths, p.depth)) for i in idx]
    checks = compare.compare(items, truth)
    if control:
        low = compare.Reference(truth.t, rounding=compare.bf16_round)
        result["control"] = compare.compare(
            [(op, q, kw, low.answer(op, q, kw)) for op, q, kw, _ in items],
            truth)
    log(f"reference_s={time.perf_counter() - t2:.3f} "
        f"compared={checks['compared']}")
    limits = p.cfg["limits"]
    verdict = {
        "mismatch": {"value": checks["mismatch"],
                     "limit": limits["mismatch"]},
        "value_gap": {"value": checks["value_gap"],
                      "limit": limits["value_gap"]},
        "compared": {"value": checks["compared"],
                     "limit": limits["min_compared"]},
    }
    result["correct"] = bool(
        checks["mismatch"] <= limits["mismatch"]
        and checks["value_gap"] <= limits["value_gap"]
        and checks["compared"] >= limits["min_compared"])
    result["checks"] = verdict
    for k, v in verdict.items():
        rel = ">=" if k == "compared" else "<="
        log(f"check {k}={v['value']!r} limit {rel} {v['limit']!r}")
    return result


def _size(r) -> int:
    if r.op == "rule_search":
        return len(r.payload[0]) + len(r.payload[1])
    if r.op == "top_k":
        return len(r.payload)
    return 1


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
        line = json.dumps(result, allow_nan=False)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    except Exception:  # noqa: BLE001 - any fault ends the run, no result
        traceback.print_exc()
        return 1
    print(line, flush=True)
    return 0
