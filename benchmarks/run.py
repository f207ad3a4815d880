"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Roofline terms for the model-side
dry-run live in ``repro.launch.roofline`` (they are derived from compiled
artifacts, not timed here); each KERNEL lane additionally reports its own
achieved-bandwidth roofline figure (``roofline.kernel_roofline``).

On a TPU the kernels compile (``kernels.ops.interpret_mode``); on a CPU
host they run in interpret mode, and those timings are interpreter
numbers, not device numbers.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

from . import paper_figs


BENCHES = [
    ("fig8_9_search", paper_figs.bench_search),
    ("fig10_search_scaling", paper_figs.bench_search_scaling),
    ("fig11_construction", paper_figs.bench_construction),
    ("fig11_build_engines", paper_figs.bench_build),
    ("fig12_topn_support", paper_figs.bench_topn_support),
    ("fig13_topn_confidence", paper_figs.bench_topn_confidence),
    ("traversal_8x", paper_figs.bench_traversal),
    ("compression", paper_figs.bench_compression),
    ("batched_search", paper_figs.bench_batched_search),
    ("rule_search_kernels", paper_figs.bench_rule_search_kernels),
    ("topk_rank_kernel", paper_figs.bench_topk_rank),
    ("batched_query_ops", paper_figs.bench_batched_query),
    ("sharded_query", paper_figs.bench_sharded_query),
    ("serve_loop", paper_figs.bench_serve),
    ("obs_overhead", paper_figs.bench_obs),
    ("compress_layout", paper_figs.bench_compress_layout),
    ("streaming_inserts", paper_figs.bench_streaming),
]


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--only", default=None, help="substring filter")
    parser.add_argument(
        "--smoke", action="store_true",
        help="tiny trie sizes (CI smoke run)",
    )
    parser.add_argument(
        "--json-out", default="BENCH_rule_search.json",
        help="path for the rule-search perf-trajectory JSON "
             "('' disables writing)",
    )
    parser.add_argument(
        "--json-out-topk", default="BENCH_topk.json",
        help="path for the ranked-extraction perf-trajectory JSON "
             "('' disables writing)",
    )
    parser.add_argument(
        "--json-out-build", default="BENCH_build.json",
        help="path for the construction-engine perf-trajectory JSON "
             "('' disables writing)",
    )
    parser.add_argument(
        "--json-out-batched", default="BENCH_batched_query.json",
        help="path for the batched-vs-loop query-engine perf-trajectory "
             "JSON ('' disables writing)",
    )
    parser.add_argument(
        "--json-out-traversal", default="BENCH_traversal.json",
        help="path for the traversal-lane perf-trajectory JSON "
             "('' disables writing)",
    )
    parser.add_argument(
        "--json-out-sharded", default="BENCH_sharded_query.json",
        help="path for the sharded-vs-single query-engine "
             "perf-trajectory JSON ('' disables writing)",
    )
    parser.add_argument(
        "--json-out-serve", default="BENCH_serve.json",
        help="path for the serve-loop SLO trajectory JSON "
             "('' disables writing)",
    )
    parser.add_argument(
        "--json-out-compress", default="BENCH_compress.json",
        help="path for the compressed-layout residency/latency "
             "trajectory JSON ('' disables writing)",
    )
    parser.add_argument(
        "--json-out-streaming", default="BENCH_streaming.json",
        help="path for the streaming-insert delta-overlay trajectory "
             "JSON ('' disables writing)",
    )
    parser.add_argument(
        "--json-out-obs", default="BENCH_obs.json",
        help="path for the observability-overhead trajectory JSON "
             "('' disables writing)",
    )
    parser.add_argument(
        "--trace-out", default="",
        help="write the obs lane's traced replay as Perfetto "
             "trace_event JSON to this path (plus a .metrics.txt dump); "
             "'' disables writing",
    )
    args = parser.parse_args()
    enable_compile_cache()
    paper_figs.SMOKE = args.smoke
    paper_figs.JSON_OUT = args.json_out
    paper_figs.JSON_OUT_TOPK = args.json_out_topk
    paper_figs.JSON_OUT_BUILD = args.json_out_build
    paper_figs.JSON_OUT_BATCHED = args.json_out_batched
    paper_figs.JSON_OUT_TRAVERSAL = args.json_out_traversal
    paper_figs.JSON_OUT_SHARDED = args.json_out_sharded
    paper_figs.JSON_OUT_SERVE = args.json_out_serve
    paper_figs.JSON_OUT_COMPRESS = args.json_out_compress
    paper_figs.JSON_OUT_STREAMING = args.json_out_streaming
    paper_figs.JSON_OUT_OBS = args.json_out_obs
    paper_figs.TRACE_OUT = args.trace_out

    print("name,us_per_call,derived")
    failed = []
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        try:
            for row in fn():
                print(row.csv(), flush=True)
        except Exception:  # pragma: no cover - harness robustness
            failed.append(name)
            traceback.print_exc(file=sys.stderr)
    if failed:
        print(f"# FAILED: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
