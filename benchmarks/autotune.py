"""Per-backend kernel autotune: sweep every ``KernelConfig`` knob over a
pow2 grid, assert bit-parity against the jnp oracles at EVERY swept
point, and write the winning table to ``benchmarks/tuning/<backend>.json``.

    PYTHONPATH=src python -m benchmarks.autotune            # full sweep
    PYTHONPATH=src python -m benchmarks.autotune --smoke    # CI smoke

Parity-before-performance is the contract that keeps the knobs
semantics-free: a candidate that fails its oracle comparison aborts the
sweep (no table is written), so a committed table can never encode a
configuration that changes results.  The one relaxation is ``reduce_bn``
— retiling reassociates the fp32 running sums, so count/max stay bitwise
while the sums compare to 1e-6 (the same contract the kernel docstring
and the tests state).

Selection is min-median-time with a near-tie rule: the built-in default
wins unless a candidate beats it by more than ``NEAR_TIE`` (3%), so
tables don't churn on timer noise.  ``--json-out`` also emits a
gate-able ``BENCH_autotune.json`` whose ``speedup_best_vs_default``
ratios the manifest gate bounds (a tuned knob should never be SLOWER
than the default it replaced).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np


NEAR_TIE = 0.03   # keep the default within 3% of the best candidate

# pow2 sweep grids (multiples of LANE=128 — KernelConfig.validate's rule)
GRIDS = {
    "rank_bn": (1024, 2048, 4096, 8192, 16384),
    "reduce_bn": (1024, 2048, 4096, 8192, 16384),
    "launch_pad_floor": (1, 2, 4, 8, 16),
}
GRIDS_SMOKE = {
    "rank_bn": (4096, 8192),
    "reduce_bn": (4096, 8192),
    "launch_pad_floor": (1, 4),
}

# fixture sizes (edges).
# 20k edges keeps the full interpret-mode sweep under ~10 min on the CPU
# CI host while staying big enough that tile-size rankings are real; on
# a TPU/GPU host (compiled kernels) bump toward the bench sizes.
SWEEP_EDGES = 20_000
SWEEP_EDGES_SMOKE = 2_048
TIMING_REPS = 5
TIMING_REPS_SMOKE = 3


def _median_us(fn, n, warmup=1):
    from .common import time_per_call_median

    return time_per_call_median(fn, n=n, warmup=warmup) * 1e6


def _fixture(n_edges: int):
    from repro.core.synthetic import synthetic_csr_trie

    return synthetic_csr_trie(n_edges)


def _assert_bitwise(got, want, what: str) -> None:
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want),
        err_msg=f"autotune parity failure: {what}",
    )


def sweep_rank_bn(arrs, grid, reps) -> dict:
    """Segmented top-k tile: time the batched rank kernel per block_n,
    bit-parity vs ``topk_rank_batch_ref`` at every point."""
    import jax.numpy as jnp

    from repro.kernels.rank import topk_rank_batch_pallas
    from repro.kernels.ref import topk_rank_batch_ref

    d2n = arrs["dfs_to_node"]
    cols = tuple(
        jnp.asarray(arrs[c][d2n])
        for c in ("support", "confidence", "lift", "node_depth")
    )
    n = int(arrs["node_parent"].shape[0])
    rng = np.random.RandomState(0)
    los = jnp.asarray(rng.randint(0, n, size=16), jnp.int32)
    his = jnp.minimum(los + rng.randint(1, n, size=16), n)
    rv, rp = topk_rank_batch_ref(*cols, los, his, k=10)
    candidates = {}
    for bn in grid:
        kv, kp = topk_rank_batch_pallas(
            *cols, los, his, k=10, interpret=True, block_n=bn
        )
        _assert_bitwise(kv, rv, f"rank_bn={bn} values")
        _assert_bitwise(kp, rp, f"rank_bn={bn} positions")
        candidates[bn] = _median_us(
            lambda: topk_rank_batch_pallas(
                *cols, los, his, k=10, interpret=True, block_n=bn
            )[0].block_until_ready(),
            reps,
        )
    return candidates


def sweep_reduce_bn(arrs, grid, reps) -> dict:
    """Traversal-reduction tile.  Count/max bitwise; the fp32 sums
    reassociate under retiling, so they compare to 1e-6."""
    import jax.numpy as jnp

    from repro.kernels.ref import trie_reduce_ref
    from repro.kernels.trie_reduce import trie_reduce_pallas

    sup = jnp.asarray(arrs["support"])
    conf = jnp.asarray(arrs["confidence"])
    dep = jnp.asarray(arrs["node_depth"])
    rn, rsup, rmax, rcsum = trie_reduce_ref(sup, conf, dep)
    candidates = {}
    for bn in grid:
        kn, ksup, kmax, kcsum = trie_reduce_pallas(
            sup, conf, dep, interpret=True, block_n=bn
        )
        _assert_bitwise(kn, rn, f"reduce_bn={bn} count")
        _assert_bitwise(kmax, rmax, f"reduce_bn={bn} max")
        np.testing.assert_allclose(
            np.asarray(ksup), np.asarray(rsup), rtol=1e-6,
            err_msg=f"autotune parity failure: reduce_bn={bn} support sum",
        )
        np.testing.assert_allclose(
            np.asarray(kcsum), np.asarray(rcsum), rtol=1e-6,
            err_msg=f"autotune parity failure: reduce_bn={bn} conf sum",
        )
        candidates[bn] = _median_us(
            lambda: trie_reduce_pallas(
                sup, conf, dep, interpret=True, block_n=bn
            )[0].block_until_ready(),
            reps,
        )
    return candidates


def sweep_launch_pad_floor(arrs, grid, reps) -> dict:
    """Launch-pad floor: time a ragged-batch descent per floor (more pad
    rows, fewer distinct shapes), results bitwise-equal on real rows."""
    import jax.numpy as jnp

    from repro.core.synthetic import synthetic_search_queries
    from repro.kernels.ops import dedup_query_rows
    from repro.kernels.rule_search import rule_search_fused
    from repro.kernels.tuning import tuning_overrides

    queries, ant_len = synthetic_search_queries(arrs, 11, 6, seed=3)
    ec_np = arrs["edge_child"]
    ei = jnp.asarray(arrs["edge_item"])
    ec = jnp.asarray(ec_np)
    ecf = jnp.asarray(arrs["confidence"][ec_np])
    esp = jnp.asarray(arrs["support"][ec_np])
    elf = jnp.asarray(arrs["lift"][ec_np])
    co = jnp.asarray(arrs["child_offsets"])
    mf = int(arrs["max_fanout"])

    def run(floor):
        with tuning_overrides(launch_pad_floor=floor):
            uq, ual, inv = dedup_query_rows(queries, ant_len)
            out = rule_search_fused(
                co, ei, ec, ecf, esp, elf,
                jnp.asarray(uq), jnp.asarray(ual), max_fanout=mf,
            )
        lift = np.asarray(out["lift"])
        return lift if inv is None else lift[inv]

    base = run(grid[0])
    candidates = {}
    for floor in grid:
        _assert_bitwise(run(floor), base, f"launch_pad_floor={floor}")
        candidates[floor] = _median_us(lambda: run(floor), reps)
    return candidates


def pick(candidates: dict, default):
    """Min-median with the near-tie rule (default sticks within 3%)."""
    best = min(candidates, key=candidates.get)
    if default in candidates:
        if candidates[default] <= candidates[best] * (1.0 + NEAR_TIE):
            return default
    return best


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny grid + fixture (CI smoke sweep)")
    parser.add_argument("--backend", default=None,
                        help="table name (default: jax.default_backend())")
    parser.add_argument("--no-write-table", action="store_true",
                        help="sweep + parity only; leave tables untouched")
    parser.add_argument("--json-out", default="BENCH_autotune.json",
                        help="gate-able sweep JSON ('' disables)")
    args = parser.parse_args()

    import jax

    from repro.kernels.tuning import DEFAULTS, write_table

    backend = args.backend or jax.default_backend()
    grids = GRIDS_SMOKE if args.smoke else GRIDS
    n_edges = SWEEP_EDGES_SMOKE if args.smoke else SWEEP_EDGES
    reps = TIMING_REPS_SMOKE if args.smoke else TIMING_REPS

    t0 = time.time()
    arrs = _fixture(n_edges)
    print(f"# autotune backend={backend} edges={n_edges} "
          f"smoke={args.smoke}", flush=True)

    results = []
    chosen = {}

    for knob, sweep in (
        ("rank_bn", lambda: sweep_rank_bn(arrs, grids["rank_bn"], reps)),
        ("reduce_bn",
         lambda: sweep_reduce_bn(arrs, grids["reduce_bn"], reps)),
        ("launch_pad_floor",
         lambda: sweep_launch_pad_floor(
             arrs, grids["launch_pad_floor"], reps)),
    ):
        candidates = sweep()
        default = getattr(DEFAULTS, knob)
        winner = pick(candidates, default)
        chosen[knob] = int(winner)
        default_us = candidates.get(default, candidates[winner])
        results.append({
            "knob": knob,
            "candidates_us": {str(k): v for k, v in candidates.items()},
            "default": default,
            "chosen": int(winner),
            "default_us": default_us,
            "best_us": candidates[winner],
            "speedup_best_vs_default":
                default_us / candidates[winner],
        })
        print(f"# {knob}: chose {winner} (default {default}; "
              f"{default_us / candidates[winner]:.2f}x)", flush=True)

    cfg = dataclasses.replace(DEFAULTS, **chosen).validate()
    if not args.no_write_table:
        path = write_table(backend, cfg, extra={
            "smoke": args.smoke,
            "sweep_edges": n_edges,
            "sweep_seconds": time.time() - t0,
        })
        print(f"# wrote {path}", flush=True)

    if args.json_out:
        payload = {
            "bench": "autotune",
            "backend": backend,
            "smoke": args.smoke,
            "unix_time": time.time(),
            "knobs_chosen": dataclasses.asdict(cfg),
            "results": results,
        }
        with open(args.json_out, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {args.json_out}", flush=True)


if __name__ == "__main__":
    main()
