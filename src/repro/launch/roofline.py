"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch × shape × mesh) cell, from the dry-run JSON records:

    compute term    = flops_per_device / peak_FLOPs
    memory term     = bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / link_bw

(The dry-run compiled module is the per-device SPMD program, so the
recorded numbers are already per-chip; dividing global quantities by chip
count gives the same terms.)  The dominant term is the bottleneck; the
MODEL_FLOPS ratio (6·N·D for dense, 6·N_active·D for MoE) measures how
much compiled compute is "useful".

Hardware constants (TPU v5e per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

# Peaks for the KERNEL bench lanes' achieved-vs-peak figure
# (``kernel_roofline``), keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" is how JAX names a TPU v5e chip; its row is Google Cloud's
# published "TPU v5e" spec (197 TFLOP/s bf16, 819 GB/s HBM), the same
# constants as above.  The cpu row is a deliberately conservative
# dual-channel DDR4 envelope (~25.6 GB/s), so interpret-mode figures read
# as what they are — emulation, nowhere near the roof.  A device kind
# missing here is an error, never a default.
KERNEL_PEAKS = {
    "TPU v5 lite": {"peak_flops": PEAK_FLOPS, "hbm_gbps": HBM_BW / 1e9},
    "cpu": {"peak_flops": 1e12, "hbm_gbps": 25.6},
}

DRYRUN_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun"
)


def kernel_roofline(
    bytes_moved: float, seconds: float, device_kind: Optional[str] = None
) -> Dict:
    """Achieved-vs-peak bandwidth for one kernel bench lane.

    ``bytes_moved`` is the lane's streamed working set per call (the
    trie kernels are memory-bound column sweeps, so bytes/peak-BW is the
    relevant roof); ``seconds`` the measured per-call time.  Returns the
    achieved GB/s, the device's peak, and their ratio — the
    bandwidth-utilization figure the bench reports emit next to each
    speedup ratio.  ``device_kind`` defaults to the first JAX device's;
    a kind without a ``KERNEL_PEAKS`` row raises ``ValueError``.
    """
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    peaks = KERNEL_PEAKS.get(device_kind)
    if peaks is None:
        raise ValueError(
            f"no KERNEL_PEAKS row for device kind {device_kind!r}; add "
            f"its published peaks with their source"
        )
    achieved = (bytes_moved / seconds) / 1e9 if seconds > 0 else 0.0
    peak = peaks["hbm_gbps"]
    return {
        "device_kind": device_kind,
        "bytes_moved": float(bytes_moved),
        "seconds": float(seconds),
        "achieved_gbps": achieved,
        "peak_gbps": peak,
        "bandwidth_util": achieved / peak if peak > 0 else 0.0,
    }


def model_flops(arch: str, shape_name: str) -> float:
    """6·N_active·D (training) / 2·N_active·D (single forward)."""
    from repro.configs import get_config, get_shape
    from repro.models import count_params_analytic

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    n_active = count_params_analytic(cfg, active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch * 1
    return 2.0 * n_active * tokens


def analyze_record(rec: Dict, use_boundary: bool = False) -> Dict:
    """use_boundary=True picks the fusion-boundary memory estimate when
    recorded; the main table uses the unfused upper bound uniformly (all
    80 baseline cells share that estimator)."""
    flops = rec["flops_per_device"]
    mem_bytes = rec["bytes_per_device"]
    if use_boundary:
        mem_bytes = rec.get("bytes_boundary_per_device", mem_bytes)
    coll = rec["collective_bytes_per_device"]["total"]
    chips = rec["n_devices"]
    terms = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": mem_bytes / HBM_BW,
        "memory_unfused_s": rec["bytes_per_device"] / HBM_BW,
        "collective_s": coll / LINK_BW,
    }
    core = {k: terms[k] for k in
            ("compute_s", "memory_s", "collective_s")}
    dominant = max(core, key=core.get)
    mf = model_flops(rec["arch"], rec["shape"])
    hlo_global = flops * chips
    bound = max(core.values())
    useful_s = (mf / chips) / PEAK_FLOPS
    out = dict(rec)
    out.update(
        {
            "terms": terms,
            "dominant": dominant,
            "model_flops_global": mf,
            "hlo_flops_global": hlo_global,
            "useful_ratio": mf / hlo_global if hlo_global else 0.0,
            # fraction of the compute roofline actually achievable given
            # the dominant bound: useful-model-time / bound-time
            "roofline_fraction": useful_s / bound if bound else 0.0,
        }
    )
    return out


def load_records(dryrun_dir: str = DRYRUN_DIR) -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def what_moves_it(rec: Dict) -> str:
    d = rec["dominant"]
    if d == "compute_s":
        if rec["useful_ratio"] < 0.4:
            return (
                "compute-bound with low useful ratio: cut non-model flops "
                "(causal chunk skipping, less remat recompute)"
            )
        return "compute-bound: larger per-chip batch or more chips"
    if d == "memory_s":
        return (
            "HBM-bound: fuse/shrink intermediates (bf16 scores, fewer "
            "materialized masks), increase arithmetic intensity"
        )
    return (
        "collective-bound: shrink collective payloads (bf16 psum, "
        "reduce-scatter instead of all-reduce) or overlap with compute"
    )


def table(records: List[Dict], mesh: Optional[str] = "pod16x16") -> str:
    rows = []
    header = (
        "| arch | shape | mesh | compute s | memory s | collective s | "
        "dominant | useful | roofline frac |"
    )
    rows.append(header)
    rows.append("|" + "---|" * 9)
    for rec in records:
        if mesh and rec["mesh"] != mesh:
            continue
        a = analyze_record(rec)
        t = a["terms"]
        rows.append(
            f"| {a['arch']} | {a['shape']} | {a['mesh']} "
            f"| {t['compute_s']:.3e} | {t['memory_s']:.3e} "
            f"| {t['collective_s']:.3e} | {a['dominant'][:-2]} "
            f"| {a['useful_ratio']:.2f} | {a['roofline_fraction']:.3f} |"
        )
    return "\n".join(rows)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dir", default=DRYRUN_DIR)
    p.add_argument("--mesh", default=None,
                   help="pod16x16 | pod2x16x16 | None=all")
    args = p.parse_args()
    records = load_records(args.dir)
    print(table(records, args.mesh))
    print()
    for rec in records:
        if args.mesh and rec["mesh"] != args.mesh:
            continue
        a = analyze_record(rec)
        print(
            f"{a['arch']} × {a['shape']} × {a['mesh']}: "
            f"{a['dominant'][:-2]}-bound — {what_moves_it(a)}"
        )


if __name__ == "__main__":
    main()
