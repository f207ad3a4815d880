"""Manifest-driven CI bench gate: fail on kernel / engine regressions.

Every gate lane lives in ``benchmarks/gates.json`` — one entry per lane
naming its committed baseline, config-key fields, gated ratio metric(s),
slack (``max_ratio``), and the command that produces a fresh ``--smoke``
JSON.  Adding a lane (the autotune sweep, say) is a manifest edit, not
new Python.

Every gate is RATIO-based so it tolerates hardware differences between
the baseline machine and the CI runner: what is compared is a speedup
*measured within the same run*, never absolute microseconds.  A fresh
higher-is-better speedup below ``baseline / max_ratio`` fails; a
lower-is-better metric (a lane's ``metrics`` list — the serve loop's
p99/p50 tail ratio and shed rate) fails above
``baseline * max_ratio + atol`` (the additive ``atol`` keeps zero-valued
baselines from turning into impossible zero ceilings).

Comparison is over the key INTERSECTION of baseline and fresh results:
the sharded lane's baseline may hold shard counts beyond the runner's
visible devices and those keys simply don't gate.  An empty intersection
is an error.  On failure the offending result records are printed as a
field-by-field JSON diff (baseline vs fresh), not just the bare ratio.

Two modes:

``--run-all``
    Run every manifest lane's bench subprocess (passing ``''`` for every
    other JSON flag so committed ``BENCH_*.json`` artifacts are never
    clobbered), gate each against its committed baseline, print a
    per-lane pass/fail table — also appended as markdown to
    ``$GITHUB_STEP_SUMMARY`` when set — and exit non-zero on any
    failure.

``--fresh PATH [--baseline PATH] [--max-ratio R]``
    Back-compat single-lane mode: gate one already-produced JSON.  The
    lane is auto-detected from the payload's ``bench`` field.

The committed baselines live under ``benchmarks/baselines/`` and are
refreshed only by the explicit ``make bench-baseline`` target — routine
``make bench-smoke`` runs write elsewhere and can never silently rebase
a gate.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(REPO, "benchmarks", "gates.json")


def load_manifest(path: str = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def load_payload(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as exc:
        print(f"bench-gate: cannot read {path}: {exc}", file=sys.stderr)
        sys.exit(2)


def index_results(payload, key_fields):
    return {
        tuple(r[k] for k in key_fields): r
        for r in payload.get("results", [])
    }


def record_diff(kind: str, cfg: str, base: dict, fresh: dict) -> str:
    """Field-by-field JSON diff of the offending result records."""
    lines = [f"bench-gate[{kind}] {cfg}: baseline vs fresh record diff:"]
    for field in sorted(set(base) | set(fresh)):
        b, f = base.get(field), fresh.get(field)
        if b == f:
            continue
        lines.append(
            f"  {field}: {json.dumps(b, default=str)} -> "
            f"{json.dumps(f, default=str)}"
        )
    return "\n".join(lines)


def check_lane(
    name: str,
    lane: dict,
    fresh_path: str,
    baseline_path=None,
    max_ratio=None,
    default_max_ratio: float = 2.0,
) -> int:
    """Gate one lane's fresh JSON.  Returns 0 pass / 1 fail / 2 error."""
    if max_ratio is None:
        max_ratio = float(lane.get("max_ratio", default_max_ratio))
    if baseline_path is None:
        baseline_path = os.path.join(REPO, lane["baseline"])
    if not os.path.exists(baseline_path):
        print(f"bench-gate: missing baseline {baseline_path}",
              file=sys.stderr)
        return 2
    fresh_payload = load_payload(fresh_path)
    baseline_payload = load_payload(baseline_path)
    kind = fresh_payload.get("bench")
    if baseline_payload.get("bench") != kind:
        print(
            f"bench-gate: baseline {baseline_path} is "
            f"{baseline_payload.get('bench')!r}, fresh is {kind!r}",
            file=sys.stderr,
        )
        return 2
    key_fields = tuple(lane["key"])
    baseline = index_results(baseline_payload, key_fields)
    fresh = index_results(fresh_payload, key_fields)
    common = sorted(set(baseline) & set(fresh), key=str)
    if not common:
        print(
            f"bench-gate[{name}]: no overlapping configs between "
            f"{baseline_path} and {fresh_path}", file=sys.stderr,
        )
        return 2
    lower_metrics = lane.get("metrics")
    failures = 0
    checks = 0
    for key in common:
        cfg = ",".join(f"{k}={v}" for k, v in zip(key_fields, key))
        if lower_metrics is None:
            base = float(baseline[key][lane["metric"]])
            new = float(fresh[key][lane["metric"]])
            floor = base / max_ratio
            verdict = "OK" if new >= floor else "REGRESSION"
            print(
                f"bench-gate[{name}] {cfg}: {lane['label']} "
                f"baseline=x{base:.2f} fresh=x{new:.2f} "
                f"floor=x{floor:.2f} -> {verdict}"
            )
            checks += 1
            if new < floor:
                failures += 1
                print(record_diff(name, cfg, baseline[key], fresh[key]))
            continue
        for m in lower_metrics:
            base = float(baseline[key][m["metric"]])
            new = float(fresh[key][m["metric"]])
            ceil = base * max_ratio + float(m.get("atol", 0.0))
            verdict = "OK" if new <= ceil else "REGRESSION"
            print(
                f"bench-gate[{name}] {cfg}: {m['label']} "
                f"baseline={base:.3f} fresh={new:.3f} "
                f"ceiling={ceil:.3f} -> {verdict}"
            )
            checks += 1
            if new > ceil:
                failures += 1
                print(record_diff(name, cfg, baseline[key], fresh[key]))
    if failures:
        print(
            f"bench-gate[{name}]: {failures}/{checks} check(s) "
            f"regressed >{max_ratio:.1f}x vs {baseline_path}",
            file=sys.stderr,
        )
        return 1
    print(f"bench-gate[{name}]: {checks} check(s) within {max_ratio:.1f}x")
    return 0


def lane_command(lane: dict, manifest: dict, fresh: str):
    """Build the subprocess argv that produces a lane's fresh JSON."""
    run = lane["run"]
    if "module" in run:
        return [sys.executable, "-m", run["module"]] + [
            a.replace("{fresh}", fresh) for a in run.get("args", [])
        ]
    cmd = [sys.executable, "-m", "benchmarks.run",
           "--only", run["only"], "--smoke"]
    for flag in manifest["json_flags"]:
        cmd += [flag, fresh if flag == run["json_flag"] else ""]
    return cmd


def run_lane(name: str, lane: dict, manifest: dict, fresh_dir: str):
    """Run one lane's bench subprocess.

    Returns (fresh_path, status, log): status is "ran" | "error".
    """
    fresh = os.path.join(fresh_dir, f"{name}.json")
    cmd = lane_command(lane, manifest, fresh)
    env = dict(os.environ)
    env.update(lane["run"].get("env", {}))
    env.setdefault("PYTHONPATH", os.path.join(REPO, "src"))
    proc = subprocess.run(
        cmd, cwd=REPO, env=env, capture_output=True, text=True
    )
    if proc.returncode != 0:
        return fresh, "error", proc.stdout + proc.stderr
    if not os.path.exists(fresh):
        return fresh, "error", (
            f"bench wrote no JSON at {fresh}\n{proc.stdout}{proc.stderr}"
        )
    return fresh, "ran", None


def write_step_summary(rows) -> None:
    path = os.environ.get("GITHUB_STEP_SUMMARY")
    if not path:
        return
    with open(path, "a") as f:
        f.write("## Bench gate\n\n| lane | status |\n|---|---|\n")
        for name, status in rows:
            icon = "✅" if status == "PASS" else "❌"
            f.write(f"| {name} | {icon} {status} |\n")
        f.write("\n")


def run_all(manifest: dict, only=None) -> int:
    rows = []
    failed = []
    default_ratio = float(manifest.get("default_max_ratio", 2.0))
    with tempfile.TemporaryDirectory(prefix="bench_gate_") as fresh_dir:
        for name, lane in manifest["lanes"].items():
            if only and only not in name:
                continue
            print(f"=== bench-gate lane: {name} ===", flush=True)
            fresh, status, log = run_lane(name, lane, manifest, fresh_dir)
            if status == "error":
                print(f"bench-gate[{name}]: bench run failed\n{log}")
                rows.append((name, "FAIL"))
                failed.append(name)
                continue
            rc = check_lane(name, lane, fresh,
                            default_max_ratio=default_ratio)
            rows.append((name, "PASS" if rc == 0 else "FAIL"))
            if rc != 0:
                failed.append(name)
    write_step_summary(rows)
    if failed:
        print(f"bench-gate: FAILED lanes: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(f"bench-gate: all {len(rows)} lane(s) passed")
    return 0


def detect_lane(manifest: dict, fresh_path: str):
    """Back-compat single-file mode: match the payload's bench kind to a
    manifest lane."""
    kind = load_payload(fresh_path).get("bench")
    for name, lane in manifest["lanes"].items():
        base = os.path.join(REPO, lane["baseline"])
        if os.path.exists(base) and \
                load_payload(base).get("bench") == kind:
            return name, lane
    print(
        f"bench-gate: no manifest lane matches bench kind {kind!r} "
        f"in {fresh_path}", file=sys.stderr,
    )
    sys.exit(2)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--run-all", action="store_true",
        help="run every manifest lane's bench and gate it",
    )
    parser.add_argument(
        "--only", default=None,
        help="with --run-all: substring filter on lane names",
    )
    parser.add_argument(
        "--fresh", default=None,
        help="single-lane mode: freshly produced smoke JSON to gate",
    )
    parser.add_argument(
        "--baseline", default=None,
        help="single-lane mode: baseline override (default: the lane's "
             "file under benchmarks/baselines/)",
    )
    parser.add_argument(
        "--max-ratio", type=float, default=None,
        help="single-lane mode: slack override (default: the lane's "
             "manifest value)",
    )
    parser.add_argument("--manifest", default=MANIFEST)
    args = parser.parse_args()
    manifest = load_manifest(args.manifest)
    if args.run_all:
        sys.exit(run_all(manifest, only=args.only))
    if not args.fresh:
        parser.error("need --run-all or --fresh PATH")
    name, lane = detect_lane(manifest, args.fresh)
    sys.exit(check_lane(
        name, lane, args.fresh,
        baseline_path=args.baseline, max_ratio=args.max_ratio,
        default_max_ratio=float(manifest.get("default_max_ratio", 2.0)),
    ))


if __name__ == "__main__":
    main()
