"""Find a cell's knee: the highest offered rate at which the server
still answers nearly every request within its deadline.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 8 \\
        --rates 500,1000,2000

One process builds the store and warms up once, then runs one open-loop
window per rate, each over a fresh scheduler, and prints one JSON line
per rate: the offered rate, ``p50_ms``, ``p95_ms``, ``goodput_qps`` and
the share of requests that met their deadline.  A cell's mix file fixes
its rate from such a sweep; the benchmark's runs never search for one.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE))
sys.path.insert(0, str(_HERE.parent / "src"))

from benchlib import harness, mixgen, openloop  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    p = harness.prepare(args.workload)
    mix = p.mix
    _, pools = mixgen.generate(mix, p.view, args.seconds, args.seed)
    t0 = time.perf_counter()
    launches = harness.warm_up(p.engine, p.cfg, mix, pools)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "build_s": p.build_s,
                      "warmup_s": time.perf_counter() - t0,
                      "warmup_launches": launches,
                      "compiles": p.clock.count,
                      "compile_s": p.clock.seconds}), flush=True)
    for rate in rates:
        m = dict(mix, arrivals=dict(mix["arrivals"], rate_qps=rate))
        reqs, _ = mixgen.generate(m, p.view, args.seconds, args.seed)
        rec, stats, compiles = harness.measure(p, reqs, args.seconds)
        lat = openloop.latencies_ms(rec)
        met = float((lat <= rec.deadline_ms).mean())

        def fin(x):
            return x if math.isfinite(x) else None

        print(json.dumps({
            "rate_qps": rate, "requests": len(reqs),
            "p50_ms": fin(openloop.percentile(lat, 50)),
            "p95_ms": fin(openloop.percentile(lat, 95)),
            "goodput_qps": openloop.goodput_qps(rec),
            "met_share": met,
            "late_p95_ms": openloop.percentile(openloop.late_ms(rec), 95),
            "wall_s": rec.wall_s, "compiles_in_window": compiles,
            "counters": stats}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
