"""Read the comparison's two ends for one cell on many seeds, in one
process: the program's numbers (sound runs) and the control's (the
reference in the program's place, its values rounded to bfloat16).

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

Prints one JSON line per seed.  The benchmark's own runs never run it;
``PERF.md`` gives the readings each limit was set from.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE))
sys.path.insert(0, str(_HERE.parent / "src"))

from benchlib import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=t0, control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "program": res["checks"],
                          "control": res["control"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
