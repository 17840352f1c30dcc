"""Read the control beside the program: one run of a cell, whose compared
requests also go through the reference computed in float8, put in the
program's place and judged by the same limits.

    python bench/control.py --workload <cell> --seed <n> --seconds 10

The benchmark's own runs never compute the control. This prints one
JSON line with both sides' readings, checks and verdicts, and exits 0
only where the program reads correct and the control does not.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench.harness import run
    res = run(args.workload, args.seed, args.seconds, False, t_start=T0,
              control=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      **res["readings"], "checks": res["checks"],
                      "correct": res["correct"],
                      "control_checks": res["control"]["checks"],
                      "control_correct": res["control"]["correct"]}))
    if not res["correct"] or res["control"]["correct"]:
        print("control: the program must read correct and the control "
              "not", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
