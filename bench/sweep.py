"""Find a configuration's knee: the highest Poisson rate it sustains.

    python bench/sweep.py --workload <cell> --rates 4 5 6 7 8 --seconds 30

One process and one set-up (the cell's configuration and length mix, the
seed's weights), then one open-loop window per rate, lowest first, each
drained before the next. A rate is sustained when the backlog (requests
due but not yet given a slot) does not grow over the window: its mean
over the last third is at most one request above its mean over the
middle third. Prints one JSON line per rate and a last line naming the
knee.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def sustained(rec, seconds: float) -> tuple:
    """(sustained?, backlog growth) of one window's record."""
    mid = [b for t, b in rec.backlog_trace if seconds / 3 <= t < 2 * seconds / 3]
    last = [b for t, b in rec.backlog_trace if 2 * seconds / 3 <= t < seconds]
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0
    growth = mean(last) - mean(mid)
    return growth <= 1.0, growth


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from bench import traffic
    from bench.harness import (build, device_info, e2e_metrics, load_cell,
                               log, serve_window)

    cellspec = load_cell(args.workload)
    model, mix = cellspec["model"], cellspec["mix"]
    device_info(True, cellspec["cell"]["chips"])
    st = build(model, args.seed)
    log(f"[sweep] set-up {time.perf_counter() - T0:.2f} s")
    knee = None
    rid0 = 0
    for rate in sorted(args.rates):
        plan = traffic.plan(dict(mix, rate_rps=rate), args.seed, args.seconds,
                            model["vocab_size"], extra_s=0.0)
        plan = [dataclasses.replace(p, rid=p.rid + rid0) for p in plan]
        rid0 += len(plan)
        rec = serve_window(st.engine, plan, args.seconds, model,
                           drain_cap=15.0)
        m = e2e_metrics(rec, ["ttft_p50_ms", "ttft_p95_ms", "itl_p95_ms",
                              "output_tok_s"])
        ok, growth = sustained(rec, args.seconds)
        row = {"rate_rps": rate, "sustained": ok,
               "backlog_growth": growth,
               "backlog_end": rec.backlog_trace[-1][1]
               if rec.backlog_trace else 0,
               **{k: v[0] for k, v in m["metrics"].items()}, **m["stats"],
               "ticks": len(rec.ticks),
               "tick_ms_mean": 1e3 * args.seconds / max(1, sum(
                   1 for t in rec.ticks.values() if t.t_end <= args.seconds))}
        print(json.dumps(row), flush=True)
        if ok:
            knee = rate
    print(json.dumps({"knee_rps": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
