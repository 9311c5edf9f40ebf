#!/usr/bin/env python3
"""Find, once, on the chip, the highest rate a configuration sustains.

    python benchmarks/find_knee.py --config <name> [--seconds 15]
        [--fractions 0.6,0.7,0.8,0.9,1.0,1.1] [--seed 100]

Runs `<config>.saturate` to read the closed-loop capacity C, then
`<config>.paced` at each fraction of C (run.py `--rate`), one process after
another (this one never touches jax, so each child gets the chip). A rate
is *sustained* when no event failed and the backlog — events offered by
their due time minus input events whose results reached the callback —
grew over the second half of the window by less than 2 % of what was
offered in it. The knee is the highest sustained rate; the paced cells run
at four fifths of it. The table goes to stdout and to
`benchmarks/out/knee_<config>.json`; a person then writes the rate, as a
plain number, into `workloads/<config>.paced.json`. No run recomputes it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import measure  # noqa: E402

GROWTH_LIMIT = 0.02


def run_once(workload: str, seed: int, seconds: float, rate: float = 0.0,
             extra=()) -> tuple:
    """(result line, detail line) of one run.py process."""
    if rate:
        extra = (*extra, "--rate", str(rate))
    out = measure.one_run(workload, seed, seconds, 0, extra)
    if "detail" not in out:
        raise SystemExit(f"{workload} at rate {rate or 'closed'} exited "
                         f"{out['rc']} with no result")
    return out["result"], out["detail"]


def sustained(result: dict, detail: dict) -> bool:
    back = detail["backlog"]
    grew = back["second_half_growth_events_per_s"]
    return (result["correct"] and result["failed"] == 0
            and grew < GROWTH_LIMIT * back["offered_events_per_s"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--fractions", default="0.6,0.7,0.8,0.9,1.0,1.1")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    extra = ("--rehearse",) if args.rehearse else ()
    result, detail = run_once(f"{args.config}.saturate", args.seed,
                              args.seconds, extra=extra)
    capacity = result["metrics"]["events_per_s"]["value"]
    rows = [{"rate": None, "mode": "closed", "events_per_s": capacity,
             "failed": result["failed"], "correct": result["correct"]}]
    print(json.dumps(rows[-1]), flush=True)
    knee = None
    for i, frac in enumerate(float(f) for f in args.fractions.split(",")):
        rate = round(capacity * frac)
        result, detail = run_once(f"{args.config}.paced", args.seed + 1 + i,
                                  args.seconds, rate=rate, extra=extra)
        back = detail["backlog"]
        row = {
            "rate": rate, "fraction_of_closed_loop": frac, "mode": "paced",
            "events_per_s": detail["end_to_end"]["events_per_s"],
            "backlog_growth_events_per_s":
                back["second_half_growth_events_per_s"],
            "growth_share_of_offered":
                back["second_half_growth_events_per_s"]
                / max(back["offered_events_per_s"], 1.0),
            "latency_p50_ms": detail["end_to_end"]["latency_p50_ms"],
            "latency_p95_ms":
                detail["per_layer"]["served.latency_p95_ms"],
            "failed": result["failed"], "correct": result["correct"],
            "sustained": sustained(result, detail),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
        if row["sustained"] and (knee is None or rate > knee):
            knee = rate
    verdict = {"config": args.config, "device": result["device"],
               "seconds": args.seconds, "closed_loop_events_per_s": capacity,
               "knee_events_per_s": knee,
               "paced_rate_events_per_s":
                   None if knee is None else round(0.8 * knee),
               "sweep": rows}
    os.makedirs(os.path.join(BENCH_DIR, "out"), exist_ok=True)
    with open(os.path.join(BENCH_DIR, "out", f"knee_{args.config}.json"),
              "w") as fh:
        json.dump(verdict, fh, indent=1)
    print(json.dumps({k: v for k, v in verdict.items() if k != "sweep"}))
    return 0 if knee else 1


if __name__ == "__main__":
    sys.exit(main())
