#!/usr/bin/env python3
"""Measure cells the way the driver admits them: for each cell, sets of runs
of the same code, each run a process of its own with another `--seed`, and
for each end-to-end metric every set's median and spread (the distance
between the quartiles over the median). A bound should be about five times
the widest spread over the cells, and never under 1 %.

    python benchmarks/measure.py --cells <a,b,...> [--sets 2] [--runs 6]
        [--seconds <run_seconds>] [--traced 1] [--first-seed 1000]

Never touches jax itself, so each run gets the chip. Prints one JSON line
per cell and writes everything, every run's lines included, to
`benchmarks/out/measure.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def one_run(cell: str, seed: int, seconds: float, trace: int,
            extra=()) -> dict:
    """One run.py process: its exit code, its result line and its detail
    line, where it printed them."""
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
           cell, "--seed", str(seed), "--seconds", str(seconds), "--trace",
           str(trace), *extra]
    proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    out = {"seed": seed, "trace": trace, "rc": proc.returncode}
    if lines:
        out["result"] = json.loads(lines[-1])
    if len(lines) > 1:
        out["detail"] = json.loads(lines[-2])
    return out


def spread(values) -> dict:
    v = np.asarray(values, np.float64)
    q1, med, q3 = np.percentile(v, [25, 50, 75])
    return {"n": int(v.size), "median": float(med),
            "spread": float((q3 - q1) / med) if med else None,
            "min": float(v.min()), "max": float(v.max())}


def summarise(sets: list) -> dict:
    """metric -> per-set median and spread, the wider spread, and how far
    the second set's median lies from the first's."""
    names = sorted({n for s in sets for r in s
                    for n in r.get("result", {}).get("metrics", {})})
    out = {}
    for name in names:
        per_set = []
        for i, s in enumerate(sets):
            # the first run of the first set compiles: its set-up is apart
            vals = [r["result"]["metrics"][name]["value"]
                    for j, r in enumerate(s)
                    if "result" in r and name in r["result"]["metrics"]
                    and not (name == "setup_s" and i == 0 and j == 0)]
            per_set.append(spread(vals) if vals else None)
        got = [p for p in per_set if p]
        out[name] = {
            "sets": per_set,
            "widest_spread": max((p["spread"] for p in got), default=None),
            "second_over_first": (got[1]["median"] / got[0]["median"] - 1
                                  if len(got) > 1 else None),
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--traced", type=int, default=0,
                    help="traced runs per cell, after the sets")
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)
    if args.seconds is None:
        with open(os.path.join(os.path.dirname(BENCH_DIR),
                               "BENCHMARK.json")) as fh:
            args.seconds = json.load(fh)["run_seconds"]
    seed = args.first_seed
    report = {"seconds": args.seconds, "cells": {}}
    for cell in args.cells.split(","):
        sets = []
        for _ in range(args.sets):
            runs = []
            for _ in range(args.runs):
                runs.append(one_run(cell, seed, args.seconds, 0))
                seed += 1
            sets.append(runs)
        traced = []
        for _ in range(args.traced):
            traced.append(one_run(cell, seed, args.seconds, 1))
            seed += 1
        every = [r for s in sets for r in s] + traced
        cell_report = {
            "summary": summarise(sets),
            "all_correct": all(r.get("result", {}).get("correct")
                               for r in every),
            "failed_events": sum(r.get("result", {}).get("failed", 0)
                                 for r in every),
            "first_run_setup_s": sets[0][0].get("result", {}).get(
                "metrics", {}).get("setup_s", {}).get("value")
            if sets and sets[0] else None,
            "sets": sets, "traced": traced,
        }
        report["cells"][cell] = cell_report
        print(json.dumps({"cell": cell, **{
            k: v for k, v in cell_report.items()
            if k not in ("sets", "traced")}}), flush=True)
    os.makedirs(os.path.join(BENCH_DIR, "out"), exist_ok=True)
    with open(os.path.join(BENCH_DIR, "out", "measure.json"), "w") as fh:
        json.dump(report, fh)
    return 0 if all(c["all_correct"] for c in report["cells"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
