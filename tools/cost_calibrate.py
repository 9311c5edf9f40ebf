#!/usr/bin/env python
"""Cost-model calibration gate (analysis/cost.py vs live telemetry).

For every app in tools/fastpath_gate.py's inventory: predict state
bytes and compile-ladder size statically, then build the real runtime,
measure allocated device state (`measure_runtime_state_bytes`) and count
actual warmup compiles, and fail if prediction drifts outside the band
(default 2x either way). This is the CI tripwire that keeps the SL5xx
admission-control math honest — a new operator that allocates state the
model doesn't price shows up here, not as a production OOM.

    python tools/cost_calibrate.py [--json] [--band 2.0]
    python tools/cost_calibrate.py --sweep   # zero-FP: no ERROR-severity
                                             # SL5xx on any known-good app

Exit codes: 0 = calibrated (or sweep clean), 1 = drift outside the band
(or an SL5xx false positive).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from fastpath_gate import APPS  # noqa: E402 — same-dir app inventory


def _ratio(live: float, predicted: float) -> float:
    if predicted <= 0:
        return 1.0 if live <= 0 else float("inf")
    return live / predicted


def calibrate(band: float) -> tuple[dict, list[str]]:
    from siddhi_tpu.analysis.cost import (compute_cost,
                                          measure_runtime_state_bytes)
    from siddhi_tpu.core.manager import SiddhiManager

    results: dict = {}
    failures: list[str] = []
    mgr = SiddhiManager()
    mgr._lint_enabled = False  # calibration measures, it doesn't gate
    for name, text in APPS.items():
        rep = compute_cost(text)
        if _shard_count(text) > 1:
            # @app:shards prediction is fleet-priced (x n), so the live
            # oracle must measure the REAL plane: build it through a
            # normal manager (the calibration one has plane construction
            # disabled along with the gates) and sum every replica
            pmgr = SiddhiManager()
            plane = pmgr.create_siddhi_app_runtime(text)
            live_bytes = sum(
                sum(measure_runtime_state_bytes(s).values())
                for s in plane.shards)
            plane.warmup()
            live_compiles = sum(
                sum(s.ctx.statistics.compiles.values())
                for s in plane.shards)
            rt = plane
            mgr_of_rt = pmgr
        else:
            rt = mgr.create_siddhi_app_runtime(text)
            live_bytes = sum(measure_runtime_state_bytes(rt).values())
            rt.warmup()
            live_compiles = sum(rt.ctx.statistics.compiles.values())
            mgr_of_rt = mgr
        r_state = _ratio(live_bytes, rep.state_bytes)
        r_comp = _ratio(live_compiles, rep.compile_ladder)
        results[name] = {
            "predicted_state_bytes": rep.state_bytes,
            "live_state_bytes": live_bytes,
            "state_ratio": round(r_state, 4),
            "predicted_compiles": rep.compile_ladder,
            "live_compiles": live_compiles,
            "compile_ratio": round(r_comp, 4),
            "exact": rep.exact,
        }
        for label, r in (("state", r_state), ("compiles", r_comp)):
            if not (1.0 / band <= r <= band):
                failures.append(
                    f"{name}: {label} drifted {r:.3f}x outside "
                    f"[{1.0 / band:.2f}, {band:.2f}]")
        rt.shutdown()
        mgr_of_rt.runtimes.pop(rt.app.name, None)
    return results, failures


def _shard_count(text: str) -> int:
    from siddhi_tpu import compiler
    from siddhi_tpu.analysis.sharding import shard_config
    try:
        cfg = shard_config(compiler.parse(text))
    except Exception:
        return 0
    return 0 if cfg is None else cfg.n


TRIPLE = re.compile(r"(\"\"\"|''')(.*?)\1", re.DOTALL)


def _in_tree_app_strings():
    """Every triple-quoted SiddhiQL-looking string under tests/ + samples/
    (same extraction as tests/test_lint.py's zero-FP sweep), plus the gate's
    inventory itself."""
    for name, text in APPS.items():
        yield f"fastpath_gate:{name}", text
    for root in ("tests", "samples"):
        for p in (REPO / root).rglob("*.py"):
            for m in TRIPLE.finditer(p.read_text()):
                s = m.group(2)
                if "define stream" in s and (
                        "insert into" in s or "select" in s):
                    yield str(p), s


def sweep() -> tuple[dict, list[str]]:
    """Zero-false-positive check: no known-good in-tree app may draw an
    ERROR-severity SL5xx finding (budget rules only fire when a budget is
    configured — a clean environment must stay clean)."""
    from siddhi_tpu import compiler
    from siddhi_tpu.analysis import Severity, analyze

    checked = 0
    failures: list[str] = []
    for src, text in _in_tree_app_strings():
        try:
            app = compiler.parse(text)
        except Exception:
            continue  # deliberately-invalid fixtures are out of scope
        try:
            report = analyze(app)
        except Exception:
            continue
        checked += 1
        hits = [d for d in report.diagnostics
                if d.rule_id.startswith("SL5")
                and d.severity is Severity.ERROR]
        for d in hits:
            failures.append(f"{src}: {d.format()}")
    if checked < 25:
        failures.append(f"sweep found too few parseable apps ({checked})")
    return {"apps_checked": checked}, failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--band", type=float, default=2.0,
                    help="allowed live/predicted drift factor (default 2x)")
    ap.add_argument("--sweep", action="store_true",
                    help="run the SL5xx zero-false-positive sweep instead "
                         "of the calibration pass")
    args = ap.parse_args(argv)

    # the gate measures the model, not the operator's shell: a stray budget
    # env would turn predictions into refusals mid-calibration
    for var in ("SIDDHI_STATE_BUDGET", "SIDDHI_COMPILE_BUDGET",
                "SIDDHI_BUDGET_MODE", "SIDDHI_LINT"):
        os.environ.pop(var, None)

    if args.sweep:
        results, failures = sweep()
    else:
        results, failures = calibrate(args.band)

    if args.as_json:
        print(json.dumps({"results": results, "failures": failures},
                         indent=2))
    else:
        if not args.sweep:
            for name, r in results.items():
                print(f"{name}: state {r['live_state_bytes']}/"
                      f"{r['predicted_state_bytes']}B "
                      f"({r['state_ratio']}x), compiles "
                      f"{r['live_compiles']}/{r['predicted_compiles']} "
                      f"({r['compile_ratio']}x)")
        else:
            print(f"sweep: {results['apps_checked']} apps checked")
        for f in failures:
            print(f"DRIFT {f}" if not args.sweep else f"FP {f}")
        print(f"cost calibration: {len(failures)} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
