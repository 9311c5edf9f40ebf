"""`python -m siddhi_tpu.lint` — lint SiddhiQL apps from the command line.

    python -m siddhi_tpu.lint app.siddhi [more.siddhi ...]
    python -m siddhi_tpu.lint --json app.siddhi
    python -m siddhi_tpu.lint --jaxpr app.siddhi     # + compiled-step hazards
    python -m siddhi_tpu.lint --scan samples/        # every *.siddhi under
    python -m siddhi_tpu.lint --self                 # SL40x concurrency lint
                                                     # over the engine source

Exit codes: 0 = no ERROR findings anywhere, 1 = at least one ERROR,
2 = a file could not be read or parsed (parse failures also surface as an
SL000 ERROR diagnostic so JSON consumers see one uniform shape).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from .analysis import Diagnostic, LintReport, Severity, analyze
from .errors import SiddhiParserError


def lint_text(text: str, *, name: str = "SiddhiApp",
              jaxpr: bool = False) -> LintReport:
    """Lint one app source. Parse failures become an SL000 ERROR diagnostic
    instead of an exception, so callers always get a report."""
    try:
        return analyze(text, jaxpr=jaxpr, name=name)
    except SiddhiParserError as e:
        report = LintReport(app_name=name)
        loc = (e.line, e.column) if e.line is not None else None
        # first line only: the Diagnostic re-renders loc, and the caret
        # snippet doesn't survive single-line report formats
        import re as _re
        msg = _re.sub(r"\s+at line -?\d+:-?\d+$", "",
                      str(e).split("\n")[0])
        report.add(Diagnostic("SL000", Severity.ERROR,
                              f"parse error: {msg}", element=name, loc=loc))
        return report


def _print_cost(path: str, cost: dict) -> None:
    """The --cost pretty-printer over a CostReport.to_dict() section."""
    from .analysis import format_size

    exact = "" if cost.get("exact") else " (estimate)"
    print(f"{path}: cost: "
          f"{format_size(cost['predicted_state_bytes'])} device state, "
          f"{cost['predicted_compiles']} compile(s){exact}")
    dom = cost.get("dominant")
    if dom:
        print(f"{path}: cost: dominant element {dom['element']!r} holds "
              f"{format_size(dom['state_bytes'])} ({dom['share']:.0%})")
    budget = cost.get("budget")
    if budget:
        state = budget.get("state_bytes")
        limit = (format_size(state) if state is not None else "-",
                 budget.get("compiles"))
        verdict = "over" if (
            (state is not None and cost["predicted_state_bytes"] > state)
            or (budget.get("compiles") is not None
                and cost["predicted_compiles"] > budget["compiles"])
        ) else "within"
        print(f"{path}: cost: budget state={limit[0]} "
              f"compiles={limit[1] if limit[1] is not None else '-'} "
              f"({budget.get('source')}, mode={budget.get('mode')}) — "
              f"{verdict} budget")


def _collect(paths: list[str], scan: bool) -> list[Path]:
    files: list[Path] = []
    for p in paths:
        path = Path(p)
        if path.is_dir():
            if scan:
                files.extend(sorted(path.rglob("*.siddhi")))
            else:
                raise SystemExit(
                    f"{path} is a directory (use --scan to recurse)")
        else:
            files.append(path)
    return files


def main(argv: list[str] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m siddhi_tpu.lint",
        description="Static lint for SiddhiQL apps (rule reference: "
                    "docs/LINT.md)")
    ap.add_argument("paths", nargs="*", help="*.siddhi files (or "
                    "directories with --scan)")
    ap.add_argument("--self", action="store_true", dest="self_mode",
                    help="lint the engine's own Python source with the "
                         "SL40x concurrency catalog instead of SiddhiQL "
                         "files (docs/CONCURRENCY.md)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit one JSON object {file: report} on stdout")
    ap.add_argument("--jaxpr", action="store_true",
                    help="also trace each query's compiled step for "
                         "host-callback/float64/upcast hazards (slower)")
    ap.add_argument("--scan", action="store_true",
                    help="recurse into directories for *.siddhi files")
    ap.add_argument("--max-severity", choices=["error", "warn", "info"],
                    default="info",
                    help="hide findings below this severity")
    ap.add_argument("--cost", action="store_true",
                    help="also print each app's static cost prediction "
                         "(state bytes, compile ladder, dominant element, "
                         "budget verdict — docs/COST.md)")
    args = ap.parse_args(argv)

    max_rank = {"error": 0, "warn": 1, "info": 2}[args.max_severity]
    if args.self_mode:
        from .analysis import lint_package
        report = lint_package()
        if args.as_json:
            print(json.dumps({report.app_name: report.to_dict()}, indent=2))
        else:
            for d in report.sorted():
                if d.severity.rank <= max_rank:
                    print(d.format())
            n_err, n_warn = len(report.errors), len(report.warnings)
            print(f"{report.app_name}: {n_err} error(s), {n_warn} "
                  f"warning(s), "
                  f"{len(report.diagnostics) - n_err - n_warn} info")
        return 1 if report.has_errors else 0
    if not args.paths:
        ap.error("paths are required unless --self is given")

    try:
        files = _collect(args.paths, args.scan)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2

    had_error = False
    had_io_or_parse_failure = False
    results: dict[str, dict] = {}

    for path in files:
        try:
            text = path.read_text()
        except OSError as e:
            print(f"{path}: {e}", file=sys.stderr)
            had_io_or_parse_failure = True
            continue
        report = lint_text(text, name=str(path), jaxpr=args.jaxpr)
        if any(d.rule_id == "SL000" for d in report.diagnostics):
            had_io_or_parse_failure = True
        if report.has_errors:
            had_error = True
        if args.as_json:
            results[str(path)] = report.to_dict()
        else:
            shown = [d for d in report.sorted()
                     if d.severity.rank <= max_rank]
            for d in shown:
                print(f"{path}: {d.format()}")
            n_err = len(report.errors)
            n_warn = len(report.warnings)
            print(f"{path}: {n_err} error(s), {n_warn} warning(s), "
                  f"{len(report.diagnostics) - n_err - n_warn} info")
            if args.cost and report.cost is not None:
                _print_cost(str(path), report.cost)

    if args.as_json:
        print(json.dumps(results, indent=2))
    if had_io_or_parse_failure:
        return 2
    return 1 if had_error else 0


if __name__ == "__main__":
    raise SystemExit(main())
