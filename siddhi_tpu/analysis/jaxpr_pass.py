"""Jaxpr hazard pass (SL2xx): trace each query's compiled step abstractly
and walk the jaxpr for device-hostile constructs.

The pass builds a *sandbox* runtime (sources/sinks/stores stripped, nothing
started) and runs `jax.make_jaxpr` over every step function with the same
abstract arguments the warmup path uses — so it sees exactly the program the
runtime would compile, at tracing cost only: no XLA compile, no device
allocation.

Hazards:
  SL201  host callbacks (`pure_callback`, `io_callback`, debug prints):
         every step invocation round-trips device→host→device, serializing
         the dispatch queue (e.g. #window.cron asks the host's cron clock
         for its next fire time, ops/windows_extra.py).
  SL202  float64 avals in the step: on TPU f64 is emulated (~10x slower);
         usually a leaked `jax_enable_x64` literal.
  SL203  widening `convert_element_type` ops: silent upcasts that double a
         column's HBM footprint mid-step.
  SL204  fastpath NOT certified: the step carries a veto — host callbacks
         or ordered jaxpr effects — that knocks pjit off its C++
         no-Python dispatch fastpath, re-paying interpreter overhead on
         every batch. `fastpath_certify(app)` returns the per-step
         verdicts; tools/fastpath_gate.py keeps the apps of its inventory
         from regressing.

Never raises over a query: one whose step cannot be traced here is skipped
(and the skip is logged at debug), because the runtime build path owns those
errors. Importing this module does raise on a jax whose jaxpr classes it
cannot find, since the walk would then see nothing below the top level.
"""

from __future__ import annotations

import logging

# no getattr-with-default here: a walk that cannot recognise a nested jaxpr
# sees only a step's top-level equations and certifies the rest unseen, so a
# jax that keeps these classes nowhere we know must stop the import
try:
    from jax.extend.core import ClosedJaxpr, Jaxpr
except ImportError:  # an older jax keeps them in jax.core
    from jax.core import ClosedJaxpr, Jaxpr

from .diagnostics import Diagnostic, LintReport, Severity

log = logging.getLogger("siddhi_tpu.lint")

_CALLBACK_PRIMS = ("pure_callback", "io_callback", "callback",
                   "debug_callback", "outside_call")


def _sub_jaxprs(value):
    """Yield any jaxprs nested inside an eqn param value."""
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _walk(jaxpr, visit) -> None:
    for eqn in jaxpr.eqns:
        visit(eqn)
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _walk(sub, visit)


class _Hazards:
    """Hazard accumulator for one step function."""

    def __init__(self) -> None:
        self.callbacks: set[str] = set()
        self.f64: set[str] = set()
        self.upcasts: set[tuple[str, str]] = set()
        self.effects: set[str] = set()

    @property
    def fastpath_vetoes(self) -> list[str]:
        """Why pjit's C++ fastpath would reject this step (empty=certified)."""
        vetoes = []
        if self.callbacks:
            vetoes.append("host callback(s): "
                          + ", ".join(sorted(self.callbacks)))
        if self.effects:
            vetoes.append("ordered effect(s): "
                          + ", ".join(sorted(self.effects)))
        return vetoes

    def visit(self, eqn) -> None:
        import numpy as np

        prim = eqn.primitive.name
        if any(prim == c or prim.endswith("_" + c) for c in _CALLBACK_PRIMS):
            cb = eqn.params.get("callback")
            tag = getattr(cb, "__name__", None) or getattr(
                getattr(cb, "callback_func", None), "__name__", None) or prim
            self.callbacks.add(str(tag))
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and np.dtype(dt) == np.float64:
                self.f64.add(prim)
        if prim == "convert_element_type":
            new = np.dtype(eqn.params.get("new_dtype"))
            srcs = [getattr(getattr(v, "aval", None), "dtype", None)
                    for v in eqn.invars]
            for src in srcs:
                if src is None:
                    continue
                src = np.dtype(src)
                if (new.kind in "fiu" and src.kind in "fiu"
                        and new.itemsize > src.itemsize):
                    self.upcasts.add((src.name, new.name))

    def report(self, report: LintReport, qname: str, suppressions,
               anchor=None, loc=None) -> None:
        def add(rule_id, severity, message):
            if suppressions.is_suppressed(rule_id, anchor):
                return
            report.add(Diagnostic(rule_id, severity, message,
                                  element=qname, loc=loc))

        if self.callbacks:
            add("SL201", Severity.WARN,
                "compiled step calls back to the host every batch "
                f"({', '.join(sorted(self.callbacks))}): device→host→device "
                "round-trip serializes dispatch (e.g. #window.cron asks "
                "the host for its next fire time)")
        if self.f64:
            add("SL202", Severity.WARN,
                "float64 values flow through the compiled step "
                f"(first seen in: {', '.join(sorted(self.f64))}); TPUs "
                "emulate f64 — keep jax_enable_x64 off or cast explicitly")
        for src, dst in sorted(self.upcasts):
            add("SL203", Severity.INFO,
                f"step silently widens {src} → {dst} "
                "(convert_element_type): doubles that column's footprint "
                "per batch")
        vetoes = self.fastpath_vetoes
        if vetoes:
            add("SL204", Severity.WARN,
                "step is NOT fastpath-certified: "
                + "; ".join(vetoes)
                + " — pjit falls back to Python dispatch every batch")


def _trace_hazards(step_fn, *args) -> _Hazards:
    import jax

    hazards = _Hazards()
    fn = getattr(step_fn, "__wrapped__", step_fn)
    jaxpr = jax.make_jaxpr(fn)(*args)
    for eff in getattr(jaxpr, "effects", ()) or ():
        hazards.effects.add(type(eff).__name__)
    _walk(jaxpr.jaxpr, hazards.visit)
    return hazards


def _steps_of(qr):
    """(tag, step_fn, args) triples for one runtime's jitted steps."""
    import jax.numpy as jnp

    from ..core.event import EventBatch

    now = jnp.int64(0)
    if hasattr(qr, "_step") and hasattr(qr, "_table_states"):
        batch = EventBatch.empty(qr.input_junction.definition, qr._batch_cap)
        yield "", qr._step, (qr.state, batch, now, qr._table_states())
    elif hasattr(qr, "_step_left"):  # join: step(state, batch, now, tstate)
        for from_left, tag in ((True, "/left"), (False, "/right")):
            side = qr.left if from_left else qr.right
            build = qr.right if from_left else qr.left
            if side.junction is None:
                continue
            if build.is_table:
                tstate = build.table.state
            elif build.is_named_window:
                tstate = build.named_window.state
            elif build.is_aggregation:
                tstate = build.agg_view.state
            else:
                tstate = None
            batch = EventBatch.empty(side.junction.definition,
                                     side.junction.batch_size)
            step = qr._step_left if from_left else qr._step_right
            yield tag, step, (qr.state, batch, now, tstate)
    elif hasattr(qr, "_steps") and hasattr(qr, "_feed_junction"):  # pattern
        for sid, step in qr._steps.items():
            junction = qr._feed_junction(sid)
            batch = EventBatch.empty(junction.definition,
                                     junction.batch_size)
            yield f"/{sid}", step, (qr.state, batch, now)


def fastpath_certify(app) -> dict:
    """Per-step fastpath verdicts for one app (SiddhiApp or SiddhiQL text):
    {step_name: {"certified": bool, "vetoes": [reason, ...]}}.

    A certified step carries no host callback and no ordered effect, so
    pjit's C++ no-Python dispatch can serve it. Steps that fail to trace
    are reported as {"certified": False, "vetoes": ["trace failed: ..."]}
    — an untraceable step cannot be certified."""
    from ..core.manager import SiddhiManager

    if isinstance(app, str):
        from ..compiler import SiddhiCompiler
        app = SiddhiCompiler.parse(app)
    out: dict = {}
    manager = SiddhiManager()
    manager._lint_enabled = False
    try:
        rt = manager.create_sandbox_siddhi_app_runtime(app)
        for name, qr in rt.query_runtimes.items():
            try:
                for tag, step, args in _steps_of(qr):
                    hazards = _trace_hazards(step, *args)
                    vetoes = hazards.fastpath_vetoes
                    out[f"{name}{tag}"] = {"certified": not vetoes,
                                           "vetoes": vetoes}
            except Exception as e:  # noqa: BLE001 — per-step best effort
                out[name] = {"certified": False,
                             "vetoes": [f"trace failed: {e}"]}
    finally:
        try:
            manager.shutdown()
        except Exception:
            log.debug("fastpath certify: manager shutdown failed",
                      exc_info=True)
    return out


def run_jaxpr_pass(app, report: LintReport, suppressions) -> None:
    """Trace every query step of `app` in a sandbox runtime and append
    SL201/SL202/SL203 findings to `report`. Best effort by design."""
    from ..core.manager import SiddhiManager

    manager = SiddhiManager()
    manager._lint_enabled = False
    try:
        try:
            rt = manager.create_sandbox_siddhi_app_runtime(app)
        except Exception:
            log.debug("jaxpr pass: sandbox build failed; pass skipped",
                      exc_info=True)
            return
        for name, qr in rt.query_runtimes.items():
            query = getattr(qr, "query", None)
            loc = getattr(query, "loc", None)
            try:
                for tag, step, args in _steps_of(qr):
                    hazards = _trace_hazards(step, *args)
                    hazards.report(report, f"{name}{tag}", suppressions,
                                   anchor=query, loc=loc)
            except Exception:
                log.debug("jaxpr pass: tracing %s failed; query skipped",
                          name, exc_info=True)
    finally:
        try:
            manager.shutdown()
        except Exception:
            log.debug("jaxpr pass: manager shutdown failed", exc_info=True)
