"""SIDDHI_PROFILE=<dir>: the operator's way to open a profiler session.

When set, the first SiddhiAppRuntime.start() in the process opens a
jax.profiler trace into <dir> (viewable in TensorBoard / Perfetto) and the
runtime that opened it closes it on shutdown. One trace per process —
concurrent apps share the capture. Inside it the served path's `siddhi.*`
stage spans (telemetry/tracing.py `Span`) lie in `/host:CPU` beside the
device's own planes, on one clock, with no device sync added.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

from ..util.locks import named_lock

log = logging.getLogger("siddhi_tpu.telemetry")

_jax_trace_lock = named_lock("telemetry.profile.jax")
_jax_trace_dir: Optional[str] = None


def maybe_start_jax_profiler() -> bool:
    """Start the process-wide jax.profiler trace if SIDDHI_PROFILE is set
    and no capture is already running. Returns True when THIS call started
    the capture (the caller then owns stop_jax_profiler())."""
    target = os.environ.get("SIDDHI_PROFILE", "").strip()
    if not target:
        return False
    global _jax_trace_dir
    with _jax_trace_lock:
        if _jax_trace_dir is not None:
            return False
        try:
            import jax
            jax.profiler.start_trace(target)
        except Exception as e:  # pragma: no cover — platform-dependent
            log.warning("SIDDHI_PROFILE=%s: trace capture unavailable: %s",
                        target, e)
            return False
        _jax_trace_dir = target
        log.info("jax.profiler trace capture -> %s", target)
        return True


def stop_jax_profiler() -> None:
    global _jax_trace_dir
    with _jax_trace_lock:
        if _jax_trace_dir is None:
            return
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:  # pragma: no cover
            log.warning("jax.profiler stop_trace failed: %s", e)
        _jax_trace_dir = None
