"""siddhi_tpu — a TPU-native streaming & complex event processing framework.

A ground-up re-design of the capabilities of the Siddhi CEP engine (reference:
io.siddhi 5.1.x, Java) for TPU hardware: SiddhiQL streaming SQL compiled to
jitted JAX/XLA kernels over columnar event micro-batches, window/NFA state in
device ring buffers, group-by as segment reductions, keyed partitioning as a
sharded axis over a `jax.sharding.Mesh`.

Public API mirrors the reference's user surface (core/SiddhiManager.java:50):

    from siddhi_tpu import SiddhiManager

    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime('''
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q1')
        from StockStream[price > 20.0] select symbol, price insert into OutStream;
    ''')
    rt.add_callback("OutStream", lambda events: print(events))
    rt.start()
    rt.get_input_handler("StockStream").send(("IBM", 75.6, 100))
    rt.flush()
"""

# LONG attributes and millisecond timestamps are int64 on device, matching the
# reference's Java longs; jax x64 must be enabled before any tracing happens.
import jax as _jax

_jax.config.update("jax_enable_x64", True)
# XLA:CPU's asynchronous dispatch can DEADLOCK nondeterministically when a
# jitted computation carrying a host callback (ops/windows_extra.py
# CronWindow's pure_callback to the cron clock) runs concurrently with
# device_get readbacks from other threads (the async stream-callback
# decoder) — observed as a 0%-CPU wall-clock hang on single-core hosts.
# Synchronous dispatch costs nothing here: the engine is already
# one-controller-synchronous per micro-batch, and on CPU "device" compute
# shares the very cores async dispatch would overlap with. TPU and other
# backends are unaffected by this CPU-only flag.
_jax.config.update("jax_cpu_enable_async_dispatch", False)

from . import compiler  # noqa: E402
from . import io  # noqa: E402,F401  (registers source/sink/mapper extensions)
from .core import function as _function  # noqa: E402,F401  (script engines)
from .ops import stream_functions as _stream_functions  # noqa: E402,F401
from .core.dtypes import config  # noqa: E402
from .core.event import Event  # noqa: E402
from .core.stream import (  # noqa: E402
    BatchStreamCallback,
    ColumnarBlock,
    StreamCallback,
)
from .core.manager import SiddhiManager  # noqa: E402
from .errors import SiddhiError, SiddhiParserError  # noqa: E402
from .query_api import SiddhiApp  # noqa: E402
from .telemetry.logs import configure_logging as _configure_logging  # noqa: E402

_configure_logging()  # no-op unless SIDDHI_LOG_FORMAT=json

__version__ = "0.1.0"

__all__ = [
    "SiddhiManager",
    "SiddhiApp",
    "Event",
    "ColumnarBlock",
    "BatchStreamCallback",
    "StreamCallback",
    "compiler",
    "config",
    "SiddhiError",
    "SiddhiParserError",
    "__version__",
]
