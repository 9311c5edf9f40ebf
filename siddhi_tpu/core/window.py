"""Named windows — `define window W (...) <window spec> output <type> events`.

Reference: core/window/Window.java:65 — a shared window entity: queries
`insert into W` feed it, queries `from W ...` receive its emissions (CURRENT on
arrival, EXPIRED on expiry, filtered by the definition's `output ... events`
clause), joins and on-demand queries probe its current contents through the
FindableProcessor surface.

TPU design: ONE jitted append step per named window — `(wstate, batch, now) ->
(wstate', chunk)` — whose state pytree lives on device and is shared by every
consumer. Downstream `from W` queries subscribe to the window's output
junction; the emitted chunk rides device-to-device (no host hop). Joins and
pull queries read `WindowOp.contents(state, now)` — the same ring the append
step maintains, so there is no copy-per-consumer the way the reference clones
StreamEvents per findable processor.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..errors import SiddhiAppCreationError
from ..extension.registry import Registry
from ..ops.window_factories import make_window
from ..ops.windows import WindowOp
from ..query_api.definition import AttributeType, StreamDefinition, WindowDefinition
from . import dtypes
from .context import SiddhiAppContext
from .event import EventBatch, EventType, StreamCodec
from .stream import StreamJunction


class NamedWindow:
    """Runtime for one `define window` (reference: core/window/Window.java:65)."""

    def __init__(self, definition: WindowDefinition, ctx: SiddhiAppContext,
                 registry: Registry) -> None:
        self.definition = definition
        self.ctx = ctx
        self.attr_types = {a.name: a.type for a in definition.attributes
                           if a.type != AttributeType.OBJECT}
        # the window's emission stream shares the definition's schema
        self.stream_definition = StreamDefinition(
            id=definition.id, attributes=definition.attributes,
            annotations=definition.annotations)
        self.codec = StreamCodec(self.stream_definition, ctx.global_strings)
        self.output_junction = StreamJunction(
            self.stream_definition, ctx, codec=self.codec)

        from ..ops.windows import make_layout
        layout = make_layout(self.attr_types)
        batch_cap = ctx.effective_batch_size
        # `define window W (...)` with no spec: pass-through emission, no
        # retained contents (reference: empty window)
        self.window: WindowOp = make_window(
            definition.window, layout, batch_cap, True, registry,
            annotations=definition.annotations,
            playback=bool(ctx.playback))

        self.state = self.window.init_state()
        self._append = jax.jit(
            lambda s, b, n: self.window.step(s, b, n), donate_argnums=(0,))
        out_type = (definition.output_event_type or "all").lower()
        if out_type not in ("all", "current", "expired"):
            raise SiddhiAppCreationError(
                f"window {definition.id!r}: bad output event type {out_type!r}")
        self.output_event_type = out_type
        from ..ops.windows import window_has_time_semantics
        self.has_time_semantics = window_has_time_semantics(self.window)

    # ------------------------------------------------------------------ feed

    def append(self, batch: EventBatch, now: int) -> None:
        """Insert arrivals (CURRENT lanes of `batch`) and publish the window's
        emissions downstream."""
        cap = self.ctx.effective_batch_size
        if batch.capacity < cap and not self.window.shape_polymorphic:
            # shape-baked window op: widen narrower (bucketed / producer-
            # chunked) inserts to the traced capacity
            batch = batch.pad_to(cap)
        self.state, chunk = self._append(self.state, batch, jnp.int64(now))
        chunk = self._apply_output_event_type(chunk)
        self.output_junction.publish_batch(chunk, now)

    def heartbeat(self, now: int) -> None:
        """Advance time with no data so time-driven expirations emit."""
        cap = self.ctx.effective_batch_size
        if self.window.shape_polymorphic and dtypes.config.shape_buckets \
                and self.ctx.mesh is None:
            cap = dtypes.bucket_capacity(0, cap)  # timer batch: min bucket
        empty = EventBatch.empty(self.stream_definition, cap)
        self.append(empty, now)

    def _apply_output_event_type(self, chunk: EventBatch) -> EventBatch:
        import dataclasses as dc
        if self.output_event_type == "current":
            keep = chunk.types == EventType.CURRENT
        elif self.output_event_type == "expired":
            keep = chunk.types == EventType.EXPIRED
        else:
            return chunk
        return dc.replace(chunk, valid=chunk.valid & keep)

    # ----------------------------------------------------------------- probe

    def contents(self, state, now):
        """Current in-window rows as (cols, ts, valid) — the FindableProcessor
        surface for joins/on-demand queries. Traced: call inside jit with the
        window's state passed as an argument."""
        return self.window.contents(state, now)


class WindowJunctionAdapter:
    """Adapts the query-output junction interface onto a named-window insert,
    renaming the query's output columns positionally onto the window schema
    (reference: InsertIntoWindowCallback — schemas match by position)."""

    def __init__(self, window: NamedWindow, out_types: Optional[dict] = None):
        self.window = window
        self.rename: Optional[dict] = None
        if out_types is not None:
            out_names = list(out_types.keys())
            win_names = list(window.attr_types.keys())
            if len(out_names) != len(win_names):
                raise SiddhiAppCreationError(
                    f"insert into window {window.definition.id!r}: query emits "
                    f"{len(out_names)} attributes, window has {len(win_names)}")
            for on, wn in zip(out_names, win_names):
                if out_types[on] != window.attr_types[wn]:
                    raise SiddhiAppCreationError(
                        f"insert into window {window.definition.id!r}: attribute "
                        f"{on!r} is {out_types[on].name}, window attribute "
                        f"{wn!r} is {window.attr_types[wn].name}")
            if out_names != win_names:
                self.rename = dict(zip(out_names, win_names))

    def publish_batch(self, batch: EventBatch, now: int) -> None:
        if self.rename:
            import dataclasses as dc
            batch = dc.replace(
                batch, cols={self.rename[k]: v for k, v in batch.cols.items()})
        self.window.append(batch, now)
