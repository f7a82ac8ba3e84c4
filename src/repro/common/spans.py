"""Host spans of the serving path.

Every span is a ``jax.profiler.TraceAnnotation`` named ``hq.<layer>.<part>``:
with a profiler session it lands in the trace beside the device ops, on
the profiler's clock; without one it costs about a microsecond. A span of
one formed batch carries the batch's sequence number as the keyword
``batch``, so its spans on the event loop and in the worker thread share
one identifier. The front end sets that number in ``BATCH`` for the
worker's call (``contextvars.copy_context().run``); ``span`` reads it.
"""
from __future__ import annotations

import contextvars
from typing import Optional

import jax

# sequence number of the formed batch the current call executes
BATCH: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "hq_batch", default=None)


def span(name: str, batch: Optional[int] = None):
    """The span ``name``, tagged with ``batch`` or else the number in
    ``BATCH`` (untagged outside a formed batch)."""
    if batch is None:
        batch = BATCH.get()
    if batch is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.TraceAnnotation(name, batch=batch)
