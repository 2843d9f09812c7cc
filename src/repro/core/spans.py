"""Host spans at the program's layer boundaries.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``vta.<name>``: inside a profiler session it lands in the session's
XSpace, on the calling thread's line and on the same clock as the
device planes; outside one it costs about a microsecond.  The profiler
session is the only switch.  Until jax has been imported (a
simulator-only process) a span is a no-op, so importing
:mod:`repro.core` stays numpy-only.

Ids are ints (program index, gang width, pool request seq), so the spans
of one request share them.  ``tagged(**ids)`` hands a caller's ids to
the spans opened under it on the same thread: the pool tags the engine's
gang span with the program and the first request it runs.
"""
from __future__ import annotations

import contextlib
import contextvars
import sys
from typing import ContextManager, Dict, Iterator

_NULL = contextlib.nullcontext()
_IDS: "contextvars.ContextVar[Dict[str, int]]" = contextvars.ContextVar(
    "vta_span_ids", default={})


def span(name: str, **ids: int) -> ContextManager:
    """A host span ``vta.<name>`` with int `ids` as its arguments."""
    annotation = getattr(sys.modules.get("jax.profiler"),
                         "TraceAnnotation", None)
    if annotation is None:
        return _NULL
    return annotation("vta." + name, **ids)


@contextlib.contextmanager
def tagged(**ids: int) -> Iterator[None]:
    """Make `ids` the value of :func:`tags` on this thread inside the
    block."""
    token = _IDS.set(ids)
    try:
        yield
    finally:
        _IDS.reset(token)


def tags() -> Dict[str, int]:
    """The ids of the innermost enclosing :func:`tagged` block."""
    return _IDS.get()
