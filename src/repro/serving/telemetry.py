"""Host spans of the served path, on the profiler's clock.

Every span is a `jax.profiler.TraceAnnotation` whose name starts with
``serve.``, so a trace reduction can line it up with the device planes
and tell the program's spans from any its caller opens. With no
profiler running a span costs well under a microsecond, so they stay
on. Call sites open `TraceAnnotation` themselves; this module holds the
per-candidate name and the span around Python's garbage collector."""

from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation

PREFIX = "serve."
_open = []          # the GC span of the collection under way


def span(phase: str, model: str) -> str:
    """`serve.<phase>.<model>`: a phase of one candidate's work."""
    return f"{PREFIX}{phase}.{model}"


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        ann = TraceAnnotation(PREFIX + "gc")
        ann.__enter__()
        _open.append(ann)
    elif _open:
        _open.pop().__exit__(None, None, None)


def install_gc_span() -> None:
    """Open `serve.gc` around every garbage collection; idempotent."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
