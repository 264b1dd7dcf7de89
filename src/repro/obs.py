"""Spans on the profiler's host trace.

One way to trace: ``span(name, **args)`` opens
``jax.profiler.TraceAnnotation("snapstore." + name, **args)``. A span
records only while a profiler trace runs (``jax.profiler.trace`` or
``start_trace``/``stop_trace``); otherwise it costs the call, about a
microsecond. Spans land on the same host clock as the runtime's own
events, so a trace lines them up with the device programs they enqueue.

- ``args`` are the span's counts, taken at the boundary the span opens;
  ``set_metadata(**more)`` on the yielded annotation adds what is known
  only inside (a new sequence id, the path taken).
- A span opened inside another on the same thread is its child.
- Inside a jitted function a span records once, while JAX traces it.

Device time is split by ``jax.named_scope`` inside jitted code instead:
the scope becomes part of every op's ``op_name`` metadata. The names in
use are listed in ``docs/architecture.md`` ("Tracing").
"""

from __future__ import annotations

import contextlib
from collections.abc import Iterator

import jax

PREFIX = "snapstore."


@contextlib.contextmanager
def span(name: str, **args) -> Iterator[jax.profiler.TraceAnnotation]:
    """A host span ``snapstore.<name>`` with ``args`` as its arguments."""
    with jax.profiler.TraceAnnotation(PREFIX + name, **args) as ann:
        yield ann
