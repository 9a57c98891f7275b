"""Stage spans of the save pipeline and the restore path.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``veloc.<name>``.  It is recorded only while a profiler trace is being
collected (``jax.profiler.start_trace``, TensorBoard's capture, or any
other client of the profiler) and costs about a microsecond otherwise, so
there is nothing to switch on.  The spans land in the profiler's own
trace: they share a clock with the device's events, and the profiler
holds them in memory until the trace is written.

Each request's root span carries its identifier (``ckpt=
"<stream>:<version>:<rank>"`` for a save, ``restore="<stream>:<rank>"``
for a restore); a child span's parent is the span open around it on the
same thread.  Byte counts known when a stage starts go on its span as
``bytes``, so a stage's rate is read where the work happens.
"""
from __future__ import annotations

import jax

#: every span this package records starts with this
PREFIX = "veloc."


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """The span ``veloc.<name>`` with ``ids`` as its trace stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def ckpt_id(name: str, version: int, rank: int) -> str:
    """The identifier a save's spans carry."""
    return f"{name}:{version}:{rank}"
