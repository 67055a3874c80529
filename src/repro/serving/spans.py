"""Named spans of the serving path, written into the ``jax.profiler`` trace.

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation`` while a
profiler trace is recording, and otherwise returns one shared null
context: the untraced path costs a module lookup and a flag check, and
no argument is formatted. The profiler keeps the spans in memory and writes them at
``stop_trace``, on the same clock as the device planes, so an idle gap
on the device can be named by the span the host had open.

Arguments are shown in the trace as the span's stats. A caller passes
values it already has; an argument that costs something to build is
computed only under ``if s is not None`` on the entered span, which is
``None`` while untraced, and attached with ``s.set_metadata(...)``.

The spans, by layer (thread in brackets):

* ingress: ``executor.inject`` [injector] — ``rid``, ``lag_us``;
* executor: ``executor.form`` [replica] — ``rows``, ``wait_ms`` of the
  oldest request; ``executor.batch`` [replica] — ``stage``, ``rows``,
  ``rid0``, ``t`` (executor-clock seconds), around the stage call and
  ``executor.complete``;
* stage runtime: ``runtime.pad`` (``bucket``, ``rows``),
  ``runtime.put``, ``runtime.launch`` (``device``), ``runtime.fetch``.

The generate program marks its phases with ``jax.named_scope``
(``prefill``, ``decode``), which the device ops carry in their
``tf_op`` metadata.
"""

from __future__ import annotations

import contextlib
import sys

NULL = contextlib.nullcontext()


def span(name: str, **args):
    """A ``TraceAnnotation`` while a profiler trace records, else
    :data:`NULL`.

    JAX is not imported here: a process that never imported
    ``jax.profiler`` records no trace, and the executor serves CPU
    stages too, whose processes need not hold JAX's objects (every
    full collection of Python's collector walks them).
    """
    profiler = sys.modules.get("jax.profiler")
    if profiler is not None and profiler.TraceAnnotation.is_enabled():
        return profiler.TraceAnnotation(name, **args)
    return NULL
