"""Host spans of the serving path, on the profiler's clock.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler trace is recording, it writes one host event named ``name`` with
``ids`` as its arguments; otherwise it costs one check of whether a trace
is active (about a microsecond).  A span only wraps work the path does
anyway: it adds no synchronisation and no transfer.

The spans of ``ServingEngine`` (ids in brackets):

- ``serving.enqueue`` [request]: a request's features copied to the device,
  on the event loop;
- ``serving.batch`` [batch, k, requests, attempt]: one micro-batch on the
  dispatch worker, over the same interval as ``RequestStats.t_execute``;
  ``requests`` lists its request ids, space-separated.  Its children, on the
  same thread and each with [batch]:
  ``serving.stack`` (stack and pad the features), ``serving.drift`` (the
  density check against the compiled program), ``serving.replan`` (the
  eager pass that plans, and the compile of a new program),
  ``serving.call`` (dispatch of the compiled program),
  ``serving.activation`` (the block-skip telemetry pulled to the host; it
  waits for the program) and ``serving.wait`` (wait for the logits);
- ``serving.split`` [batch]: right after its batch, on the same thread: the
  per-request slices of the logits, their stats and the hand-back.
"""
from __future__ import annotations

import jax


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` carrying ``ids`` (numbers or strings without
    ``,`` or ``#``, which the trace format reserves)."""
    return jax.profiler.TraceAnnotation(name, **ids)
