"""Schedule-phase tracing helpers.

Two span flavours with different lifetimes:

* :func:`phase_scope` — a ``jax.named_scope``: a *trace-time* annotation
  that names the ops staged inside it, so the gather/compute/reduce
  chunks of the TMP schedules (megatron/wang/oases/fused) and the
  optimizer update (``optim.adamw``) appear in the compiled HLO's op
  metadata and in XLA profiles.  Zero runtime cost — the scope only
  exists while tracing.
* :func:`trace_annotation` — a ``jax.profiler.TraceAnnotation``: a
  *host-side* region (the trainer's step, the engine's tick phases,
  every :meth:`Recorder.span`) on the Python track of a
  ``jax.profiler`` capture, on the same clock as the device ops.

Both are safe to leave in hot paths unconditionally.  Host spans are
named ``<layer>.<what>`` (``train.step``, ``serve.dispatch``).
"""
from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation


def phase_scope(name: str):
    """Name the jax ops staged inside the block (XLA-profile visible)."""
    return jax.named_scope(name)


def trace_annotation(name: str) -> TraceAnnotation:
    """Host-side profiler region (about a microsecond when no profile is
    being captured)."""
    return TraceAnnotation(name)
