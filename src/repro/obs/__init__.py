"""Unified telemetry subsystem (DESIGN.md §11).

``repro.obs`` is the one place runtime observability lives:

* :class:`Recorder` — counters / gauges / histograms, structured events,
  wall-clock spans; JSONL sink + in-memory ring buffer; a
  :class:`NullRecorder` disabled mode whose calls cost well under a
  microsecond (the hot paths are instrumented unconditionally);
* :func:`phase_scope` / :func:`trace_annotation` — schedule-phase spans
  that surface the TMP gather/compute/reduce chunks and the optimizer in
  XLA profiles, and host regions on the profiler's clock;
* ``python -m repro.obs.report`` — render a run's JSONL into per-phase
  breakdown tables (the reproduction's own Fig. 2).
"""
from repro.obs.recorder import (NULL, NullRecorder,  # noqa: F401
                                Recorder, configure, get_recorder,
                                set_recorder)
from repro.obs.tracing import phase_scope, trace_annotation  # noqa: F401

__all__ = [
    "Recorder", "NullRecorder", "NULL",
    "configure", "get_recorder", "set_recorder",
    "phase_scope", "trace_annotation",
]
