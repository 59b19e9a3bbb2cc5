"""Observability layer for the campaign engine.

Its pieces import nothing of the sweep stack or the engines, so both can
import from here without cycles:

* :mod:`~repro.obs.trace` -- versioned JSONL dispatch traces.  The runner
  emits one structured span per fused megabatch dispatch (plan key, bucket
  population, padding ratios, device fill, wall seconds and their stage
  split, compile-cache hits) plus campaign-level bookend spans; spans are
  deterministic modulo the :data:`~repro.obs.trace.TIMING_KEYS` fields.
* :mod:`~repro.obs.probes` -- the opt-in in-simulation probe spec
  (``Campaign.probes``): a fixed (stride, samples) downsampling grid both
  engines use to carry a per-layer queue-occupancy time series out of the
  jitted pipelines without splitting compiled shapes.
* :mod:`~repro.obs.stages` -- the host stages of a dispatch (prep,
  execute, fetch, post, record), each both a ``sweep.<stage>`` span on the
  profiler's timeline and a ``<stage>_s`` field of the dispatch span, and
  the ``jax.named_scope`` names of the engines' device stages.
* :mod:`~repro.obs.log` -- the structured sweep logger (quiet / info /
  debug) and the one-line-per-dispatch progress format.
* :mod:`~repro.obs.report` -- renders a trace (+ optional results) into the
  ``python -m repro.sweep report`` cost summary.
"""
from .log import SweepLogger, dispatch_line
from .probes import ProbeSpec, QueueProbe, probe_shape
from .report import render_report
from .trace import (TIMING_KEYS, TRACE_SCHEMA, TraceWriter, load_trace,
                    strip_timing)

__all__ = [
    "SweepLogger", "dispatch_line",
    "ProbeSpec", "QueueProbe", "probe_shape",
    "render_report",
    "TIMING_KEYS", "TRACE_SCHEMA", "TraceWriter", "load_trace",
    "strip_timing",
]
