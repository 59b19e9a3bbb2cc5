"""Stage spans of a campaign's dispatches, on two clocks at once.

``stage(name)`` marks one piece of a dispatch's host path.  It opens
``jax.profiler.TraceAnnotation(f"sweep.{name}")``, so the interval lands on
the profiler's timeline (where a device trace labels idle gaps by the host
span over them), and adds the stage's seconds to ``<name>_s`` of the
dispatch being collected (:func:`collect`), so the runner's dispatch span
carries the same split with no profiler running.  ``count(name, n)`` adds an
integer counter to the same dispatch.  Outside :func:`collect`, a stage only
annotates and a count is dropped.

Compile time is kept apart: every ``<name>_s`` is the stage's wall seconds
minus the JAX compile time inside it (tracing, lowering, backend compile or
persistent-cache load, as ``jax.monitoring`` reports their time spans), and
:func:`collect` writes the compile seconds of the whole dispatch to
``compile_s``.  So ``execute_s`` is the jitted call's own time, and a warm
dispatch reports ``compile_s`` 0.

The stages are always on: with no profiler running an annotation costs
about a microsecond, and each dispatch opens one span per stage.

``scopes()`` names consecutive stages of a traced (jitted) function with
``jax.named_scope``: the device operations of each stage carry its name in
their HLO metadata, which changes no compiled code.
"""
from __future__ import annotations

import contextlib
import contextvars
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

# The duration fields every dispatch span carries (0.0 when the stage did
# not run), and its counters.
STAGE_KEYS = ("prep_s", "execute_s", "fetch_s", "post_s", "retry_s",
              "record_s", "compile_s")
COUNTERS = ("bytes_in", "bytes_out", "jsq_retries")

# jax.monitoring time-span events that make up a compile; nested traces of
# inner jits overlap their outer trace, so their union is what counts.
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})


class _Collector:
    def __init__(self):
        self.fields: Dict[str, float] = {}
        self.compiles: List[Tuple[float, float]] = []   # time.time() spans


_CURRENT: contextvars.ContextVar[Optional[_Collector]] = \
    contextvars.ContextVar("repro_obs_dispatch", default=None)
_listening = False      # jax.monitoring listeners are process-wide: add once


def _on_time_span(event: str, start: float, end: float, **_) -> None:
    col = _CURRENT.get()
    if col is not None and event in COMPILE_EVENTS:
        col.compiles.append((start, end))


def _covered(spans, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] covered by the union of ``spans``."""
    total, reach = 0.0, lo
    for s, e in sorted(spans):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            total += e - s
            reach = e
    return total


@contextlib.contextmanager
def collect() -> Iterator[Dict[str, float]]:
    """Collect the stages and counters of one dispatch into the yielded
    dict.  On exit it holds every :data:`STAGE_KEYS` and :data:`COUNTERS`
    field (zero where nothing was added), ``compile_s`` included."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        _listening = True
    col = _Collector()
    token = _CURRENT.set(col)
    w0 = time.time()
    try:
        yield col.fields
    finally:
        _CURRENT.reset(token)
        col.fields["compile_s"] = _covered(col.compiles, w0, time.time())
        for k in STAGE_KEYS:
            col.fields.setdefault(k, 0.0)
        for k in COUNTERS:
            col.fields.setdefault(k, 0)


@contextlib.contextmanager
def stage(name: str, key: Optional[str] = None) -> Iterator[None]:
    """Annotate ``sweep.<name>`` and add its seconds, net of compile time,
    to ``<key or name>_s`` of the dispatch being collected."""
    col = _CURRENT.get()
    with jax.profiler.TraceAnnotation(f"sweep.{name}"):
        if col is None:
            yield
            return
        w0, t0 = time.time(), time.perf_counter()
        try:
            yield
        finally:
            secs = time.perf_counter() - t0
            secs -= _covered(col.compiles, w0, time.time())
            field = f"{key or name}_s"
            col.fields[field] = col.fields.get(field, 0.0) + secs


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name`` of the dispatch being collected."""
    col = _CURRENT.get()
    if col is not None:
        col.fields[name] = col.fields.get(name, 0) + int(n)


def _nbytes(tree) -> int:
    """Bytes of the arrays in ``tree`` as the device holds them (64-bit host
    arrays travel as 32-bit ones unless JAX runs in 64-bit mode)."""
    itemsize: Dict = {}
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        if x.dtype not in itemsize:
            itemsize[x.dtype] = jax.dtypes.canonicalize_dtype(x.dtype).itemsize
        total += x.size * itemsize[x.dtype]
    return int(total)


def execute(fn, *args):
    """A jitted call on a dispatch's stacked host operands, through its
    outputs being ready, as the ``execute`` stage; counts ``bytes_in``.

    The call copies the operands to the device itself: JAX's compiled-call
    path moves numpy arguments at a fraction of what a ``jax.device_put``
    of the same few dozen arrays costs in Python (a separate put stage made
    a lone fast-engine point about a fifth slower on a TPU v5e host), so
    that copy is part of ``execute_s``."""
    count("bytes_in", _nbytes(args))
    with stage("execute"):
        return jax.block_until_ready(fn(*args))


def fetch(tree):
    """The outputs copied to host numpy arrays, as the ``fetch`` stage;
    counts ``bytes_out``."""
    with stage("fetch"):
        out = jax.tree_util.tree_map(np.asarray, tree)
    count("bytes_out", _nbytes(out))
    return out


@contextlib.contextmanager
def scopes() -> Iterator:
    """Name consecutive stages of a traced function: each call of the
    yielded function closes the open ``jax.named_scope`` and opens one named
    after its argument (None opens none); the last closes on exit."""
    stack = contextlib.ExitStack()

    def at(name: Optional[str]) -> None:
        stack.close()
        if name is not None:
            stack.enter_context(jax.named_scope(name))
    with stack:
        yield at
