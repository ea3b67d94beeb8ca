"""Distributed span tracing with cross-task context propagation.

Design analog: reference ``python/ray/util/tracing/tracing_helper.py:53``
(_inject_tracing_into_function / propagated OpenTelemetry contexts).  No
OTel SDK ships in the image, so the span model is self-contained but
OTLP-shaped (trace_id / span_id / parent_id / name / start / end /
attributes) — an exporter adapter is one function away.

How it flows:
  * ``enable()`` (or env RT_TRACING=1) turns on capture in this process.
  * ``with span("step"):`` opens a span; the current span rides a
    contextvar.
  * Task/actor submissions stamp the current (trace_id, span_id) into the
    task spec; executors open a child span around the function body — so
    a driver span, the remote task's span, and any nested task's span
    form one tree across processes.
  * Finished spans ride the existing task-event pipeline to the GCS
    (kind="span"); ``get_spans()`` pages them back through the state API.

Spans are for one-a-task granularity (two ``uuid4()`` and a GCS event
each, on the wall clock).  The hot loops use ``region()`` instead: a
named host interval written into the JAX profiler's own trace, so it is
on the device trace's clock, live exactly while a profiler session runs
in this process, and never sent to the GCS.

The convention of a region's attributes, in this one place.  A region's
attributes are fixed when it is entered, so the numbers of a phase that
has just ended (a wait that spanned an ``await``, a thread crossing, the
region before) ride on the region that FOLLOWS it, as ``<phase>_<clock>``:
whole microseconds, integers.  ``<phase>_us`` is the wall clock
(``time.perf_counter``), ``<phase>_cpu_us`` the CPU clock of the thread
that ran the phase (``time.thread_time``: wall less CPU is what that thread
spent not running, waiting for the GIL or a lock), ``<phase>_loop_cpu_us``
the CPU clock of the actor's event-loop thread over the same interval,
whichever thread read it (``time.pthread_getcpuclockid``).  A thread's CPU
clock is a system call (0.3 us on plain Linux, 6 us under the chip
machine's sandbox), so a hot path reads it for every region only while
``recording()`` says a session would carry it, and for a sample otherwise.
Counts follow the same rule: what a step turned out to be
is known when it is fetched, so a block model's ``denoise_slots``,
``commit_slots``, ``dropped_tail`` and ``dropped_stray`` ride on the
``rt:engine.deliver`` that follows the fetch beside its ``tokens`` (0 to B a
slot), while what the host knows when it dispatches (``active``,
``block_len``, ``live_tokens``, ``width_pages``) is on the
``rt:engine.decode.dispatch``.  A host may tick its thread CPU clocks coarsely (the chip's machine does, in
steps of 10 ms): one region then reads 0 or a whole tick, and only sums
over many regions are readings.  ``watch_gc()``
puts the collector's passes on the same timeline as ``rt:gc`` regions and
counts them always (``gc_stats()``): the collector holds the GIL, so a pass
on any thread is a stall of every thread.

Sums follow the rule too.  Work that is too fine for a region of its own (a
streamed token's stages, a frame packed or parsed: thousands a second) is
added, always, into one accumulator of this module (``accumulator`` / ``sums``:
seconds as ``<what>_s``, counts bare), whether or not jax is imported; and
what a sum GREW BY since the region before rides on the region that follows,
as any phase does: a decode step's ``rt:engine.decode.dispatch`` carries the
growth of the streams' and the transport's sums since the submission before
it (``stream_store_us``, ``rpc_out_us``, ``yields``, ``msgs_out``, ...), over
the very interval of its ``step_loop_cpu_us``.  The sections that feed such
sums are synchronous and do not nest, so they add up beside each other.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import gc
import os
import sys
import time
import uuid
from typing import Any, Dict, List, Optional

_current: "contextvars.ContextVar" = contextvars.ContextVar(
    "rt_trace_ctx", default=None)   # (trace_id, span_id) | None
_enabled: Optional[bool] = None


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    # The env answer is cached: this gate sits on the task/actor submit
    # hot path, and a per-call os.environ lookup measured ~9us there.
    # enable()/disable() still override at any time.
    global _enabled
    if _enabled is None:
        _enabled = os.environ.get("RT_TRACING", "") == "1"
    return _enabled


_NO_REGION = contextlib.nullcontext()


def region(name: str, **attrs):
    """``with region("engine.schedule", active=3):`` names what this
    thread does until the block ends, as the event ``rt:<name>`` of the
    JAX profiler's host plane, its attributes as the event's stats.  It
    costs well under a microsecond while no profiler session runs.  jax
    is never imported here: a process without it (the ingress, the
    raylet) gets one shared no-op.  Hold no region across an ``await``:
    it would cover other coroutines' work."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_REGION
    return jax.profiler.TraceAnnotation("rt:" + name, **attrs)


def recording() -> bool:
    """Whether a profiler session is recording this process's regions right
    now.  For a reading that costs something and that only a region would
    carry (the engine's thread CPU clocks); ``region()`` itself needs no
    such test.  Asked from any thread at any time (the transport asks once
    a frame): while another thread is still importing jax the module is
    there without its ``profiler``, and nothing records yet."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    return profiler is not None and profiler.TraceAnnotation.is_enabled()


_sums: Dict[str, float] = collections.defaultdict(float)   # see accumulator()


def accumulator() -> Dict[str, float]:
    """This process's always-on sums, the dict itself (a ``defaultdict`` of
    floats): seconds of a synchronous section (``stream.store_s``,
    ``rpc.out_s``) or a count (``stream.yields``, ``rpc.msgs_out``).  A path
    that adds thousands of times a second keeps the dict and adds in place,
    ``acc[key] += amount``, from the process's loop thread."""
    return _sums


def sums(prefix: str = "") -> Dict[str, float]:
    """The sums whose key starts with ``prefix``, the prefix cut off."""
    cut = len(prefix)
    return {key[cut:]: value for key, value in list(_sums.items())
            if key.startswith(prefix)}


_gc = {"passes": [0, 0, 0], "pause_s": [0.0, 0.0, 0.0],   # by generation
       "pause_max_s": 0.0}                                  # see gc_stats()
_gc_open: Optional[tuple] = None   # (the pass's region, when it started)


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    global _gc_open
    if phase == "start":
        open_region = region("gc", generation=info["generation"])
        open_region.__enter__()
        _gc_open = (open_region, time.perf_counter())
    elif _gc_open is not None:
        (open_region, started), _gc_open = _gc_open, None
        pause = time.perf_counter() - started
        open_region.__exit__(None, None, None)
        _gc["passes"][info["generation"]] += 1
        _gc["pause_s"][info["generation"]] += pause
        _gc["pause_max_s"] = max(_gc["pause_max_s"], pause)


def watch_gc() -> None:
    """From now on every pass of this process's collector is an ``rt:gc``
    region with its ``generation`` (a no-op of a region where jax is not
    imported, as ``region()``) and is counted in ``gc_stats()``.  In a
    trace viewer ``generation`` tells the full pass (2: tens of
    milliseconds with every thread stalled, what ``gc.freeze()`` would
    shorten) from the young ones (tenths of a millisecond, many a second).
    Calling it again changes nothing.  It costs a pass two Python calls."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_stats() -> Dict[str, Any]:
    """Collector passes since ``watch_gc()``: ``passes`` and ``pause_s``
    (wall seconds inside them) by generation 0, 1, 2, and ``pause_max_s``,
    the longest single pass."""
    return {"passes": list(_gc["passes"]), "pause_s": list(_gc["pause_s"]),
            "pause_max_s": _gc["pause_max_s"]}


def current_context() -> Optional[tuple]:
    """(trace_id, span_id) to propagate, or None."""
    return _current.get()


@contextlib.contextmanager
def span(name: str, attributes: Optional[Dict[str, Any]] = None,
         _remote_parent: Optional[tuple] = None):
    """Open a span; records on exit when tracing is enabled."""
    if not enabled():
        yield None
        return
    parent = _remote_parent or _current.get()
    trace_id = parent[0] if parent else uuid.uuid4().hex
    span_id = uuid.uuid4().hex[:16]
    token = _current.set((trace_id, span_id))
    t0 = time.time()
    err: Optional[str] = None
    try:
        with region(name):
            yield (trace_id, span_id)
    except BaseException as e:
        err = repr(e)
        raise
    finally:
        _current.reset(token)
        _record({
            "kind": "span",
            "task_id": span_id,            # state-API identity column
            "name": name,
            "trace_id": trace_id,
            "span_id": span_id,
            "parent_id": parent[1] if parent else None,
            "start": t0,
            "end": time.time(),
            "status": "FAILED" if err else "FINISHED",
            "attributes": {**(attributes or {}),
                           **({"error": err} if err else {})},
        })


def _record(event: Dict[str, Any]) -> None:
    try:
        from ray_tpu._private.worker import get_core
        get_core().record_task_event(event)
    except Exception:
        pass  # not connected: tracing is best-effort


def get_spans(limit: int = 5000,
              trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """Finished spans from the GCS (newest first); optionally one trace.
    The trace filter is pushed down server-side — the page limit applies
    AFTER filtering, so a busy retention window can't truncate a trace."""
    from ray_tpu.util.state import list_tasks
    return list_tasks(limit=limit, kind="span", trace_id=trace_id)
