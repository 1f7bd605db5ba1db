"""Host spans on the profiler's clock, and a Chrome trace-event
(Perfetto-loadable) writer for solver timelines.

``span(name, ...)`` is the one way the program opens a host span. It is
always a ``jax.profiler.TraceAnnotation``, so while a profiler session
runs (``pso_run --profile-dir``, ``jax.profiler.trace``) the span lands in
the profiler's host plane, on the same clock as the device trace; with no
session it records nothing and costs about a microsecond. Its names are
``SPAN_NAMES``. A call site that also feeds a ``ServingMetrics`` span or a
``TraceWriter`` event passes them to ``span``, which feeds both from one
pair of ``perf_counter`` reads.

``TraceWriter`` emits the JSON object format of the Trace Event spec — a
``traceEvents`` list of phase-coded events — which both
``chrome://tracing`` and https://ui.perfetto.dev load directly. The
writer keeps its own process/thread registries so callers name rows
semantically ("serving" / "lane 0") instead of juggling pid/tid integers:

- ``complete(name, ts_us, dur_us, ...)`` — a span (ph "X"): serving
  requests, lane dispatches, solve chunks.
- ``instant(name, ts_us, ...)`` — a point event (ph "i"): admissions,
  ejections, publications.
- ``counter(name, ts_us, values)`` — a counter track (ph "C"): lane fill,
  per-chunk gbest.

Timestamps are microseconds on any monotonic base (the program stamps
``now_us()``); ``to_dict()`` rebases them to zero so the timeline starts
at t=0 regardless of the clock.

``profiler_session(logdir)`` optionally brackets a region with a
``jax.profiler`` trace, and raises when the trace cannot start.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation


def now_us() -> float:
    """The program's host clock: ``perf_counter`` in microseconds."""
    return time.perf_counter() * 1e6


class SpanNames(NamedTuple):
    """Every host span the program opens, from the entry calls down.
    Nesting: ``prepare``, ``init``, ``launch`` and ``rows`` inside
    ``solve``/``solve_many``; ``readback`` in ``Result``'s properties,
    after the call; ``sched_*`` inside ``sched_step``, except
    ``sched_standalone``, inside ``sched_admit``."""
    solve: str = "repro.solve"                # the whole repro.solve call
    solve_many: str = "repro.solve_many"      # the whole repro.solve_many
    prepare: str = "repro.prepare"            # problem, method, config
    init: str = "repro.init"                  # init_swarm / init_batch
    launch: str = "repro.launch"              # the segment runners
    rows: str = "repro.rows"                  # solve_many's per-row Results
    readback: str = "repro.readback"          # Result gbest/best reads
    sched_step: str = "repro.sched.step"      # ContinuousScheduler.step
    sched_admit: str = "repro.sched.admit"    # admission of the pending
    sched_standalone: str = "repro.sched.standalone"  # a fallback solve
    sched_compile: str = "repro.sched.compile"  # a lane program's build
    sched_dispatch: str = "repro.sched.dispatch"  # one lane dispatch
    sched_eject: str = "repro.sched.eject"    # one tail ejection
    sched_finish: str = "repro.sched.finish"  # one finished row's harvest
    chunk: str = "repro.chunk"                # one pso_run chunk


SPAN_NAMES = SpanNames()


class span:
    """A host span named ``name`` (one of ``SPAN_NAMES``).

    ``metrics``/``metric`` feed a ``ServingMetrics`` span, ``trace`` a
    ``TraceWriter`` complete event; both from the same two clock reads,
    and only for a region that did not raise. ``event`` is called at exit,
    only when ``trace`` is given, so the call site formats nothing on the
    untraced path; without it the event takes ``name``. After exit,
    ``t0_us`` and ``dur_us`` hold the reading when a sink was given."""

    __slots__ = ("_name", "_ann", "_metrics", "_metric", "_trace", "_event",
                 "t0_us", "dur_us")

    def __init__(self, name: str, *, metrics=None, metric: str = "",
                 trace: Optional["TraceWriter"] = None,
                 event: Optional[Callable[[], Tuple[str, Dict[str, Any]]]]
                 = None):
        self._name = name
        self._ann = TraceAnnotation(name)
        self._metrics, self._metric = metrics, metric
        self._trace, self._event = trace, event
        self.t0_us = self.dur_us = None

    def __enter__(self) -> "span":
        self._ann.__enter__()
        if self._metrics is not None or self._trace is not None:
            self.t0_us = now_us()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.t0_us is not None:
            self.dur_us = now_us() - self.t0_us
        self._ann.__exit__(exc_type, exc, tb)
        if self.t0_us is None or exc_type is not None:
            return
        if self._metrics is not None:
            self._metrics.observe(self._metric, self.dur_us)
        if self._trace is not None:
            name, kw = (self._event() if self._event is not None
                        else (self._name, {}))
            self._trace.complete(name, self.t0_us, self.dur_us, **kw)


class TraceWriter:
    """Accumulates trace events; one instance per exported timeline."""

    def __init__(self) -> None:
        self._events: List[Dict[str, Any]] = []
        self._pids: Dict[str, int] = {}
        self._tids: Dict[tuple, int] = {}

    def _pid(self, process: str) -> int:
        pid = self._pids.get(process)
        if pid is None:
            pid = len(self._pids) + 1
            self._pids[process] = pid
            self._events.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": process},
            })
        return pid

    def _tid(self, process: str, thread: str) -> int:
        key = (process, thread)
        tid = self._tids.get(key)
        if tid is None:
            pid = self._pid(process)
            tid = sum(1 for p, _ in self._tids if p == process) + 1
            self._tids[key] = tid
            self._events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": thread},
            })
        return tid

    def complete(self, name: str, ts_us: float, dur_us: float, *,
                 process: str = "solver", thread: str = "main",
                 cat: str = "solve",
                 args: Optional[Dict[str, Any]] = None) -> None:
        """A finished span: ``[ts_us, ts_us + dur_us]`` on a named row."""
        self._events.append({
            "name": name, "ph": "X", "cat": cat,
            "ts": float(ts_us), "dur": max(0.0, float(dur_us)),
            "pid": self._pid(process), "tid": self._tid(process, thread),
            "args": dict(args or {}),
        })

    def instant(self, name: str, ts_us: float, *,
                process: str = "solver", thread: str = "main",
                cat: str = "solve",
                args: Optional[Dict[str, Any]] = None) -> None:
        """A point event (thread-scoped tick mark)."""
        self._events.append({
            "name": name, "ph": "i", "s": "t", "cat": cat,
            "ts": float(ts_us),
            "pid": self._pid(process), "tid": self._tid(process, thread),
            "args": dict(args or {}),
        })

    def counter(self, name: str, ts_us: float,
                values: Dict[str, float], *,
                process: str = "solver", cat: str = "solve") -> None:
        """A sample on a counter track (rendered as a stacked area)."""
        self._events.append({
            "name": name, "ph": "C", "cat": cat, "ts": float(ts_us),
            "pid": self._pid(process), "tid": 0,
            "args": {k: float(v) for k, v in values.items()},
        })

    @property
    def event_count(self) -> int:
        return len(self._events)

    def to_dict(self) -> Dict[str, Any]:
        """The trace document, timestamps rebased to start at 0."""
        stamped = [e["ts"] for e in self._events if "ts" in e]
        base = min(stamped) if stamped else 0.0
        events = []
        for e in self._events:
            e = dict(e)
            if "ts" in e:
                e["ts"] = e["ts"] - base
            events.append(e)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        """Serialize to a Perfetto-loadable ``trace.json``."""
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)


@contextlib.contextmanager
def profiler_session(logdir: Optional[str]):
    """Optionally bracket a region with a ``jax.profiler`` trace.

    Without a ``logdir`` this is a no-op that yields False. With one it
    yields True once the trace has started, and raises when the trace
    cannot start: a caller that asked for a trace must not get a run
    without one.
    """
    if not logdir:
        yield False
        return
    from jax import profiler
    profiler.start_trace(logdir)
    try:
        yield True
    finally:
        profiler.stop_trace()
