"""Chrome trace-event (Perfetto-loadable) writer for solver timelines.

Emits the JSON object format of the Trace Event spec — a ``traceEvents``
list of phase-coded events — which both ``chrome://tracing`` and
https://ui.perfetto.dev load directly. The writer keeps its own
process/thread registries so callers name rows semantically ("serving" /
"lane 0") instead of juggling pid/tid integers:

- ``complete(name, ts_us, dur_us, ...)`` — a span (ph "X"): serving
  requests, lane dispatches, solve chunks.
- ``instant(name, ts_us, ...)`` — a point event (ph "i"): admissions,
  ejections, publications.
- ``counter(name, ts_us, values)`` — a counter track (ph "C"): lane fill,
  per-chunk gbest.
- ``span(name, ...)`` — context manager wrapping a host-side region with
  ``time.perf_counter`` stamps.

Timestamps are microseconds on any monotonic base; ``to_dict()`` rebases
them to zero so the timeline starts at t=0 regardless of the clock.

``profiler_session(logdir)`` optionally brackets a region with a
``jax.profiler`` trace (XLA-level events alongside ours); it degrades to
a no-op when the profiler backend is unavailable, so callers never gate
on it.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Dict, List, Optional


def _now_us() -> float:
    return time.perf_counter() * 1e6


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

    @contextlib.contextmanager
    def span(self, name: str, *, process: str = "solver",
             thread: str = "main", cat: str = "solve",
             args: Optional[Dict[str, Any]] = None):
        """Wrap a host-side region as a complete event."""
        t0 = _now_us()
        try:
            yield self
        finally:
            self.complete(name, t0, _now_us() - t0, process=process,
                          thread=thread, cat=cat, args=args)

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
