"""The entries a cell drives, and the closed and open loops around them.

Every call goes through the program's public surface exactly as a user
makes it: ``repro.solve``, ``repro.solve_many(problems=...)`` or a
``repro.serving.ContinuousScheduler`` fed by ``submit`` and ``step``. A
solve counts as done when its answer (gbest value and position) is on the
host. Host spans named after what the benchmark is doing (``solve``,
``solve_many``, ``submit``, ``sched.step``, ``result``) go into the
profiler's trace when one is being taken.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from traffic import Arrival, Row

# Fixed, inside the checkout: the serving replica's exported lane programs.
AOT_DIR = ".serving_cache"


@dataclasses.dataclass
class Done:
    """One finished solve: what it answered and when."""
    row: Row
    fit: float                   # gbest, maximised form
    pos: np.ndarray              # gbest position [D]
    start_s: float               # due (open loop) or sent (closed loop)
    end_s: float                 # answer on the host
    swarm: Optional[tuple] = None  # (pos [N, D], vel [N, D]) when kept

    @property
    def latency_s(self) -> float:
        return self.end_s - self.start_s


class Spans:
    """``jax.profiler.TraceAnnotation`` spans, or nothing when not
    tracing (the end-to-end runs carry no instrumentation)."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else contextlib.nullcontext()


class Reservoir:
    """A seeded uniform sample of ``k`` items from a stream of unknown
    length (Algorithm R): which items are kept depends on the seed and
    the count alone."""

    def __init__(self, k: int, g: np.random.Generator):
        self.k, self.g, self.seen = k, g, 0

    def offer(self) -> Optional[int]:
        """The slot the next item goes to, or None when it is not kept."""
        self.seen += 1
        if self.seen <= self.k:
            return self.seen - 1
        j = int(self.g.integers(0, self.seen))
        return j if j < self.k else None


def _method(config: dict):
    import repro
    m = config["method"]
    return repro.Method(variant=m["variant"], backend=m.get("backend", "auto"),
                        sync_every=int(m.get("sync_every", 8)))


def _common(config: dict) -> dict:
    return dict(dim=config["dim"], particles=config["particles"],
                iters=config["iters"], w=config["w"], c1=config["c1"],
                c2=config["c2"], dtype=config["dtype"])


class SolveEntry:
    """``repro.solve``: one solve per call."""
    span = "solve"

    def __init__(self, config: dict):
        self.kw = dict(_common(config), method=_method(config))

    def __call__(self, rows: Sequence[Row]):
        import repro
        return [repro.solve(fn, seed=seed, **self.kw) for fn, seed in rows]


class SolveManyEntry:
    """``repro.solve_many(problems=...)``: one batched call, a row each."""
    span = "solve_many"

    def __init__(self, config: dict):
        self.kw = dict(_common(config), method=_method(config))

    def __call__(self, rows: Sequence[Row]):
        import repro
        return repro.solve_many(problems=[f for f, _ in rows],
                                seeds=[s for _, s in rows], **self.kw)


ENTRIES = {"solve": SolveEntry, "solve_many": SolveManyEntry}


def _swarm_to_host(swarm):
    return None if swarm is None else tuple(np.asarray(a) for a in swarm)


def closed_loop(entry, calls, seconds: float, sample: Reservoir,
                spans: Spans, clock=time.perf_counter):
    """Calls back to back until ``seconds`` have passed. Returns the
    finished solves, the sampled ones, and the loop's length in seconds
    (from the first send to the last answer)."""
    done: List[Done] = []
    kept: Dict[int, Done] = {}
    t0 = clock()
    while clock() - t0 < seconds:
        rows = next(calls)
        slots = [sample.offer() for _ in rows]
        sent = clock() - t0
        with spans(entry.span):
            res = entry(rows)
        with spans("result"):
            answers = [(r.gbest_fit, r.best_pos) for r in res]
        end = clock() - t0
        for row, slot, r, (f, p) in zip(rows, slots, res, answers):
            d = Done(row, f, p, sent, end)
            if slot is not None:
                d.swarm = (r.state.pos, r.state.vel)
            done.append(d)
            if slot is not None:
                kept[slot] = d
    elapsed = clock() - t0
    for d in kept.values():
        d.swarm = _swarm_to_host(d.swarm)
    return done, [kept[i] for i in sorted(kept)], elapsed


def scheduler_requests(config: dict, rows: Sequence[Row]):
    from repro.launch.serve import SolveRequest
    m = config["method"]
    return [SolveRequest(dim=config["dim"], particle_cnt=config["particles"],
                         fitness=fn, seed=seed, iters=config["iters"],
                         variant=m["variant"], dtype=config["dtype"],
                         sync_every=int(m.get("sync_every", 8)))
            for fn, seed in rows]


def make_scheduler(config: dict, root: str, metrics=None):
    """The serving replica as deployed: default lane width, exported lane
    programs kept in a fixed directory of the checkout."""
    from repro.serving import CompileCache, ContinuousScheduler
    cc = CompileCache(path=os.path.join(root, AOT_DIR))
    return ContinuousScheduler(compile_cache=cc, metrics=metrics)


def open_loop(sched, config: dict, arrivals: Sequence[Arrival],
              seconds: float, sample: Reservoir, spans: Spans,
              drain_s: float = 60.0, clock=time.perf_counter):
    """Submit each request when it is due, step the scheduler whenever it
    has work, and take the answers it returns. After the window closes,
    keep stepping until every request due in it has its answer, or
    ``drain_s`` more seconds have passed.

    Returns the finished solves, the sampled ones, how late each request
    was submitted (seconds), and the requests that never finished."""
    reqs = scheduler_requests(config, [a.row for a in arrivals])
    slots = [sample.offer() for _ in arrivals]
    tickets: Dict[int, int] = {}
    done: List[Done] = []
    kept: Dict[int, Done] = {}
    late: List[float] = []
    i, n = 0, len(arrivals)
    t0 = clock()
    while True:
        now = clock() - t0
        while i < n and arrivals[i].due_s <= now:
            with spans("submit"):
                tickets[sched.submit(reqs[i])] = i
            late.append(now - arrivals[i].due_s)
            i += 1
        if sched.busy:
            with spans("sched.step"):
                out = sched.step()
            end = clock() - t0
            with spans("result"):
                for t, res in out.items():
                    k = tickets.pop(t, None)
                    if k is None:       # not from this window
                        continue
                    ok = res.error is None
                    d = Done(arrivals[k].row,
                             float(res.gbest_fit) if ok else float("nan"),
                             np.asarray(res.gbest_pos), arrivals[k].due_s,
                             end)
                    done.append(d)
                    if slots[k] is not None:
                        kept[slots[k]] = d
        elif i < n:
            time.sleep(max(0.0, min(arrivals[i].due_s - now, 0.01)))
        else:
            break
        if now > seconds + drain_s:
            break
    missing = [arrivals[k].row for k in tickets.values()] + \
        [a.row for a in arrivals[i:]]
    return done, [kept[j] for j in sorted(kept)], late, missing


def warm_scheduler(sched, config: dict, rows: Sequence[Row]) -> None:
    """Drive every program a window of this traffic runs through the
    scheduler: lane programs, a splice into each slot of a running lane,
    and the tail ejection. Budgets are one chunk plus the configuration's
    own remainder, so the ejection runs the remainder's program."""
    se = int(config["method"].get("sync_every", 8))
    iters = se + config["iters"] % se
    reqs = [dataclasses.replace(r, iters=iters)
            for r in scheduler_requests(config, rows)]
    for r in reqs:
        sched.submit(r)
    sched.drain()


class CompileCounter:
    """Counts XLA compiles (persistent-cache loads included) and traces
    while ``active``: a measured window should compile nothing. Traces
    alone are host work of the program (an eager ``vmap`` traces on every
    call) and are only reported."""

    def __init__(self):
        import jax
        self.active = False
        self.compiles: List[str] = []
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if not self.active:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(kw.get("fun_name", ""))
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
