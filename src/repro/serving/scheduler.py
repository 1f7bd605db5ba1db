"""Continuous-batching scheduler: admit requests into in-flight batched
async solves at chunk boundaries.

The flush server (``repro.launch.serve``) batches one queue generation at
a time: every request in a flush rides a padded ``solve_many`` keyed on
its FULL shape *including* ``iters``, and nothing new can join until the
whole batch returns. A serving tier sees a *stream* — arrivals are
staggered and iteration budgets differ — and flush batching pays twice:
mixed-``iters`` traffic fragments into many small padded groups, and a
late arrival waits a whole batch.

This scheduler keeps a small number of persistent **lanes** running
instead. A lane is a ``SwarmBatch`` of ``width`` independent rows that
advances ``sync_every`` iterations per dispatch (one chunk) through ONE
compiled program, reused for the lane's whole lifetime. The paper's
enhanced queue-lock semantics make the chunk boundary a natural
preemption point: blocks only touch shared state at publication points,
so between chunks every row is at a publication boundary and its state is
a complete, bit-exact checkpoint (PR-4/PR-6 machinery: ``SwarmState``
carries the block-local ``lbest_*`` buffers, and splitting an async run
at sync points is bit-identical to the uninterrupted run —
tests/test_checkpoint.py).

Admission invariants (the whole correctness argument):

1. **Rows are admitted and removed only between dispatches** — i.e. at
   chunk boundaries. A fresh row enters via
   ``pso.init_swarm_async`` (init + seeded locals — exactly what
   ``run_async`` would do on its first call) spliced in with
   ``multi_swarm.set_batch_row``; the program never restarts.
2. **Every row in a lane is always at phase 0** (``iteration`` a
   multiple of the lane's ``sync_every``): rows start at 0 and advance in
   whole chunks, so the vmapped program's static ``phase=0`` is exact for
   every row at every dispatch — no phase-group splitting, ever.
3. **Iteration budgets are honored per row.** A request for ``T``
   iterations rides ``T // sync_every`` chunks; a non-zero remainder
   ejects the row at the last chunk boundary and finishes standalone via
   ``run_async`` (the proven resume path — publication schedule
   unchanged). Requests shorter than one chunk never enter a lane.

Consequence: every per-request result computes the standalone
``core.pso.solve(cfg, seed, T, "async", sync_every)`` of that request,
within the agreement limits of tests/agreement.py (a lane program and a
standalone solve are two compiled programs; tests/test_serving.py),
while steady-state throughput beats flush batching on mixed traffic —
lane compile keys DROP ``iters`` (accounting is per-row), so traffic that
fragments the flush server's groups rides one full lane here
(benchmarks/loadgen.py).

Heterogeneous lanes: registry built-ins coalesce into one lane per solve
shape (``lax.switch`` row dispatch, exactly the flush server's two-tier
grouping). Per-row problem descriptors are TRACED operands, so admitting
a *different* built-in into a freed slot recompiles nothing
(``multi_swarm.set_problem_row``).

Cold start: with a ``CompileCache`` attached, each lane program is traced
once ever — a restarted replica deserializes the exported program and
serves its first request with zero re-traces (``trace_events == 0``).

Synchronous variants have no publication boundaries to preempt at; those
requests (and sub-chunk ones) run standalone, counted in
``standalone_solves``.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.blocking import default_block_count
from repro.core.multi_swarm import (ProblemRows, batch_row, hetero_fid,
                                    init_row, problem_rows, run_many,
                                    set_batch_row, set_problem_row,
                                    stack_states)
from repro.core.problem import resolve_problem
from repro.core.pso import (HeteroRow, PSOConfig, init_swarm_async,
                            run_async, solve)
from repro.launch.serve import (_HETERO, _HETERO_CANONICAL_FITNESS,
                                SolveRequest, SolveResult, request_error)
from repro.telemetry.trace import SPAN_NAMES, now_us, span

from .compile_cache import CompileCache
from .metrics import ServingMetrics


@dataclasses.dataclass
class _Active:
    """One admitted request occupying a lane slot."""
    ticket: int
    request: SolveRequest
    done: int = 0            # iterations applied so far
    submitted_us: float = 0.0
    admitted_us: float = 0.0
    history: Optional[list] = None   # [(iteration, gbest_fit), ...] samples


class _Lane:
    """One persistent batched program: ``width`` slots advancing in chunks."""

    def __init__(self, key: Tuple, cfg: PSOConfig, width: int,
                 sync_every: int, hetero: bool, table=None):
        self.key = key
        self.uid = 0                           # display id (trace rows)
        self.cfg = cfg.resolved()
        self.width = width
        self.sync_every = sync_every
        self.hetero = hetero
        self.table = table
        self.nb = default_block_count(self.cfg.particle_cnt)
        self.batch = None                      # SwarmBatch [width, ...]
        self.rows: Optional[ProblemRows] = None
        self.slots: List[Optional[_Active]] = [None] * width
        self.chunks_dispatched = 0
        self.program = None

    @property
    def active_count(self) -> int:
        return sum(1 for a in self.slots if a is not None)

    def free_slot(self) -> Optional[int]:
        for i, a in enumerate(self.slots):
            if a is None:
                return i
        return None

    def program_key(self) -> str:
        c = self.cfg
        # Stable across processes (Python's tuple hash is salted): content
        # lanes key on a digest of the problem's content-hash tuple.
        content = (_HETERO if self.hetero
                   else "content:" + hashlib.sha1(
                       repr(self.key).encode()).hexdigest()[:16])
        return (f"lane|d{c.dim}|n{c.particle_cnt}|{c.dtype}"
                f"|se{self.sync_every}|nb{self.nb}|w{self.width}"
                f"|r{c.update_rule}|t{c.topology}|{content}")


class ContinuousScheduler:
    """Streaming solve front end over persistent batched async lanes.

    ``lane_width`` rows per lane; ``coalesce_registry`` merges registry
    built-ins at one solve shape into heterogeneous lanes; ``compile_cache`` (a
    ``serving.CompileCache``) makes lane programs restart-persistent;
    ``autotune=True`` rewrites async requests' ``sync_every`` to the
    model-tuned value and caps lane width at the autotuner's bucket
    ladder's last rung — the point where the cost model prices per-row
    gains as flattened, so admission never grows a lane past what pays.

    Telemetry (``repro.telemetry``): ``trace`` (a ``TraceWriter``) records
    the serving timeline — one Perfetto row per lane with a span per
    dispatched chunk, admit/eject instants, a per-request span, and a
    lane-fill counter track. ``record_history=True`` samples every lane
    row's gbest at its chunk boundaries onto ``SolveResult.history``
    (lane-riding async requests only; standalone fallbacks report None).

    Profiler spans (``repro.telemetry.span``): ``sched_step`` and
    ``sched_dispatch`` on every step, ``sched_admit`` while requests
    wait, ``sched_eject``/``sched_finish`` per row leaving a lane. The
    chunk and standalone events and the ``dispatch_us``/``compile_us``
    samples are fed from those spans' clock reads.

    Single-threaded and synchronous like ``SolveServer``: ``submit`` +
    ``step``/``drain`` (or one-shot ``run``).
    """

    def __init__(self, lane_width: int = 8,
                 coalesce_registry: bool = True,
                 compile_cache: Optional[CompileCache] = None,
                 autotune: bool = False,
                 metrics: Optional[ServingMetrics] = None,
                 trace=None, record_history: bool = False):
        self.lane_width = lane_width
        self.coalesce_registry = coalesce_registry
        self.autotune = autotune
        self.metrics = metrics or ServingMetrics()
        self.trace = trace
        self.record_history = record_history
        self.compile_cache = compile_cache
        if compile_cache is not None and compile_cache.metrics is None:
            compile_cache.metrics = self.metrics
        self._lanes: "OrderedDict[Tuple, _Lane]" = OrderedDict()
        self._pending: List[_Active] = []
        self._results: Dict[int, SolveResult] = {}
        self._ticket = 0
        self._ladder_width: Dict[Tuple, int] = {}

    # -- submission --------------------------------------------------------
    def submit(self, req: SolveRequest) -> int:
        t = self._ticket
        self._ticket += 1
        self.metrics.inc("submitted")
        self._pending.append(_Active(ticket=t, request=req,
                                     submitted_us=now_us()))
        return t

    def _tuned(self, r: SolveRequest) -> SolveRequest:
        if not self.autotune or r.variant != "async":
            return r
        from repro.core.autotune import tuned_sync_every
        k = tuned_sync_every(r.fitness, r.dim, r.particle_cnt, r.iters,
                             r.dtype)
        return dataclasses.replace(r, sync_every=k)

    # -- lane keying -------------------------------------------------------
    def _lane_key(self, r: SolveRequest) -> Tuple:
        """Like ``SolveRequest.group_key`` but WITHOUT ``iters`` — per-row
        accounting means mixed budgets share a lane."""
        hetero = self.coalesce_registry and hetero_fid(r.fitness) is not None
        content = _HETERO if hetero else resolve_problem(
            r.fitness).cache_key()
        return (r.dim, r.particle_cnt, r.dtype, r.sync_every,
                r.rule, r._topology_key(), content)

    def _lane_for(self, r: SolveRequest) -> _Lane:
        key = self._lane_key(r)
        lane = self._lanes.get(key)
        if lane is not None:
            return lane
        hetero = key[-1] == _HETERO
        if hetero:
            cfg = PSOConfig(dim=r.dim, particle_cnt=r.particle_cnt,
                            fitness=_HETERO_CANONICAL_FITNESS,
                            dtype=r.dtype, update_rule=r.rule,
                            topology=r._topology_key())
        else:
            cfg = PSOConfig(dim=r.dim, particle_cnt=r.particle_cnt,
                            fitness=r.fitness, dtype=r.dtype,
                            update_rule=r.rule,
                            topology=r._topology_key())
        lane = _Lane(key, cfg, self._width_for(r), r.sync_every, hetero)
        lane.uid = len(self._lanes)
        self._lanes[key] = lane
        return lane

    def _width_for(self, r: SolveRequest) -> int:
        if not self.autotune:
            return self.lane_width
        key = (r.dim, r.particle_cnt, r.variant, r.dtype)
        if key not in self._ladder_width:
            from repro.core.autotune import bucket_ladder
            ladder = bucket_ladder(
                r.fitness, r.dim, r.particle_cnt, r.iters,
                max_batch=self.lane_width, variant=r.variant,
                dtype=r.dtype)
            self._ladder_width[key] = ladder[-1]
        return self._ladder_width[key]

    # -- admission ---------------------------------------------------------
    def _admit(self) -> None:
        still: List[_Active] = []
        for a in self._pending:
            err = request_error(a.request)
            if err is not None:
                # mirror the flush server's admission rejection: a bad
                # variant/rule/topology gets its own error result and
                # never reaches a lane or a standalone solve
                self.metrics.inc("failed")
                self._results[a.ticket] = SolveResult(
                    request=a.request, gbest_fit=float("nan"),
                    gbest_pos=np.full((a.request.dim,), np.nan),
                    batch_size=0, error=err)
                continue
            r = self._tuned(a.request)
            if r.variant != "async" or r.iters < max(1, r.sync_every):
                self._solve_standalone(a, r)
                continue
            lane = self._lane_for(r)
            slot = lane.free_slot()
            if slot is None:
                still.append(a)     # lane full: wait for a chunk boundary
                continue
            self._splice(lane, slot, a, r)
        self._pending = still

    def _fresh_state(self, lane: _Lane, r: SolveRequest):
        """A fresh row for the lane, locals seeded (phase 0, iteration 0)."""
        if not lane.hetero:
            return init_swarm_async(lane.cfg, r.seed, n_blocks=lane.nb), None
        prob = resolve_problem(r.fitness)
        one, table = problem_rows([prob], lane.cfg.dim, lane.cfg.dtype)
        if lane.table is None:
            lane.table = table
        # the hetero init program at S=1, as a hetero batch's rows start
        return init_row(lane.cfg, r.seed, one, lane.table, lane.nb), one

    def _splice(self, lane: _Lane, slot: int, a: _Active,
                r: SolveRequest) -> None:
        state, one = self._fresh_state(lane, r)
        if lane.batch is None:
            # First admission bootstraps the lane: dead slots replicate the
            # first row (well-defined bounds, never read back).
            lane.batch = stack_states([state] * lane.width)
            if lane.hetero:
                lane.rows = ProblemRows(*jax_broadcast_rows(one, lane.width))
        else:
            lane.batch = set_batch_row(lane.batch, slot, state)
            if lane.hetero:
                lane.rows = set_problem_row(lane.rows, slot, one)
        a.request = r
        a.admitted_us = now_us()
        self.metrics.observe("queue_us", a.admitted_us - a.submitted_us)
        self.metrics.inc("admitted")
        if lane.chunks_dispatched:
            self.metrics.inc("row_swaps")
        if self.record_history:
            a.history = []
        if self.trace is not None:
            self.trace.instant(
                f"admit t{a.ticket}", a.admitted_us, process="serving",
                thread=f"lane {lane.uid}", cat="admission",
                args={"slot": slot, "fitness": str(r.fitness),
                      "iters": r.iters})
        lane.slots[slot] = a

    # -- standalone fallbacks ---------------------------------------------
    def _solve_standalone(self, a: _Active, r: SolveRequest) -> None:
        a.admitted_us = now_us()
        self.metrics.observe("queue_us", a.admitted_us - a.submitted_us)
        cfg = PSOConfig(dim=r.dim, particle_cnt=r.particle_cnt,
                        fitness=r.fitness, dtype=r.dtype,
                        update_rule=r.rule, topology=r._topology_key())
        with span(SPAN_NAMES.sched_standalone, trace=self.trace,
                  event=lambda: (f"standalone t{a.ticket}", dict(
                      process="serving", thread="standalone", cat="solve",
                      args={"fitness": str(r.fitness), "variant": r.variant,
                            "iters": r.iters}))):
            st = solve(cfg, r.seed, r.iters, r.variant, r.sync_every)
        self.metrics.inc("standalone_solves")
        self._finish(a, float(st.gbest_fit), np.asarray(st.gbest_pos),
                     batch_size=1)

    def _eject(self, lane: _Lane, slot: int, rem: int) -> None:
        """Finish a row's sub-chunk remainder standalone at a boundary."""
        a = lane.slots[slot]
        state = batch_row(lane.batch, slot)
        if lane.hetero:
            hr = HeteroRow(fid=lane.rows.fid[slot], lo=lane.rows.lo[slot],
                           hi=lane.rows.hi[slot], mv=lane.rows.mv[slot])
            st = run_async(lane.cfg, state, rem,
                           sync_every=lane.sync_every, n_blocks=lane.nb,
                           hetero_row=hr, table=lane.table)
        else:
            st = run_async(lane.cfg, state, rem,
                           sync_every=lane.sync_every, n_blocks=lane.nb)
        lane.slots[slot] = None
        self.metrics.inc("tail_ejections")
        if a.history is not None:
            a.history.append((a.request.iters, float(st.gbest_fit)))
        if self.trace is not None:
            self.trace.instant(
                f"eject t{a.ticket}", now_us(), process="serving",
                thread=f"lane {lane.uid}", cat="admission",
                args={"slot": slot, "remainder": rem})
        self._finish(a, float(st.gbest_fit), np.asarray(st.gbest_pos),
                     batch_size=lane.width)

    def _finish(self, a: _Active, gf: float, gp: np.ndarray,
                batch_size: int) -> None:
        now = now_us()
        self.metrics.observe("solve_us", now - a.admitted_us)
        self.metrics.observe("e2e_us", now - a.submitted_us)
        self.metrics.inc("completed")
        hist = None
        if a.history:
            from repro.api import History
            its, fits = zip(*a.history)
            hist = History(iteration=np.asarray(its, dtype=np.int64),
                           gbest_fit=np.asarray(fits), violation=None)
        if self.trace is not None:
            self.trace.complete(
                f"request t{a.ticket}", a.submitted_us,
                now - a.submitted_us, process="requests",
                thread=f"ticket {a.ticket}", cat="request",
                args={"fitness": str(a.request.fitness),
                      "iters": a.request.iters,
                      "batch_size": batch_size, "gbest_fit": gf})
        self._results[a.ticket] = SolveResult(
            request=a.request, gbest_fit=gf, gbest_pos=gp,
            batch_size=batch_size, history=hist)

    # -- dispatch ----------------------------------------------------------
    def _lane_program(self, lane: _Lane):
        if lane.program is not None:
            return lane.program
        cfg, chunk, se, nb, table = (lane.cfg, lane.sync_every,
                                     lane.sync_every, lane.nb, lane.table)
        if lane.hetero:
            def build(batch, rows):
                return run_many(cfg, batch, chunk, "async", sync_every=se,
                                rows=rows, table=table, n_blocks=nb)
            args = (lane.batch, lane.rows)
        else:
            def build(batch):
                return run_many(cfg, batch, chunk, "async", sync_every=se,
                                n_blocks=nb)
            args = (lane.batch,)
        if self.compile_cache is None:
            lane.program = build
        else:
            with span(SPAN_NAMES.sched_compile, metrics=self.metrics,
                      metric="compile_us"):
                lane.program = self.compile_cache.get(
                    lane.program_key(), build, *args)
        return lane.program

    def _chunk_event(self, lane: _Lane):
        """The ``TraceWriter`` event of the lane's next dispatch."""
        return (f"chunk {lane.chunks_dispatched + 1}", dict(
            process="serving", thread=f"lane {lane.uid}", cat="dispatch",
            args={"active": lane.active_count, "width": lane.width,
                  "sync_every": lane.sync_every}))

    def _dispatch(self, lane: _Lane) -> None:
        program = self._lane_program(lane)
        args = (lane.batch, lane.rows) if lane.hetero else (lane.batch,)
        with span(SPAN_NAMES.sched_dispatch, metrics=self.metrics,
                  metric="dispatch_us", trace=self.trace,
                  event=lambda: self._chunk_event(lane)) as sp:
            out = program(*args)
            out.gbest_fit.block_until_ready()
        lane.batch = out
        lane.chunks_dispatched += 1
        self.metrics.inc("dispatches")
        self.metrics.inc("lane_slots", lane.width)
        self.metrics.inc("lane_active_slots", lane.active_count)
        if self.trace is not None:
            self.trace.counter(f"lane {lane.uid} fill", sp.t0_us,
                               {"active": lane.active_count,
                                "idle": lane.width - lane.active_count})
        for i, a in enumerate(lane.slots):
            if a is not None:
                a.done += lane.sync_every
                if a.history is not None:
                    a.history.append((a.done,
                                      float(lane.batch.gbest_fit[i])))

    # -- the loop ----------------------------------------------------------
    def step(self) -> Dict[int, SolveResult]:
        """One scheduling round: admit at the boundary, advance every
        active lane one chunk, harvest completions. Returns the results
        that completed this round (also retained for ``drain``/``run``)."""
        with span(SPAN_NAMES.sched_step):
            before = set(self._results)
            if self._pending:
                with span(SPAN_NAMES.sched_admit):
                    self._admit()
            for lane in list(self._lanes.values()):
                # Boundary bookkeeping first: rows whose remainder is
                # shorter than a chunk leave now (standalone finish,
                # proven resume).
                for i, a in enumerate(lane.slots):
                    if a is None:
                        continue
                    rem = a.request.iters - a.done
                    if 0 < rem < lane.sync_every:
                        with span(SPAN_NAMES.sched_eject):
                            self._eject(lane, i, rem)
                if lane.active_count == 0:
                    continue
                self._dispatch(lane)
                for i, a in enumerate(lane.slots):
                    if a is not None and a.done >= a.request.iters:
                        with span(SPAN_NAMES.sched_finish):
                            gf = float(lane.batch.gbest_fit[i])
                            gp = np.asarray(lane.batch.gbest_pos[i])
                            lane.slots[i] = None
                            self._finish(a, gf, gp, batch_size=lane.width)
            return {t: r for t, r in self._results.items()
                    if t not in before}

    @property
    def busy(self) -> bool:
        return bool(self._pending) or any(
            lane.active_count for lane in self._lanes.values())

    def drain(self) -> Dict[int, SolveResult]:
        """Step until every submitted request has a result."""
        while self.busy:
            self.step()
        return dict(self._results)

    def run(self, requests) -> List[SolveResult]:
        """Convenience one-shot: submit all + drain, results in order."""
        tickets = [self.submit(r) for r in requests]
        resolved = self.drain()
        return [resolved[t] for t in tickets]

    def snapshot(self) -> dict:
        """Serving state: metrics + lane occupancy + compile-cache stats."""
        doc = self.metrics.snapshot()
        doc["lanes"] = [
            {"key": repr(lane.key), "width": lane.width,
             "active": lane.active_count,
             "chunks": lane.chunks_dispatched}
            for lane in self._lanes.values()]
        if self.compile_cache is not None:
            doc["compile_cache"] = self.compile_cache.snapshot()
        return doc


def jax_broadcast_rows(one: ProblemRows, width: int) -> tuple:
    """Replicate a 1-row descriptor set to ``width`` rows (lane bootstrap)."""
    import jax
    import jax.numpy as jnp
    return tuple(jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[:1], (width,) + a.shape[1:]),
        tuple(one)))
