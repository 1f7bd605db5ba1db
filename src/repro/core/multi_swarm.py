"""Batched multi-swarm engine: many independent PSO solves in ONE device
program (the scaling layer on top of the paper's single-swarm
queue/queue-lock algorithms).

The paper (arXiv 2205.01313) amortizes aggregation cost *within* one swarm;
serving-scale workloads (tuning sweeps, per-request optimizations) need to
amortize across *many* swarms — different seeds, and optionally different
(w, c1, c2) hyper-parameters — without paying one dispatch + compile per
swarm. This module vmaps the three step variants from ``repro.core.pso``
over a leading swarm axis, so a batch of S solves costs one compile and one
dispatch per ``run_many`` call. PSO-PS (arXiv 2009.03816) makes the same
move to keep distributed populations device-resident.

RNG stream convention
---------------------
Each swarm carries its own ``seed`` and the counter RNG is keyed by
``(seed, iteration, stream, element_index)`` with element indices local to
the swarm (particle * D + dim, exactly the single-swarm ``index_offset=0``
convention of ``init_swarm``/``_advance``). vmap changes neither the
counters nor the update, so row ``s`` of a batch computes the trajectory
of a standalone ``solve(cfg, seeds[s])``: batching is a scheduling
transform, never a semantic one.

Agreement
---------
The batched program and the standalone one are different compiled
programs. XLA:CPU chooses loop-body fusion and FMA contraction per
compiled shape, while the chip and the eager oracles round op by op, so a
row and its standalone solve agree within an ulp or two a step, which PSO's
chaotic dynamics can grow over a long run. tests/agreement.py states the
limits (the benchmark's own): positions and velocities within 1e-5 of the
box, fitness values within 1e-5 relative, and for runs long enough to flip
a pbest comparison, the outcome (gbest value, and the reported value
against the objective at the reported position). A batch of any size runs
as it is; no row is added.

Per-swarm hyper-parameters
--------------------------
``coeffs=(w, c1, c2)`` (each shape ``[S]``) rides the same vmap, which is
what lets ``repro.core.tuner.make_solve_many_fitness`` evaluate a whole
population of PSO hyper-parameter candidates as one batched solve.

The Pallas counterpart (one ``pallas_call`` advancing S swarms x iters with
per-swarm gbest buffers) is ``repro.kernels.ops.run_queue_lock_fused_batch``.

Problems: ``cfg.fitness`` may be a registered benchmark name or a
first-class ``repro.core.problem.Problem`` (user objective, per-dimension
bounds, min/max sense) — the vmapped step functions and the batched Pallas
kernels both resolve it through the same registry/adapter machinery, so a
batch of custom-objective solves is one device program too. The serving
front end (``repro.launch.serve``) relies on this plus content-hashed
compile keys to batch identical custom objectives safely.
"""
from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .problem import Problem, resolve_problem
from .pso import (ASYNC_SYNC_EVERY, HeteroRow, PSOConfig, STEP_FNS,
                  SwarmState, _init_body, count_init_trace, run_async,
                  seed_operand)

Array = jnp.ndarray


class ProblemRows(NamedTuple):
    """Per-row problem descriptors for a heterogeneous batch.

    Built by ``problem_rows`` against a *static* dispatch table (a tuple of
    registered ``Problem``s, by default the built-in benchmark suite):
    ``fid[s]`` indexes the table, and the bound columns replicate exactly
    the arithmetic ``PSOConfig.resolved()`` would have produced for row
    ``s``'s problem (Python-float64 ``0.5 * (hi - lo)`` then a cast), so a
    heterogeneous row has the box of the standalone solve of its problem.
    ``sense``/``cmode``/``pweight`` are descriptor metadata —
    constant for the built-in table (max-sense, unconstrained, weight 0) —
    reserved for the two-tier custom-objective follow-on where penalty-mode
    registry entries join the table.
    """

    fid: Array      # [S] int32 — index into the static dispatch table
    lo: Array       # [S, D] lower box bound per row
    hi: Array       # [S, D] upper box bound per row
    mv: Array       # [S, D] velocity clamp per row
    sense: Array    # [S] int32: +1 max / -1 min (baked into the branch)
    cmode: Array    # [S] int32: 0 unconstrained / 1 penalty
    pweight: Array  # [S] penalty weight (0 when unconstrained)

    @property
    def swarm_cnt(self) -> int:
        return self.fid.shape[0]



def hetero_fid(fitness) -> Optional[int]:
    """Index of ``fitness`` in the built-in dispatch table, else None.

    The coalescing eligibility test: a request whose problem IS one of the
    registered built-ins (dataclass equality — ``fn`` by identity, so
    registry-resolved instances match and a user's re-built lookalike does
    not) can ride a shared heterogeneous batch; everything else keeps
    content-hash isolation.
    """
    from .fitness import BUILTIN_PROBLEMS
    try:
        prob = resolve_problem(fitness)
    except (KeyError, TypeError):
        return None
    for i, p in enumerate(BUILTIN_PROBLEMS):
        if prob == p:
            return i
    return None


def _row_bound(v, d: int, dt) -> np.ndarray:
    """Resolved Bound (scalar or per-dim tuple) -> [D] host array."""
    if isinstance(v, tuple):
        return np.asarray(v, dt)
    return np.full((d,), v, dt)


def problem_rows(problems: Sequence, dim: int, dtype: str = "float32",
                 table: Optional[Tuple[Problem, ...]] = None
                 ) -> Tuple[ProblemRows, Tuple[Problem, ...]]:
    """Build the per-row descriptors for a heterogeneous batch.

    ``problems`` are names or ``Problem``s, each of which must appear in
    ``table`` (default: the built-in benchmark suite) — the static branch
    tuple the engines ``lax.switch`` over. Table entries must be
    unconstrained or penalty-mode (the penalty rides ``max_fn``):
    projection/repair entries would need per-row init/advance hooks and are
    rejected. Returns ``(rows, table)``.
    """
    from .fitness import BUILTIN_PROBLEMS
    table = BUILTIN_PROBLEMS if table is None else tuple(table)
    for p in table:
        if p.projection_fn is not None or (
                p.constrained and p.constraints.mode == "repair"):
            raise ValueError(
                f"problem {p.name!r}: projection/repair constraint modes "
                "cannot join a heterogeneous dispatch table (per-row "
                "init/advance hooks); solve it in its own batch")
    dt = np.dtype(dtype)
    fid, lo, hi, mv, sense, cmode, pw = [], [], [], [], [], [], []
    for f in problems:
        prob = resolve_problem(f)
        try:
            i = table.index(prob)
        except ValueError:
            raise ValueError(
                f"problem {prob.name!r} is not in the heterogeneous "
                "dispatch table; solve it in its own (content-keyed) batch"
            ) from None
        # Exactly the standalone bound resolution (max_v = 0.5 * (hi - lo)
        # in Python float64, then one cast).
        r = PSOConfig(dim=dim, fitness=prob, dtype=dtype).resolved()
        fid.append(i)
        lo.append(_row_bound(r.min_pos, dim, dt))
        hi.append(_row_bound(r.max_pos, dim, dt))
        mv.append(_row_bound(r.max_v, dim, dt))
        sense.append(1 if prob.sense == "max" else -1)
        cset = prob.constraints
        penalized = cset is not None and cset.mode == "penalty"
        cmode.append(1 if penalized else 0)
        pw.append(cset.weight if penalized else 0.0)
    return ProblemRows(
        fid=jnp.asarray(fid, jnp.int32),
        lo=jnp.asarray(np.stack(lo)), hi=jnp.asarray(np.stack(hi)),
        mv=jnp.asarray(np.stack(mv)),
        sense=jnp.asarray(sense, jnp.int32),
        cmode=jnp.asarray(cmode, jnp.int32),
        pweight=jnp.asarray(np.asarray(pw, dt)),
    ), table


def _hetero_rows(rows: ProblemRows) -> HeteroRow:
    """The engine-facing slice of the descriptors (vmaps to per-row)."""
    return HeteroRow(fid=rows.fid, lo=rows.lo, hi=rows.hi, mv=rows.mv)


class SwarmBatch(NamedTuple):
    """S independent swarms, stacked on a leading axis.

    Field order matches ``SwarmState`` exactly so the two convert by
    positional splat (``SwarmBatch(*state_pytree)``) and vmapped SwarmState
    functions apply directly.
    """

    pos: Array        # [S, N, D]
    vel: Array        # [S, N, D]
    fit: Array        # [S, N]
    pbest_pos: Array  # [S, N, D]
    pbest_fit: Array  # [S, N]
    gbest_pos: Array  # [S, D]
    gbest_fit: Array  # [S]
    iteration: Array  # [S] int32
    seed: Array       # [S] uint32
    lbest_pos: Optional[Array] = None  # [S, nb, D] async block-local bests
    lbest_fit: Optional[Array] = None  # [S, nb]

    @property
    def swarm_cnt(self) -> int:
        return self.gbest_fit.shape[0]


def init_batch(cfg: PSOConfig, seeds, rows: Optional[ProblemRows] = None,
               table: Optional[Tuple[Problem, ...]] = None) -> SwarmBatch:
    """Initialize S swarms, one per entry of ``seeds``.

    Row ``s`` is the init of ``init_swarm(cfg, seeds[s])``: the RNG counters
    are untouched by the vmap. With ``rows``/``table`` (heterogeneous
    batch) row ``s`` takes its box from its row's columns and its objective
    through the table's switch, as the run does, so its positions are its
    problem's standalone init.

    One compiled program per config, S and table, the seeds (as uint32,
    ``pso.seed_operand``) and the rows its operands: a new mix of problems
    compiles nothing.
    """
    cfg = cfg.resolved()
    seeds = seed_operand(seeds)
    hr = None if rows is None else _hetero_rows(rows)
    return _init_batch_program(cfg, seeds, hr, table, None)


@partial(jax.jit, static_argnames=("cfg", "table", "n_blocks"))
def _init_batch_program(cfg: PSOConfig, seeds, hr: Optional[HeteroRow],
                        table, n_blocks: Optional[int]) -> SwarmBatch:
    count_init_trace()
    n = cfg.particle_cnt
    if hr is None:
        return SwarmBatch(*jax.vmap(lambda sd: _init_body(
            cfg, sd, 0, n, n_blocks))(seeds))
    return SwarmBatch(*jax.vmap(lambda sd, r: _init_body(
        cfg, sd, 0, n, n_blocks, hetero=(table, r)))(seeds, hr))


def init_row(cfg: PSOConfig, seed, row: ProblemRows,
             table: Tuple[Problem, ...],
             n_blocks: Optional[int] = None) -> SwarmState:
    """A one-row hetero ``init_batch``, as a standalone swarm: the same
    program at S=1 and its row, in one dispatch (the scheduler's hetero
    admission)."""
    return _init_row_program(cfg.resolved(), seed_operand([seed]),
                             _hetero_rows(row), table, n_blocks)


@partial(jax.jit, static_argnames=("cfg", "table", "n_blocks"))
def _init_row_program(cfg: PSOConfig, seeds, hr: HeteroRow, table,
                      n_blocks: Optional[int]) -> SwarmState:
    batch = _init_batch_program(cfg, seeds, hr, table, n_blocks)
    return SwarmState(*(None if a is None else a[0] for a in batch))


def batch_row(batch: SwarmBatch, s: int) -> SwarmState:
    """Extract swarm ``s`` as a standalone SwarmState."""
    return SwarmState(*(jax.tree_util.tree_map(lambda a: a[s], tuple(batch))))


def host_rows(batch: SwarmBatch) -> List[SwarmState]:
    """Fetch a finished batch to the host in ONE transfer and split it into
    standalone swarms whose fields are numpy views of the fetched arrays
    (``lbest_*`` stay None when the batch has none).

    Row ``s`` holds the same bytes as ``batch_row(batch, s)``, without an
    eager device slice per field and row: the batched facade's split of a
    finished call. ``batch_row`` stays the device-side extraction for a
    batch that keeps running (the scheduler's ejection)."""
    host = jax.device_get(batch)
    # a[s, ...] keeps a 0-d array where a[s] would give a numpy scalar
    return [SwarmState(*(None if a is None else a[s, ...] for a in host))
            for s in range(host.swarm_cnt)]


def stack_states(states: Sequence[SwarmState]) -> SwarmBatch:
    """Stack standalone swarms into a batch (inverse of ``batch_row``)."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    return SwarmBatch(*stacked)


def set_batch_row(batch: SwarmBatch, s: int, state: SwarmState) -> SwarmBatch:
    """Splice a standalone swarm into row ``s`` (the scheduler's admission
    primitive: a continuous-batching lane swaps a finished row for a fresh
    request without restarting the program).

    The batch's pytree structure is fixed by the in-flight compiled
    program, so ``state`` must match it field-for-field — in particular an
    async batch carries ``lbest_*`` and the admitted row must too (build
    it with ``repro.core.pso.init_swarm_async``).
    """
    if (batch.lbest_fit is None) != (state.lbest_fit is None):
        raise ValueError(
            "row/batch lbest structure mismatch: splice rows built with "
            "init_swarm_async into async batches (and bare init_swarm "
            "rows into synchronous ones)")
    return SwarmBatch(*jax.tree_util.tree_map(
        lambda a, v: a.at[s].set(v), tuple(batch), tuple(state)))


def set_problem_row(rows: ProblemRows, s: int, one: ProblemRows
                    ) -> ProblemRows:
    """Splice row 0 of a 1-row descriptor set into row ``s`` of ``rows``.

    The hetero half of lane admission: descriptors are TRACED operands of
    the batched program (only the table is static), so retargeting a lane
    slot at a different registered problem recompiles nothing.
    """
    return ProblemRows(*jax.tree_util.tree_map(
        lambda a, v: a.at[s].set(v[0]), tuple(rows), tuple(one)))


@partial(jax.jit,
         static_argnames=("cfg", "iters", "sync_every", "phase", "table",
                          "n_blocks"))
def _run_many_async(cfg: PSOConfig, batch: SwarmBatch, iters: int,
                    sync_every: int,
                    coeffs: Optional[Tuple[Array, Array, Array]] = None,
                    phase: int = 0, rows: Optional[ProblemRows] = None,
                    table=None,
                    n_blocks: Optional[int] = None) -> SwarmBatch:
    hr = None if rows is None else _hetero_rows(rows)
    if coeffs is None and hr is None:
        fn = jax.vmap(lambda s: run_async(
            cfg, s, iters, sync_every=sync_every, phase=phase,
            n_blocks=n_blocks))
        return SwarmBatch(*fn(SwarmState(*batch)))
    if coeffs is None:
        fn = jax.vmap(lambda s, f: run_async(
            cfg, s, iters, sync_every=sync_every, phase=phase,
            hetero_row=f, table=table, n_blocks=n_blocks))
        return SwarmBatch(*fn(SwarmState(*batch), hr))
    w, c1, c2 = (jnp.asarray(c) for c in coeffs)
    if hr is None:
        fn = jax.vmap(lambda s, w_, c1_, c2_: run_async(
            cfg, s, iters, sync_every=sync_every, coeffs=(w_, c1_, c2_),
            phase=phase, n_blocks=n_blocks))
        return SwarmBatch(*fn(SwarmState(*batch), w, c1, c2))
    fn = jax.vmap(lambda s, w_, c1_, c2_, f: run_async(
        cfg, s, iters, sync_every=sync_every, coeffs=(w_, c1_, c2_),
        phase=phase, hetero_row=f, table=table, n_blocks=n_blocks))
    return SwarmBatch(*fn(SwarmState(*batch), w, c1, c2, hr))


def _batch_phases(batch: SwarmBatch, sync_every: int) -> Tuple[int, ...]:
    """Per-swarm resume phases (``iteration % sync_every``), host-side.

    ``run_async``'s publication schedule aligns to absolute iteration
    numbers via a *static* ``phase``; under vmap the per-row iteration is a
    tracer, so the single-swarm auto-derivation silently fell back to 0 and
    a resumed batched async solve restarted every swarm's staleness window
    (PR 5 known bug). The phases are read off the concrete batch before jit
    entry instead. Under a trace (run_many called inside jit) the counters
    are unreadable — fall back to 0, the historical relative behavior.
    """
    se = max(1, sync_every)
    try:
        return tuple(int(i) % se for i in batch.iteration)
    except (TypeError, jax.errors.ConcretizationTypeError,
            jax.errors.TracerIntegerConversionError):
        return (0,) * batch.swarm_cnt


def _batched_step(cfg: PSOConfig, variant: str, coeffs, hr, table):
    """One vmapped step over the batch, closed over the static extras
    (per-swarm coeffs and/or hetero rows) — shared by the fori_loop runner
    and the history-recording scan so both trace the same computation."""
    step = STEP_FNS[variant]
    if coeffs is None and hr is None:
        step_b = jax.vmap(lambda s: step(cfg, s))
        return lambda b: SwarmBatch(*step_b(SwarmState(*b)))
    if hr is None:
        w, c1, c2 = (jnp.asarray(c) for c in coeffs)
        step_b = jax.vmap(
            lambda s, w_, c1_, c2_: step(cfg, s, coeffs=(w_, c1_, c2_)))
        return lambda b: SwarmBatch(*step_b(SwarmState(*b), w, c1, c2))
    if coeffs is None:
        step_b = jax.vmap(lambda s, h: step(cfg, s, hetero=(table, h)))
        return lambda b: SwarmBatch(*step_b(SwarmState(*b), hr))
    w, c1, c2 = (jnp.asarray(c) for c in coeffs)
    step_b = jax.vmap(
        lambda s, w_, c1_, c2_, h: step(cfg, s, coeffs=(w_, c1_, c2_),
                                        hetero=(table, h)))
    return lambda b: SwarmBatch(*step_b(SwarmState(*b), w, c1, c2, hr))


@partial(jax.jit, static_argnames=("cfg", "iters", "variant", "table"))
def _run_many_stepped(cfg: PSOConfig, batch: SwarmBatch, iters: int,
                      variant: str,
                      coeffs: Optional[Tuple[Array, Array, Array]] = None,
                      rows: Optional[ProblemRows] = None, table=None
                      ) -> SwarmBatch:
    hr = None if rows is None else _hetero_rows(rows)
    step_b = _batched_step(cfg, variant, coeffs, hr, table)
    return jax.lax.fori_loop(0, iters, lambda _, b: step_b(b), batch)


@partial(jax.jit, static_argnames=("cfg", "iters", "variant", "table"))
def _run_many_stepped_history(cfg: PSOConfig, batch: SwarmBatch, iters: int,
                              variant: str,
                              coeffs=None, rows: Optional[ProblemRows] = None,
                              table=None):
    """``_run_many_stepped`` that also stacks the per-iteration gbest
    trajectory: one scan over the same vmapped step, collecting
    ``gbest_fit`` [iters, S] (and the recorded gbest's aggregate constraint
    violation for constrained homogeneous batches — hetero rows are
    built-in table entries, so their violations are identically zero)."""
    hr = None if rows is None else _hetero_rows(rows)
    step_b = _batched_step(cfg, variant, coeffs, hr, table)
    vf = None if rows is not None else cfg.problem.violation_fn

    def body(b, _):
        b = step_b(b)
        v = (jax.vmap(vf)(b.gbest_pos) if vf is not None
             else jnp.zeros_like(b.gbest_fit))
        return b, (b.gbest_fit, v)

    batch, (fits, viols) = jax.lax.scan(body, batch, xs=None, length=iters)
    return batch, fits, viols


def run_many(cfg: PSOConfig, batch: SwarmBatch, iters: int,
             variant: str = "queue",
             coeffs: Optional[Tuple[Array, Array, Array]] = None,
             sync_every: int = ASYNC_SYNC_EVERY,
             rows: Optional[ProblemRows] = None,
             table: Optional[Tuple[Problem, ...]] = None,
             n_blocks: Optional[int] = None) -> SwarmBatch:
    """Advance every swarm of the batch ``iters`` iterations in lockstep.

    One fori_loop over one vmapped step: a single compiled program, a single
    dispatch, no host round-trips between iterations or between swarms.
    ``variant="async"`` vmaps the whole ``run_async`` loop nest instead (it
    carries block-local bests across iterations, so it cannot ride the
    per-step registry); ``run_async`` is cond-free, so the vmap is a pure
    scheduling transform like the others.
    A thin dispatcher over the jitted implementations, so synchronous
    variants never key their jit cache on the (irrelevant) ``sync_every``.
    """
    cfg = cfg.resolved()
    s_cnt = batch.swarm_cnt
    if variant == "async":
        phases = _batch_phases(batch, sync_every)
        uniq = sorted(set(phases))
        if len(uniq) == 1:
            return _run_many_async(cfg, batch, iters, sync_every, coeffs,
                                   uniq[0], rows, table, n_blocks)
        # Mixed resume points (rows checkpointed at different iterations):
        # phase is static per compiled program, so dispatch one
        # program per phase group and scatter the rows back in place.
        out_rows = [None] * s_cnt
        for ph in uniq:
            idx = [i for i, p in enumerate(phases) if p == ph]
            take = jnp.asarray(idx)
            sub = SwarmBatch(*jax.tree_util.tree_map(
                lambda a: a[take], tuple(batch)))
            sub_coeffs = (tuple(jnp.asarray(c)[take] for c in coeffs)
                          if coeffs is not None else None)
            sub_rows = (ProblemRows(*jax.tree_util.tree_map(
                lambda a: a[take], tuple(rows)))
                if rows is not None else None)
            out = run_many(cfg, sub, iters, variant, sub_coeffs, sync_every,
                           sub_rows, table, n_blocks)
            for j, i in enumerate(idx):
                out_rows[i] = jax.tree_util.tree_map(lambda a: a[j],
                                                     tuple(out))
        return SwarmBatch(*jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *out_rows))
    if batch.lbest_fit is not None:
        # mirror run(): sync variants advance gbest without maintaining the
        # async block-local cache — drop it so a later async run re-seeds
        batch = batch._replace(lbest_pos=None, lbest_fit=None)
    return _run_many_stepped(cfg, batch, iters, variant, coeffs, rows, table)


def run_many_with_history(cfg: PSOConfig, batch: SwarmBatch, iters: int,
                          variant: str = "queue",
                          coeffs: Optional[Tuple[Array, Array, Array]] = None,
                          sync_every: int = ASYNC_SYNC_EVERY,
                          rows: Optional[ProblemRows] = None,
                          table: Optional[Tuple[Problem, ...]] = None,
                          n_blocks: Optional[int] = None):
    """``run_many`` that also records every row's gbest trajectory.

    Returns ``(batch, (iterations, gbest_fits, violations))`` with
    ``iterations`` a length-K tuple of absolute iteration numbers and
    ``gbest_fits`` a ``[K, S]`` array — one sample per sync point per row,
    mirroring the single-swarm ``run_with_history`` semantics: every
    iteration for the synchronous variants (one scanned program), every
    publication boundary for ``async`` (the vmapped loop nest is segmented
    at sync points, which the checkpoint/resume machinery makes
    bit-identical to the uninterrupted run). ``violations`` is ``[K, S]``
    for constrained homogeneous batches, else None (hetero rows are
    built-in table entries — unconstrained or static-penalty). Assumes the
    lockstep batches the facades build (all rows at one iteration count).
    """
    cfg = cfg.resolved()
    constrained = rows is None and cfg.problem.constrained
    if iters <= 0:
        empty = jnp.zeros((0, batch.swarm_cnt), batch.gbest_fit.dtype)
        return batch, ((), empty, empty if constrained else None)
    if variant == "async":
        vf = None if rows is not None else cfg.problem.violation_fn
        its, fits, viols = [], [], []
        done = 0
        while done < iters:
            k = min(max(1, sync_every), iters - done)
            batch = run_many(cfg, batch, k, variant, coeffs, sync_every,
                             rows, table, n_blocks)
            done += k
            its.append(int(batch.iteration[0]))
            fits.append(batch.gbest_fit)
            if vf is not None:
                viols.append(jax.vmap(vf)(batch.gbest_pos))
        return batch, (tuple(its), jnp.stack(fits),
                       jnp.stack(viols) if constrained else None)
    if batch.lbest_fit is not None:
        batch = batch._replace(lbest_pos=None, lbest_fit=None)
    start = int(batch.iteration[0])
    batch, fits, viols = _run_many_stepped_history(cfg, batch, iters,
                                                   variant, coeffs, rows,
                                                   table)
    its = tuple(range(start + 1, start + iters + 1))
    return batch, (its, fits, viols if constrained else None)


def solve_many(cfg: PSOConfig, seeds, iters: int = 1000,
               variant: str = "queue",
               coeffs: Optional[Tuple[Array, Array, Array]] = None,
               sync_every: int = ASYNC_SYNC_EVERY,
               problems: Optional[Sequence] = None,
               n_blocks: Optional[int] = None) -> SwarmBatch:
    """Batched one-shot: init + run for S independent solves.

    ``seeds`` is any int sequence/array of length S; ``variant`` is one of
    ``reduction | queue | queue_lock | async``; ``coeffs`` optionally
    supplies per-swarm ``(w, c1, c2)`` arrays; ``sync_every`` is the async
    variant's publication interval. Row ``s`` of the result computes
    ``solve(cfg, seeds[s], iters, variant)`` when ``coeffs`` is None
    (module docstring: "Agreement").

    ``problems`` (length S, names or registered built-in ``Problem``s)
    makes the batch *heterogeneous*: row ``s`` solves ``problems[s]`` —
    its own objective (dispatched by ``lax.switch`` inside one compiled
    program) and its own box bounds — and computes
    ``solve(cfg_s, seeds[s], iters, variant)`` with ``cfg_s`` the same
    config pointed at ``problems[s]``. ``cfg.fitness`` is ignored for the
    rows (it only keys the compile cache — the serving layer pins it to a
    canonical value so every mix shares one program) and explicit
    ``min_pos``/``max_pos``/``max_v`` overrides are rejected: bounds come
    from each row's problem.
    """
    if problems is not None:
        if (cfg.min_pos is not None or cfg.max_pos is not None
                or cfg.max_v is not None):
            raise ValueError(
                "heterogeneous batches take bounds from each row's "
                "problem; pass a config without min_pos/max_pos/max_v "
                "overrides (and not already resolved())")
        seeds = jnp.asarray(seeds)
        if len(problems) != seeds.shape[0]:
            raise ValueError(
                f"{len(problems)} problems for {seeds.shape[0]} seeds")
        rows, table = problem_rows(problems, cfg.dim, cfg.dtype)
        cfg = cfg.resolved()
        batch = init_batch(cfg, seeds, rows=rows, table=table)
        return run_many(cfg, batch, iters, variant, coeffs, sync_every,
                        rows, table, n_blocks)
    cfg = cfg.resolved()
    return run_many(cfg, init_batch(cfg, seeds), iters, variant, coeffs,
                    sync_every, n_blocks=n_blocks)


def best_of_batch(batch: SwarmBatch) -> Tuple[Array, Array, Array]:
    """(best gbest_fit, its gbest_pos, winning swarm index) over the batch."""
    b = jnp.argmax(batch.gbest_fit)
    return batch.gbest_fit[b], batch.gbest_pos[b], b
