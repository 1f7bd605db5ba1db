"""The unified solve facade: ``repro.solve(problem, ...) -> Result``.

One entry point over the previously scattered surfaces (``core.pso.solve``,
``core.multi_swarm.solve_many``, ``kernels.ops.run_queue_lock_fused{,_batch,
_async,_async_batch}`` and the serving backend plumbing):

    import repro

    res = repro.solve("cubic", dim=120, particles=2048, iters=500)
    res = repro.solve(my_problem, iters=1000,
                      method=repro.Method(variant="async", backend="kernel"))

``problem`` is a registered name, a ``repro.Problem`` (user objective with
per-dimension bounds and min/max sense), or a bare pure-jnp callable.
``Method`` picks the aggregation variant and execution backend:

* ``variant``: ``reduction | queue | queue_lock | async`` (paper §3.2/§4).
* ``backend``: ``jnp`` (vmap-able XLA step functions), ``kernel`` (the
  fused/async Pallas TPU kernels; only ``queue_lock``/``async`` exist as
  kernels), or ``auto`` — kernel on a TPU backend for the two fused
  variants, jnp everywhere else. The kernels run compiled on a TPU and in
  the Pallas interpreter on any other backend; nothing here chooses.
* ``islands``/``exchange_interval``: shard the swarm over devices
  (``repro.core.distributed``) — ``variant="async"`` uses the barrier-free
  island ring exchange, the synchronous variants the ``_pmax_best``
  collective.

Results are reported in the problem's OWN sense: for a ``sense="min"``
problem ``Result.best_fit`` is the minimized objective value (the engine
maximizes internally; see ``repro.core.problem``).

Constrained problems (``Problem(constraints=ConstraintSet(...))`` — see
``repro.core.constraints``) report ``Result.feasible``/``violation``, and
``repro.best`` ranks results by the Deb feasibility rule. The adaptive
penalty ramp is applied here, by segmenting the run into static-weight
segments (each a plain solve on any backend) and re-weighting the carried
fitness at boundaries. ``Method(record_history=True)`` additionally
records the gbest-per-sync-point trajectory (``Result.history``,
``Result.first_feasible_iter``) on every single-device backend — the jnp
engines scan it in-program; the kernel backend chunks the launch at sync
points and reads the gbest back at each boundary.
``Method(telemetry=True)`` threads the in-kernel contention counters
(queue updates / gbest publications / per-block improvement events —
``repro.telemetry``) through the fused Pallas kernels onto
``Result.telemetry``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.multi_swarm import (SwarmBatch, host_rows, init_batch,
                                    run_many, run_many_with_history)
from repro.core.problem import Problem, resolve_problem
from repro.core.pso import (ASYNC_SYNC_EVERY, PSOConfig, SwarmState,
                            VARIANTS, init_swarm, run, run_with_history)
from repro.core.update_rules import (TOPOLOGIES, resolve_rule, rule_names)
from repro.telemetry import SPAN_NAMES, KernelCounters, span

_KERNEL_VARIANTS = ("queue_lock", "async")


def _default_backend() -> str:
    import jax
    return "tpu" if jax.default_backend() == "tpu" else "cpu-like"


@dataclasses.dataclass(frozen=True)
class Method:
    """How to run a solve: aggregation variant + execution backend.

    ``backend="auto"`` applies the fixed rule: the kernel backend on an
    actual TPU for the two fused variants (``queue_lock``/``async``), jnp
    everywhere else — EXCEPT when ``telemetry=True``, which always
    resolves to the kernel (the contention counters are collected inside
    the fused Pallas kernels; on non-TPU hosts the kernel runs in
    interpret mode). Problems with a projection-mode constraint set always
    resolve to jnp (see ``resolve_backend``). ``record_history=True`` works
    on either backend: the
    jnp engines scan the trajectory in-program, the kernel backend chunks
    its launch at sync points with a gbest readback per boundary.

    ``schedule="auto"`` goes further: instead of the fixed rule, the
    roofline autotuner (``repro.core.autotune``) picks the whole
    ``(variant, backend, block_n, sync_every)`` schedule per solve shape —
    cost-model ranking with a measured micro-run fallback, cached per
    shape. Under ``schedule="auto"`` the ``variant`` field is a
    preference, not a pin (the tuner may select a different variant); pin
    ``backend="jnp"``/``"kernel"`` to restrict the tuner's backend scope.
    The default ``schedule="fixed"`` keeps every knob exactly as given.

    ``islands > 0`` shards the swarm over that many devices
    (``repro.core.distributed``): particles split into equal islands, each
    island iterates locally and the global best is exchanged every
    ``exchange_interval`` iterations — via the barrier collective for the
    synchronous variants, via the asynchronous neighbor ring for
    ``variant="async"`` (staleness bound: ``sync_every`` iterations within
    an island plus ``islands`` exchange rounds across them).
    """

    variant: str = "queue"
    backend: str = "auto"                 # auto | jnp | kernel
    sync_every: int = ASYNC_SYNC_EVERY    # async variant publication interval
    block_n: Optional[int] = None         # kernel particle-block size
    islands: int = 0                      # >0: shard over this many devices
    exchange_interval: int = 1            # iterations between island syncs
    record_history: bool = False          # Result.history: gbest per sync
    # point (any single-device backend; islands do not surface it)
    telemetry: bool = False               # Result.telemetry: in-kernel
    # contention counters (kernel backend only — repro.telemetry)
    schedule: str = "fixed"               # fixed | auto (roofline autotuner)
    rule: str = "pso"                     # per-particle update rule
    # (repro.core.update_rules: pso | sso | lowcost | custom registrations)
    topology: str = "gbest"               # async block-neighborhood pull
    # (gbest star | ring | vonneumann — repro.core.topology)

    def __post_init__(self):
        if self.schedule not in ("fixed", "auto"):
            raise ValueError(
                f"unknown schedule {self.schedule!r}; one of fixed|auto")
        if self.schedule == "auto" and self.islands:
            raise ValueError(
                "schedule='auto' tunes single-device schedules; the island "
                "runners pick their own block layout — use schedule='fixed'")
        if self.variant not in VARIANTS:
            raise ValueError(
                f"unknown variant {self.variant!r}; one of {VARIANTS}")
        if self.backend not in ("auto", "jnp", "kernel"):
            raise ValueError(
                f"unknown backend {self.backend!r}; one of auto|jnp|kernel")
        if self.backend == "kernel" and self.variant not in _KERNEL_VARIANTS:
            raise ValueError(
                f"backend='kernel' implements the kernel-eligible variants "
                f"{_KERNEL_VARIANTS}, not {self.variant!r}; use "
                f"backend='jnp'/'auto' for the other members of {VARIANTS}")
        r = resolve_rule(self.rule)       # raises listing rule_names()
        if (self.backend == "kernel" or self.telemetry) \
                and not r.kernel_eligible:
            eligible = tuple(n for n in rule_names()
                             if resolve_rule(n).kernel_eligible)
            raise ValueError(
                f"update rule {r.name!r} is not kernel-eligible; "
                f"kernel-eligible rules: {eligible} — use backend='jnp'")
        if self.telemetry and self.variant not in _KERNEL_VARIANTS:
            raise ValueError(
                f"telemetry counters are collected inside the fused Pallas "
                f"kernels, which implement {_KERNEL_VARIANTS} — "
                f"variant={self.variant!r} has no kernel to count in")
        if self.telemetry and self.backend == "jnp":
            raise ValueError(
                "telemetry counters are collected inside the fused Pallas "
                "kernels; use backend='kernel' or 'auto' (auto resolves to "
                "the kernel when telemetry is on)")
        if self.telemetry and self.islands:
            raise ValueError(
                "telemetry counters are single-device only (the island "
                "runners do not thread the counter outputs)")
        if self.topology not in TOPOLOGIES:
            raise ValueError(
                f"unknown topology {self.topology!r}; one of {TOPOLOGIES}")
        if self.topology != "gbest" and self.variant != "async":
            raise ValueError(
                f"topology={self.topology!r} generalizes the async "
                f"variant's block-local pull; variant={self.variant!r} has "
                f"no block-local bests — use variant='async' (lbest "
                f"topologies: {TOPOLOGIES[1:]})")
        if self.islands < 0 or self.exchange_interval < 1:
            raise ValueError(
                f"islands={self.islands} must be >= 0 and "
                f"exchange_interval={self.exchange_interval} >= 1")
        if self.backend == "kernel" and self.islands and \
                self.variant == "async":
            raise ValueError(
                "async islands run the jnp ring local loop; use "
                "backend='auto'/'jnp' (the Pallas async kernel has no "
                "multi-device ring yet)")
        if self.record_history and self.islands:
            raise ValueError(
                "record_history is single-device only (the island runners "
                "do not surface per-iteration gbest); drop islands= or "
                "record the trajectory from a single-device solve")

    def _pinned_backend(self, problem: Problem) -> Optional[str]:
        """The backend fixed by the problem or by this Method's knobs, or
        None where ``backend="auto"`` leaves the choice open."""
        from repro.kernels.pso_step import tpu_lowerable
        if not tpu_lowerable(problem):
            # Projection-mode constraint sets sort inside the advance, and
            # Mosaic has no sort lowering: these problems run on jnp.
            if self.backend == "kernel" or self.telemetry:
                raise ValueError(
                    f"problem {problem.name!r} has a projection-mode "
                    f"constraint set, which the Pallas kernels cannot "
                    f"compile on a TPU (no sort lowering); use "
                    f"backend='jnp' or 'auto' without telemetry")
            return "jnp"
        if self.backend != "auto":
            return self.backend
        if self.telemetry:
            # the contention counters only exist inside the fused kernels
            return "kernel"
        return None

    def resolve_backend(self, problem: Problem) -> str:
        pinned = self._pinned_backend(problem)
        if pinned is not None:
            return pinned
        if self.variant in _KERNEL_VARIANTS and _default_backend() == "tpu":
            return "kernel"
        return "jnp"

    def resolve_schedule(self, problem, d: int, n: int, iters: int, *,
                         dtype: str = "float32", batch: int = 1,
                         hetero_table: int = 0, measure: bool = True):
        """The grown form of ``resolve_backend``: a full execution
        schedule for one solve shape. ``schedule="fixed"`` returns this
        Method's own knobs (backend resolved by the fixed rule);
        ``schedule="auto"`` asks the roofline autotuner — cost-model
        ranking, measured micro-run fallback (``measure=False`` stops at
        the model), on-disk cache per shape."""
        from repro.core.autotune import Schedule, resolve_schedule
        prob = resolve_problem(problem)
        if self.schedule != "auto":
            return Schedule(variant=self.variant,
                            backend=self.resolve_backend(prob),
                            block_n=self.block_n,
                            sync_every=self.sync_every, source="fixed")
        pinned = self._pinned_backend(prob)
        kernel_ok = None if pinned is None else pinned == "kernel"
        return resolve_schedule(
            problem, d, n, iters, dtype=dtype, batch=batch,
            hetero_table=hetero_table, record_history=self.record_history,
            measure=measure, kernel_ok=kernel_ok, rule=self.rule)


@dataclasses.dataclass(frozen=True, eq=False)
class History:
    """Convergence history: the gbest trajectory sampled at sync points
    (every iteration for the synchronous jnp variants, every publication
    boundary for ``async``). ``violation`` is the recorded gbest's
    aggregate constraint violation — None for unconstrained problems."""

    iteration: np.ndarray              # [K] absolute iteration numbers
    gbest_fit: np.ndarray              # [K] canonical (maximized) fitness
    violation: Optional[np.ndarray]    # [K] or None (unconstrained)

    def __len__(self) -> int:
        return len(self.iteration)


@dataclasses.dataclass(frozen=True, eq=False)
class Result:
    """A finished solve. ``best_fit``/``best_pos`` are in the problem's own
    sense; ``state`` is the raw (canonical-max) SwarmState for resuming.
    A ``solve_many`` row's ``state`` holds host numpy arrays (the batch is
    fetched once and split on the host), a ``solve``'s device arrays; the
    engines and ``stack_states`` take either, with the same results.

    Constrained problems additionally report ``feasible``/``violation``
    (the Deb-rule inputs — see ``repro.core.constraints``), and
    ``history``/``first_feasible_iter`` when the solve ran with
    ``Method(record_history=True)``. ``telemetry`` carries the in-kernel
    contention counters (``repro.telemetry.KernelCounters``) when the
    solve ran with ``Method(telemetry=True)``."""

    problem: Problem
    config: PSOConfig
    method: Method
    iters: int
    state: SwarmState
    history: Optional[History] = None
    telemetry: Optional[KernelCounters] = None

    @property
    def best_fit(self) -> float:
        with span(SPAN_NAMES.readback):
            return float(self.problem.user_value(self.state.gbest_fit))

    @property
    def best_pos(self) -> np.ndarray:
        with span(SPAN_NAMES.readback):
            return np.asarray(self.state.gbest_pos)

    @property
    def gbest_fit(self) -> float:
        """Canonical (maximized) fitness, as the engine tracks it."""
        with span(SPAN_NAMES.readback):
            return float(self.state.gbest_fit)

    @property
    def violation(self) -> float:
        """Aggregate constraint violation at ``best_pos`` (0.0 when
        unconstrained or exactly feasible)."""
        return self.problem.violation_at(self.state.gbest_pos)

    @property
    def feasible(self) -> bool:
        """True iff ``best_pos`` satisfies every constraint (trivially True
        for unconstrained problems)."""
        return self.violation <= 0.0

    @property
    def first_feasible_iter(self) -> Optional[int]:
        """The first recorded iteration whose gbest was feasible, or None
        (never feasible, or no history was recorded). Unconstrained
        problems report 0 — feasible from the start."""
        if not self.problem.constrained:
            return 0
        if self.history is None or self.history.violation is None:
            return None
        feas = np.flatnonzero(self.history.violation <= 0.0)
        return int(self.history.iteration[feas[0]]) if feas.size else None


def _effective_method(m: Method, problem, cfg: PSOConfig, iters: int,
                      batch: int = 1, hetero_table: int = 0) -> Method:
    """Collapse ``schedule="auto"`` into a concrete fixed Method via the
    autotuner (one resolution per solve, covering every ramp segment)."""
    if m.schedule != "auto":
        return m
    s = m.resolve_schedule(problem, cfg.dim, cfg.particle_cnt, iters,
                           dtype=cfg.dtype, batch=batch,
                           hetero_table=hetero_table)
    # lbest topologies only exist on the async variant's block-local
    # machinery — the tuner may not migrate such a request off async
    variant = s.variant if m.topology == "gbest" else m.variant
    return dataclasses.replace(m, variant=variant, backend=s.backend,
                               block_n=s.block_n, sync_every=s.sync_every,
                               schedule="fixed")


def _jnp_async_blocks(m: Method, n: int) -> Optional[int]:
    """The jnp engines take a block COUNT where the kernels take a block
    size; translate a tuned ``block_n`` for the async fallback."""
    if m.variant != "async" or m.block_n is None:
        return None
    return max(1, n // m.block_n)


def _make_method(method: Optional[Method], variant, backend, sync_every,
                 block_n, record_history=None,
                 schedule=None, rule=None, topology=None,
                 telemetry=None) -> Method:
    explicit = dict(variant=variant, backend=backend, sync_every=sync_every,
                    block_n=block_n,
                    record_history=record_history, schedule=schedule,
                    rule=rule, topology=topology, telemetry=telemetry)
    given = {k: v for k, v in explicit.items() if v is not None}
    if method is not None:
        if given:
            raise ValueError(
                f"pass either method= or the loose kwargs {sorted(given)}, "
                f"not both")
        return method
    return Method(**{**dict(variant="queue"), **given})


def _make_config(problem: Problem, dim, particles, w, c1, c2, dtype,
                 min_pos, max_pos, max_v, m: Optional[Method] = None
                 ) -> PSOConfig:
    if dim is None:
        dim = problem.ndim or 1
    kw = dict(dim=dim, particle_cnt=particles, fitness=problem, dtype=dtype,
              min_pos=min_pos, max_pos=max_pos, max_v=max_v)
    if m is not None:
        kw.update(update_rule=m.rule, topology=m.topology)
    for k, v in (("w", w), ("c1", c1), ("c2", c2)):
        if v is not None:
            kw[k] = v
    return PSOConfig(**kw).resolved()


def solve(problem: Union[str, Problem], *,
          dim: Optional[int] = None, particles: int = 1024,
          iters: int = 1000, seed: int = 0,
          method: Optional[Method] = None,
          variant: Optional[str] = None, backend: Optional[str] = None,
          sync_every: Optional[int] = None, block_n: Optional[int] = None,
          w: Optional[float] = None, c1: Optional[float] = None,
          c2: Optional[float] = None, dtype: str = "float32",
          min_pos=None, max_pos=None, max_v=None,
          record_history: Optional[bool] = None,
          schedule: Optional[str] = None,
          rule: Optional[str] = None,
          topology: Optional[str] = None,
          telemetry: Optional[bool] = None) -> Result:
    """Solve ``problem`` with ``particles`` particles for ``iters``
    iterations. Either pass a full ``method=Method(...)`` or the loose
    ``variant=``/``backend=``/... kwargs (not both). ``dim`` defaults to
    the problem's per-dimension bound length (else 1).
    ``schedule="auto"`` lets the roofline autotuner pick the execution
    schedule for this shape (see ``Method``).
    """
    with span(SPAN_NAMES.solve):
        with span(SPAN_NAMES.prepare):
            prob = resolve_problem(problem)
            m = _make_method(method, variant, backend, sync_every, block_n,
                             record_history, schedule, rule, topology,
                             telemetry)
            cfg = _make_config(prob, dim, particles, w, c1, c2, dtype,
                               min_pos, max_pos, max_v, m)
            m = _effective_method(m, prob, cfg, iters)
        if m.islands:
            state = _run_islands(prob, cfg, seed, iters, m)
            hist, tel = None, None
        else:
            with span(SPAN_NAMES.init):
                state = init_swarm(cfg, seed)
            with span(SPAN_NAMES.launch):
                state, hist, tel = _run_segmented(prob, cfg, state, iters, m)
        return Result(problem=prob, config=cfg, method=m, iters=iters,
                      state=state, history=hist, telemetry=tel)


def _run_islands(prob: Problem, cfg: PSOConfig, seed: int, iters: int,
                 m: Method) -> SwarmState:
    """The sharded path: init once, then one ``make_distributed_run`` per
    penalty-ramp segment over an ``m.islands``-device mesh (a single
    full-length runner when no ramp is configured). The mesh and sharded
    init are built once; only the per-segment runner re-jits, keyed on
    ``(weight, seg_iters)`` like every other backend's ramp."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh
    from repro.core.distributed import (init_sharded_swarm,
                                        make_distributed_run)
    devs = jax.devices()
    if m.islands > len(devs):
        raise ValueError(
            f"islands={m.islands} exceeds the {len(devs)} available "
            f"device(s)")
    mesh = Mesh(_np.asarray(devs[:m.islands]), ("data",))
    local_step = None
    if m.variant != "async" and m.resolve_backend(prob) == "kernel":
        from repro.kernels.ops import make_fused_local_step
        local_step = make_fused_local_step(block_n=m.block_n)
    with span(SPAN_NAMES.init):
        state = init_sharded_swarm(cfg, seed, mesh)

    def run_seg(cfg_k: PSOConfig, s: SwarmState, seg_iters: int):
        runner = make_distributed_run(
            cfg_k, mesh, iters=seg_iters, variant=m.variant,
            exchange_interval=m.exchange_interval, local_step_fn=local_step,
            sync_every=m.sync_every)
        return runner(s), None

    def reweight(cfg_k: PSOConfig, s: SwarmState) -> SwarmState:
        # The async ring takes lbest-free inputs (its in_specs carry no
        # locals; each segment re-seeds its block caches), so drop them
        # before re-weighting — sync variants never carry them here.
        return _reweight_state(cfg_k, s._replace(lbest_pos=None,
                                                 lbest_fit=None))

    with span(SPAN_NAMES.launch):
        state, _ = _ramp_loop(prob, cfg, state, iters, run_seg, reweight)
    return state


def _ramp_segments(iters: int, cset):
    """(segment_iters, penalty_weight) pairs for the adaptive penalty ramp
    (``repro.core.constraints``): segment k of ``ramp_every`` iterations
    runs at ``weight * ramp**k``. A single ``(iters, None)`` segment (the
    unchanged problem) when no ramp is configured."""
    if (cset is None or cset.mode != "penalty" or cset.ramp_every <= 0
            or cset.ramp == 1.0):
        return [(iters, None)]
    segs, done, k = [], 0, 0
    while done < iters:
        n = min(cset.ramp_every, iters - done)
        segs.append((n, cset.weight * (cset.ramp ** k)))
        done += n
        k += 1
    return segs


def _reweight_state(cfg: PSOConfig, state: SwarmState) -> SwarmState:
    """Re-evaluate the carried fitness fields under a new penalty weight
    (ramp segment boundary): current/pbest/block-local fitness from their
    positions, gbest re-selected from the re-weighted pbests — so the
    selection invariants (gbest == max(pbest)) hold at every weight."""
    import jax.numpy as jnp
    fn = cfg.fitness_fn
    fit = fn(state.pos)
    pbf = fn(state.pbest_pos)
    b = jnp.argmax(pbf)
    state = state._replace(fit=fit, pbest_fit=pbf,
                           gbest_pos=state.pbest_pos[b], gbest_fit=pbf[b])
    if state.lbest_fit is not None:
        state = state._replace(lbest_fit=fn(state.lbest_pos))
    return state


def _ramp_loop(prob: Problem, cfg: PSOConfig, state, iters: int,
               run_seg, reweight):
    """The shared penalty-ramp scheduler: each segment is a plain
    static-weight run (so the ramp composes with every backend), with the
    carried fitness re-weighted at segment boundaries. ``run_seg(cfg,
    state, seg_iters) -> (state, history|None)``; ``reweight(cfg, state)
    -> state``. Used by both ``solve`` (SwarmState) and ``solve_many``
    (SwarmBatch). Returns (state, [history, ...])."""
    hists = []
    first = True
    for seg_iters, weight in _ramp_segments(iters, prob.constraints):
        if weight is None:
            cfg_k = cfg
        else:
            cfg_k = dataclasses.replace(
                cfg, fitness=prob.with_penalty_weight(weight))
            if not first:
                state = reweight(cfg_k, state)
        state, h = run_seg(cfg_k, state, seg_iters)
        if h is not None:
            hists.append(h)
        first = False
    return state, hists


def _sum_counters(cnts):
    """Fold per-segment counter records into one (None when empty)."""
    total = None
    for c in cnts:
        total = c if total is None else total + c
    return total


def _run_segmented(prob: Problem, cfg: PSOConfig, state: SwarmState,
                   iters: int, m: Method):
    cnts = []

    def seg(c, s, k):
        s, h, cnt = _run_state(c, s, k, m)
        if cnt is not None:
            cnts.append(cnt)
        return s, h

    state, hists = _ramp_loop(prob, cfg, state, iters, seg, _reweight_state)
    tel = _sum_counters(cnts)
    if not hists:
        return state, None, tel
    return state, History(
        iteration=np.concatenate([h[0] for h in hists]),
        gbest_fit=np.concatenate([h[1] for h in hists]),
        violation=(None if hists[0][2] is None
                   else np.concatenate([h[2] for h in hists]))), tel


def _run_state(cfg: PSOConfig, state: SwarmState, iters: int, m: Method):
    """One static-weight segment -> (state, history-or-None,
    KernelCounters-or-None)."""
    if m.resolve_backend(cfg.problem) == "kernel":
        return _run_state_kernel(cfg, state, iters, m)
    if m.record_history:
        state, (its, fits, viols) = run_with_history(
            cfg, state, iters, m.variant, sync_every=m.sync_every)
        return state, (np.asarray(its, dtype=np.int64), np.asarray(fits),
                       None if viols is None else np.asarray(viols)), None
    return run(cfg, state, iters, m.variant, sync_every=m.sync_every,
               n_blocks=_jnp_async_blocks(m, state.pos.shape[0])), None, None


def _run_state_kernel(cfg: PSOConfig, state: SwarmState, iters: int,
                      m: Method):
    """The kernel-backend segment runner, optionally threading the
    in-kernel telemetry counters and/or recording the gbest trajectory.

    History chunks the launch at sync points with a gbest readback per
    boundary: every grid step for the fused sync kernel (its grid is
    iteration-major, so chunking the host loop is bit-exact) and every
    ``sync_every`` boundary for async (the chunk seams coincide with the
    kernel's own block-resident chunks; exact for a single particle block,
    a more-synchronous interleaving for multi-block layouts — see
    ``kernels.ops.run_queue_lock_fused_async``). Counters are additive, so
    per-chunk counts sum to the uninterrupted run's."""
    from repro.kernels.ops import (run_queue_lock_fused,
                                   run_queue_lock_fused_async)

    def launch(s, k):
        if m.variant == "async":
            return run_queue_lock_fused_async(
                cfg, s, k, sync_every=m.sync_every, block_n=m.block_n,
                telemetry=m.telemetry)
        return run_queue_lock_fused(cfg, s, k, block_n=m.block_n,
                                    telemetry=m.telemetry)

    if not m.record_history:
        if m.telemetry:
            state, cnt = launch(state, iters)
            return state, None, KernelCounters.from_array(cnt)
        return launch(state, iters), None, None
    vf = cfg.problem.violation_fn
    stride = max(1, m.sync_every) if m.variant == "async" else 1
    its, fits, viols, cnts = [], [], [], []
    done = 0
    while done < iters:
        k = min(stride, iters - done)
        if m.telemetry:
            state, cnt = launch(state, k)
            cnts.append(KernelCounters.from_array(cnt))
        else:
            state = launch(state, k)
        done += k
        its.append(int(state.iteration))
        fits.append(np.asarray(state.gbest_fit))
        viols.append(np.asarray(vf(state.gbest_pos))
                     if vf is not None else 0.0)
    hist = (np.asarray(its, dtype=np.int64), np.asarray(fits),
            np.asarray(viols) if cfg.problem.constrained else None)
    return state, hist, _sum_counters(cnts)


def solve_many(problem: Union[str, Problem, None] = None,
               seeds: Sequence[int] = (), *,
               problems: Optional[Sequence[Union[str, Problem]]] = None,
               dim: Optional[int] = None, particles: int = 1024,
               iters: int = 1000,
               method: Optional[Method] = None,
               variant: Optional[str] = None, backend: Optional[str] = None,
               sync_every: Optional[int] = None,
               block_n: Optional[int] = None,
               coeffs: Optional[Tuple] = None,
               w: Optional[float] = None, c1: Optional[float] = None,
               c2: Optional[float] = None, dtype: str = "float32",
               min_pos=None, max_pos=None, max_v=None,
               record_history: Optional[bool] = None,
               schedule: Optional[str] = None,
               rule: Optional[str] = None,
               topology: Optional[str] = None,
               telemetry: Optional[bool] = None) -> List[Result]:
    """Batched facade: one independent solve per entry of ``seeds``, all in
    ONE device program (vmapped jnp engine, or the batched fused/async
    Pallas kernels for ``backend="kernel"``). Row ``s`` is bit-identical to
    ``solve(problem, seed=seeds[s], ...)`` with the same method when
    ``coeffs`` is None. Returns one ``Result`` per seed.

    ``problems=`` (instead of ``problem``) makes the batch heterogeneous:
    row ``s`` solves ``problems[s]`` — each a registered built-in — with
    its own objective and box bounds dispatched by ``lax.switch`` inside
    the one program (jnp engine and both batched kernels). Bounds come
    from each row's problem, so the ``min_pos``/``max_pos``/``max_v``
    overrides are rejected; penalty-ramp schedules don't apply (built-in
    table entries are unconstrained or static-penalty). The validated
    exactness surface is ``gbest_pos``/``gbest_fit`` (see
    ``repro.core.pso``'s heterogeneous-dispatch notes for the full
    envelope).

    The finished batch reaches the host in one transfer and is split
    there: every row's ``Result.state`` holds numpy arrays, bit-identical
    to ``repro.core.batch_row`` of the device batch, so ``best_fit``,
    ``best_pos`` and ``gbest_fit`` read no device. Resuming from such rows
    (``stack_states`` into ``run_many``) gives the device rows' results.
    """
    with span(SPAN_NAMES.solve_many):
        with span(SPAN_NAMES.prepare):
            m = _make_method(method, variant, backend, sync_every, block_n,
                             record_history, schedule, rule, topology,
                             telemetry)
            if m.islands:
                raise ValueError(
                    "islands shard ONE swarm over devices; use solve()"
                    " — solve_many batches independent swarms instead")
            if (problem is None) == (problems is None):
                raise ValueError(
                    "pass exactly one of problem= (homogeneous batch) or "
                    "problems= (one problem per seed)")
            if problems is None:
                prob = resolve_problem(problem)
                cfg = _make_config(prob, dim, particles, w, c1, c2, dtype,
                                   min_pos, max_pos, max_v, m)
                m = _effective_method(m, prob, cfg, iters, batch=len(seeds))
        if problems is not None:
            return _solve_many_hetero(problems, seeds, m, dim, particles,
                                      iters, coeffs, w, c1, c2, dtype,
                                      min_pos, max_pos, max_v)
        with span(SPAN_NAMES.init):
            batch = init_batch(cfg, np.asarray(seeds, dtype=np.int64))
        cnts = []

        def seg(c, b, k):
            b, h, cnt = _run_batch(c, b, k, m, coeffs)
            if cnt is not None:
                cnts.append(cnt)
            return b, h

        with span(SPAN_NAMES.launch):
            batch, hists = _ramp_loop(prob, cfg, batch, iters, seg,
                                      _reweight_batch)
        with span(SPAN_NAMES.rows):
            states = host_rows(batch)
            rows_hist = _row_histories(hists, len(states))
            rows_tel = _row_counters(cnts, len(states))
            return [Result(problem=prob, config=cfg, method=m, iters=iters,
                           state=st, history=rows_hist[s],
                           telemetry=rows_tel[s])
                    for s, st in enumerate(states)]


def _row_histories(hists, s_cnt: int) -> List[Optional[History]]:
    """Per-row History objects from per-segment ``(its, [K,S] fits,
    [K,S] viols|None)`` records (all-None when no history was recorded)."""
    if not hists:
        return [None] * s_cnt
    its = np.concatenate([np.asarray(h[0], dtype=np.int64) for h in hists])
    fits = np.concatenate([np.asarray(h[1]) for h in hists])
    viols = (None if hists[0][2] is None
             else np.concatenate([np.asarray(h[2]) for h in hists]))
    return [History(iteration=its, gbest_fit=fits[:, s],
                    violation=None if viols is None else viols[:, s])
            for s in range(s_cnt)]


def _row_counters(cnts, s_cnt: int) -> List[Optional[KernelCounters]]:
    """Per-row KernelCounters from per-segment ``[S, 3]`` count arrays."""
    total = _sum_counters([np.asarray(c) for c in cnts])
    if total is None:
        return [None] * s_cnt
    return [KernelCounters.from_array(total[s]) for s in range(s_cnt)]


def _solve_many_hetero(problems, seeds, m: Method, dim, particles, iters,
                       coeffs, w, c1, c2, dtype,
                       min_pos, max_pos, max_v) -> List[Result]:
    """``solve_many(problems=[...])``: per-row problem dispatch, every
    backend from one ``problem_rows`` descriptor set and one
    ``init_batch``."""
    from repro.core.multi_swarm import problem_rows
    from repro.core.pso import hetero_member_config
    with span(SPAN_NAMES.prepare):
        if min_pos is not None or max_pos is not None or max_v is not None:
            raise ValueError("heterogeneous batches take bounds from each "
                             "row's problem; drop min_pos/max_pos/max_v")
        probs = [resolve_problem(p) for p in problems]
        if len(probs) != len(seeds):
            raise ValueError(
                f"{len(probs)} problems for {len(seeds)} seeds")
        # cfg.fitness is a canonical placeholder: the rows carry the real
        # objectives, and a fixed value lets every mix share one compiled
        # program. Bounds stay unset — the core validates that.
        kw = dict(dim=dim if dim is not None else 1, particle_cnt=particles,
                  fitness="cubic", dtype=dtype,
                  update_rule=m.rule, topology=m.topology)
        for key, v in (("w", w), ("c1", c1), ("c2", c2)):
            if v is not None:
                kw[key] = v
        cfg = PSOConfig(**kw)
        m = _effective_method(
            m, probs[0], cfg, iters, batch=len(seeds),
            hetero_table=len({p.cache_key() for p in probs}))
        kernel = m.resolve_backend(probs[0]) == "kernel"
        if kernel and coeffs is not None:
            raise ValueError("per-swarm coeffs are a jnp-backend feature")
        rows, table = problem_rows(probs, cfg.dim, cfg.dtype)
        rcfg = cfg.resolved()
    with span(SPAN_NAMES.init):
        batch = init_batch(rcfg, np.asarray(seeds, dtype=np.int64),
                           rows=rows, table=table)
    hists, cnts = [], []
    with span(SPAN_NAMES.launch):
        if kernel:
            batch, hist, cnt = _run_batch_kernel(rcfg, batch, iters, m,
                                                 rows=rows, table=table)
            if hist is not None:
                hists.append(hist)
            if cnt is not None:
                cnts.append(cnt)
        elif m.record_history:
            batch, (its, fits, viols) = run_many_with_history(
                rcfg, batch, iters, m.variant, coeffs,
                sync_every=m.sync_every, rows=rows, table=table,
                n_blocks=_jnp_async_blocks(m, cfg.particle_cnt))
            hists.append((np.asarray(its, dtype=np.int64),
                          np.asarray(fits),
                          None if viols is None else np.asarray(viols)))
        else:
            batch = run_many(rcfg, batch, iters, m.variant, coeffs,
                             sync_every=m.sync_every, rows=rows, table=table,
                             n_blocks=_jnp_async_blocks(m, cfg.particle_cnt))
    with span(SPAN_NAMES.rows):
        states = host_rows(batch)
        rows_hist = _row_histories(hists, len(states))
        rows_tel = _row_counters(cnts, len(states))
        return [Result(problem=probs[s],
                       config=hetero_member_config(cfg, probs[s]),
                       method=m, iters=iters, state=st,
                       history=rows_hist[s], telemetry=rows_tel[s])
                for s, st in enumerate(states)]


def _reweight_batch(cfg: PSOConfig, batch: SwarmBatch) -> SwarmBatch:
    """Batched ``_reweight_state`` (ramp segment boundary)."""
    import jax.numpy as jnp
    fn = cfg.fitness_fn
    fit = fn(batch.pos)                               # [S, N]
    pbf = fn(batch.pbest_pos)
    b = jnp.argmax(pbf, axis=1)                       # [S]
    gp = jnp.take_along_axis(batch.pbest_pos, b[:, None, None], axis=1)[:, 0]
    gf = jnp.take_along_axis(pbf, b[:, None], axis=1)[:, 0]
    batch = batch._replace(fit=fit, pbest_fit=pbf, gbest_pos=gp,
                           gbest_fit=gf)
    if batch.lbest_fit is not None:
        batch = batch._replace(lbest_fit=fn(batch.lbest_pos))
    return batch


def _run_batch(cfg: PSOConfig, batch: SwarmBatch, iters: int, m: Method,
               coeffs):
    """One static-weight batched segment -> (batch, history-or-None,
    [S, 3] counter rows or None)."""
    if m.resolve_backend(cfg.problem) == "kernel":
        if coeffs is not None:
            raise ValueError("per-swarm coeffs are a jnp-backend feature")
        return _run_batch_kernel(cfg, batch, iters, m)
    if m.record_history:
        batch, (its, fits, viols) = run_many_with_history(
            cfg, batch, iters, m.variant, coeffs, sync_every=m.sync_every,
            n_blocks=_jnp_async_blocks(m, batch.pos.shape[1]))
        return batch, (np.asarray(its, dtype=np.int64), np.asarray(fits),
                       None if viols is None else np.asarray(viols)), None
    return run_many(cfg, batch, iters, m.variant, coeffs,
                    sync_every=m.sync_every,
                    n_blocks=_jnp_async_blocks(m, batch.pos.shape[1])
                    ), None, None


def _run_batch_kernel(cfg: PSOConfig, batch: SwarmBatch, iters: int,
                      m: Method, rows=None, table=None):
    """Batched-kernel segment runner: the batched fused/async Pallas
    kernels, with the same optional telemetry threading and chunked
    history readbacks as ``_run_state_kernel`` (one ``[K, S]`` trajectory
    sample per sync point). ``rows``/``table`` make the batch
    heterogeneous (per-row ``lax.switch`` objective dispatch)."""
    from repro.kernels.ops import (run_queue_lock_fused_batch,
                                   run_queue_lock_fused_async_batch)
    fids = None if rows is None else rows.fid

    def launch(b, k):
        if m.variant == "async":
            return run_queue_lock_fused_async_batch(
                cfg, b, k, sync_every=m.sync_every, block_n=m.block_n,
                fids=fids, table=table, telemetry=m.telemetry)
        return run_queue_lock_fused_batch(
            cfg, b, k, block_n=m.block_n, fids=fids, table=table,
            telemetry=m.telemetry)

    if not m.record_history:
        if m.telemetry:
            batch, cnt = launch(batch, iters)
            return batch, None, cnt
        return launch(batch, iters), None, None
    vf = None if rows is not None else cfg.problem.violation_fn
    stride = max(1, m.sync_every) if m.variant == "async" else 1
    its, fits, viols, cnts = [], [], [], []
    done = 0
    while done < iters:
        k = min(stride, iters - done)
        if m.telemetry:
            batch, cnt = launch(batch, k)
            cnts.append(np.asarray(cnt))
        else:
            batch = launch(batch, k)
        done += k
        its.append(int(batch.iteration[0]))
        fits.append(np.asarray(batch.gbest_fit))
        if vf is not None:
            import jax
            viols.append(np.asarray(jax.vmap(vf)(batch.gbest_pos)))
    constrained = rows is None and cfg.problem.constrained
    hist = (np.asarray(its, dtype=np.int64), np.asarray(fits),
            np.asarray(viols) if constrained and viols else None)
    return batch, hist, _sum_counters(cnts)


def solve_stream(requests: Sequence, *, lane_width: int = 8,
                 coalesce_registry: bool = True,
                 compile_cache=None, autotune: bool = False,
                 metrics=None, record_history: bool = False,
                 trace=None, trace_path: Optional[str] = None) -> List:
    """Streaming facade: run a stream of independent solve requests
    through the continuous-batching scheduler
    (``repro.serving.ContinuousScheduler``).

    ``requests`` are ``repro.launch.serve.SolveRequest``s (or dicts of
    their fields). Async-variant requests ride persistent batched lanes
    with chunk-boundary admission — every result bit-identical to the
    standalone ``solve`` of its request — while synchronous-variant and
    sub-chunk requests fall back to standalone solves. ``compile_cache``
    (a ``repro.serving.CompileCache``, or a directory path for one) makes
    the lane programs persist across process restarts; ``metrics`` (a
    ``repro.serving.ServingMetrics``) collects latency spans and
    batch-fill counters. Returns one ``SolveResult`` per request, in
    request order.

    Telemetry: ``trace`` (a ``repro.telemetry.TraceWriter``) records the
    serving timeline — one Perfetto row per lane, a span per dispatched
    chunk — and ``trace_path`` writes it as ``trace.json`` on completion
    (allocating a writer if ``trace`` is None). ``record_history=True``
    accumulates each request's gbest-vs-iteration series at its lane's
    chunk boundaries onto ``SolveResult.history``.
    """
    from repro.launch.serve import SolveRequest
    from repro.serving import CompileCache, ContinuousScheduler
    if isinstance(compile_cache, str):
        compile_cache = CompileCache(path=compile_cache)
    if trace is None and trace_path is not None:
        from repro.telemetry import TraceWriter
        trace = TraceWriter()
    reqs = [r if isinstance(r, SolveRequest) else SolveRequest(**r)
            for r in requests]
    sched = ContinuousScheduler(
        lane_width=lane_width, coalesce_registry=coalesce_registry,
        compile_cache=compile_cache, autotune=autotune, metrics=metrics,
        trace=trace, record_history=record_history)
    out = sched.run(reqs)
    if trace is not None and trace_path is not None:
        trace.write(trace_path)
    return out


def best(results: Sequence[Result]) -> Result:
    """The best Result of a batch, by the Deb feasibility rule: a feasible
    result beats any infeasible one; feasible results compare on fitness
    (the problem's own sense); infeasible results compare on violation
    (smaller wins). For unconstrained problems every result is feasible at
    violation zero, so this is exactly the old max-fitness rule."""
    results = list(results)
    feas = [r for r in results if r.feasible]
    if feas:
        return max(feas, key=lambda r: r.gbest_fit)
    return min(results, key=lambda r: r.violation)
