"""Parallel PSO (PPSO) in JAX: state, config, and the three aggregation
variants from the paper, expressed TPU-natively.

Variants (paper §3.2, §4):
  * ``step_reduction``  — state of the art the paper compares against: an
    unconditional full argmax reduction over all particles every iteration.
  * ``step_queue``      — the paper's queue algorithm, adapted: the swarm-wide
    reduction is *predicated* on ``any(fit > gbest_fit)``. Because improvement
    is rare (<0.1 % of iterations at steady state, §4.1), the expensive
    argmax + D-dim position gather is skipped almost always; only a cheap
    vectorized compare + ``any`` runs unconditionally.
  * ``step_queue_lock`` — the fused variant. At the library level the fusion
    (removing the second kernel) is realized by the Pallas kernel in
    ``repro.kernels``; the jnp fallback here additionally fuses the pbest and
    gbest conditionals into a single predicated block so that XLA emits one
    conditional region instead of two.
  * ``step_async``/``run_async`` — the paper's *enhanced* queue-lock:
    particle blocks run asynchronously against block-local bests and the
    shared gbest is published/pulled only every ``sync_every`` iterations
    (relaxed consistency: a block's view is at most ``sync_every``
    iterations stale). The Pallas counterpart is
    ``repro.kernels.ops.run_queue_lock_fused_async``.

Semantics note: all parallel variants are *synchronous* PPSO — every particle
sees the gbest of the previous iteration (the paper's Fig. 1 workflow). The
sequential SPSO (Alg. 1), where gbest updates mid-iteration, lives in
``repro.core.serial`` and is used as the CPU baseline and semantic oracle.

Scaling note: all three step functions are written to vmap cleanly over a
leading swarm axis — ``repro.core.multi_swarm.solve_many`` batches many
independent solves (heterogeneous seeds, optionally per-swarm ``coeffs``
overriding (w, c1, c2)) into one device program whose rows take the
standalone path's steps (``multi_swarm``: "Agreement"). Keep step-function
``lax.cond`` branch outputs small (scalars / [D]); see ``step_queue_lock``
for why.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import rng
from .blocking import default_block_count
from .constraints import deb_improved, repair_init_positions
from .fitness import DEFAULT_BOUNDS, FITNESS_FNS  # noqa: F401 (legacy API)
from .problem import Bound, Problem, broadcast_bounds, resolve_problem
from .update_rules import TOPOLOGIES, resolve_rule, rule_names

Array = jnp.ndarray


def _bound_operand(v, dt):
    """Bound -> jnp operand: scalars stay Python floats (weak-typed, the
    seed arithmetic, bit-for-bit); per-dimension tuples become [D] arrays
    that broadcast against [N, D] / [S, N, D] state."""
    return v if not isinstance(v, tuple) else jnp.asarray(v, dt)


@dataclasses.dataclass(frozen=True)
class PSOConfig:
    """Static PSO problem configuration (paper Table 1).

    ``fitness`` is a registered problem name (the legacy string path, e.g.
    ``"cubic"``) or a first-class ``repro.core.problem.Problem`` carrying a
    user-defined pure-jnp objective, bounds and sense. ``min_pos``/``max_pos``
    /``max_v`` override the problem's domain; each is a scalar or a
    length-``dim`` tuple (per-dimension boxes). The config stays hashable —
    it is a jit static argument everywhere.

    ``update_rule`` names the per-particle update rule
    (``repro.core.update_rules``: ``"pso"``/``"sso"``/``"lowcost"``);
    ``topology`` names the async variant's block-neighborhood pull
    (``"gbest"`` star, ``"ring"``, ``"vonneumann"`` —
    ``repro.core.topology``). Both default to the paper's algorithm and
    are Python-gated so default configs trace the exact pre-portfolio
    jaxprs.
    """

    dim: int = 1
    particle_cnt: int = 1024
    w: float = 1.0          # inertia (paper §6.1: w = 1)
    c1: float = 2.0         # cognitive coefficient
    c2: float = 2.0         # social coefficient
    fitness: Union[str, Problem] = "cubic"
    min_pos: Optional[Bound] = None   # default: fitness-specific domain
    max_pos: Optional[Bound] = None
    max_v: Optional[Bound] = None     # default: half the position range
    dtype: str = "float32"
    update_rule: str = "pso"
    topology: str = "gbest"

    def __post_init__(self):
        # Normalize any sequence bound to a tuple so the config stays
        # hashable (lists/arrays would break jit static hashing).
        for f in ("min_pos", "max_pos", "max_v"):
            v = getattr(self, f)
            if v is not None and not isinstance(v, (int, float, tuple)):
                object.__setattr__(self, f, tuple(float(x) for x in v))
        resolve_rule(self.update_rule)   # raises with the enumeration
        if self.topology not in TOPOLOGIES:
            raise ValueError(
                f"unknown topology {self.topology!r}; one of {TOPOLOGIES}")

    @property
    def problem(self) -> Problem:
        return resolve_problem(self.fitness)

    def resolved(self) -> "PSOConfig":
        prob = self.problem
        lo, hi = prob.lo, prob.hi
        min_pos = lo if self.min_pos is None else self.min_pos
        max_pos = hi if self.max_pos is None else self.max_pos
        min_pos, max_pos = broadcast_bounds(min_pos, max_pos)
        for name, v in (("min_pos", min_pos), ("max_pos", max_pos)):
            if isinstance(v, tuple) and len(v) != self.dim:
                raise ValueError(
                    f"{name} has {len(v)} entries but dim={self.dim}")
        if self.max_v is None:
            if isinstance(min_pos, tuple):
                max_v: Bound = tuple(0.5 * (h - l)
                                     for l, h in zip(min_pos, max_pos))
            else:
                max_v = 0.5 * (max_pos - min_pos)
        else:
            max_v = self.max_v
            if isinstance(max_v, tuple) and len(max_v) != self.dim:
                raise ValueError(
                    f"max_v has {len(max_v)} entries but dim={self.dim}")
        return dataclasses.replace(self, min_pos=min_pos, max_pos=max_pos, max_v=max_v)

    @property
    def fitness_fn(self) -> Callable[[Array], Array]:
        """The objective in canonical (maximization) form. For legacy string
        configs this is the exact ``FITNESS_FNS`` function object."""
        return self.problem.max_fn

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


class SwarmState(NamedTuple):
    """Full swarm state — everything needed to checkpoint/resume/reshard.

    ``lbest_pos``/``lbest_fit`` are the async variant's block-local bests
    (one slot per particle block; the Pallas async kernel's side buffers,
    surfaced at the library level). They default to ``None`` — synchronous
    variants never materialize them — and ride the pytree when present, so
    a checkpoint taken mid-async-run carries the blocks' local knowledge
    and resume does not restart the staleness window (see ``run_async``).
    """

    pos: Array        # [N, D]
    vel: Array        # [N, D]
    fit: Array        # [N]
    pbest_pos: Array  # [N, D]
    pbest_fit: Array  # [N]
    gbest_pos: Array  # [D]
    gbest_fit: Array  # []
    iteration: Array  # [] int32 — RNG counter component
    seed: Array       # [] uint32
    lbest_pos: Optional[Array] = None  # [nb, D] async block-local bests
    lbest_fit: Optional[Array] = None  # [nb]


# RNG stream ids (keep in sync with kernels/pso_step.py).
STREAM_INIT_POS = 0
STREAM_INIT_VEL = 1
STREAM_R1 = 2
STREAM_R2 = 3


# Heterogeneous dispatch convention: ``hetero=(table, row)`` threads through
# ``_advance``, the step functions and ``_init_body``, where ``table`` is a
# static tuple of ``Problem``s (a trace-time Python constant; the jit entry
# points in ``multi_swarm`` key their cache on it) and ``row`` is a
# ``HeteroRow`` of traced per-swarm operands: the [] int32 index into the
# table plus the row's [D] bound columns. ``hetero=None`` everywhere keeps
# the exact pre-hetero jaxprs (Python-gated). The dispatch is deliberately
# as narrow as possible: the RNG draw and raw velocity/position update
# chain stay OUTSIDE the switch — byte-identical ops to the homogeneous
# trace, with the bounds as runtime [D] operands instead of inlined
# constants — and only the objective evaluation goes through
# ``lax.switch`` over ``Problem.max_fn`` branches. Under vmap the batched
# switch lowers to compute-all-branches + ``select_n``, i.e. a hetero batch
# costs ``len(table)`` objective evaluations per step (bounded: the table
# is the built-in registry).
#
# Agreement (tests/test_hetero.py): a hetero row computes the trajectory of
# the standalone ``solve`` of its problem, the same RNG counters and the
# same update. The two are different compiled programs (bounds as runtime
# columns, the objective behind a vmapped switch), and XLA:CPU chooses FMA
# contraction and reduction order per program, so they agree within the
# limits of tests/agreement.py (the benchmark's own), not bit for bit.


class HeteroRow(NamedTuple):
    """Per-swarm dispatch operands for a heterogeneous batch row.

    ``fid`` indexes the static problem table; ``lo``/``hi``/``mv`` are the
    row's [D] position/velocity bound columns, precomputed host-side by
    ``multi_swarm.problem_rows`` with the exact arithmetic
    ``PSOConfig.resolved()`` uses (float64 then weak-f32 cast), so a row's
    runtime bounds are bitwise the constants its standalone solve inlines.
    """
    fid: Array   # [] int32
    lo: Array    # [D]
    hi: Array    # [D]
    mv: Array    # [D]


def _hetero_fitness(table, fid: Array, pos: Array) -> Array:
    """Canonical (maximization, penalty-baked) fitness of row ``fid``."""
    return jax.lax.switch(fid, [p.max_fn for p in table], pos)


def hetero_member_config(cfg: PSOConfig, prob: Problem) -> PSOConfig:
    """``cfg`` re-pointed at one dispatch-table member, bounds re-derived.

    Exactly the config a standalone ``solve`` of ``prob`` at this
    dim/particle_cnt/w/c1/c2/dtype would resolve — the per-branch static
    config the kernel-path heterogeneous dispatch closes each branch over.
    """
    return dataclasses.replace(cfg, fitness=prob, min_pos=None,
                               max_pos=None, max_v=None).resolved()


def init_swarm(cfg: PSOConfig, seed: int, n: Optional[int] = None,
               index_offset: int = 0) -> SwarmState:
    """Initialize a swarm (paper Alg. 1 step 1).

    ``n``/``index_offset`` support sharded construction: a shard owning
    particles [off, off+n) builds exactly the same particles as the
    corresponding slice of a monolithic swarm (elastic resharding invariant,
    tested in tests/test_distributed.py).

    One compiled program per static key (config, ``n``; see
    ``_init_program``), the seed and ``index_offset`` its operands, so a
    new seed never retraces. Inside a trace (shard_map's ``per_shard``)
    the program inlines into the caller's.
    """
    cfg = cfg.resolved()
    return _init_program(cfg, seed_operand(seed), index_offset,
                         n=cfg.particle_cnt if n is None else n,
                         n_blocks=None)


@partial(jax.jit, static_argnames=("cfg", "n", "n_blocks"))
def _init_program(cfg: PSOConfig, seed, index_offset, n: int,
                  n_blocks: Optional[int]) -> SwarmState:
    """One swarm's init as one XLA program (``_init_body``)."""
    count_init_trace()
    return _init_body(cfg, seed, index_offset, n, n_blocks)


def _init_body(cfg: PSOConfig, seed, index_offset, n: int,
               n_blocks: Optional[int], hetero=None) -> SwarmState:
    """One swarm's init, traced into a compiled program: RNG draws, box
    scaling, objective, argmax (and, with ``n_blocks``, the async locals).
    ``hetero=(table, row)`` takes the box from the row's columns and the
    objective through the table's switch, as ``_advance`` does."""
    d = cfg.dim
    dt = cfg.jnp_dtype
    idx = (jnp.arange(n * d, dtype=jnp.uint32).reshape(n, d)
           + jnp.asarray(index_offset).astype(jnp.uint32) * jnp.uint32(d))
    u_pos = rng.uniform(seed, 0, STREAM_INIT_POS, idx, dtype=dt)
    u_vel = rng.uniform(seed, 0, STREAM_INIT_VEL, idx, dtype=dt)
    if hetero is None:
        lo = _bound_operand(cfg.min_pos, dt)
        hi = _bound_operand(cfg.max_pos, dt)
        mv = _bound_operand(cfg.max_v, dt)
    else:
        table, hr = hetero
        lo, hi, mv = hr.lo, hr.hi, hr.mv
    span = hi - lo
    pos = lo + _rounded(span * u_pos)
    vel = -mv + _rounded(2.0 * mv * u_vel)
    if hetero is not None:
        # table members are unconstrained or penalty-mode (problem_rows)
        fit = _hetero_fitness(table, hr.fid, pos)
    else:
        prob = cfg.problem
        if prob.projection_fn is not None:
            # projection mode: start feasible (box draw projected in-place)
            pos = prob.projection_fn(pos)
        elif prob.constrained and prob.constraints.mode == "repair":
            # repair mode: resample infeasible draws (attempt-indexed RNG
            # on the init stream; see constraints.repair_init_positions)
            pos = repair_init_positions(
                prob.constraints, prob.violation_fn, pos, lo, span, seed,
                STREAM_INIT_POS, idx, dt)
        fit = cfg.fitness_fn(pos)
    best = jnp.argmax(fit)
    s = SwarmState(
        pos=pos, vel=vel, fit=fit,
        pbest_pos=pos, pbest_fit=fit,
        gbest_pos=pos[best], gbest_fit=fit[best],
        iteration=jnp.zeros((), jnp.int32),
        seed=jnp.asarray(seed, jnp.uint32),
    )
    if n_blocks is None:
        return s
    lbp, lbf = init_async_locals(s, n_blocks)
    return s._replace(lbest_pos=lbp, lbest_fit=lbf)


def _rounded(x: Array) -> Array:
    """``x`` itself, as a value no compiler contracts into the add that
    follows: each init product is rounded on its own, as op-by-op
    execution, the eager oracles (``kernels/ref.py``) and the benchmark's
    reference do. XLA:CPU otherwise fuses ``lo + span * u`` into an FMA,
    a swarm starts an ulp off the oracles' init, and PSO's chaotic steps
    grow that past the agreement limits of tests/agreement.py within a
    dozen iterations (tests/test_topology.py's ring case). The max with
    the dtype's lowest finite value is exact for every product that
    occurs."""
    return jnp.maximum(x, jnp.finfo(x.dtype).min)


_init_traces = 0


def init_traces() -> int:
    """How many times a compiled init program (``_init_program`` and
    ``multi_swarm._init_batch_program``) has been traced in this process:
    one per new static key, so a count that grows with the seeds served
    means the init cache is missing
    (``repro.telemetry.init_traces``). The programs' cache is the
    process's, and so is this count. Under ``jax.disable_jit()`` nothing
    is compiled and nothing is counted."""
    return _init_traces


def count_init_trace() -> None:
    """Called at trace time by each compiled init program."""
    global _init_traces
    if not jax.config.jax_disable_jit:
        _init_traces += 1


def seed_operand(seed) -> np.ndarray:
    """A host seed as the uint32 the RNG keys on.

    Exactly the bits of ``jnp.asarray(seed).astype(uint32)`` (two's
    complement, modulo 2**32), for Python ints, numpy integers of any
    width and arrays of them alike, as one dtype: the compiled init
    programs see the same abstract operand for every seed and never
    retrace for a new value or integer type.
    """
    return (np.asarray(seed).astype(np.int64) & 0xFFFFFFFF).astype(np.uint32)


def _advance(cfg: PSOConfig, s: SwarmState, index_offset: int = 0,
             coeffs: Optional[Tuple[Array, Array, Array]] = None,
             gbest_pos: Optional[Array] = None, hetero=None
             ) -> Tuple[Array, Array, Array]:
    """Steps 2–3 of Alg. 1: velocity/position update + fitness, vectorized.

    Returns (pos, vel, fit) for iteration ``s.iteration + 1``.

    ``coeffs`` optionally overrides ``(w, c1, c2)`` with traced scalars —
    the hook ``repro.core.multi_swarm.solve_many`` uses to vmap one compiled
    program over *per-swarm* hyper-parameters (meta-tuning). When ``None``
    the config's Python floats are used, producing the exact same jaxpr as
    before the hook existed. ``gbest_pos`` optionally overrides the social
    attractor (any shape broadcastable to [N, D]) — the hook ``step_async``
    uses to steer each particle toward its *block's* local best instead of
    the shared swarm best. ``hetero=(table, row)`` swaps the inlined bound
    constants for the row's runtime columns and the objective for the table
    dispatch — the heterogeneous batch hook; also Python-gated (see the
    convention note above ``HeteroRow``).
    """
    n, d = s.pos.shape
    dt = s.pos.dtype
    it = s.iteration + 1
    w, c1, c2 = coeffs if coeffs is not None else (cfg.w, cfg.c1, cfg.c2)
    gbp = s.gbest_pos[None, :] if gbest_pos is None else gbest_pos
    idx = (jnp.arange(n * d, dtype=jnp.uint32).reshape(n, d)
           + jnp.uint32(index_offset * d))
    r1 = rng.uniform(s.seed, it, STREAM_R1, idx, dtype=dt)
    r2 = rng.uniform(s.seed, it, STREAM_R2, idx, dtype=dt)
    rule = resolve_rule(cfg.update_rule)
    if hetero is not None:
        table, hr = hetero
        pos, vel = rule.advance(r1, r2, s.pos, s.vel, s.pbest_pos, gbp,
                                w=w, c1=c1, c2=c2, mv=hr.mv, lo=hr.lo,
                                hi=hr.hi)
        return pos, vel, _hetero_fitness(table, hr.fid, pos)
    pos, vel = rule.advance(r1, r2, s.pos, s.vel, s.pbest_pos, gbp,
                            w=w, c1=c1, c2=c2,
                            mv=_bound_operand(cfg.max_v, dt),
                            lo=_bound_operand(cfg.min_pos, dt),
                            hi=_bound_operand(cfg.max_pos, dt))
    proj = cfg.problem.projection_fn
    if proj is not None:
        # the constrained post-advance hook (mode="projection"): clip to
        # the box first, then project onto the feasible set. Python-gated,
        # so unconstrained jaxprs are untouched bit-for-bit.
        pos = proj(pos)
    fit = cfg.fitness_fn(pos)
    return pos, vel, fit


def deb_selection_fn(cfg: PSOConfig):
    """The engine-level constrained pbest comparator, or None.

    Deb-rule selection (``constraints.deb_improved``) applies to the
    ``projection`` and ``repair`` constraint modes only: ``penalty`` mode
    keeps the raw canonical-fitness fold (the penalty already rides
    ``Problem.max_fn``), and unconstrained problems are Python-gated out so
    their jaxprs stay bit-identical to the pre-Deb engine. The returned
    callable computes ``improved(fit_new, pos_new, fit_old, pos_old) ->
    bool [N]``.
    """
    prob = cfg.problem
    if not prob.constrained or prob.constraints.mode == "penalty":
        return None
    vf = prob.violation_fn

    def better(fit_new, pos_new, fit_old, pos_old):
        return deb_improved(fit_new, vf(pos_new), fit_old, vf(pos_old))

    return better


def _update_pbest(s: SwarmState, pos: Array, fit: Array,
                  better=None) -> Tuple[Array, Array]:
    improved = (fit > s.pbest_fit if better is None
                else better(fit, pos, s.pbest_fit, s.pbest_pos))
    pbest_fit = jnp.where(improved, fit, s.pbest_fit)
    pbest_pos = jnp.where(improved[:, None], pos, s.pbest_pos)
    return pbest_pos, pbest_fit


def step_reduction(cfg: PSOConfig, s: SwarmState,
                   coeffs: Optional[Tuple[Array, Array, Array]] = None,
                   hetero=None) -> SwarmState:
    """Baseline: unconditional full argmax reduction (paper §3.2)."""
    pos, vel, fit = _advance(cfg, s, coeffs=coeffs, hetero=hetero)
    pbest_pos, pbest_fit = _update_pbest(
        s, pos, fit, deb_selection_fn(cfg) if hetero is None else None)
    best = jnp.argmax(pbest_fit)                      # O(N) reduction, always
    cand_fit = pbest_fit[best]
    cand_pos = pbest_pos[best]                        # O(D) gather, always
    take = cand_fit > s.gbest_fit
    gbest_fit = jnp.where(take, cand_fit, s.gbest_fit)
    gbest_pos = jnp.where(take, cand_pos, s.gbest_pos)
    return s._replace(pos=pos, vel=vel, fit=fit, pbest_pos=pbest_pos,
                      pbest_fit=pbest_fit, gbest_pos=gbest_pos,
                      gbest_fit=gbest_fit, iteration=s.iteration + 1)


def step_queue(cfg: PSOConfig, s: SwarmState,
               coeffs: Optional[Tuple[Array, Array, Array]] = None,
               hetero=None) -> SwarmState:
    """Queue algorithm (paper §4.1), TPU adaptation.

    The shared-memory queue + atomicAdd degenerates on a SIMD core into a
    *mask*: ``improved = fit > gbest_fit`` is the queue membership, and the
    argmax over improved lanes is thread-0's scan. The paper's win — skipping
    memory traffic when the queue is empty — maps to predicating the argmax +
    gather on the cheap scalar ``any(improved)``.
    """
    pos, vel, fit = _advance(cfg, s, coeffs=coeffs, hetero=hetero)
    pbest_pos, pbest_fit = _update_pbest(
        s, pos, fit, deb_selection_fn(cfg) if hetero is None else None)
    improved = fit > s.gbest_fit                      # cheap vector compare
    any_improved = jnp.any(improved)                  # scalar "queue non-empty"

    def publish(operand):
        fit_, pos_, gf, gp = operand
        best = jnp.argmax(jnp.where(improved, fit_, -jnp.inf))
        return fit_[best], pos_[best]

    def skip(operand):
        _, _, gf, gp = operand
        return gf, gp

    gbest_fit, gbest_pos = jax.lax.cond(
        any_improved, publish, skip, (fit, pos, s.gbest_fit, s.gbest_pos))
    return s._replace(pos=pos, vel=vel, fit=fit, pbest_pos=pbest_pos,
                      pbest_fit=pbest_fit, gbest_pos=gbest_pos,
                      gbest_fit=gbest_fit, iteration=s.iteration + 1)


def step_queue_lock(cfg: PSOConfig, s: SwarmState,
                    coeffs: Optional[Tuple[Array, Array, Array]] = None,
                    hetero=None) -> SwarmState:
    """Queue-lock (paper §4.2) jnp fallback: predicated gbest publication.

    The real fusion win (one pallas_call spanning all iterations with gbest
    carried in SMEM — the TPU analogue of removing the 2nd kernel and the
    spin-lock, including folding the rare O(N·D) pbest-position write under
    the improvement predicate) is ``repro.kernels.ops.run_queue_lock_fused``;
    this function keeps identical semantics for non-kernel paths with the
    argmax + D-dim gather predicated on the rare ``any(improved)``.

    The cond deliberately carries only the small gbest pair ([], [D]) —
    never an [N, D] operand: under vmap the cond becomes a select of both
    branches, and an [N, D] branch output would be computed and selected
    every iteration instead of a [D] gather.
    """
    pos, vel, fit = _advance(cfg, s, coeffs=coeffs, hetero=hetero)
    better = deb_selection_fn(cfg) if hetero is None else None
    p_improved = (fit > s.pbest_fit if better is None
                  else better(fit, pos, s.pbest_fit, s.pbest_pos))
    pbest_fit = jnp.where(p_improved, fit, s.pbest_fit)
    pbest_pos = jnp.where(p_improved[:, None], pos, s.pbest_pos)
    any_p = jnp.any(p_improved)

    def publish(operand):
        gf, gp = operand
        best = jnp.argmax(pbest_fit)                  # rare O(N) + O(D) gather
        take = pbest_fit[best] > gf
        return (jnp.where(take, pbest_fit[best], gf),
                jnp.where(take, pbest_pos[best], gp))

    def skip(operand):
        return operand

    gbest_fit, gbest_pos = jax.lax.cond(
        any_p, publish, skip, (s.gbest_fit, s.gbest_pos))
    return s._replace(pos=pos, vel=vel, fit=fit, pbest_pos=pbest_pos,
                      pbest_fit=pbest_fit, gbest_pos=gbest_pos,
                      gbest_fit=gbest_fit, iteration=s.iteration + 1)


STEP_FNS = {
    "reduction": step_reduction,
    "queue": step_queue,
    "queue_lock": step_queue_lock,
}

# All aggregation variants accepted by run/solve/solve_many/serve. "async"
# is not in STEP_FNS because it carries extra block-local state between
# iterations (see run_async); run()/run_many() dispatch it explicitly.
VARIANTS = ("reduction", "queue", "queue_lock", "async")

# Default publication interval for the async variant (iterations between
# cross-block gbest syncs). 8 keeps the staleness window small while
# amortizing the reduction ~an order of magnitude.
ASYNC_SYNC_EVERY = 8


def init_async_locals(state: SwarmState, n_blocks: int
                      ) -> Tuple[Array, Array]:
    """Block-local bests seeded from the shared gbest: ([nb, D], [nb])."""
    lbp = jnp.broadcast_to(state.gbest_pos[None, :],
                           (n_blocks,) + state.gbest_pos.shape)
    lbf = jnp.broadcast_to(state.gbest_fit, (n_blocks,))
    return jnp.asarray(lbp), jnp.asarray(lbf)


def init_swarm_async(cfg: PSOConfig, seed: int,
                     n_blocks: Optional[int] = None) -> SwarmState:
    """``init_swarm`` with the async block-local bests already attached.

    The serving scheduler's admission seam: a freshly admitted request's
    row must splice into an in-flight batch whose pytree structure carries
    ``lbest_pos``/``lbest_fit`` (the batch was built for the async
    variant), so the fresh row needs the buffers too. Seeding them with
    ``init_async_locals`` at iteration 0 is exactly what ``run_async``
    would have done on its first call for a bare ``init_swarm`` state —
    the carried-locals resume path and the fresh-seed path coincide at
    phase 0 — so an admitted row is bit-identical to the standalone
    solve of its request (tests/test_serving.py).

    One compiled program, like ``init_swarm``.
    """
    cfg = cfg.resolved()
    return _init_program(
        cfg, seed_operand(seed), 0, n=cfg.particle_cnt,
        n_blocks=n_blocks or _default_async_blocks(cfg.particle_cnt))


def step_async(cfg: PSOConfig, s: SwarmState,
               local: Tuple[Array, Array],
               coeffs: Optional[Tuple[Array, Array, Array]] = None,
               index_offset=None, hetero=None
               ) -> Tuple[SwarmState, Tuple[Array, Array]]:
    """One ASYNC queue-lock iteration (paper's enhanced variant, §4.2).

    Every block of ``n // nb`` particles advances against its *block-local*
    best ``local = (lbp [nb, D], lbf [nb])`` — zero cross-block
    communication. The iteration's per-block winner is folded into the local
    best; the shared ``s.gbest_*`` fields are left untouched (stale) until
    ``publish_async_locals`` syncs them, which ``run_async`` does every
    ``sync_every`` iterations. Deliberately cond-free (pure where/argmax)
    so it vmaps over a swarm axis without changing semantics.

    ``index_offset`` (optional, may be traced — e.g. ``axis_index * local_n``
    under shard_map) shifts the particle RNG indices so a shard owning
    particles [off, off+n) draws exactly the slice of the monolithic swarm's
    random stream (the ``init_swarm`` sharding convention). ``None`` keeps
    the exact pre-existing single-chip trace.
    """
    lbp, lbf = local
    n, d = s.pos.shape
    nb = lbf.shape[0]
    bn = n // nb
    gb = jnp.repeat(lbp, bn, axis=0)              # particle -> its block best
    pos, vel, fit = _advance(cfg, s, coeffs=coeffs, gbest_pos=gb,
                             index_offset=(0 if index_offset is None
                                           else index_offset),
                             hetero=hetero)
    pbest_pos, pbest_fit = _update_pbest(
        s, pos, fit, deb_selection_fn(cfg) if hetero is None else None)
    fb = fit.reshape(nb, bn)
    bi = jnp.argmax(fb, axis=1)                   # per-block iteration winner
    bfit = jnp.take_along_axis(fb, bi[:, None], axis=1)[:, 0]
    bpos = pos.reshape(nb, bn, d)[jnp.arange(nb), bi]
    take = bfit > lbf
    lbf = jnp.where(take, bfit, lbf)
    lbp = jnp.where(take[:, None], bpos, lbp)
    s = s._replace(pos=pos, vel=vel, fit=fit, pbest_pos=pbest_pos,
                   pbest_fit=pbest_fit, iteration=s.iteration + 1)
    return s, (lbp, lbf)


def publish_async_locals(s: SwarmState, local: Tuple[Array, Array]
                         ) -> Tuple[SwarmState, Tuple[Array, Array]]:
    """The sync point: publish the best local into the shared gbest, then
    pull the (new) shared gbest back into every block's local. After this,
    every block sees the true swarm-wide best — staleness resets to zero."""
    s, (lbp, lbf) = flush_async_locals(s, local)
    lbf = jnp.broadcast_to(s.gbest_fit, lbf.shape)
    lbp = jnp.broadcast_to(s.gbest_pos[None, :], lbp.shape)
    return s, (lbp, lbf)


def flush_async_locals(s: SwarmState, local: Tuple[Array, Array]
                       ) -> Tuple[SwarmState, Tuple[Array, Array]]:
    """Publish-only half of a sync: fold the best block-local into the
    shared gbest WITHOUT pulling it back into the blocks. Used for the
    forced end-of-call flush at a non-scheduled boundary: the returned
    state satisfies ``gbest_fit == max(pbest_fit)``, while the untouched
    locals let a resumed run continue each block exactly where it left off
    instead of restarting the staleness window."""
    lbp, lbf = local
    b = jnp.argmax(lbf)
    take = lbf[b] > s.gbest_fit
    gf = jnp.where(take, lbf[b], s.gbest_fit)
    gp = jnp.where(take, lbp[b], s.gbest_pos)
    return s._replace(gbest_pos=gp, gbest_fit=gf), (lbp, lbf)


def _default_async_blocks(n: int, target: int = 512) -> int:
    """Block count giving the largest block size ≤ target that divides n.

    Shares ``repro.core.blocking.pick_block_n`` with the Pallas kernels
    (``lane=1``: the jnp fallback has no tile-alignment constraint, which
    keeps its pre-unification block choices bit-for-bit)."""
    return default_block_count(n, target)


def run_async(cfg: PSOConfig, state: SwarmState, iters: int,
              sync_every: int = ASYNC_SYNC_EVERY,
              n_blocks: Optional[int] = None,
              coeffs: Optional[Tuple[Array, Array, Array]] = None,
              phase: Optional[int] = None, index_offset=None,
              hetero_row: Optional["HeteroRow"] = None,
              table=None) -> SwarmState:
    """``iters`` iterations of relaxed-consistency async PSO (jnp fallback).

    The library-level mirror of the Pallas async queue-lock: particle
    blocks run against block-local bests and the shared gbest is
    published/pulled only every ``sync_every`` iterations, so any block's
    view of the swarm best is at most ``sync_every`` iterations stale. A
    final sync always runs before returning: the result's ``gbest_fit``
    equals ``max(pbest_fit)`` exactly. With ``sync_every=1`` every
    iteration syncs — the synchronous queue-lock semantics as a special
    case. vmap-clean (no lax.cond anywhere) for ``multi_swarm.solve_many``.

    Checkpoint/resume: the returned state carries the block-local bests
    (``lbest_pos``/``lbest_fit``); a new call whose state carries them (with
    a matching block count) resumes from them instead of re-seeding from
    the shared gbest, and the end-of-call flush at a non-sync-aligned
    boundary publishes WITHOUT resetting them (``flush_async_locals``), so
    splitting a run across calls at sync points is bit-identical to the
    uninterrupted run (tests/test_checkpoint.py). ``phase`` is the resume
    point's offset into the staleness window (``iteration % sync_every``,
    static): sync points stay aligned to absolute iteration numbers, so
    even a mid-window split keeps the uninterrupted publication schedule.

    ``index_offset`` (optional, traced) shifts particle RNG indices for
    sharded swarms — see ``step_async``.
    """
    if phase is None:
        # Auto-align to the absolute iteration count when it is concrete
        # (the host-side resume path); under a trace (vmap'd batch engine)
        # fall back to 0 — the historical relative-window behavior.
        try:
            phase = int(state.iteration) % max(1, sync_every)
        except (TypeError, jax.errors.ConcretizationTypeError,
                jax.errors.TracerIntegerConversionError):
            phase = 0
    return _run_async(cfg, state, iters, sync_every, n_blocks, coeffs,
                      phase, index_offset, hetero_row, table)


@partial(jax.jit,
         static_argnames=("cfg", "iters", "sync_every", "n_blocks", "phase",
                          "table"))
def _run_async(cfg: PSOConfig, state: SwarmState, iters: int,
               sync_every: int, n_blocks: Optional[int],
               coeffs, phase: int, index_offset,
               hetero_row=None, table=None) -> SwarmState:
    cfg = cfg.resolved()
    hetero = None if hetero_row is None else (table, hetero_row)
    n, _ = state.pos.shape
    nb = n_blocks or _default_async_blocks(n)
    if n % nb:
        raise ValueError(f"n_blocks={nb} does not divide particle_cnt={n}")
    if iters <= 0:
        return state
    sync_every = max(1, sync_every)
    phase = phase % sync_every
    carried = (state.lbest_fit is not None
               and state.lbest_fit.shape == (nb,))
    local = ((state.lbest_pos, state.lbest_fit) if carried
             else init_async_locals(state, nb))
    state = state._replace(lbest_pos=None, lbest_fit=None)

    # The scheduled sync: star topology publishes + pulls the shared gbest
    # into every block; lbest topologies flush to the shared gbest (for
    # monitoring and the final answer) but each block pulls only from its
    # NEIGHBORHOOD of block-locals, so information diffuses hop by hop
    # (repro.core.topology). Python-gated: the default "gbest" traces the
    # exact pre-topology jaxpr.
    if cfg.topology == "gbest":
        scheduled_publish = publish_async_locals
    else:
        from .topology import block_neighbor_best

        def scheduled_publish(s, local):
            s, (lbp, lbf) = flush_async_locals(s, local)
            lbp, lbf = block_neighbor_best(lbf, lbp, cfg.topology)
            return s, (lbp, lbf)

    def one(carry):
        s, local = carry
        return step_async(cfg, s, local, coeffs=coeffs,
                          index_offset=index_offset, hetero=hetero)

    def chunk(span, publish=None):
        publish = scheduled_publish if publish is None else publish
        def body(_, carry):
            s, local = carry
            s, local = jax.lax.fori_loop(
                0, span, lambda _, c: one(c), (s, local))
            return publish(s, local)
        return body

    # Segment the run so publish points land on absolute iteration numbers
    # ≡ 0 (mod sync_every): an optional head chunk completes the window the
    # resume point interrupted, full chunks follow, and a trailing remainder
    # flushes publish-only (no pull — see flush_async_locals).
    if phase:
        head = min(iters, sync_every - phase)
        chunks, rem = divmod(iters - head, sync_every)
    else:
        head, (chunks, rem) = 0, divmod(iters, sync_every)
    carry = (state, local)
    if head:
        scheduled = head == sync_every - phase
        carry = chunk(head, scheduled_publish if scheduled
                      else flush_async_locals)(0, carry)
    if chunks:
        carry = jax.lax.fori_loop(0, chunks, chunk(sync_every), carry)
    if rem:
        carry = chunk(rem, flush_async_locals)(0, carry)
    s, (lbp, lbf) = carry
    return s._replace(lbest_pos=lbp, lbest_fit=lbf)


@partial(jax.jit, static_argnames=("cfg", "iters", "variant"))
def _run_stepped(cfg: PSOConfig, state: SwarmState, iters: int,
                 variant: str) -> SwarmState:
    step = STEP_FNS[variant]
    return jax.lax.fori_loop(0, iters, lambda _, s: step(cfg, s), state)


def run(cfg: PSOConfig, state: SwarmState, iters: int,
        variant: str = "queue",
        sync_every: int = ASYNC_SYNC_EVERY,
        n_blocks: Optional[int] = None) -> SwarmState:
    """Run ``iters`` PSO iterations with the chosen aggregation variant.

    ``sync_every`` and ``n_blocks`` only affect ``variant="async"``
    (publication interval and particle-block count — the schedule knobs
    the autotuner picks; ``n_blocks=None`` keeps the heuristic default).
    A thin dispatcher over the jitted implementations, so synchronous
    variants never key their jit cache on the (irrelevant) ``sync_every``.
    """
    cfg = cfg.resolved()
    if variant == "async":
        return run_async(cfg, state, iters, sync_every=sync_every,
                         n_blocks=n_blocks)
    if state.lbest_fit is not None:
        # Sync variants advance gbest without maintaining the async
        # block-local cache; drop it so a later async run re-seeds fresh.
        state = state._replace(lbest_pos=None, lbest_fit=None)
    return _run_stepped(cfg, state, iters, variant)


def solve(cfg: PSOConfig, seed: int = 0, iters: int = 1000,
          variant: str = "queue",
          sync_every: int = ASYNC_SYNC_EVERY) -> SwarmState:
    """Convenience one-shot: init + run."""
    cfg = cfg.resolved()
    return run(cfg, init_swarm(cfg, seed), iters, variant, sync_every)


# --------------------------------------------------------------------------
# Convergence history (ROADMAP follow-on (c)): gbest per sync point.
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "iters", "variant"))
def _run_stepped_history(cfg: PSOConfig, state: SwarmState, iters: int,
                         variant: str):
    step = STEP_FNS[variant]
    vf = cfg.problem.violation_fn

    def body(s, _):
        s = step(cfg, s)
        v = (vf(s.gbest_pos) if vf is not None
             else jnp.zeros((), s.gbest_fit.dtype))
        return s, (s.gbest_fit, v)

    state, (fits, viols) = jax.lax.scan(body, state, xs=None, length=iters)
    return state, fits, viols


def run_with_history(cfg: PSOConfig, state: SwarmState, iters: int,
                     variant: str = "queue",
                     sync_every: int = ASYNC_SYNC_EVERY):
    """Like ``run`` but also records the gbest trajectory.

    Returns ``(state, (iterations, gbest_fits, violations))`` where the
    arrays hold one entry per sync point — every iteration for the
    synchronous variants (a ``lax.scan`` over the same step functions, so
    one device program), every publication boundary for ``async`` (the run
    is segmented at sync points, which the checkpoint/resume machinery
    makes bit-identical to the uninterrupted run — tests/test_checkpoint).
    ``violations`` is the aggregate constraint violation of the recorded
    gbest position (None for unconstrained problems): constrained runs use
    it to report the first-feasible iteration (``repro.Result``).
    """
    cfg = cfg.resolved()
    constrained = cfg.problem.constrained
    if iters <= 0:
        empty = jnp.zeros((0,), state.gbest_fit.dtype)
        return state, ((), empty, empty if constrained else None)
    if variant != "async":
        if state.lbest_fit is not None:
            state = state._replace(lbest_pos=None, lbest_fit=None)
        start = int(state.iteration)
        state, fits, viols = _run_stepped_history(cfg, state, iters, variant)
        its = tuple(range(start + 1, start + iters + 1))
        return state, (its, fits, viols if constrained else None)
    vf = cfg.problem.violation_fn
    its, fits, viols = [], [], []
    done = 0
    while done < iters:
        k = min(max(1, sync_every), iters - done)
        state = run_async(cfg, state, k, sync_every=sync_every)
        done += k
        its.append(int(state.iteration))
        fits.append(state.gbest_fit)
        viols.append(vf(state.gbest_pos) if vf is not None
                     else jnp.zeros((), state.gbest_fit.dtype))
    fits = jnp.stack(fits)
    viols = jnp.stack(viols)
    return state, (tuple(its), fits, viols if constrained else None)
