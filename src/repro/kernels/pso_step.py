"""Pallas TPU kernels for cuPSO — one scaffold, seven calls.

Layout — SoA, D-major (the paper's §5.1 coalescing rule, translated):
arrays are ``[Dpad, N]`` with the *particle* index on the 128-wide lane
dimension and the problem dimension on sublanes (padded to a multiple of 8).
A VPU lane plays the role of a CUDA thread: all lanes touch consecutive
particles of the same dimension — Fig. 2 of the paper, verbatim, in TPU tile
terms. For D=1 this packs 16× denser than a dim-on-lanes layout.

Scaffold vs update rule
-----------------------
The paper's contribution is the queue-lock *aggregation* scaffold — grids,
the intra-block candidate queue, block-local bests, sparse publication —
which is orthogonal to the per-particle *update rule*. This module keeps
exactly ONE copy of that scaffold: two generators,

``_make_sync_kernel(queue=..., batched=..., hetero=...)``
    emits the synchronous bodies (one advance + pbest fold + publication
    per grid step) — ``_queue_kernel``, ``_fused_kernel``,
    ``_fused_batch_kernel`` and ``_hetero_fused_batch_kernel`` are its four
    instantiations;

``_make_async_kernel(batched=..., hetero=...)``
    emits the block-resident asynchronous bodies (``sync_every``-iteration
    chunks against a block-local best, shared-best pull at chunk entry and
    predicated publish at chunk exit) — ``_fused_async_kernel``,
    ``_fused_async_batch_kernel`` and ``_hetero_fused_async_batch_kernel``
    are its three instantiations.

Every body closes over an ``repro.core.update_rules.UpdateRule`` — the
algorithm half. The default ``"pso"`` rule reproduces the pre-refactor
``_advance_block`` velocity/position chain bit-for-bit (pinned by the
trajectory digests in tests/test_problem.py); ``"sso"`` and ``"lowcost"``
ride the same scaffold with zero kernel-side changes, validated per
``(rule, variant)`` against the matching ``ref.py`` oracles in
tests/test_update_rules.py. Cross-cutting features (constraints, per-dim
bounds, hetero dispatch) are now threaded through the scaffold once instead
of through seven hand-maintained bodies.

The async builders additionally take a block-neighborhood ``topology``
(``"gbest"`` | ``"ring"`` | ``"vonneumann"``, see ``repro.core.topology``):
with an lbest topology a block refreshes its chunk-entry local best from
its *neighbor blocks'* local slots (``kernel_neighbor_ids``) instead of the
shared gbest, which is still flushed at chunk exit for monitoring and the
final answer. ``topology="gbest"`` compiles the exact pre-refactor pull.

The seven pallas_call builders:

``queue`` (single iteration, grid = particle blocks)
    The paper's §4.1 two-kernel structure. Kernel 1 advances particles,
    evaluates fitness, updates pbest, and publishes a per-block
    ``(aux_fit, aux_idx)`` candidate — computed as a *masked* max over only
    the lanes that improve on the stale gbest (the SIMD degeneration of the
    shared-memory queue: membership mask == queue, one vectorized max ==
    thread-0's scan). The "2nd kernel" (cross-block argmax + conditional
    gbest update) is a tiny jnp epilogue in ``ops.py`` operating on
    ``nblocks`` scalars. Only the particle *index* is published, never the
    D-dim position (paper §5.3): the position is gathered once, after the
    cross-block winner is known.

``fused_batch`` (queue-lock, grid = (swarms, iterations, particle blocks))
    The multi-swarm extension of ``fused``: one ``pallas_call`` advances S
    *independent* swarms x iters. State is packed ``[S, Dpad, N]``; each
    swarm has its own gbest column in a ``[S, Dpad, 1]`` buffer, its own
    SMEM gbest_fit slot, and its own
    ``(seed, iteration)`` RNG counters, so swarm s is bit-identical to a
    standalone ``fused`` run with the same seed and block size. The grid is
    swarm-major: a swarm's gbest buffers stay resident across all its
    iterations before the next swarm is touched. This is the kernel behind
    ``repro.kernels.ops.run_queue_lock_fused_batch`` and the Pallas leg of
    ``repro.core.multi_swarm.solve_many``.

``fused`` (queue-lock, grid = (iterations, particle blocks))
    The paper's §4.2 fusion, strengthened: ONE ``pallas_call`` spans *all*
    iterations. The global best lives in output buffers whose block index is
    constant across the grid, so (a) on TPU they are fetched/flushed once,
    not per step, and (b) sequential grid execution serializes every block's
    conditional publication — the atomicCAS spin-lock costs literally
    nothing. State arrays are input/output-aliased, so the swarm never
    round-trips to HBM between iterations when the block count is 1.
    Semantics: block b at iteration t sees the gbest already updated by
    blocks 0..b-1 of iteration t (fresher than synchronous PPSO; mirrored
    exactly by ``ref.run_fused_oracle``).

``fused_async`` (async queue-lock, grid = (particle blocks, iter chunks))
    The paper's *enhanced* queue-lock: thread groups run asynchronously and
    update the shared best only occasionally. The grid is the TRANSPOSE of
    ``fused`` — block-major — so each particle block stays resident (state
    tile fetched/flushed once for its entire iteration span, not per
    iteration) and runs ``sync_every`` iterations per grid step against a
    *block-local* best carried in the fori-loop registers and persisted in
    small ``[nb, Dpad, 1]``/SMEM ``[nb]`` side buffers. The shared ``[Dpad,1]``
    + SMEM gbest is touched only at chunk boundaries: a pull (read) at chunk
    entry and a predicated publish (write) at chunk exit — the lock
    acquisition shrinks from every (iteration x block) to every
    ``sync_every`` iterations, and the rare-improvement predicate usually
    skips the write entirely.

    Consistency model: a block's view of the swarm-wide best is at most
    ``sync_every`` iterations stale, and (block-major order) block b
    additionally inherits everything blocks 0..b-1 published during their
    whole span. With a single block the local best IS the global best, so
    the trajectory is bit-identical to ``fused`` for every ``sync_every``
    (the sync kernel is the async kernel's special case); with several
    blocks the schedule is genuinely relaxed and is mirrored by
    ``ref.run_fused_async_oracle``. ``fused_async_batch`` adds the leading
    swarm axis (grid (swarms, blocks, chunks)) with per-swarm gbest buffers
    and per-(swarm, block) local-best slots.

Objectives: every kernel takes ``fitness`` as a registered name or a
``repro.core.problem.Problem``. Names and built-in Problems select the
hand-tuned ``_fitness_dmajor`` forms below (bit-identical to the
pre-Problem-API kernels); any other Problem is lowered automatically by
``dmajor_adapter`` (transpose into the user's ``[bn, d]`` view) with its
captured array constants hoisted into explicit pallas_call operands by
``lower_statics`` — Pallas forbids captured consts.
Per-dimension bounds ride the same const-threading as ``[Dpad, 1]``
columns.

Constraints: a Problem carrying a ``repro.core.constraints.ConstraintSet``
always lowers by conversion — ``penalty`` mode is invisible here (the
penalty rides ``Problem.max_fn`` like any custom objective), ``projection``
mode adds a post-clip transform inside ``_advance_block``
(``kernel_projection`` lifts the user operator to the d-major tile layout;
its captured consts hoist through ``lower_statics`` exactly like objective
consts), and ``repair`` mode only affects ``init_swarm`` (kernels receive
an already-repaired state). For ``projection``/``repair`` modes the pbest
fold inside every kernel body applies the Deb rule (feasible > fitness >
violation, ``repro.core.constraints.deb_improved``) via the d-major
``kernel_violation`` form — the same engine-level gate as
``repro.core.pso.deb_selection_fn``; ``penalty`` mode and unconstrained
problems keep the raw ``fit > pbest`` fold bit-for-bit.

Validated in interpret mode against the eager ``ref.py`` oracles (same
counter RNG, same tile math) over shape/dtype sweeps in
tests/test_kernels.py and tests/test_async.py, custom objectives in
tests/test_problem.py. A kernel and its oracle are two programs: XLA:CPU
chooses FMA contraction and reduction order per compiled program, while
the chip and the oracles round op by op, so they agree within the stated
limits of tests/agreement.py (the benchmark's own), not bit for bit.
tests/test_tpu_compile.py compiles every builder but ``queue_step_call``
with Mosaic for a described TPU v5e.

Mosaic layout rule: a VMEM block's last two dims must each equal the
array's or be a multiple of the (8, 128) tile. So per-swarm and per-block
column buffers are rank 3 with the column index on the leading, untiled
axis — batched ``gbest_pos`` is ``[S, Dpad, 1]`` and ``local_pos`` is
``[S*nb, Dpad, 1]`` — and the batched state is ``[S, Dpad, N]``, so a block
of a swarm with fewer than 128 particles still spans its whole lane axis.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rng
from repro.core.blocking import LANE
from repro.core.constraints import deb_improved
from repro.core.pso import STREAM_R1, STREAM_R2
from repro.core.problem import Problem
from repro.core.topology import kernel_neighbor_ids
from repro.core.update_rules import resolve_rule
from repro.telemetry.streams import record_sync

SUBLANE = 8
_BIG_I32 = np.int32(2 ** 30)


def default_interpret() -> bool:
    """Pallas interpret mode, decided once from the platform: the kernels
    compile with Mosaic exactly when JAX's default backend is a TPU, and run
    in the interpreter everywhere else. Every builder below resolves
    ``interpret=None`` through here; no layer above them chooses."""
    return jax.default_backend() != "tpu"


def _interpret(interpret) -> bool:
    return default_interpret() if interpret is None else interpret


def _arbitrary(ndim: int):
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.ARBITRARY,) * ndim)


def pad_dim(d: int) -> int:
    return max(SUBLANE, -(-d // SUBLANE) * SUBLANE)


# --------------------------------------------------------------------------
# In-kernel fitness (D-major layout): pos [Dpad, bn] -> fit [1, bn].
# Padded sublanes are masked. Must match repro.core.fitness row-for-row.
# --------------------------------------------------------------------------

def _fitness_dmajor(name: str, pos, dmask, d_real: int):
    zero = jnp.zeros_like(pos)
    if name == "cubic":
        v = pos * pos * pos - 0.8 * (pos * pos) - 1000.0 * pos + 8000.0
        return jnp.sum(jnp.where(dmask, v, zero), axis=0, keepdims=True)
    if name == "sphere":
        return -jnp.sum(jnp.where(dmask, pos * pos, zero), axis=0, keepdims=True)
    if name == "rastrigin":
        v = pos * pos - 10.0 * jnp.cos(2.0 * jnp.pi * pos)
        s = jnp.sum(jnp.where(dmask, v, zero), axis=0, keepdims=True)
        return -(10.0 * d_real + s)
    if name == "griewank":
        dsub = lax.broadcasted_iota(jnp.int32, pos.shape, 0).astype(pos.dtype) + 1.0
        s = jnp.sum(jnp.where(dmask, pos * pos, zero), axis=0, keepdims=True) / 4000.0
        c = jnp.cos(pos / jnp.sqrt(dsub))
        # Mosaic has no reduce_prod: multiply the real sublanes row by row.
        p = c[0:1]
        for i in range(1, d_real):
            p = p * c[i:i + 1]
        return -(s - p + 1.0)
    if name == "ackley":
        s1 = jnp.sqrt(jnp.sum(jnp.where(dmask, pos * pos, zero), axis=0,
                              keepdims=True) / d_real)
        c = jnp.cos(2.0 * jnp.pi * pos)
        s2 = jnp.sum(jnp.where(dmask, c, zero), axis=0, keepdims=True) / d_real
        return -(-20.0 * jnp.exp(-0.2 * s1) - jnp.exp(s2) + 20.0 + jnp.e)
    if name == "rosenbrock":
        if d_real == 1:          # library convention: degenerates to -(1-x)^2
            v = (1.0 - pos) * (1.0 - pos)
            return -jnp.sum(jnp.where(dmask, v, zero), axis=0, keepdims=True)
        # Coupled-dim sum over pairs (x_i, x_{i+1}): shift the sublane axis
        # down by one so every row i also sees row i+1. The wrapped row is
        # masked out (pairs exist only for i < d_real - 1).
        nxt = jnp.concatenate([pos[1:], pos[:1]], axis=0)
        dsub = lax.broadcasted_iota(jnp.int32, pos.shape, 0)
        pair_mask = dsub < (d_real - 1)
        v = (100.0 * (nxt - pos * pos) * (nxt - pos * pos)
             + (1.0 - pos) * (1.0 - pos))
        return -jnp.sum(jnp.where(pair_mask, v, zero), axis=0, keepdims=True)
    raise NotImplementedError(f"kernel fitness {name!r}")


KERNEL_FITNESS = ("cubic", "sphere", "rastrigin", "griewank", "ackley",
                  "rosenbrock")


def dmajor_adapter(fn):
    """Lift a pure-jnp objective ``fn(pos[..., D]) -> fit[...]`` into the
    masked d-major kernel layout ``(pos [Dpad, bn], dmask, d_real) ->
    fit [1, bn]``.

    The padded sublanes are removed by a static slice (they are already
    zero-masked by ``_advance_block``, but slicing means ``fn`` never sees
    them at all — no masking contract is imposed on user objectives), then
    the tile is transposed so ``fn`` receives its documented particle-major
    ``[bn, d]`` view. This is what lets ANY registered/user Problem run
    inside the fused, async and batched Pallas kernels without a
    hand-written d-major form; the six built-ins keep their hand-tuned
    ``_fitness_dmajor`` forms as fast paths (transpose-free), parity-tested
    against this adapter in tests/test_problem.py.
    """
    def lifted(pos, dmask, d_real):
        del dmask
        return fn(pos[:d_real, :].T)[None, :]
    lifted.__name__ = f"dmajor[{getattr(fn, '__name__', 'fn')}]"
    return lifted


def kernel_fitness(fitness):
    """Resolve a config's ``fitness`` (str | Problem) to the in-kernel
    d-major callable ``(pos, dmask, d_real) -> [1, bn]`` in canonical
    (maximization) form.

    Built-in names and built-in Problems take the hand-tuned
    ``_fitness_dmajor`` fast path (bit-identical to the pre-Problem-API
    kernels); any other registered name resolves through the registry
    first (a registered custom/constrained problem is addressable by
    string everywhere, including the kernel backend); a Problem with a
    user ``kernel_fn`` uses it verbatim (it must already be
    canonical-max, see ``repro.core.problem``); any other Problem is
    lowered by ``dmajor_adapter``.
    """
    if isinstance(fitness, str):
        if fitness in KERNEL_FITNESS:
            return functools.partial(_fitness_dmajor, fitness)
        from repro.core.problem import get_problem
        fitness = get_problem(fitness)
    if not isinstance(fitness, Problem):
        raise TypeError(f"fitness must be str or Problem, got {fitness!r}")
    if fitness.kernel_fn is not None:
        return fitness.kernel_fn
    from repro.core.fitness import FITNESS_FNS
    if (fitness.sense == "max" and fitness.name in KERNEL_FITNESS
            and fitness.fn is FITNESS_FNS.get(fitness.name)
            and fitness.constraints is None):
        # constrained problems never take the hand-tuned fast path: the
        # penalty must ride max_fn, and projection-mode advances must be
        # pinned like any converted objective (see _resolve_statics).
        return functools.partial(_fitness_dmajor, fitness.name)
    return dmajor_adapter(fitness.max_fn)


def kernel_projection(fitness):
    """Resolve a Problem's feasibility projection to the d-major tile form
    ``pos [Dpad, bn] -> pos [Dpad, bn]`` (padded sublanes re-zeroed), or
    None when the objective has no projection-mode constraints.

    Mirrors ``dmajor_adapter``: the user operator sees its documented
    particle-major ``[bn, d]`` view. Applied AFTER the box clip inside
    ``_advance_block`` (the box-clip composition); its captured array
    constants are hoisted into pallas_call operands by ``lower_statics``
    exactly like objective consts.
    """
    if isinstance(fitness, str):
        if fitness in KERNEL_FITNESS:
            return None                        # built-ins are box-only
        from repro.core.problem import get_problem
        fitness = get_problem(fitness)
    if not isinstance(fitness, Problem):
        return None
    proj = fitness.projection_fn
    if proj is None:
        return None

    def lifted(pos, d_real):
        dpad, bn = pos.shape
        out = proj(pos[:d_real, :].T).T            # [d_real, bn]
        if dpad == d_real:
            return out
        return jnp.concatenate(
            [out, jnp.zeros((dpad - d_real, bn), pos.dtype)], axis=0)

    lifted.__name__ = f"dmajor_proj[{getattr(proj, '__name__', 'fn')}]"
    return lifted


def tpu_lowerable(fitness) -> bool:
    """False when the kernels cannot compile ``fitness`` for a TPU: a
    projection-mode constraint set projects inside the advance
    (``kernel_projection``), its operators sort along the dimension axis
    (``project_simplex``), and Mosaic has no lowering for sort. Such
    problems run on the jnp engines (``repro.api.Method.resolve_backend``);
    the interpreter still runs their kernels for the oracle tests."""
    return kernel_projection(fitness) is None


def kernel_violation(fitness):
    """Resolve a Problem's aggregate constraint violation to the d-major
    tile form ``(pos [Dpad, bn], d_real) -> viol [1, bn]``, or None when
    Deb-rule pbest selection does not apply (unconstrained problems and
    ``penalty`` mode, whose penalty already rides ``max_fn``).

    Drives the kernels' constrained pbest fold (feasible > fitness >
    violation — ``repro.core.constraints.deb_improved``, the same gate as
    the jnp engine's ``deb_selection_fn``). Mirrors ``dmajor_adapter``:
    the user constraint functions see their documented particle-major
    ``[bn, d]`` view; captured array constants hoist through
    ``lower_statics`` exactly like objective/projection consts.
    """
    if isinstance(fitness, str):
        if fitness in KERNEL_FITNESS:
            return None                        # built-ins are unconstrained
        from repro.core.problem import get_problem
        fitness = get_problem(fitness)
    if not isinstance(fitness, Problem):
        return None
    if not fitness.constrained or fitness.constraints.mode == "penalty":
        return None
    vf = fitness.violation_fn

    def lifted(pos, d_real):
        return vf(pos[:d_real, :].T)[None, :]

    lifted.__name__ = f"dmajor_viol[{fitness.name}]"
    return lifted


def _pbest_improved(fit, pos, pbf, pbp, viol):
    """The kernels' pbest-selection mask [1, bn]: raw fitness compare, or
    the Deb rule when a ``kernel_violation`` form is present (projection /
    repair constraint modes)."""
    if viol is None:
        return fit > pbf
    return deb_improved(fit, viol(pos), pbf, viol(pbp))


def is_converted(fitness) -> bool:
    """True when ``kernel_fitness`` lowers ``fitness`` by conversion (the
    d-major adapter or a user ``kernel_fn``) rather than the hand-tuned
    ``_fitness_dmajor`` forms: converted objectives hoist their captured
    arrays through ``lower_statics``."""
    return getattr(kernel_fitness(fitness), "func", None) is not _fitness_dmajor


def _bound_col(v, dpad, dtype):
    """Bound -> kernel operand: scalars stay Python floats (the seed
    arithmetic, bit-for-bit); per-dimension tuples become a [Dpad, 1]
    constant column (padded sublanes get 0 — their lanes are re-masked
    after the clip anyway) broadcasting over the lane axis."""
    if not isinstance(v, tuple):
        return v
    col = np.zeros((dpad, 1), np.dtype(dtype))
    col[:len(v), 0] = v
    return jnp.asarray(col)


# --------------------------------------------------------------------------
# Static lowering: objectives + per-dim bounds as pallas-legal operands.
#
# Pallas forbids kernels that capture array constants, but a user objective
# is free to close over weight/target vectors (and per-dimension bounds ARE
# [Dpad, 1] columns). ``lower_statics`` closure-converts the resolved
# fitness (jax.closure_convert hoists every captured array into an explicit
# argument) and collects bound columns, returning a ``consts`` tuple the
# call builders append as extra pallas_call inputs; ``_resolve_statics``
# rebuilds the operands inside the kernel from the fetched const values.
# The legacy path (string fitness, scalar bounds) produces ZERO consts and
# bypasses closure conversion entirely — its kernels are the seed kernels,
# bit-for-bit.
# --------------------------------------------------------------------------

class _Slot:
    """Marker: the operand lives in the kernel's const inputs at ``index``."""
    __slots__ = ("index",)

    def __init__(self, index: int):
        self.index = index


def lower_statics(fitness, *, d, dpad, bn, dtype,
                  min_pos, max_pos, max_v):
    """Lower (fitness, bounds) statics to (statics dict, const arrays).

    ``consts`` must be appended, in order, to the pallas_call operands
    (specs from ``_const_specs``). ``statics`` entries are Python scalars,
    ``_Slot`` markers pointing into the const values, and the fitness
    callable (plus its own const slots when closure conversion ran).
    """
    consts = []

    def slot(arr):
        consts.append(arr)
        return _Slot(len(consts) - 1)

    st = {}
    for name, v in (("min_pos", min_pos), ("max_pos", max_pos),
                    ("max_v", max_v)):
        st[name] = slot(_bound_col(v, dpad, dtype)) if isinstance(v, tuple) \
            else v
    fitfn = kernel_fitness(fitness)
    if not is_converted(fitness):
        # Hand-tuned forms are const-free by construction: skip conversion
        # so the legacy jaxpr (and its compiled bits) are untouched.
        st["fit"] = fitfn
        st["fit_slots"] = None
    else:
        # Trace once to hoist every array constant the objective bakes in
        # (weight/target vectors etc. — jax.closure_convert only hoists
        # closed-over *tracers*, so pull the jaxpr consts out ourselves).
        closed = jax.make_jaxpr(lambda p, m: fitfn(p, m, d))(
            jax.ShapeDtypeStruct((dpad, bn), dtype),
            jax.ShapeDtypeStruct((dpad, bn), jnp.bool_))

        def pure(p, m, *cvals, _jaxpr=closed.jaxpr):
            out = jax.core.eval_jaxpr(_jaxpr, cvals, p, m)
            if len(out) != 1:
                raise ValueError("objective must return a single array")
            return out[0]

        st["fit"] = pure
        st["fit_slots"] = tuple(slot(jnp.asarray(c)) for c in closed.consts)
    projfn = kernel_projection(fitness)
    if projfn is None:
        st["proj"] = None
        st["proj_slots"] = None
    else:
        # Same hoisting for the feasibility projection: user operators may
        # close over arrays (targets, metric weights), which Pallas forbids
        # as captured consts.
        pclosed = jax.make_jaxpr(lambda p: projfn(p, d))(
            jax.ShapeDtypeStruct((dpad, bn), dtype))

        def pure_proj(p, *cvals, _jaxpr=pclosed.jaxpr):
            out = jax.core.eval_jaxpr(_jaxpr, cvals, p)
            if len(out) != 1:
                raise ValueError("projection must return a single array")
            return out[0]

        st["proj"] = pure_proj
        st["proj_slots"] = tuple(slot(jnp.asarray(c))
                                 for c in pclosed.consts)
    violfn = kernel_violation(fitness)
    if violfn is None:
        st["viol"] = None
        st["viol_slots"] = None
    else:
        # And for the Deb-fold violation form (projection/repair modes):
        # user constraint fns may close over arrays too.
        vclosed = jax.make_jaxpr(lambda p: violfn(p, d))(
            jax.ShapeDtypeStruct((dpad, bn), dtype))

        def pure_viol(p, *cvals, _jaxpr=vclosed.jaxpr):
            out = jax.core.eval_jaxpr(_jaxpr, cvals, p)
            if len(out) != 1:
                raise ValueError("violation must return a single array")
            return out[0]

        st["viol"] = pure_viol
        st["viol_slots"] = tuple(slot(jnp.asarray(c))
                                 for c in vclosed.consts)
    st["n_consts"] = len(consts)
    return st, tuple(consts)


def _resolve_statics(st, const_vals):
    """Kernel-side inverse of ``lower_statics``: returns
    (min_pos, max_pos, max_v, fitfn, proj, viol) with
    fitfn(pos, dmask, d_real), proj(pos) and viol(pos) (each or None).
    """
    def get(v):
        return const_vals[v.index] if isinstance(v, _Slot) else v

    if st["fit_slots"] is None:
        fit = st["fit"]
    else:
        pure = st["fit"]
        extra = tuple(const_vals[s.index] for s in st["fit_slots"])

        def fit(pos, dmask, d_real, _pure=pure, _extra=extra):
            del d_real  # baked in at closure-conversion time
            return _pure(pos, dmask, *_extra)

    if st["proj"] is None:
        proj = None
    else:
        pure_proj = st["proj"]
        pextra = tuple(const_vals[s.index] for s in st["proj_slots"])

        def proj(pos, _pure=pure_proj, _extra=pextra):
            return _pure(pos, *_extra)

    if st["viol"] is None:
        viol = None
    else:
        pure_viol = st["viol"]
        vextra = tuple(const_vals[s.index] for s in st["viol_slots"])

        def viol(pos, _pure=pure_viol, _extra=vextra):
            return _pure(pos, *_extra)

    return (get(st["min_pos"]), get(st["max_pos"]), get(st["max_v"]), fit,
            proj, viol)


def _const_specs(consts):
    """Whole-array BlockSpecs for the const inputs (grid-invariant)."""
    return [pl.BlockSpec(c.shape, lambda *g, _r=c.ndim: (0,) * _r)
            for c in consts]


#: the default rule — its ``advance`` is the seed kernels' velocity chain
_PSO_RULE = resolve_rule("pso")


def _advance_block(seed, it, pos, vel, pbp, gp_col, block_base, *,
                   w, c1, c2, min_pos, max_pos, max_v, d_real, project=None,
                   rule=None):
    """Paper Alg. 1 steps 2–3 for one [Dpad, bn] tile.

    Shared verbatim by the kernel bodies and the ``ref.py`` oracle so that
    interpret-mode validation isolates the *pallas orchestration* (grid,
    aliasing, blocking, predication); the math itself is validated against
    the independent ``repro.core.pso`` implementation in tests.
    ``min_pos``/``max_pos``/``max_v`` are scalars or per-dimension tuples
    (lowered to constant [Dpad, 1] columns). ``project`` is the optional
    feasibility projection ``pos [Dpad, bn] -> pos`` applied after the box
    clip (constrained problems, mode="projection" — see
    ``repro.core.constraints``). ``rule`` is the pluggable
    ``repro.core.update_rules.UpdateRule`` (None -> the default ``"pso"``
    rule, whose elementwise chain is the pre-refactor body bit-for-bit);
    the scaffold owns RNG indexing, sublane masking and projection, the
    rule owns only the pos/vel math. Returns (pos, vel, dmask, lane).
    """
    dpad, bn = pos.shape
    min_pos = _bound_col(min_pos, dpad, pos.dtype)
    max_pos = _bound_col(max_pos, dpad, pos.dtype)
    max_v = _bound_col(max_v, dpad, pos.dtype)
    dsub = lax.broadcasted_iota(jnp.int32, (dpad, bn), 0)
    lane = lax.broadcasted_iota(jnp.int32, (dpad, bn), 1)
    dmask = dsub < d_real
    # Global RNG index: particle * D + dim — identical to the library path.
    gidx = ((block_base + lane) * d_real + dsub).astype(jnp.uint32)
    r1 = rng.uniform(seed, it, STREAM_R1, gidx, dtype=pos.dtype)
    r2 = rng.uniform(seed, it, STREAM_R2, gidx, dtype=pos.dtype)
    rule = _PSO_RULE if rule is None else rule
    # gp_col [Dpad, 1] broadcasts over lanes inside the rule.
    pos, vel = rule.advance(r1, r2, pos, vel, pbp, gp_col,
                            w=w, c1=c1, c2=c2, mv=max_v,
                            lo=min_pos, hi=max_pos)
    if project is not None:
        pos = project(pos)
    zero = jnp.zeros_like(pos)
    return jnp.where(dmask, pos, zero), jnp.where(dmask, vel, zero), dmask, lane


# --------------------------------------------------------------------------
# Shared scaffold machinery: pbest fold, candidate queue, winner gather.
# --------------------------------------------------------------------------

def _kernel_rule(rule):
    """Resolve + gate a rule for the Pallas scaffolds (builder entry)."""
    rule = resolve_rule(rule)
    if not rule.kernel_eligible:
        raise ValueError(
            f"update rule {rule.name!r} is not kernel-eligible "
            f"(non-elementwise advance); use the jnp backend")
    return rule


def _fold_pbest(fit, pos, pbf_ref, pbp_ref, viol):
    """Alg. 1 step 4: fold the pbest refs in place (raw fitness compare,
    or the Deb rule when a ``kernel_violation`` form is present).

    Returns the per-lane improvement mask so telemetry-enabled scaffolds
    can count block-improvement events without recomputing the compare."""
    pbf = pbf_ref[...]
    pbp = pbp_ref[...]
    imp = _pbest_improved(fit, pos, pbf, pbp, viol)
    pbf_ref[...] = jnp.where(imp, fit, pbf)
    pbp_ref[...] = jnp.where(imp, pos, pbp)
    return imp


def _queue_best(fit, best):
    """The paper's intra-block queue, degenerated to SIMD folds: membership
    mask (lanes improving on ``best``) == the queue, one vectorized masked
    max == thread-0's scan, first-lane tie-break. Returns ``(bf, bidx)``
    with ``bf == -inf`` when the queue is empty."""
    neg = jnp.full_like(fit, -jnp.inf)
    q_fit = jnp.where(fit > best, fit, neg)
    bf = jnp.max(q_fit)
    lane_row = lax.broadcasted_iota(jnp.int32, fit.shape, 1)
    bidx = jnp.min(jnp.where(q_fit >= bf, lane_row, _BIG_I32))
    return bf, bidx


def _gather_winner(pos, dmask, lane, bidx):
    """§5.3 trick: gather the winning lane's position column as a masked
    sum — one vectorized pass, only run on (rare) improvement."""
    sel = (lane == bidx) & dmask
    return jnp.sum(jnp.where(sel, pos, jnp.zeros_like(pos)),
                   axis=1, keepdims=True)


def _copy_smem(dst, src, n: int):
    """Copy the first ``n`` slots of an SMEM ref (seeding a resident
    output from its aliased input)."""
    def body(i, carry):
        dst[i] = src[i]
        return carry
    lax.fori_loop(0, n, body, 0)


def _block_copy(srcs, dsts, sem):
    """Copy each ``srcs[i]`` into ``dsts[i]`` (HBM <-> VMEM) and wait."""
    copies = [pltpu.make_async_copy(a, b, sem.at[i])
              for i, (a, b) in enumerate(zip(srcs, dsts))]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def _sync_state_specs(dpad, block_n, nb, dtype, batched):
    """(specs of pos/vel/pbest_pos/pbest_fit, scratch, dma) for the
    synchronous kernels: HBM refs plus VMEM block scratch and DMA
    semaphores when a swarm spans several blocks, else one resident window
    per swarm (see ``_make_sync_kernel``)."""
    if nb > 1:
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((dpad, block_n), dtype),
                   pltpu.VMEM((dpad, block_n), dtype),
                   pltpu.VMEM((dpad, block_n), dtype),
                   pltpu.VMEM((1, block_n), dtype),
                   pltpu.SemaphoreType.DMA((4,))]
        return [hbm] * 4, scratch, True
    if batched:
        mat = pl.BlockSpec((None, dpad, block_n), lambda s, *_: (s, 0, 0))
        row = pl.BlockSpec((None, 1, block_n), lambda s, *_: (s, 0, 0))
    else:
        mat = pl.BlockSpec((dpad, block_n), lambda *_: (0, 0))
        row = pl.BlockSpec((1, block_n), lambda *_: (0, 0))
    return [mat, mat, mat, row], [], False


# --------------------------------------------------------------------------
# THE synchronous scaffold: one generator, four kernel bodies.
# --------------------------------------------------------------------------

def _make_sync_kernel(*, queue=False, batched=False, hetero=False,
                      telemetry=False):
    """Generate a synchronous kernel body from the shared scaffold.

    One advance + pbest fold + publication per grid step. Modes:

    * ``queue``   — kernel 1: gbest is a read-only input; publication is an
      unconditional per-block ``(aux_fit, aux_idx)`` pair (the cross-block
      argmax is ops.py's tiny jnp epilogue — the paper's "2nd kernel").
    * default     — kernel 2 (fused queue-lock): in-place predicated
      publication under sequential-grid serialization (the lock).
    * ``batched`` — kernel 3: leading swarm grid axis with per-swarm RNG
      counters and gbest slots; row s is bit-identical to a standalone
      kernel-2 run.
    * ``hetero``  — kernel 3h: per-swarm objective via ``lax.switch`` over
      branch-static member configs (``statics`` is the
      ``_hetero_branches`` tuple, not a ``lower_statics`` dict); the
      scalar switch index makes this a real conditional — one branch
      executes per grid step.

    The returned body is specialized by the call builders via
    ``functools.partial`` with the static kwargs
    ``(w, c1, c2, d_real, rule, statics)``; ``rule`` is the resolved
    :class:`repro.core.update_rules.UpdateRule` every variant closes over.

    ``telemetry`` appends one aliased int32 SMEM counter buffer (3 slots
    per swarm: queue updates / publications / block improvements — see
    ``repro.telemetry.counters``) and accumulates into it per grid step.
    The gate is Python-level, so a telemetry-off body traces to exactly
    the pre-telemetry jaxpr.

    State traffic (``dma``, chosen by the builders from the block count):
    with several blocks per swarm the particle state stays in HBM
    (``pl.ANY``) and each grid step copies its block into VMEM scratch and
    back, waiting on both copies — a block is revisited every iteration,
    and automatic pipelining would prefetch that revisit from HBM before
    the previous write-back of the same block had landed. With one block
    per swarm the block is a resident output window instead, seeded from
    its input at the swarm's first iteration (a DMA slice of a swarm
    narrower than one 128-lane tile would be refused). The gbest buffers
    and counters are resident outputs too, seeded on first visit: Mosaic
    does not load an output block from HBM, aliased or not.
    """
    if queue and telemetry:
        raise ValueError("the two-kernel queue variant publishes via the "
                         "jnp epilogue; count there, not in-kernel")

    def kernel(*refs, w, c1, c2, d_real, rule, statics, dma):
        # --- operands: scalar prefix, aliased state inputs, consts,
        # counts input, outputs, then the VMEM block scratch + semaphores
        npre = 3 if (queue or hetero) else 2 if batched else 1
        nstate = 4 if queue else 6
        ins = refs[npre:npre + nstate]
        k = npre + nstate
        nc = 0 if hetero else statics["n_consts"]
        const_vals = tuple(r[...] for r in refs[k:k + nc])
        k += nc
        cnt_in = refs[k] if telemetry else None
        k += int(telemetry)
        if dma:
            outs = refs[k:-5]
            pos_ref, vel_ref, pbp_ref, pbf_ref, sem = refs[-5:]
        else:
            outs = refs[k:]
            pos_ref, vel_ref, pbp_ref, pbf_ref = outs[:4]
        if queue:
            scal_ref, gp_ref, gf_in_ref = refs[:3]
            aux_fit_ref, aux_idx_ref = outs[4:6]
        else:
            gp_ref, gf_ref = outs[4:6]
            gp_in, gf_in = ins[4:6]
            cnt_ref = outs[6] if telemetry else None
        if hetero:
            seeds_ref, its_ref, fids_ref = refs[:3]
            branches = statics
        elif batched:
            seeds_ref, its_ref = refs[:2]
        elif not queue:
            scal_ref = refs[0]
        if not hetero:
            min_pos, max_pos, max_v, fitness, proj, viol = \
                _resolve_statics(statics, const_vals)
        # --- grid coordinates and RNG counters
        if batched or hetero:
            s = pl.program_id(0)
            t = pl.program_id(1)
            b = pl.program_id(2)
            seed = seeds_ref[s]
            it = its_ref[s] + t + 1
            slot = s
        elif queue:
            b = pl.program_id(0)
            seed = scal_ref[0]
            it = scal_ref[1] + 1
            slot = 0
        else:
            t = pl.program_id(0)
            b = pl.program_id(1)
            seed = scal_ref[0]
            it = scal_ref[1] + t + 1
            slot = 0
        bn = pos_ref.shape[1]
        base = b * bn      # block base LOCAL to the swarm: RNG indices
                           # match a standalone swarm bit-for-bit
        if not queue:
            first = (t == 0) & (b == 0)     # this swarm's first grid step

            @pl.when(first)
            def _seed_gbest_column():
                gp_ref[...] = gp_in[...]

            nslots = gf_in.shape[0]

            @pl.when(first & (slot == 0))   # the kernel's first grid step
            def _seed_smem():
                _copy_smem(gf_ref, gf_in, nslots)
                if telemetry:
                    _copy_smem(cnt_ref, cnt_in, 3 * nslots)

        vmem = (pos_ref, vel_ref, pbp_ref, pbf_ref)
        if dma:        # outs[:4] are the aliased HBM state, updated in place
            views = [h.at[s] if (batched or hetero) else h for h in outs[:4]]
            views = [v.at[:, pl.ds(base, bn)] for v in views]
            _block_copy(views, vmem, sem)
        elif queue:
            for o, i in zip(vmem, ins[:4]):
                o[...] = i[...]
        else:
            @pl.when(t == 0)
            def _seed_state():
                for o, i in zip(vmem, ins[:4]):
                    o[...] = i[...]
        # --- advance + objective
        if hetero:
            def mk(st):
                min_pos, max_pos, max_v, fitness, proj, viol = \
                    _resolve_statics(st, ())
                del viol   # hetero tables are unconstrained/penalty-mode

                def branch(op):
                    pos0, vel0, pbp0, gp0 = op
                    pos, vel, dmask, _ = _advance_block(
                        seed, it, pos0, vel0, pbp0, gp0, base,
                        w=w, c1=c1, c2=c2, min_pos=min_pos,
                        max_pos=max_pos, max_v=max_v, d_real=d_real,
                        project=proj, rule=rule)
                    return pos, vel, fitness(pos, dmask, d_real)

                return branch

            pos, vel, fit = lax.switch(
                fids_ref[s], [mk(st) for st in branches],
                (pos_ref[...], vel_ref[...], pbp_ref[...], gp_ref[...]))
            dpad = pos.shape[0]
            dmask = lax.broadcasted_iota(jnp.int32, (dpad, bn), 0) < d_real
            lane = lax.broadcasted_iota(jnp.int32, (dpad, bn), 1)
            viol = None
        else:
            pos, vel, dmask, lane = _advance_block(
                seed, it, pos_ref[...], vel_ref[...], pbp_ref[...],
                gp_ref[...], base, w=w, c1=c1, c2=c2, min_pos=min_pos,
                max_pos=max_pos, max_v=max_v, d_real=d_real, project=proj,
                rule=rule)
            fit = fitness(pos, dmask, d_real)                # [1, bn]
        # --- pbest fold + state writes
        imp = _fold_pbest(fit, pos, pbf_ref, pbp_ref, viol)
        pos_ref[...] = pos
        vel_ref[...] = vel
        if dma:
            _block_copy(vmem, views, sem)
        # --- publication
        if queue:
            # Candidates are lanes improving on the (stale) global best;
            # published as (fit, index) only — §5.3, never the position.
            bf, bidx = _queue_best(fit, gf_in_ref[0])
            aux_fit_ref[0] = bf                          # -inf if empty
            aux_idx_ref[0] = base + bidx
        else:
            # Queue-lock: serialized in-kernel publication (grid order =
            # the lock) behind the rare-improvement predicate (§4.1).
            gf = gf_ref[slot]
            q_mask = fit > gf

            @pl.when(jnp.any(q_mask))
            def _publish():
                bf, bidx = _queue_best(fit, gf)
                gf_ref[slot] = bf
                gp_ref[...] = _gather_winner(pos, dmask, lane, bidx)

            if telemetry:
                # One conditional guards both the queue fold and the
                # publication here, so queue_updates == publications by
                # construction (docs/observability.md) — matching the
                # oracle's single ``if any(q_mask)`` program point.
                inc = jnp.any(q_mask).astype(jnp.int32)
                c0 = 3 * slot
                cnt_ref[c0] = cnt_ref[c0] + inc
                cnt_ref[c0 + 1] = cnt_ref[c0 + 1] + inc
                cnt_ref[c0 + 2] = (cnt_ref[c0 + 2]
                                   + jnp.any(imp).astype(jnp.int32))

    kernel.__name__ = ("_queue_kernel" if queue else
                       "_hetero_fused_batch_kernel" if hetero else
                       "_fused_batch_kernel" if batched else "_fused_kernel")
    if telemetry:
        kernel.__name__ += "_tel"
    return kernel


# The four synchronous kernel bodies: thin instantiations of the scaffold,
# plus the telemetry (counter-carrying) twins of the three fused ones.
_queue_kernel = _make_sync_kernel(queue=True)
_fused_kernel = _make_sync_kernel()
_fused_batch_kernel = _make_sync_kernel(batched=True)
_hetero_fused_batch_kernel = _make_sync_kernel(batched=True, hetero=True)
_fused_kernel_tel = _make_sync_kernel(telemetry=True)
_fused_batch_kernel_tel = _make_sync_kernel(batched=True, telemetry=True)
_hetero_fused_batch_kernel_tel = _make_sync_kernel(batched=True, hetero=True,
                                                   telemetry=True)


# --------------------------------------------------------------------------
# Kernel 1: queue algorithm — one iteration, grid over particle blocks.
# --------------------------------------------------------------------------

def queue_step_call(n: int, d: int, block_n: int, dtype, *,
                    w, c1, c2, min_pos, max_pos, max_v, fitness,
                    rule="pso", interpret=None):
    """Build the pallas_call for one queue iteration.

    Args (runtime): scal[2]i32, gbest_pos[Dpad,1], gbest_fit[1],
                    pos/vel/pbest_pos [Dpad,N], pbest_fit [1,N]
    Returns: (pos, vel, pbest_pos, pbest_fit, aux_fit[nb], aux_idx[nb])
    """
    assert n % block_n == 0, (n, block_n)
    interpret = _interpret(interpret)
    nb = n // block_n
    dpad = pad_dim(d)
    st, consts = lower_statics(fitness, d=d, dpad=dpad, bn=block_n,
                               dtype=dtype, min_pos=min_pos,
                               max_pos=max_pos, max_v=max_v)
    kern = functools.partial(_queue_kernel, w=w, c1=c1, c2=c2, d_real=d,
                             rule=_kernel_rule(rule), statics=st)
    state, scratch, dma = _sync_state_specs(dpad, block_n, nb, dtype, False)
    kern = functools.partial(kern, dma=dma)
    call = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # scal
            pl.BlockSpec((dpad, 1), lambda b: (0, 0)),        # gbest_pos
            pl.BlockSpec(memory_space=pltpu.SMEM),            # gbest_fit
        ] + state + _const_specs(consts),
        out_specs=state + [
            pl.BlockSpec((1,), lambda b: (b,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1,), lambda b: (b,), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dpad, n), dtype),           # pos
            jax.ShapeDtypeStruct((dpad, n), dtype),           # vel
            jax.ShapeDtypeStruct((dpad, n), dtype),           # pbest_pos
            jax.ShapeDtypeStruct((1, n), dtype),              # pbest_fit
            jax.ShapeDtypeStruct((nb,), dtype),               # aux_fit
            jax.ShapeDtypeStruct((nb,), jnp.int32),           # aux_idx
        ],
        input_output_aliases={3: 0, 4: 1, 5: 2, 6: 3},
        scratch_shapes=scratch,
        interpret=interpret,
        name="cupso_queue_step",
    )
    return lambda *args: call(*args, *consts)


# --------------------------------------------------------------------------
# Kernel 2: fused queue-lock — grid (iterations, particle blocks).
# --------------------------------------------------------------------------

def fused_call(n: int, d: int, iters: int, block_n: int, dtype, *,
               w, c1, c2, min_pos, max_pos, max_v, fitness,
               rule="pso", interpret=None, telemetry=False):
    """Build the fused multi-iteration queue-lock pallas_call.

    Args (runtime): scal[2]i32, pos/vel/pbest_pos [Dpad,N], pbest_fit [1,N],
                    gbest_pos [Dpad,1], gbest_fit [1]
    Returns the same six state arrays after ``iters`` iterations.
    ``telemetry`` appends an aliased counts[3]i32 operand (last arg, last
    result) accumulating the contention counters — see repro.telemetry.
    """
    assert n % block_n == 0, (n, block_n)
    interpret = _interpret(interpret)
    nb = n // block_n
    dpad = pad_dim(d)
    st, consts = lower_statics(fitness, d=d, dpad=dpad, bn=block_n,
                               dtype=dtype, min_pos=min_pos,
                               max_pos=max_pos, max_v=max_v)
    body = _fused_kernel_tel if telemetry else _fused_kernel
    kern = functools.partial(body, w=w, c1=c1, c2=c2, d_real=d,
                             rule=_kernel_rule(rule), statics=st)
    state, scratch, dma = _sync_state_specs(dpad, block_n, nb, dtype, False)
    kern = functools.partial(kern, dma=dma)
    gpc = pl.BlockSpec((dpad, 1), lambda t, b: (0, 0))
    gfs = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),        # scal
                *state, gpc, gfs] + _const_specs(consts)
    out_specs = [*state, gpc, gfs]
    out_shape = [
        jax.ShapeDtypeStruct((dpad, n), dtype),               # pos
        jax.ShapeDtypeStruct((dpad, n), dtype),               # vel
        jax.ShapeDtypeStruct((dpad, n), dtype),               # pbest_pos
        jax.ShapeDtypeStruct((1, n), dtype),                  # pbest_fit
        jax.ShapeDtypeStruct((dpad, 1), dtype),               # gbest_pos
        jax.ShapeDtypeStruct((1,), dtype),                    # gbest_fit
    ]
    aliases = {1: 0, 2: 1, 3: 2, 4: 3, 5: 4, 6: 5}
    if telemetry:
        in_specs.append(gfs)                                  # counts in
        out_specs.append(gfs)
        out_shape.append(jax.ShapeDtypeStruct((3,), jnp.int32))
        aliases[7 + len(consts)] = 6
    grid, name = (iters, nb), "cupso_fused_queue_lock"
    record_sync(name, grid, swarms=1, streamed=dma, dpad=dpad,
                block_n=block_n, dtype=dtype)
    call = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        scratch_shapes=scratch,
        compiler_params=_arbitrary(2),
        interpret=interpret,
        name=name,
    )
    if telemetry:
        # counts is the caller's LAST positional arg; consts slot in
        # before it to keep the kernel's operand order (consts then cnt).
        return lambda *args: call(*args[:-1], *consts, args[-1])
    return lambda *args: call(*args, *consts)


# --------------------------------------------------------------------------
# Kernel 3: batched fused queue-lock — grid (swarms, iterations, blocks).
# --------------------------------------------------------------------------

def fused_batch_call(s_cnt: int, n: int, d: int, iters: int, block_n: int,
                     dtype, *, w, c1, c2, min_pos, max_pos, max_v, fitness,
                     rule="pso", interpret=None, telemetry=False):
    """Build the batched fused queue-lock pallas_call (S swarms x iters).

    Args (runtime): seeds[S]i32, iterations[S]i32,
                    pos/vel/pbest_pos [S, Dpad, N], pbest_fit [S, 1, N],
                    gbest_pos [S, Dpad, 1], gbest_fit [S]
    Returns the same six state arrays after ``iters`` iterations of every
    swarm. Swarm-major grid: the per-swarm gbest column and SMEM fitness
    slot are revisited only within one swarm's iteration span.
    ``telemetry`` appends an aliased counts[3*S]i32 operand (per-swarm
    contention counters — see repro.telemetry).
    """
    assert n % block_n == 0, (n, block_n)
    interpret = _interpret(interpret)
    nb = n // block_n
    dpad = pad_dim(d)
    st, consts = lower_statics(fitness, d=d, dpad=dpad, bn=block_n,
                               dtype=dtype, min_pos=min_pos,
                               max_pos=max_pos, max_v=max_v)
    body = _fused_batch_kernel_tel if telemetry else _fused_batch_kernel
    kern = functools.partial(body, w=w, c1=c1, c2=c2,
                             d_real=d, rule=_kernel_rule(rule), statics=st)
    state, scratch, dma = _sync_state_specs(dpad, block_n, nb, dtype, True)
    kern = functools.partial(kern, dma=dma)
    gpc = pl.BlockSpec((None, dpad, 1), lambda s, t, b: (s, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem, smem,                                   # seeds, iters
                *state, gpc, smem] + _const_specs(consts)
    out_specs = [*state, gpc, smem]
    out_shape = [
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # pos
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # vel
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # pbest_pos
        jax.ShapeDtypeStruct((s_cnt, 1, n), dtype),           # pbest_fit
        jax.ShapeDtypeStruct((s_cnt, dpad, 1), dtype),        # gbest_pos
        jax.ShapeDtypeStruct((s_cnt,), dtype),                # gbest_fit
    ]
    aliases = {2: 0, 3: 1, 4: 2, 5: 3, 6: 4, 7: 5}
    if telemetry:
        in_specs.append(smem)                                 # counts in
        out_specs.append(smem)
        out_shape.append(jax.ShapeDtypeStruct((3 * s_cnt,), jnp.int32))
        aliases[8 + len(consts)] = 6
    grid, name = (s_cnt, iters, nb), "cupso_fused_queue_lock_batch"
    record_sync(name, grid, swarms=s_cnt, streamed=dma, dpad=dpad,
                block_n=block_n, dtype=dtype)
    call = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        scratch_shapes=scratch,
        compiler_params=_arbitrary(3),
        interpret=interpret,
        name=name,
    )
    if telemetry:
        return lambda *args: call(*args[:-1], *consts, args[-1])
    return lambda *args: call(*args, *consts)


# --------------------------------------------------------------------------
# Kernel 3h: heterogeneous batched fused queue-lock — per-swarm objective.
#
# Same grid and orchestration as kernel 3, plus a per-swarm ``fid`` scalar
# (SMEM) indexing a static problem table. The advance + objective go through
# ``lax.switch`` with each branch closing over its member's *static* bounds
# and hand-tuned fitness — exactly the subgraph kernel 3 would compile for a
# homogeneous batch of that problem. The switch index is a scalar (one
# swarm per grid step), so this is a real conditional: one branch executes
# per grid step and a mixed batch does NOT pay a compute-all-branches
# ``select_n`` the way the vmapped jnp engine does. The pbest fold and the
# queue-lock publication are objective-independent and stay outside the
# switch. Table members must lower const-free (the built-in registry does;
# ``lower_statics`` consts would need per-branch operand plumbing).
# --------------------------------------------------------------------------

def _hetero_branches(members, *, d, dpad, bn, dtype):
    """Per-member kernel statics for a hetero dispatch table.

    ``members`` is a tuple of ``(fitness, min_pos, max_pos, max_v)``; each
    must lower without const operands and without a feasibility projection
    (``problem_rows`` rejects projection/repair members before this).
    """
    branches = []
    for fitness, mn, mx, mv in members:
        st, consts = lower_statics(fitness, d=d, dpad=dpad, bn=bn,
                                   dtype=dtype, min_pos=mn, max_pos=mx,
                                   max_v=mv)
        if consts:
            raise ValueError(
                "heterogeneous kernel dispatch requires const-free table "
                "members (array-closing objectives need their own batch)")
        branches.append(st)
    return tuple(branches)


def hetero_fused_batch_call(s_cnt: int, n: int, d: int, iters: int,
                            block_n: int, dtype, *, w, c1, c2, members,
                            rule="pso", interpret=None, telemetry=False):
    """Batched fused queue-lock with a per-swarm problem (kernel 3h).

    Args (runtime): seeds[S]i32, iterations[S]i32, fids[S]i32, then the six
    state arrays of ``fused_batch_call``. ``members[k]`` is the static
    ``(fitness, min_pos, max_pos, max_v)`` branch ``fids == k`` dispatches
    to. ``telemetry`` appends an aliased counts[3*S]i32 operand.
    """
    assert n % block_n == 0, (n, block_n)
    interpret = _interpret(interpret)
    nb = n // block_n
    dpad = pad_dim(d)
    branches = _hetero_branches(members, d=d, dpad=dpad, bn=block_n,
                                dtype=dtype)
    body = (_hetero_fused_batch_kernel_tel if telemetry
            else _hetero_fused_batch_kernel)
    kern = functools.partial(body, w=w, c1=c1, c2=c2,
                             d_real=d, rule=_kernel_rule(rule),
                             statics=branches)
    state, scratch, dma = _sync_state_specs(dpad, block_n, nb, dtype, True)
    kern = functools.partial(kern, dma=dma)
    gpc = pl.BlockSpec((None, dpad, 1), lambda s, t, b: (s, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem, smem, smem,                      # seeds, iters, fids
                *state, gpc, smem]
    out_specs = [*state, gpc, smem]
    out_shape = [
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # pos
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # vel
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # pbest_pos
        jax.ShapeDtypeStruct((s_cnt, 1, n), dtype),           # pbest_fit
        jax.ShapeDtypeStruct((s_cnt, dpad, 1), dtype),        # gbest_pos
        jax.ShapeDtypeStruct((s_cnt,), dtype),                # gbest_fit
    ]
    aliases = {3: 0, 4: 1, 5: 2, 6: 3, 7: 4, 8: 5}
    if telemetry:
        in_specs.append(smem)                                 # counts in
        out_specs.append(smem)
        out_shape.append(jax.ShapeDtypeStruct((3 * s_cnt,), jnp.int32))
        aliases[9] = 6
    grid, name = (s_cnt, iters, nb), "cupso_hetero_fused_queue_lock_batch"
    record_sync(name, grid, swarms=s_cnt, streamed=dma, dpad=dpad,
                block_n=block_n, dtype=dtype)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        scratch_shapes=scratch,
        compiler_params=_arbitrary(3),
        interpret=interpret,
        name=name,
    )


# --------------------------------------------------------------------------
# Kernel 4: async queue-lock — grid (blocks, iteration chunks), block-major.
# --------------------------------------------------------------------------

def _async_chunk_body(scal0, it_base, sync_every, base,
                      pos, vel, pbp, pbf, lp, lf, *,
                      w, c1, c2, min_pos, max_pos, max_v, d_real, fitness,
                      project=None, viol=None, rule=None,
                      counts=False):
    """``sync_every`` iterations of one block against its block-local best.

    Pure value-level fori_loop (no ref writes inside the loop) shared by
    the single and batched async kernels. The local-best update applies
    exactly the fused kernel's publication rule (masked max, first-lane
    tie-break, masked-sum position gather), but into the loop carry instead
    of the shared SMEM/VMEM gbest buffers — so with a single block the
    trajectory is bit-identical to the synchronous fused kernel.

    ``counts`` (telemetry) extends the carry with two scalar int32 event
    counters — iterations where the local queue was non-empty, and
    iterations where any lane improved its pbest — returned as trailing
    elements for the scaffold to fold into the counter buffer.
    """
    def body(tl, carry):
        if counts:
            pos, vel, pbp, pbf, lp, lf, nq, nimp = carry
        else:
            pos, vel, pbp, pbf, lp, lf = carry
        pos, vel, dmask, lane = _advance_block(
            scal0, it_base + tl + 1, pos, vel, pbp, lp, base,
            w=w, c1=c1, c2=c2, min_pos=min_pos, max_pos=max_pos,
            max_v=max_v, d_real=d_real, project=project, rule=rule)
        fit = fitness(pos, dmask, d_real)
        imp = _pbest_improved(fit, pos, pbf, pbp, viol)
        pbf = jnp.where(imp, fit, pbf)
        pbp = jnp.where(imp, pos, pbp)
        # Block-local queue: same rule as the fused kernel's _publish, as
        # unconditional where-folds (a fori carry cannot be predicated).
        bf, bidx = _queue_best(fit, lf)        # bf == -inf when queue empty
        cand = _gather_winner(pos, dmask, lane, bidx)
        anyq = bf > lf                         # == jnp.any(fit > lf)
        lf = jnp.where(anyq, bf, lf)
        lp = jnp.where(anyq, cand, lp)
        if counts:
            nq = nq + anyq.astype(jnp.int32)
            nimp = nimp + jnp.any(imp).astype(jnp.int32)
            return pos, vel, pbp, pbf, lp, lf, nq, nimp
        return pos, vel, pbp, pbf, lp, lf

    init = (pos, vel, pbp, pbf, lp, lf)
    if counts:
        zero = jnp.zeros((), jnp.int32)
        init = init + (zero, zero)
    return lax.fori_loop(0, sync_every, body, init)


# --------------------------------------------------------------------------
# THE asynchronous scaffold: one generator, three kernel bodies.
# --------------------------------------------------------------------------

def _make_async_kernel(*, batched=False, hetero=False, telemetry=False):
    """Generate an asynchronous (block-resident) kernel body from the
    shared scaffold.

    Each grid step runs one ``sync_every``-iteration chunk of one particle
    block against its block-local best (``_async_chunk_body``), touching
    the shared buffers only at the chunk boundary: a local-best refresh on
    entry (the read half of the paper's lock) and a predicated publish on
    exit. Modes mirror ``_make_sync_kernel``: ``batched`` adds the leading
    swarm axis (per-swarm gbest slots, per-(swarm, block) local slots);
    ``hetero`` dispatches the whole chunk body through ``lax.switch``
    (``statics`` is the ``_hetero_branches`` tuple).

    ``topology`` selects the chunk-entry refresh source (see
    ``repro.core.topology``): ``"gbest"`` pulls the shared gbest — the
    paper's star, compiled exactly as before — while ``"ring"`` /
    ``"vonneumann"`` fold the NEIGHBOR blocks' local slots instead
    (``kernel_neighbor_ids``; ``lp_ref`` is whole-array blocked in this
    mode and neighbor columns are read by a dynamic index on its leading,
    untiled axis — never along lanes), so swarm knowledge diffuses
    hop by hop while the shared gbest remains a monitoring/final-answer
    flush target only.

    ``telemetry`` mirrors ``_make_sync_kernel``: an aliased int32 SMEM
    counter buffer rides as the last operand, accumulating the chunk's
    local-queue updates and pbest improvements plus the chunk-exit
    publication, per swarm. Python-gated — off means the untouched jaxpr.
    """
    def kernel(*refs, nb, sync_every, w, c1, c2, d_real, rule, topology,
               statics):
        # --- operands: scalar prefix, aliased state inputs, consts,
        # counts input, then the outputs
        npre = 3 if hetero else 2 if batched else 1
        ins = refs[npre:npre + 8]
        k = npre + 8
        nc = 0 if hetero else statics["n_consts"]
        const_vals = tuple(r[...] for r in refs[k:k + nc])
        k += nc
        cnt_in = refs[k] if telemetry else None
        k += int(telemetry)
        (pos_ref, vel_ref, pbp_ref, pbf_ref, gp_ref, gf_ref,
         lp_ref, lf_ref) = refs[k:k + 8]
        cnt_ref = refs[k + 8] if telemetry else None
        if hetero:
            seeds_ref, its_ref, fids_ref = refs[:3]
            branches = statics
        elif batched:
            seeds_ref, its_ref = refs[:2]
        else:
            scal_ref = refs[0]
        if not hetero:
            min_pos, max_pos, max_v, fitness, proj, viol = \
                _resolve_statics(statics, const_vals)
        # --- grid coordinates, RNG counters, local/global slots
        if batched or hetero:
            s = pl.program_id(0)
            b = pl.program_id(1)
            c = pl.program_id(2)
            seed = seeds_ref[s]
            it0 = its_ref[s] + c * sync_every
            gslot = s
            slot = s * nb + b      # per-(swarm, block) local-best slot
        else:
            b = pl.program_id(0)
            c = pl.program_id(1)
            seed = scal_ref[0]
            it0 = scal_ref[1] + c * sync_every
            gslot = 0
            slot = b
        bn = pos_ref.shape[1]
        base = b * bn      # swarm-local: RNG matches a standalone run
        # --- first visits: seed the resident outputs from their aliased
        # inputs (Mosaic does not load an output block from HBM). A block's
        # state stays resident for all its chunks, so it is read once.
        _seed_async_outputs(ins, (pos_ref, vel_ref, pbp_ref, pbf_ref, gp_ref,
                                  gf_ref, lp_ref, lf_ref),
                            cnt_in, cnt_ref, b=b, c=c, slot=slot,
                            swarm=gslot,
                            lbest=topology != "gbest")
        # --- chunk entry: refresh the block-local best (the read half of
        # the paper's lock).
        lf = lf_ref[slot]
        if topology == "gbest":
            # Star: pull the shared gbest. A no-op for the first grid
            # block and for nb == 1; later blocks inherit everything
            # earlier blocks published.
            lp = lp_ref[...]
            gf0 = gf_ref[gslot]
            pull = gf0 > lf
            lf = jnp.where(pull, gf0, lf)
            lp = jnp.where(pull, gp_ref[...], lp)
        else:
            # lbest: fold the neighbor blocks' local slots instead — the
            # shared gbest is never read back, so swarm knowledge diffuses
            # hop by hop (classic lbest dynamics at block granularity).
            lp = lp_ref[slot]
            for nbr in kernel_neighbor_ids(b, nb, topology):
                nslot = s * nb + nbr if (batched or hetero) else nbr
                nf = lf_ref[nslot]
                take = nf > lf
                lf = jnp.where(take, nf, lf)
                lp = jnp.where(take, lp_ref[nslot], lp)
        # --- the resident chunk: sync_every iterations vs the local best
        if hetero:
            def mk(st):
                min_pos, max_pos, max_v, fitness, proj, viol = \
                    _resolve_statics(st, ())
                del viol   # hetero tables are unconstrained/penalty-mode

                def branch(op):
                    pos, vel, pbp, pbf, lp_, lf_ = op
                    return _async_chunk_body(
                        seed, it0, sync_every, base, pos, vel, pbp, pbf,
                        lp_, lf_, w=w, c1=c1, c2=c2, min_pos=min_pos,
                        max_pos=max_pos, max_v=max_v, d_real=d_real,
                        fitness=fitness, project=proj, viol=None,
                        rule=rule, counts=telemetry)

                return branch

            out = lax.switch(
                fids_ref[s], [mk(st) for st in branches],
                (pos_ref[...], vel_ref[...], pbp_ref[...], pbf_ref[...],
                 lp, lf))
        else:
            out = _async_chunk_body(
                seed, it0, sync_every, base,
                pos_ref[...], vel_ref[...], pbp_ref[...], pbf_ref[...],
                lp, lf, w=w, c1=c1, c2=c2, min_pos=min_pos,
                max_pos=max_pos, max_v=max_v, d_real=d_real,
                fitness=fitness, project=proj, viol=viol,
                rule=rule, counts=telemetry)
        if telemetry:
            pos, vel, pbp, pbf, lp, lf, nq, nimp = out
        else:
            pos, vel, pbp, pbf, lp, lf = out
        pos_ref[...] = pos
        vel_ref[...] = vel
        pbp_ref[...] = pbp
        pbf_ref[...] = pbf
        if topology == "gbest":
            lp_ref[...] = lp
        else:
            lp_ref[slot] = lp
        lf_ref[slot] = lf

        # --- chunk boundary: the ONLY cross-block write, and only on the
        # rare improvement (the paper's occasional lock acquisition). With
        # an lbest topology this is the monitoring/final-answer flush; the
        # entry refresh above never reads it back.
        if telemetry:
            # Fold the chunk's event counts before the publish mutates
            # gf_ref: publications counts shared-slot writes (the lock
            # acquisitions), queue_updates the block-local folds — their
            # ratio is the paper's contention-avoidance story measured.
            c0 = 3 * gslot
            pub = (lf > gf_ref[gslot]).astype(jnp.int32)
            cnt_ref[c0] = cnt_ref[c0] + nq
            cnt_ref[c0 + 1] = cnt_ref[c0 + 1] + pub
            cnt_ref[c0 + 2] = cnt_ref[c0 + 2] + nimp

        @pl.when(lf > gf_ref[gslot])
        def _publish():
            gf_ref[gslot] = lf
            gp_ref[...] = lp

    kernel.__name__ = (
        "_hetero_fused_async_batch_kernel" if hetero else
        "_fused_async_batch_kernel" if batched else "_fused_async_kernel")
    if telemetry:
        kernel.__name__ += "_tel"
    return kernel


def _seed_async_outputs(ins, outs, cnt_in, cnt_ref, *, b, c, slot, swarm,
                        lbest):
    """Seed the async kernels' resident outputs on first visit: the block's
    state and local slot at its first chunk, the swarm's gbest column at
    its first block, and the whole SMEM buffers (plus, under an lbest
    topology, the whole local-best array) at the kernel's first step."""
    (pos_in, vel_in, pbp_in, pbf_in, gp_in, gf_in, lp_in, lf_in) = ins
    (pos_ref, vel_ref, pbp_ref, pbf_ref, gp_ref, gf_ref, lp_ref,
     lf_ref) = outs
    first_block_step = c == 0
    first_swarm_step = first_block_step & (b == 0)

    @pl.when(first_block_step)
    def _state():
        pos_ref[...] = pos_in[...]
        vel_ref[...] = vel_in[...]
        pbp_ref[...] = pbp_in[...]
        pbf_ref[...] = pbf_in[...]
        if not lbest:
            lp_ref[...] = lp_in[...]
            lf_ref[slot] = lf_in[slot]

    @pl.when(first_swarm_step)
    def _gbest_column():
        gp_ref[...] = gp_in[...]

    @pl.when(first_swarm_step & (swarm == 0))
    def _whole():
        nslots = gf_in.shape[0]
        _copy_smem(gf_ref, gf_in, nslots)
        if cnt_ref is not None:
            _copy_smem(cnt_ref, cnt_in, 3 * nslots)
        if lbest:
            lp_ref[...] = lp_in[...]
            _copy_smem(lf_ref, lf_in, lf_in.shape[0])


# The three asynchronous kernel bodies: instantiations of the scaffold,
# plus their telemetry (counter-carrying) twins.
_fused_async_kernel = _make_async_kernel()
_fused_async_batch_kernel = _make_async_kernel(batched=True)
_hetero_fused_async_batch_kernel = _make_async_kernel(batched=True,
                                                      hetero=True)
_fused_async_kernel_tel = _make_async_kernel(telemetry=True)
_fused_async_batch_kernel_tel = _make_async_kernel(batched=True,
                                                   telemetry=True)
_hetero_fused_async_batch_kernel_tel = _make_async_kernel(
    batched=True, hetero=True, telemetry=True)


def _async_local_spec(topology, dpad, nb_total, index_map_own):
    """BlockSpec for the ``[nb_total, Dpad, 1]`` ``local_pos`` buffer: the
    block's own column (leading axis squeezed) under the star topology, or
    the whole array under an lbest topology so neighbor columns are
    addressable by a dynamic leading-axis index."""
    if topology == "gbest":
        return pl.BlockSpec((None, dpad, 1), index_map_own)
    return pl.BlockSpec((nb_total, dpad, 1), lambda *g: (0, 0, 0))


def fused_async_call(n: int, d: int, iters: int, block_n: int,
                     sync_every: int, dtype, *, w, c1, c2, min_pos, max_pos,
                     max_v, fitness, rule="pso", topology="gbest",
                     interpret=None, telemetry=False):
    """Build the asynchronous queue-lock pallas_call (grid (blocks, chunks)).

    Args (runtime): scal[2]i32, pos/vel/pbest_pos [Dpad,N], pbest_fit [1,N],
                    gbest_pos [Dpad,1], gbest_fit [1],
                    local_pos [nb,Dpad,1], local_fit [nb]
    Returns the same eight state arrays after ``iters`` iterations. The
    caller seeds local_pos/local_fit from the shared gbest (one column/slot
    per block); ``iters`` must be a multiple of ``sync_every`` (the ops
    wrapper splits a remainder into a second call). ``telemetry`` appends
    an aliased counts[3]i32 operand — see repro.telemetry.
    """
    assert n % block_n == 0, (n, block_n)
    interpret = _interpret(interpret)
    assert iters % sync_every == 0, (iters, sync_every)
    nb = n // block_n
    chunks = iters // sync_every
    dpad = pad_dim(d)
    st, consts = lower_statics(fitness, d=d, dpad=dpad, bn=block_n,
                               dtype=dtype, min_pos=min_pos,
                               max_pos=max_pos, max_v=max_v)
    body = _fused_async_kernel_tel if telemetry else _fused_async_kernel
    kern = functools.partial(body, nb=nb,
                             sync_every=sync_every, w=w, c1=c1, c2=c2,
                             d_real=d, rule=_kernel_rule(rule),
                             topology=topology, statics=st)
    mat = pl.BlockSpec((dpad, block_n), lambda b, c: (0, b))
    row = pl.BlockSpec((1, block_n), lambda b, c: (0, b))
    gpc = pl.BlockSpec((dpad, 1), lambda b, c: (0, 0))
    lpc = _async_local_spec(topology, dpad, nb, lambda b, c: (b, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem,                                         # scal
                mat, mat, mat, row, gpc, smem, lpc, smem] \
        + _const_specs(consts)
    out_specs = [mat, mat, mat, row, gpc, smem, lpc, smem]
    out_shape = [
        jax.ShapeDtypeStruct((dpad, n), dtype),               # pos
        jax.ShapeDtypeStruct((dpad, n), dtype),               # vel
        jax.ShapeDtypeStruct((dpad, n), dtype),               # pbest_pos
        jax.ShapeDtypeStruct((1, n), dtype),                  # pbest_fit
        jax.ShapeDtypeStruct((dpad, 1), dtype),               # gbest_pos
        jax.ShapeDtypeStruct((1,), dtype),                    # gbest_fit
        jax.ShapeDtypeStruct((nb, dpad, 1), dtype),           # local_pos
        jax.ShapeDtypeStruct((nb,), dtype),                   # local_fit
    ]
    aliases = {1: 0, 2: 1, 3: 2, 4: 3, 5: 4, 6: 5, 7: 6, 8: 7}
    if telemetry:
        in_specs.append(smem)                                 # counts in
        out_specs.append(smem)
        out_shape.append(jax.ShapeDtypeStruct((3,), jnp.int32))
        aliases[9 + len(consts)] = 8
    call = pl.pallas_call(
        kern,
        grid=(nb, chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=_arbitrary(2),
        interpret=interpret,
        name="cupso_fused_queue_lock_async",
    )
    if telemetry:
        return lambda *args: call(*args[:-1], *consts, args[-1])
    return lambda *args: call(*args, *consts)


def fused_async_batch_call(s_cnt: int, n: int, d: int, iters: int,
                           block_n: int, sync_every: int, dtype, *,
                           w, c1, c2, min_pos, max_pos, max_v, fitness,
                           rule="pso", topology="gbest", interpret=None,
                           telemetry=False):
    """Batched async queue-lock: grid (swarms, blocks, chunks).

    Args (runtime): seeds[S]i32, iterations[S]i32,
                    pos/vel/pbest_pos [S, Dpad, N], pbest_fit [S, 1, N],
                    gbest_pos [S, Dpad, 1], gbest_fit [S],
                    local_pos [S*nb, Dpad, 1], local_fit [S*nb]
    Swarm-major then block-major: swarm s's block b runs its whole iteration
    span while resident, exactly like a standalone ``fused_async_call`` —
    row s is bit-identical to the single-swarm async kernel. ``telemetry``
    appends an aliased counts[3*S]i32 operand.
    """
    assert n % block_n == 0, (n, block_n)
    interpret = _interpret(interpret)
    assert iters % sync_every == 0, (iters, sync_every)
    nb = n // block_n
    chunks = iters // sync_every
    dpad = pad_dim(d)
    st, consts = lower_statics(fitness, d=d, dpad=dpad, bn=block_n,
                               dtype=dtype, min_pos=min_pos,
                               max_pos=max_pos, max_v=max_v)
    body = (_fused_async_batch_kernel_tel if telemetry
            else _fused_async_batch_kernel)
    kern = functools.partial(body, nb=nb,
                             sync_every=sync_every, w=w, c1=c1, c2=c2,
                             d_real=d, rule=_kernel_rule(rule),
                             topology=topology, statics=st)
    mat = pl.BlockSpec((None, dpad, block_n), lambda s, b, c: (s, 0, b))
    row = pl.BlockSpec((None, 1, block_n), lambda s, b, c: (s, 0, b))
    gpc = pl.BlockSpec((None, dpad, 1), lambda s, b, c: (s, 0, 0))
    lpc = _async_local_spec(topology, dpad, s_cnt * nb,
                            lambda s, b, c: (s * nb + b, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem, smem,                                   # seeds, iters
                mat, mat, mat, row, gpc, smem, lpc, smem] \
        + _const_specs(consts)
    out_specs = [mat, mat, mat, row, gpc, smem, lpc, smem]
    out_shape = [
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # pos
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # vel
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # pbest_pos
        jax.ShapeDtypeStruct((s_cnt, 1, n), dtype),           # pbest_fit
        jax.ShapeDtypeStruct((s_cnt, dpad, 1), dtype),        # gbest_pos
        jax.ShapeDtypeStruct((s_cnt,), dtype),                # gbest_fit
        jax.ShapeDtypeStruct((s_cnt * nb, dpad, 1), dtype),   # local_pos
        jax.ShapeDtypeStruct((s_cnt * nb,), dtype),           # local_fit
    ]
    aliases = {2: 0, 3: 1, 4: 2, 5: 3, 6: 4, 7: 5, 8: 6, 9: 7}
    if telemetry:
        in_specs.append(smem)                                 # counts in
        out_specs.append(smem)
        out_shape.append(jax.ShapeDtypeStruct((3 * s_cnt,), jnp.int32))
        aliases[10 + len(consts)] = 8
    call = pl.pallas_call(
        kern,
        grid=(s_cnt, nb, chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=_arbitrary(3),
        interpret=interpret,
        name="cupso_fused_queue_lock_async_batch",
    )
    if telemetry:
        return lambda *args: call(*args[:-1], *consts, args[-1])
    return lambda *args: call(*args, *consts)


# --------------------------------------------------------------------------
# Kernel 4h: heterogeneous batched async queue-lock — per-swarm objective.
# Kernel 3h's dispatch (scalar per-swarm fid, branch-static member configs,
# one branch executed per grid step) applied to kernel 4's batched grid:
# each branch runs the whole ``sync_every``-iteration chunk body.
# --------------------------------------------------------------------------

def hetero_fused_async_batch_call(s_cnt: int, n: int, d: int, iters: int,
                                  block_n: int, sync_every: int, dtype, *,
                                  w, c1, c2, members, rule="pso",
                                  topology="gbest", interpret=None,
                                  telemetry=False):
    """Batched async queue-lock with a per-swarm problem (kernel 4h).

    Args (runtime): seeds[S]i32, iterations[S]i32, fids[S]i32, then the
    eight state arrays of ``fused_async_batch_call``. ``members`` as in
    ``hetero_fused_batch_call``. ``telemetry`` appends an aliased
    counts[3*S]i32 operand.
    """
    assert n % block_n == 0, (n, block_n)
    interpret = _interpret(interpret)
    assert iters % sync_every == 0, (iters, sync_every)
    nb = n // block_n
    chunks = iters // sync_every
    dpad = pad_dim(d)
    branches = _hetero_branches(members, d=d, dpad=dpad, bn=block_n,
                                dtype=dtype)
    body = (_hetero_fused_async_batch_kernel_tel if telemetry
            else _hetero_fused_async_batch_kernel)
    kern = functools.partial(body, nb=nb,
                             sync_every=sync_every, w=w, c1=c1, c2=c2,
                             d_real=d, rule=_kernel_rule(rule),
                             topology=topology, statics=branches)
    mat = pl.BlockSpec((None, dpad, block_n), lambda s, b, c: (s, 0, b))
    row = pl.BlockSpec((None, 1, block_n), lambda s, b, c: (s, 0, b))
    gpc = pl.BlockSpec((None, dpad, 1), lambda s, b, c: (s, 0, 0))
    lpc = _async_local_spec(topology, dpad, s_cnt * nb,
                            lambda s, b, c: (s * nb + b, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem, smem, smem,                      # seeds, iters, fids
                mat, mat, mat, row, gpc, smem, lpc, smem]
    out_specs = [mat, mat, mat, row, gpc, smem, lpc, smem]
    out_shape = [
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # pos
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # vel
        jax.ShapeDtypeStruct((s_cnt, dpad, n), dtype),        # pbest_pos
        jax.ShapeDtypeStruct((s_cnt, 1, n), dtype),           # pbest_fit
        jax.ShapeDtypeStruct((s_cnt, dpad, 1), dtype),        # gbest_pos
        jax.ShapeDtypeStruct((s_cnt,), dtype),                # gbest_fit
        jax.ShapeDtypeStruct((s_cnt * nb, dpad, 1), dtype),   # local_pos
        jax.ShapeDtypeStruct((s_cnt * nb,), dtype),           # local_fit
    ]
    aliases = {3: 0, 4: 1, 5: 2, 6: 3, 7: 4, 8: 5, 9: 6, 10: 7}
    if telemetry:
        in_specs.append(smem)                                 # counts in
        out_specs.append(smem)
        out_shape.append(jax.ShapeDtypeStruct((3 * s_cnt,), jnp.int32))
        aliases[11] = 8
    return pl.pallas_call(
        kern,
        grid=(s_cnt, nb, chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=_arbitrary(3),
        interpret=interpret,
        name="cupso_hetero_fused_queue_lock_async_batch",
    )
