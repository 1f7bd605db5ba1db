"""Jitted public wrappers around the Pallas cuPSO kernels.

Handles layout packing ([N, D] particle-major library layout ↔ [Dpad, N]
D-major kernel layout), block-size selection, the queue algorithm's tiny
cross-block second stage, and SwarmState plumbing so kernels are drop-in
replacements for the ``repro.core.pso`` step functions.

No wrapper takes an ``interpret`` flag: the builders compile the kernels
with Mosaic on a TPU and run them in the Pallas interpreter on any other
backend (``pso_step.default_interpret``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.blocking import LANE, pick_block_n  # noqa: F401 (re-export:
# pick_block_n is the shared block-sizing helper in repro.core.blocking,
# also used by core.pso._default_async_blocks with lane=1)
from repro.core.multi_swarm import SwarmBatch
from repro.core.pso import (ASYNC_SYNC_EVERY, PSOConfig, SwarmState,
                            hetero_member_config)
from .pso_step import (fused_async_batch_call, fused_async_call,
                       fused_batch_call, fused_call,
                       hetero_fused_async_batch_call,
                       hetero_fused_batch_call, pad_dim,
                       queue_step_call)


def _resolve_block(n: int, block_n: Optional[int]) -> int:
    """Validate an explicit ``block_n`` override (the autotuner and users
    both pass them now) or fall back to the heuristic pick. Every kernel
    wrapper routes through here so a non-dividing override fails loudly at
    the call site instead of as a shape error inside the pallas_call."""
    bn = block_n or pick_block_n(n)
    if bn < 1 or n % bn:
        raise ValueError(
            f"block_n={bn} must be a positive divisor of particle_cnt={n}")
    return bn


def pack_dmajor(pos, d: int):
    """[N, D] -> [Dpad, N] (zero-padded sublanes)."""
    n = pos.shape[0]
    dpad = pad_dim(d)
    out = jnp.zeros((dpad, n), pos.dtype)
    return out.at[:d, :].set(pos.T)


def unpack_dmajor(arr, d: int):
    """[Dpad, N] -> [N, D]."""
    return arr[:d, :].T


def pack_columns(x, d: int):
    """[K, D] -> [K, Dpad, 1]: one zero-padded column per row (per-swarm
    gbests, per-block local bests), the column index on the leading axis."""
    return jnp.zeros((x.shape[0], pad_dim(d), 1), x.dtype).at[:, :d, 0].set(x)


def unpack_columns(arr, d: int):
    """[K, Dpad, 1] -> [K, D]."""
    return arr[:, :d, 0]


def _cfg_kwargs(cfg: PSOConfig):
    """Static kernel parameters from a config. ``fitness`` stays a
    str | Problem (resolved to the d-major callable by the call builders
    via ``pso_step.kernel_fitness``); bounds stay scalars or per-dimension
    tuples (lowered to [Dpad, 1] columns by ``pso_step._advance_block``)."""
    cfg = cfg.resolved()
    return dict(w=cfg.w, c1=cfg.c1, c2=cfg.c2, min_pos=cfg.min_pos,
                max_pos=cfg.max_pos, max_v=cfg.max_v, fitness=cfg.fitness,
                rule=cfg.update_rule)


def state_to_kernel(s: SwarmState, d: int):
    """SwarmState -> packed kernel operands."""
    scal = jnp.stack([s.seed.astype(jnp.int32),
                      s.iteration.astype(jnp.int32)])
    return (scal,
            pack_dmajor(s.pos, d), pack_dmajor(s.vel, d),
            pack_dmajor(s.pbest_pos, d), s.pbest_fit[None, :],
            pack_dmajor(s.gbest_pos[None, :], d), s.gbest_fit[None])


def kernel_to_state(s: SwarmState, d: int, pos, vel, pbp, pbf, gp, gf,
                    iters: int) -> SwarmState:
    return s._replace(
        pos=unpack_dmajor(pos, d), vel=unpack_dmajor(vel, d),
        fit=pbf[0],  # NOTE: kernels do not retain raw fit; pbest_fit ≥ fit
        pbest_pos=unpack_dmajor(pbp, d), pbest_fit=pbf[0],
        gbest_pos=gp[:d, 0], gbest_fit=gf[0],
        iteration=s.iteration + iters,
        # sync kernels invalidate any async block-local cache; the async
        # wrapper re-attaches its (externalized) buffers afterwards
        lbest_pos=None, lbest_fit=None)


@functools.partial(jax.jit, static_argnames=("cfg", "block_n"))
def queue_step(cfg: PSOConfig, s: SwarmState,
               block_n: Optional[int] = None) -> SwarmState:
    """One PSO iteration via the queue kernel + jnp cross-block epilogue.

    Semantics match ``repro.core.pso.step_queue`` (stale-gbest comparison).
    """
    cfg = cfg.resolved()
    n, d = s.pos.shape
    bn = _resolve_block(n, block_n)
    scal, pos, vel, pbp, pbf, gp, gf = state_to_kernel(s, d)
    call = queue_step_call(n, d, bn, s.pos.dtype, **_cfg_kwargs(cfg))
    pos, vel, pbp, pbf, aux_fit, aux_idx = call(
        scal, gp, gf, pos, vel, pbp, pbf)
    # --- 2nd kernel (paper Fig. 1), shrunk to an O(nblocks) jnp epilogue.
    wb = jnp.argmax(aux_fit)
    cand_fit = aux_fit[wb]
    take = cand_fit > s.gbest_fit
    cand_pos = jax.lax.dynamic_index_in_dim(  # §5.3: gather pos by index once
        pos, aux_idx[wb], axis=1, keepdims=True)
    gp = jnp.where(take, cand_pos, gp)
    gf = jnp.where(take, cand_fit[None], gf)
    return kernel_to_state(s, d, pos, vel, pbp, pbf, gp, gf, 1)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "iters", "block_n", "telemetry"))
def run_queue_lock_fused(cfg: PSOConfig, s: SwarmState, iters: int,
                         block_n: Optional[int] = None,
                         telemetry: bool = False):
    """``iters`` iterations in ONE pallas_call (fused queue-lock, §4.2+).

    One launch for every iteration: the global best is published in-kernel
    under sequential-grid serialization and stays on the chip throughout.
    A swarm of one block keeps its state resident in VMEM for the whole
    launch. A swarm of several blocks streams it: each grid step copies its
    block of positions, velocities and pbests HBM -> VMEM and back, waiting
    on both copies, so a launch moves ``iters x nb x 2 x (3 Dpad + 1) x
    block_n x itemsize`` bytes. The builder records that count under the
    kernel's name (``repro.telemetry.kernel_streams()``).

    ``telemetry=True`` returns ``(state, counts)`` where ``counts`` is the
    in-kernel contention counter buffer ([3] int32 — see
    ``repro.telemetry.counters``); off (the default) returns the state
    alone from the byte-identical pre-telemetry program.
    """
    cfg = cfg.resolved()
    n, d = s.pos.shape
    bn = _resolve_block(n, block_n)
    scal, pos, vel, pbp, pbf, gp, gf = state_to_kernel(s, d)
    call = fused_call(n, d, iters, bn, s.pos.dtype, telemetry=telemetry,
                      **_cfg_kwargs(cfg))
    if telemetry:
        cnt = jnp.zeros((3,), jnp.int32)
        pos, vel, pbp, pbf, gp, gf, cnt = call(scal, pos, vel, pbp, pbf,
                                               gp, gf, cnt)
        return kernel_to_state(s, d, pos, vel, pbp, pbf, gp, gf, iters), cnt
    pos, vel, pbp, pbf, gp, gf = call(scal, pos, vel, pbp, pbf, gp, gf)
    return kernel_to_state(s, d, pos, vel, pbp, pbf, gp, gf, iters)


def pack_dmajor_batch(x, d: int):
    """[S, N, D] -> [S, Dpad, N] (zero-padded sublanes)."""
    s_cnt, n, _ = x.shape
    out = jnp.zeros((s_cnt, pad_dim(d), n), x.dtype)
    return out.at[:, :d, :].set(jnp.swapaxes(x, 1, 2))


def unpack_dmajor_batch(arr, d: int):
    """[S, Dpad, N] -> [S, N, D]."""
    return jnp.swapaxes(arr[:, :d, :], 1, 2)


def _hetero_members(cfg: PSOConfig, table):
    """Static kernel branch descriptors for a hetero dispatch table.

    Branch ``k`` closes over exactly the statics a homogeneous kernel of
    ``table[k]`` at this dim/coeffs/dtype would compile with
    (``hetero_member_config`` re-derives the member's resolved bounds).
    """
    return tuple(
        (ck.fitness, ck.min_pos, ck.max_pos, ck.max_v)
        for ck in (hetero_member_config(cfg, p) for p in table))


@functools.partial(jax.jit,
                   static_argnames=("cfg", "iters", "block_n", "table",
                                    "telemetry"))
def run_queue_lock_fused_batch(cfg: PSOConfig, batch: SwarmBatch, iters: int,
                               block_n: Optional[int] = None, fids=None,
                               table=None, telemetry: bool = False):
    """S independent swarms x ``iters`` iterations in ONE pallas_call.

    The multi-swarm analogue of ``run_queue_lock_fused``: per-swarm gbest
    buffers and per-swarm ``(seed, iteration)`` RNG counters ride a third
    (swarm-major) grid dimension, so row ``s`` of the batch is bit-identical
    to ``run_queue_lock_fused`` on ``batch_row(batch, s)`` with the same
    ``block_n`` — asserted in tests/test_multi_swarm.py. On TPU this is the
    serving hot path: a whole request batch advances with zero host
    round-trips and one kernel launch.

    ``telemetry=True`` returns ``(batch, counts)`` with ``counts`` shaped
    [S, 3] — row ``s`` holds swarm ``s``'s contention counters.
    """
    cfg = cfg.resolved()
    s_cnt, n, d = batch.pos.shape
    bn = _resolve_block(n, block_n)
    seeds = batch.seed.astype(jnp.int32)
    its = batch.iteration.astype(jnp.int32)
    pos = pack_dmajor_batch(batch.pos, d)
    vel = pack_dmajor_batch(batch.vel, d)
    pbp = pack_dmajor_batch(batch.pbest_pos, d)
    pbf = batch.pbest_fit.reshape(s_cnt, 1, n)
    gp = pack_columns(batch.gbest_pos, d)
    gf = batch.gbest_fit
    cnt = jnp.zeros((3 * s_cnt,), jnp.int32) if telemetry else None
    if fids is None:
        call = fused_batch_call(s_cnt, n, d, iters, bn, batch.pos.dtype,
                                telemetry=telemetry, **_cfg_kwargs(cfg))
        if telemetry:
            pos, vel, pbp, pbf, gp, gf, cnt = call(
                seeds, its, pos, vel, pbp, pbf, gp, gf, cnt)
        else:
            pos, vel, pbp, pbf, gp, gf = call(seeds, its, pos, vel, pbp,
                                              pbf, gp, gf)
    else:
        # Heterogeneous batch: per-swarm objective via kernel 3h. The cfg
        # contributes dim/coeffs/dtype only; bounds and objective come from
        # the member table (see ``multi_swarm.problem_rows``).
        rcfg = cfg.resolved()
        call = hetero_fused_batch_call(
            s_cnt, n, d, iters, bn, batch.pos.dtype, w=rcfg.w, c1=rcfg.c1,
            c2=rcfg.c2, members=_hetero_members(cfg, table),
            rule=rcfg.update_rule, telemetry=telemetry)
        if telemetry:
            pos, vel, pbp, pbf, gp, gf, cnt = call(
                seeds, its, fids.astype(jnp.int32), pos, vel, pbp, pbf,
                gp, gf, cnt)
        else:
            pos, vel, pbp, pbf, gp, gf = call(
                seeds, its, fids.astype(jnp.int32), pos, vel, pbp, pbf,
                gp, gf)
    pbf = pbf.reshape(s_cnt, n)
    out = batch._replace(
        pos=unpack_dmajor_batch(pos, d),
        vel=unpack_dmajor_batch(vel, d),
        fit=pbf,  # kernels do not retain raw fit; pbest_fit >= fit
        pbest_pos=unpack_dmajor_batch(pbp, d), pbest_fit=pbf,
        gbest_pos=unpack_columns(gp, d), gbest_fit=gf,
        iteration=batch.iteration + iters,
        lbest_pos=None, lbest_fit=None)
    if telemetry:
        return out, cnt.reshape(s_cnt, 3)
    return out


def _async_spans(iters: int, sync_every: int):
    """Split ``iters`` into (offset, span, chunk) phases for the async kernel.

    The kernel requires span % chunk == 0, so a non-multiple ``iters`` runs
    as a main phase of full ``sync_every`` chunks plus one remainder phase
    (a single shorter chunk). RNG counters chain across phases, and the
    block-local bests ride along, so the split is semantics-preserving
    (mirrored by ``ref.run_fused_async_oracle``). Degenerate inputs clamp
    the same way the jnp ``run_async`` does: ``iters <= 0`` is a no-op and
    ``sync_every`` is forced into [1, iters].
    """
    if iters <= 0:
        return []
    sync_every = max(1, min(sync_every, iters))
    main = (iters // sync_every) * sync_every
    phases = [(0, main, sync_every)]
    if iters - main:
        phases.append((main, iters - main, iters - main))
    return phases


@functools.partial(jax.jit,
                   static_argnames=("cfg", "iters", "sync_every", "block_n",
                                    "telemetry"))
def run_queue_lock_fused_async(cfg: PSOConfig, s: SwarmState, iters: int,
                               sync_every: int = ASYNC_SYNC_EVERY,
                               block_n: Optional[int] = None,
                               telemetry: bool = False):
    """``iters`` iterations of the ASYNC queue-lock in one pallas_call.

    The paper's enhanced algorithm: the grid is block-major
    ``(blocks, iter_chunks)`` — each particle block stays resident for its
    whole iteration span and runs ``sync_every`` iterations per grid step
    against a block-local best, touching the shared gbest buffers only at
    chunk boundaries (pull on entry, predicated publish on exit). Each
    block's view of the swarm best is therefore at most ``sync_every``
    iterations stale. With ``block_n == n`` (a single block — the default
    pick for n ≤ 512) the local best IS the global best and the result is
    bit-identical to ``run_queue_lock_fused`` for every ``sync_every``;
    the synchronous kernel is the ``sync_every=1`` single-block special
    case of this one.

    ``telemetry=True`` returns ``(state, counts)`` ([3] int32 contention
    counters, accumulated across the remainder-phase split via the
    aliased buffer).
    """
    cfg = cfg.resolved()
    n, d = s.pos.shape
    bn = _resolve_block(n, block_n)
    nb = n // bn
    scal, pos, vel, pbp, pbf, gp, gf = state_to_kernel(s, d)
    if s.lbest_fit is not None and s.lbest_fit.shape == (nb,):
        # resume the externalized block-local bests (checkpoint/resume
        # keeps the staleness window instead of restarting it)
        lp = pack_columns(s.lbest_pos, d)
        lf = s.lbest_fit
    else:
        lp = jnp.tile(gp[None], (nb, 1, 1))    # local bests seeded from gbest
        lf = jnp.tile(gf, nb)
    cnt = jnp.zeros((3,), jnp.int32) if telemetry else None
    for off, span, chunk in _async_spans(iters, sync_every):
        call = fused_async_call(n, d, span, bn, chunk, s.pos.dtype,
                                topology=cfg.topology, telemetry=telemetry,
                                **_cfg_kwargs(cfg))
        args = (scal + jnp.array([0, off], jnp.int32),
                pos, vel, pbp, pbf, gp, gf, lp, lf)
        if telemetry:
            pos, vel, pbp, pbf, gp, gf, lp, lf, cnt = call(*args, cnt)
        else:
            pos, vel, pbp, pbf, gp, gf, lp, lf = call(*args)
    out = kernel_to_state(s, d, pos, vel, pbp, pbf, gp, gf, iters)
    out = out._replace(lbest_pos=unpack_columns(lp, d), lbest_fit=lf)
    if telemetry:
        return out, cnt
    return out


@functools.partial(jax.jit,
                   static_argnames=("cfg", "iters", "sync_every", "block_n",
                                    "table", "telemetry"))
def run_queue_lock_fused_async_batch(cfg: PSOConfig, batch: SwarmBatch,
                                     iters: int,
                                     sync_every: int = ASYNC_SYNC_EVERY,
                                     block_n: Optional[int] = None,
                                     fids=None, table=None,
                                     telemetry: bool = False):
    """S independent swarms through the async queue-lock in one pallas_call.

    Grid ``(swarms, blocks, iter_chunks)``: per-swarm gbest buffers and
    per-(swarm, block) local-best slots, so row ``s`` is bit-identical to
    ``run_queue_lock_fused_async`` on ``batch_row(batch, s)`` with the same
    ``block_n``/``sync_every``. The serving hot path for ``variant="async"``.

    ``telemetry=True`` returns ``(batch, counts)`` with [S, 3] per-swarm
    contention counters.
    """
    cfg = cfg.resolved()
    s_cnt, n, d = batch.pos.shape
    bn = _resolve_block(n, block_n)
    nb = n // bn
    seeds = batch.seed.astype(jnp.int32)
    its = batch.iteration.astype(jnp.int32)
    pos = pack_dmajor_batch(batch.pos, d)
    vel = pack_dmajor_batch(batch.vel, d)
    pbp = pack_dmajor_batch(batch.pbest_pos, d)
    pbf = batch.pbest_fit.reshape(s_cnt, 1, n)
    gp = pack_columns(batch.gbest_pos, d)
    gf = batch.gbest_fit
    if batch.lbest_fit is not None and batch.lbest_fit.shape == (s_cnt, nb):
        lp = pack_columns(batch.lbest_pos.reshape(s_cnt * nb, d), d)
        lf = batch.lbest_fit.reshape(s_cnt * nb)
    else:
        lp = jnp.repeat(gp, nb, axis=0)        # [S*nb, Dpad, 1], swarm-major
        lf = jnp.repeat(gf, nb)
    cnt = jnp.zeros((3 * s_cnt,), jnp.int32) if telemetry else None
    for off, span, chunk in _async_spans(iters, sync_every):
        if fids is None:
            call = fused_async_batch_call(s_cnt, n, d, span, bn, chunk,
                                          batch.pos.dtype,
                                          topology=cfg.topology,
                                          telemetry=telemetry,
                                          **_cfg_kwargs(cfg))
            args = (seeds, its + jnp.int32(off), pos, vel, pbp, pbf, gp,
                    gf, lp, lf)
        else:
            rcfg = cfg.resolved()
            call = hetero_fused_async_batch_call(
                s_cnt, n, d, span, bn, chunk, batch.pos.dtype, w=rcfg.w,
                c1=rcfg.c1, c2=rcfg.c2, members=_hetero_members(cfg, table),
                rule=rcfg.update_rule, topology=cfg.topology,
                telemetry=telemetry)
            args = (seeds, its + jnp.int32(off), fids.astype(jnp.int32),
                    pos, vel, pbp, pbf, gp, gf, lp, lf)
        if telemetry:
            pos, vel, pbp, pbf, gp, gf, lp, lf, cnt = call(*args, cnt)
        else:
            pos, vel, pbp, pbf, gp, gf, lp, lf = call(*args)
    pbf = pbf.reshape(s_cnt, n)
    out = batch._replace(
        pos=unpack_dmajor_batch(pos, d),
        vel=unpack_dmajor_batch(vel, d),
        fit=pbf,  # kernels do not retain raw fit; pbest_fit >= fit
        pbest_pos=unpack_dmajor_batch(pbp, d), pbest_fit=pbf,
        gbest_pos=unpack_columns(gp, d), gbest_fit=gf,
        iteration=batch.iteration + iters,
        lbest_pos=unpack_columns(lp, d).reshape(s_cnt, nb, d),
        lbest_fit=lf.reshape(s_cnt, nb))
    if telemetry:
        return out, cnt.reshape(s_cnt, 3)
    return out


def make_fused_local_step(iters_per_call: int = 1, block_n=None):
    """Adapter: fused kernel as a ``local_step_fn`` for distributed swarms."""
    def step(cfg: PSOConfig, s: SwarmState) -> SwarmState:
        return run_queue_lock_fused(cfg, s, iters_per_call, block_n=block_n)
    return step
