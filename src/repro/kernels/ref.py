"""Pure-jnp oracles for the Pallas kernels (no pallas_call anywhere).

These reproduce, eagerly and without any Pallas machinery, exactly what the
kernels are *supposed* to compute — including the sequential-block
publication order of the fused queue-lock kernel (block b of iteration t
sees the gbest already updated by blocks 0..b-1 of iteration t). They share
the tile math helpers with the kernel module so interpret-mode comparisons
isolate the pallas orchestration; the math itself is independently checked
against ``repro.core.pso`` in tests/test_kernels.py.

All oracles work on the packed D-major layout (see ops.py for pack/unpack).
``fitness`` accepts a registered name or a ``repro.core.problem.Problem``;
it resolves through the SAME ``pso_step.kernel_fitness`` (hand-tuned fast
path or generic d-major adapter) as the kernels, so custom objectives are
checked like the built-ins.

The oracles round op by op, as the chip does; a compiled program on
XLA:CPU chooses FMA contraction and reduction order per shape. So a
kernel or engine and its oracle agree within the stated limits of
tests/agreement.py (the benchmark's own), not bit for bit.
"""
from __future__ import annotations

from typing import Tuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import block_neighbor_best, kernel_neighbor_ids
from repro.core.update_rules import resolve_rule

from .pso_step import (_advance_block, _pbest_improved, kernel_fitness,
                       kernel_projection, kernel_violation, pad_dim)


def run_islands_ring_oracle(cfg, seed: int, n_shards: int, iters: int,
                            exchange_interval: int,
                            sync_every: int = 8, n_blocks=None):
    """Eager oracle for the async island ring in ``repro.core.distributed``.

    Simulates ``n_shards`` islands as explicit per-island SwarmStates (the
    ``init_swarm(index_offset)`` sharding convention) and runs, per exchange
    round, the island-local ``run_async`` loop followed by one Python-level
    ring hop with the exact ``ring_exchange`` fold semantics (strict-
    improvement predicate, lowest-owner tie-break, NaN-as--inf) and the
    local-best pull, then the ``n_shards - 1`` drain hops. With one shard
    the whole thing reduces to ``run_async`` on the monolithic swarm —
    bit-identically, since the self-hop fold and pull are exact no-ops.

    Returns ``(islands, history)``: the final per-island states, and one
    ``[(gbest_fit, owner), ...]`` snapshot per exchange round (taken after
    the hop), from which tests assert the staleness bound — any island's
    round-r best is visible on island ``(i + d) % n_shards`` by round
    ``r + d``, i.e. everywhere within ``n_shards`` rounds — and the
    final-flush invariant (every island's gbest equals the max over all
    pbests after the drain).
    """
    from repro.core.blocking import default_block_count
    from repro.core.pso import init_swarm, run_async

    cfg = cfg.resolved()
    if cfg.particle_cnt % n_shards:
        raise ValueError("particle_cnt not divisible by n_shards")
    local_n = cfg.particle_cnt // n_shards
    nb = n_blocks or default_block_count(local_n)
    sync_eff = min(sync_every, exchange_interval)
    if exchange_interval % sync_eff:
        raise ValueError("sync_every must divide exchange_interval")

    islands = [init_swarm(cfg, seed, n=local_n, index_offset=i * local_n)
               for i in range(n_shards)]
    # init-time reconcile (init_sharded_swarm's _pmax_best): lowest-index
    # winner of the max init fit owns the shared starting gbest.
    fits = [float(s.gbest_fit) for s in islands]
    best = max(f for f in fits if not np.isnan(f)) if any(
        not np.isnan(f) for f in fits) else -np.inf
    win = min(i for i, f in enumerate(fits)
              if (not np.isnan(f)) and f >= best) if best > -np.inf else 0
    islands = [s._replace(gbest_fit=islands[win].gbest_fit,
                          gbest_pos=islands[win].gbest_pos)
               for s in islands]
    owners = list(range(n_shards))

    def hop(islands, owners):
        snap = [(jnp.where(jnp.isnan(s.gbest_fit), -jnp.inf, s.gbest_fit),
                 s.gbest_pos, owners[i]) for i, s in enumerate(islands)]
        out, own_out = [], []
        for i, s in enumerate(islands):
            rf, rp, ro = snap[(i - 1) % n_shards]
            gf, gp, own = snap[i][0], s.gbest_pos, owners[i]
            better = bool(rf > gf) or (bool(rf == gf) and ro < own)
            if better:
                gf, gp, own = rf, rp, ro
            lbf, lbp = s.lbest_fit, s.lbest_pos
            if lbf is not None:
                take = gf > lbf
                lbf = jnp.where(take, gf, lbf)
                lbp = jnp.where(take[:, None], gp[None, :], lbp)
            out.append(s._replace(gbest_fit=jnp.asarray(gf), gbest_pos=gp,
                                  lbest_fit=lbf, lbest_pos=lbp))
            own_out.append(own)
        return out, own_out

    rounds, rem = divmod(iters, exchange_interval)
    spans = [exchange_interval] * rounds + ([rem] if rem else [])
    history = []
    for k in spans:
        nxt = []
        for i, s in enumerate(islands):
            prev = float(s.gbest_fit)
            s = run_async(cfg, s, k, sync_every=sync_eff, n_blocks=nb,
                          phase=0, index_offset=i * local_n)
            if float(s.gbest_fit) > prev:
                owners[i] = i
            nxt.append(s)
        islands, owners = hop(nxt, owners)
        history.append([(float(s.gbest_fit), owners[i])
                        for i, s in enumerate(islands)])
    for _ in range(n_shards - 1):
        islands, owners = hop(islands, owners)
        history.append([(float(s.gbest_fit), owners[i])
                        for i, s in enumerate(islands)])
    return islands, history


def run_constrained_oracle(cfg, seed: int, iters: int,
                           variant: str = "queue_lock",
                           sync_every: int = 8,
                           n_blocks: int = None):
    """Eager oracle for CONSTRAINED solves through the jnp engines.

    An independent re-implementation of ``repro.core.pso``'s synchronous
    queue-lock (and, for ``variant="async"``, the relaxed block-local
    schedule) in the library's particle-major [N, D] layout: init (with
    the projection / repair-resample constrained init), the
    velocity/position/clip advance, the post-advance projection hook, the
    penalized canonical fitness, pbest folds, and the variant's gbest
    publication rule — a Python iteration loop with Python-level
    publication conditionals, no ``cond``, ``fori_loop`` or ``pallas_call``
    anywhere. Only the advance+fitness subgraph runs under ``jit``, as the
    engine's does; the pbest/gbest select folds are rounding-free and stay
    eager.

    The jnp engine, dispatched per iteration or as one loop program, agrees
    with this oracle within the limits of tests/agreement.py, constraints
    and all (tests/test_constraints.py). The kernel backends are checked
    against ``run_fused_oracle``/``run_fused_async_oracle``, which thread
    the same projection/penalty through the d-major tile machinery.

    Returns a ``repro.core.pso.SwarmState``.
    """
    from repro.core import rng as _rng
    from repro.core.blocking import default_block_count
    from repro.core.constraints import deb_improved, repair_init_positions
    from repro.core.pso import (STREAM_INIT_POS, STREAM_INIT_VEL, STREAM_R1,
                                STREAM_R2, SwarmState)

    if variant not in ("queue_lock", "async"):
        raise ValueError(f"unsupported oracle variant {variant!r}")
    cfg = cfg.resolved()
    prob = cfg.problem
    fit_fn = prob.max_fn                       # penalty rides the wrapper
    proj = prob.projection_fn
    # Deb-rule pbest selection for projection/repair modes (penalty mode
    # stays on raw canonical fitness) — the engine's deb_selection_fn gate,
    # mirrored here so the comparison stays like-for-like.
    deb_vf = (prob.violation_fn
              if prob.constrained and prob.constraints.mode != "penalty"
              else None)
    n, d = cfg.particle_cnt, cfg.dim
    dt = jnp.dtype(cfg.dtype)

    def op(v):
        return jnp.asarray(v, dt) if isinstance(v, tuple) else v

    lo, hi, mv = op(cfg.min_pos), op(cfg.max_pos), op(cfg.max_v)
    idx = jnp.arange(n * d, dtype=jnp.uint32).reshape(n, d)
    pos = lo + (hi - lo) * _rng.uniform(seed, 0, STREAM_INIT_POS, idx, dt)
    vel = -mv + 2.0 * mv * _rng.uniform(seed, 0, STREAM_INIT_VEL, idx, dt)
    if proj is not None:
        pos = proj(pos)
    elif prob.constrained and prob.constraints.mode == "repair":
        pos = repair_init_positions(prob.constraints, prob.violation_fn,
                                    pos, lo, hi - lo, seed,
                                    STREAM_INIT_POS, idx, dt)
    fit = fit_fn(pos)
    pbp, pbf = pos, fit
    b = jnp.argmax(fit)
    gp, gf = pos[b], fit[b]

    nb = n_blocks or default_block_count(n)
    bn = n // nb
    if variant == "async":
        lbp = jnp.broadcast_to(gp[None, :], (nb, d))
        lbf = jnp.broadcast_to(gf, (nb,))

    orule = resolve_rule(cfg.update_rule)

    @jax.jit
    def advance(vel, pos, pbp, attractor, r1, r2):
        p, v = orule.advance(r1, r2, pos, vel, pbp, attractor,
                             w=cfg.w, c1=cfg.c1, c2=cfg.c2,
                             mv=mv, lo=lo, hi=hi)
        if proj is not None:
            p = proj(p)
        return p, v, fit_fn(p)

    for t in range(1, iters + 1):
        r1 = _rng.uniform(seed, t, STREAM_R1, idx, dt)
        r2 = _rng.uniform(seed, t, STREAM_R2, idx, dt)
        attractor = (gp[None, :] if variant != "async"
                     else jnp.repeat(lbp, bn, axis=0))
        pos, vel, fit = advance(vel, pos, pbp, attractor, r1, r2)
        imp = (fit > pbf if deb_vf is None
               else deb_improved(fit, deb_vf(pos), pbf, deb_vf(pbp)))
        pbf = jnp.where(imp, fit, pbf)
        pbp = jnp.where(imp[:, None], pos, pbp)
        if variant == "async":
            fb = fit.reshape(nb, bn)
            bi = jnp.argmax(fb, axis=1)
            bfit = jnp.take_along_axis(fb, bi[:, None], axis=1)[:, 0]
            bpos = pos.reshape(nb, bn, d)[jnp.arange(nb), bi]
            take = bfit > lbf
            lbf = jnp.where(take, bfit, lbf)
            lbp = jnp.where(take[:, None], bpos, lbp)
            sched = t % max(1, sync_every) == 0
            if sched or t == iters:
                wb = jnp.argmax(lbf)
                if float(lbf[wb]) > float(gf):
                    gf, gp = lbf[wb], lbp[wb]
                if sched:    # scheduled sync point: publish AND pull; an
                    # unscheduled final boundary flushes publish-only
                    # (mirrors run_async's flush_async_locals tail)
                    if cfg.topology == "gbest":
                        lbf = jnp.broadcast_to(gf, lbf.shape)
                        lbp = jnp.broadcast_to(gp[None, :], lbp.shape)
                    else:  # lbest pull: neighborhood fold of block-locals
                        lbp, lbf = block_neighbor_best(lbf, lbp,
                                                       cfg.topology)
        else:
            if bool(jnp.any(imp)):           # queue-lock publication rule
                wb = jnp.argmax(pbf)
                if float(pbf[wb]) > float(gf):
                    gf, gp = pbf[wb], pbp[wb]

    state = SwarmState(pos=pos, vel=vel, fit=fit, pbest_pos=pbp,
                       pbest_fit=pbf, gbest_pos=gp, gbest_fit=gf,
                       iteration=jnp.asarray(iters, jnp.int32),
                       seed=jnp.asarray(seed, jnp.uint32))
    if variant == "async":
        state = state._replace(lbest_pos=lbp, lbest_fit=lbf)
    return state


def _advance_fn(fitness, **kw):
    """The oracles' advance step: the plain eager ``_advance_block`` for
    every objective. A projection-mode constraint set rides the same hook
    as in the kernels: the d-major ``kernel_projection`` form applied after
    the box clip inside ``_advance_block``.
    """
    lifted = kernel_projection(fitness)
    if lifted is not None:
        d_real = kw["d_real"]
        kw = dict(kw, project=lambda p: lifted(p, d_real))
    return functools.partial(_advance_block, **kw)

_BIG = np.int32(2 ** 30)


def _block_views(arrs, b, bn):
    return [a[..., b * bn:(b + 1) * bn] for a in arrs]


def queue_step_oracle(seed, iteration, pos, vel, pbp, pbf, gp, gf,
                      block_n: int, *, w, c1, c2, min_pos, max_pos, max_v,
                      d_real: int, fitness, rule="pso"):
    """One queue-algorithm iteration (kernel 1 + the jnp 2nd stage).

    Inputs in D-major layout: pos/vel/pbp [Dpad, N], pbf [1, N],
    gp [Dpad, 1], gf scalar. Returns the updated six arrays.
    """
    dpad, n = pos.shape
    nb = n // block_n
    fitfn = kernel_fitness(fitness)
    vf = kernel_violation(fitness)
    viol = None if vf is None else (lambda p: vf(p, d_real))
    adv = _advance_fn(fitness, w=w, c1=c1, c2=c2, min_pos=min_pos,
                      max_pos=max_pos, max_v=max_v, d_real=d_real,
                      rule=resolve_rule(rule))
    pos, vel, pbp, pbf = map(jnp.asarray, (pos, vel, pbp, pbf))
    aux_fit = []
    aux_idx = []
    new = {k: [] for k in ("pos", "vel", "pbp", "pbf")}
    for b in range(nb):
        p, v, bp, bf_ = _block_views((pos, vel, pbp, pbf), b, block_n)
        p, v, dmask, lane = adv(seed, iteration + 1, p, v, bp, gp,
                                b * block_n)
        fit = fitfn(p, dmask, d_real)
        imp = _pbest_improved(fit, p, bf_, bp, viol)
        bf_ = jnp.where(imp, fit, bf_)
        bp = jnp.where(imp, p, bp)
        new["pos"].append(p); new["vel"].append(v)
        new["pbp"].append(bp); new["pbf"].append(bf_)
        q = jnp.where(fit > gf, fit, -jnp.inf)
        best = jnp.max(q)
        lane_row = jnp.broadcast_to(jnp.arange(block_n)[None, :], q.shape)
        bidx = jnp.min(jnp.where(q >= best, lane_row, _BIG))
        aux_fit.append(best)
        aux_idx.append(b * block_n + bidx)
    pos = jnp.concatenate(new["pos"], axis=-1)
    vel = jnp.concatenate(new["vel"], axis=-1)
    pbp = jnp.concatenate(new["pbp"], axis=-1)
    pbf = jnp.concatenate(new["pbf"], axis=-1)
    aux_fit = jnp.stack(aux_fit)
    aux_idx = jnp.stack(aux_idx).astype(jnp.int32)
    # 2nd stage (cross-block): conditional global-best update.
    wb = int(jnp.argmax(aux_fit))
    if float(aux_fit[wb]) > float(gf):
        gf = aux_fit[wb]
        gp = pos[:, int(aux_idx[wb]):int(aux_idx[wb]) + 1]
    return pos, vel, pbp, pbf, gp, gf, aux_fit, aux_idx


def run_fused_oracle(seed, base_iter, pos, vel, pbp, pbf, gp, gf,
                     iters: int, block_n: int, *, w, c1, c2, min_pos,
                     max_pos, max_v, d_real: int, fitness, rule="pso",
                     counters=None):
    """The fused queue-lock kernel's exact semantics, eagerly.

    Sequential (t, b) loop; gbest is updated in place so later blocks of the
    same iteration see it — mirroring TPU sequential grid execution.

    ``counters``: an optional dict whose ``queue_updates`` /
    ``publications`` / ``block_improvements`` keys are incremented at the
    same program points the telemetry kernels count — the validation
    oracle for ``repro.telemetry`` (one conditional guards both the queue
    fold and the publication here, so the first two move together).
    """
    dpad, n = pos.shape
    nb = n // block_n
    fitfn = kernel_fitness(fitness)
    vf = kernel_violation(fitness)
    viol = None if vf is None else (lambda p: vf(p, d_real))
    adv = _advance_fn(fitness, w=w, c1=c1, c2=c2, min_pos=min_pos,
                      max_pos=max_pos, max_v=max_v, d_real=d_real,
                      rule=resolve_rule(rule))
    pos, vel, pbp, pbf, gp = map(jnp.asarray, (pos, vel, pbp, pbf, gp))
    gf = jnp.asarray(gf)
    pos, vel, pbp, pbf = (np.array(pos), np.array(vel), np.array(pbp),
                          np.array(pbf))
    for t in range(iters):
        for b in range(nb):
            sl = slice(b * block_n, (b + 1) * block_n)
            p, v, dmask, lane = adv(
                seed, base_iter + t + 1,
                jnp.asarray(pos[:, sl]), jnp.asarray(vel[:, sl]),
                jnp.asarray(pbp[:, sl]), gp, b * block_n)
            fit = fitfn(p, dmask, d_real)
            bf_ = jnp.asarray(pbf[:, sl])
            bp = jnp.asarray(pbp[:, sl])
            imp = _pbest_improved(fit, p, bf_, bp, viol)
            pbf[:, sl] = np.array(jnp.where(imp, fit, bf_))
            pbp[:, sl] = np.array(jnp.where(imp, p, bp))
            pos[:, sl] = np.array(p)
            vel[:, sl] = np.array(v)
            if counters is not None and bool(jnp.any(imp)):
                counters["block_improvements"] = (
                    counters.get("block_improvements", 0) + 1)
            q_mask = fit > gf
            if bool(jnp.any(q_mask)):                 # rare publication
                if counters is not None:
                    counters["queue_updates"] = (
                        counters.get("queue_updates", 0) + 1)
                    counters["publications"] = (
                        counters.get("publications", 0) + 1)
                q = jnp.where(q_mask, fit, -jnp.inf)
                bf = jnp.max(q)
                lane_row = jnp.broadcast_to(
                    jnp.arange(block_n)[None, :], q.shape)
                bidx = int(jnp.min(jnp.where(q >= bf, lane_row, _BIG)))
                gf = bf
                sel = (lane == bidx) & dmask
                gp = jnp.sum(jnp.where(sel, p, jnp.zeros_like(p)),
                             axis=1, keepdims=True)
    return (jnp.asarray(pos), jnp.asarray(vel), jnp.asarray(pbp),
            jnp.asarray(pbf), gp, gf)


def run_fused_async_oracle(seed, base_iter, pos, vel, pbp, pbf, gp, gf,
                           iters: int, block_n: int, sync_every: int, *,
                           w, c1, c2, min_pos, max_pos, max_v, d_real: int,
                           fitness, rule="pso", topology="gbest",
                           counters=None):
    """The async queue-lock kernel's exact semantics, eagerly.

    Block-major: block b runs its ENTIRE iteration span (all chunks of
    ``sync_every`` iterations) before block b+1 starts, maintaining a
    block-local best; the shared gbest is pulled at chunk entry and
    conditionally published at chunk exit — mirroring the kernel's
    (blocks, chunks) grid order bit-for-bit, including the ops-wrapper
    behaviour of running a trailing ``iters % sync_every`` remainder as a
    second block-major phase over all blocks.

    With an lbest ``topology`` (``"ring"`` / ``"vonneumann"``) the chunk
    entry folds the NEIGHBOR blocks' local slots (same stencil and fold
    order as the kernel's ``kernel_neighbor_ids`` loop) instead of pulling
    the shared gbest, which remains a chunk-exit flush target only —
    mirroring the kernel's block-major diffusion schedule bit-for-bit.

    Returns the kernel's eight state arrays, ``local_pos`` in its
    ``[nb, Dpad, 1]`` layout.

    ``counters`` mirrors ``run_fused_oracle``: ``queue_updates`` counts
    inner iterations with a non-empty block-local queue, ``publications``
    counts chunk-exit shared-gbest writes, ``block_improvements`` counts
    (iteration, block) pbest-fold events — the async telemetry kernels'
    validation oracle.
    """
    dpad, n = pos.shape
    nb = n // block_n
    fitfn = kernel_fitness(fitness)
    vf = kernel_violation(fitness)
    viol = None if vf is None else (lambda p: vf(p, d_real))
    adv = _advance_fn(fitness, w=w, c1=c1, c2=c2, min_pos=min_pos,
                      max_pos=max_pos, max_v=max_v, d_real=d_real,
                      rule=resolve_rule(rule))
    pos, vel, pbp, pbf, gp = map(jnp.asarray, (pos, vel, pbp, pbf, gp))
    gf = jnp.asarray(gf)
    pos, vel, pbp, pbf = (np.array(pos), np.array(vel), np.array(pbp),
                          np.array(pbf))
    # Local bests seeded from the shared gbest, one slot per block — exactly
    # what ops.run_queue_lock_fused_async hands the kernel. The phase split
    # (and its degenerate-input clamps) is the wrapper's own, not a copy.
    from .ops import _async_spans
    lp = [jnp.array(gp) for _ in range(nb)]      # each [Dpad, 1]
    lf = [jnp.asarray(gf) for _ in range(nb)]
    for it_off, span, k in _async_spans(iters, sync_every):
        for b in range(nb):
            sl = slice(b * block_n, (b + 1) * block_n)
            for c in range(span // k):
                if topology == "gbest":
                    # chunk entry: pull shared into local
                    if float(gf) > float(lf[b]):
                        lf[b] = gf
                        lp[b] = gp
                else:
                    # lbest: fold neighbor block-locals, same running-max
                    # order as the kernel's kernel_neighbor_ids loop
                    for nbr in kernel_neighbor_ids(b, nb, topology):
                        if float(lf[nbr]) > float(lf[b]):
                            lf[b] = lf[nbr]
                            lp[b] = lp[nbr]
                for tl in range(k):
                    it = base_iter + it_off + c * k + tl + 1
                    p, v, dmask, lane = adv(
                        seed, it,
                        jnp.asarray(pos[:, sl]), jnp.asarray(vel[:, sl]),
                        jnp.asarray(pbp[:, sl]), lp[b], b * block_n)
                    fit = fitfn(p, dmask, d_real)
                    bf_ = jnp.asarray(pbf[:, sl])
                    bp = jnp.asarray(pbp[:, sl])
                    imp = _pbest_improved(fit, p, bf_, bp, viol)
                    pbf[:, sl] = np.array(jnp.where(imp, fit, bf_))
                    pbp[:, sl] = np.array(jnp.where(imp, p, bp))
                    pos[:, sl] = np.array(p)
                    vel[:, sl] = np.array(v)
                    if counters is not None and bool(jnp.any(imp)):
                        counters["block_improvements"] = (
                            counters.get("block_improvements", 0) + 1)
                    q_mask = fit > lf[b]
                    if bool(jnp.any(q_mask)):    # local publication
                        if counters is not None:
                            counters["queue_updates"] = (
                                counters.get("queue_updates", 0) + 1)
                        q = jnp.where(q_mask, fit, -jnp.inf)
                        best = jnp.max(q)
                        lane_row = jnp.broadcast_to(
                            jnp.arange(block_n)[None, :], q.shape)
                        bidx = int(jnp.min(jnp.where(q >= best, lane_row,
                                                     _BIG)))
                        lf[b] = best
                        sel = (lane == bidx) & dmask
                        lp[b] = jnp.sum(jnp.where(sel, p, jnp.zeros_like(p)),
                                        axis=1, keepdims=True)
                # chunk exit: rare cross-block publication
                if float(lf[b]) > float(gf):
                    if counters is not None:
                        counters["publications"] = (
                            counters.get("publications", 0) + 1)
                    gf = lf[b]
                    gp = lp[b]
    lp_arr = jnp.stack([jnp.asarray(x) for x in lp])   # [nb, Dpad, 1]
    lf_arr = jnp.stack([jnp.asarray(x).reshape(()) for x in lf])
    return (jnp.asarray(pos), jnp.asarray(vel), jnp.asarray(pbp),
            jnp.asarray(pbf), gp, gf, lp_arr, lf_arr)
