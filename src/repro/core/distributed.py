"""Multi-chip / multi-pod PSO: the paper's "future work: multi-GPU" built out
to datacenter scale with shard_map.

Design:
  * Particles are sharded over the ("pod", "data") mesh axes. Each shard runs
    the full per-particle pipeline (advance + fitness + pbest) locally using
    the single-chip step variants — including the Pallas fused kernel when
    enabled.
  * The swarm-global best is the only cross-chip state. Synchronous mode
    (``exchange_interval=1``) all-reduces a scalar ``(fit, argmax-owner)``
    pair every iteration — the collective analogue of the paper's reduction
    kernel, but already minimized to O(1) bytes (8 B) per chip per iteration.
  * Island mode (``exchange_interval=K>1``) is the datacenter analogue of the
    queue-lock idea: shards iterate against a stale global best and publish
    occasionally. One collective per K iterations instead of per iteration.
  * ``variant="async"`` extends the async queue-lock's relaxed-consistency
    contract ACROSS devices — the **island ring**. There is no global
    barrier collective at all: each exchange is a single neighbor push of
    the island's current best ``(gbest_fit, owner, gbest_pos)`` around a
    ring (``lax.ppermute`` — the shard_map spelling of a
    ``make_async_remote_copy`` neighbor DMA), folded into the receiver
    under a rare-improvement predicate (the O(D) position select only
    applies when the received fit actually beats the local view, with
    lowest-owner-index tie-breaking so every shard converges to the same
    winner). Gossip-style forwarding — each shard pushes the best it
    *knows*, not just its own — gives the documented staleness bound:

        an island's published best reaches ALL shards within
        ``n_shards`` exchange rounds (one hop per round),

    on top of the intra-island bound of ``sync_every`` iterations from
    ``run_async``. A final drain of ``n_shards - 1`` exchange-only hops
    makes the run end fully synchronized: every shard's ``gbest`` equals
    the max over all pbests everywhere (the final-flush invariant, mirrored
    eagerly by ``repro.kernels.ref.run_islands_ring_oracle``).
  * gbest_pos (O(D) bytes) is broadcast from the winning shard only — via a
    pmax-weighted select in sync mode, via the predicated ring fold in async
    mode — so no gather of positions ever crosses the network unless an
    improvement actually happened (the paper's §5.3 index trick at cluster
    scale).

Remainder handling: ``iters`` need not divide ``exchange_interval`` — a
trailing short round (same RNG-counter chaining as
``ops.run_queue_lock_fused_async``'s tail phase) runs the leftover
iterations and still exchanges afterwards.

Elasticity: ``init_sharded_swarm`` builds shard-local particles from global
indices, so a checkpoint taken on 256 chips restores bit-identically on 64 or
1024 (tests/test_distributed.py::test_elastic_reshard_equivalence). The async
ring keeps the same convention by threading ``index_offset`` into the
shard-local ``run_async`` loop.

Problems: ``cfg.fitness`` may be a registered name or a first-class
``repro.core.problem.Problem`` — the shard-local step functions evaluate
``cfg.fitness_fn`` (canonical-max form, per-dimension bounds included)
inside shard_map unchanged, so user objectives distribute for free
(tests/test_problem.py::test_distributed_custom_problem).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .blocking import default_block_count
from .pso import (ASYNC_SYNC_EVERY, PSOConfig, STEP_FNS, SwarmState,
                  init_async_locals, init_swarm, run_async)

Array = jnp.ndarray

def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def swarm_pspec(particle_axes, with_locals: bool = False) -> SwarmState:
    """PartitionSpecs for a SwarmState sharded over ``particle_axes``.

    ``with_locals`` adds specs for the async block-local best buffers
    (``lbest_*``), which are shard-private and therefore sharded on the
    block axis like the particles.
    """
    pa = particle_axes
    return SwarmState(
        pos=P(pa, None), vel=P(pa, None), fit=P(pa),
        pbest_pos=P(pa, None), pbest_fit=P(pa),
        gbest_pos=P(None), gbest_fit=P(), iteration=P(), seed=P(),
        lbest_pos=P(pa, None) if with_locals else None,
        lbest_fit=P(pa) if with_locals else None,
    )


def _axes_tuple(particle_axes):
    return ((particle_axes,) if isinstance(particle_axes, str)
            else tuple(particle_axes))


def _n_shards(mesh: Mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def init_sharded_swarm(cfg: PSOConfig, seed: int, mesh: Mesh,
                       particle_axes=("data",)) -> SwarmState:
    """Initialize a swarm laid out over ``mesh`` without materializing it
    densely on one host: each shard constructs only its own slice via the
    counter RNG (index_offset), then the arrays are device_put with the
    swarm sharding."""
    cfg = cfg.resolved()
    axes = _axes_tuple(particle_axes)
    n_shards = _n_shards(mesh, axes)
    if cfg.particle_cnt % n_shards:
        raise ValueError(
            f"particle_cnt={cfg.particle_cnt} not divisible by {n_shards} shards")
    if n_shards == 1:
        # One shard owns everything: build the monolithic swarm directly so
        # the state is bit-identical to init_swarm (the shard_map-compiled
        # init fuses 1 ulp differently on XLA:CPU), then lay it out.
        state = init_swarm(cfg, seed)
        specs = swarm_pspec(axes if len(axes) > 1 else axes[0])
        return jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            state, specs)

    def per_shard():
        # Runs under shard_map: build the local slice from global indices.
        shard_id = jax.lax.axis_index(axes)
        local_n = cfg.particle_cnt // n_shards
        local = init_swarm(cfg, seed, n=local_n,
                           index_offset=shard_id * local_n)
        # Reconcile the global best across shards.
        gfit, gpos = _pmax_best(local.gbest_fit, local.gbest_pos, axes)
        return local._replace(gbest_fit=gfit, gbest_pos=gpos)

    specs = swarm_pspec(axes if len(axes) > 1 else axes[0])
    fn = _shard_map(per_shard, mesh, (), specs)
    return jax.jit(fn)()


_INT_MAX = jnp.iinfo(jnp.int32).max


def _pmax_best(fit: Array, pos: Array, axes) -> Tuple[Array, Array]:
    """All-reduce a (scalar fit, D-dim pos) pair to the global argmax.

    Communicates the scalar twice (max + masked-min for tie-broken
    ownership) and the position once, only from the winner — O(D) total,
    not O(N·D). Contract (tests/test_islands_ring.py):

      * the LOWEST shard index achieving the max fit owns the broadcast —
        ties are deterministic and every shard returns that owner's pos;
      * ``±inf`` fits participate normally (an all ``-inf`` swarm elects
        shard 0);
      * NaN guard: a NaN fit is treated as ``-inf`` and can never own the
        broadcast (an all-NaN swarm returns ``-inf`` and shard 0's pos)
        rather than poisoning ``gbest_pos`` with a zero sum.
    """
    fit = jnp.where(jnp.isnan(fit), -jnp.inf, fit)
    gfit = jax.lax.pmax(fit, axes)
    me = jax.lax.axis_index(axes)
    # Tie-break: lowest shard index that achieves the max owns the broadcast.
    winner = jax.lax.pmin(jnp.where(fit >= gfit, me, _INT_MAX), axes)
    contrib = jnp.where(me == winner, pos, jnp.zeros_like(pos))
    gpos = jax.lax.psum(contrib, axes)
    return gfit, gpos


def _ring_perm(n_shards: int):
    return [(i, (i + 1) % n_shards) for i in range(n_shards)]


def ring_exchange(gf: Array, gp: Array, owner: Array, axis, n_shards: int
                  ) -> Tuple[Array, Array, Array]:
    """One ring hop of the async island exchange.

    Pushes the shard's current known-best ``(fit, pos, owner)`` to its
    ring successor and folds the received candidate into the local view
    under the improvement predicate

        ``(recv_fit > fit) | (recv_fit == fit & recv_owner < owner)``

    so ties converge to the lowest originating shard everywhere, NaN never
    propagates (NaN compares false), and the O(D) position select applies
    only on actual improvement. Because each shard forwards the best it
    KNOWS (gossip), a value published anywhere reaches all ``n_shards``
    shards in at most ``n_shards - 1`` hops.
    """
    gf = jnp.where(jnp.isnan(gf), -jnp.inf, gf)
    perm = _ring_perm(n_shards)
    rf = jax.lax.ppermute(gf, axis, perm)
    rp = jax.lax.ppermute(gp, axis, perm)
    ro = jax.lax.ppermute(owner, axis, perm)
    better = (rf > gf) | ((rf == gf) & (ro < owner))
    return (jnp.where(better, rf, gf),
            jnp.where(better, rp, gp),
            jnp.where(better, ro, owner))


def make_distributed_run(cfg: PSOConfig, mesh: Mesh, iters: int,
                         variant: str = "queue",
                         exchange_interval: int = 1,
                         particle_axes=("data",),
                         local_step_fn=None,
                         sync_every: int = ASYNC_SYNC_EVERY,
                         n_blocks: Optional[int] = None):
    """Build a jitted ``run(state) -> state`` over the mesh.

    exchange_interval=1  → synchronous PPSO (reduction-equivalent semantics).
    exchange_interval=K  → island mode: K local iterations per global
                           exchange (queue-lock analogue at scale).
    ``iters % exchange_interval`` may be nonzero: the leftover iterations
    run as a shorter trailing round (RNG counters chain through unchanged)
    followed by a final exchange.

    ``variant="async"`` runs the RING path (module docstring): the
    shard-local loop is ``run_async`` (block-local bests carried in
    ``SwarmState.lbest_*``, publication every ``sync_every`` iterations)
    and the cross-shard exchange is a neighbor-only ``ring_exchange``
    instead of the ``_pmax_best`` barrier collective. ``sync_every`` must
    divide ``exchange_interval`` (it is clamped down to it when larger) so
    every exchange round keeps the same publication schedule as the
    uninterrupted single-chip run — with ONE shard the ring path computes
    ``run_async``'s trajectory, within the agreement limits of
    tests/agreement.py (tests/test_islands_ring.py).

    ``local_step_fn(cfg, state) -> state`` overrides the shard-local step
    of the synchronous variants (e.g. the Pallas fused kernel from
    repro.kernels.ops); the async ring has its own chunked local loop.
    """
    cfg = cfg.resolved()
    axes = _axes_tuple(particle_axes)
    n_shards = _n_shards(mesh, axes)
    rounds, rem = divmod(iters, exchange_interval)
    specs = swarm_pspec(axes if len(axes) > 1 else axes[0])

    if variant == "async":
        if local_step_fn is not None:
            raise NotImplementedError(
                "variant='async' islands run the built-in jnp run_async "
                "local loop; local_step_fn only overrides sync variants")
        if len(axes) != 1:
            raise NotImplementedError(
                "the async island ring exchanges over a single mesh axis; "
                f"got particle_axes={axes}")
        return _make_async_ring_run(cfg, mesh, iters, exchange_interval,
                                    axes, sync_every, n_blocks, specs,
                                    n_shards)

    step = local_step_fn if local_step_fn is not None else STEP_FNS[variant]

    def shard_body(state: SwarmState) -> SwarmState:
        def local_span(s, k):
            return jax.lax.fori_loop(0, k, lambda _, t: step(cfg, t), s)

        def one_round(k):
            def body(_, s):
                # K purely-local iterations against the (possibly stale)
                # gbest, then the serialized publication collective.
                s = local_span(s, k)
                gfit, gpos = _pmax_best(s.gbest_fit, s.gbest_pos, axes)
                return s._replace(gbest_fit=gfit, gbest_pos=gpos)
            return body

        state = jax.lax.fori_loop(0, rounds, one_round(exchange_interval),
                                  state)
        if rem:
            state = one_round(rem)(0, state)
        return state

    fn = _shard_map(shard_body, mesh, (specs,), specs)
    return jax.jit(fn)


def _make_async_ring_run(cfg: PSOConfig, mesh: Mesh, iters: int,
                         exchange_interval: int, axes,
                         sync_every: int, n_blocks: Optional[int],
                         specs, n_shards: int):
    """The async island ring runner (see make_distributed_run)."""
    axis = axes[0]
    local_n = cfg.particle_cnt // n_shards
    nb = n_blocks or default_block_count(local_n)
    rounds, rem = divmod(iters, exchange_interval)
    # Keep every round's intra-island publication schedule aligned with the
    # uninterrupted run: sync points must land on round boundaries.
    sync_eff = min(sync_every, exchange_interval)
    if exchange_interval % sync_eff:
        raise ValueError(
            f"sync_every={sync_every} must divide "
            f"exchange_interval={exchange_interval} for async islands")
    out_specs = swarm_pspec(axes if len(axes) > 1 else axes[0],
                            with_locals=True)

    def shard_body(state: SwarmState) -> SwarmState:
        me = jax.lax.axis_index(axes).astype(jnp.int32)
        # One shard owns the whole swarm: a static None keeps the
        # single-chip run_async index arithmetic.
        offset = None if n_shards == 1 else me * local_n
        lbp, lbf = init_async_locals(state, nb)
        state = state._replace(lbest_pos=lbp, lbest_fit=lbf)
        owner = me

        def exchange(s: SwarmState, owner):
            gf, gp, owner = ring_exchange(s.gbest_fit, s.gbest_pos, owner,
                                          axis, n_shards)
            # Pull the (possibly fresher) ring best into the block locals
            # so the next round's blocks steer toward it immediately.
            take = gf > s.lbest_fit
            lbf = jnp.where(take, gf, s.lbest_fit)
            lbp = jnp.where(take[:, None], gp[None, :], s.lbest_pos)
            return s._replace(gbest_fit=gf, gbest_pos=gp,
                              lbest_fit=lbf, lbest_pos=lbp), owner

        def one_round(k):
            def body(_, carry):
                s, owner = carry
                prev = s.gbest_fit
                s = run_async(cfg, s, k, sync_every=sync_eff, n_blocks=nb,
                              phase=0, index_offset=offset)
                # A gbest raised during the local span is our discovery.
                owner = jnp.where(s.gbest_fit > prev, me, owner)
                return exchange(s, owner)
            return body

        carry = (state, owner)
        if rounds:
            carry = jax.lax.fori_loop(
                0, rounds, one_round(exchange_interval), carry)
        if rem:
            carry = one_round(rem)(0, carry)
        state, owner = carry
        # Drain: n_shards - 1 exchange-only hops complete the propagation of
        # every island's final best — afterwards gbest is identical on all
        # shards and equals max over all pbests (final-flush invariant).
        for _ in range(n_shards - 1):
            state, owner = exchange(state, owner)
        return state

    fn = _shard_map(shard_body, mesh, (specs,), out_specs)
    return jax.jit(fn)


def gather_swarm(state: SwarmState) -> SwarmState:
    """Fetch a fully-replicated host copy (for checkpointing / inspection)."""
    return jax.tree.map(lambda x: jax.device_get(x), state)
