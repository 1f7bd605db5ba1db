"""Flush-batching front end for PSO solves: collect a queue generation,
group by compile key, dispatch padded batches.

This is the simpler of the repo's two serving front ends. ``SolveServer``
collects submitted requests until ``flush()``, groups them by their
*compilation key*, pads each group to a bucketed batch size (one
compiled program per (key, bucket), not per request count), and routes
every group through a single ``solve_many`` — or through the batched
fused Pallas kernels for the ``queue_lock``/``async`` variants with
``backend="kernel"``. It is the right tool for OFFLINE batches: all
requests known up front, throughput over latency, no arrivals mid-solve.

For STREAMING traffic — staggered arrivals, mixed iteration budgets,
tail-latency targets — use the continuous-batching scheduler built on
top of this module's request/result types:
``repro.serving.ContinuousScheduler`` keeps persistent batched async
lanes running and admits new requests at chunk boundaries instead of
waiting for a whole flush to return (architecture, admission invariants
and the restart story: docs/serving.md). The two front ends share
``SolveRequest``/``SolveResult``/``ServingMetrics``, and
``benchmarks/loadgen.py`` races them on the same trace.

Grouping here is two-tier. Requests whose problem is one of the
registered built-ins (``hetero_fid``) coalesce into a single
HETEROGENEOUS batch keyed only on the shape of the solve — ``(dim,
particle_cnt, iters, variant, dtype, sync_every)`` — with each row's
objective and box bounds dispatched by ``lax.switch`` inside one
compiled program, so a mixed sphere/rastrigin/ackley trace rides one
dispatch. Row results lean on ``gbest_fit``/``gbest_pos``, the validated
bit-exactness surface of the heterogeneous engines.
``coalesce_registry=False`` restores the legacy content-hash-only keys.
Custom ``Problem``s keep the second tier: their grouping key hashes the
problem's CONTENT (``Problem.cache_key``), never its name or identity,
so distinct objectives never share a batch and re-submitted identical
ones still do. Constrained problems ride the same machinery, and
``SolveResult.feasible``/``violation`` report Deb-rule feasibility.

Failure isolation: a group whose solve raises no longer poisons the
whole flush — the other groups return normally and the offending
tickets resolve to error results (``SolveResult.error`` set,
``SolveResult.ok`` False; see ``flush``).

    PYTHONPATH=src python -m repro.launch.serve --requests 24 --iters 200

Padding rows reuse the group's first seed and are dropped before results
are returned; they cost compute but never correctness. ``ServeStats``
reports how much padding each flush paid, and an attached
``repro.serving.ServingMetrics`` additionally records per-request
queue/solve latency spans and dispatch counters for the JSON snapshot.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core import ASYNC_SYNC_EVERY, PSOConfig
from repro.core.multi_swarm import (hetero_fid, init_batch, problem_rows,
                                    solve_many)
from repro.core.problem import Problem, resolve_problem

# Buckets bound the jit cache to one program per (key, bucket).
_MIN_BUCKET = 4
BUCKETS = (_MIN_BUCKET, 8, 16, 32, 64, 128)

# Hetero batch keys carry this marker in the content-hash slot: every
# registry built-in at the same solve shape lands in ONE group. The batch's
# PSOConfig is pinned to a canonical fitness so every mix that shares a
# group key also shares a compiled program (cfg.fitness only keys the jit
# cache for heterogeneous batches — the rows carry the real objectives).
_HETERO = "__hetero__"
_HETERO_CANONICAL_FITNESS = "cubic"


@dataclasses.dataclass(frozen=True)
class SolveRequest:
    """One independent PSO solve.

    ``sync_every`` is the ``variant="async"`` publication interval. It only
    enters the compile key for async requests — the synchronous variants
    ignore it, and keying on it would split otherwise-identical requests
    into separate batches and duplicate compiled programs.
    """

    dim: int = 1
    particle_cnt: int = 1024
    fitness: Union[str, Problem] = "cubic"
    seed: int = 0
    iters: int = 1000
    variant: str = "queue"
    dtype: str = "float32"
    sync_every: int = ASYNC_SYNC_EVERY
    rule: str = "pso"          # update rule (repro.core.update_rules)
    topology: str = "gbest"    # async lbest topology (repro.core.topology)

    def _topology_key(self) -> str:
        """The topology only exists on the async variant's block-local
        machinery — keying sync requests on it would split identical
        programs (mirrors the ``sync_every`` rationale above)."""
        return self.topology if self.variant == "async" else "gbest"

    @property
    def batch_key(self) -> Tuple:
        """Everything that forces a distinct compiled program. The problem
        enters by CONTENT hash (see module docstring), resolving registered
        names through the registry so a string and its Problem batch
        together."""
        return (self.dim, self.particle_cnt,
                resolve_problem(self.fitness).cache_key(), self.iters,
                self.variant, self.dtype,
                self.sync_every if self.variant == "async" else 0,
                self.rule, self._topology_key())

    @property
    def hetero_eligible(self) -> bool:
        """True when the problem is a registered built-in: the request can
        ride a shared heterogeneous batch with other built-ins."""
        return hetero_fid(self.fitness) is not None

    def group_key(self, coalesce_registry: bool = True) -> Tuple:
        """The server's grouping key: hetero marker for built-ins (all
        built-ins at one solve shape coalesce), content hash otherwise."""
        if coalesce_registry and self.hetero_eligible:
            return (self.dim, self.particle_cnt, _HETERO, self.iters,
                    self.variant, self.dtype,
                    self.sync_every if self.variant == "async" else 0,
                    self.rule, self._topology_key())
        return self.batch_key

    def config(self) -> PSOConfig:
        return PSOConfig(dim=self.dim, particle_cnt=self.particle_cnt,
                         fitness=self.fitness, dtype=self.dtype,
                         update_rule=self.rule,
                         topology=self._topology_key())


@dataclasses.dataclass
class SolveResult:
    request: SolveRequest
    gbest_fit: float         # canonical (maximized) fitness
    gbest_pos: np.ndarray
    batch_size: int          # padded batch the request rode in
    error: Optional[BaseException] = None  # set when the solve raised
    history: Optional[object] = None  # repro.History: gbest-vs-iteration
    # series sampled at the lane's chunk boundaries (continuous scheduler
    # with record_history=True; None elsewhere)

    @property
    def ok(self) -> bool:
        """False when this request's group failed: ``error`` holds the
        exception and the ``gbest_*`` fields are meaningless."""
        return self.error is None

    @property
    def objective(self) -> float:
        """The objective value in the problem's OWN sense (a sense="min"
        problem reports the minimized value)."""
        if not self.ok:
            raise RuntimeError(
                f"request failed: {self.error!r}") from self.error
        return float(resolve_problem(self.request.fitness)
                     .user_value(self.gbest_fit))

    @property
    def violation(self) -> float:
        """Aggregate constraint violation at ``gbest_pos`` (0.0 for
        unconstrained problems) — the Deb-rule input, mirrored from
        ``repro.Result.violation`` so serving responses carry the same
        feasibility report as the facade."""
        return resolve_problem(self.request.fitness).violation_at(
            self.gbest_pos)

    @property
    def feasible(self) -> bool:
        return self.violation <= 0.0


def request_error(r: SolveRequest) -> Optional[Exception]:
    """Per-request admission validation: the rejection (or None).

    Returned, not raised, so an unknown variant/rule/topology resolves to
    its OWN error result (``SolveResult.error`` set, ``ok`` False) at
    flush time instead of poisoning the whole group it would have been
    batched into — the group-level isolation in ``flush`` only catches
    solves that raise, and a bad name would otherwise raise while
    *grouping* (``group_key`` resolves the problem) or compile-key every
    valid request in the group into the failure."""
    from repro.core.pso import VARIANTS
    from repro.core.update_rules import TOPOLOGIES, resolve_rule
    if r.variant not in VARIANTS:
        return ValueError(
            f"unknown variant {r.variant!r}; one of {VARIANTS}")
    try:
        resolve_rule(r.rule)
    except ValueError as e:
        return e
    if r.topology not in TOPOLOGIES:
        return ValueError(
            f"unknown topology {r.topology!r}; one of {TOPOLOGIES}")
    try:
        resolve_problem(r.fitness)
    except (KeyError, ValueError, TypeError) as e:
        return e
    return None


@dataclasses.dataclass
class ServeStats:
    requests: int = 0
    dispatches: int = 0      # batched device programs launched
    padded_rows: int = 0     # wasted swarm slots from bucket padding
    hetero_dispatches: int = 0  # of which: heterogeneous (mixed-problem)
    failed: int = 0          # requests whose group's solve raised

    @property
    def batch_fill(self) -> float:
        """Mean real (non-padding) rows per dispatch — the coalescing
        payoff metric: higher means fewer, fuller device programs."""
        return self.requests / self.dispatches if self.dispatches else 0.0

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["batch_fill"] = self.batch_fill
        return d


def bucket_size(k: int, max_batch: int = BUCKETS[-1],
                buckets: Tuple[int, ...] = BUCKETS) -> int:
    """Smallest bucket >= k (capped): bounds the jit cache per batch_key.

    ``buckets`` defaults to the static ladder; an autotuning server passes
    a model-tuned ladder instead (``repro.core.autotune.bucket_ladder``)."""
    for b in buckets:
        if b >= min(k, max_batch):
            return min(b, max_batch)
    return max_batch


class SolveServer:
    """Collects solve requests and dispatches them as padded batches.

    ``backend="jnp"`` runs every variant through the vmapped ``solve_many``;
    ``backend="kernel"`` routes ``queue_lock`` and ``async`` requests through
    the batched fused Pallas kernels (compiled on a TPU, interpreted on any
    other backend) and everything else through the jnp path. A group whose
    problem the kernels cannot compile (``Method.resolve_backend``) fails
    with that error. ``coalesce_registry`` (default on) merges every registered
    built-in problem at the same solve shape into one heterogeneous batch
    (``lax.switch`` row dispatch); off, grouping falls back to the legacy
    per-problem content-hash keys.

    ``autotune=True`` consults the roofline autotuner
    (``repro.core.autotune``, model-only: no timed micro-runs on the
    serving path, but previously measured cache entries win): async
    requests' ``sync_every`` is rewritten to the tuned value for their
    shape BEFORE grouping — the tuned interval is part of the batch
    compile key, so every request at one shape shares one tuned compiled
    program — and the bucket ladder is re-derived per grouping shape from
    the cost model (buckets past the point of diminishing per-row returns
    are dropped, shrinking the jit-cache footprint).
    """

    def __init__(self, max_batch: int = 64, backend: str = "jnp",
                 block_n: Optional[int] = None,
                 coalesce_registry: bool = True, autotune: bool = False,
                 metrics=None):
        if backend not in ("jnp", "kernel"):
            raise ValueError(f"unknown backend {backend!r}")
        if max_batch < BUCKETS[0]:
            raise ValueError(
                f"max_batch={max_batch} < minimum bucket {BUCKETS[0]}")
        self.max_batch = max_batch
        self.backend = backend
        self.block_n = block_n
        self.coalesce_registry = coalesce_registry
        self.autotune = autotune
        self.stats = ServeStats()
        self.metrics = metrics   # optional repro.serving.ServingMetrics
        self._pending: List[Tuple[int, SolveRequest, float]] = []
        self._ticket = 0
        self._ladders: Dict[Tuple, Tuple[int, ...]] = {}

    def _tuned_request(self, r: SolveRequest) -> SolveRequest:
        """Rewrite an async request's publication interval to the tuned
        value for its shape (no-op for sync variants / autotune off)."""
        if not self.autotune or r.variant != "async":
            return r
        from repro.core.autotune import tuned_sync_every
        k = tuned_sync_every(r.fitness, r.dim, r.particle_cnt, r.iters,
                             r.dtype)
        return dataclasses.replace(r, sync_every=k)

    def _buckets_for(self, r0: SolveRequest) -> Tuple[int, ...]:
        """The bucket ladder for one grouping shape: static by default,
        model-tuned (and memoized per shape) when autotuning."""
        if not self.autotune:
            return BUCKETS
        key = (r0.dim, r0.particle_cnt, r0.iters, r0.variant, r0.dtype)
        if key not in self._ladders:
            from repro.core.autotune import bucket_ladder
            self._ladders[key] = bucket_ladder(
                r0.fitness, r0.dim, r0.particle_cnt, r0.iters,
                max_batch=self.max_batch, variant=r0.variant,
                dtype=r0.dtype, min_bucket=_MIN_BUCKET)
        return self._ladders[key]

    def submit(self, req: SolveRequest) -> int:
        """Enqueue a request; returns a ticket resolved by ``flush()``."""
        t = self._ticket
        self._ticket += 1
        self._pending.append((t, req, time.perf_counter()))
        if self.metrics is not None:
            self.metrics.inc("submitted")
        return t

    def _solve_group(self, reqs: List[SolveRequest]) -> List[SolveResult]:
        """One compilation group -> one (or a few, if > max_batch) dispatches."""
        out: List[SolveResult] = []
        hetero = (self.coalesce_registry
                  and all(r.hetero_eligible for r in reqs))
        for lo in range(0, len(reqs), self.max_batch):
            chunk = reqs[lo:lo + self.max_batch]
            k = len(chunk)
            padded = bucket_size(k, self.max_batch,
                                 self._buckets_for(chunk[0]))
            seeds = np.array([r.seed for r in chunk]
                             + [chunk[0].seed] * (padded - k), dtype=np.int64)
            r0 = chunk[0]
            if hetero:
                # Padding rows replicate the first request's problem too, so
                # they stay as dead weight with well-defined bounds.
                probs = ([r.fitness for r in chunk]
                         + [r0.fitness] * (padded - k))
                cfg = PSOConfig(dim=r0.dim, particle_cnt=r0.particle_cnt,
                                fitness=_HETERO_CANONICAL_FITNESS,
                                dtype=r0.dtype, update_rule=r0.rule,
                                topology=r0._topology_key())
                batch = self._dispatch_hetero(cfg, seeds, probs, r0)
            else:
                cfg = r0.config()
                batch = self._dispatch_uniform(cfg, seeds, r0)
            gf = np.asarray(batch.gbest_fit)
            gp = np.asarray(batch.gbest_pos)
            self.stats.dispatches += 1
            self.stats.hetero_dispatches += int(hetero)
            self.stats.padded_rows += padded - k
            if self.metrics is not None:
                self.metrics.inc("dispatches")
                self.metrics.inc("lane_slots", padded)
                self.metrics.inc("lane_active_slots", k)
            out.extend(SolveResult(request=r, gbest_fit=float(gf[i]),
                                   gbest_pos=gp[i], batch_size=padded)
                       for i, r in enumerate(chunk))
        return out

    def _dispatch_uniform(self, cfg: PSOConfig, seeds: np.ndarray,
                          r0: SolveRequest):
        """Legacy single-problem dispatch (content-hash-keyed groups)."""
        kernel = (self.backend == "kernel"
                  and r0.variant in ("queue_lock", "async"))
        if kernel:
            # the api's rule: raises for a problem the kernels cannot compile
            from repro.api import Method
            Method(variant=r0.variant, backend="kernel").resolve_backend(
                cfg.problem)
        if kernel and r0.variant == "queue_lock":
            from repro.kernels.ops import run_queue_lock_fused_batch
            return run_queue_lock_fused_batch(
                cfg, init_batch(cfg, seeds), iters=r0.iters,
                block_n=self.block_n)
        if kernel and r0.variant == "async":
            from repro.kernels.ops import run_queue_lock_fused_async_batch
            return run_queue_lock_fused_async_batch(
                cfg, init_batch(cfg, seeds), iters=r0.iters,
                sync_every=r0.sync_every, block_n=self.block_n)
        return solve_many(cfg, seeds, iters=r0.iters, variant=r0.variant,
                          sync_every=r0.sync_every)

    def _dispatch_hetero(self, cfg: PSOConfig, seeds: np.ndarray,
                         probs: List[Union[str, Problem]], r0: SolveRequest):
        """Mixed-problem dispatch: per-row objective/bounds descriptors +
        ``lax.switch`` dispatch, one compiled program for the whole mix."""
        if self.backend == "kernel" and r0.variant in ("queue_lock", "async"):
            rows, table = problem_rows(probs, cfg.dim, cfg.dtype)
            rcfg = cfg.resolved()
            batch = init_batch(rcfg, seeds, rows=rows, table=table)
            if r0.variant == "queue_lock":
                from repro.kernels.ops import run_queue_lock_fused_batch
                return run_queue_lock_fused_batch(
                    rcfg, batch, iters=r0.iters, block_n=self.block_n,
                    fids=rows.fid, table=table)
            from repro.kernels.ops import run_queue_lock_fused_async_batch
            return run_queue_lock_fused_async_batch(
                rcfg, batch, iters=r0.iters, sync_every=r0.sync_every,
                block_n=self.block_n, fids=rows.fid, table=table)
        return solve_many(cfg, seeds, iters=r0.iters, variant=r0.variant,
                          sync_every=r0.sync_every, problems=probs)

    def flush(self) -> Dict[int, SolveResult]:
        """Dispatch all pending requests; returns {ticket: result}.

        Failures are isolated per GROUP (the dispatch unit): if one
        group's solve raises, its tickets resolve to error results
        (``SolveResult.error`` set, ``ok`` False) and every other group
        returns normally — a poisoned custom objective cannot take down
        unrelated requests sharing the flush.
        """
        groups: Dict[Tuple, List[Tuple[int, SolveRequest, float]]] = \
            defaultdict(list)
        results: Dict[int, SolveResult] = {}
        for t, r, ts in self._pending:
            err = request_error(r)
            if err is not None:
                # reject at admission: the bad request gets its own error
                # result and never joins (or poisons) a dispatch group
                self.stats.failed += 1
                if self.metrics is not None:
                    self.metrics.inc("failed")
                results[t] = SolveResult(
                    request=r, gbest_fit=float("nan"),
                    gbest_pos=np.full((r.dim,), np.nan),
                    batch_size=0, error=err)
                continue
            r = self._tuned_request(r)   # tuned sync_every enters group_key
            groups[r.group_key(self.coalesce_registry)].append((t, r, ts))
        self._pending.clear()
        for _, members in sorted(groups.items(), key=lambda kv: repr(kv[0])):
            tickets = [t for t, _, _ in members]
            t0 = time.perf_counter()
            try:
                solved = self._solve_group([r for _, r, _ in members])
            except Exception as e:
                self.stats.failed += len(members)
                if self.metrics is not None:
                    self.metrics.inc("failed", len(members))
                results.update(
                    (t, SolveResult(request=r, gbest_fit=float("nan"),
                                    gbest_pos=np.full((r.dim,), np.nan),
                                    batch_size=0, error=e))
                    for t, r, _ in members)
                continue
            results.update(zip(tickets, solved))
            self.stats.requests += len(members)
            if self.metrics is not None:
                now = time.perf_counter()
                self.metrics.inc("completed", len(members))
                self.metrics.observe("dispatch_us", (now - t0) * 1e6)
                for _, _, ts in members:
                    self.metrics.observe("e2e_us", (now - ts) * 1e6)
        return results

    def solve_all(self, requests: Sequence[SolveRequest]) -> List[SolveResult]:
        """Convenience: submit + flush, results in request order."""
        tickets = [self.submit(r) for r in requests]
        resolved = self.flush()
        return [resolved[t] for t in tickets]

    def snapshot(self) -> dict:
        """ServeStats (+ the attached metrics sink, if any) as JSON-able
        dict — the flush-server half of the serving observability story."""
        doc = {"stats": self.stats.as_dict()}
        if self.metrics is not None:
            doc["metrics"] = self.metrics.snapshot()
        return doc

    def prometheus(self, *, prefix: str = "repro") -> str:
        """This server's serving state as a Prometheus text exposition
        (``repro.telemetry.prometheus_text``). With a metrics sink
        attached, renders its spans and counters; without one, renders the
        ServeStats counters and batch fill."""
        if self.metrics is not None:
            return self.metrics.prometheus(prefix=prefix)
        from repro.telemetry import prometheus_text
        counters = {k: v for k, v in self.stats.as_dict().items()
                    if k != "batch_fill"}
        return prometheus_text(
            {"counters": counters, "batch_fill": self.stats.batch_fill,
             "spans": {}}, prefix=prefix)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--backend", default="jnp", choices=["jnp", "kernel"])
    ap.add_argument("--variant", default="auto",
                    choices=["auto", "reduction", "queue", "queue_lock",
                             "async"])
    ap.add_argument("--sync-every", type=int, default=ASYNC_SYNC_EVERY,
                    help="async variant publication interval")
    ap.add_argument("--no-coalesce", action="store_true",
                    help="legacy per-problem content-hash grouping")
    ap.add_argument("--autotune", action="store_true",
                    help="roofline-tuned sync_every + bucket ladder")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a Prometheus text exposition of the "
                         "serving metrics here after the flush")
    args = ap.parse_args()
    from repro.serving import use_persistent_cache
    use_persistent_cache()
    # A mixed workload: four built-in objectives over two solve shapes. With
    # registry coalescing each shape is ONE heterogeneous dispatch; with
    # --no-coalesce each (shape, problem) pair compiles and runs alone.
    if args.variant == "auto":
        variant = "queue_lock" if args.backend == "kernel" else "queue"
    else:
        variant = args.variant
    mix = [("cubic", 1, 256), ("sphere", 1, 256),
           ("rastrigin", 10, 128), ("ackley", 10, 128)]
    reqs = [SolveRequest(dim=d, particle_cnt=n, fitness=f, seed=i,
                         iters=args.iters, variant=variant,
                         sync_every=args.sync_every)
            for i, (f, d, n) in ((i, mix[i % len(mix)])
                                 for i in range(args.requests))]
    metrics = None
    if args.metrics_out:
        from repro.serving import ServingMetrics
        metrics = ServingMetrics()
    srv = SolveServer(max_batch=args.max_batch, backend=args.backend,
                      coalesce_registry=not args.no_coalesce,
                      autotune=args.autotune, metrics=metrics)
    t0 = time.time()
    results = srv.solve_all(reqs)
    dt = time.time() - t0
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(srv.prometheus())
        print(f"metrics -> {args.metrics_out}")
    for r in results[:4]:
        print(f"req({r.request.fitness}, dim={r.request.dim}, "
              f"seed={r.request.seed}) gbest_fit={r.gbest_fit:.6g} "
              f"(batch={r.batch_size})")
    s = srv.stats
    print(f"{s.requests} requests in {s.dispatches} dispatches "
          f"({s.hetero_dispatches} heterogeneous, {s.padded_rows} padded "
          f"rows, fill={s.batch_fill:.1f}), wall={dt:.3f}s "
          f"({s.requests / dt:.1f} solves/s)")
    for r in results:
        if not r.ok:
            print(f"FAILED req({r.request.fitness}, dim={r.request.dim}, "
                  f"seed={r.request.seed}): {r.error!r}", file=sys.stderr)
    return 1 if s.failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
