"""Schedule autotuning: pick ``(variant, backend, block_n, sync_every)``
per solve shape from the roofline cost model, with measured fallback.

``repro.Method(schedule="auto")`` routes here instead of the fixed
``resolve_backend`` rule. Resolution is three-stage:

1. **Cache** — measured optima persist per ``(backend scope, shape key)``
   in an on-disk JSON cache (``REPRO_AUTOTUNE_CACHE``, default
   ``~/.cache/repro/autotune.json``) fronted by an in-process LRU, so the
   second resolve of a shape never re-measures (and a serving replica
   inherits its predecessor's tuning).
2. **Model** — ``repro.roofline.pso_cost`` prices every candidate
   schedule (variants x block sizes x sync intervals) with a calibration
   fitted from the committed benchmark history; candidates rank by
   predicted microseconds per iteration.
3. **Measured fallback** — the top-``K`` model picks PLUS the fixed
   default schedule run timed micro-iterations (``tuner``-style
   self-measurement); the measured argmin wins, except that a challenger
   within ``MEASURE_NOISE_MARGIN`` of the fixed default loses to it
   (hysteresis — a within-noise win would flip sign on re-measurement).
   Including the fixed default makes the tuned choice never worse than
   the fixed rule by construction, model error notwithstanding.

Kernel-backend candidates only enter on an actual TPU backend — in
interpret mode the per-grid-step cost (~30us on this container, fitted
from the async_sweep history) makes every kernel schedule lose, and the
model would have to know interpret-mode throughput to price them fairly.

The serving layer (``repro.launch.serve``) uses the model-only entry
points: ``tuned_sync_every`` rewrites async requests' publication
interval before grouping (the tuned value is part of the batch compile
key, so cached programs are shared), and ``bucket_ladder`` drops bucket
sizes whose marginal per-row gain the model prices below threshold.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_LRU_SIZE = 512
#: measured micro-run length (iterations) and repeats for the fallback
MEASURE_ITERS = 24
MEASURE_REPEATS = 2
#: how many model-ranked candidates the measured fallback times
TOP_K = 3
#: hysteresis: a candidate must beat the measured fixed default by this
#: fraction to displace it. Micro-run timings on a busy host carry ~5%
#: noise; without a margin the tuner would "win" coin flips at resolve
#: time and lose them on the next independent measurement. Real schedule
#: gains (the async kernel's 3-4x, a wrong-variant pin's 1.5x) clear
#: this easily.
MEASURE_NOISE_MARGIN = 0.10
SYNC_EVERY_CHOICES = (1, 4, 8, 16, 32, 64)


@dataclasses.dataclass(frozen=True)
class Schedule:
    """A fully-resolved execution schedule for one solve shape.

    ``block_n`` is the kernel block size / jnp async block size (None:
    the ``pick_block_n`` default); ``sync_every`` only matters for
    ``variant="async"``. ``source`` records how the schedule was chosen:
    ``fixed`` (the legacy rule), ``model`` (analytic ranking only),
    ``measured`` (micro-run fallback) or ``cache`` (a previously measured
    optimum)."""

    variant: str
    backend: str
    block_n: Optional[int] = None
    sync_every: int = 8
    source: str = "fixed"
    predicted_us: Optional[float] = None
    measured_us: Optional[float] = None

    def replace(self, **kw) -> "Schedule":
        return dataclasses.replace(self, **kw)


def _kernel_ok() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def shape_key(problem, d: int, n: int, iters: int, dtype: str,
              batch: int = 1, hetero_table: int = 0,
              rule: str = "pso") -> str:
    """Stable cache key for one solve shape. ``iters`` is bucketed to its
    power-of-two ceiling — schedule choice is insensitive to small iter
    differences, and unbucketed keys would fragment the cache. The update
    rule is part of the shape: its op mix moves the compute roofline."""
    from repro.core.problem import resolve_problem

    it = 1
    while it < max(1, iters):
        it *= 2
    prob = resolve_problem(problem)
    pid = prob.name if not prob.constrained else f"{prob.name}+c"
    if not FITNESS_NAMED(prob):
        pid = f"custom:{hash(prob.cache_key()) & 0xffffffff:x}"
    return (f"{pid}|d{d}|n{n}|i{it}|{dtype}|b{batch}|h{hetero_table}"
            f"|r{rule}")


def FITNESS_NAMED(prob) -> bool:
    from repro.core.fitness import BUILTIN_PROBLEMS
    return any(prob.name == p.name for p in BUILTIN_PROBLEMS)


class AutotuneCache:
    """Measured-optima store: on-disk JSON + in-process LRU.

    The disk document maps ``{scope}::{shape_key} -> schedule dict``;
    writes are atomic (tmp + rename) and last-writer-wins — concurrent
    tuners may each measure once, which is safe, just redundant."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or os.environ.get(CACHE_ENV) or os.path.join(
            os.path.expanduser("~"), ".cache", "repro", "autotune.json")
        self._lru: "OrderedDict[str, Schedule]" = OrderedDict()
        self._disk_loaded = False

    def _load_disk(self) -> dict:
        try:
            with open(self.path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def get(self, scope: str, key: str) -> Optional[Schedule]:
        k = f"{scope}::{key}"
        if k in self._lru:
            self._lru.move_to_end(k)
            return self._lru[k]
        if not self._disk_loaded:
            for dk, v in self._load_disk().items():
                try:
                    self._lru.setdefault(dk, Schedule(**v))
                except TypeError:
                    continue    # stale schema: ignore, will re-measure
            self._disk_loaded = True
            if k in self._lru:
                return self._lru[k]
        return None

    def put(self, scope: str, key: str, sched: Schedule) -> None:
        k = f"{scope}::{key}"
        self._lru[k] = sched.replace(source="cache")
        self._lru.move_to_end(k)
        while len(self._lru) > _LRU_SIZE:
            self._lru.popitem(last=False)
        doc = self._load_disk()
        doc[k] = dataclasses.asdict(sched.replace(source="cache"))
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass    # cache is an optimization; never fail the solve


_CACHE: Optional[AutotuneCache] = None


def default_cache() -> AutotuneCache:
    global _CACHE
    if _CACHE is None or _CACHE.path != (
            os.environ.get(CACHE_ENV) or _CACHE.path):
        _CACHE = AutotuneCache()
    return _CACHE


def fixed_schedule(variant: str = "queue", *, record_history: bool = False,
                   sync_every: int = 8,
                   block_n: Optional[int] = None) -> Schedule:
    """The legacy ``Method.resolve_backend`` rule as a Schedule: kernel on
    TPU for the fused variants (unless history is requested), jnp else."""
    backend = ("kernel" if variant in ("queue_lock", "async")
               and not record_history and _kernel_ok() else "jnp")
    return Schedule(variant=variant, backend=backend, block_n=block_n,
                    sync_every=sync_every, source="fixed")


def _block_choices(n: int, kernel: bool) -> List[Optional[int]]:
    """Candidate block sizes: the heuristic default plus the divisors of
    ``n`` nearest the roofline-relevant range (a handful, not all)."""
    from repro.core.blocking import LANE, pick_block_n

    lane = LANE if kernel else 1
    default = pick_block_n(n, lane=lane)
    divs = [b for b in range(1, n + 1) if n % b == 0]
    good = [b for b in divs if 32 <= b <= 1024 and (b % lane == 0)]
    picks = {None, default}
    for target in (128, 256, 512):
        cands = [b for b in good if b <= target]
        if cands:
            picks.add(max(cands))
    if n <= 1024:
        picks.add(n)
    return sorted(picks, key=lambda b: (b is None, b))


def candidate_schedules(d: int, n: int, iters: int, *,
                        kernel_ok: Optional[bool] = None,
                        variants: Optional[Sequence[str]] = None,
                        max_candidates: int = 24) -> List[Schedule]:
    """Enumerate the schedule search space for one shape.

    Synchronous variants contribute one candidate each (their block/sync
    knobs don't exist or don't matter); ``async`` fans out over block
    sizes x sync intervals. Kernel backends join only when ``kernel_ok``
    (a real TPU)."""
    if kernel_ok is None:
        kernel_ok = _kernel_ok()
    variants = tuple(variants or ("reduction", "queue", "queue_lock",
                                  "async"))
    out: List[Schedule] = []
    for v in variants:
        if v != "async":
            out.append(Schedule(v, "jnp"))
            if kernel_ok and v == "queue_lock":
                for bn in _block_choices(n, kernel=True):
                    out.append(Schedule(v, "kernel", block_n=bn))
            continue
        syncs = [k for k in SYNC_EVERY_CHOICES if k <= max(1, iters)] or [1]
        for bn in _block_choices(n, kernel=False):
            for k in syncs:
                out.append(Schedule("async", "jnp", block_n=bn,
                                    sync_every=k))
        if kernel_ok:
            for bn in _block_choices(n, kernel=True):
                for k in syncs:
                    out.append(Schedule("async", "kernel", block_n=bn,
                                        sync_every=k))
    # Thin the async fan-out evenly if over budget (keep first/last knobs).
    if len(out) > max_candidates:
        sync_like = [s for s in out if s.variant != "async"]
        asyncs = [s for s in out if s.variant == "async"]
        keep = max(1, max_candidates - len(sync_like))
        step = max(1, len(asyncs) // keep)
        out = sync_like + asyncs[::step][:keep]
    return out


def _bench_baseline_path() -> Optional[str]:
    p = os.environ.get("REPRO_BENCH_BASELINE")
    if p:
        return p
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    cand = os.path.join(repo, "benchmarks", "BENCH_pso.json")
    return cand if os.path.exists(cand) else None


def rank_schedules(cands: Sequence[Schedule], problem, d: int, n: int,
                   iters: int, dtype: str = "float32", batch: int = 1,
                   hetero_table: int = 0, rule: str = "pso",
                   calib=None) -> List[Schedule]:
    """Model-rank candidates (ascending predicted us/iter). Candidates the
    model cannot price (e.g. a block size the kernel would reject) are
    dropped."""
    from repro.core.blocking import LANE
    from repro.roofline import pso_cost

    if calib is None:
        calib = pso_cost.fit_calibration(_bench_baseline_path())
    ranked = []
    for s in cands:
        if s.block_n is not None and (n % s.block_n
                                      or (s.backend == "kernel"
                                          and s.block_n % LANE
                                          and s.block_n != n)):
            continue
        us = pso_cost.estimate_us_per_iter(
            s.variant, problem, d, n, dtype=dtype, backend=s.backend,
            block_n=s.block_n, sync_every=s.sync_every, batch=batch,
            hetero_table=hetero_table, rule=rule, calib=calib)
        ranked.append(s.replace(source="model", predicted_us=us))
    ranked.sort(key=lambda s: s.predicted_us)
    return ranked


def measure_schedule(sched: Schedule, problem, d: int, n: int,
                     dtype: str = "float32", seed: int = 0,
                     iters: int = MEASURE_ITERS,
                     repeats: int = MEASURE_REPEATS,
                     rule: str = "pso") -> float:
    """Time a micro-run of ``sched`` (us per iteration, best of
    ``repeats`` after a compile warmup). Goes straight at the engine
    entry points — never back through the facade, so measurement cannot
    recurse into resolution."""
    from repro.core.pso import PSOConfig, init_swarm, run
    from repro.core.problem import resolve_problem

    prob = resolve_problem(problem)
    cfg = PSOConfig(dim=d, particle_cnt=n, fitness=prob,
                    dtype=dtype, update_rule=rule).resolved()
    state = init_swarm(cfg, seed)

    if sched.backend == "kernel":
        from repro.kernels.ops import (run_queue_lock_fused,
                                       run_queue_lock_fused_async)
        if sched.variant == "async":
            def go():
                return run_queue_lock_fused_async(
                    cfg, state, iters, sync_every=sched.sync_every,
                    block_n=sched.block_n)
        else:
            def go():
                return run_queue_lock_fused(cfg, state, iters,
                                            block_n=sched.block_n)
    else:
        n_blocks = (n // sched.block_n
                    if sched.variant == "async" and sched.block_n else None)

        def go():
            return run(cfg, state, iters, sched.variant,
                       sync_every=sched.sync_every, n_blocks=n_blocks)

    go().gbest_fit.block_until_ready()          # compile + warm caches
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        go().gbest_fit.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / iters * 1e6)
    return best


def resolve_schedule(problem, d: int, n: int, iters: int, *,
                     dtype: str = "float32", batch: int = 1,
                     hetero_table: int = 0, record_history: bool = False,
                     measure: bool = True, top_k: int = TOP_K,
                     cache: Optional[AutotuneCache] = None,
                     kernel_ok: Optional[bool] = None,
                     variants: Optional[Sequence[str]] = None,
                     rule: str = "pso") -> Schedule:
    """The ``schedule="auto"`` entry point: cache -> model -> measured.

    ``measure=False`` (the serving layer) stops after the model ranking —
    no micro-runs, bounded latency — but still reads the cache, so a
    previously measured optimum wins. ``record_history`` restricts to the
    jnp engines (history is a jnp-engine feature). The fixed-default
    schedule is ALWAYS among the measured candidates, and a challenger
    must beat it by ``MEASURE_NOISE_MARGIN`` to displace it: the tuned
    pick is never worse than the fixed rule, and within-noise ties keep
    the default. A kernel candidate that raises on a TPU propagates: on
    the chip that is a defect to surface, not a schedule that lost."""
    cache = cache or default_cache()
    if kernel_ok is None:
        kernel_ok = _kernel_ok() and not record_history
    scope = "kernel" if kernel_ok else "jnp"
    key = shape_key(problem, d, n, iters, dtype, batch, hetero_table,
                    rule=rule)
    hit = cache.get(scope, key)
    if hit is not None:
        return hit
    cands = candidate_schedules(d, n, iters, kernel_ok=kernel_ok,
                                variants=variants)
    ranked = rank_schedules(cands, problem, d, n, iters, dtype=dtype,
                            batch=batch, hetero_table=hetero_table,
                            rule=rule)
    if not ranked:
        return fixed_schedule(record_history=record_history)
    if not measure:
        return ranked[0]
    fixed = fixed_schedule(record_history=record_history)
    if not kernel_ok and fixed.backend == "kernel":
        fixed = fixed.replace(backend="jnp")
    def is_fixed(s: Schedule) -> bool:
        return (s.variant == fixed.variant and s.backend == fixed.backend
                and s.block_n == fixed.block_n
                and (s.variant != "async"
                     or s.sync_every == fixed.sync_every))

    to_measure = list(ranked[:max(1, top_k)])
    if not any(is_fixed(s) for s in to_measure):
        to_measure.append(fixed.replace(source="model"))
    timed = []
    for s in to_measure:
        try:
            timed.append(s.replace(source="measured",
                                   measured_us=measure_schedule(
                                       s, problem, d, n, dtype,
                                       rule=rule)))
        except Exception:
            if s.backend == "kernel" and _kernel_ok():
                raise   # a kernel that fails on the chip is a defect
            continue    # an unmeasurable candidate just drops out
    if not timed:
        return ranked[0]
    best = min(timed, key=lambda s: s.measured_us)
    # Hysteresis: keep the fixed default unless the winner clearly beats
    # it — a within-noise "win" would not survive re-measurement.
    anchor = next((s for s in timed if is_fixed(s)), None)
    if (anchor is not None and not is_fixed(best)
            and best.measured_us
            > (1.0 - MEASURE_NOISE_MARGIN) * anchor.measured_us):
        best = anchor
    cache.put(scope, key, best)
    return best


# --------------------------------------------------------------------------
# Serving-layer entry points (model-only: bounded latency).
# --------------------------------------------------------------------------

def tuned_sync_every(problem, d: int, n: int, iters: int,
                     dtype: str = "float32", batch: int = 1,
                     cache: Optional[AutotuneCache] = None) -> int:
    """Best publication interval for an async solve at this shape (model
    ranking restricted to ``variant="async"``, cache-backed)."""
    s = resolve_schedule(problem, d, n, iters, dtype=dtype, batch=batch,
                         measure=False, cache=cache, variants=("async",))
    return s.sync_every


def seed_priors(cache: Optional[AutotuneCache] = None,
                problems: Optional[Sequence] = None,
                dims: Sequence[int] = (1, 8),
                particles: Sequence[int] = (256, 1024),
                iters: int = 1024, dtype: str = "float32") -> int:
    """Pre-populate the cache with model-ranked schedules for the
    registry x a small shape grid (per-problem autotune priors).

    A fresh replica resolving ``schedule="auto"`` for an unseen shape
    pays timed micro-runs; a CI-built priors file (uploaded as an
    artifact and installed via ``REPRO_AUTOTUNE_CACHE``) means the first
    solve of every common shape starts from the cost model's best pick
    instead — bounded latency, no measurement. Already-cached keys
    (including genuinely measured optima) are never overwritten. Returns
    the number of entries seeded.
    """
    from repro.core.fitness import BUILTIN_PROBLEMS

    cache = cache or default_cache()
    if problems is None:
        problems = [p.name for p in BUILTIN_PROBLEMS]
    scope = "kernel" if _kernel_ok() else "jnp"
    seeded = 0
    for prob in problems:
        for d in dims:
            for n in particles:
                key = shape_key(prob, d, n, iters, dtype)
                if cache.get(scope, key) is not None:
                    continue
                cands = candidate_schedules(d, n, iters,
                                            kernel_ok=_kernel_ok())
                ranked = rank_schedules(cands, prob, d, n, iters,
                                        dtype=dtype)
                if ranked:
                    cache.put(scope, key, ranked[0])
                    seeded += 1
    return seeded


def bucket_ladder(problem, d: int, n: int, iters: int, *,
                  max_batch: int = 128, variant: str = "queue",
                  dtype: str = "float32", min_bucket: int = 4,
                  gain_threshold: float = 0.05) -> Tuple[int, ...]:
    """Batch-size buckets for the serving layer, from the cost model.

    Doubling the bucket always doubles the work; it pays when the
    per-ROW predicted cost drops by at least ``gain_threshold`` (fixed
    overheads amortizing). Buckets past the point of diminishing returns
    are dropped, shrinking the jit-cache footprint without losing fill."""
    from repro.roofline import pso_cost

    calib = pso_cost.fit_calibration(_bench_baseline_path())
    ladder = [min_bucket]
    backend = "jnp"     # serving ladders are priced for the jnp engine
    prev_row = pso_cost.estimate_us_per_iter(
        variant, problem, d, n, dtype=dtype, backend=backend,
        batch=min_bucket, calib=calib) / min_bucket
    b = min_bucket * 2
    while b <= max_batch:
        row = pso_cost.estimate_us_per_iter(
            variant, problem, d, n, dtype=dtype, backend=backend,
            batch=b, calib=calib) / b
        ladder.append(b)
        if row >= prev_row * (1.0 - gain_threshold):
            break   # per-row cost flattened: larger buckets don't pay
        prev_row = row
        b *= 2
    return tuple(ladder)


def _main(argv=None) -> int:
    """CLI: ``python -m repro.core.autotune --seed-priors`` (the CI step
    that builds the priors artifact)."""
    import argparse

    ap = argparse.ArgumentParser(
        description="Autotune cache utilities (schedule priors)")
    ap.add_argument("--seed-priors", action="store_true",
                    help="seed model-ranked schedules for the registry "
                         "x shape grid")
    ap.add_argument("--cache", default=None,
                    help="cache file (default: REPRO_AUTOTUNE_CACHE or "
                         "~/.cache/repro/autotune.json)")
    ap.add_argument("--dims", default="1,8")
    ap.add_argument("--particles", default="256,1024")
    ap.add_argument("--iters", type=int, default=1024)
    args = ap.parse_args(argv)
    cache = AutotuneCache(args.cache) if args.cache else default_cache()
    if args.seed_priors:
        n = seed_priors(
            cache=cache,
            dims=tuple(int(x) for x in args.dims.split(",")),
            particles=tuple(int(x) for x in args.particles.split(",")),
            iters=args.iters)
        print(f"seeded {n} schedule prior(s) into {cache.path}")
        return 0
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(_main())
