"""Load generator: continuous batching vs flush batching on a mixed trace.

The serving claim under test (docs/serving.md): a stream with staggered
arrivals and MIXED iteration budgets fragments the flush server — its
group keys include ``iters``, so a wave of async requests at one solve
shape but four different budgets splits into four groups, each padded
up to its bucket and each running its full budget on mostly-dead rows.
The continuous scheduler's lane keys DROP ``iters`` (accounting is per
row), so the same trace rides one full persistent lane and completed
rows hand their slot to the next arrival at a chunk boundary.

Both legs run the identical trace with the identical wave structure (a
wave of arrivals, then one scheduling opportunity: ``flush()`` vs
``step()``), share the ``ServingMetrics`` instrumentation, and are
measured in steady state: the first pass over the trace is warmup (it
pays the compiles; recorded as ``first_pass_s``), the second pass is the
reported one. Per-request results from the two legs are cross-checked
for bitwise agreement — both front ends sit on the row-bit-exact batched
engine, so any disagreement is a bug, not noise.

Reported per leg: wall us per request (the primary ``us_per_call``),
steady-state requests/s, e2e latency p50/p99, and batch fill
(real rows per dispatched slot). ``benchmarks/run.py`` wraps this as the
``serving/`` record family; standalone:

    PYTHONPATH=src python benchmarks/loadgen.py --smoke [--json out.json]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import List, Optional

NAMES = ("cubic", "sphere", "rastrigin", "ackley", "griewank", "rosenbrock")


def make_trace(n_requests: int, dim: int = 6, particles: int = 64,
               sync_every: int = 8,
               iters_choices=(16, 32, 48, 64)) -> List:
    """A deterministic mixed trace: round-robin over the six built-ins
    crossed with the iteration budgets (coprime cycle lengths, so every
    (objective, budget) pair occurs). All-async, all one solve shape —
    the regime where lane sharing pays and flush grouping fragments."""
    from repro.launch.serve import SolveRequest
    return [SolveRequest(dim=dim, particle_cnt=particles,
                         fitness=NAMES[k % len(NAMES)], seed=k,
                         iters=iters_choices[k % len(iters_choices)],
                         variant="async", sync_every=sync_every)
            for k in range(n_requests)]


def _leg_summary(n: int, elapsed_s: float, metrics) -> dict:
    lat = metrics.span("e2e_us")
    return {"requests": n,
            "elapsed_s": elapsed_s,
            "requests_per_s": n / elapsed_s,
            "us_per_request": 1e6 * elapsed_s / n,
            "p50_us": lat.p50_us, "p99_us": lat.p99_us,
            "batch_fill": metrics.batch_fill,
            "dispatches": int(metrics.get("dispatches"))}


def run_continuous(trace, wave: int = 8, lane_width: int = 8,
                   compile_cache=None) -> dict:
    """One pass of the trace through ``ContinuousScheduler``: submit a
    wave, take one scheduling step, repeat; drain the tail."""
    from repro.serving import ContinuousScheduler, ServingMetrics
    m = ServingMetrics()
    sched = ContinuousScheduler(lane_width=lane_width,
                                compile_cache=compile_cache, metrics=m)
    t0 = time.perf_counter()
    tickets = []
    for lo in range(0, len(trace), wave):
        tickets.extend(sched.submit(r) for r in trace[lo:lo + wave])
        sched.step()
    resolved = sched.drain()
    elapsed = time.perf_counter() - t0
    out = _leg_summary(len(trace), elapsed, m)
    out["results"] = [resolved[t] for t in tickets]
    out["snapshot"] = sched.snapshot()
    return out


def run_flush(trace, wave: int = 8, coalesce_registry: bool = True) -> dict:
    """One pass of the trace through the flush server: submit a wave,
    ``flush()``, repeat — the same arrival structure as the continuous
    leg, but every wave blocks until its whole (fragmented) batch set
    returns."""
    from repro.launch.serve import SolveServer
    from repro.serving import ServingMetrics
    m = ServingMetrics()
    srv = SolveServer(coalesce_registry=coalesce_registry, metrics=m)
    t0 = time.perf_counter()
    tickets, resolved = [], {}
    for lo in range(0, len(trace), wave):
        tickets.extend(srv.submit(r) for r in trace[lo:lo + wave])
        resolved.update(srv.flush())
    elapsed = time.perf_counter() - t0
    out = _leg_summary(len(trace), elapsed, m)
    out["results"] = [resolved[t] for t in tickets]
    out["snapshot"] = srv.snapshot()
    return out


def _strip(leg: dict) -> dict:
    return {k: v for k, v in leg.items() if k not in ("results", "snapshot")}


def run_loadgen(smoke: bool = False, wave: int = 8, lane_width: int = 8,
                compile_cache=None, trace: Optional[list] = None) -> dict:
    """Race the two front ends on the same mixed trace (steady state).

    Pass 1 of each leg pays the compiles (warmup; both legs' programs are
    jit-cached in-process afterwards), pass 2 is reported. Returns the
    two steady-state leg summaries plus the cross-check and speedup.
    """
    if trace is None:
        n = 48 if smoke else 96
        iters_choices = (8, 16, 24, 32) if smoke else (16, 32, 48, 64)
        trace = make_trace(n, iters_choices=iters_choices)
    if compile_cache is None and os.environ.get("REPRO_COMPILE_CACHE"):
        # CI sets the env var so the lane programs' AOT blobs ship as an
        # artifact; XLA-cache redirection is left to the serving replica
        # (benchmarks elsewhere in the process keep their own compiles).
        from repro.serving import CompileCache
        compile_cache = CompileCache()
        compile_cache.prewarm()
    warm_c = run_continuous(trace, wave, lane_width, compile_cache)
    cont = run_continuous(trace, wave, lane_width, compile_cache)
    warm_f = run_flush(trace, wave)
    flush = run_flush(trace, wave)
    agree = all(
        rc.gbest_fit == rf.gbest_fit
        and (rc.gbest_pos == rf.gbest_pos).all()
        for rc, rf in zip(cont["results"], flush["results"]))
    return {"n_requests": len(trace),
            "wave": wave,
            "continuous": _strip(cont),
            "flush": _strip(flush),
            "continuous_first_pass_s": warm_c["elapsed_s"],
            "flush_first_pass_s": warm_f["elapsed_s"],
            "speedup_vs_flush": (cont["requests_per_s"]
                                 / flush["requests_per_s"]),
            "gbest_agree": bool(agree),
            "continuous_snapshot": cont["snapshot"],
            "flush_snapshot": flush["snapshot"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace (24 requests, short budgets)")
    ap.add_argument("--wave", type=int, default=8,
                    help="arrivals per scheduling opportunity")
    ap.add_argument("--lane-width", type=int, default=8)
    ap.add_argument("--compile-cache", default=None,
                    help="directory for the persistent AOT compile cache")
    ap.add_argument("--json", default="",
                    help="write the full report here ('' disables)")
    args = ap.parse_args()
    cc = None
    if args.compile_cache:
        from repro.serving import CompileCache
        cc = CompileCache(args.compile_cache)    # places the XLA cache too
        cc.prewarm()
    rep = run_loadgen(smoke=args.smoke, wave=args.wave,
                      lane_width=args.lane_width, compile_cache=cc)
    for leg in ("continuous", "flush"):
        s = rep[leg]
        print(f"{leg:>10s}: {s['requests_per_s']:8.2f} req/s  "
              f"p50={s['p50_us']:.0f}us p99={s['p99_us']:.0f}us  "
              f"fill={s['batch_fill']:.2f}  dispatches={s['dispatches']}")
    print(f"continuous vs flush: {rep['speedup_vs_flush']:.2f}x req/s, "
          f"results bitwise agree: {rep['gbest_agree']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=2, default=str)
            f.write("\n")
        print(f"# wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
