"""Find the knee of an open-loop cell: the highest arrival rate the
serving path sustains. One process, warmed once, then one open-loop window
per rate; prints one JSON line per rate.

    python3 benchmarks/chip/sweep.py --workload cec05-d10.service \\
        --rates 2 4 8 16 --seconds 20 --seed 7

Each line gives ``steady_rate``, the solves finished per second in the
window's second half. Below capacity it follows the offered rate; above
it, with the lanes always full, it reads the capacity itself. A rate is
sustained when every request due in the window finished inside it or
within one request's service time after it, without a backlog that grows
through the window. ``--out`` also writes the lines to a file. The cell's
traffic file records the capacity and the rate chosen from it; this script
is how they were found, and no run of the benchmark calls it.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--drain", type=float, default=15.0)
    p.add_argument("--out", default=None)
    a = p.parse_args(argv)
    import spec
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    import drivers
    import traffic
    from repro.serving import ServingMetrics, use_persistent_cache
    use_persistent_cache()
    cell = spec.load_cell(a.workload)
    fns = list(spec.functions(cell.config))
    metrics = ServingMetrics()
    sched = drivers.make_scheduler(cell.config, spec.ROOT, metrics)
    g = traffic.closed_calls({"rows_per_call": 16}, fns, a.seed,
                             traffic.WARMUP)
    drivers.warm_scheduler(sched, cell.config, next(g))
    out = open(a.out, "w") if a.out else None
    for rate in a.rates:
        arr = traffic.open_arrivals(dict(cell.traffic, rate_per_s=rate), fns,
                                    a.seed, a.seconds)
        c0 = dict(metrics.counters)
        d0 = metrics.span("dispatch_us")
        n0, s0 = d0.count, d0.total_us
        t = time.perf_counter()
        done, _, late, missing = drivers.open_loop(
            sched, cell.config, arr, a.seconds,
            drivers.Reservoir(0, traffic.rng(a.seed, traffic.SAMPLE)),
            drivers.Spans(False), drain_s=a.drain)
        lat = sorted(d.latency_s for d in done)
        inside = sum(1 for d in done if d.end_s <= a.seconds)
        half = sum(1 for d in done if a.seconds / 2 < d.end_s <= a.seconds)
        c1 = metrics.counters
        slots = c1.get("lane_slots", 0) - c0.get("lane_slots", 0)
        line = json.dumps(dict(
            rate=rate, seed=a.seed, seconds=a.seconds, offered=len(arr),
            done_in_window=inside, solve_rate=inside / a.seconds,
            steady_rate=half / (a.seconds / 2), unfinished=len(missing),
            p50_s=statistics.median(lat) if lat else None,
            p95_s=statistics.quantiles(lat, n=20)[-1] if len(lat) > 1
            else None,
            max_s=lat[-1] if lat else None, late_max_s=max(late or [0]),
            drain_s=time.perf_counter() - t - a.seconds,
            dispatch_ms=(d0.total_us - s0) / max(1, d0.count - n0) / 1e3,
            batch_fill=(c1.get("lane_active_slots", 0)
                        - c0.get("lane_active_slots", 0)) / max(1, slots)))
        print(line, flush=True)
        if out:
            print(line, file=out, flush=True)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
