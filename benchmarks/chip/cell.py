#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 benchmarks/chip/cell.py --workload t4-cubic1d.solo \\
        --seed 7 --seconds 20 --trace 0

1. Loads the cell named in ``BENCHMARK.json`` and its pieces by name
   (``spec.py``).
2. Makes the traffic from ``--seed`` (``traffic.py``).
3. Warms the cell's own shapes through the same entry, with JAX's
   persistent compilation cache at the fixed path the program uses
   (``repro.serving.use_persistent_cache``). Everything up to the first
   timed request is ``setup_s``.
4. Measures for ``--seconds``. With ``--trace 1`` the window runs under
   the profiler and the per-layer metrics are read from the trace and the
   program's counters; otherwise the end-to-end metrics are reported.
5. Compares a sample of the window's answers with the plain reference
   (``correct.py``) and prints one JSON line last on standard output.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result. It never falls back to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import spec  # noqa: E402

#: traces of ``--trace 1`` runs, a fixed directory inside the checkout.
TRACE_DIR = ".bench_trace"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell needs."""


def devices(chips: int):
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {devs}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return devs


def _trace_options():
    """Host events of the benchmark's own spans and the runtime, no
    Python function tracer (it would record every call of the loop)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def p95(values):
    """Nearest-rank 95th percentile: the smallest value with at least 95%
    of the values at or below it."""
    s = sorted(values)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


def _memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, RuntimeError):
            pass
    return max(peaks) if peaks else 0


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        root: str = spec.ROOT, devs=None, t_start: float = T_START,
        log=print) -> dict:
    """One run of ``cell``; returns the result line's object. ``devs`` are
    the devices the run reports (a chip run passes ``devices(...)``)."""
    import jax
    import drivers
    import traffic
    import correct
    from repro.serving import ServingMetrics, use_persistent_cache

    use_persistent_cache()
    config, mix = cell.config, cell.traffic
    fns = list(spec.functions(config))
    sample = drivers.Reservoir(int(config["correct"]["sample"]),
                               traffic.rng(seed, traffic.SAMPLE))
    spans = drivers.Spans(trace)
    metrics = ServingMetrics()
    counter = drivers.CompileCounter()

    # --- set-up: the entry and every shape this traffic uses
    if mix["loop"] == "closed":
        entry = drivers.ENTRIES[mix["entry"]](config)
        warm = traffic.closed_calls(mix, fns, seed, traffic.WARMUP)
        res = entry(next(warm))
        jax.block_until_ready([(r.gbest_fit, r.best_pos, r.state.pos,
                                r.state.vel) for r in res])
        del res
        calls = traffic.closed_calls(mix, fns, seed)
    elif mix["loop"] == "open" and mix["entry"] == "scheduler":
        sched = drivers.make_scheduler(config, root, metrics)
        width = sched.lane_width
        warm = traffic.closed_calls({"rows_per_call": 2 * width}, fns, seed,
                                    traffic.WARMUP)
        drivers.warm_scheduler(sched, config, next(warm))
        arrivals = traffic.open_arrivals(mix, fns, seed, seconds)
    else:
        raise ValueError(f"no driver for loop {mix['loop']!r} with entry "
                         f"{mix['entry']!r}")
    setup_s = time.perf_counter() - t_start

    # --- the measured window
    trace_dir = os.path.join(root, TRACE_DIR, cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    before = {"counters": dict(metrics.counters),
              "dispatch": (metrics.span("dispatch_us").count,
                           metrics.span("dispatch_us").total_us)}
    counter.active = True
    late, missing = [], []
    with spans("bench.window"):
        if mix["loop"] == "closed":
            done, kept, elapsed = drivers.closed_loop(entry, calls, seconds,
                                                      sample, spans)
            in_window = len(done)
        else:
            done, kept, late, missing = drivers.open_loop(
                sched, config, arrivals, seconds, sample, spans)
            elapsed = seconds
            in_window = sum(1 for d in done if d.end_s <= seconds)
    counter.active = False
    if trace:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace: written in {time.perf_counter() - t_stop!r} s")
    after = {"counters": dict(metrics.counters),
             "dispatch": (metrics.span("dispatch_us").count,
                          metrics.span("dispatch_us").total_us)}
    devs = devs if devs is not None else jax.devices()
    mem_peak = _memory_peak(devs)
    if mix["loop"] == "closed":
        del entry
    else:
        del sched
    gc.collect()

    failed = len(missing) + sum(1 for d in done if not math.isfinite(d.fit))
    attempted = len(done) + len(missing)
    if late:
        log(f"generator lateness: max {max(late)!r} s, mean "
            f"{sum(late) / len(late)!r} s over {len(late)} requests")
    if done:
        lat = sorted(1e3 * d.latency_s for d in done)
        q = {k: lat[min(len(lat) - 1, int(k / 100 * len(lat)))]
             for k in (0, 50, 90, 95)}
        log(f"latency ms: min {q[0]:.1f}, p50 {q[50]:.1f}, p90 {q[90]:.1f},"
            f" p95 {q[95]:.1f}, max {lat[-1]:.1f}; slowest "
            f"{[round(x, 1) for x in lat[-12:]]}")
    steps = after["dispatch"][0] - before["dispatch"][0]
    if steps:
        log(f"scheduler: {steps} dispatches, "
            f"{(after['dispatch'][1] - before['dispatch'][1]) / steps:.1f} "
            f"us each")
    if counter.compiles:
        log(f"compiled inside the window: {counter.compiles}")
    log(f"traces inside the window: {counter.traces}")

    out = {}
    if trace:
        out["metrics"], out["breakdown"], busy_s, window_s = _per_layer(
            cell, done, before, after, trace_dir, devs, log)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        lat = [d.latency_s for d in done]
        values = {
            "setup_s": setup_s,
            "solve_rate": in_window / elapsed,
            "latency_p95_ms": 1e3 * p95(lat) if lat else None,
        }
        out["metrics"] = {}
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is None:
                raise KeyError(f"no value for end-to-end metric {m['name']!r}")
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"window: {len(done)} solves in {elapsed!r} s, {in_window} "
            f"inside the window; setup {setup_s!r} s")

    # --- correct: the sampled answers against the reference, after the
    # window, with the program's state freed
    t_ref = time.perf_counter()
    ref = correct.reference_for(config)
    nums = correct.numbers(config, kept, ref)
    log(f"reference: {len(kept)} answers compared in "
        f"{time.perf_counter() - t_ref!r} s")
    ok, checks = correct.judge(nums, config["correct"]["limits"],
                               missing=failed)
    checks["window_compiles"] = {"value": len(counter.compiles), "limit": 0}
    if counter.compiles:
        ok = False
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    if trace:
        device.update(busy_s=busy_s, window_s=window_s)
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": out["metrics"], "device": device}
    if trace:
        result["breakdown"] = out["breakdown"]
    result["checks"] = checks
    for line in correct.check_lines(checks):
        log(line)
    return result


def _per_layer(cell, done, before, after, trace_dir, devs, log):
    """(metrics, breakdown, busy_s, window_s) of a traced window."""
    import work
    import xtrace
    t_read = time.perf_counter()
    summary = xtrace.summarize(xtrace.load(trace_dir), len(devs))
    log(f"trace: read in {time.perf_counter() - t_read!r} s")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    ops, byt = work.work(cell.config, [d.row[0] for d in done])
    ctx = {
        "config": cell.config,
        "trace": summary,
        "solves": len(done),
        "pdi": float(len(done)) * cell.config["particles"]
        * cell.config["dim"] * cell.config["iters"],
        "work": (ops, byt),
        "peak": work.peak_for(devs[0].device_kind, peaks),
        "serving": {
            "counters": {k: after["counters"].get(k, 0)
                         - before["counters"].get(k, 0)
                         for k in after["counters"]},
            "dispatch_count": after["dispatch"][0] - before["dispatch"][0],
            "dispatch_us": after["dispatch"][1] - before["dispatch"][1],
        },
        "log": log,
    }
    metrics = {}
    for m in cell.per_layer:
        v = spec.load_reader(m["name"]).read(ctx)
        if v is None:
            log(f"per-layer {m['name']}: nothing to read in this run")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    log(f"trace: busy {summary.busy_s!r} s of {summary.window_s!r} s, "
        f"kernels {summary.kernel_s!r} s")
    return (metrics, summary.breakdown(), summary.busy_s, summary.window_s)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    src = os.path.join(spec.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"no program to measure: {src}/repro is missing",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    cell = spec.load_cell(a.workload)
    try:
        devs = devices(cell.chips)
    except NoChip as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 3

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = run(cell, a.seed, a.seconds, bool(a.trace), devs=devs, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
