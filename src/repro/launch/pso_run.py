"""PSO launcher — the paper's workload as the framework's serving-style
entry point.

    PYTHONPATH=src python -m repro.launch.pso_run --dim 120 \
        --particles 32768 --iters 1000 --variant queue --kernel \
        --islands 8 --exchange 50 --ckpt-dir /tmp/pso_ckpt

--kernel uses the fused Pallas queue-lock kernel (compiled on a TPU, run
in the Pallas interpreter elsewhere);
--islands N runs N shard_map islands over the first N available devices
(on a pod, particles shard over the data axis; see DESIGN.md §3).
``--islands N --variant async`` runs the asynchronous island ring: no
barrier collective at all — islands exchange their best over a neighbor
ring every --exchange iterations, with the documented staleness bound of
--sync-every iterations within an island plus N exchange rounds across
islands (core/distributed.py).

``--fitness`` accepts any problem registered with
``repro.register_problem`` (the six paper benchmarks ship registered, plus
the constrained ``sphere_simplex``/``sphere_simplex_pen``); for one-off
user objectives use the library facade ``repro.solve`` instead — see
examples/custom_objective.py.

``--constraint`` attaches constraints to the chosen fitness: expression
presets like ``"sum(x)<=1"``/``"norm(x)<=2"``/``"min(x)>=0"``/
``"sum(x)==1"`` (repeatable), or the named preset ``simplex``.
``--constraint-mode`` picks penalty (default; ``--penalty-weight``),
repair, or projection (projection needs the ``simplex`` preset — general
expressions have no automatic projection operator). The run then reports
``violation=``/``feasible=`` next to the usual gbest line. See
``repro.core.constraints`` for the mode semantics and the Deb rule.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.api import Method
from repro.core import ASYNC_SYNC_EVERY, PSOConfig, init_swarm, run
from repro.core.constraints import constrain_problem, constraint_set_from_cli
from repro.core.problem import list_problems, resolve_problem
from repro.core.distributed import (gather_swarm, init_sharded_swarm,
                                    make_distributed_run)
from repro.runtime import RunnerConfig, StepRunner
from repro.serving import use_persistent_cache
from repro.telemetry import (SPAN_NAMES, KernelCounters, TraceWriter,
                             profiler_session, span)
from repro import checkpoint as ckpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=120)
    ap.add_argument("--particles", type=int, default=32768)
    ap.add_argument("--iters", type=int, default=1000)
    ap.add_argument("--fitness", default="cubic",
                    help="registered problem name (see repro.list_problems)")
    ap.add_argument("--variant", default="queue",
                    choices=["reduction", "queue", "queue_lock", "async"])
    ap.add_argument("--sync-every", type=int, default=ASYNC_SYNC_EVERY,
                    help="async variant: iterations between gbest syncs")
    ap.add_argument("--rule", default="pso",
                    help="per-particle update rule (pso|sso|lowcost or a "
                         "custom repro.core.update_rules registration)")
    ap.add_argument("--topology", default="gbest",
                    choices=["gbest", "ring", "vonneumann"],
                    help="async variant: block-neighborhood best pull "
                         "(lbest topologies need --variant async)")
    ap.add_argument("--kernel", action="store_true",
                    help="use the fused Pallas kernel for local steps")
    ap.add_argument("--islands", type=int, default=0,
                    help="shard over devices with this exchange group")
    ap.add_argument("--exchange", type=int, default=1,
                    help="island gbest exchange interval")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N iterations (0=off)")
    ap.add_argument("--constraint", action="append", default=[],
                    metavar="SPEC",
                    help="constraint preset: 'sum(x)<=1'-style expressions "
                         "(sum|norm|norm2|min|max, <=|>=|==; repeatable) "
                         "or the named preset 'simplex'")
    ap.add_argument("--constraint-mode", default="penalty",
                    choices=["penalty", "projection", "repair"],
                    help="how constraints are enforced (core.constraints)")
    ap.add_argument("--penalty-weight", type=float, default=1000.0,
                    help="penalty mode: weight per unit violation")
    ap.add_argument("--telemetry", action="store_true",
                    help="thread the in-kernel contention counters "
                         "through the run (requires --kernel; "
                         "docs/observability.md)")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="write a Perfetto-loadable trace.json of the "
                         "run's solve chunks here")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="write a Prometheus text exposition (chunk "
                         "latency + kernel counters) here")
    ap.add_argument("--profile-dir", default="", metavar="DIR",
                    help="also capture a jax.profiler trace into DIR "
                         "(fails if the trace cannot start)")
    args = ap.parse_args()
    use_persistent_cache()

    if args.fitness not in list_problems():
        ap.error(f"unknown fitness {args.fitness!r}; registered problems: "
                 f"{', '.join(list_problems())}")
    fitness = args.fitness
    if args.constraint:
        try:
            cset = constraint_set_from_cli(args.constraint,
                                           mode=args.constraint_mode,
                                           weight=args.penalty_weight)
            fitness = constrain_problem(args.fitness, cset)
        except ValueError as e:
            ap.error(str(e))
    from repro.core.update_rules import rule_names
    if args.rule not in rule_names():
        ap.error(f"unknown update rule {args.rule!r}; "
                 f"one of {', '.join(rule_names())}")
    if args.topology != "gbest" and args.variant != "async":
        ap.error(f"--topology {args.topology} generalizes the async "
                 f"variant's block-local pull; use --variant async")
    if args.topology != "gbest" and args.islands:
        ap.error("--topology applies within one device's block grid; "
                 "drop --islands (the island ring is its own topology)")
    cfg = PSOConfig(dim=args.dim, particle_cnt=args.particles,
                    fitness=fitness, update_rule=args.rule,
                    topology=args.topology).resolved()
    if args.kernel and not args.islands and args.variant not in (
            "queue_lock", "async"):
        # only the fused queue-lock kernels exist; don't silently run
        # queue_lock semantics under a reduction/queue label
        ap.error(f"--kernel implements queue_lock/async, not "
                 f"{args.variant!r}")
    if args.kernel and args.islands and args.variant == "async":
        # the async island ring runs the jnp local loop (ROADMAP: Pallas
        # async kernel + ring composition is a TPU-hardware follow-on)
        ap.error("--kernel --islands does not support --variant async; "
                 "drop --kernel (the ring uses the jnp async local loop)")
    if args.kernel:
        # the api's rule for problems the kernels cannot compile; islands
        # run the fused queue_lock kernel as their local step
        kvariant = "async" if args.variant == "async" else "queue_lock"
        try:
            Method(variant=kvariant, backend="kernel").resolve_backend(
                cfg.problem)
        except ValueError as e:
            ap.error(f"--kernel: {e}")
    if args.telemetry and not args.kernel:
        ap.error("--telemetry counts inside the fused Pallas kernels; "
                 "add --kernel (with --variant queue_lock or async)")
    if args.telemetry and args.islands:
        ap.error("--telemetry is single-device; drop --islands")
    trace = metrics = tel = None
    if args.trace_out:
        trace = TraceWriter()
    if args.metrics_out:
        from repro.serving import ServingMetrics
        metrics = ServingMetrics()

    import contextlib
    prof = contextlib.ExitStack()
    if args.profile_dir:
        prof.enter_context(profiler_session(args.profile_dir))
    t0 = time.time()
    if args.islands:
        devs = jax.devices()
        if args.islands > len(devs):
            ap.error(f"--islands {args.islands} exceeds the {len(devs)} "
                     f"available device(s)")
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(devs[:args.islands]), ("data",))
        state = init_sharded_swarm(cfg, args.seed, mesh)
        local_step = None
        if args.kernel:
            from repro.kernels.ops import make_fused_local_step
            local_step = make_fused_local_step(iters_per_call=1)
        runner = make_distributed_run(
            cfg, mesh, iters=args.iters, variant=args.variant,
            exchange_interval=args.exchange, local_step_fn=local_step,
            sync_every=args.sync_every)
        state = runner(state)
    else:
        state = init_swarm(cfg, args.seed)
        if args.kernel:
            from repro.kernels.ops import (run_queue_lock_fused,
                                           run_queue_lock_fused_async)
            if args.variant == "async":
                step_chunk = lambda st, k: run_queue_lock_fused_async(
                    cfg, st, iters=k, sync_every=args.sync_every,
                    telemetry=args.telemetry)
            else:
                step_chunk = lambda st, k: run_queue_lock_fused(
                    cfg, st, iters=k, telemetry=args.telemetry)
        else:
            step_chunk = lambda st, k: run(cfg, st, k, args.variant,
                                           sync_every=args.sync_every)
        chunk = args.ckpt_every or args.iters
        done = 0
        while done < args.iters:
            n = min(chunk, args.iters - done)
            # the sinks time each chunk to its answer on the host
            with span(SPAN_NAMES.chunk, trace=trace, metrics=metrics,
                      metric="chunk_us",
                      event=lambda: (f"chunk @{done + n}", dict(
                          process="solver", thread="chunks", cat="solve",
                          args={"iters": n, "variant": args.variant}))):
                if args.telemetry:
                    state, cnt = step_chunk(state, n)
                    c = KernelCounters.from_array(cnt)
                    tel = c if tel is None else tel + c
                else:
                    state = step_chunk(state, n)
                if trace is not None or metrics is not None:
                    jax.block_until_ready(state.gbest_fit)
            if metrics is not None:
                metrics.inc("chunks")
            done += n
            if args.ckpt_dir:
                ckpt.save(args.ckpt_dir, done, gather_swarm(state))
    prof.close()
    gf = float(state.gbest_fit)
    dt = time.time() - t0
    extra = ""
    prob = resolve_problem(fitness)
    if prob.constrained:
        viol = prob.violation_at(state.gbest_pos)
        extra = f"violation={viol:.3g}  feasible={viol <= 0.0}  "
    print(f"gbest_fit={gf:.6g}  {extra}iters={args.iters}  "
          f"particles={args.particles}  dim={args.dim}  "
          f"wall={dt:.3f}s  ({1e6*dt/args.iters:.1f} us/iter)")
    if tel is not None:
        d = tel.as_dict()
        print("telemetry: " + "  ".join(f"{k}={v}" for k, v in d.items()))
    if trace is not None:
        trace.write(args.trace_out)
        print(f"trace: {args.trace_out}")
    if metrics is not None:
        with open(args.metrics_out, "w") as f:
            f.write(metrics.prometheus(
                kernel_counters=None if tel is None else tel.as_dict()))
        print(f"metrics: {args.metrics_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
