"""In-program solver telemetry (DESIGN: the observability layer).

The paper's contention story — queue-lock publication vs reduction memory
traffic — is invisible from outside a fused kernel: the host sees one
dispatch, not the per-iteration gbest races it resolved. This package
makes every engine report what it actually did, at five levels:

1. **Kernel counters** (``counters``): the fused Pallas kernels optionally
   emit per-swarm int32 event counts (queue-best updates, gbest
   publications, per-block pbest improvements) accumulated in SMEM across
   the whole grid. Off by default — the counter code is Python-gated at
   trace time, so a telemetry-off program is byte-identical to the
   pre-telemetry jaxpr and every bit-exactness pin stands untouched.
   Validated against the eager oracles in ``repro.kernels.ref``
   (tests/test_telemetry.py).

2. **Convergence traces**: ``Method(record_history=True)`` now covers all
   engines — jnp single-swarm (per-iteration), the kernel backend
   (chunk-boundary gbest readbacks), ``solve_many`` + heterogeneous
   batches (per-row series), and the continuous scheduler's lanes
   (per-row samples at every dispatched chunk). See
   ``repro.api`` / ``repro.serving.scheduler``.

3. **Exporters** (``trace``, ``prometheus``): a Chrome/Perfetto
   ``trace.json`` writer for serving spans, lane dispatches and solve
   chunks (load the file in https://ui.perfetto.dev), and a Prometheus
   text-exposition renderer for ``ServingMetrics.snapshot()`` plus kernel
   counters. Reachable from ``repro.solve_stream`` (``trace=`` /
   ``trace_path=``), ``SolveServer`` (``.prometheus()``), and the
   ``pso_run`` CLI (``--telemetry`` / ``--trace-out`` /
   ``--metrics-out``).

4. **Profiler spans** (``trace.span``, names in ``SPAN_NAMES``): every
   layer boundary of the engines and the scheduler opens a
   ``jax.profiler.TraceAnnotation``, recorded on the device trace's
   clock whenever a profiler session runs, and nothing otherwise.
   ``init_traces()`` counts the traces of the compiled swarm-init
   programs: how often their cache missed.

5. **Kernel stream accounts** (``streams``): each build of a synchronous
   fused kernel records, under its kernel name, the grid steps of one
   launch and the bytes one launch moves between HBM and VMEM
   (``kernel_streams()``), counted from the layout the builder chose:
   state streamed block by block every grid step, or resident.

docs/observability.md documents the counter semantics and trace schema.
"""
from .counters import (COUNTER_NAMES, SLOTS_PER_SWARM, KernelCounters,
                       zero_counts)
from .prometheus import prometheus_text
from .streams import StreamAccount, kernel_streams
from .trace import SPAN_NAMES, TraceWriter, profiler_session, span


def init_traces() -> int:
    """Traces of the compiled swarm-init programs so far in this process
    (``repro.core.pso.init_traces``): one per config and shape, so a
    count that grows with the seeds served means the init cache misses."""
    from repro.core.pso import init_traces as count
    return count()


__all__ = [
    "init_traces",
    "kernel_streams",
    "StreamAccount",
    "COUNTER_NAMES",
    "SLOTS_PER_SWARM",
    "KernelCounters",
    "zero_counts",
    "prometheus_text",
    "SPAN_NAMES",
    "TraceWriter",
    "profiler_session",
    "span",
]
