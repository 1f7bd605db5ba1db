"""Serving subsystem: continuous batching, AOT compile cache, metrics.

Three layers over the flush server in ``repro.launch.serve``:

* ``scheduler.ContinuousScheduler`` — persistent batched async lanes with
  chunk-boundary admission (the streaming front end).
* ``compile_cache.CompileCache`` — ``jax.export``-backed persistent AOT
  programs, so a restarted replica serves its first request with zero
  re-traces.
* ``metrics.ServingMetrics`` — queue/compile/solve latency spans
  (p50/p99), batch-fill and preemption counters, JSON snapshots.

See docs/serving.md for the architecture and the admission invariants.
"""
from .compile_cache import CompileCache, use_persistent_cache
from .metrics import LatencyStat, ServingMetrics
from .scheduler import ContinuousScheduler

__all__ = ["CompileCache", "ContinuousScheduler", "LatencyStat",
           "ServingMetrics", "use_persistent_cache"]
