"""The kernels' HBM <-> VMEM stream in a traced window: the program's own
account of each launch, times the launches the device trace holds.

The program records, for each synchronous kernel it builds, the grid steps
of one launch and the bytes one launch moves between HBM and VMEM
(``repro.telemetry.kernel_streams()``, keyed by the kernel's name). The
trace says how often each kernel ran in the window and for how long: its
events on chip 0's ``XLA Ops`` line whose short name (``xtrace.short_name``)
is the kernel's, counted where they start inside the window (the host span
``bench.window``), with their whole device durations. Warm-up launches end
before the window opens and are not counted.

``stream(ctx)`` sums both over the kernels that have an account and ran:
``(bytes, grid_steps, seconds, launches)``. None where the program keeps no
account (a program without ``kernel_streams``), or no accounted kernel ran
in the window; the readers ``kernel.stream_share`` and ``kernel.step_us``
then report nothing.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, NamedTuple, Optional, Tuple

import xphases
import xtrace


class Stream(NamedTuple):
    bytes: float
    grid_steps: int
    seconds: float
    launches: int


def accounts(ctx) -> Optional[Dict[str, Tuple[int, int]]]:
    """``{kernel name: (grid_steps, bytes)}`` of one launch of each kernel:
    ``ctx["streams"]`` where the caller put them there, else the program's
    own; None where the program keeps none."""
    if "streams" in ctx:
        return ctx["streams"]
    try:
        from repro.telemetry import kernel_streams
    except ImportError:
        return None
    return {name: (a.grid_steps, a.bytes)
            for name, a in kernel_streams().items()}


def _window(pd) -> Optional[Tuple[int, int]]:
    spans = xtrace.host_spans(pd, (xtrace.WINDOW,))
    return (spans[0][1], spans[0][2]) if spans else None


def launches(pd, names) -> Dict[str, Tuple[int, float]]:
    """``{name: (launches, device seconds)}`` of the kernels ``names`` on
    chip 0 in the window of the trace ``pd`` (without a ``bench.window``
    span, the span of chip 0's events)."""
    planes = xtrace.device_planes(pd)
    if not planes:
        raise ValueError("the trace has no TPU device plane")
    evs = [(xtrace.short_name(ev.name), int(ev.start_ns), int(ev.end_ns))
           for line in planes[0].lines if line.name == xtrace.OPS_LINE
           for ev in line.events]
    if not evs:
        return {}
    lo, hi = _window(pd) or (min(s for _, s, _ in evs),
                             max(e for _, _, e in evs))
    out: Dict[str, Tuple[int, float]] = {}
    for name, s, e in evs:
        if name in names and lo <= s < hi:
            n, t = out.get(name, (0, 0.0))
            out[name] = (n + 1, t + (e - s) * 1e-9)
    return out


_CACHE: Dict[tuple, tuple] = {}


def _traced(ctx, names, root: str) -> Optional[Dict[str, Tuple[int, float]]]:
    """The launches of ``names`` in the newest trace under ``root``, read
    once; None where there is none or its window is not the one
    ``ctx["trace"]`` summarized."""
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    key = (path, os.stat(path).st_mtime_ns, tuple(sorted(names)))
    if key not in _CACHE:
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        _CACHE.clear()
        _CACHE[key] = (_window(pd), launches(pd, names))
    window, got = _CACHE[key]
    summary = ctx.get("trace")
    if (summary is not None and window is not None
            and abs(summary.window_s - (window[1] - window[0]) * 1e-9)
            > 1e-9):
        return None
    return got


def stream(ctx, root: str = xphases.TRACE_ROOT) -> Optional[Stream]:
    """The window's accounted kernel launches, summed (see the module's
    docstring); ``ctx["launches"]`` stands for the trace where present."""
    acc = accounts(ctx)
    if not acc:
        return None
    got = ctx["launches"] if "launches" in ctx else _traced(ctx, acc, root)
    if not got:
        return None
    byt, steps, secs, n = 0.0, 0, 0.0, 0
    for name, (count, seconds) in got.items():
        if name in acc and count:
            per_steps, per_bytes = acc[name]
            byt += float(count) * per_bytes
            steps += count * per_steps
            secs += seconds
            n += count
    return Stream(byt, steps, secs, n) if n and secs > 0 else None
