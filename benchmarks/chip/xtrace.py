"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time and idle share, the Pallas kernels'
device time, the device operations that took most time, and the longest
idle gaps with what the host was doing in each.

* The window is the host span ``bench.window`` that ``cell.py`` opens
  around the measured loop; without it, the span of all device events.
* Busy time is the union of the intervals of the device's operations
  (plane ``/device:TPU:<i>``, line ``XLA Ops``) inside the window,
  averaged over the chips used.
* Kernel time is the summed duration of the operations that carry a
  kernel's name: every Pallas kernel of the program is named with
  ``KERNEL_PREFIX`` (``pallas_call(name=...)``).
* A gap is a stretch of the window in which the device ran nothing. It is
  attributed to the innermost of the benchmark's own host spans
  (``SPANS``) covering its middle, or ``idle`` where none does.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

KERNEL_PREFIX = "cupso_"
WINDOW = "bench.window"
SPANS = ("solve", "solve_many", "submit", "sched.step", "result")
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
TOP = 10

Interval = Tuple[int, int]          # [start_ns, end_ns)


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ``ProfileData``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def short_name(name: str) -> str:
    """An HLO op's instruction name without its ``%`` and numeric suffix
    (``%cupso_fused_queue_lock.1 = (f32[...]) custom-call(...)`` ->
    ``cupso_fused_queue_lock``): the name the breakdown groups by."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def is_kernel(name: str) -> bool:
    return KERNEL_PREFIX in name.split(" = ", 1)[0]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(iv: Interval, lo: int, hi: int) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of [lo, hi) that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap: Interval, spans: Sequence[Tuple[str, int, int]]) -> str:
    """The innermost span (shortest) covering the gap's middle."""
    mid = (gap[0] + gap[1]) // 2
    best = None
    for name, s, e in spans:
        if s <= mid < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "idle"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_s: float
    ops: Dict[str, float]                 # short op name -> s (chip 0)
    idle_gaps: List[Tuple[str, float]]    # the TOP longest (chip 0)

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:TOP]]}


def device_planes(pd) -> List:
    planes = [(int(m.group(1)), p) for p in pd.planes
              for m in [_DEVICE.match(p.name)] if m]
    return [p for _, p in sorted(planes, key=lambda ip: ip[0])]


def host_spans(pd, names=SPANS + (WINDOW,)) -> List[Tuple[str, int, int]]:
    out = []
    for p in pd.planes:
        if _DEVICE.match(p.name):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
    return out


def summarize(pd, chips: int = 1) -> Summary:
    planes = device_planes(pd)[:chips]
    if not planes:
        raise ValueError("the trace has no TPU device plane")
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    per_chip = []
    for p in planes:
        evs = [ev for line in p.lines if line.name == OPS_LINE
               for ev in line.events]
        per_chip.append([(ev.name, int(ev.start_ns), int(ev.end_ns))
                         for ev in evs])
    if windows:
        lo, hi = windows[0]
    else:
        allv = [(s, e) for evs in per_chip for _, s, e in evs]
        lo, hi = min(s for s, _ in allv), max(e for _, e in allv)
    busy, kern = [], []
    ops: Dict[str, float] = {}
    idle: List[Tuple[str, float]] = []
    for i, evs in enumerate(per_chip):
        ivs = [c for _, s, e in evs for c in [clip((s, e), lo, hi)] if c]
        merged = union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        k = 0.0
        for name, s, e in evs:
            c = clip((s, e), lo, hi)
            if c is None:
                continue
            if is_kernel(name):
                k += (c[1] - c[0]) * 1e-9
            if i == 0:
                key = short_name(name)
                ops[key] = ops.get(key, 0.0) + (c[1] - c[0]) * 1e-9
        kern.append(k)
        if i == 0:
            # Only the longest gaps are reported, and only they are
            # attributed: a served window has tens of thousands of gaps
            # and of spans, too many to pair up one by one.
            inner = [sp for sp in spans if sp[0] != WINDOW]
            longest = sorted(gaps(merged, lo, hi),
                             key=lambda g: g[0] - g[1])[:TOP]
            idle = [(attribute(g, inner), (g[1] - g[0]) * 1e-9)
                    for g in longest]
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / len(busy),
                   kernel_s=sum(kern) / len(kern), ops=ops, idle_gaps=idle)
