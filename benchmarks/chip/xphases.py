"""Reduce the program's own host spans in a traced window, name by name.

The program opens ``jax.profiler.TraceAnnotation`` spans whose names start
with ``PROGRAM_PREFIX`` (``repro.telemetry.SPAN_NAMES``). They lie on the
profiler's host plane, on the device trace's clock. ``reduce`` reads them
from the ``.xplane.pb`` that ``xtrace.summarize`` reads, in the same window
(the host span ``bench.window``), and gives for each name
``(count, wall_s, idle_s)``:

* ``count``: its events that start in the window;
* ``wall_s``: their durations clipped to the window, summed;
* ``idle_s``: the length of the union of its intervals inside chip 0's
  device-idle gaps, found in one sweep over both sorted lists (a served
  window has tens of thousands of each, too many to pair one by one).

It also names the longest idle gaps by the innermost span covering their
middle, the program's where one is as short as the benchmark's, and
measures how much of the idle time lies under some program span. Those go
to the run's log; the result line's ``breakdown`` stays ``xtrace``'s.

A program that opens no such spans gives no phases, and the readers that
use them (``layer_metrics/engine.*``, ``sched.host_ms``) report nothing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import spec
import xtrace

PROGRAM_PREFIX = "repro."
CALLS = ("repro.solve", "repro.solve_many")     # the program's entry calls
#: where ``cell.py`` writes a ``--trace 1`` run's trace (``cell.TRACE_DIR``).
TRACE_ROOT = os.path.join(spec.ROOT, ".bench_trace")

Interval = xtrace.Interval
Span = Tuple[str, int, int]


@dataclasses.dataclass
class Phases:
    window_s: float
    idle_s: float                          # chip 0's idle time
    under_program_s: float                 # of it, under a program span
    by_name: Dict[str, Tuple[int, float, float]]  # count, wall_s, idle_s
    idle_gaps: List[Tuple[str, float]]     # the TOP longest, named


def covered(intervals: Sequence[Interval], free: Sequence[Interval]) -> int:
    """Length of ``intervals`` (merged) inside ``free`` (sorted, disjoint),
    in one sweep over both."""
    total, j = 0, 0
    for s, e in xtrace.union(intervals):
        while j < len(free) and free[j][1] <= s:
            j += 1
        k = j
        while k < len(free) and free[k][0] < e:
            total += min(e, free[k][1]) - max(s, free[k][0])
            k += 1
    return total


def attribute(gap: Interval, spans: Sequence[Span]) -> str:
    """The innermost span (shortest) covering the gap's middle; of two as
    long, the program's."""
    mid = (gap[0] + gap[1]) // 2
    best = None
    for name, s, e in spans:
        if s <= mid < e:
            key = (e - s, not name.startswith(PROGRAM_PREFIX))
            if best is None or key < best[0]:
                best = (key, name)
    return best[1] if best else "idle"


def host_spans(pd) -> List[Span]:
    """The benchmark's host spans (``xtrace.SPANS``, ``xtrace.WINDOW``) and
    the program's, from every plane but the devices'."""
    device = {p.name for p in xtrace.device_planes(pd)}
    names = set(xtrace.SPANS) | {xtrace.WINDOW}
    out = []
    for p in pd.planes:
        if p.name in device:
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name in names or ev.name.startswith(PROGRAM_PREFIX):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
    return out


def by_name(spans: Sequence[Span], free: Sequence[Interval], lo: int,
            hi: int) -> Dict[str, Tuple[int, float, float]]:
    """``{name: (count, wall_s, idle_s)}`` of the program's spans in the
    window ``[lo, hi)``, ``free`` being the device's idle gaps in it."""
    ivs: Dict[str, List[Interval]] = {}
    count: Dict[str, int] = {}
    for name, s, e in spans:
        if not name.startswith(PROGRAM_PREFIX):
            continue
        if lo <= s < hi:
            count[name] = count.get(name, 0) + 1
        c = xtrace.clip((s, e), lo, hi)
        if c is not None:
            ivs.setdefault(name, []).append(c)
    return {name: (count.get(name, 0), sum(e - s for s, e in iv) * 1e-9,
                   covered(iv, free) * 1e-9)
            for name, iv in sorted(ivs.items())}


def reduce(pd) -> Phases:
    """The program's phases in chip 0's window of the trace ``pd``."""
    planes = xtrace.device_planes(pd)
    if not planes:
        raise ValueError("the trace has no TPU device plane")
    ops = [(int(ev.start_ns), int(ev.end_ns)) for line in planes[0].lines
           if line.name == xtrace.OPS_LINE for ev in line.events]
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == xtrace.WINDOW]
    if windows:
        lo, hi = windows[0]
    else:
        lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    busy = xtrace.union([c for iv in ops
                         for c in [xtrace.clip(iv, lo, hi)] if c])
    free = xtrace.gaps(busy, lo, hi)
    inner = [sp for sp in spans if sp[0] != xtrace.WINDOW]
    program = [xtrace.clip((s, e), lo, hi) for n, s, e in inner
               if n.startswith(PROGRAM_PREFIX)]
    longest = sorted(free, key=lambda g: g[0] - g[1])[:xtrace.TOP]
    return Phases(
        window_s=(hi - lo) * 1e-9,
        idle_s=sum(e - s for s, e in free) * 1e-9,
        under_program_s=covered([c for c in program if c], free) * 1e-9,
        by_name=by_name(inner, free, lo, hi),
        idle_gaps=[(attribute(g, inner), (g[1] - g[0]) * 1e-9)
                   for g in longest])


def log_lines(ph: Phases) -> List[str]:
    share = ph.under_program_s / ph.idle_s if ph.idle_s > 0 else 0.0
    out = [f"program spans: {ph.under_program_s!r} s of {ph.idle_s!r} s "
           f"device-idle under a program span ({100 * share!r}%)"]
    out += [f"program span {n}: count {c}, wall {w!r} s, idle {i!r} s"
            for n, (c, w, i) in ph.by_name.items()]
    out.append("longest idle gaps, named with the program's spans: "
               + ", ".join(f"{n} {s!r}" for n, s in ph.idle_gaps))
    return out


_CACHE: Dict[Tuple[str, int], Phases] = {}


def of(ctx, root: str = TRACE_ROOT) -> Optional[Phases]:
    """The phases of the run that ``ctx`` describes: ``ctx["phases"]``
    where the caller put them there, else reduced once from the newest
    trace under ``root`` (``cell.py`` writes it there and removes it only
    after its readers ran). None where there is no trace, where its window
    is not the one ``ctx["trace"]`` summarized, or where it holds no
    program span."""
    if "phases" in ctx:
        ph = ctx["phases"]
    else:
        files = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            return None
        path = max(files, key=os.path.getmtime)
        key = (path, os.stat(path).st_mtime_ns)
        if key not in _CACHE:
            from jax.profiler import ProfileData
            ph = reduce(ProfileData.from_file(path))
            _CACHE.clear()
            _CACHE[key] = ph
            if "log" in ctx:
                for line in log_lines(ph):
                    ctx["log"](line)
        ph = _CACHE[key]
    if ph is None or not ph.by_name:
        return None
    summary = ctx.get("trace")
    if summary is not None and abs(summary.window_s - ph.window_s) > 1e-9:
        return None
    return ph


def idle_ms_per_call(ph: Optional[Phases], names: Sequence[str]):
    """Device-idle ms under the program spans ``names``, per entry call
    (``CALLS`` spans that start in the window); None where the window has
    no entry call or none of those spans."""
    if ph is None:
        return None
    calls = sum(ph.by_name[n][0] for n in CALLS if n in ph.by_name)
    got = [ph.by_name[n][2] for n in names if n in ph.by_name]
    return 1e3 * sum(got) / calls if calls and got else None
