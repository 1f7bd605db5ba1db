"""sched.dispatch_ms: mean time of one lane dispatch in the window, in ms.

Scheduler layer (``serving/scheduler.py``). Read from the program's own
``ServingMetrics`` span ``dispatch_us`` (timed around the lane program
with ``block_until_ready`` in ``_dispatch``): its total over its count,
both taken as the window's delta. Nothing to read where no lane ran."""


def read(ctx):
    s = ctx["serving"]
    n = s["dispatch_count"]
    return s["dispatch_us"] / n / 1e3 if n else None
