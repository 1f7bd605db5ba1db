"""sched.host_ms: the scheduler step's host time outside its lane
dispatches, per step, in ms.

Scheduler layer (``serving/scheduler.py``: admission, tail ejection,
harvest of finished rows and bookkeeping). The summed durations of the
program's ``repro.sched.step`` spans less those of its
``repro.sched.dispatch`` spans (each clipped to the window,
``xphases``), over the ``repro.sched.step`` spans that start in the
window. Nothing to read where the program opens no such spans."""

import xphases


def read(ctx):
    ph = xphases.of(ctx)
    step = ph.by_name.get("repro.sched.step") if ph else None
    if not step or not step[0]:
        return None
    dispatch = ph.by_name.get("repro.sched.dispatch", (0, 0.0, 0.0))
    return 1e3 * (step[1] - dispatch[1]) / step[0]
