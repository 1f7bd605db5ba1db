"""sched.batch_fill: share of the dispatched lane slots that held a
request, in %.

Scheduler layer. Read from the program's ``ServingMetrics`` counters
``lane_active_slots`` over ``lane_slots``, both as the window's delta.
Nothing to read where no lane ran."""


def read(ctx):
    c = ctx["serving"]["counters"]
    slots = c.get("lane_slots", 0)
    return 100.0 * c.get("lane_active_slots", 0) / slots if slots else None
