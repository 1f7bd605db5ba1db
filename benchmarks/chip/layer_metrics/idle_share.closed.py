"""idle_share.closed: share of the traced window in which no operation ran
on the device, in %.

Device layer. One minus the union of the device operations' intervals in
the profiler's trace over the window's length (xtrace.summarize),
averaged over the chips used."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s) if t.window_s > 0 else None
