"""kernel.step_us: the kernels' device time per grid step, in us.

Kernel layer (``kernels/pso_step.py``). The traced device time of the
window's launches of the synchronous kernels that the program keeps an
account of, over their grid steps (each launch's steps from
``repro.telemetry.kernel_streams()``, times the launches on chip 0 in the
window; ``xstream``). A grid step advances one block of one swarm by one
iteration. Nothing to read where the program keeps no account or no
accounted kernel ran in the window."""

import xstream


def read(ctx):
    s = xstream.stream(ctx)
    return 1e6 * s.seconds / s.grid_steps if s is not None else None
