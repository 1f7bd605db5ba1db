"""kernel.ns_per_pdi: device time of the Pallas kernels per
particle-dimension-iteration, in ns.

Kernel layer (``kernels/pso_step.py``). The kernels' device time is the
sum of the traced durations of the device operations named after them
(``xtrace.KERNEL_PREFIX``); it is divided by particles x D x iterations
summed over every solve of the traced window (real D and N, never the
padded ones). Nothing to read where no kernel ran."""


def read(ctx):
    k = ctx["trace"].kernel_s
    return 1e9 * k / ctx["pdi"] if k > 0 and ctx["pdi"] else None
