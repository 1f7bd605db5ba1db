"""kernel.stream_share: the bytes the window's kernel launches moved
between HBM and VMEM, over their device time, as a share of the chip's
HBM bandwidth, in %.

Kernel layer (``kernels/pso_step.py``). The bytes are the program's own
account of each launch of a synchronous kernel (``repro.telemetry
.kernel_streams()``: the state blocks each grid step copies in and out, or
a resident swarm's state once) times the launches of that kernel on chip 0
in the traced window; the time is those launches' traced device time
(``xstream``). Over ``peaks.json``'s HBM bytes/s: the share of its
bandwidth roofline the kernel reaches on the bytes it really moves.
Nothing to read where the program keeps no account or no accounted kernel
ran in the window."""

import xstream


def read(ctx):
    s = xstream.stream(ctx)
    if s is None:
        return None
    ctx["log"](f"kernel.stream_share: {s.bytes!r} bytes in {s.launches} "
               f"launches, {s.seconds!r} s of kernels")
    return 100.0 * s.bytes / s.seconds / ctx["peak"]["hbm_bytes_per_s"]
