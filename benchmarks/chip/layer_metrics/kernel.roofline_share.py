"""kernel.roofline_share: the kernels' share of their roofline, in %.

Kernel layer. The least time the chip could take for the window's solves
(the larger of their operations over peak FLOP/s and their compulsory
bytes over peak HBM bytes/s, ``work.py`` and ``peaks.json``) over the
kernels' traced device time. The bound that sets it goes to the log.
Nothing to read where no kernel ran."""

import work


def read(ctx):
    k = ctx["trace"].kernel_s
    if k <= 0:
        return None
    ops, byt = ctx["work"]
    share, bound = work.roofline(ops, byt, k, ctx["peak"])
    ctx["log"](f"kernel.roofline_share: bound by {bound} ({ops!r} ops, "
               f"{byt!r} bytes, {k!r} s of kernels)")
    return share
