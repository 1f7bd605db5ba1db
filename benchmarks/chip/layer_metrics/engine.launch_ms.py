"""engine.launch_ms: device-idle time under the program's
``repro.prepare`` and ``repro.launch`` spans per entry call, in ms.

Engines layer (``api.py``: problem, method and config resolution, then
the segment runners up to the enqueue of the device work). The part of
chip 0's idle gaps that each name's spans cover (``xphases``), summed
over the two names, over the ``repro.solve`` + ``repro.solve_many``
spans that start in the window. Nothing to read where the program opens
no such spans."""

import xphases


def read(ctx):
    return xphases.idle_ms_per_call(xphases.of(ctx),
                                    ("repro.prepare", "repro.launch"))
