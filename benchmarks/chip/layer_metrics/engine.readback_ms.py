"""engine.readback_ms: device-idle time under the program's
``repro.rows`` and ``repro.readback`` spans per entry call, in ms.

Engines layer (``api.py``: ``solve_many``'s split of the batch into
per-row ``Result``s, and the device-to-host reads of ``Result.gbest_fit``,
``best_fit`` and ``best_pos``). The part of chip 0's idle gaps that each
name's spans cover (``xphases``), summed over the two names, over the
``repro.solve`` + ``repro.solve_many`` spans that start in the window.
Nothing to read where the program opens no such spans."""

import xphases


def read(ctx):
    return xphases.idle_ms_per_call(xphases.of(ctx),
                                    ("repro.rows", "repro.readback"))
