"""engine.init_ms: device-idle time under the program's ``repro.init``
spans per entry call, in ms.

Engines layer (``api.py``: ``init_swarm`` / ``init_batch``, the vmapped
init's retrace included). The part of chip 0's idle gaps that the union
of the ``repro.init`` spans covers (``xphases``), over the
``repro.solve`` + ``repro.solve_many`` spans that start in the window.
Nothing to read where the program opens no such spans."""

import xphases


def read(ctx):
    return xphases.idle_ms_per_call(xphases.of(ctx), ("repro.init",))
