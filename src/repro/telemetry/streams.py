"""The synchronous kernels' own account of their HBM <-> VMEM traffic.

Each build of a synchronous fused kernel (``repro.kernels.pso_step``:
``fused_call``, ``fused_batch_call``, ``hetero_fused_batch_call``) records
here, under the kernel's name (its ``pallas_call`` name), what one launch
of it does:

* ``grid_steps``: the grid's steps, ``swarms x iterations x blocks``;
* ``bytes``: the bytes one launch moves between HBM and VMEM. A swarm of
  several blocks is *streamed*: every grid step copies its block of the
  positions, velocities and pbest positions ``[Dpad, block_n]`` and of the
  pbest values ``[1, block_n]`` in and back out. A swarm of one block is
  *resident*: its state comes in once and goes out once. Either way the
  gbest column ``[Dpad, 1]`` comes in and goes out once per swarm; the
  SMEM scalars (RNG counters, gbest value, counters) are not counted;
* the choices that set them: ``streamed``, ``dpad``, ``block_n``,
  ``grid`` and the element size.

The count is made from the same statics the builder lays the kernel out
with, so a change to the blocking or the residency changes it too. A
build happens once per shape (the builders run while JAX traces), so the
account costs a dict write per compiled shape and nothing per solve. The
latest build of a name wins: a process that launches one kernel at two
shapes cannot tell their launches apart by name.

``kernel_streams()`` returns the accounts so far; the benchmark's
``kernel.stream_share`` and ``kernel.step_us`` multiply them by the
launches a profiler trace holds.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Tuple

import numpy as np


class StreamAccount(NamedTuple):
    """What one launch of a kernel moves, and the layout that decides it."""
    grid_steps: int
    bytes: int
    streamed: bool
    dpad: int
    block_n: int
    grid: Tuple[int, ...]
    itemsize: int


# Process-wide like JAX's own compile caches: the builders run inside
# traces, where no caller-owned object reaches them.
_ACCOUNTS: Dict[str, StreamAccount] = {}


def record_sync(name: str, grid: Tuple[int, ...], *, swarms: int,
                streamed: bool, dpad: int, block_n: int, dtype) -> None:
    """Record the account of the synchronous kernel ``name``: grid
    ``grid`` over ``swarms`` swarms of ``[dpad, block_n]`` blocks of
    ``dtype``, their state ``streamed`` (copied in and out every grid step)
    or resident."""
    itemsize = np.dtype(dtype).itemsize
    steps = math.prod(grid)
    block = (3 * dpad + 1) * block_n * itemsize     # pos, vel, pbp, pbf
    moves = steps if streamed else swarms           # block copies each way
    gbest = swarms * dpad * itemsize
    _ACCOUNTS[name] = StreamAccount(
        grid_steps=steps, bytes=2 * (moves * block + gbest),
        streamed=streamed, dpad=dpad, block_n=block_n, grid=tuple(grid),
        itemsize=itemsize)


def kernel_streams() -> Dict[str, StreamAccount]:
    """``{kernel name: StreamAccount}`` of every synchronous kernel built
    in this process so far (the latest build of each name)."""
    return dict(_ACCOUNTS)
