"""The work a solve needs, counted from a configuration's real shapes.

The count is of the algorithm, not of an implementation, so the same
solve counts the same whatever engine or kernel runs it:

* operations: the paper's update (Eq. 1-2) and the objective, per
  particle-dimension and iteration, plus the pbest fold and the queue
  membership test per particle and iteration. The generator counts as the
  two uniform values it yields per particle-dimension and iteration, not
  as the integer operations of its hash, so a cheaper generator shows as a
  faster kernel and not as less work. A transcendental counts as one
  operation.
* bytes: the compulsory traffic, the swarm state read once and written
  once per solve (positions, velocities and pbest positions ``[N, D]``,
  pbest values ``[N]``, the gbest ``[D]`` and its value). Real ``D`` and
  ``N``: no padded sublanes or lanes, and no stream per iteration, which a
  kernel that keeps its block resident does not pay.

``FITNESS_MIX`` is copied from ``src/repro/roofline/pso_cost.py``
(``FITNESS_MIX``, counted there from the objectives' jnp source): per
particle-dimension adds/multiplies and transcendentals, then the same per
particle (the reduction's tail).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

#: objective -> (flops/dim, flops/particle, transcendentals/dim,
#: transcendentals/particle), from roofline/pso_cost.py FITNESS_MIX.
FITNESS_MIX: Dict[str, Tuple[float, float, float, float]] = {
    "cubic": (9.0, 0.0, 0.0, 0.0),
    "sphere": (2.0, 1.0, 0.0, 0.0),
    "rosenbrock": (8.0, 1.0, 0.0, 0.0),
    "griewank": (4.0, 4.0, 1.0, 0.0),
    "rastrigin": (5.0, 3.0, 1.0, 0.0),
    "ackley": (4.0, 7.0, 1.0, 3.0),
}

#: w v + c1 r1 (p - x) + c2 r2 (g - x): 5 mul + 4 add; clip v (2); x + v
#: (1); clip x (2); pbest position select (1).
ADVANCE_OPS = 15.0
#: uniform values drawn per particle-dimension per iteration (r1, r2).
RNG_VALUES = 2.0
#: pbest compare + select (2) and the queue membership compare (1).
PER_PARTICLE_OPS = 3.0


def solve_ops(fn: str, particles: int, dim: int, iters: int) -> float:
    fd, fp, td, tp = FITNESS_MIX[fn]
    per_pd = ADVANCE_OPS + RNG_VALUES + fd + td
    per_p = PER_PARTICLE_OPS + fp + tp
    return float(iters) * particles * (dim * per_pd + per_p)


def solve_bytes(particles: int, dim: int, itemsize: int = 4) -> float:
    state = 3 * particles * dim + particles + dim + 1
    return 2.0 * itemsize * state


def work(config: dict, fns: Iterable[str]) -> Tuple[float, float]:
    """(operations, bytes) of one solve of each objective in ``fns``."""
    n, d, t = config["particles"], config["dim"], config["iters"]
    item = 2 if config["dtype"] == "bfloat16" else 4
    ops = byt = 0.0
    for fn in fns:
        ops += solve_ops(fn, n, d, t)
        byt += solve_bytes(n, d, item)
    return ops, byt


def roofline(ops: float, byt: float, seconds: float, peak: dict):
    """(share of the roofline in %, the bound that sets it): the least time
    the chip could take, the larger of ops over peak FLOP/s and bytes over
    peak bytes/s, divided by the time taken."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = byt / peak["hbm_bytes_per_s"]
    least, bound = (t_ops, "flops") if t_ops >= t_mem else (t_mem, "hbm")
    return 100.0 * least / seconds, bound


def peak_for(kind: str, table: dict) -> dict:
    """The peaks of a device kind; an unknown kind is an error."""
    try:
        return table["devices"][kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table['devices'])}") from None
