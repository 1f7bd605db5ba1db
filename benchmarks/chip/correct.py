"""How ``correct`` is decided: the answers the window produced, held to the
plain reference (``reference.py``) for the same objective and seed.

A sample of the window's answers, drawn from ``--seed``, is compared once
the window has closed. Per answer:

* ``path``: the largest coordinate gap between the reported gbest position
  and the reference's, over the box width. A sound program takes the
  reference's steps, so the gap is 0; an answer whose gap exceeds
  ``PATH_TOL`` has left the reference's path (diverged).
* ``fit``: relative gap between the reported gbest value and the
  reference's, on answers that kept the path (the two objective codes may
  round the final sum differently by an ulp or two).
* ``self``: relative gap between the reported value and the objective at
  the reported position in float64: what the answer says about itself.
* ``swarm`` (where the entry returns the swarm): the largest gap of any
  particle's position or velocity from the reference's, over the box.

The numbers compared are, over the sample: ``diverged_share`` (answers off
the path), and the largest ``fit_gap``, ``self_gap`` and ``swarm_gap``.
Each has its own limit in the configuration's file; how each limit was set
is in PERF.md.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from reference import Reference, objective64

#: a coordinate gap above this share of the box is a different path.
PATH_TOL = 1e-6


def reference_for(config: dict, dtype: Optional[str] = None) -> Reference:
    fns = {k: (float(v[0]), float(v[1]))
           for k, v in config["functions"].items()}
    return Reference(dim=config["dim"], particles=config["particles"],
                     iters=config["iters"],
                     blocks=config["semantics"]["blocks"], functions=fns,
                     w=config["w"], c1=config["c1"], c2=config["c2"],
                     dtype=dtype or config["dtype"])


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1.0)


def numbers(config: dict, sample: Sequence, ref: Reference
            ) -> Dict[str, Optional[float]]:
    """The compared numbers over ``sample`` (objects with ``row``, ``fit``,
    ``pos`` and ``swarm``)."""
    boxes = {k: float(v[1]) - float(v[0])
             for k, v in config["functions"].items()}
    diverged, fits, selfs, swarms = 0, [], [], []
    for d in sample:
        fn, seed = d.row
        box = boxes[fn]
        pos = np.asarray(d.pos, np.float64)
        if not (math.isfinite(d.fit) and np.all(np.isfinite(pos))):
            diverged += 1
            selfs.append(math.inf)
            continue
        a = ref.solve(fn, seed)
        selfs.append(_rel(d.fit, objective64(fn, pos)))
        if np.max(np.abs(pos - a.gbest_pos)) / box > PATH_TOL:
            diverged += 1
            continue
        fits.append(_rel(d.fit, float(a.gbest_fit)))
        if d.swarm is not None:
            p, v = (np.asarray(x, np.float64) for x in d.swarm)
            swarms.append(max(np.max(np.abs(p - a.pos)),
                              np.max(np.abs(v - a.vel))) / box)
    return {
        "diverged_share": diverged / len(sample) if sample else None,
        "fit_gap": max(fits) if fits else None,
        "self_gap": max(selfs) if selfs else None,
        "swarm_gap": max(swarms) if swarms else None,
    }


def judge(nums: Dict[str, Optional[float]], limits: Dict[str, float],
          missing: int = 0) -> Tuple[bool, Dict[str, dict]]:
    """(correct, {name: {value, limit}}). Every limited number that the
    run could read has to be within its limit; ``diverged_share`` and
    ``self_gap`` are always readable, and an answer that never came fails.
    """
    checks: Dict[str, dict] = {}
    ok = missing == 0
    for name, limit in limits.items():
        v = nums.get(name)
        checks[name] = {"value": v, "limit": limit}
        if v is None:
            if name in ("diverged_share", "self_gap"):
                ok = False
            continue
        if not v <= limit:
            ok = False
    checks["missing"] = {"value": missing, "limit": 0}
    return ok, checks


def check_lines(checks: Dict[str, dict]) -> List[str]:
    return [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
            for k, c in checks.items()]
