"""The agreement contract: how closely two programs that compute the same
PSO trajectory must agree.

An engine and its eager oracle, a batched row and its standalone solve, a
kernel and the jnp engine, a one-shard island ring and ``run_async``: each
pair takes the same steps from the same RNG counters, but as two compiled
programs. XLA:CPU chooses FMA contraction and reduction order per compiled
shape, while the chip and the oracles round op by op, so the same
trajectory computed by two programs differs by an ulp or two. These are
the limits, the benchmark's own (``benchmarks/chip/correct.py`` and the
configurations' limits), so that the tests and the chip hold the system to
one standard:

* positions and velocities within ``SWARM_TOL`` of the box span
  (``swarm_gap``);
* fitness values within ``FIT_TOL``, relative (``fit_gap``);
* where a run is long enough for an ulp to flip a pbest comparison, the
  outcome: the gbest value within ``FIT_TOL`` relative, and the reported
  value against the objective at the reported position within
  ``SELF_TOL`` (``self_gap``).

A check inside ONE compiled program (the same program run twice, RNG
determinism, the default rule against the same rule spelled out) stays
bitwise and does not use this module.
"""
from __future__ import annotations

import numpy as np

#: position or velocity gap over the box span.
SWARM_TOL = 1e-5
#: fitness value gap, ``|a - b| / max(|b|, 1)``.
FIT_TOL = 1e-5
#: reported value against the objective at the reported position,
#: relative like ``FIT_TOL``.
SELF_TOL = 1e-4

SWARM_FIELDS = ("pos", "vel", "pbest_pos", "gbest_pos")
FIT_FIELDS = ("fit", "pbest_fit", "gbest_fit")
STATE_FIELDS = SWARM_FIELDS + FIT_FIELDS


def box_span(cfg) -> float:
    """The narrowest open side of ``cfg``'s box (the strictest scale; a
    frozen dimension, ``lo == hi``, holds its particles exactly)."""
    cfg = cfg.resolved()
    sides = np.atleast_1d(np.asarray(cfg.max_pos, np.float64)
                          - np.asarray(cfg.min_pos, np.float64))
    open_sides = sides[sides > 0]
    return float(open_sides.min()) if open_sides.size else 1.0


def swarm_gap(got, want, span: float) -> float:
    a = np.asarray(got, np.float64)
    b = np.asarray(want, np.float64)
    assert a.shape == b.shape, (a.shape, b.shape)
    return float(np.max(np.abs(a - b), initial=0.0)) / span


def fit_gap(got, want) -> float:
    a = np.asarray(got, np.float64)
    b = np.asarray(want, np.float64)
    assert a.shape == b.shape, (a.shape, b.shape)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0),
                        initial=0.0))


def assert_swarm_close(got, want, span: float, msg: str = "") -> None:
    gap = swarm_gap(got, want, span)
    assert gap <= SWARM_TOL, f"{msg}: swarm gap {gap!r} > {SWARM_TOL}"


def assert_fit_close(got, want, msg: str = "") -> None:
    gap = fit_gap(got, want)
    assert gap <= FIT_TOL, f"{msg}: fit gap {gap!r} > {FIT_TOL}"


def assert_states_agree(got, want, span: float, fields=STATE_FIELDS,
                        msg: str = "") -> None:
    """Two swarm states (``SwarmState``-like, same layout) field by field:
    position-valued fields against the box, fitness-valued ones
    relative."""
    for f in fields:
        a, b = getattr(got, f), getattr(want, f)
        if f.endswith("_fit") or f == "fit":
            assert_fit_close(a, b, f"{msg} {f}")
        else:
            assert_swarm_close(a, b, span, f"{msg} {f}")


def assert_outcome(got_fit, got_pos, want_fit, objective,
                   msg: str = "") -> None:
    """The outcome form: the reported gbest value against the other
    program's, and against ``objective`` (canonical form, ``[N, D] ->
    [N]``) at the reported position."""
    assert_fit_close(got_fit, want_fit, f"{msg} gbest_fit")
    pos = np.asarray(got_pos, np.float32).reshape(1, -1)
    at = float(np.asarray(objective(pos))[0])
    gap = fit_gap(float(got_fit), at)
    assert gap <= SELF_TOL, f"{msg}: self gap {gap!r} > {SELF_TOL}"
