"""The one traffic generator: a mix file's parameters and ``--seed`` in,
the requests of a run out.

A mix file (``traffic/<mix>.json``) holds data only:

* ``loop``: ``closed`` (one client sends its next call when the last one
  has returned) or ``open`` (requests are due on a schedule, whether or
  not earlier ones have finished);
* ``entry``: the program's entry the calls go through: ``solve``,
  ``solve_many`` or ``scheduler`` (``ContinuousScheduler.submit/step``);
* ``rows_per_call`` (closed loops): solves per call; ``"campaign"`` means
  every objective of the configuration ``runs_per_function`` times;
* ``rate_per_s`` (open loops): mean arrival rate of a Poisson process.
  A window gets ``round(rate * seconds)`` arrivals whose gaps are the
  exponential distribution's quantiles at ``(i + 1/2) / (k + 1)``, scaled
  to fill the window, in an order drawn from ``schedule``;
* ``schedule`` (open loops, optional, default 0): the number the order
  of the gaps is drawn from. It is the mix's, not ``--seed``'s: every
  seed gets the same arrival times, and the seed draws which request
  (objective and solve seed) falls on each. A queue's tail swings with
  the order of its gaps far more than with anything else a seed
  changes, so a seed of its own would make a run's tail the seed's;
* ``profile`` (open loops, optional): a cycle of ``[seconds, relative
  rate]`` phases repeated from the window's start, such as
  ``[[2, 1], [3, 0]]`` for bursts of two seconds every five. The same
  gaps are laid out in the process's own time (the integral of the
  rate), so arrivals fall only where the rate is above 0, and the
  window's mean rate stays ``rate_per_s``.

Objectives are spread evenly over the configuration's list (each appears
equally often, up to one, in an order drawn from the seed), and every
solve gets its own seed below 2**31, drawn from ``--seed``.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Sequence, Tuple

import numpy as np

SEED_LIMIT = 2 ** 31 - 1
# Sub-streams of --seed, so traffic, warm-up and the sample never share
# draws; SCHEDULE is the stream of a mix's own ``schedule``.
TRAFFIC, WARMUP, SAMPLE, SCHEDULE = 0, 1, 2, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed), stream]))


Row = Tuple[str, int]          # (objective, solve seed)


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float               # seconds after the window opens
    row: Row


def _spread(functions: Sequence[str], k: int, g: np.random.Generator
            ) -> List[str]:
    names = list(np.resize(np.asarray(functions, dtype=object), k))
    return [names[i] for i in g.permutation(k)]


def closed_calls(mix: dict, functions: Sequence[str], seed: int,
                 stream: int = TRAFFIC) -> Iterator[List[Row]]:
    """Endless calls of a closed loop, each a list of rows."""
    g = rng(seed, stream)
    per = mix["rows_per_call"]
    if per == "campaign":
        names = [f for f in functions for _ in range(mix["runs_per_function"])]
    else:
        names = None
    while True:
        if names is None:
            fns = _spread(functions, int(per), g) if len(functions) > 1 \
                else list(functions) * int(per)
        else:
            fns = names
        seeds = g.integers(0, SEED_LIMIT, size=len(fns))
        yield [(f, int(s)) for f, s in zip(fns, seeds)]


def _warp(profile, seconds: float):
    """``u -> t``: a point of the window in the process's own time
    (``u`` in ``[0, 1]``, the share of the window's expected arrivals)
    to the time it falls at under the cycled ``profile``."""
    starts, ends, rates = [], [], []
    t, i = 0.0, 0
    while t < seconds:
        length, rate = (float(x) for x in profile[i % len(profile)])
        if length <= 0 or rate < 0:
            raise ValueError(f"bad phase {profile[i % len(profile)]!r}")
        if rate > 0:
            starts.append(t)
            ends.append(min(t + length, seconds))
            rates.append(rate)
        t, i = t + length, i + 1
    starts, ends, rates = map(np.asarray, (starts, ends, rates))
    if not len(rates):
        raise ValueError("the profile has no phase with a rate above 0")
    mass = np.cumsum((ends - starts) * rates)
    before = mass - (ends - starts) * rates

    def warp(u):
        y = np.asarray(u) * mass[-1]
        j = np.minimum(np.searchsorted(mass, y, side="left"), len(mass) - 1)
        return starts[j] + (y - before[j]) / rates[j]
    return warp


def open_arrivals(mix: dict, functions: Sequence[str], seed: int,
                  seconds: float, stream: int = TRAFFIC) -> List[Arrival]:
    """The due times and rows of an open loop's window."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    g = rng(seed, stream)
    k = int(round(float(mix["rate_per_s"]) * seconds))
    gaps = -np.log1p(-(np.arange(k + 1) + 0.5) / (k + 1))
    gaps = rng(int(mix.get("schedule", 0)), SCHEDULE).permutation(
        gaps * (seconds / gaps.sum()))
    due = np.cumsum(gaps)[:-1]
    if mix.get("profile"):
        due = _warp(mix["profile"], seconds)(due / seconds)
    fns = _spread(functions, k, g)
    seeds = g.integers(0, SEED_LIMIT, size=k)
    return [Arrival(float(t), (f, int(s)))
            for t, f, s in zip(due, fns, seeds)]
