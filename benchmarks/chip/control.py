#!/usr/bin/env python3
"""Readings that the limits of ``correct.py`` are set from.

    python3 benchmarks/chip/control.py --config cec05-d10 --seeds 3 \\
        --sound 2

For each seed it draws the sample a cell run would compare (``sample``
rows of the configuration, from the traffic generator) and prints one JSON
line per kind of answer, with the numbers ``correct.numbers`` reads and
whether ``correct.judge`` accepts them:

* ``control``: the reference itself in the program's place, computed in
  bfloat16, the next precision below the configuration's float32;
* ``unchanged``: the reference's initial swarm, as a solve that returns
  its state without a step would report it;
* ``half``: half of the sample answered from the initial swarm, as a batch
  of which half was left out;
* ``altered_pos`` and ``altered_fit``: the reference's answers with the
  gbest position moved by a thousandth (scaled by 0.999 and shifted by
  1e-3), or the gbest value scaled by 0.999, as an answer altered where
  it is produced;
* ``one_wrong``: the reference's answers with only the first one's
  position altered, as one lane slot or one row of a batch gone wrong.

``--sound N`` also runs the program itself through the configuration's
batched entry (``repro.solve`` for one swarm, else ``solve_many`` with N
calls of the whole campaign) and prints the numbers of every answer it
gave, over all of them: the lower readings, and how often a sound answer
leaves the reference's path.

The benchmark's own runs never call this; it runs on the chip at the
cells' own sizes, and a test runs it on the CPU at a small size.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import correct  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402


@dataclasses.dataclass
class Answer:
    row: tuple
    fit: float
    pos: np.ndarray
    swarm: Optional[tuple] = None


def sample_rows(config: dict, seed: int) -> List[tuple]:
    """The rows a run with ``--seed`` could sample: ``sample`` solves of
    the configuration's objectives, spread evenly."""
    k = int(config["correct"]["sample"])
    fns = list(spec.functions(config))
    return next(traffic.closed_calls({"rows_per_call": k}, fns, seed,
                                     traffic.SAMPLE))


def from_reference(ref, rows, swarm: bool) -> List[Answer]:
    out = []
    for fn, seed in rows:
        a = ref.solve(fn, seed)
        out.append(Answer((fn, seed), float(a.gbest_fit), a.gbest_pos,
                          (a.pos, a.vel) if swarm else None))
    return out


def readings(config: dict, seed: int, ref32, ref16, ref0) -> List[dict]:
    rows = sample_rows(config, seed)
    swarm = "swarm_gap" in config["correct"]["limits"]
    good = from_reference(ref32, rows, swarm)
    init = from_reference(ref0, rows, swarm)
    half = len(rows) // 2
    moved = [dataclasses.replace(a, pos=a.pos * 0.999 + 1e-3) for a in good]
    kinds = {
        "control": from_reference(ref16, rows, swarm),
        "unchanged": init,
        "half": good[:half] + init[half:],
        "altered_pos": moved,
        "one_wrong": moved[:1] + good[1:],
        "altered_fit": [dataclasses.replace(a, fit=a.fit * 0.999)
                        for a in good],
    }
    out = []
    for kind, answers in kinds.items():
        nums = correct.numbers(config, answers, ref32)
        ok, _ = correct.judge(nums, config["correct"]["limits"])
        out.append(dict(kind=kind, seed=seed, correct=ok, **nums))
    return out


def sound(config: dict, calls: int, seed: int, ref32,
          lanes: bool = False) -> dict:
    """Every answer of ``calls`` calls of the program's batched entry, held
    to the reference one by one. ``lanes`` sends the same rows through the
    serving scheduler instead."""
    import drivers
    fns = list(spec.functions(config))
    if lanes:
        mix = {"rows_per_call": "campaign", "runs_per_function": 25}
        gen = traffic.closed_calls(mix, fns, seed)
        rows = [r for _ in range(calls) for r in next(gen)]
        sched = drivers.make_scheduler(config, spec.ROOT)
        res = sched.run(drivers.scheduler_requests(config, rows))
        answers = [Answer(row, r.gbest_fit, r.gbest_pos)
                   for row, r in zip(rows, res)]
        nums = correct.numbers(config, answers, ref32)
        ok, _ = correct.judge(nums, config["correct"]["limits"])
        return dict(kind="sound-lanes", seed=seed, answers=len(answers),
                    correct=ok, **nums)
    if len(fns) == 1:
        entry = drivers.SolveEntry(config)
        mix = {"rows_per_call": 1}
    else:
        entry = drivers.SolveManyEntry(config)
        mix = {"rows_per_call": "campaign", "runs_per_function": 25}
    gen = traffic.closed_calls(mix, fns, seed)
    answers = []
    for _ in range(calls):
        rows = next(gen)
        for row, r in zip(rows, entry(rows)):
            sw = (np.asarray(r.state.pos), np.asarray(r.state.vel)) \
                if len(fns) == 1 else None
            answers.append(Answer(row, r.gbest_fit, r.best_pos, sw))
    nums = correct.numbers(config, answers, ref32)
    ok, _ = correct.judge(nums, config["correct"]["limits"])
    return dict(kind="sound", seed=seed, answers=len(answers), correct=ok,
                **nums)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2 ** 31 + 7)
    p.add_argument("--sound", type=int, default=0)
    p.add_argument("--lanes", type=int, default=0)
    a = p.parse_args(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from repro.serving import use_persistent_cache
    use_persistent_cache()
    config = spec.load_config(a.config)
    ref32 = correct.reference_for(config)
    ref16 = correct.reference_for(config, dtype="bfloat16")
    ref0 = correct.reference_for(dict(config, iters=0))
    for i in range(a.seeds):
        for line in readings(config, a.first_seed + i, ref32, ref16, ref0):
            print(json.dumps(line), flush=True)
    if a.sound:
        print(json.dumps(sound(config, a.sound, a.first_seed, ref32)),
              flush=True)
    if a.lanes:
        print(json.dumps(sound(config, a.lanes, a.first_seed + 1, ref32,
                               lanes=True)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
