"""The seeded traffic generator and the tail statistic."""
import collections
import os

import numpy as np
import pytest

import cell
import traffic

FNS = ["sphere", "rosenbrock", "griewank", "ackley", "rastrigin"]
BIG_SEED = 2 ** 31 + 12345          # seeds beyond 32 signed bits


def test_open_arrivals_are_deterministic_per_seed():
    mix = {"rate_per_s": 40.0}
    a = traffic.open_arrivals(mix, FNS, BIG_SEED, 10.0)
    b = traffic.open_arrivals(mix, FNS, BIG_SEED, 10.0)
    c = traffic.open_arrivals(mix, FNS, BIG_SEED + 1, 10.0)
    assert a == b
    assert a != c
    # the seed draws the requests; the arrival times are the mix's
    assert [x.due_s for x in a] == [x.due_s for x in c]
    assert [x.row for x in a] != [x.row for x in c]


@pytest.mark.parametrize("seed", [1, 77, BIG_SEED])
def test_open_arrivals_count_gap_mean_and_mix(seed):
    rate, seconds = 40.0, 25.0
    arr = traffic.open_arrivals({"rate_per_s": rate}, FNS, seed, seconds)
    # every seed gets the same amount of work
    assert len(arr) == 1000
    due = np.array([a.due_s for a in arr])
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < seconds
    # k + 1 gaps fill the window: from its start to the first arrival,
    # between arrivals, and from the last to its end
    gaps = np.diff(np.concatenate([[0.0], due, [seconds]]))
    # Poisson: exponential gaps of mean and spread 1/rate
    assert abs(gaps.mean() * rate - 1.0) < 0.01
    assert abs(np.std(gaps) * rate - 1.0) < 0.05
    # every seed has the same gaps in the same order; another schedule
    # has the same set of gaps, in another order
    other = [a.due_s for a in traffic.open_arrivals(
        {"rate_per_s": rate}, FNS, seed + 1, seconds)]
    assert other == list(due)
    moved = [a.due_s for a in traffic.open_arrivals(
        {"rate_per_s": rate, "schedule": 1}, FNS, seed, seconds)]
    gaps2 = np.diff(np.concatenate([[0.0], moved, [seconds]]))
    assert not np.allclose(gaps, gaps2)
    assert np.allclose(np.sort(gaps), np.sort(gaps2))
    # functions spread evenly, every solve seed below 2**31
    counts = collections.Counter(a.row[0] for a in arr)
    assert counts == {f: 200 for f in FNS}
    assert all(0 <= a.row[1] < 2 ** 31 for a in arr)


@pytest.mark.parametrize("seed", [3, BIG_SEED])
def test_burst_profile_is_data_only(seed, tmp_path):
    """On/off bursts come from a mix file alone: the same number of
    arrivals at the same mean rate, none in an off phase, and the same
    gaps in the process's own time for every seed."""
    import json
    import spec
    os.makedirs(tmp_path / "traffic")
    mix = {"loop": "open", "entry": "scheduler", "rate_per_s": 6.0,
           "profile": [[2.0, 1.0], [3.0, 0.0]]}
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps(mix))
    mix = spec.load_traffic("burst", str(tmp_path))
    seconds = 20.0
    arr = traffic.open_arrivals(mix, FNS, seed, seconds)
    flat = traffic.open_arrivals({"rate_per_s": 6.0}, FNS, seed, seconds)
    assert len(arr) == len(flat) == 120
    due = np.array([a.due_s for a in arr])
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < seconds
    # on for 2 s of every 5: every arrival in an on phase, 15/s there
    assert np.all(due % 5.0 < 2.0)
    # the flat window's times, squeezed into the on phases
    flat_due = np.array([a.due_s for a in flat])
    on = flat_due / seconds * 8.0                 # 8 s on in the window
    assert np.allclose(due, on // 2.0 * 5.0 + on % 2.0)
    assert [a.row for a in arr] == [a.row for a in flat]
    assert traffic.open_arrivals(mix, FNS, seed, seconds) == arr


def test_closed_calls_campaign_and_solo():
    g = traffic.closed_calls({"rows_per_call": "campaign",
                              "runs_per_function": 25}, FNS, BIG_SEED)
    call = next(g)
    assert len(call) == 125
    assert collections.Counter(f for f, _ in call) == {f: 25 for f in FNS}
    assert next(g) != call                       # fresh seeds every call
    solo = traffic.closed_calls({"rows_per_call": 1}, ["cubic"], 3)
    rows = [next(solo) for _ in range(4)]
    again = traffic.closed_calls({"rows_per_call": 1}, ["cubic"], 3)
    assert rows == [next(again) for _ in range(4)]
    assert all(len(r) == 1 and r[0][0] == "cubic" for r in rows)


def test_streams_of_one_seed_do_not_share_draws():
    mix = {"rows_per_call": 8}
    warm = next(traffic.closed_calls(mix, FNS, 5, traffic.WARMUP))
    timed = next(traffic.closed_calls(mix, FNS, 5, traffic.TRAFFIC))
    assert {s for _, s in warm}.isdisjoint({s for _, s in timed})


def test_p95_is_over_all_requests():
    # 100 latencies 1..100: the nearest-rank p95 is the 95th value
    assert cell.p95(list(range(100, 0, -1))) == 95
    # one slow request in twenty sets the tail; none of it is averaged away
    assert cell.p95([1.0] * 19 + [50.0]) == 1.0
    assert cell.p95([1.0] * 18 + [50.0, 60.0]) == 50.0
    assert cell.p95([7.0]) == 7.0


def test_open_loop_latency_counts_from_due_time():
    """A request due while the loop was busy waits, and its latency counts
    the wait: measured from its due time, not from when it was sent."""
    import drivers

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = Clock()

    class Result:
        error = None
        gbest_fit = -1.0
        gbest_pos = np.zeros(2)

    class Sched:
        """Each step takes 0.5 s; a request finishes one step after it
        was submitted."""
        def __init__(self):
            self.pending, self.n = [], 0

        @property
        def busy(self):
            return bool(self.pending)

        def submit(self, req):
            self.pending.append(self.n)
            self.n += 1
            return self.n - 1

        def step(self):
            clock.t += 0.5
            out = {t: Result() for t in self.pending}
            self.pending = []
            return out

    arr = [traffic.Arrival(0.0, ("sphere", 1)),
           traffic.Arrival(0.1, ("sphere", 2))]
    config = {"dim": 2, "particles": 8, "iters": 16, "dtype": "float32",
              "method": {"variant": "async", "sync_every": 8}}
    done, _, late, missing = drivers.open_loop(
        Sched(), config, arr, 1.0,
        drivers.Reservoir(0, traffic.rng(1, traffic.SAMPLE)),
        drivers.Spans(False), clock=clock)
    assert missing == []
    lat = sorted(d.latency_s for d in done)
    # the first finishes 0.5 s after it was due; the second was due at 0.1
    # but submitted at 0.5 (the loop was stepping) and finished at 1.0
    assert lat == pytest.approx([0.5, 0.9])
    assert late == pytest.approx([0.0, 0.4])
