"""``repro.solve_many`` splits its finished batch on the host.

The batch reaches the host in one transfer (``multi_swarm.host_rows``) and
each row's ``Result.state`` holds numpy views of it. These tests hold the
rows to ``batch_row`` of the same device batch bit for bit, keep the
facade off per-row device slicing, and resume from host rows.
"""
import numpy as np
import pytest

import repro
import repro.api as api
from repro.core import multi_swarm
from repro.core.multi_swarm import batch_row, run_many, stack_states
from repro.kernels import ops

SEEDS = (5, 6, 2 ** 31 + 7)
ITERS = 12

# one finished batch per engine path the facade splits
CASES = {
    # hetero rows, the async batched kernel: lbest_* present
    "hetero-async-kernel": dict(
        problems=["sphere", "rastrigin", "ackley"], dim=2, particles=128,
        iters=16, backend="kernel", variant="async", sync_every=8,
        block_n=64),
    # one problem, synchronous jnp engine: lbest_* None
    "homog-sync-jnp": dict(
        problem="cubic", dim=2, particles=64, iters=ITERS, variant="queue"),
    # one problem, fused kernel with per-row histories and counters
    "homog-kernel-history-telemetry": dict(
        problem="cubic", dim=2, particles=128, iters=ITERS,
        backend="kernel", variant="queue_lock", block_n=64,
        record_history=True, telemetry=True),
    # hetero rows, jnp async engine with per-row histories
    "hetero-async-jnp-history": dict(
        problems=["cubic", "sphere", "rastrigin"], dim=2, particles=64,
        iters=ITERS, backend="jnp", variant="async", record_history=True),
}


def _solve(case: str, monkeypatch):
    """``solve_many`` of ``case`` and the device batch it split."""
    seen = []
    split = multi_swarm.host_rows

    def spy(batch):
        seen.append(batch)
        return split(batch)
    monkeypatch.setattr(api, "host_rows", spy)
    res = repro.solve_many(seeds=SEEDS, **CASES[case])
    assert len(seen) == 1
    return res, seen[0]


def _same_bits(got, want):
    want = np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("case", list(CASES))
def test_solve_many_rows_equal_batch_row(case, monkeypatch):
    """Every field of every row is ``batch_row`` of the finished batch, bit
    for bit; histories and counters stay with their rows."""
    res, batch = _solve(case, monkeypatch)
    assert len(res) == len(SEEDS) == batch.swarm_cnt
    kw = CASES[case]
    for s, r in enumerate(res):
        dev = batch_row(batch, s)
        for name, got, want in zip(dev._fields, r.state, dev):
            assert (got is None) == (want is None), name
            if got is not None:
                _same_bits(got, want)
        assert r.gbest_fit == float(np.asarray(dev.gbest_fit))
        _same_bits(r.best_pos, dev.gbest_pos)
        if kw.get("record_history"):
            assert r.history.iteration[-1] == kw["iters"]
            assert r.history.gbest_fit[-1] == r.state.gbest_fit
        else:
            assert r.history is None
        if kw.get("telemetry"):
            one = repro.solve(kw["problem"], seed=SEEDS[s], dim=kw["dim"],
                              particles=kw["particles"], iters=kw["iters"],
                              backend=kw["backend"], variant=kw["variant"],
                              block_n=kw["block_n"], telemetry=True)
            assert r.telemetry == one.telemetry
        else:
            assert r.telemetry is None
    if kw["variant"] == "async":
        assert res[0].state.lbest_fit is not None
    else:
        assert res[0].state.lbest_fit is None


@pytest.mark.parametrize("case", list(CASES))
def test_solve_many_splits_without_device_slices(case, monkeypatch):
    """The facade never slices rows on the device: ``batch_row`` raises
    for the whole call, and every row's fields are host arrays."""
    def refuse(batch, s):
        raise AssertionError("solve_many sliced a row on the device")
    monkeypatch.setattr(multi_swarm, "batch_row", refuse)
    monkeypatch.setattr(api, "batch_row", refuse, raising=False)
    res = repro.solve_many(seeds=SEEDS, **CASES[case])
    for r in res:
        for name, a in zip(r.state._fields, r.state):
            if a is not None:
                assert isinstance(a, np.ndarray), name
        assert isinstance(r.best_pos, np.ndarray)


@pytest.mark.parametrize("variant,backend",
                         [("queue", "jnp"), ("async", "jnp"),
                          ("async", "kernel")])
def test_resume_from_host_rows_equals_device_rows(variant, backend,
                                                  monkeypatch):
    """Host rows stacked back into a batch resume exactly as the device
    rows of the same batch do."""
    case = dict(problem="cubic", dim=2, particles=128, iters=ITERS,
                variant=variant, backend=backend, block_n=64)
    monkeypatch.setitem(CASES, "resume", case)
    res, batch = _solve("resume", monkeypatch)
    cfg = res[0].config
    host = stack_states([r.state for r in res])
    dev = stack_states([batch_row(batch, s) for s in range(len(SEEDS))])

    def more(b):
        if backend == "kernel":
            return ops.run_queue_lock_fused_async_batch(
                cfg, b, 8, sync_every=8, block_n=64)
        return run_many(cfg, b, 8, variant, sync_every=8,
                        n_blocks=api._jnp_async_blocks(res[0].method, 128))
    a, b = more(host), more(dev)
    for name, x, y in zip(a._fields, a, b):
        assert (x is None) == (y is None), name
        if x is not None:
            _same_bits(np.asarray(x), y)
    assert int(np.asarray(a.iteration)[0]) == ITERS + 8
