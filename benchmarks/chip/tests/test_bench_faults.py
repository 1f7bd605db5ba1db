"""A run with the timed path broken underneath has to come out not
correct. Each fault is planted in the program's engine at a tiny size on
the CPU; the harness runs as on the chip from set-up to the comparison,
with only its look for a chip skipped."""

import jax
import pytest

import cell
import spec

TINY_1D = {
    "dim": 1, "particles": 256, "iters": 8, "w": 1.0, "c1": 2.0, "c2": 2.0,
    "dtype": "float32", "functions": {"cubic": [-100.0, 100.0]},
    "method": {"variant": "queue_lock", "backend": "auto"},
    "semantics": {"blocks": 1},
    "correct": {"sample": 4, "limits": {
        "fit_gap": 1e-5, "self_gap": 1e-4, "diverged_share": 0.0,
        "swarm_gap": 1e-5}},
}
TINY_CEC = {
    "dim": 3, "particles": 16, "iters": 20, "w": 1.0, "c1": 2.0, "c2": 2.0,
    "dtype": "float32",
    "functions": {"sphere": [-100.0, 100.0], "rastrigin": [-5.12, 5.12]},
    "method": {"variant": "async", "backend": "auto", "sync_every": 8},
    "semantics": {"blocks": 1},
    "correct": {"sample": 4, "limits": {
        "fit_gap": 1e-5, "self_gap": 1e-4, "diverged_share": 0.0}},
}
E2E = [{"name": "setup_s", "unit": "s"},
       {"name": "solve_rate", "unit": "solves/s"}]
CELLS = {
    "solo": spec.Cell("tiny.solo", 1, TINY_1D,
                      {"loop": "closed", "entry": "solve",
                       "rows_per_call": 1}, E2E, []),
    "batch": spec.Cell("tiny.batch", 1, TINY_CEC,
                       {"loop": "closed", "entry": "solve_many",
                        "rows_per_call": "campaign",
                        "runs_per_function": 2}, E2E, []),
    "service": spec.Cell("tiny.service", 1, TINY_CEC,
                         {"loop": "open", "entry": "scheduler",
                          "rate_per_s": 16.0}, E2E, []),
}


def _unchanged(real):
    def run_many(cfg, batch, iters, *a, **k):
        return batch
    return run_many


def _half(real):
    def run_many(cfg, batch, iters, *a, **k):
        out = real(cfg, batch, iters, *a, **k)
        half = batch.gbest_fit.shape[0] // 2
        return type(out)(*(o if i is None else o.at[half:].set(i[half:])
                           for o, i in zip(out, batch)))
    return run_many


def _altered(real):
    def run_many(cfg, batch, iters, *a, **k):
        out = real(cfg, batch, iters, *a, **k)
        return out._replace(gbest_pos=out.gbest_pos * 0.999 + 1e-3)
    return run_many


def _one_slot(real):
    def run_many(cfg, batch, iters, *a, **k):
        out = real(cfg, batch, iters, *a, **k)
        return out._replace(
            gbest_pos=out.gbest_pos.at[0].set(out.gbest_pos[0] * 0.999
                                              + 1e-3))
    return run_many


def _plant(monkeypatch, which, fault):
    """Break the engine the cell's entry drives on this backend: the
    single-swarm segment runner behind ``repro.solve``, or the batched
    runner behind ``solve_many`` and the scheduler's lanes (the lanes'
    tail ejection runs on the row's state, so it stays sound)."""
    import repro.api
    import repro.core.multi_swarm as ms
    import repro.serving.scheduler as sch
    if which == "solo":
        real = repro.api._run_state

        def batched(cfg, batch, iters, *a, **k):
            s, h, t = real(cfg, batch, iters, *a, **k)
            return s
        broken = fault(batched)
        monkeypatch.setattr(repro.api, "_run_state",
                            lambda c, s, i, m: (broken(c, s, i, m), None,
                                                None))
    else:
        broken = fault(ms.run_many)
        monkeypatch.setattr(ms, "run_many", broken)
        monkeypatch.setattr(sch, "run_many", broken)


def _run(which, tmp_path, seed=2 ** 31 + 99):
    return cell.run(CELLS[which], seed, 0.5, False, root=str(tmp_path),
                    devs=jax.devices(), log=lambda m: None)


def test_sound_run_is_correct(tmp_path):
    out = _run("solo", tmp_path)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "solve_rate"}


@pytest.mark.parametrize("which,fault", [
    ("solo", _unchanged), ("solo", _altered),
    ("batch", _unchanged), ("batch", _half), ("batch", _altered),
    ("service", _unchanged), ("service", _half), ("service", _altered),
])
def test_broken_engine_is_not_correct(which, fault, tmp_path, monkeypatch):
    if which == "solo" and fault is _unchanged:
        # a solve that returns its state unchanged: the init state
        import repro.api
        monkeypatch.setattr(repro.api, "_run_state",
                            lambda c, s, i, m: (s, None, None))
    else:
        _plant(monkeypatch, which, fault)
    out = _run(which, tmp_path)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("which", ["batch", "service"])
def test_one_wrong_slot_is_not_correct(which, tmp_path, monkeypatch):
    """One row of every batch (one lane slot) altered: with the sample
    covering the whole window, that one wrong answer in it is enough."""
    c = CELLS[which]
    config = dict(c.config, correct=dict(c.config["correct"], sample=10000))
    CELLS["wide"] = spec.Cell(c.name, 1, config, c.traffic, E2E, [])
    try:
        _plant(monkeypatch, which, _one_slot)
        out = _run("wide", tmp_path)
    finally:
        del CELLS["wide"]
    assert not out["correct"], out["checks"]
    assert 0 < out["checks"]["diverged_share"]["value"] < 1
