"""Schedule autotuner (repro.core.autotune) + its facade/serving wiring:
candidate enumeration, model ranking, measured fallback with the
never-worse fixed anchor, the on-disk cache (hit on second resolve, no
re-measurement), Method(schedule=...) semantics, and the serving-layer
model-only entry points."""
import json
import os

import pytest

import repro
from repro.core import autotune as at
from repro.core.autotune import (AutotuneCache, Schedule,
                                 candidate_schedules, fixed_schedule,
                                 rank_schedules, resolve_schedule,
                                 shape_key)


@pytest.fixture
def cache(tmp_path):
    return AutotuneCache(str(tmp_path / "autotune.json"))


# --------------------------------------------------------------------------
# Shape keys and candidate enumeration.
# --------------------------------------------------------------------------

def test_shape_key_buckets_iters():
    a = shape_key("sphere", 4, 256, 50, "float32")
    b = shape_key("sphere", 4, 256, 64, "float32")
    c = shape_key("sphere", 4, 256, 65, "float32")
    assert a == b != c          # 50 and 64 share the pow2 bucket; 65 doesn't


def test_shape_key_distinguishes_custom_and_constrained():
    import jax.numpy as jnp
    from repro.core.problem import Problem
    custom = Problem(name="my_bowl", sense="min",
                     fn=lambda x: jnp.sum(x ** 2, axis=-1))
    assert "custom:" in shape_key(custom, 4, 256, 64, "float32")
    assert shape_key("sphere", 4, 256, 64, "float32") != \
        shape_key("sphere_simplex", 4, 256, 64, "float32")


def test_candidates_no_kernel_without_tpu():
    cands = candidate_schedules(4, 256, 64, kernel_ok=False)
    assert cands and all(s.backend == "jnp" for s in cands)
    variants = {s.variant for s in cands}
    assert variants == {"reduction", "queue", "queue_lock", "async"}
    # async fans out over block sizes x sync intervals
    assert sum(s.variant == "async" for s in cands) > 1


def test_candidates_kernel_on_tpu_and_budget():
    cands = candidate_schedules(4, 256, 64, kernel_ok=True,
                                max_candidates=24)
    assert any(s.backend == "kernel" for s in cands)
    assert len(cands) <= 24
    # thinning keeps the non-async variants intact
    assert {s.variant for s in cands if s.variant != "async"} == \
        {"reduction", "queue", "queue_lock"}


def test_candidate_block_choices_divide():
    for s in candidate_schedules(8, 384, 64, kernel_ok=True):
        if s.block_n is not None:
            assert 384 % s.block_n == 0


# --------------------------------------------------------------------------
# Ranking and resolution.
# --------------------------------------------------------------------------

def test_rank_orders_by_predicted_us_and_drops_invalid():
    cands = [Schedule("queue", "jnp"), Schedule("async", "jnp",
                                                block_n=100, sync_every=8),
             Schedule("async", "jnp", block_n=64, sync_every=8)]
    ranked = rank_schedules(cands, "sphere", 4, 256, 64)
    # block_n=100 does not divide 256: dropped
    assert all(s.block_n != 100 for s in ranked)
    assert len(ranked) == 2
    assert all(s.source == "model" and s.predicted_us is not None
               for s in ranked)
    us = [s.predicted_us for s in ranked]
    assert us == sorted(us)


def test_resolve_model_only_no_measurement(cache, monkeypatch):
    def boom(*a, **k):
        raise AssertionError("measure_schedule called under measure=False")
    monkeypatch.setattr(at, "measure_schedule", boom)
    s = resolve_schedule("sphere", 4, 256, 64, measure=False, cache=cache,
                         kernel_ok=False)
    assert s.source == "model" and s.backend == "jnp"


def test_resolve_measured_includes_fixed_anchor(cache, monkeypatch):
    """The fixed default must be among the timed candidates even when the
    model ranks it outside the top-K — the never-worse guarantee."""
    measured = []

    def fake_measure(sched, *a, **k):
        measured.append(sched)
        return 100.0 if sched.variant != "queue" else 1.0
    monkeypatch.setattr(at, "measure_schedule", fake_measure)
    # force a ranking where queue cannot be in the top-K
    monkeypatch.setattr(at, "rank_schedules", lambda cands, *a, **k: [
        Schedule("async", "jnp", block_n=64, sync_every=k_, source="model",
                 predicted_us=float(k_)) for k_ in (1, 2, 4, 8)])
    s = resolve_schedule("sphere", 4, 256, 64, cache=cache, kernel_ok=False,
                         top_k=3)
    assert any(m.variant == "queue" for m in measured)
    assert s.variant == "queue" and s.source == "measured"
    assert s.measured_us == 1.0


def test_resolve_noise_margin_keeps_fixed_default(cache, monkeypatch):
    """A challenger within MEASURE_NOISE_MARGIN of the fixed default must
    lose to it — within-noise wins flip sign on re-measurement."""
    def fake_measure(sched, *a, **k):
        # challenger "wins" by 5% — inside the 10% noise margin
        return 95.0 if sched.variant == "async" else 100.0
    monkeypatch.setattr(at, "measure_schedule", fake_measure)
    monkeypatch.setattr(at, "rank_schedules", lambda cands, *a, **k: [
        Schedule("async", "jnp", block_n=64, sync_every=8, source="model",
                 predicted_us=1.0)])
    s = resolve_schedule("sphere", 4, 256, 64, cache=cache, kernel_ok=False)
    assert s.variant == "queue"             # the fixed default held

    def clear_win(sched, *a, **k):
        return 50.0 if sched.variant == "async" else 100.0
    monkeypatch.setattr(at, "measure_schedule", clear_win)
    s2 = resolve_schedule("sphere", 4, 512, 64, cache=cache,
                          kernel_ok=False)
    assert s2.variant == "async"            # a 2x win displaces it


def test_resolve_kernel_failure_surfaces_on_tpu(cache, monkeypatch):
    """On a TPU a kernel candidate that raises is a defect to surface, not
    a schedule that quietly loses to jnp; elsewhere it just drops out."""
    def kernel_breaks(sched, *a, **k):
        if sched.backend == "kernel":
            raise RuntimeError("Mosaic refused the kernel")
        return 10.0
    monkeypatch.setattr(at, "measure_schedule", kernel_breaks)
    monkeypatch.setattr(at, "rank_schedules", lambda cands, *a, **k: [
        Schedule("queue_lock", "kernel", block_n=128, source="model",
                 predicted_us=1.0)])
    s = resolve_schedule("sphere", 4, 256, 64, cache=cache, kernel_ok=True)
    assert s.backend == "jnp"
    monkeypatch.setattr(at, "_kernel_ok", lambda: True)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        resolve_schedule("sphere", 4, 512, 64, cache=cache, kernel_ok=True)


def test_resolve_cache_hit_skips_measurement(cache, monkeypatch):
    calls = {"n": 0}
    real = at.measure_schedule

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)
    monkeypatch.setattr(at, "measure_schedule", counting)
    first = resolve_schedule("sphere", 4, 128, 16, cache=cache,
                             kernel_ok=False, top_k=1)
    n_first = calls["n"]
    assert n_first >= 1 and first.source == "measured"
    second = resolve_schedule("sphere", 4, 128, 16, cache=cache,
                              kernel_ok=False, top_k=1)
    assert calls["n"] == n_first            # no re-measurement
    assert second.source == "cache"
    assert (second.variant, second.backend, second.block_n) == \
        (first.variant, first.backend, first.block_n)


def test_cache_survives_process_restart(cache, tmp_path):
    cache.put("jnp", "k1", Schedule("async", "jnp", block_n=64,
                                    sync_every=16, measured_us=3.0))
    fresh = AutotuneCache(cache.path)        # same disk, new LRU
    hit = fresh.get("jnp", "k1")
    assert hit is not None and hit.source == "cache"
    assert (hit.variant, hit.block_n, hit.sync_every) == ("async", 64, 16)
    assert fresh.get("kernel", "k1") is None     # scope separates


def test_cache_tolerates_corrupt_file(tmp_path):
    p = tmp_path / "autotune.json"
    p.write_text("{not json")
    c = AutotuneCache(str(p))
    assert c.get("jnp", "k") is None
    c.put("jnp", "k", Schedule("queue", "jnp"))
    assert json.load(open(p))                    # rewritten valid


def test_measure_schedule_smoke():
    t = at.measure_schedule(Schedule("queue", "jnp"), "sphere", 2, 64,
                            iters=4, repeats=1)
    assert 0 < t < 1e6


# --------------------------------------------------------------------------
# Facade wiring: Method(schedule=...).
# --------------------------------------------------------------------------

def test_method_schedule_validation():
    assert repro.Method().schedule == "fixed"
    repro.Method(schedule="auto")
    with pytest.raises(ValueError, match="schedule"):
        repro.Method(schedule="bogus")
    with pytest.raises(ValueError, match="island"):
        repro.Method(schedule="auto", islands=2)


def test_method_fixed_schedule_matches_legacy_rule():
    s = repro.Method(variant="queue").resolve_schedule("sphere", 4, 128, 8)
    assert (s.variant, s.backend, s.source) == ("queue", "jnp", "fixed")


def test_method_auto_schedule_resolves_and_solves(tmp_path, monkeypatch):
    monkeypatch.setenv(at.CACHE_ENV, str(tmp_path / "cache.json"))
    m = repro.Method(schedule="auto")
    s = m.resolve_schedule("sphere", 4, 128, 16, measure=False)
    assert s.source in ("model", "cache")
    r = repro.solve("sphere", dim=4, particles=128, iters=16, seed=0,
                    schedule="auto")
    import numpy as np
    assert np.isfinite(r.best_fit)
    # fixed-schedule solves still work with the feature present
    rf = repro.solve("sphere", dim=4, particles=128, iters=16, seed=0)
    assert np.isfinite(rf.best_fit)


def test_record_history_restricts_auto_to_jnp(tmp_path, monkeypatch):
    monkeypatch.setenv(at.CACHE_ENV, str(tmp_path / "cache.json"))
    m = repro.Method(schedule="auto", record_history=True)
    s = m.resolve_schedule("sphere", 4, 128, 16, measure=False)
    assert s.backend == "jnp"


def test_auto_history_no_longer_forces_jnp():
    # record_history used to force backend="auto" to jnp (with a one-time
    # warning); the kernel backend now records history by chunking its
    # launch at sync points, so auto resolves by the plain device rule and
    # never warns.
    cubic = repro.resolve_problem("cubic")
    m = repro.Method(backend="auto", variant="queue_lock",
                     record_history=True)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert m.resolve_backend(cubic) in ("jnp", "kernel")
    # the explicit kernel pin is accepted now, too
    mk = repro.Method(backend="kernel", variant="queue_lock",
                      record_history=True)
    assert mk.resolve_backend(cubic) == "kernel"


# --------------------------------------------------------------------------
# Serving-layer entry points (model-only).
# --------------------------------------------------------------------------

def test_tuned_sync_every_is_valid(cache):
    k = at.tuned_sync_every("sphere", 4, 256, 64, cache=cache)
    assert k in at.SYNC_EVERY_CHOICES


def test_bucket_ladder_shape():
    ladder = at.bucket_ladder("sphere", 4, 128, 32, max_batch=64)
    assert ladder[0] == 4
    assert list(ladder) == sorted(set(ladder))
    assert all(b <= 64 for b in ladder)
    assert all(ladder[i + 1] == 2 * ladder[i]
               for i in range(len(ladder) - 1))


def test_serve_autotune_rewrites_async_sync_every(tmp_path, monkeypatch):
    monkeypatch.setenv(at.CACHE_ENV, str(tmp_path / "cache.json"))
    from repro.launch.serve import SolveRequest, SolveServer
    srv = SolveServer(max_batch=4, autotune=True)
    r = SolveRequest(fitness="sphere", dim=4, particle_cnt=64, iters=16,
                     seed=0, variant="async")
    tuned = srv._tuned_request(r)
    assert tuned.sync_every in at.SYNC_EVERY_CHOICES
    # non-async requests pass through untouched
    rq = SolveRequest(fitness="sphere", dim=4, particle_cnt=64, iters=16,
                      seed=0, variant="queue")
    assert srv._tuned_request(rq) is rq


def test_serve_autotune_end_to_end(tmp_path, monkeypatch):
    monkeypatch.setenv(at.CACHE_ENV, str(tmp_path / "cache.json"))
    from repro.launch.serve import SolveRequest, SolveServer
    plain = SolveServer(max_batch=4)
    tuned = SolveServer(max_batch=4, autotune=True)
    reqs = [SolveRequest(fitness="sphere", dim=4, particle_cnt=64,
                         iters=16, seed=s, variant="queue")
            for s in range(3)]
    a = plain.solve_all(reqs)
    b = tuned.solve_all(reqs)
    for x, y in zip(a, b):
        assert x.gbest_fit == y.gbest_fit   # sync variants: no change
