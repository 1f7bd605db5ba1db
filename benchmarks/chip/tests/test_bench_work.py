"""The work function, against counts made by hand for both configurations,
and the peak table."""
import json
import os

import pytest

import spec
import work


def test_t4_cubic1d_solve_by_hand():
    cfg = spec.load_config("t4-cubic1d")
    ops, byt = work.work(cfg, ["cubic"])
    # per particle-dim: advance 15 + 2 uniforms + cubic 9 = 26; per
    # particle: pbest 2 + queue 1 = 3; 200 iterations of 131,072 x 1
    assert ops == 200 * 131072 * (26 + 3) == 760_217_600
    # pos, vel, pbest_pos (N x 1) + pbest_fit (N) + gbest (1 + 1), f32,
    # read once and written once
    assert byt == 2 * 4 * (3 * 131072 + 131072 + 2) == 4_194_320


def test_cec05_d10_campaign_by_hand():
    cfg = spec.load_config("cec05-d10")
    per_solve = {  # 2,500 iterations x 40 particles x (10 per_pd + per_p)
        "sphere": 2500 * 40 * (10 * 19 + 4),
        "rosenbrock": 2500 * 40 * (10 * 25 + 4),
        "griewank": 2500 * 40 * (10 * 22 + 7),
        "ackley": 2500 * 40 * (10 * 22 + 13),
        "rastrigin": 2500 * 40 * (10 * 23 + 6),
    }
    for fn, want in per_solve.items():
        assert work.work(cfg, [fn])[0] == want
    campaign = [f for f in cfg["functions"] for _ in range(25)]
    ops, byt = work.work(cfg, campaign)
    assert ops == 2_860_000_000
    assert byt == 125 * 2 * 4 * (3 * 40 * 10 + 40 + 10 + 1) == 1_251_000


def test_roofline_names_its_bound():
    peak = {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    share, bound = work.roofline(760_217_600, 4_194_320, 0.055, peak)
    assert bound == "hbm"
    assert share == pytest.approx(100 * 4_194_320 / 8.19e11 / 0.055)
    share, bound = work.roofline(1.97e14, 1.0, 2.0, peak)
    assert (bound, share) == ("flops", pytest.approx(50.0))


def test_peaks_by_device_kind():
    with open(os.path.join(spec.HERE, "peaks.json")) as f:
        table = json.load(f)
    v5e = work.peak_for("TPU v5 lite", table)
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    assert "TPU v5e" in table["source"] or "v5e" in table["source"]
    with pytest.raises(KeyError, match="no peaks"):
        work.peak_for("TPU v99", table)
