"""The control (the reference in bfloat16 in the program's place) and the
planted faults come out not correct, at a size a test run holds."""
import pytest

import control
import correct

TINY_1D = {
    "dim": 1, "particles": 512, "iters": 6, "w": 1.0, "c1": 2.0, "c2": 2.0,
    "dtype": "float32", "functions": {"cubic": [-100.0, 100.0]},
    "semantics": {"blocks": 2},
    "correct": {"sample": 4, "limits": {
        "fit_gap": 1e-5, "self_gap": 1e-4, "diverged_share": 0.0,
        "swarm_gap": 1e-5}},
}
TINY_CEC = {
    "dim": 10, "particles": 40, "iters": 40, "w": 1.0, "c1": 2.0,
    "c2": 2.0, "dtype": "float32",
    "functions": {"sphere": [-100.0, 100.0], "rosenbrock": [-30.0, 30.0],
                  "griewank": [-600.0, 600.0], "ackley": [-32.0, 32.0],
                  "rastrigin": [-5.12, 5.12]},
    "semantics": {"blocks": 1},
    "correct": {"sample": 8, "limits": {
        "fit_gap": 1e-5, "self_gap": 1e-4, "diverged_share": 0.0}},
}


@pytest.mark.parametrize("config", [TINY_1D, TINY_CEC],
                         ids=["t4-cubic1d", "cec05-d10"])
def test_control_and_faults_fail(config):
    ref32 = correct.reference_for(config)
    ref16 = correct.reference_for(config, dtype="bfloat16")
    ref0 = correct.reference_for(dict(config, iters=0))
    lines = control.readings(config, 2 ** 31 + 3, ref32, ref16, ref0)
    assert {ln["kind"] for ln in lines} == {
        "control", "unchanged", "half", "altered_pos", "one_wrong",
        "altered_fit"}
    for ln in lines:
        assert not ln["correct"], ln


def test_reference_agrees_with_itself():
    ref = correct.reference_for(TINY_CEC)
    ans = control.from_reference(ref, control.sample_rows(TINY_CEC, 9),
                                 swarm=False)
    nums = correct.numbers(TINY_CEC, ans, ref)
    assert nums["diverged_share"] == 0.0 and nums["fit_gap"] == 0.0
    assert nums["self_gap"] < 1e-5
    assert correct.judge(nums, TINY_CEC["correct"]["limits"])[0]
