"""The paper's Table 5 swarm (``configs/t5-rastrigin120.json``) at a size a
test run holds, with the same shape of work: 120-D rastrigin, 8 particle
blocks of [120, 128] streamed through the fused kernel (the Pallas
interpreter here), held to the plain reference under the configuration's
own limits."""
import control
import correct
import spec

CELL = "t5-rastrigin120.solo"


def _small():
    """The cell's configuration at 1,024 particles in 8 blocks of 128, 20
    iterations: every dimension, the objective, the coefficients and the
    limits are the cell's."""
    config = spec.load_cell(CELL).config
    return dict(config, particles=1024, iters=20,
                semantics=dict(config["semantics"], blocks=8))


def test_config_loads_and_its_blocks_divide_its_particles():
    from repro.core.blocking import pick_block_n
    cell = spec.load_cell(CELL)
    c = cell.config
    assert cell.chips == 1 and cell.traffic["entry"] == "solve"
    assert (c["dim"], c["particles"], c["iters"]) == (120, 32768, 200)
    assert c["particles"] % c["semantics"]["blocks"] == 0
    # the reference's blocks are the kernel's: the one the program picks
    assert c["particles"] // c["semantics"]["blocks"] == \
        pick_block_n(c["particles"])
    assert set(c["reduced"]) <= set(c["cut"])
    assert "functions" in c["assumed"]
    names = {m["name"] for m in cell.per_layer}
    assert {"kernel.stream_share", "kernel.step_us"} <= names


def test_kernel_solves_are_correct():
    import repro
    config = _small()
    m = repro.Method(variant="queue_lock", backend="kernel", block_n=128)
    ref = correct.reference_for(config)
    answers = []
    for fn, seed in control.sample_rows(config, 2 ** 31 + 11):
        r = repro.solve(fn, dim=config["dim"], particles=config["particles"],
                        iters=config["iters"], seed=seed, method=m,
                        w=config["w"], c1=config["c1"], c2=config["c2"],
                        dtype=config["dtype"])
        answers.append(control.Answer((fn, seed), r.gbest_fit, r.best_pos))
    nums = correct.numbers(config, answers, ref)
    ok, checks = correct.judge(nums, config["correct"]["limits"])
    assert ok, checks
    assert nums["diverged_share"] == 0.0


def test_control_and_faults_fail():
    config = _small()
    ref32 = correct.reference_for(config)
    ref16 = correct.reference_for(config, dtype="bfloat16")
    ref0 = correct.reference_for(dict(config, iters=0))
    lines = control.readings(config, 2 ** 31 + 5, ref32, ref16, ref0)
    assert {ln["kind"] for ln in lines} >= {"control", "one_wrong"}
    for ln in lines:
        assert not ln["correct"], ln

