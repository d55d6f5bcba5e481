"""The harness end to end on the CPU at a toy size: it refuses a host
without a TPU and a checkout without the program, a sound run comes out
correct, and a run with the timed path broken underneath does not."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import faults, run, spec
from bench import traffic as gen
from bench.spec import ROOT
from bench.tests.tiny import tiny_config, tiny_traffic

CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2 ** 33 + 5
# the toy size's own limit on the widest gap: sound runs read 0-0.15 over
# a dozen seeds in both configurations, the faults below 0.86 or more
TINY_LIMIT = 0.45
CELLS = [("mistral-7b", "doc32k-decode"), ("llava-next-34b", "imgdoc-qa")]
# with every cluster retrieved the wave index is exact, and the program's
# widest gap is bfloat16's alone: 0-0.025 over a dozen seeds, against
# 0.05-0.28 for the fp8 control read at the program's positions
EXACT_LIMIT = 0.05
CONTROL = "fp8"


def _bench_cmd(workload):
    return [sys.executable, "bench/run.py", "--workload", workload,
            "--seed", str(SEED), "--seconds", "1", "--trace", "0"]


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        _bench_cmd(spec.load_benchmark()["workloads"][0]["name"]),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        _bench_cmd(spec.load_benchmark()["workloads"][0]["name"]),
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def _run(hook=None, cell=CELLS[0], seed=SEED, cfgd=None,
         limit=TINY_LIMIT):
    metrics = [{"name": "out_tok_s", "unit": "tokens/s"}]
    return run.run_cell({"name": "tiny"}, cfgd or tiny_config(cell[0]),
                        tiny_traffic(cell[1]), {"max_logit_gap": limit},
                        metrics, seed, 1.0, False, CPU_DEVICE,
                        engine_hook=hook)


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c[1])
def test_a_sound_run_is_correct(cell):
    res = _run(cell=cell)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["metrics"]["out_tok_s"]["value"] > 0


@pytest.fixture
def restore_kernel():
    yield
    faults.restore()


@pytest.mark.parametrize("fault", ["token-altered", "state-unchanged",
                                   "kernel-zeroed"])
def test_a_broken_timed_path_is_not_correct(fault, restore_kernel):
    res = _run(hook=faults.FAULTS[fault])
    assert res["checks"]["max_logit_gap"]["value"] > TINY_LIMIT
    assert res["correct"] is False


def _exact_config():
    cfgd = tiny_config()
    cfgd["retro"].update(retrieval_frac=1.0, estimation_frac=0.0)
    return cfgd


def test_the_control_is_not_correct():
    """The reference in the program's place, one precision step below
    bfloat16, comes out not correct from the harness, at a limit that the
    program's own run meets."""
    cfgd = _exact_config()
    res = _run(cfgd=cfgd, limit=EXACT_LIMIT)
    assert res["correct"] is True, res["checks"]
    res = _run(hook=faults.control(cfgd, SEED, CONTROL), cfgd=cfgd,
               limit=EXACT_LIMIT)
    assert res["checks"]["max_logit_gap"]["value"] > EXACT_LIMIT
    assert res["correct"] is False


def test_readings_of_the_program_its_controls_and_a_fault(restore_kernel):
    """``readings.py`` reads the program, its controls and a fault on the
    same seed through one engine per fault."""
    from bench import readings
    recs = readings.readings(tiny_config(), tiny_traffic(), [SEED], {SEED},
                             ["kernel-zeroed"], [SEED], n=2,
                             emit=lambda line: None)
    prog, fault = recs
    assert (prog["what"], fault["what"]) == ("program", "kernel-zeroed")
    assert prog["program"]["max_logit_gap"] <= TINY_LIMIT
    assert set(prog["controls"]) == set(readings.CONTROLS)
    assert fault["program"]["max_logit_gap"] > TINY_LIMIT
    assert not fault["controls"] and prog["failed"] == fault["failed"] == 0
