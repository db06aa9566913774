"""The harness end to end on the CPU, at small sizes.

    PYTHONPATH=. python -m pytest -q bench/tests

Each case drives one run of a cell (``bench/tests/plant.py``) in a
process of its own, with the chip check skipped, and reads its result
line. A sound program comes out correct; each fault the cell can have,
and the bf16x3 control in the program's place, come out not correct
under the cell's committed limits.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import cells

BENCH = cells.BENCH
ROOT = cells.ROOT
PLANT = os.path.join(BENCH, "tests", "plant.py")
RUN = os.path.join(BENCH, "run.py")
# the stated accuracy at the small size of these runs: loose, since the
# faults are caught by the gaps to the reference, not by the target
SMALL_TARGET = "0.5"


def _cases():
    out = []
    for w in cells.benchmark()["workloads"]:
        spmd = w["chips"] > 1
        faults = ["none", "state_unchanged", "half_batch", "answer_altered",
                  "control"] + (["no_exchange"] if spmd else [])
        out += [(w["name"], f) for f in faults]
    return out


def _env(tmp_path, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={devices}").strip()
    return env


@pytest.mark.parametrize("cell,fault", _cases())
def test_only_the_sound_program_is_correct(cell, fault, tmp_path):
    chips = cells.load_cell(cell)["entry"]["chips"]
    proc = subprocess.run(
        [sys.executable, PLANT, cell, fault, "--target", SMALL_TARGET],
        env=_env(tmp_path, chips), capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1
    assert out["correct"] is (fault == "none"), (fault, out["checks"])


def test_refuses_without_a_chip(tmp_path):
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "ijcnn1.sync-p4", "--seed", "7",
         "--seconds", "1", "--trace", "0"],
        env=_env(tmp_path), capture_output=True, text=True, timeout=300,
        cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    env = _env(tmp_path)
    env.pop("PYTHONPATH", None)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ijcnn1.sync-p4",
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
