"""The command refuses to give a result where it cannot measure: on the
CPU, and in a directory that holds the benchmark without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness

CELLS = [w["name"] for w in json.load(open(os.path.join(
    harness.ROOT, "BENCHMARK.json")))["workloads"]]


def _run(root, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "chip", "run.py"),
         "--workload", cell, "--seed", str(2 ** 33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


@pytest.mark.parametrize("cell", CELLS)
def test_no_result_without_a_tpu(cell):
    r = _run(harness.ROOT, cell)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path), CELLS[0])
    assert r.returncode != 0 and r.stdout.strip() == ""
