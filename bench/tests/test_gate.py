"""The harness refuses to measure anything but the chip."""

import json
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import REPO


def run_harness(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm-stc-1c",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def assert_no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


def test_exits_without_a_result_on_the_cpu():
    proc = run_harness(REPO)
    assert_no_result(proc)
    assert "no TPU" in proc.stderr


def test_exits_without_a_result_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert_no_result(run_harness(tmp_path))
