"""``python3 -m chipbench.run`` refuses to measure without a TPU."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import harness


def run_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "exp1.train",
         "--seed", "3000000007", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_exits_2_without_a_tpu():
    p = run_cli(harness.ROOT)
    assert p.returncode == 2, p.stderr
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
