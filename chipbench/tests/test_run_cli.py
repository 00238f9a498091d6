"""run.py refuses to produce a result without a TPU, and without the
program beside it."""
import os
import shutil
import subprocess
import sys

from chipbench import spec

ARGS = ["--workload", "granite-code-serve", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, capture_output=True, text=True, env=env,
                          timeout=300)


def _no_result(out):
    return out.returncode != 0 and '"correct"' not in out.stdout


def test_refuses_off_tpu():
    assert _no_result(_run(spec.ROOT))


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert _no_result(_run(tmp_path))
