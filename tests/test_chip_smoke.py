"""chip_smoke.py off the chip: its phases on CPU at micro-hello with the
Pallas kernels in interpret mode, its refusal to run without a TPU, and the
compilation-cache helper every entry point calls."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_arch, reduce_for_smoke
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fork_serve_phases_micro_hello_interpret(smoke):
    lines = []
    meter = smoke.fork_serve(get_arch("micro-hello"), backend="interpret",
                             impl="interpret", seed=0, prompt_lens=(5, 9),
                             new_tokens=4, log=lines.append)
    assert meter["kernel.paged_attention.interpret"] > 0
    assert any("bit for bit" in m for m in lines)
    assert any("tokens agreeing with ref" in m for m in lines)


def test_kernel_meter_check_rejects_a_fallback(smoke):
    meters = {"kernel.page_gather.pallas": 3, "kernel.cow_scatter.pallas": 2,
              "kernel.paged_attention.pallas": 5}
    assert smoke.check_kernel_meters(meters, "pallas")["page_gather"] == 3
    for bad in ({"kernel.paged_attention.jnp": 1},
                {"kernel.page_gather.interpret": 1},
                {"kernel.cow_scatter.pallas": 0}):
        with pytest.raises(smoke.SmokeFailure):
            smoke.check_kernel_meters({**meters, **bad}, "pallas")


def _run_script(path, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(path)], capture_output=True,
                          text=True, env=env, timeout=300)


def test_refuses_without_tpu():
    out = _run_script(SCRIPT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_fails_alone_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path / "chip_smoke.py")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


ELASTIC = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util, dataclasses
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.configs.base import get_arch, reduce_for_smoke
cfg = dataclasses.replace(reduce_for_smoke(get_arch("train-100m")),
                          compute_dtype="bfloat16")
cs.elastic_phase(cfg, seed=0, steps=2, batch=8, seq=32)
print("ELASTIC_OK")
"""


def test_elastic_phase_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", ELASTIC, str(SCRIPT)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ELASTIC_OK" in out.stdout
    assert "state equal bit for bit" in out.stdout


def test_compile_cache_follows_env_else_fixed_checkout_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, "/some/where")
    assert compile_cache.enable_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads the env
    monkeypatch.delenv(compile_cache.ENV)
    try:
        d = compile_cache.enable_compile_cache()
        assert d == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
