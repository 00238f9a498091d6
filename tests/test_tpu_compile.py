"""The main path's kernels compiled for a described TPU v5e chip.

Nothing here runs on a chip: the installed TPU compiler compiles for a
topology that is described, not attached, and refuses what the chip would
refuse (Mosaic's block tiling, VMEM, HBM).  The topology is described in a
fixture, never while a module is imported, so every pytest-xdist worker
collects the same tests and only the worker given this file loads the TPU
library.  Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels.cow_scatter.kernel import cow_scatter, cow_scatter_runs
from repro.kernels.page_gather.kernel import page_gather, page_gather_runs
from repro.kernels.paged_attention.kernel import paged_attention
from repro.memory.pool import PAGE_ELEMS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or it cannot be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent cache would store these chip programs but could not
    # read them back without a chip; keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# K (kv heads) = 1, 12, 32 and 4: MQA, the paper's largest function, MHA
# with an unaligned head_dim, and GQA
@pytest.mark.parametrize("arch", ["gemma3-1b", "micro-large", "stablelm-3b",
                                  "qwen2-7b"])
def test_paged_attention_compiles(one_chip, arch):
    cfg = get_arch(arch)
    K, hd = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // K
    B, P, F, Tp = 4, 8, 256, 16
    dt = jnp.dtype(cfg.compute_dtype)
    s = lambda shape, d=jnp.int32: _spec(one_chip, shape, d)  # noqa: E731
    compiled = paged_attention.lower(
        s((B, K, G, hd), dt), s((F, K, Tp, hd), dt), s((F, K, Tp, hd), dt),
        s((B, P)), s((B,)), v_page_table=s((B, P)), starts=s((B,)),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


N_PAGES = 64        # pages moved per launch; the pool is 16x to 512x that


def _pool_kernel_temp(sharding, kernel, frames, dtype):
    s = lambda shape, d=jnp.int32: _spec(sharding, shape, d)  # noqa: E731
    pool = s((frames, PAGE_ELEMS // 128, 128), dtype)   # device-pool layout
    one = s((1,))
    pages = s((N_PAGES, PAGE_ELEMS), dtype)
    lowered = {
        "page_gather": lambda: page_gather.lower(
            pool, s((N_PAGES,)), interpret=False),
        "page_gather_runs": lambda: page_gather_runs.lower(
            pool, one, one, one, max_len=N_PAGES, n_out=N_PAGES,
            interpret=False),
        "cow_scatter": lambda: cow_scatter.lower(
            pool, s((N_PAGES,)), pages, interpret=False),
        "cow_scatter_runs": lambda: cow_scatter_runs.lower(
            pool, one, one, one, pages, max_len=N_PAGES, interpret=False),
    }[kernel]()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel", ["page_gather", "page_gather_runs",
                                    "cow_scatter", "cow_scatter_runs"])
def test_pool_kernel_temp_bytes_independent_of_pool(one_chip, kernel, dtype):
    small, large = (_pool_kernel_temp(one_chip, kernel, f, dtype)
                    for f in (1024, 32768))
    assert small == large, (small, large)
    # at most a relayout of what the launch moves, never of the pool
    assert large <= N_PAGES * PAGE_ELEMS * jnp.dtype(dtype).itemsize
