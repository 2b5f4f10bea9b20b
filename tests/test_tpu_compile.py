"""Main-path Pallas kernels compiled for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
tiles not aligned to the (8, 128) layout, rank-1 VMEM blocks, more VMEM
than a kernel may hold. These tests compile each kernel with the TPU
compiler for a described ``v5e:2x2`` topology (no chip attached; nothing
runs) and require the Pallas call to survive as a ``tpu_custom_call``.

The topology is described only inside the module fixture, which skips
where the TPU compiler cannot describe it: describing it takes libtpu's
process lock, so it must never happen while test modules are imported.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import attention, gemm_os, paged_attention

PAGE = 16
BATCH = 8
N_BLOCKS = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # unless placed, the TPU compiler logs under /tmp, a path shared by
        # every checkout on the machine
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # any failure to describe it: no compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache without that chip: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_case(arch, T, pool_dtype=jnp.bfloat16, window=0):
    cfg = get_config(arch)
    H, KV, D = cfg.num_heads, cfg.kv_heads, cfg.resolved_head_dim
    P = BATCH * N_BLOCKS + 1
    q = (BATCH, H, D) if T == 1 else (BATCH, T, H, D)
    scale = 4.0 if pool_dtype == jnp.int8 else None

    def fn(q, k, v, bt, ln):
        return paged_attention.paged_attention(
            q, k, v, bt, ln, kv_scale=scale, window=window, interpret=False)

    return fn, [(q, jnp.bfloat16), ((P, PAGE, KV, D), pool_dtype),
                ((P, PAGE, KV, D), pool_dtype),
                ((BATCH, N_BLOCKS), jnp.int32), ((BATCH,), jnp.int32)]


CASES = {
    # qwen2.5-3b decode: 16 q heads over 2 KV heads x 128 dims
    "paged-qwen-T1": lambda: _paged_case("qwen2.5-3b", 1),
    "paged-qwen-T4": lambda: _paged_case("qwen2.5-3b", 4),
    "paged-qwen-int8": lambda: _paged_case("qwen2.5-3b", 1, jnp.int8),
    # granite-3-2b decode: 32 q heads over 8 KV heads x 64 dims (two heads
    # share a 128-lane slab row)
    "paged-granite-T1": lambda: _paged_case("granite-3-2b", 1),
    # recurrentgemma-9b local attention: 1 KV head x 256 dims, window 2048
    "paged-rgemma-window": lambda: _paged_case(
        "recurrentgemma-9b", 1, window=2048),
    "gemm-bf16": lambda: (
        lambda x, w: gemm_os.gemm_os(x, w, interpret=False),
        [((2048, 2048), jnp.bfloat16), ((2048, 11008), jnp.bfloat16)]),
    "gemm-int8-quant": lambda: (
        lambda x, w: gemm_os.gemm_os(x, w, quant_scale=0.01,
                                     interpret=False),
        [((2048, 2048), jnp.int8), ((2048, 11008), jnp.int8)]),
    # prefill-shaped flash MHA with a traced per-sequence valid length
    "mha-kv-valid": lambda: (
        lambda q, k, v, n: attention.mha(q, k, v, kv_valid=n,
                                         interpret=False),
        [((2, 512, 16, 128), jnp.bfloat16), ((2, 512, 2, 128), jnp.bfloat16),
         ((2, 512, 2, 128), jnp.bfloat16), ((2,), jnp.int32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("case", ["paged-qwen-T1", "paged-qwen-T4"])
def test_paged_kernel_reads_the_pool_in_place(one_chip, case):
    """The kernel's row view of a (P, page, KV, D) pool is a bitcast of
    the layout XLA gives the pool: no copy of a whole pool per call."""
    fn, shapes = CASES[case]()
    pool = "bf16[%d," % shapes[1][0][0]
    made = [line for line in _compiled_text(fn, one_chip, *shapes)
            .splitlines() if "=" in line and pool in line.split("=", 1)[1]
            .split("(", 1)[0] and "parameter(" not in line]
    assert not [m for m in made if "bitcast(" not in m], made
