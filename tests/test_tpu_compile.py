"""Every Pallas kernel compiles through Mosaic at qwen2-0.5b's widths.

The TPU compiler is installed without a chip: it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (a block not
aligned to the (8, 128) tiling, more VMEM than a kernel may use) — which
interpret mode never checks.  Widths: 14 query and 2 KV heads, head dim
64, KV block 32, 64 selected blocks (token budget 2048), 256-block pools
(8k-token contexts), a 2048-token prefill window; f32 and bf16.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test collection runs
in every worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

B, HQ, HKV, D, BS, K, NB = 4, 14, 2, 64, 32, 64, 256
PREFILL_T = 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from a persistent
    # cache without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _cases(dt):
    """kernel name -> (kernel, argument shapes, static kwargs)."""
    i32 = jnp.int32
    pool = (HKV, NB, BS, D)
    return {
        "sparse_decode_attention": (
            ops.sparse_decode_attention,
            [((B, HQ, D), dt), ((B, HKV, NB, BS, D), dt),
             ((B, HKV, NB, BS, D), dt), ((B, HKV, K), i32),
             ((B, HKV, K), i32), ((B,), i32)], {}),
        "gather_blocks_hkv": (
            ops.gather_blocks_hkv, [(pool, dt), ((K,), i32)], {}),
        "scatter_blocks_hkv": (
            ops.scatter_blocks_hkv,
            [(pool, dt), ((HKV, K, BS, D), dt), ((K,), i32)], {}),
        "flash_prefill": (
            ops.flash_prefill,
            [((1, PREFILL_T, HQ, D), dt), ((1, PREFILL_T, HKV, D), dt),
             ((1, PREFILL_T, HKV, D), dt)], {}),
        "block_score": (
            ops.block_score,
            [((B, HQ, D), dt), ((B, HKV, NB, D), dt),
             ((B, HKV, NB, D), dt)], {}),
        "quantize_blocks": (
            ops.quantize_blocks, [((HKV, K, BS, D), dt)], {}),
        "dequantize_blocks": (
            ops.dequantize_blocks,
            [((HKV, K, BS, D), jnp.int8), ((HKV, K), jnp.float32)], {}),
        "dequantize_scatter_blocks": (
            ops.dequantize_scatter_blocks,
            [(pool, dt), ((HKV, K, BS, D), jnp.int8),
             ((HKV, K), jnp.float32), ((K,), i32)], {}),
    }


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(_cases(jnp.float32)))
def test_kernel_compiles_for_v5e(one_chip, name, dt):
    kernel, shapes, kw = _cases(dt)[name]
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip)
            for s, t in shapes]
    compiled = kernel.lower(*args, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
