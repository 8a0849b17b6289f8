"""int8 KV-block (de)quantization for the DRAM offload tier (§3.2 + ROADMAP
"quantized DRAM tier").

The quantized tier stores offloaded KV blocks as symmetric int8 with ONE
f32 scale per (kv-head, block): ``scale = amax(block)/127``,
``q = clip(round(x/scale), -127, 127)``, ``dequant = q * scale``.  Per-head
scales matter because K/V magnitude varies strongly across kv heads; a
per-tensor scale would crush small-magnitude heads' resolution.

Three kernels, mirroring the fp transfer pair:

- ``quantize_blocks``  — fuses into the FlashD2H save path: the gathered
  per-head block stripe quantizes on the way to the DRAM staging buffer,
  so the D2H DMA moves ~1/dtype_bytes of the fp payload plus 4 B/head/block
  of scales.
- ``dequantize_blocks`` — the FlashH2D inverse: int8 payload + scales back
  to the compute dtype after the (now smaller) H2D DMA.
- ``dequantize_scatter_blocks`` — ``scatter_blocks_hkv`` with the dequant
  fused in: lands int8 restore payloads straight into a request's fp
  device slots in one launch (restore-before-use stays a single fused op).

All are checked in interpret mode against the ``ref.py`` oracles, and
compile through Mosaic at the served widths (tests/test_tpu_compile.py).
Kernel, oracle and the numpy host-pool twin share one rounding:
``int8_scale`` below (scale = amax * f32(1/127), then its reciprocal) and
``rint`` (round-half-to-even).  A division by the constant 127 would be
rewritten to a reciprocal multiply inside a compiled kernel but not in an
eager oracle, and the one-ulp difference in the scale flips exact .5
boundaries, which bf16 inputs hit often.

Scales are (H, K) f32.  Mosaic tiles the last two dims of a block by
(8, 128) unless they span the array, so a per-(head, block) ``(1, 1)``
scale block cannot compile: ``quantize_blocks`` keeps the whole scale
array in ONE output block, resident across the grid, and the dequantizers
read it whole from SMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


INV_127 = np.float32(1.0 / 127.0)


def int8_scale(amax):
    """amax (any shape, f32) -> (scale, inv): the one rounding rule of the
    int8 tier.  All-zero blocks get scale 0 and inv 1 (they quantize to
    0 and dequantize to exact zeros)."""
    scale = amax * INV_127
    inv = jnp.where(scale > 0.0, 1.0 / jnp.where(scale > 0.0, scale, 1.0),
                    1.0)
    return scale, inv


def _quant_kernel(blk_ref, q_ref, scale_ref):
    h, i = pl.program_id(0), pl.program_id(1)
    x = blk_ref[0, 0].astype(jnp.float32)                # (bs, D)
    amax = jnp.max(jnp.max(jnp.abs(x), axis=1, keepdims=True), axis=0,
                   keepdims=True)                        # (1, 1)
    scale, inv = int8_scale(amax)
    q_ref[0, 0] = jnp.clip(jnp.rint(x * inv), -127.0, 127.0
                           ).astype(jnp.int8)

    @pl.when((h == 0) & (i == 0))
    def _init():
        scale_ref[...] = jnp.zeros_like(scale_ref)

    # the (H, K) scale block stays in VMEM for the whole grid: set this
    # step's entry with a masked full-tile select
    shape = scale_ref.shape
    hit = ((jax.lax.broadcasted_iota(jnp.int32, shape, 0) == h)
           & (jax.lax.broadcasted_iota(jnp.int32, shape, 1) == i))
    scale_ref[...] = jnp.where(hit, scale, scale_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_blocks(blocks: jax.Array, *,
                    interpret: Optional[bool] = None
                    ) -> tuple[jax.Array, jax.Array]:
    """blocks: (H, K, bs, D) fp -> (q (H, K, bs, D) int8, scales (H, K) f32).

    One grid step per (kv-head, block); the amax reduction and the
    scale/round run on the VPU over the (bs, D) tile.  The (bs, D) data
    tile compiles when bs is a multiple of 32 (the int8 sublane tile) and
    D spans the head axis or is a multiple of 128."""
    H, K, bs, D = blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(H, K),
        in_specs=[pl.BlockSpec((1, 1, bs, D), lambda h, i: (h, i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, bs, D), lambda h, i: (h, i, 0, 0)),
            pl.BlockSpec((H, K), lambda h, i: (0, 0)),
        ],
    )
    return pl.pallas_call(
        _quant_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((H, K, bs, D), jnp.int8),
            jax.ShapeDtypeStruct((H, K), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(blocks)


def _dequant_kernel(q_ref, scale_ref, out_ref):
    scale = scale_ref[pl.program_id(0), pl.program_id(1)]
    out_ref[...] = q_ref[...].astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_blocks(q: jax.Array, scales: jax.Array, *,
                      interpret: Optional[bool] = None) -> jax.Array:
    """q: (H, K, bs, D) int8, scales: (H, K) f32 -> (H, K, bs, D) f32."""
    H, K, bs, D = q.shape
    assert scales.shape == (H, K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(H, K),
        in_specs=[
            pl.BlockSpec((1, 1, bs, D), lambda h, i: (h, i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),           # scales
        ],
        out_specs=pl.BlockSpec((1, 1, bs, D), lambda h, i: (h, i, 0, 0)),
    )
    return pl.pallas_call(
        _dequant_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, K, bs, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, scales.astype(jnp.float32))


def _dequant_scatter_kernel(dest_ref, q_ref, scale_ref, pool_in_ref,
                            pool_out_ref):
    del pool_in_ref  # aliased with pool_out_ref; unvisited blocks persist
    scale = scale_ref[pl.program_id(0), pl.program_id(1)]
    pool_out_ref[...] = (q_ref[...].astype(jnp.float32) * scale
                         ).astype(pool_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_scatter_blocks(pool: jax.Array, q: jax.Array,
                              scales: jax.Array, dest_blocks: jax.Array, *,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Fused dequant + head-major block scatter (quantized FlashH2D restore).

    pool: (H, NB, bs, D) fp device slots; q: (H, K, bs, D) int8 payload;
    scales: (H, K) f32; dest_blocks: (K,) int32 destination block ids.
    Returns the updated pool (aliased in place) — the int8 H2D payload
    dequantizes on the VPU as each (head, block) tile lands, so the
    restore window still sees exactly one fused launch per layer."""
    H, NB, bs, D = pool.shape
    K = dest_blocks.shape[0]
    assert q.shape == (H, K, bs, D)
    assert scales.shape == (H, K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, K),
        in_specs=[
            pl.BlockSpec((1, 1, bs, D),
                         lambda h, i, dref: (h, i, 0, 0)),        # q
            pl.BlockSpec(memory_space=pltpu.SMEM),               # scales
            pl.BlockSpec((1, 1, bs, D),
                         lambda h, i, dref: (h, dref[i], 0, 0)),  # pool in
        ],
        out_specs=pl.BlockSpec((1, 1, bs, D),
                               lambda h, i, dref: (h, dref[i], 0, 0)),
    )
    return pl.pallas_call(
        _dequant_scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},  # pool (arg idx incl. prefetch) -> out 0
        interpret=resolve_interpret(interpret),
    )(dest_blocks.astype(jnp.int32), q, scales.astype(jnp.float32), pool)
