"""FlashH2D TPU analogue: fused fragmented KV-block gather (paper §3.2.1).

The paper's FlashH2D fuses many small per-block HBM<-DRAM copies into ONE
GPU kernel via CUDA UVA.  The TPU-native equivalent is a single Pallas
program whose *scalar-prefetched* index map drives one block-granular DMA
per grid step: the block ids arrive in SMEM before the body runs, so the
memory system streams all K fragmented blocks back-to-back — one launch,
full link utilisation, no per-copy descriptor overhead.

On a real deployment the source pool lives in host memory
(``jax.device_put(pool, ...memory_kind="pinned_host")``) and the same index
map expresses the H2D stream.  Checked in interpret mode against
``ref.gather_blocks`` and compiled through Mosaic for a described v5e.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _gather_kernel(idx_ref, pool_ref, out_ref):
    # pool_ref is the (1, bs, D) block selected by the index map — one DMA.
    out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_blocks(pool: jax.Array, idx: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """pool: (NB, bs, D); idx: (K,) int32 -> (K, bs, D)."""
    NB, bs, D = pool.shape
    K = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, bs, D), lambda i, idx_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, D), lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, bs, D), pool.dtype),
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32), pool)


def _gather_hkv_kernel(idx_ref, pool_ref, out_ref):
    out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_blocks_hkv(pool: jax.Array, idx: jax.Array, *,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Head-major fused gather: pool (H, NB, bs, D); idx (K,) int32 ->
    (H, K, bs, D).

    The per-head variant the persistent device plane
    (``repro.core.device_pool``) uses for one batch row: the paper's
    (H, N, D) layout (§3.2, Fig. 5) keeps each head's blocks contiguous, so
    the grid streams one (head, block) DMA per step — all H*K fragmented
    blocks in ONE launch."""
    H, NB, bs, D = pool.shape
    K = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, K),
        in_specs=[
            pl.BlockSpec((1, 1, bs, D),
                         lambda h, i, idx_ref: (h, idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bs, D),
                               lambda h, i, idx_ref: (h, i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_hkv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, K, bs, D), pool.dtype),
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32), pool)
