"""FlashD2H TPU analogue: contiguous KV flush -> paged pool scatter (§3.2.2).

The paper's FlashD2H saves newly generated KV in two phases: (1) one big
contiguous D2H memcpy into a DRAM staging buffer, (2) CPU threads scatter
the buffer into the per-head KV blocks asynchronously.  Phase (1) maps to a
single contiguous device->host DMA on TPU; phase (2) — placing contiguous
data into scattered pool blocks — is expressed here as one Pallas program
whose *output* index map scatters whole blocks, with the pool aliased
in-place (``input_output_aliases``) so untouched blocks are preserved.

Validated in interpret mode against ``ref.scatter_blocks``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _scatter_kernel(dest_ref, new_ref, pool_in_ref, pool_out_ref):
    del pool_in_ref  # aliased with pool_out_ref; unvisited blocks persist
    pool_out_ref[...] = new_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_blocks(pool: jax.Array, new_kv: jax.Array, dest_blocks: jax.Array,
                   *, interpret: Optional[bool] = None) -> jax.Array:
    """pool: (NB, bs, D); new_kv: (T, D) with T = n_new*bs (contiguous);
    dest_blocks: (n_new,) int32.  Returns updated pool."""
    NB, bs, D = pool.shape
    n_new = dest_blocks.shape[0]
    assert new_kv.shape[0] == n_new * bs
    new_blk = new_kv.reshape(n_new, bs, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_new,),
        in_specs=[
            pl.BlockSpec((1, bs, D), lambda i, dref: (i, 0, 0)),        # new
            pl.BlockSpec((1, bs, D), lambda i, dref: (dref[i], 0, 0)),  # pool in
        ],
        out_specs=pl.BlockSpec((1, bs, D), lambda i, dref: (dref[i], 0, 0)),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},  # pool (arg idx incl. prefetch) -> out 0
        interpret=resolve_interpret(interpret),
    )(dest_blocks.astype(jnp.int32), new_blk, pool)


def _scatter_hkv_kernel(dest_ref, new_ref, pool_in_ref, pool_out_ref):
    del pool_in_ref  # aliased with pool_out_ref; unvisited blocks persist
    pool_out_ref[...] = new_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_blocks_hkv(pool: jax.Array, new_kv: jax.Array,
                       dest_blocks: jax.Array, *,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Head-major block scatter: pool (H, NB, bs, D); new_kv (H, K, bs, D);
    dest_blocks (K,) int32.  Returns the updated pool (aliased in place).

    The per-head variant the persistent device plane uses to land fused
    FlashH2D payloads (``KVCacheManager.load_blocks_fused``) — and to zero
    HBM-evicted blocks — directly in a batch row's device slots: one grid
    step per (head, block), whole-block granularity, untouched blocks
    preserved via ``input_output_aliases``."""
    H, NB, bs, D = pool.shape
    K = dest_blocks.shape[0]
    assert new_kv.shape == (H, K, bs, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, K),
        in_specs=[
            pl.BlockSpec((1, 1, bs, D),
                         lambda h, i, dref: (h, i, 0, 0)),        # new
            pl.BlockSpec((1, 1, bs, D),
                         lambda h, i, dref: (h, dref[i], 0, 0)),  # pool in
        ],
        out_specs=pl.BlockSpec((1, 1, bs, D),
                               lambda h, i, dref: (h, dref[i], 0, 0)),
    )
    return pl.pallas_call(
        _scatter_hkv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},  # pool (arg idx incl. prefetch) -> out 0
        interpret=resolve_interpret(interpret),
    )(dest_blocks.astype(jnp.int32), new_kv, pool)
