"""Flagship kernel: block-sparse paged decode attention (DSA compute phase).

One decode token attends to its top-K selected KV blocks.  The selected
block ids are *scalar-prefetched* so the BlockSpec index map DMAs exactly
the K fragmented blocks out of the paged pool — the same fused-transfer
idea as FlashH2D, applied to the attention read itself
(select-then-compute, paper Fig. 2).

Grid: (B, Hkv, K) with K innermost.  Online-softmax state (m, l, acc) lives
in VMEM scratch across the K steps of one (b, h) pair; the output tile is
written on the last step.  Tile shapes: q (G, D) — the GQA group — and
(bs, D) per KV block; D and bs are MXU/VPU-aligned (128 / 32) for the
assigned configs.

Validated in interpret mode against ``ref.sparse_decode_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -1e30


def _make_kernel(scale: float, bs: int, K: int):
    def kernel(idx_ref, valid_ref, lens_ref,   # scalar prefetch (SMEM)
               q_ref, k_ref, v_ref,            # VMEM tiles
               out_ref,                        # output tile
               m_ref, l_ref, acc_ref):         # VMEM scratch
        b = pl.program_id(0)
        h = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0, 0].astype(jnp.float32)                  # (G, D)
        k = k_ref[0, 0, 0].astype(jnp.float32)               # (bs, D)
        v = v_ref[0, 0, 0].astype(jnp.float32)               # (bs, Dv)

        blk = idx_ref[b, h, j]
        ok = valid_ref[b, h, j]
        cur = lens_ref[b]

        s = (q @ k.T) * scale                                # (G, bs)
        pos = blk * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        mask = (pos < cur) & (ok > 0)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + p @ v
        m_ref[...] = m_new

        @pl.when(j == K - 1)
        def _finalize():
            l = jnp.maximum(l_ref[...], 1e-30)
            out_ref[0, 0] = (acc_ref[...] / l).astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def sparse_decode_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_idx: jax.Array,
                            sel_valid: jax.Array, cur_len: jax.Array, *,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Hq, D); pools: (B, Hkv, NB, bs, D[v]); block_idx/sel_valid:
    (B, Hkv, K); cur_len: (B,) int32.  Returns (B, Hq, Dv)."""
    B, Hq, D = q.shape
    _, Hkv, NB, bs, _ = k_pool.shape
    Dv = v_pool.shape[-1]
    K = block_idx.shape[-1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qg = q.reshape(B, Hkv, G, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, K),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, j, idx, val, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs, D),
                         lambda b, h, j, idx, val, lens:
                         (b, h, idx[b, h, j], 0, 0)),
            pl.BlockSpec((1, 1, 1, bs, Dv),
                         lambda b, h, j, idx, val, lens:
                         (b, h, idx[b, h, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, h, j, idx, val, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),   # m
            pltpu.VMEM((G, 1), jnp.float32),   # l
            pltpu.VMEM((G, Dv), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        _make_kernel(scale, bs, K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_idx.astype(jnp.int32), sel_valid.astype(jnp.int32),
      cur_len.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(B, Hq, Dv)
