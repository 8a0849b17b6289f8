"""Pure-jnp oracles for every Pallas kernel (per-kernel allclose tests)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.dsa import score_blocks, sparse_decode_attention_ref
from repro.kernels.quant_blocks import int8_scale

NEG_INF = -1e30


def gather_blocks(pool: jax.Array, idx: jax.Array) -> jax.Array:
    """FlashH2D oracle: pool (NB, bs, D), idx (K,) -> (K, bs, D)."""
    return pool[idx]


def gather_blocks_hkv(pool: jax.Array, idx: jax.Array) -> jax.Array:
    """Head-major FlashH2D oracle: pool (H, NB, bs, D), idx (K,) ->
    (H, K, bs, D)."""
    return pool[:, idx]


def scatter_blocks_hkv(pool: jax.Array, new_kv: jax.Array,
                       dest_blocks: jax.Array) -> jax.Array:
    """Head-major block-scatter oracle: pool (H, NB, bs, D);
    new_kv (H, K, bs, D); dest_blocks (K,)."""
    return pool.at[:, dest_blocks].set(new_kv)


def scatter_blocks(pool: jax.Array, new_kv: jax.Array,
                   dest_blocks: jax.Array) -> jax.Array:
    """FlashD2H oracle.

    pool: (NB, bs, D); new_kv: (T, D) contiguous, T = n_new_blocks * bs;
    dest_blocks: (n_new_blocks,) destination block ids.
    Returns the pool with new blocks placed (whole-block granularity — the
    paper flushes blocks when they fill)."""
    nb, bs, D = pool.shape
    n_new = dest_blocks.shape[0]
    blocks = new_kv.reshape(n_new, bs, D)
    return pool.at[dest_blocks].set(blocks)


def quantize_blocks(blocks: jax.Array) -> tuple[jax.Array, jax.Array]:
    """int8 offload-quant oracle: blocks (H, K, bs, D) fp ->
    (q (H, K, bs, D) int8, scales (H, K) f32).

    Symmetric per-(head, block) quantization: scale = amax/127 over each
    (bs, D) tile, q = clip(rint(x * (1/scale)), -127, 127).  All-zero blocks get
    scale 0 (and quantize to 0) — dequant maps them back to exact zeros."""
    x = blocks.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=(-2, -1))
    # the kernel's own rounding rule (see quant_blocks.int8_scale)
    scales, inv = int8_scale(amax)
    q = jnp.clip(jnp.rint(x * inv[..., None, None]), -127.0, 127.0)
    return q.astype(jnp.int8), scales


def dequantize_blocks(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse oracle: q (H, K, bs, D) int8, scales (H, K) ->
    (H, K, bs, D) f32."""
    return q.astype(jnp.float32) * scales[..., None, None]


def dequantize_scatter_blocks(pool: jax.Array, q: jax.Array,
                              scales: jax.Array, dest_blocks: jax.Array
                              ) -> jax.Array:
    """Fused dequant-restore oracle: pool (H, NB, bs, D); q (H, K, bs, D)
    int8; scales (H, K); dest_blocks (K,).  Returns pool with the
    dequantized blocks placed (quantized ``scatter_blocks_hkv``)."""
    new = dequantize_blocks(q, scales).astype(pool.dtype)
    return pool.at[:, dest_blocks].set(new)


def block_score(q: jax.Array, meta_min: jax.Array, meta_max: jax.Array
                ) -> jax.Array:
    """Quest cuboid upper-bound scores, group-max over GQA query heads.

    q: (B, Hq, D); meta_min/max: (B, Hkv, NB, D) -> (B, Hkv, NB) f32."""
    meta = jnp.stack([meta_min, meta_max], axis=-2)
    return score_blocks(q, meta, method="cuboid", group_reduce="max")


def sparse_decode_attention(q, k_pool, v_pool, block_idx, sel_valid, cur_len,
                            scale: Optional[float] = None):
    """(B,Hq,D) x pools (B,Hkv,NB,bs,D) + selection -> (B,Hq,Dv)."""
    return sparse_decode_attention_ref(q, k_pool, v_pool, block_idx,
                                       sel_valid, cur_len, scale)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  scale: Optional[float] = None, q_offset: int = 0
                  ) -> jax.Array:
    """Causal full attention oracle.  q: (B,Sq,Hq,D); k/v: (B,Sk,Hkv,D)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Sk)
    mask = qpos[:, None] >= kpos[None, :]
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, Dv).astype(q.dtype)
