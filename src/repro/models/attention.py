"""Attention modules: GQA (MHA/MQA as special cases) and MLA.

Three execution paths per flavour:

* ``*_train``   — full causal self-attention over a sequence (training and
  plain/layer-segmented prefill).  Uses memory-bounded blocked ("flash
  style") attention in pure jnp; the Pallas ``flash_prefill`` kernel mirrors
  the inner loop for TPU.
* ``*_decode_step`` — one new token against the paged KV pool with DSA
  block selection (SparseServe decode path).
* ``cross_attention`` — Whisper decoder cross-attention over cached encoder
  keys/values.

KV pool layout is the paper's head-major (H, N, D): ``(B, Hkv, NB, bs, D)``
so per-head block selection touches contiguous memory (§3.2, Fig. 5).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dsa
from repro.models.common import (DSAConfig, ModelConfig, apply_rope, dense_init, rms_norm, split_keys)

NEG_INF = -1e30

# Cost-calibration mode (roofline/calibrate.py): forces single-trip scans in
# blocked attention so XLA's cost analysis (which counts while-loop bodies
# ONCE, not x trip-count) reports exact FLOPs.  Never used on real runs.
EXACT_COST_MODE = False

# Context-parallel decode (shard_map) — §Perf optimization.  Baseline GSPMD
# all-gathers the block-sharded KV pool for the DSA gather (GBs per step);
# the CP paths keep pool data on its shard: in BLOCK mode only the (small)
# block SCORES are all-gathered, the global top-k is computed redundantly
# per shard, each shard attends over its LOCAL selected blocks, and
# partials merge with a logsumexp psum; in HEAD mode (staged plane,
# Hkv % n == 0) even that is unnecessary — selection and attention are
# per-kv-head-local and only the selected ids / per-head outputs cross the
# mesh.  The mesh arrives as an explicit ``launch.plane_mesh.PlaneMesh``
# threaded through every entry point (None -> single-device path); the
# former ``CP_AXES`` module global is gone.


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_gqa_params(cfg: ModelConfig, key: jax.Array, dtype,
                    cross: bool = False) -> Dict[str, jax.Array]:
    d, Hq, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, Hq * hd), dtype),
        "wk": dense_init(ks[1], (d, Hkv * hd), dtype),
        "wv": dense_init(ks[2], (d, Hkv * hd), dtype),
        "wo": dense_init(ks[3], (Hq * hd, d), dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((Hq * hd,), dtype)
        p["bk"] = jnp.zeros((Hkv * hd,), dtype)
        p["bv"] = jnp.zeros((Hkv * hd,), dtype)
    return p


def init_mla_params(cfg: ModelConfig, key: jax.Array, dtype) -> Dict[str, jax.Array]:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    ks = split_keys(key, 8)
    return {
        "w_dq": dense_init(ks[0], (d, m.q_lora_rank), dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "w_uq": dense_init(ks[1], (m.q_lora_rank,
                                   H * (m.qk_nope_head_dim + m.qk_rope_head_dim)), dtype),
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank), dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "w_kr": dense_init(ks[3], (d, m.qk_rope_head_dim), dtype),
        "w_uk": dense_init(ks[4], (m.kv_lora_rank, H * m.qk_nope_head_dim), dtype),
        "w_uv": dense_init(ks[5], (m.kv_lora_rank, H * m.v_head_dim), dtype),
        "wo": dense_init(ks[6], (H * m.v_head_dim, d), dtype),
    }


# ---------------------------------------------------------------------------
# Blocked ("flash-style") causal attention — memory bounded, pure jnp
# ---------------------------------------------------------------------------

def flash_attention_jnp(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        scale: float, causal: bool = True, q_offset=0,
                        q_chunk: int = 512, k_chunk: int = 512,
                        triangular: bool = False) -> jax.Array:
    """Online-softmax blocked attention.

    q: (B, Sq, Hq, D);  k/v: (B, Sk, Hkv, Dk/Dv).  GQA via head grouping.
    q_offset: absolute position of q[0] (chunked prefill continuation).
    triangular: skip fully-masked key chunks (halves causal FLOPs;
        §Perf optimization — unrolls the q-chunk loop in Python).
    Returns (B, Sq, Hq, Dv).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, Dk = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    if EXACT_COST_MODE:          # single-trip scans -> exact XLA flop count
        q_chunk, k_chunk = Sq, Sk
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    # pad to multiples
    pq = (-Sq) % q_chunk
    pk = (-Sk) % k_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq = (Sq + pq) // q_chunk
    nk = (Sk + pk) // k_chunk
    qr = q.reshape(B, nq, q_chunk, Hkv, G, D)
    kr = k.reshape(B, nk, k_chunk, Hkv, Dk)
    vr = v.reshape(B, nk, k_chunk, Hkv, Dv)
    kpos = jnp.arange(Sk + pk).reshape(nk, k_chunk)
    k_valid = (kpos < Sk)

    def one_q_chunk(iq, q_i, n_kv):
        # q_i: (B, q_chunk, Hkv, G, D)
        qpos = q_offset + iq * q_chunk + jnp.arange(q_chunk)

        def body(carry, j):
            m, l, acc = carry
            k_j = jax.lax.dynamic_index_in_dim(kr, j, axis=1, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vr, j, axis=1, keepdims=False)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_i.astype(jnp.float32),
                           k_j.astype(jnp.float32)) * scale
            kp = j * k_chunk + jnp.arange(k_chunk)
            mask = k_valid[j][None, :] if not causal else (
                (qpos[:, None] >= kp[None, :]) & k_valid[j][None, :])
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, v_j.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      jnp.arange(n_kv, dtype=jnp.int32))
        o = acc / jnp.maximum(l[..., None], 1e-30)
        # (B,Hkv,G,q_chunk,Dv) -> (B, q_chunk, Hkv, G, Dv)
        return jnp.transpose(o, (0, 3, 1, 2, 4))

    if triangular and causal:
        # python loop: static per-chunk kv bound -> no masked-out compute
        outs = []
        for iq in range(nq):
            q_i = qr[:, iq]
            hi = min(nk, (q_offset + (iq + 1) * q_chunk + k_chunk - 1) // k_chunk)
            outs.append(one_q_chunk(iq, q_i, max(hi, 1)))
        o = jnp.stack(outs, axis=1)
    else:
        o = jax.vmap(lambda iq, q_i: one_q_chunk(iq, q_i, nk),
                     in_axes=(0, 1), out_axes=1)(
            jnp.arange(nq, dtype=jnp.int32), qr)
    o = o.reshape(B, nq * q_chunk, Hq, Dv)[:, :Sq]
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA: train / prefill path
# ---------------------------------------------------------------------------

def gqa_project_qkv(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array):
    """x: (B, S, d) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd) with RoPE applied."""
    B, S, _ = x.shape
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, Hq, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_self_attention(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                       positions: jax.Array, *,
                       k_ctx: Optional[jax.Array] = None,
                       v_ctx: Optional[jax.Array] = None,
                       causal: bool = True, q_offset=0,
                       triangular: bool = False,
                       return_kv: bool = False):
    """Full (train / prefill) self-attention.  Optional dense context
    ``k_ctx/v_ctx`` (B, S_past, Hkv, hd) supports chunked prefill."""
    q, k, v = gqa_project_qkv(p, cfg, x, positions)
    if k_ctx is not None:
        k_all = jnp.concatenate([k_ctx, k], axis=1)
        v_all = jnp.concatenate([v_ctx, v], axis=1)
    else:
        k_all, v_all = k, v
    scale = 1.0 / (cfg.head_dim ** 0.5)
    o = flash_attention_jnp(q, k_all, v_all, scale=scale, causal=causal,
                            q_offset=q_offset, triangular=triangular)
    B, S = x.shape[:2]
    out = o.reshape(B, S, -1) @ p["wo"]
    if return_kv:
        return out, k, v
    return out


def cross_attention(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                    k_enc: jax.Array, v_enc: jax.Array):
    """Whisper decoder cross-attention; k_enc/v_enc: (B, S_enc, Hkv, hd)
    (already projected + cached once per request)."""
    B, S, _ = x.shape
    Hq, hd = cfg.num_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, Hq, hd)
    scale = 1.0 / (hd ** 0.5)
    o = flash_attention_jnp(q, k_enc, v_enc, scale=scale, causal=False)
    return o.reshape(B, S, -1) @ p["wo"]


def project_enc_kv(p: Dict[str, jax.Array], cfg: ModelConfig, enc: jax.Array):
    B, S, _ = enc.shape
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    k = (enc @ p["wk"]).reshape(B, S, Hkv, hd)
    v = (enc @ p["wv"]).reshape(B, S, Hkv, hd)
    return k, v


# ---------------------------------------------------------------------------
# Paged KV pool (decode)
# ---------------------------------------------------------------------------

def init_layer_kv_pool(cfg: ModelConfig, batch: int, num_blocks: int,
                       dtype) -> Dict[str, jax.Array]:
    """Per-layer paged pool + DSA metadata (zeros; filled by prefill/decode)."""
    bs = cfg.dsa.block_size
    if cfg.attention_type == "mla":
        m = cfg.mla
        lat = m.latent_dim
        return {
            # latent cache acts as a single-kv-head pool; k==v==latent
            "k": jnp.zeros((batch, 1, num_blocks, bs, lat), dtype),
            "meta": jnp.zeros(dsa.metadata_shape(cfg.dsa, num_blocks, lat,
                                                 (batch, 1)), jnp.float32),
        }
    hd = cfg.head_dim
    Hkv = cfg.num_kv_heads
    return {
        "k": jnp.zeros((batch, Hkv, num_blocks, bs, hd), dtype),
        "v": jnp.zeros((batch, Hkv, num_blocks, bs, hd), dtype),
        "meta": jnp.zeros(dsa.metadata_shape(cfg.dsa, num_blocks, hd,
                                             (batch, Hkv)), jnp.float32),
    }


def pad_pool_cache(cache: Dict[str, jax.Array], num_blocks: int
                   ) -> Dict[str, jax.Array]:
    """Zero-pad an attn-layer pool cache ({k[,v],meta}) along the block axis
    (axis 2 for every component) to `num_blocks` — the padded-batch
    abstraction batched decode uses to stack requests with heterogeneous
    pool sizes.  Padded blocks sit beyond every request's ``cur_len`` so DSA
    selection masks them out (select_blocks' n_valid bound)."""
    nb = cache["k"].shape[2]
    if nb == num_blocks:
        return cache
    if nb > num_blocks:
        raise ValueError(f"cannot pad pool of {nb} blocks down to "
                         f"{num_blocks}")
    pad = num_blocks - nb
    return {
        key: jnp.pad(arr, ((0, 0), (0, 0), (0, pad))
                     + ((0, 0),) * (arr.ndim - 3))
        for key, arr in cache.items()
    }


def slice_pool_cache(cache: Dict[str, jax.Array], num_blocks: int
                     ) -> Dict[str, jax.Array]:
    """Inverse of ``pad_pool_cache``: trim the block axis back to the
    request's own pool size after a batched decode step."""
    if cache["k"].shape[2] == num_blocks:
        return cache
    return {key: arr[:, :, :num_blocks] for key, arr in cache.items()}


def _append_to_pool(pool: jax.Array, new: jax.Array, cur_len: jax.Array,
                    block_size: int) -> jax.Array:
    """pool: (B, H, NB, bs, D); new: (B, H, D); cur_len: (B,)."""
    B, H = new.shape[0], new.shape[1]
    blk = cur_len // block_size
    slot = cur_len % block_size
    bidx = jnp.arange(B)[:, None]
    hidx = jnp.arange(H)[None, :]
    return pool.at[bidx, hidx, blk[:, None], slot[:, None]].set(
        new.astype(pool.dtype))


def _update_meta(meta: jax.Array, new_k: jax.Array, cur_len: jax.Array,
                 dsa_cfg: DSAConfig) -> jax.Array:
    """Incrementally update block metadata for the block receiving new_k.

    meta mean:   (B,H,NB,D);  cuboid: (B,H,NB,2,D).  new_k: (B,H,D)."""
    B, H, _ = new_k.shape
    bs = dsa_cfg.block_size
    blk = cur_len // bs
    slot = cur_len % bs
    bidx = jnp.arange(B)[:, None]
    hidx = jnp.arange(H)[None, :]
    kf = new_k.astype(jnp.float32)
    if dsa_cfg.metadata == "mean":
        old = meta[bidx, hidx, blk[:, None]]              # (B,H,D)
        cnt = slot[:, None, None].astype(jnp.float32)
        new_mean = (old * cnt + kf) / (cnt + 1.0)
        return meta.at[bidx, hidx, blk[:, None]].set(new_mean)
    old = meta[bidx, hidx, blk[:, None]]                  # (B,H,2,D)
    fresh = slot[:, None, None] == 0                      # new block starts
    old_mn = jnp.where(fresh, jnp.inf, old[..., 0, :])
    old_mx = jnp.where(fresh, -jnp.inf, old[..., 1, :])
    mn = jnp.minimum(old_mn, kf)
    mx = jnp.maximum(old_mx, kf)
    return meta.at[bidx, hidx, blk[:, None]].set(jnp.stack([mn, mx], axis=-2))


# ---------------------------------------------------------------------------
# Context-parallel decode attention (shard_map over the pool's block axis)
# ---------------------------------------------------------------------------

def _append_masked(pool, new, lblk, slot, mine):
    """Scatter `new` (B,H,D) into pool at (lblk, slot) only where mine (B,)."""
    B, H = new.shape[0], new.shape[1]
    bidx = jnp.arange(B)[:, None]
    hidx = jnp.arange(H)[None, :]
    old = pool[bidx, hidx, lblk[:, None], slot[:, None]]         # (B,H,D)
    val = jnp.where(mine[:, None, None], new.astype(pool.dtype), old)
    return pool.at[bidx, hidx, lblk[:, None], slot[:, None]].set(val)


def _update_meta_masked(meta, new_k, lblk, slot, mine, dsa_cfg):
    B, H, _ = new_k.shape
    bidx = jnp.arange(B)[:, None]
    hidx = jnp.arange(H)[None, :]
    kf = new_k.astype(jnp.float32)
    old = meta[bidx, hidx, lblk[:, None]]
    if dsa_cfg.metadata == "mean":
        cnt = slot[:, None, None].astype(jnp.float32)
        upd = (old * cnt + kf) / (cnt + 1.0)
    else:
        fresh = slot[:, None, None] == 0
        old_mn = jnp.where(fresh, jnp.inf, old[..., 0, :])
        old_mx = jnp.where(fresh, -jnp.inf, old[..., 1, :])
        upd = jnp.stack([jnp.minimum(old_mn, kf),
                         jnp.maximum(old_mx, kf)], axis=-2)
    sel = mine[:, None, None] if dsa_cfg.metadata == "mean" \
        else mine[:, None, None, None]
    upd = jnp.where(sel, upd, old)
    return meta.at[bidx, hidx, lblk[:, None]].set(upd)


def _cp_decode_local(cfg: ModelConfig, q, k, v, kpool, vpool, meta, cur_len,
                     model_axis: str):
    """Per-shard body: pools hold NB_loc local blocks."""
    bs = cfg.dsa.block_size
    NB_loc = kpool.shape[2]
    shard = jax.lax.axis_index(model_axis)
    offset = shard * NB_loc

    blk = cur_len // bs
    slot = cur_len % bs
    mine = (blk >= offset) & (blk < offset + NB_loc)
    lblk = jnp.clip(blk - offset, 0, NB_loc - 1)
    kpool = _append_masked(kpool, k, lblk, slot, mine)
    vpool = _append_masked(vpool, v, lblk, slot, mine)
    meta = _update_meta_masked(meta, k, lblk, slot, mine, cfg.dsa)
    new_len = cur_len + 1

    # local scores -> all-gather the SCORES (tiny), not the pool
    scores_loc = dsa.score_blocks(q, meta, cfg.dsa.metadata)     # (B,Hkv,NBl)
    scores = jax.lax.all_gather(scores_loc, model_axis, axis=2, tiled=True)
    idx, valid = dsa.select_blocks(scores, cfg.dsa, new_len)     # global ids
    loc_valid = valid & (idx >= offset) & (idx < offset + NB_loc)
    lidx = jnp.clip(idx - offset, 0, NB_loc - 1)
    acc, m, l = dsa.sparse_decode_attention_partial(
        q, kpool, vpool, lidx, loc_valid, new_len, offset)
    # logsumexp merge across shards
    m_g = jax.lax.pmax(m, model_axis)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_g), 0.0)
    l_g = jax.lax.psum(l * corr, model_axis)
    acc_g = jax.lax.psum(acc * corr[..., None], model_axis)
    o = (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q.dtype)
    return o, kpool, vpool, meta, idx


def _cp_mla_decode_local(cfg: ModelConfig, q_eff, latent, kpool, meta,
                         cur_len, model_axis: str):
    """MLA variant: ONE latent head, k_pool doubles as v_pool (value = the
    first kv_lora_rank dims, sliced by the caller)."""
    bs = cfg.dsa.block_size
    NB_loc = kpool.shape[2]
    shard = jax.lax.axis_index(model_axis)
    offset = shard * NB_loc

    blk = cur_len // bs
    slot = cur_len % bs
    mine = (blk >= offset) & (blk < offset + NB_loc)
    lblk = jnp.clip(blk - offset, 0, NB_loc - 1)
    lat1 = latent[:, None, :]                       # (B, 1, lat)
    kpool = _append_masked(kpool, lat1, lblk, slot, mine)
    meta = _update_meta_masked(meta, lat1, lblk, slot, mine, cfg.dsa)
    new_len = cur_len + 1

    scores_loc = dsa.score_blocks(q_eff, meta, cfg.dsa.metadata)
    scores = jax.lax.all_gather(scores_loc, model_axis, axis=2, tiled=True)
    idx, valid = dsa.select_blocks(scores, cfg.dsa, new_len)
    loc_valid = valid & (idx >= offset) & (idx < offset + NB_loc)
    lidx = jnp.clip(idx - offset, 0, NB_loc - 1)
    m_cfg = cfg.mla
    scale = 1.0 / ((m_cfg.qk_nope_head_dim + m_cfg.qk_rope_head_dim) ** 0.5)
    acc, m, l = dsa.sparse_decode_attention_partial(
        q_eff, kpool, kpool, lidx, loc_valid, new_len, offset, scale=scale)
    m_g = jax.lax.pmax(m, model_axis)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_g), 0.0)
    l_g = jax.lax.psum(l * corr, model_axis)
    acc_g = jax.lax.psum(acc * corr[..., None], model_axis)
    o_lat = (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q_eff.dtype)
    return o_lat, kpool, meta, idx


def cp_mla_decode_attention(cfg: ModelConfig, q_eff, latent, cache, cur_len,
                            *, pm):
    """Context-parallel MLA decode (latent pool block-sharded over the
    model axis).  pm: ``launch.plane_mesh.PlaneMesh``."""
    from jax.sharding import PartitionSpec as P
    # drop batch sharding when B doesn't divide the dp axes (e.g. batch=1
    # long-context decode: pure context parallelism over the model axis)
    dp = pm.dp_entry(q_eff.shape[0])
    model_axis = pm.model_axis
    vec = P(dp, None, None)
    lat_s = P(dp, None)
    pool_s = P(dp, None, model_axis, None, None)
    meta_s = P(*([dp, None, model_axis] + [None] * (cache["meta"].ndim - 3)))
    fn = jax.shard_map(
        lambda q_, lt_, kp_, mt_, cl_: _cp_mla_decode_local(
            cfg, q_, lt_, kp_, mt_, cl_, model_axis),
        check_vma=False, mesh=pm.mesh,
        in_specs=(vec, lat_s, pool_s, meta_s, P(dp)),
        out_specs=(vec, pool_s, meta_s, vec))
    o_lat, kpool, meta, idx = fn(q_eff, latent, cache["k"], cache["meta"],
                                 cur_len)
    return o_lat, {"k": kpool, "meta": meta}, idx


def cp_decode_attention(cfg: ModelConfig, q, k, v, cache, cur_len, *, pm):
    """shard_map context-parallel select-then-compute decode attention
    (fused form: the whole select+attend in ONE shard_map; the staged
    plane uses the split ``gqa_select_step_cp``/``gqa_attend_step_cp``).

    q (B,Hq,hd); k/v (B,Hkv,hd) new-token projections; cache pools sharded
    (dp, None, model, None, None).  pm: ``launch.plane_mesh.PlaneMesh``.
    Returns (o, new_cache, selected)."""
    from jax.sharding import PartitionSpec as P
    dp = pm.dp_entry(q.shape[0])
    model_axis = pm.model_axis
    vec = P(dp, None, None)
    pool_s = P(dp, None, model_axis, None, None)
    meta_s = P(*([dp, None, model_axis]
                 + [None] * (cache["meta"].ndim - 3)))
    fn = jax.shard_map(
        lambda q_, k_, v_, kp_, vp_, mt_, cl_: _cp_decode_local(
            cfg, q_, k_, v_, kp_, vp_, mt_, cl_, model_axis),
        check_vma=False, mesh=pm.mesh,
        in_specs=(vec, vec, vec, pool_s, pool_s, meta_s, P(dp)),
        out_specs=(vec, pool_s, pool_s, meta_s, vec))
    o, kpool, vpool, meta, idx = fn(q, k, v, cache["k"], cache["v"],
                                    cache["meta"], cur_len)
    return o, {"k": kpool, "v": vpool, "meta": meta}, idx


# ---------------------------------------------------------------------------
# Context-parallel STAGED decode stages (the sharded plane's select/attend)
#
# Mirrors of ``gqa/mla_select_step`` and ``gqa/mla_attend_step`` whose
# pool-touching core runs under shard_map so the plane's persistent pool
# slots live sharded across ``pm.model_axis``.  Two layouts (see
# ``PlaneMesh.pool_shard_mode``):
#
# * "heads" — pool sharded on the KV-HEAD axis.  Scoring, top-k and
#   block-sparse attention are per-kv-head-local, so select and attend run
#   with ZERO pool communication; the out_specs' reassembly of the selected
#   ids (select) and the per-head outputs (attend) is the only data that
#   crosses the model axis.
# * "blocks" — pool sharded on the BLOCK axis (MLA latent pools; head
#   counts that don't divide).  Select all-gathers only the block SCORES
#   (B,Hkv,NB fp32) and computes the global top-k redundantly per shard;
#   attend computes flash partials over the LOCAL selected blocks and
#   merges with a logsumexp psum.
#
# Either way the selections handed back to the host are GLOBAL block ids,
# so the engine's LRU / FlashD2H / FlashH2D staging is layout-agnostic.
# Projections (q/k/v, output) and the layer epilogue stay replicated
# outside the shard_map.
# ---------------------------------------------------------------------------


def gqa_select_step_cp(p: Dict[str, jax.Array], cfg: ModelConfig,
                       x: jax.Array, cache: Dict[str, jax.Array],
                       cur_len: jax.Array, pm, *,
                       step_mask: Optional[jax.Array] = None):
    """Sharded select stage: append new KV + update metadata + score +
    top-k with the pool sharded per ``pm``.  Returns (q, new_cache, idx,
    valid) exactly like ``gqa_select_step``; idx/valid are GLOBAL."""
    from jax.sharding import PartitionSpec as P
    if not cfg.dsa.enabled:
        raise NotImplementedError("sharded planes require DSA "
                                  "(cfg.dsa.enabled)")
    bs = cfg.dsa.block_size
    q, k, v = _gqa_project_decode(p, cfg, x, cur_len)
    B, Hq, hd = q.shape
    Hkv = cache["k"].shape[1]
    G = Hq // Hkv
    mask = (step_mask if step_mask is not None
            else jnp.ones((B,), dtype=bool))
    dp = pm.dp_entry(B)
    m = pm.model_axis
    mode = pm.pool_shard_mode(cfg)
    vec = P(dp)

    if mode == "heads":
        pool_s = P(dp, m, None, None, None)
        meta_s = P(*([dp, m] + [None] * (cache["meta"].ndim - 2)))
        hvec = P(dp, m, None)

        def body(q4_, k_, v_, kp_, vp_, mt_, cl_, mk_):
            blk, slot = cl_ // bs, cl_ % bs
            kp_ = _append_masked(kp_, k_, blk, slot, mk_)
            vp_ = _append_masked(vp_, v_, blk, slot, mk_)
            mt_ = _update_meta_masked(mt_, k_, blk, slot, mk_, cfg.dsa)
            Bl, Hl = q4_.shape[0], q4_.shape[1]
            qh = q4_.reshape(Bl, Hl * G, q4_.shape[-1])
            scores = dsa.score_blocks(qh, mt_, cfg.dsa.metadata)
            idx_, valid_ = dsa.select_blocks(scores, cfg.dsa, cl_ + 1)
            return kp_, vp_, mt_, idx_, valid_

        fn = jax.shard_map(
            body, check_vma=False, mesh=pm.mesh,
            in_specs=(P(dp, m, None, None), hvec, hvec, pool_s, pool_s,
                      meta_s, vec, vec),
            out_specs=(pool_s, pool_s, meta_s, P(dp, m, None),
                       P(dp, m, None)))
        kp, vp, mt, idx, valid = fn(q.reshape(B, Hkv, G, hd), k, v,
                                    cache["k"], cache["v"], cache["meta"],
                                    cur_len, mask)
        # pools STAY sharded; the ids handed to the host / attend go back
        # to replicated so their sharding cannot leak into later stages
        idx, valid = pm.replicate((idx, valid))
        return q, {"k": kp, "v": vp, "meta": mt}, idx, valid

    pool_s = P(dp, None, m, None, None)
    meta_s = P(*([dp, None, m] + [None] * (cache["meta"].ndim - 3)))

    def body(q_, k_, v_, kp_, vp_, mt_, cl_, mk_):
        NB_loc = kp_.shape[2]
        offset = jax.lax.axis_index(m) * NB_loc
        blk, slot = cl_ // bs, cl_ % bs
        mine = (blk >= offset) & (blk < offset + NB_loc) & mk_
        lblk = jnp.clip(blk - offset, 0, NB_loc - 1)
        kp_ = _append_masked(kp_, k_, lblk, slot, mine)
        vp_ = _append_masked(vp_, v_, lblk, slot, mine)
        mt_ = _update_meta_masked(mt_, k_, lblk, slot, mine, cfg.dsa)
        # all-gather the SCORES (tiny), never the pool: global top-k is
        # computed redundantly per shard -> replicated GLOBAL ids
        scores_loc = dsa.score_blocks(q_, mt_, cfg.dsa.metadata)
        scores = jax.lax.all_gather(scores_loc, m, axis=2, tiled=True)
        idx_, valid_ = dsa.select_blocks(scores, cfg.dsa, cl_ + 1)
        return kp_, vp_, mt_, idx_, valid_

    fn = jax.shard_map(
        body, check_vma=False, mesh=pm.mesh,
        in_specs=(P(dp, None, None), P(dp, None, None), P(dp, None, None),
                  pool_s, pool_s, meta_s, vec, vec),
        out_specs=(pool_s, pool_s, meta_s, P(dp, None, None),
                   P(dp, None, None)))
    kp, vp, mt, idx, valid = fn(q, k, v, cache["k"], cache["v"],
                                cache["meta"], cur_len, mask)
    idx, valid = pm.replicate((idx, valid))
    return q, {"k": kp, "v": vp, "meta": mt}, idx, valid


def gqa_attend_step_cp(p: Dict[str, jax.Array], cfg: ModelConfig,
                       q: jax.Array, cache: Dict[str, jax.Array],
                       cur_len: jax.Array, idx: jax.Array,
                       valid: jax.Array, pm) -> jax.Array:
    """Sharded compute stage: block-sparse attention over the sharded
    (possibly host-restored) pool + output projection.  Read-only on
    ``cache``; uses the reference attention inside shard_map."""
    from jax.sharding import PartitionSpec as P
    B, Hq, hd = q.shape
    Hkv = cache["k"].shape[1]
    G = Hq // Hkv
    dp = pm.dp_entry(B)
    m = pm.model_axis
    new_len = cur_len + 1

    if pm.pool_shard_mode(cfg) == "heads":
        pool_s = P(dp, m, None, None, None)

        def body(q4_, kp_, vp_, nl_, idx_, valid_):
            Bl, Hl = q4_.shape[0], q4_.shape[1]
            qh = q4_.reshape(Bl, Hl * G, q4_.shape[-1])
            o = dsa.sparse_decode_attention_ref(qh, kp_, vp_, idx_, valid_,
                                                nl_)
            return o.reshape(Bl, Hl, G, o.shape[-1])

        fn = jax.shard_map(
            body, check_vma=False, mesh=pm.mesh,
            in_specs=(P(dp, m, None, None), pool_s, pool_s, P(dp),
                      P(dp, m, None), P(dp, m, None)),
            out_specs=P(dp, m, None, None))
        o = pm.replicate(fn(q.reshape(B, Hkv, G, hd), cache["k"],
                            cache["v"], new_len, idx, valid))
        return o.reshape(B, Hq * o.shape[-1]) @ p["wo"]

    pool_s = P(dp, None, m, None, None)

    def body(q_, kp_, vp_, nl_, idx_, valid_):
        NB_loc = kp_.shape[2]
        offset = jax.lax.axis_index(m) * NB_loc
        loc_valid = valid_ & (idx_ >= offset) & (idx_ < offset + NB_loc)
        lidx = jnp.clip(idx_ - offset, 0, NB_loc - 1)
        acc, mx, l = dsa.sparse_decode_attention_partial(
            q_, kp_, vp_, lidx, loc_valid, nl_, offset)
        m_g = jax.lax.pmax(mx, m)
        corr = jnp.where(jnp.isfinite(mx), jnp.exp(mx - m_g), 0.0)
        l_g = jax.lax.psum(l * corr, m)
        acc_g = jax.lax.psum(acc * corr[..., None], m)
        return (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q_.dtype)

    fn = jax.shard_map(
        body, check_vma=False, mesh=pm.mesh,
        in_specs=(P(dp, None, None), pool_s, pool_s, P(dp),
                  P(dp, None, None), P(dp, None, None)),
        out_specs=P(dp, None, None))
    o = pm.replicate(fn(q, cache["k"], cache["v"], new_len, idx, valid))
    return o.reshape(B, Hq * o.shape[-1]) @ p["wo"]


def mla_select_step_cp(p: Dict[str, jax.Array], cfg: ModelConfig,
                       x: jax.Array, cache: Dict[str, jax.Array],
                       cur_len: jax.Array, pm, *,
                       step_mask: Optional[jax.Array] = None):
    """MLA sharded select stage (latent pool: ONE kv head -> always block
    mode).  Returns (q_eff, new_cache, idx, valid); idx GLOBAL."""
    from jax.sharding import PartitionSpec as P
    if not cfg.dsa.enabled:
        raise NotImplementedError("sharded planes require DSA "
                                  "(cfg.dsa.enabled)")
    bs = cfg.dsa.block_size
    q_eff, latent = _mla_project_decode(p, cfg, x, cur_len)
    B = q_eff.shape[0]
    mask = (step_mask if step_mask is not None
            else jnp.ones((B,), dtype=bool))
    dp = pm.dp_entry(B)
    m = pm.model_axis
    pool_s = P(dp, None, m, None, None)
    meta_s = P(*([dp, None, m] + [None] * (cache["meta"].ndim - 3)))

    def body(q_, lat_, kp_, mt_, cl_, mk_):
        NB_loc = kp_.shape[2]
        offset = jax.lax.axis_index(m) * NB_loc
        blk, slot = cl_ // bs, cl_ % bs
        mine = (blk >= offset) & (blk < offset + NB_loc) & mk_
        lblk = jnp.clip(blk - offset, 0, NB_loc - 1)
        lat1 = lat_[:, None, :]
        kp_ = _append_masked(kp_, lat1, lblk, slot, mine)
        mt_ = _update_meta_masked(mt_, lat1, lblk, slot, mine, cfg.dsa)
        scores_loc = dsa.score_blocks(q_, mt_, cfg.dsa.metadata)
        scores = jax.lax.all_gather(scores_loc, m, axis=2, tiled=True)
        idx_, valid_ = dsa.select_blocks(scores, cfg.dsa, cl_ + 1)
        return kp_, mt_, idx_, valid_

    fn = jax.shard_map(
        body, check_vma=False, mesh=pm.mesh,
        in_specs=(P(dp, None, None), P(dp, None), pool_s, meta_s,
                  P(dp), P(dp)),
        out_specs=(pool_s, meta_s, P(dp, None, None), P(dp, None, None)))
    kp, mt, idx, valid = fn(q_eff, latent, cache["k"], cache["meta"],
                            cur_len, mask)
    idx, valid = pm.replicate((idx, valid))
    return q_eff, {"k": kp, "meta": mt}, idx, valid


def mla_attend_step_cp(p: Dict[str, jax.Array], cfg: ModelConfig,
                       q_eff: jax.Array, cache: Dict[str, jax.Array],
                       cur_len: jax.Array, idx: jax.Array,
                       valid: jax.Array, pm) -> jax.Array:
    """MLA sharded compute stage: latent block-sparse attention partials
    over the local shard + logsumexp merge + value/output projection."""
    from jax.sharding import PartitionSpec as P
    mc = cfg.mla
    B = q_eff.shape[0]
    H = cfg.num_heads
    dn, dr, dv, lat = (mc.qk_nope_head_dim, mc.qk_rope_head_dim,
                       mc.v_head_dim, mc.kv_lora_rank)
    scale = 1.0 / ((dn + dr) ** 0.5)
    dp = pm.dp_entry(B)
    m = pm.model_axis
    pool_s = P(dp, None, m, None, None)

    def body(q_, kp_, nl_, idx_, valid_):
        NB_loc = kp_.shape[2]
        offset = jax.lax.axis_index(m) * NB_loc
        loc_valid = valid_ & (idx_ >= offset) & (idx_ < offset + NB_loc)
        lidx = jnp.clip(idx_ - offset, 0, NB_loc - 1)
        acc, mx, l = dsa.sparse_decode_attention_partial(
            q_, kp_, kp_, lidx, loc_valid, nl_, offset, scale=scale)
        m_g = jax.lax.pmax(mx, m)
        corr = jnp.where(jnp.isfinite(mx), jnp.exp(mx - m_g), 0.0)
        l_g = jax.lax.psum(l * corr, m)
        acc_g = jax.lax.psum(acc * corr[..., None], m)
        return (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q_.dtype)

    fn = jax.shard_map(
        body, check_vma=False, mesh=pm.mesh,
        in_specs=(P(dp, None, None), pool_s, P(dp), P(dp, None, None),
                  P(dp, None, None)),
        out_specs=P(dp, None, None))
    o_lat = pm.replicate(fn(q_eff, cache["k"], cur_len + 1,
                            idx, valid))[..., :lat]
    w_uv = p["w_uv"].reshape(lat, H, dv)
    o = jnp.einsum("bhl,lhd->bhd", o_lat.astype(jnp.float32),
                   w_uv.astype(jnp.float32)).astype(q_eff.dtype)
    return o.reshape(B, H * dv) @ p["wo"]


# ---------------------------------------------------------------------------
# GQA decode step (DSA select-then-compute)
#
# The decode forward is split into two stage functions so the serving
# engine's STAGED decode plane can interleave host work between them:
#
#   gqa_select_step : project q/k/v, append the new KV to the paged pool,
#                     update DSA metadata, score + top-k select.
#   gqa_attend_step : block-sparse attention over the (possibly restored)
#                     pool + output projection.  Cache is READ-ONLY here —
#                     the host may have scattered H2D restore payloads into
#                     it between the two stages.
#
# ``gqa_decode_step`` composes the two in one trace (the fused plane); the
# staged plane jits each stage separately, so a fused FlashH2D restore of
# HBM-evicted blocks can land between select and attend — before use.
# ---------------------------------------------------------------------------

def _gqa_project_decode(p: Dict[str, jax.Array], cfg: ModelConfig,
                        x: jax.Array, cur_len: jax.Array):
    """Decode-token q/k/v projections with RoPE at position cur_len.
    x: (B, d) -> q (B,Hq,hd), k/v (B,Hkv,hd)."""
    B, _ = x.shape
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, 1, Hq, hd)
    k = k.reshape(B, 1, Hkv, hd)
    v = v.reshape(B, 1, Hkv, hd)
    q = apply_rope(q, cur_len[:, None], cfg.rope_theta)[:, 0]   # (B,Hq,hd)
    k = apply_rope(k, cur_len[:, None], cfg.rope_theta)[:, 0]
    return q, k, v[:, 0]


def gqa_select_step(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                    cache: Dict[str, jax.Array], cur_len: jax.Array,
                    *, step_mask: Optional[jax.Array] = None):
    """Select stage: append new KV, update metadata, score + top-k.

    Returns (q, new_cache, idx, valid); idx/valid are None when DSA is
    disabled (the attend stage then runs dense attention over the pool).
    step_mask: rows where False keep pool/meta byte-for-byte unchanged."""
    bs = cfg.dsa.block_size
    q, k, v = _gqa_project_decode(p, cfg, x, cur_len)
    if step_mask is None:
        k_pool = _append_to_pool(cache["k"], k, cur_len, bs)
        v_pool = _append_to_pool(cache["v"], v, cur_len, bs)
        meta = _update_meta(cache["meta"], k, cur_len, cfg.dsa)
    else:
        blk, slot = cur_len // bs, cur_len % bs
        k_pool = _append_masked(cache["k"], k, blk, slot, step_mask)
        v_pool = _append_masked(cache["v"], v, blk, slot, step_mask)
        meta = _update_meta_masked(cache["meta"], k, blk, slot, step_mask,
                                   cfg.dsa)
    idx = valid = None
    if cfg.dsa.enabled:
        scores = dsa.score_blocks(q, meta, cfg.dsa.metadata)
        idx, valid = dsa.select_blocks(scores, cfg.dsa, cur_len + 1)
    return q, {"k": k_pool, "v": v_pool, "meta": meta}, idx, valid


def gqa_attend_step(p: Dict[str, jax.Array], cfg: ModelConfig, q: jax.Array,
                    cache: Dict[str, jax.Array], cur_len: jax.Array,
                    idx: Optional[jax.Array], valid: Optional[jax.Array],
                    *, attn_impl: str = "ref") -> jax.Array:
    """Compute stage: block-sparse attention over the selected blocks of the
    (possibly restored) pool, then the output projection.  Pure read of
    ``cache`` — never mutates it."""
    B, Hq, hd = q.shape
    new_len = cur_len + 1
    if idx is None:
        o = dsa.full_decode_attention_ref(q, cache["k"], cache["v"], new_len)
    elif attn_impl == "kernel":
        from repro.kernels import ops as kops
        o = kops.sparse_decode_attention(q, cache["k"], cache["v"], idx,
                                         valid, new_len)
    else:
        o = dsa.sparse_decode_attention_ref(q, cache["k"], cache["v"], idx,
                                            valid, new_len)
    return o.reshape(B, Hq * hd) @ p["wo"]


def gqa_decode_step(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                    cache: Dict[str, jax.Array], cur_len: jax.Array,
                    *, attn_impl: str = "ref",
                    plane_mesh=None,
                    step_mask: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode token, select and attend FUSED in one trace.
    x: (B, d); cur_len: (B,) tokens already cached.

    Select-then-compute (paper Fig. 2): write new KV -> update metadata ->
    score blocks -> top-k -> block-sparse attention.
    plane_mesh: ``launch.plane_mesh.PlaneMesh`` — context-parallel decode
    over a block-sharded pool (one fused shard_map) — or None.
    step_mask: optional (B,) bool — rows where False keep their pool/meta
    byte-for-byte unchanged (the persistent device plane steps a padded
    batch whose inactive rows must not mutate; attention still computes
    garbage for those rows, which the caller discards).
    """
    B, _ = x.shape
    Hq, hd = cfg.num_heads, cfg.head_dim

    if plane_mesh is not None and cfg.dsa.enabled:
        if step_mask is not None:
            raise NotImplementedError(
                "fused context-parallel decode does not support step_mask "
                "(the sharded PLANES use the staged select/attend split; "
                "sharding the fused persistent plane is a follow-up)")
        q, k, v = _gqa_project_decode(p, cfg, x, cur_len)
        o, new_cache, sel = cp_decode_attention(cfg, q, k, v, cache,
                                                cur_len, pm=plane_mesh)
        out = o.reshape(B, Hq * hd) @ p["wo"]
        return out, new_cache, sel

    q, new_cache, idx, valid = gqa_select_step(p, cfg, x, cache, cur_len,
                                               step_mask=step_mask)
    out = gqa_attend_step(p, cfg, q, new_cache, cur_len, idx, valid,
                          attn_impl=attn_impl)
    return out, new_cache, idx


def cross_decode_step(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                      k_enc: jax.Array, v_enc: jax.Array) -> jax.Array:
    """Whisper decoder cross-attn for one token; x: (B, d)."""
    out = cross_attention(p, cfg, x[:, None, :], k_enc, v_enc)
    return out[:, 0]


# ---------------------------------------------------------------------------
# MLA — MiniCPM3 / DeepSeek-V2 latent attention
# ---------------------------------------------------------------------------

def mla_self_attention(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                       positions: jax.Array, *, return_latent: bool = False):
    """Train / prefill MLA (non-absorbed form).  x: (B, S, d)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    cq = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    qall = (cq @ p["w_uq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = qall[..., :dn], qall[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = x @ p["w_dkv"]                                  # (B,S,lat)
    c_kv_n = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope((x @ p["w_kr"])[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]           # (B,S,dr) shared
    k_nope = (c_kv_n @ p["w_uk"]).reshape(B, S, H, dn)
    vfull = (c_kv_n @ p["w_uv"]).reshape(B, S, H, dv)

    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))],
                        axis=-1)
    scale = 1.0 / ((dn + dr) ** 0.5)
    o = flash_attention_jnp(q, k, vfull, scale=scale, causal=True)
    out = o.reshape(B, S, H * dv) @ p["wo"]
    if return_latent:
        latent = jnp.concatenate([c_kv_n, k_rope], axis=-1)  # (B,S,lat+dr)
        return out, latent
    return out


def _mla_project_decode(p: Dict[str, jax.Array], cfg: ModelConfig,
                        x: jax.Array, cur_len: jax.Array):
    """Absorbed-form decode projections: effective query in latent space and
    the new token's latent KV.  x: (B, d) -> (q_eff (B,H,lat+dr),
    latent (B, lat+dr))."""
    m = cfg.mla
    B, _ = x.shape
    H = cfg.num_heads
    dn, dr, lat = m.qk_nope_head_dim, m.qk_rope_head_dim, m.kv_lora_rank

    cq = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    qall = (cq @ p["w_uq"]).reshape(B, H, dn + dr)
    q_nope, q_rope = qall[..., :dn], qall[..., dn:]
    q_rope = apply_rope(q_rope[:, None], cur_len[:, None], cfg.rope_theta)[:, 0]

    # absorb W_UK into the query: q_abs[h] = q_nope[h] @ W_UK[:, h, :].T
    w_uk = p["w_uk"].reshape(lat, H, dn)
    q_abs = jnp.einsum("bhd,lhd->bhl", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32)).astype(x.dtype)
    q_eff = jnp.concatenate([q_abs, q_rope], axis=-1)       # (B,H,lat+dr)

    c_kv = x @ p["w_dkv"]
    c_kv_n = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope((x @ p["w_kr"])[:, None, None, :], cur_len[:, None],
                        cfg.rope_theta)[:, 0, 0]
    latent = jnp.concatenate([c_kv_n, k_rope], axis=-1)     # (B, lat+dr)
    return q_eff, latent


def mla_select_step(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                    cache: Dict[str, jax.Array], cur_len: jax.Array,
                    *, step_mask: Optional[jax.Array] = None):
    """MLA select stage (mirror of ``gqa_select_step`` over the latent
    pool).  Returns (q_eff, new_cache, idx, valid)."""
    bs = cfg.dsa.block_size
    q_eff, latent = _mla_project_decode(p, cfg, x, cur_len)
    if step_mask is None:
        k_pool = _append_to_pool(cache["k"], latent[:, None, :], cur_len, bs)
        meta = _update_meta(cache["meta"], latent[:, None, :], cur_len,
                            cfg.dsa)
    else:
        blk, slot = cur_len // bs, cur_len % bs
        k_pool = _append_masked(cache["k"], latent[:, None, :], blk, slot,
                                step_mask)
        meta = _update_meta_masked(cache["meta"], latent[:, None, :], blk,
                                   slot, step_mask, cfg.dsa)
    idx = valid = None
    if cfg.dsa.enabled:
        scores = dsa.score_blocks(q_eff, meta, cfg.dsa.metadata)
        idx, valid = dsa.select_blocks(scores, cfg.dsa, cur_len + 1)
    return q_eff, {"k": k_pool, "meta": meta}, idx, valid


def mla_attend_step(p: Dict[str, jax.Array], cfg: ModelConfig,
                    q_eff: jax.Array, cache: Dict[str, jax.Array],
                    cur_len: jax.Array, idx: Optional[jax.Array],
                    valid: Optional[jax.Array], *,
                    attn_impl: str = "ref") -> jax.Array:
    """MLA compute stage: latent block-sparse attention over the (possibly
    restored) pool, value up-projection, output projection.  Read-only on
    ``cache``."""
    m = cfg.mla
    B = q_eff.shape[0]
    H = cfg.num_heads
    dn, dr, dv, lat = (m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
                       m.kv_lora_rank)
    scale = 1.0 / ((dn + dr) ** 0.5)
    new_len = cur_len + 1
    k_pool = cache["k"]
    if idx is None:
        o_lat = dsa.full_decode_attention_ref(q_eff, k_pool, k_pool, new_len,
                                              scale=scale)
    else:
        o_lat = dsa.sparse_decode_attention_ref(q_eff, k_pool, k_pool, idx,
                                                valid, new_len, scale=scale)
    # o_lat: (B, H, lat+dr); value part is the first `lat` dims
    o_lat = o_lat[..., :lat]
    w_uv = p["w_uv"].reshape(lat, H, dv)
    o = jnp.einsum("bhl,lhd->bhd", o_lat.astype(jnp.float32),
                   w_uv.astype(jnp.float32)).astype(q_eff.dtype)
    return o.reshape(B, H * dv) @ p["wo"]


def mla_decode_step(p: Dict[str, jax.Array], cfg: ModelConfig, x: jax.Array,
                    cache: Dict[str, jax.Array], cur_len: jax.Array,
                    *, attn_impl: str = "ref", plane_mesh=None,
                    step_mask: Optional[jax.Array] = None):
    """Absorbed-form MLA decode, select and attend FUSED in one trace (see
    the GQA stage split above): the latent cache behaves as a single KV head
    with key dim (kv_lora_rank + rope) and value = latent (kv_lora_rank).
    DSA metadata lives in latent space — beyond-paper extension (DESIGN §4).
    plane_mesh: see ``gqa_decode_step`` (latent pool block-sharded).
    step_mask: see ``gqa_decode_step`` — False rows leave the cache unchanged.
    """
    m = cfg.mla
    B, _ = x.shape
    H = cfg.num_heads
    dv, lat = m.v_head_dim, m.kv_lora_rank

    if plane_mesh is not None and cfg.dsa.enabled:
        if step_mask is not None:
            raise NotImplementedError(
                "fused context-parallel decode does not support step_mask "
                "(see gqa_decode_step)")
        q_eff, latent = _mla_project_decode(p, cfg, x, cur_len)
        o_lat, new_cache, sel = cp_mla_decode_attention(
            cfg, q_eff, latent, cache, cur_len, pm=plane_mesh)
        o_lat = o_lat[..., :lat]
        w_uv = p["w_uv"].reshape(lat, H, dv)
        o = jnp.einsum("bhl,lhd->bhd", o_lat.astype(jnp.float32),
                       w_uv.astype(jnp.float32)).astype(x.dtype)
        out = o.reshape(B, H * dv) @ p["wo"]
        return out, new_cache, sel

    q_eff, new_cache, idx, valid = mla_select_step(p, cfg, x, cache, cur_len,
                                                   step_mask=step_mask)
    out = mla_attend_step(p, cfg, q_eff, new_cache, cur_len, idx, valid,
                          attn_impl=attn_impl)
    return out, new_cache, idx
