"""Unified model assembly for every assigned architecture.

Public surface (all pure functions; params/state are pytrees):

    init_params(cfg, key, dtype)                 -> params
    forward_train(params, cfg, batch)            -> (loss, logits)
    prefill(params, cfg, inputs)                 -> (last_logits, DecodeState)
    prefill_layer(params, cfg, l, hidden, ...)   -> (hidden', layer_kv)   [layer-segmented]
    init_decode_state(cfg, batch, num_blocks)    -> DecodeState
    decode_step(params, cfg, token, state)       -> (logits, DecodeState)

Layer iteration is a Python loop (static unroll): it uniformly supports the
heterogeneous hybrids (Jamba attn/mamba interleave, MoE every other layer)
and gives layer-segmented prefill direct per-layer access.

DecodeState is a dict pytree:
    {"caches": [per-layer cache dict], "cur_len": (B,) int32, "extra": {...}}
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import mamba as mamba_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.common import (ModelConfig, dense_init, layer_norm,
                                 rms_norm, sinusoidal_positions, split_keys)


# ---------------------------------------------------------------------------
# Layer kinds
# ---------------------------------------------------------------------------

def layer_kind(cfg: ModelConfig, i: int) -> str:
    """'attn' | 'mamba' | 'rwkv' mixer for layer i."""
    if cfg.attention_type == "none":
        return "rwkv"
    if cfg.arch_type == "hybrid" and not cfg.is_attention_layer(i):
        return "mamba"
    return "attn"


def is_homogeneous(cfg: ModelConfig) -> bool:
    """True when every layer has identical structure — enables the
    scan-over-stacked-layers fast path (one compiled layer body instead of
    num_layers copies; essential for 60+-layer configs)."""
    kinds = {layer_kind(cfg, i) for i in range(cfg.num_layers)}
    moes = {cfg.is_moe_layer(i) for i in range(cfg.num_layers)}
    return len(kinds) == 1 and len(moes) == 1


def layers_stacked(params: Dict) -> bool:
    return isinstance(params["layers"], dict)


def get_layer(params: Dict, i) -> Dict:
    """Layer i's params — list mode or stacked mode (traced i allowed)."""
    layers = params["layers"]
    if isinstance(layers, list):
        return layers[i]
    from repro.models.common import take_layer
    return take_layer(layers, i)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, i: int, key: jax.Array, dtype,
                with_cross: bool = False) -> Dict:
    ks = split_keys(key, 4)
    kind = layer_kind(cfg, i)
    p: Dict[str, Any] = {}  # NOTE: kind is derived from cfg (layer_kind),
    if kind == "rwkv":      # never stored in params (strings break pytrees)
        p["ln1"] = {"w": jnp.ones((cfg.d_model,), jnp.float32),
                    "b": jnp.zeros((cfg.d_model,), jnp.float32)}
        p["ln2"] = {"w": jnp.ones((cfg.d_model,), jnp.float32),
                    "b": jnp.zeros((cfg.d_model,), jnp.float32)}
        p["rwkv"] = rwkv_mod.init_rwkv_params(cfg, ks[0], dtype)
        return p
    p["attn_norm"] = jnp.ones((cfg.d_model,), dtype)
    p["ffn_norm"] = jnp.ones((cfg.d_model,), dtype)
    if kind == "mamba":
        p["mamba"] = mamba_mod.init_mamba_params(cfg, ks[0], dtype)
    elif cfg.attention_type == "mla":
        p["attn"] = attn.init_mla_params(cfg, ks[0], dtype)
    else:
        p["attn"] = attn.init_gqa_params(cfg, ks[0], dtype)
    if with_cross:
        p["cross_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = attn.init_gqa_params(cfg, ks[3], dtype, cross=True)
    if cfg.is_moe_layer(i):
        p["moe"] = ffn_mod.init_moe_params(cfg, ks[1], dtype)
    else:
        p["ffn"] = ffn_mod.init_ffn_params(cfg, ks[1], dtype)
    return p


def _init_whisper_encoder(cfg: ModelConfig, key: jax.Array, dtype) -> Dict:
    ks = split_keys(key, cfg.encoder_layers + 1)
    layers = []
    for i in range(cfg.encoder_layers):
        sub = split_keys(ks[i], 2)
        layers.append({
            "attn_norm": jnp.ones((cfg.d_model,), dtype),
            "ffn_norm": jnp.ones((cfg.d_model,), dtype),
            "attn": attn.init_gqa_params(cfg, sub[0], dtype),
            "ffn": ffn_mod.init_ffn_params(cfg, sub[1], dtype),
        })
    return {"layers": layers,
            "final_norm": jnp.ones((cfg.d_model,), dtype)}


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16, stacked: Optional[bool] = None) -> Dict:
    """stacked=None -> auto (stack when every layer is identical).
    Stacked layers carry a leading num_layers axis and forward passes scan
    over them; list mode unrolls a Python loop (needed for heterogeneous
    hybrids like Jamba and for per-layer engine access)."""
    if stacked is None:
        stacked = is_homogeneous(cfg)
    ks = split_keys(key, cfg.num_layers + 4)
    layer_list = [
        _init_layer(cfg, i, ks[i + 1], dtype,
                    with_cross=cfg.is_encoder_decoder)
        for i in range(cfg.num_layers)
    ]
    from repro.models.common import stack_layers
    params: Dict[str, Any] = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype,
                            scale=0.02),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "layers": (stack_layers(layer_list) if stacked and is_homogeneous(cfg)
                   else layer_list),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[cfg.num_layers + 1],
                                       (cfg.d_model, cfg.vocab_size), dtype,
                                       scale=0.02)
    if cfg.is_encoder_decoder:
        params["encoder"] = _init_whisper_encoder(
            cfg, ks[cfg.num_layers + 2], dtype)
    return params


# ---------------------------------------------------------------------------
# Layer forward (full sequence): train / prefill
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, w, x):
    if isinstance(w, dict):          # rwkv / whisper layer-norm
        return layer_norm(x, w["w"], w["b"], cfg.norm_eps)
    return rms_norm(x, w, cfg.norm_eps)


def layer_forward(p: Dict, cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array, *, kind: str = "attn",
                  rec_state: Optional[Dict] = None,
                  enc_kv: Optional[Tuple] = None,
                  k_ctx=None, v_ctx=None, q_offset=0,
                  triangular: bool = False,
                  return_kv: bool = False,
                  moe_drop_free: bool = False):
    """One transformer layer over a full sequence.

    Returns (x_out, aux_loss, layer_kv_or_None, new_rec_state_or_None).
    layer_kv: for attn layers (k, v) each (B, S, Hkv, hd) — or (latent,) for
    MLA — used by prefill to populate the paged pool.

    moe_drop_free: serving prefill paths set this so MoE expert capacity
    (a TRAINING throughput knob) cannot drop tokens — capacity scales with
    the tokens in the forward, so a dropped token would make batched /
    chunked / layer-segmented prefill executions diverge from each other
    (the same convention as the decode step's drop-free MoE).
    """
    aux = jnp.zeros((), jnp.float32)
    kv_out = None
    new_rec = None

    if kind == "rwkv":
        h, new_rec = rwkv_mod.rwkv_time_mix(
            p["rwkv"], cfg, _norm(cfg, p["ln1"], x), rec_state)
        x = x + h
        h, new_rec = rwkv_mod.rwkv_channel_mix(
            p["rwkv"], _norm(cfg, p["ln2"], x), new_rec)
        return x + h, aux, None, new_rec

    h_in = _norm(cfg, p["attn_norm"], x)
    if kind == "mamba":
        if rec_state is not None:
            h, new_rec = mamba_mod.mamba_forward(p["mamba"], cfg, h_in,
                                                 rec_state, return_state=True)
        else:
            h = mamba_mod.mamba_forward(p["mamba"], cfg, h_in)
        x = x + h
    elif cfg.attention_type == "mla":
        if return_kv:
            h, latent = attn.mla_self_attention(p["attn"], cfg, h_in,
                                                positions, return_latent=True)
            kv_out = (latent,)
        else:
            h = attn.mla_self_attention(p["attn"], cfg, h_in, positions)
        x = x + h
    else:
        out = attn.gqa_self_attention(p["attn"], cfg, h_in, positions,
                                      k_ctx=k_ctx, v_ctx=v_ctx,
                                      q_offset=q_offset,
                                      triangular=triangular,
                                      return_kv=return_kv)
        if return_kv:
            h, k, v = out
            kv_out = (k, v)
        else:
            h = out
        x = x + h

    x, aux = _layer_epilogue(p, cfg, x, enc_kv, moe_drop_free)
    return x, aux, kv_out, new_rec


def _layer_epilogue(p: Dict, cfg: ModelConfig, x: jax.Array, enc_kv,
                    moe_drop_free: bool):
    """Post-mixer part of a full-sequence layer: cross-attention (whisper)
    + FFN/MoE.  One implementation shared by ``layer_forward`` and the
    sequence-sharded prefill path so their numerics can never drift.
    Returns (x_out, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if enc_kv is not None and "cross" in p:
        h = attn.cross_attention(p["cross"], cfg,
                                 _norm(cfg, p["cross_norm"], x), *enc_kv)
        x = x + h
    h_in = _norm(cfg, p["ffn_norm"], x)
    if "moe" in p:
        h, aux = ffn_mod.moe_apply(p["moe"], cfg, h_in,
                                   drop_free=moe_drop_free)
    else:
        h = ffn_mod.ffn_apply(p["ffn"], h_in)
    return x + h, aux


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_inputs(params: Dict, cfg: ModelConfig, inputs: Dict
                 ) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden (B,S,d), positions (B,S))."""
    tokens = inputs["tokens"]
    B = tokens.shape[0]
    h = params["embed"][tokens]
    if cfg.frontend == "vit_patch_stub":
        patches = inputs["patch_embeds"].astype(h.dtype)       # (B, P, d)
        h = jnp.concatenate([patches, h], axis=1)
    S = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return h, positions


def lm_head(params: Dict, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    h = _norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


def whisper_encode(params: Dict, cfg: ModelConfig, frames: jax.Array
                   ) -> jax.Array:
    """frames: (B, T_enc, d) stub embeddings (conv/mel frontend is stubbed
    per assignment). Bidirectional encoder."""
    B, T, d = frames.shape
    h = frames + sinusoidal_positions(T, d).astype(frames.dtype)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    enc = params["encoder"]
    for p in enc["layers"]:
        a = attn.gqa_self_attention(p["attn"], cfg,
                                    rms_norm(h, p["attn_norm"], cfg.norm_eps),
                                    positions, causal=False)
        h = h + a
        f = ffn_mod.ffn_apply(p["ffn"],
                              rms_norm(h, p["ffn_norm"], cfg.norm_eps))
        h = h + f
    return rms_norm(h, enc["final_norm"], cfg.norm_eps)


def project_encoder_kv(params: Dict, cfg: ModelConfig, enc_out: jax.Array):
    """List mode: [(k, v)] per layer.  Stacked mode: (k, v) with leading L."""
    if layers_stacked(params):
        return jax.vmap(lambda pc: attn.project_enc_kv(pc, cfg, enc_out))(
            params["layers"]["cross"])
    return [attn.project_enc_kv(p["cross"], cfg, enc_out)
            for p in params["layers"]]


# ---------------------------------------------------------------------------
# Train forward
# ---------------------------------------------------------------------------

def _fresh_rec_state(cfg: ModelConfig, kind: str, batch: int, dtype):
    if kind == "mamba":
        return mamba_mod.init_mamba_state(cfg, batch, dtype)
    if kind == "rwkv":
        return rwkv_mod.init_rwkv_state(cfg, batch, dtype)
    return None


def _stack_enc_kvs(enc_kvs):
    """[(k,v)] * L -> (k (L,B,S,H,hd), v (L,B,S,H,hd))."""
    return (jnp.stack([k for k, _ in enc_kvs], axis=0),
            jnp.stack([v for _, v in enc_kvs], axis=0))


def _layers_scan_train(params: Dict, cfg: ModelConfig, h: jax.Array,
                       positions: jax.Array, enc_kvs, *,
                       remat: bool, triangular: bool):
    """Homogeneous-layer fast path: ONE compiled layer body via lax.scan."""
    kind = layer_kind(cfg, 0)
    B = h.shape[0]

    def body(carry, xs):
        h_, aux_ = carry
        p = xs["p"]
        enc = (xs["enc_k"], xs["enc_v"]) if "enc_k" in xs else None
        rec = _fresh_rec_state(cfg, kind, B, h_.dtype)
        h2, a, _, _ = layer_forward(p, cfg, h_, positions, kind=kind,
                                    rec_state=rec, enc_kv=enc,
                                    triangular=triangular)
        return (h2, aux_ + a), None

    if remat:
        body = jax.checkpoint(body)
    xs: Dict[str, Any] = {"p": params["layers"]}
    if enc_kvs is not None:
        xs["enc_k"], xs["enc_v"] = _stack_enc_kvs(enc_kvs) \
            if isinstance(enc_kvs, list) else enc_kvs
    (h, aux_total), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                     xs)
    return h, aux_total


def forward_train(params: Dict, cfg: ModelConfig, batch: Dict,
                  *, triangular: bool = False,
                  remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """batch: {"tokens": (B,S), "labels": (B,S) [, "frames"/"patch_embeds"]}
    Returns (loss, logits)."""
    h, positions = embed_inputs(params, cfg, batch)
    B, S, _ = h.shape

    enc_kvs = None
    if cfg.is_encoder_decoder:
        enc_out = whisper_encode(params, cfg, batch["frames"])
        enc_kvs = project_encoder_kv(params, cfg, enc_out)

    if layers_stacked(params):
        h, aux_total = _layers_scan_train(params, cfg, h, positions, enc_kvs,
                                          remat=remat, triangular=triangular)
    else:
        aux_total = jnp.zeros((), jnp.float32)
        rec_states = _init_rec_states(cfg, B, h.dtype)
        for i in range(cfg.num_layers):
            p = get_layer(params, i)
            kind = layer_kind(cfg, i)
            def run(h_, rs, p=p, kind=kind, i=i):
                return layer_forward(p, cfg, h_, positions, kind=kind,
                                     rec_state=rs,
                                     enc_kv=enc_kvs[i] if enc_kvs else None,
                                     triangular=triangular)
            if remat:
                run = jax.checkpoint(run)
            h, aux, _, new_rec = run(h, rec_states[i])
            aux_total = aux_total + aux
            rec_states[i] = new_rec

    logits = lm_head(params, cfg, h)
    labels = batch["labels"]
    if cfg.frontend == "vit_patch_stub":                      # logits cover patches too
        logits_txt = logits[:, -labels.shape[1]:, :]
    else:
        logits_txt = logits
    loss = cross_entropy(logits_txt, labels)
    return loss + 0.01 * aux_total, logits_txt


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


def _init_rec_states(cfg: ModelConfig, batch: int, dtype) -> List:
    states = []
    for i in range(cfg.num_layers):
        kind = layer_kind(cfg, i)
        if kind == "mamba":
            states.append(mamba_mod.init_mamba_state(cfg, batch, dtype))
        elif kind == "rwkv":
            states.append(rwkv_mod.init_rwkv_state(cfg, batch, dtype))
        else:
            states.append(None)
    return states


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, num_blocks: int,
                      dtype=jnp.bfloat16, enc_kvs=None,
                      stacked: Optional[bool] = None) -> Dict:
    """stacked=None -> auto (stacked when layers are homogeneous).
    Stacked caches are ONE pytree with leading num_layers axis (scan path);
    list caches are per-layer (engine / heterogeneous path)."""
    if stacked is None:
        stacked = is_homogeneous(cfg)
    if stacked and is_homogeneous(cfg):
        kind = layer_kind(cfg, 0)
        if kind == "attn":
            one = attn.init_layer_kv_pool(cfg, batch, num_blocks, dtype)
        elif kind == "mamba":
            one = mamba_mod.init_mamba_state(cfg, batch, dtype)
        else:
            one = rwkv_mod.init_rwkv_state(cfg, batch, dtype)
        caches: Any = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape), one)
    else:
        caches = []
        for i in range(cfg.num_layers):
            kind = layer_kind(cfg, i)
            if kind == "attn":
                caches.append(attn.init_layer_kv_pool(cfg, batch, num_blocks,
                                                      dtype))
            elif kind == "mamba":
                caches.append(mamba_mod.init_mamba_state(cfg, batch, dtype))
            else:
                caches.append(rwkv_mod.init_rwkv_state(cfg, batch, dtype))
    state = {"caches": caches,
             "cur_len": jnp.zeros((batch,), jnp.int32),
             "extra": {}}
    if enc_kvs is not None:
        state["extra"]["enc_kvs"] = enc_kvs
    return state


# ---------------------------------------------------------------------------
# Prefill (plain, full prompt) — fills the paged pools
# ---------------------------------------------------------------------------

def _kv_to_pool(cfg: ModelConfig, k: jax.Array, num_blocks: int, pool_dtype):
    """(B, S, Hkv, D) -> (B, Hkv, NB, bs, D), zero-padded."""
    from repro.core import dsa as dsa_mod
    B, S, Hkv, D = k.shape
    bs = cfg.dsa.block_size
    pad = num_blocks * bs - S
    k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pool = jnp.transpose(k.reshape(B, num_blocks, bs, Hkv, D), (0, 3, 1, 2, 4))
    valid = (jnp.arange(num_blocks * bs) < S).reshape(num_blocks, bs)
    valid = jnp.broadcast_to(valid, (B, Hkv, num_blocks, bs))
    meta = dsa_mod.build_block_metadata(pool, cfg.dsa.metadata, valid)
    return pool.astype(pool_dtype), meta


def _prefill_layer_caches(cfg: ModelConfig, kv_out, new_rec, num_blocks: int,
                          cache_dtype):
    if kv_out is None:
        return new_rec
    if cfg.attention_type == "mla":
        (latent,) = kv_out
        kpool, meta = _kv_to_pool(cfg, latent[:, :, None, :], num_blocks,
                                  cache_dtype)
        return {"k": kpool, "meta": meta}
    k, v = kv_out
    kpool, meta = _kv_to_pool(cfg, k, num_blocks, cache_dtype)
    vpool, _ = _kv_to_pool(cfg, v, num_blocks, cache_dtype)
    return {"k": kpool, "v": vpool, "meta": meta}


def _layers_scan_prefill(params: Dict, cfg: ModelConfig, h: jax.Array,
                         positions: jax.Array, enc_kvs, num_blocks: int,
                         cache_dtype, triangular: bool):
    kind = layer_kind(cfg, 0)
    B = h.shape[0]

    def body(h_, xs):
        p = xs["p"]
        enc = (xs["enc_k"], xs["enc_v"]) if "enc_k" in xs else None
        rec = _fresh_rec_state(cfg, kind, B, h_.dtype)
        h2, _, kv_out, new_rec = layer_forward(
            p, cfg, h_, positions, kind=kind, rec_state=rec, enc_kv=enc,
            triangular=triangular, return_kv=True)
        return h2, _prefill_layer_caches(cfg, kv_out, new_rec, num_blocks,
                                         cache_dtype)

    xs: Dict[str, Any] = {"p": params["layers"]}
    if enc_kvs is not None:
        xs["enc_k"], xs["enc_v"] = _stack_enc_kvs(enc_kvs) \
            if isinstance(enc_kvs, list) else enc_kvs
    h, caches = jax.lax.scan(body, h, xs)
    return h, caches


def prefill(params: Dict, cfg: ModelConfig, inputs: Dict, num_blocks: int,
            *, cache_dtype=jnp.bfloat16, triangular: bool = False
            ) -> Tuple[jax.Array, Dict]:
    """Plain prefill: full forward, return last-token logits + DecodeState.

    Stacked params -> scan path -> STACKED caches; list params -> per-layer
    cache list.  decode_step accepts both."""
    h, positions = embed_inputs(params, cfg, inputs)
    B, S, _ = h.shape
    enc_kvs = None
    if cfg.is_encoder_decoder:
        enc_out = whisper_encode(params, cfg, inputs["frames"])
        enc_kvs = project_encoder_kv(params, cfg, enc_out)

    if layers_stacked(params):
        h, caches = _layers_scan_prefill(params, cfg, h, positions, enc_kvs,
                                         num_blocks, cache_dtype, triangular)
    else:
        rec_states = _init_rec_states(cfg, B, h.dtype)
        caches = []
        for i in range(cfg.num_layers):
            p = get_layer(params, i)
            h, _, kv_out, new_rec = layer_forward(
                p, cfg, h, positions, kind=layer_kind(cfg, i),
                rec_state=rec_states[i],
                enc_kv=enc_kvs[i] if enc_kvs else None,
                triangular=triangular, return_kv=True)
            caches.append(_prefill_layer_caches(cfg, kv_out, new_rec,
                                                num_blocks, cache_dtype))

    logits = lm_head(params, cfg, h[:, -1:, :])[:, 0]
    state = {"caches": caches,
             "cur_len": jnp.full((B,), S, jnp.int32),
             "extra": ({"enc_kvs": enc_kvs} if enc_kvs else {})}
    return logits, state


# ---------------------------------------------------------------------------
# Layer-segmented prefill (SparseServe §3.4)
# ---------------------------------------------------------------------------

def prefill_embed(params: Dict, cfg: ModelConfig, inputs: Dict):
    """Segment 0 of layer-segmented prefill: embedding (+ encoder for A/V)."""
    h, positions = embed_inputs(params, cfg, inputs)
    enc_kvs = None
    if cfg.is_encoder_decoder:
        enc_out = whisper_encode(params, cfg, inputs["frames"])
        enc_kvs = project_encoder_kv(params, cfg, enc_out)
    return h, positions, enc_kvs


def index_enc_kvs(enc_kvs, i: int):
    """Layer i's (k, v) cross-attn cache — list or stacked form."""
    if enc_kvs is None:
        return None
    if isinstance(enc_kvs, list):
        return enc_kvs[i]
    return (enc_kvs[0][i], enc_kvs[1][i])


def prefill_layer(params: Dict, cfg: ModelConfig, layer_idx: int,
                  h: jax.Array, positions: jax.Array, *,
                  rec_state=None, enc_kv=None, triangular: bool = False,
                  moe_drop_free: bool = False):
    """Run ONE layer of prefill over the whole prompt (layer-segmented
    prefill).  The caller saves the returned per-layer KV to DRAM and evicts
    it before calling layer l+1 — bounding HBM to one layer of KV."""
    p = get_layer(params, layer_idx)
    h, _, kv_out, new_rec = layer_forward(p, cfg, h, positions,
                                          kind=layer_kind(cfg, layer_idx),
                                          rec_state=rec_state, enc_kv=enc_kv,
                                          triangular=triangular,
                                          return_kv=True,
                                          moe_drop_free=moe_drop_free)
    return h, kv_out, new_rec


def prefill_finalize(params: Dict, cfg: ModelConfig, h: jax.Array):
    """Last segment: final norm + head on the last position."""
    return lm_head(params, cfg, h[:, -1:, :])[:, 0]


# ---------------------------------------------------------------------------
# Batched layer-segmented prefill (the PrefillPlane's stage functions)
#
# The prefill plane (``repro.core.prefill_plane``) batches the SAME-layer
# segments of many requests into one jitted launch over right-padded rows.
# These functions are the masked layer bodies it jits: ``token_mask`` marks
# each row's real tokens (right padding), ``step_mask`` parks rows whose
# request is not scheduled (their hidden / recurrent state comes back
# byte-for-byte unchanged, like the decode plane's step_mask).  Exactness
# under padding:
#
# * attention — causal masking alone protects real tokens (padding sits
#   strictly AFTER every real position, so no real query ever attends to a
#   padded key); masked lanes PRESERVE their incoming residual, so right
#   padding stays at its admitted zeros and real tokens of later chunks
#   that fall inside a bucketed window keep their layer-input values;
# * recurrent (mamba/rwkv) — the masked forwards carry the recurrent state
#   THROUGH padded steps unchanged and gather shift/conv windows from each
#   row's last valid position, so the carried state equals an unpadded
#   run's (see ``mamba_forward(token_mask=...)`` / ``rwkv_time_mix``);
# * MoE — runs drop-free (expert capacity must not couple batched rows).
# ---------------------------------------------------------------------------

def prefill_attn_layer_batched(p: Dict, cfg: ModelConfig, h: jax.Array,
                               positions: jax.Array, token_mask: jax.Array,
                               step_mask: jax.Array, *,
                               k_ctx=None, v_ctx=None, q_offset=0,
                               enc_kv=None, plane_mesh=None):
    """One ATTENTION layer over a padded batch of same-layer segments.

    h: (B, T, d) — the rows' residual stream over this segment's token
    window; positions: (B, T) absolute positions; k_ctx/v_ctx: earlier
    chunks of the SAME layer (chunked layer segments; None for chunk 0);
    q_offset: the window's absolute start (scalar; traced, so distinct
    chunk starts share one compile per shape).

    plane_mesh: sequence-shard the window across the mesh's model axis
    (``_prefill_attn_layer_batched_cp``); MLA layers run replicated (no
    latent-context path to shard — same restriction as chunked segments).

    Returns (h_out, kv_out): h_out masked (masked lanes preserve the
    incoming residual, parked rows return unchanged); kv_out = (k, v) each
    (B, T, Hkv, hd) — or (latent,) (B, T, lat) for MLA — valid where
    token_mask is set.
    """
    if plane_mesh is not None and cfg.attention_type != "mla":
        return _prefill_attn_layer_batched_cp(
            p, cfg, h, positions, token_mask, step_mask, k_ctx=k_ctx,
            v_ctx=v_ctx, q_offset=q_offset, enc_kv=enc_kv, pm=plane_mesh)
    x, _, kv_out, _ = layer_forward(p, cfg, h, positions, kind="attn",
                                    enc_kv=enc_kv, k_ctx=k_ctx, v_ctx=v_ctx,
                                    q_offset=q_offset, return_kv=True,
                                    moe_drop_free=True)
    # masked lanes PRESERVE the incoming residual: right padding stays at
    # its admitted zeros, real tokens of LATER chunks inside the bucketed
    # window keep their layer-input values for their own chunk's launch,
    # and parked rows (step_mask False => token_mask all-False) come back
    # byte-for-byte unchanged
    x = jnp.where(token_mask[..., None] & step_mask[:, None, None], x, h)
    return x, kv_out


def _prefill_attn_layer_batched_cp(p: Dict, cfg: ModelConfig, h: jax.Array,
                                   positions: jax.Array,
                                   token_mask: jax.Array,
                                   step_mask: jax.Array, *,
                                   k_ctx, v_ctx, q_offset, enc_kv, pm):
    """Sequence-sharded GQA prefill layer (context-parallel prefill).

    Only the quadratic part is sharded: the window's QUERIES split across
    ``pm.model_axis`` and each shard runs blocked attention of its query
    slice against the full window K/V; the out-spec reassembles the
    attention outputs.  Projections and the layer epilogue (residual, Wo,
    cross-attn, FFN/MoE) run replicated at the SAME shapes as the
    single-device path, and every value handed onward is pinned back to
    replicated sharding (``pm.replicate``) — both deliberately, for
    exactness: per-shard matmul row counts and leaked out-spec shardings
    each perturb numerics (a leaked sequence sharding would GSPMD-partition
    a later mamba scan), which breaks the token-identical oracle bar.
    Windows that do not divide the axis are padded with causally-invisible
    tail tokens (key index > every real query position) and trimmed after.
    """
    from jax.sharding import PartitionSpec as P
    m = pm.model_axis
    n = pm.model_size
    B, T, _ = h.shape
    pad = (-T) % n
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0))) if pad else h
    pos_p = (jnp.pad(positions, ((0, 0), (0, pad)), mode="edge")
             if pad else positions)
    T_loc = (T + pad) // n
    scale = 1.0 / (cfg.head_dim ** 0.5)

    # replicated projections — bitwise-identical to the single-device path
    h_in = _norm(cfg, p["attn_norm"], hp)
    q, k, v = attn.gqa_project_qkv(p["attn"], cfg, h_in, pos_p)
    k_all, v_all = k, v
    if k_ctx is not None:
        k_all = jnp.concatenate([k_ctx, k_all], axis=1)
        v_all = jnp.concatenate([v_ctx, v_all], axis=1)

    def body(q_l, k_, v_, qo_):
        qo_loc = qo_ + jax.lax.axis_index(m) * T_loc
        return attn.flash_attention_jnp(q_l, k_, v_, scale=scale,
                                        causal=True, q_offset=qo_loc)

    seq4 = P(None, m, None, None)
    rep4 = P(None, None, None, None)
    fn = jax.shard_map(body, check_vma=False, mesh=pm.mesh,
                       in_specs=(seq4, rep4, rep4, P()), out_specs=seq4)
    o = pm.replicate(fn(q, k_all, v_all, jnp.asarray(q_offset, jnp.int32)))
    x = hp + o.reshape(B, T + pad, -1) @ p["attn"]["wo"]
    if pad:
        x, k, v = x[:, :T], k[:, :T], v[:, :T]
    # replicated epilogue on the full window — the SAME implementation
    # layer_forward runs, so the paths cannot drift (see docstring)
    x, _ = _layer_epilogue(p, cfg, x, enc_kv, moe_drop_free=True)
    # same lane-preserving mask as the replicated path
    x = jnp.where(token_mask[..., None] & step_mask[:, None, None], x, h)
    return pm.replicate((x, (k, v)))


def prefill_recurrent_layer_batched(p: Dict, cfg: ModelConfig, kind: str,
                                    h: jax.Array, token_mask: jax.Array,
                                    step_mask: jax.Array, rec_state):
    """One mamba/rwkv layer over a padded batch of same-layer segments.
    Returns (h_out, new_rec_state), both masked: parked rows' hidden AND
    recurrent state come back unchanged."""
    if kind == "rwkv":
        x = h
        out, st = rwkv_mod.rwkv_time_mix(p["rwkv"], cfg,
                                         _norm(cfg, p["ln1"], x), rec_state,
                                         token_mask=token_mask)
        x = x + out
        out, st = rwkv_mod.rwkv_channel_mix(p["rwkv"],
                                            _norm(cfg, p["ln2"], x), st,
                                            token_mask=token_mask)
        x = x + out
    else:
        h_in = _norm(cfg, p["attn_norm"], h)
        out, st = mamba_mod.mamba_forward(p["mamba"], cfg, h_in, rec_state,
                                          return_state=True,
                                          token_mask=token_mask)
        x = h + out
        h_in = _norm(cfg, p["ffn_norm"], x)
        if "moe" in p:
            f, _ = ffn_mod.moe_apply(p["moe"], cfg, h_in, drop_free=True)
        else:
            f = ffn_mod.ffn_apply(p["ffn"], h_in)
        x = x + f
    # same lane-preserving mask as the attention stage (see above)
    x = jnp.where(token_mask[..., None] & step_mask[:, None, None], x, h)
    st = _mask_state(st, rec_state, step_mask)
    return x, st


def prefill_logits_batched(params: Dict, cfg: ModelConfig, h: jax.Array,
                           tok_len: jax.Array) -> jax.Array:
    """Finalize stage of the prefill plane: gather each row's LAST REAL
    hidden state (h: (B, S_cap, d), tok_len: (B,)) and run the lm head.
    Returns (B, V); only finishing rows' logits are meaningful."""
    idx = jnp.maximum(tok_len - 1, 0)
    h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    return lm_head(params, cfg, h_last)[:, 0]


# ---------------------------------------------------------------------------
# Batched multi-request decode: padded-batch stack / unstack
# ---------------------------------------------------------------------------

def is_pool_cache(c: Any) -> bool:
    """True for an attn-layer paged-pool cache ({k[,v],meta})."""
    return isinstance(c, dict) and "k" in c and "meta" in c


def stack_decode_states(states: List[Dict]) -> Tuple[Dict, List[Tuple[int, List[Optional[int]]]]]:
    """Stack per-request DecodeStates into ONE padded batch state.

    The serving engine holds one DecodeState per request, with per-layer KV
    pools whose block counts differ (prompt + generation budgets differ).
    Batched decode pads every attn-layer pool along the block axis to the
    batch maximum and concatenates along batch; recurrent-layer states (and
    ``extra`` pytrees such as whisper enc_kvs) concatenate directly, so
    requests whose extra shapes differ must be grouped by the caller.

    Requires list-mode caches (the engine's representation).  Returns
    (batched_state, layout) where layout records each input's (batch_size,
    per-layer num_blocks) for ``unstack_decode_states``.
    """
    if not states:
        raise ValueError("stack_decode_states: empty batch")
    L = len(states[0]["caches"])
    layout: List[Tuple[int, List[Optional[int]]]] = []
    for s in states:
        if not isinstance(s["caches"], list):
            raise ValueError("stack_decode_states requires list-mode caches "
                             "(per-layer), not stacked scan caches")
        nbs = [s["caches"][l]["k"].shape[2] if is_pool_cache(s["caches"][l])
               else None for l in range(L)]
        layout.append((int(s["cur_len"].shape[0]), nbs))

    caches: List[Any] = []
    for l in range(L):
        parts = [s["caches"][l] for s in states]
        if is_pool_cache(parts[0]):
            nb_max = max(p["k"].shape[2] for p in parts)
            parts = [attn.pad_pool_cache(p, nb_max) for p in parts]
            caches.append({key: jnp.concatenate([p[key] for p in parts],
                                                axis=0)
                           for key in parts[0]})
        else:
            caches.append(jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *parts))
    batched = {
        "caches": caches,
        "cur_len": jnp.concatenate([s["cur_len"] for s in states], axis=0),
        "extra": (jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                               *[s["extra"] for s in states])
                  if states[0]["extra"] else {}),
    }
    return batched, layout


def unstack_decode_states(state: Dict,
                          layout: List[Tuple[int, List[Optional[int]]]]
                          ) -> List[Dict]:
    """Split a batched DecodeState back into per-request states, trimming
    each attn-layer pool to the request's own block count."""
    out: List[Dict] = []
    row = 0
    for B, nbs in layout:
        sl = slice(row, row + B)
        caches: List[Any] = []
        for l, c in enumerate(state["caches"]):
            if is_pool_cache(c):
                caches.append(attn.slice_pool_cache(
                    {key: arr[sl] for key, arr in c.items()}, nbs[l]))
            else:
                caches.append(jax.tree.map(lambda x: x[sl], c))
        out.append({
            "caches": caches,
            "cur_len": state["cur_len"][sl],
            "extra": (jax.tree.map(lambda x: x[sl], state["extra"])
                      if state["extra"] else {}),
        })
        row += B
    return out


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

def _mask_state(new, old, step_mask: jax.Array):
    """Per-leaf select: keep `old` wherever step_mask is False (row axis 0)."""
    def sel(n, o):
        m = step_mask.reshape((step_mask.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new, old)


def _decode_epilogue(p: Dict, cfg: ModelConfig, x: jax.Array, enc_kv):
    """Post-mixer part of a decode layer: cross-attention (whisper) + FFN.
    Shared by the fused (``_decode_layer``) and staged
    (``decode_attend_layer``) paths so their numerics agree."""
    if enc_kv is not None and "cross" in p:
        h = attn.cross_decode_step(p["cross"], cfg,
                                   _norm(cfg, p["cross_norm"], x), *enc_kv)
        x = x + h
    h_in = _norm(cfg, p["ffn_norm"], x)
    if "moe" in p:
        # drop_free: expert capacity must not couple the requests of a
        # batched decode step (keeps batched == per-request decode)
        h, _ = ffn_mod.moe_apply(p["moe"], cfg, h_in[:, None, :],
                                 drop_free=True)
        h = h[:, 0]
    else:
        h = ffn_mod.ffn_apply(p["ffn"], h_in)
    return x + h


def _decode_layer(p: Dict, cfg: ModelConfig, kind: str, x: jax.Array,
                  cache, cur_len: jax.Array, enc_kv, attn_impl: str,
                  step_mask: Optional[jax.Array] = None,
                  plane_mesh=None):
    """One decode layer.  Returns (x, new_cache, sel_or_None).

    step_mask (B,) bool: rows where False must leave `cache` unchanged —
    paged pools use masked scatter at the source (``attn._append_masked``),
    recurrent states are reverted leaf-wise.
    plane_mesh: context-parallel mesh for the attention mixer (the FUSED
    shard_map path; the staged plane shards via decode_select/attend_layer
    instead); recurrent mixers always run replicated."""
    sel = None
    if kind == "rwkv":
        old = cache
        h, cache = rwkv_mod.rwkv_time_mix_step(
            p["rwkv"], cfg, _norm(cfg, p["ln1"], x), cache)
        x = x + h
        h, cache = rwkv_mod.rwkv_channel_mix_step(
            p["rwkv"], _norm(cfg, p["ln2"], x), cache)
        if step_mask is not None:
            cache = _mask_state(cache, old, step_mask)
        return x + h, cache, sel
    h_in = _norm(cfg, p["attn_norm"], x)
    if kind == "mamba":
        old = cache
        h, cache = mamba_mod.mamba_decode_step(p["mamba"], cfg, h_in, cache)
        if step_mask is not None:
            cache = _mask_state(cache, old, step_mask)
    elif cfg.attention_type == "mla":
        h, cache, sel = attn.mla_decode_step(p["attn"], cfg, h_in, cache,
                                             cur_len, attn_impl=attn_impl,
                                             plane_mesh=plane_mesh,
                                             step_mask=step_mask)
    else:
        h, cache, sel = attn.gqa_decode_step(p["attn"], cfg, h_in, cache,
                                             cur_len, attn_impl=attn_impl,
                                             plane_mesh=plane_mesh,
                                             step_mask=step_mask)
    x = x + h
    return _decode_epilogue(p, cfg, x, enc_kv), cache, sel


# ---------------------------------------------------------------------------
# Staged per-layer decode (select -> [host restore] -> attend)
#
# The staged decode plane (``repro.core.device_pool.step_staged``) runs ONE
# layer at a time so the serving engine can stage HBM-miss restores between
# a layer's DSA selection and its attention: select emits the selections
# (and appends the layer's new KV), the host lands the fused FlashH2D
# payloads in the device pool, attend then reads the restored blocks — which
# is what makes block-granular device eviction oracle-exact.  All functions
# here take the LAYER's params (``get_layer``), not the full model, so one
# jit trace serves every structurally identical layer.
# ---------------------------------------------------------------------------

def decode_embed(params: Dict, cfg: ModelConfig, tokens: jax.Array
                 ) -> jax.Array:
    """Stage 0: token embedding.  tokens (B,) -> x (B, d)."""
    return params["embed"][tokens]


def decode_select_layer(p: Dict, cfg: ModelConfig, x: jax.Array, cache,
                        cur_len: jax.Array,
                        step_mask: Optional[jax.Array] = None,
                        plane_mesh=None):
    """Select stage of one ATTENTION layer: pre-norm, project, append the
    new token's KV to the paged pool, update DSA metadata, score + top-k.
    Returns (q, new_cache, idx, valid) — idx/valid None when DSA is off.
    plane_mesh: shard the pool-touching core across the mesh
    (``attention.gqa/mla_select_step_cp``); idx/valid stay GLOBAL ids."""
    h_in = _norm(cfg, p["attn_norm"], x)
    if plane_mesh is not None:
        if cfg.attention_type == "mla":
            return attn.mla_select_step_cp(p["attn"], cfg, h_in, cache,
                                           cur_len, plane_mesh,
                                           step_mask=step_mask)
        return attn.gqa_select_step_cp(p["attn"], cfg, h_in, cache, cur_len,
                                       plane_mesh, step_mask=step_mask)
    if cfg.attention_type == "mla":
        return attn.mla_select_step(p["attn"], cfg, h_in, cache, cur_len,
                                    step_mask=step_mask)
    return attn.gqa_select_step(p["attn"], cfg, h_in, cache, cur_len,
                                step_mask=step_mask)


def decode_attend_layer(p: Dict, cfg: ModelConfig, x: jax.Array,
                        q: jax.Array, cache, cur_len: jax.Array,
                        idx, valid, enc_kv=None,
                        attn_impl: str = "ref", plane_mesh=None) -> jax.Array:
    """Compute stage of one ATTENTION layer: block-sparse attention over the
    (possibly restored) pool + residual + cross-attn + FFN.  Reads ``cache``
    but never writes it — the host may have scattered restore payloads into
    it after the select stage.  plane_mesh: run the attention core sharded
    (``attention.gqa/mla_attend_step_cp``); epilogue stays replicated."""
    if plane_mesh is not None:
        if cfg.attention_type == "mla":
            h = attn.mla_attend_step_cp(p["attn"], cfg, q, cache, cur_len,
                                        idx, valid, plane_mesh)
        else:
            h = attn.gqa_attend_step_cp(p["attn"], cfg, q, cache, cur_len,
                                        idx, valid, plane_mesh)
    elif cfg.attention_type == "mla":
        h = attn.mla_attend_step(p["attn"], cfg, q, cache, cur_len, idx,
                                 valid, attn_impl=attn_impl)
    else:
        h = attn.gqa_attend_step(p["attn"], cfg, q, cache, cur_len, idx,
                                 valid, attn_impl=attn_impl)
    return _decode_epilogue(p, cfg, x + h, enc_kv)


def decode_recurrent_layer(p: Dict, cfg: ModelConfig, kind: str,
                           x: jax.Array, cache,
                           step_mask: Optional[jax.Array] = None):
    """One mamba/rwkv layer as a single stage (no selection, no restore —
    recurrent layers hold no paged KV).  Returns (x, new_cache)."""
    dummy_len = jnp.zeros((x.shape[0],), jnp.int32)   # unused by recurrents
    x, cache, _ = _decode_layer(p, cfg, kind, x, cache, dummy_len,
                                None, "ref", step_mask=step_mask)
    return x, cache


def decode_logits(params: Dict, cfg: ModelConfig, x: jax.Array,
                  cur_len: jax.Array,
                  step_mask: Optional[jax.Array] = None):
    """Final stage: lm head + cur_len advance (masked rows stay parked).
    Returns (logits (B, V), new_cur_len (B,))."""
    logits = lm_head(params, cfg, x[:, None, :])[:, 0]
    new_len = (cur_len + 1 if step_mask is None
               else cur_len + step_mask.astype(jnp.int32))
    return logits, new_len


def _decode_scan(params: Dict, cfg: ModelConfig, x: jax.Array, state: Dict,
                 attn_impl: str, plane_mesh=None):
    """Scan path over stacked layers + stacked caches."""
    kind = layer_kind(cfg, 0)
    cur_len = state["cur_len"]
    enc_kvs = state["extra"].get("enc_kvs")

    def body(x_, xs):
        enc = (xs["enc_k"], xs["enc_v"]) if "enc_k" in xs else None
        x2, new_cache, sel = _decode_layer(xs["p"], cfg, kind, x_,
                                           xs["cache"], cur_len, enc,
                                           attn_impl,
                                           plane_mesh=plane_mesh)
        ys = {"cache": new_cache}
        if sel is not None:
            ys["sel"] = sel
        return x2, ys

    xs: Dict[str, Any] = {"p": params["layers"], "cache": state["caches"]}
    if enc_kvs is not None:
        xs["enc_k"], xs["enc_v"] = enc_kvs
    x, ys = jax.lax.scan(body, x, xs)
    sel_stacked = ys.get("sel")
    return x, ys["cache"], sel_stacked


def decode_step(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                state: Dict, *, attn_impl: str = "ref",
                return_info: bool = False,
                step_mask: Optional[jax.Array] = None,
                plane_mesh=None):
    """tokens: (B,) int32 — one new token per request.

    With return_info=True also returns {"selected": {layer: (B,Hkv,K)}} —
    the DSA block selections the serving engine feeds to the LRU cache and
    the working-set estimator.  Stacked caches take the scan fast path.

    step_mask: optional (B,) bool.  Rows where False are "parked": their
    caches (paged pools, metadata, recurrent states) and cur_len come back
    byte-for-byte unchanged, while the forward still runs at the full padded
    batch shape.  This is what lets the persistent device plane
    (``repro.core.device_pool``) jit ONE bucketed batch shape and step an
    arbitrary subset of resident requests per iteration.  Only supported
    with list-mode caches (the serving engine's representation).

    plane_mesh: ``launch.plane_mesh.PlaneMesh`` — fused context-parallel
    decode over block-sharded pools (what ``launch/dryrun.py`` lowers;
    formerly the ``attention.CP_AXES`` module global)."""
    B = tokens.shape[0]
    cur_len = state["cur_len"]
    x = params["embed"][tokens]                              # (B, d)
    enc_kvs = state["extra"].get("enc_kvs")

    info: Dict[str, Any] = {"selected": {}}
    if isinstance(state["caches"], dict):                    # stacked/scan
        if step_mask is not None:
            raise ValueError("step_mask requires list-mode caches")
        x, new_caches, sel_stacked = _decode_scan(params, cfg, x, state,
                                                  attn_impl,
                                                  plane_mesh=plane_mesh)
        if sel_stacked is not None and return_info:
            for i in range(cfg.num_layers):
                info["selected"][i] = sel_stacked[i]
    else:
        new_caches = []
        for i in range(cfg.num_layers):
            p = get_layer(params, i)
            kind = layer_kind(cfg, i)
            x, cache, sel = _decode_layer(
                p, cfg, kind, x, state["caches"][i], cur_len,
                index_enc_kvs(enc_kvs, i), attn_impl, step_mask=step_mask,
                plane_mesh=plane_mesh)
            if sel is not None:
                info["selected"][i] = sel
            new_caches.append(cache)

    logits = lm_head(params, cfg, x[:, None, :])[:, 0]
    new_len = (cur_len + 1 if step_mask is None
               else cur_len + step_mask.astype(jnp.int32))
    new_state = {"caches": new_caches, "cur_len": new_len,
                 "extra": state["extra"]}
    if return_info:
        return logits, new_state, info
    return logits, new_state
