"""Qwen2 and Qwen2.5 (``model_type`` ``qwen2``): RMSNorm, q/k/v with
bias, rotary embedding on the two halves of each head, grouped-query
attention, SwiGLU, tied or untied head.  Every layer is of one kind.

The architecture module of ``bench/benchkit/spec.py``'s docstring: sizes,
the program's ``ModelConfig``, the weights, the reference layer and head,
and the operation counts.
"""
from __future__ import annotations

from typing import Any, Dict


def read_shapes(s, cfg: Dict[str, Any]) -> None:
    s.heads = int(cfg["num_attention_heads"])
    s.kv_heads = int(cfg["num_key_value_heads"])
    s.head_dim = int(cfg.get("head_dim", s.d // s.heads))
    s.ff = int(cfg["intermediate_size"])
    s.rope_theta = float(cfg["rope_theta"])
    s.qkv_bias = bool(cfg["qkv_bias"])


def model_config(s, cfg: Dict[str, Any], name: str, dsa):
    from repro.models.common import ModelConfig
    return ModelConfig(
        name=name, arch_type="dense", num_layers=s.layers, d_model=s.d,
        num_heads=s.heads, num_kv_heads=s.kv_heads, d_ff=s.ff,
        vocab_size=s.vocab, head_dim=s.head_dim, qkv_bias=s.qkv_bias,
        rope_theta=s.rope_theta, norm_eps=s.eps, tie_embeddings=s.tied,
        dsa=dsa, source=cfg["source"])


def weights(s, key, dt):
    """Layers stacked on a leading axis.  Linear weights are N(0,
    1/fan_in), the embedding N(0, 0.02^2); norm weights are 1 + N(0,
    0.1^2) and q/k/v biases N(0, 0.1^2), so the norm and bias paths carry
    numbers that a dropped weight would change."""
    import jax
    import jax.numpy as jnp
    L, d, f, V = s.layers, s.d, s.ff, s.vocab
    qd, kd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dt)

    attn = {"wq": normal((L, d, qd), d ** -0.5),
            "wk": normal((L, d, kd), d ** -0.5),
            "wv": normal((L, d, kd), d ** -0.5),
            "wo": normal((L, qd, d), qd ** -0.5)}
    if s.qkv_bias:
        attn.update(bq=normal((L, qd), 0.1), bk=normal((L, kd), 0.1),
                    bv=normal((L, kd), 0.1))
    p = {"embed": normal((V, d), 0.02),
         "final_norm": 1.0 + normal((d,), 0.1),
         "layers": {
             "attn_norm": 1.0 + normal((L, d), 0.1),
             "ffn_norm": 1.0 + normal((L, d), 0.1),
             "attn": attn,
             "ffn": {"w_gate": normal((L, d, f), d ** -0.5),
                     "w_up": normal((L, d, f), d ** -0.5),
                     "w_down": normal((L, f, d), f ** -0.5)}}}
    if not s.tied:
        p["lm_head"] = normal((d, V), 0.02)
    return p


def embed(params, ids):
    return params["embed"][ids]


def layer_params(params, layer: int):
    import jax
    return jax.tree.map(lambda x: x[layer], params["layers"])


def head_params(params):
    return {k: v for k, v in params.items() if k != "layers"}


def layer_kind(s, layer: int) -> str:
    return "decoder"


def reference_layer(s, kind: str, ops, S: int, n: int):
    import jax
    import jax.numpy as jnp
    from benchkit import reference as R
    dt, mm = ops.dt, ops.mm
    H, Hk, D = s.heads, s.kv_heads, s.head_dim
    scale = 1.0 / (D ** 0.5)
    T = S + n

    def layer(p, h):
        pos = jnp.arange(T)
        a = p["attn"]
        x = R.rms(h, p["attn_norm"], s.eps)
        q, k, v = mm(x, a["wq"]), mm(x, a["wk"]), mm(x, a["wv"])
        if "bq" in a:
            q = q + a["bq"].astype(dt)
            k = k + a["bk"].astype(dt)
            v = v + a["bv"].astype(dt)
        q = R.rope(q.reshape(T, H, D), pos, s.rope_theta)
        k = R.rope(k.reshape(T, Hk, D), pos, s.rope_theta)
        v = v.reshape(T, Hk, D)
        o = R.prompt_attention(q[:S], k[:S], v[:S], scale, ops)
        if n:
            o = jnp.concatenate(
                [o, R.dsa_attention(q[S:], k, v, S, scale, s, ops)], 0)
        h = h + mm(o, a["wo"])
        f = p["ffn"]
        x = R.rms(h, p["ffn_norm"], s.eps)
        g = mm(x, f["w_gate"])
        u = mm(x, f["w_up"])
        h = h + mm((jax.nn.silu(g.astype(jnp.float32))
                    * u.astype(jnp.float32)).astype(dt), f["w_down"])
        return h, {"k": k, "v": v}

    return layer


def reference_head(s, ops):
    import jax.numpy as jnp
    from benchkit import reference as R

    def head(params, h):
        x = R.rms(h, params["final_norm"], s.eps)
        w = params["embed"].T if s.tied else params["lm_head"]
        return jnp.matmul(x, w.astype(x.dtype), precision=ops.prec,
                          preferred_element_type=jnp.float32)
    return head


def layer_counts(s, layer: int) -> Dict[str, float]:
    """q, k, v, o and the three FFN matrices; QK^T and PV, each 2
    operations per query head, key and dimension; cuboid scoring, two dot
    products per query head and block."""
    qd, kd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    per_pair = 4.0 * s.heads * s.head_dim
    return {"matmul_params": s.d * (qd + 2 * kd) + qd * s.d
            + 3 * s.d * s.ff,
            "pair_ops": per_pair, "score_ops": per_pair,
            "attend_ops": per_pair}
