"""A configuration file -> the program's ModelConfig and EngineConfig, and
the weights, made on the device from the seed in one jitted call.

The configuration file uses the published ``config.json`` key names
(``hidden_size``, ``num_hidden_layers``, ...).  Its ``dsa`` group states
the sparse-attention semantics the model is served with (the reference
follows the same group), and its ``engine`` group the few engine settings
the cell fixes; every other engine setting stays at the program's default.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

DTYPES = ("float32",)


class Shapes:
    """The sizes the benchmark reads from a configuration file: for the
    weights, the reference and the operation counts."""

    def __init__(self, cfg: Dict[str, Any]):
        self.d = int(cfg["hidden_size"])
        self.layers = int(cfg["num_hidden_layers"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg["num_key_value_heads"])
        self.head_dim = int(cfg.get("head_dim", self.d // self.heads))
        self.ff = int(cfg["intermediate_size"])
        self.vocab = int(cfg["vocab_size"])
        self.tied = bool(cfg["tie_word_embeddings"])
        self.rope_theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        self.qkv_bias = bool(cfg["qkv_bias"])
        self.dtype = cfg["torch_dtype"]
        if self.dtype not in DTYPES:
            raise ValueError(f"serving dtype {self.dtype!r} not in {DTYPES}")
        dsa = cfg["dsa"]
        self.block = int(dsa["block_size"])
        self.budget = int(dsa["token_budget"])
        self.top_k = max(1, self.budget // self.block)
        self.metadata = dsa["metadata"]
        self.sink_blocks = int(dsa["sink_blocks"])
        self.recent_blocks = int(dsa["recent_blocks"])
        if self.metadata != "cuboid":
            raise ValueError("the reference scores cuboid metadata only")


def seed_key(seed: int):
    """A JAX key from any whole-number seed (the driver's exceed 32 bits)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(1)
    return jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)


def model_config(cfg: Dict[str, Any], name: str):
    """The program's ModelConfig for a configuration file."""
    from repro.models.common import DSAConfig, ModelConfig
    s = Shapes(cfg)
    dsa = cfg["dsa"]
    return ModelConfig(
        name=name, arch_type="dense", num_layers=s.layers, d_model=s.d,
        num_heads=s.heads, num_kv_heads=s.kv_heads, d_ff=s.ff,
        vocab_size=s.vocab, head_dim=s.head_dim, qkv_bias=s.qkv_bias,
        rope_theta=s.rope_theta, norm_eps=s.eps, tie_embeddings=s.tied,
        dsa=DSAConfig(block_size=s.block, token_budget=s.budget,
                      metadata=s.metadata, sink_blocks=s.sink_blocks,
                      recent_blocks=s.recent_blocks,
                      window=int(dsa.get("window", 12))),
        source=cfg["source"])


def engine_config(cfg: Dict[str, Any]):
    from repro.serving.engine import EngineConfig
    return EngineConfig(**cfg.get("engine", {}))


def init_weights(shapes: Shapes, seed: int, dtype=None):
    """The weights, in the pytree the program serves (layers stacked on a
    leading axis), drawn on the device in ONE jitted call from ``seed``.

    Linear weights are N(0, 1/fan_in), the embedding N(0, 0.02^2); norm
    weights are 1 + N(0, 0.1^2) and q/k/v biases N(0, 0.1^2), so the norm
    and bias paths carry numbers that a dropped weight would change."""
    import jax
    import jax.numpy as jnp
    s = shapes
    dt = jnp.dtype(dtype or s.dtype)
    L, d, f, V = s.layers, s.d, s.ff, s.vocab
    qd, kd = s.heads * s.head_dim, s.kv_heads * s.head_dim

    def make(key):
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

    return jax.jit(make)(seed_key(seed))
