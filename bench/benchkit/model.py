"""A configuration file -> the program's ModelConfig and EngineConfig, and
the weights, made on the device from the seed in one jitted call.

The configuration file uses the published ``config.json`` key names
(``hidden_size``, ``num_hidden_layers``, ...).  Its ``dsa`` group states
the sparse-attention semantics the model is served with (the reference
follows the same group), and its ``engine`` group the few engine settings
the cell fixes; every other engine setting stays at the program's default.
What depends on the architecture is in ``bench/archs/<model_type>.py``,
found by the file's ``model_type`` (``spec.load_arch``).
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Dict

import numpy as np

from benchkit.spec import ROOT, load_arch

DTYPES = ("float32",)


class Shapes:
    """The sizes the benchmark reads from a configuration file: for the
    weights, the reference and the operation counts.  The keys every
    architecture has are read here, the rest by the architecture's
    ``read_shapes``; ``arch`` is its module."""

    def __init__(self, cfg: Dict[str, Any], root: Path = ROOT):
        self.model_type = cfg["model_type"]
        self.arch = load_arch(root, self.model_type)
        self.d = int(cfg["hidden_size"])
        self.layers = int(cfg["num_hidden_layers"])
        self.vocab = int(cfg["vocab_size"])
        self.tied = bool(cfg["tie_word_embeddings"])
        self.eps = float(cfg["rms_norm_eps"])
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
        self.arch.read_shapes(self, cfg)


def seed_key(seed: int):
    """A JAX key from any whole-number seed (the driver's exceed 32 bits)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(1)
    return jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)


def model_config(cfg: Dict[str, Any], name: str, root: Path = ROOT):
    """The program's ModelConfig for a configuration file."""
    from repro.models.common import DSAConfig
    s = Shapes(cfg, root)
    dsa = DSAConfig(block_size=s.block, token_budget=s.budget,
                    metadata=s.metadata, sink_blocks=s.sink_blocks,
                    recent_blocks=s.recent_blocks,
                    window=int(cfg["dsa"].get("window", 12)))
    return s.arch.model_config(s, cfg, name, dsa)


def engine_config(cfg: Dict[str, Any]):
    from repro.serving.engine import EngineConfig
    return EngineConfig(**cfg.get("engine", {}))


def init_weights(shapes: Shapes, seed: int, dtype=None):
    """The weights, in the pytree the program serves, drawn on the device
    in ONE jitted call from ``seed`` by the architecture's ``weights``."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype or shapes.dtype)

    def make(key):
        return shapes.arch.weights(shapes, key, dt)
    return jax.jit(make)(seed_key(seed))
