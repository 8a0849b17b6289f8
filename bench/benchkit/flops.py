"""Operations and bytes the model's mathematics needs, from its shapes.

These count the work the served model requires, whichever implementation
runs it: a multiply-add is 2 operations; causal attention counts each
query against the keys at or before it; decode attention counts the DSA
budget's selected tokens, and DSA scoring every written block.
"""
from __future__ import annotations

from benchkit.model import Shapes


def dense_params_per_layer(s: Shapes) -> int:
    """Weights of one layer's matrix multiplications (biases and norms are
    elementwise and left out)."""
    qd, kd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return s.d * (qd + 2 * kd) + qd * s.d + 3 * s.d * s.ff


def head_flops(s: Shapes) -> int:
    """The output projection for one token's logits."""
    return 2 * s.d * s.vocab


def prefill_layer_flops(s: Shapes, start: int, stop: int) -> float:
    """One layer over prompt positions [start, stop): dense matmuls plus
    causal attention (QK^T and PV, each 2 ops per query-key-dim)."""
    n = stop - start
    keys = (stop * (stop + 1) - start * (start + 1)) / 2   # sum of p + 1
    return 2.0 * dense_params_per_layer(s) * n + \
        4.0 * s.heads * s.head_dim * keys


def prefill_flops(s: Shapes, prompt: int, layer: int = None,
                  done_in_layer: int = 0) -> float:
    """Work of a prompt's prefill up to the cursor (``layer``, tokens done
    in it); the whole prefill when ``layer`` is None.  The last position's
    logits are counted with the last layer."""
    if layer is None or layer >= s.layers:
        return s.layers * prefill_layer_flops(s, 0, prompt) + head_flops(s)
    return layer * prefill_layer_flops(s, 0, prompt) + \
        prefill_layer_flops(s, 0, min(done_in_layer, prompt))


def decode_token_flops(s: Shapes, context: int) -> float:
    """One output token at ``context`` tokens of KV (itself included):
    dense matmuls, logits, cuboid scoring of every written block (two
    dot products per query head and block), and attention over the
    tokens the DSA budget selects."""
    blocks = -(-context // s.block)
    attended = min(context, s.top_k * s.block)
    per_layer = (2.0 * dense_params_per_layer(s)
                 + 4.0 * s.heads * s.head_dim * blocks
                 + 4.0 * s.heads * s.head_dim * attended)
    return s.layers * per_layer + head_flops(s)
