"""Operations and bytes the model's mathematics needs, from its shapes.

These count the work the served model requires, whichever implementation
runs it: a multiply-add is 2 operations; causal attention counts each
query against the keys at or before it; decode attention counts the DSA
budget's selected tokens, and DSA scoring every written block.

Each layer's counts come from its architecture's ``layer_counts(s,
layer)`` (``bench/archs/<model_type>.py``), a dict of

* ``matmul_params``: weights of the layer's matrix multiplications that
  one token multiplies by (biases and norms are elementwise and left out;
  of routed experts, those a token is sent to);
* ``pair_ops``: prompt attention operations per causal query-key pair;
* ``score_ops``: DSA scoring operations per written block, one decode
  token;
* ``attend_ops``: decode attention operations per selected key.
"""
from __future__ import annotations

from benchkit.model import Shapes


def dense_params_per_layer(s: Shapes, layer: int = 0) -> int:
    """Weights of layer ``layer``'s matrix multiplications."""
    return s.arch.layer_counts(s, layer)["matmul_params"]


def head_flops(s: Shapes) -> int:
    """The output projection for one token's logits."""
    return 2 * s.d * s.vocab


def prefill_layer_flops(s: Shapes, start: int, stop: int,
                        layer: int = 0) -> float:
    """Layer ``layer`` over prompt positions [start, stop): dense matmuls
    plus causal attention."""
    c = s.arch.layer_counts(s, layer)
    n = stop - start
    keys = (stop * (stop + 1) - start * (start + 1)) / 2   # sum of p + 1
    return 2.0 * c["matmul_params"] * n + c["pair_ops"] * keys


def prefill_flops(s: Shapes, prompt: int, layer: int = None,
                  done_in_layer: int = 0) -> float:
    """Work of a prompt's prefill up to the cursor (``layer``, tokens done
    in it); the whole prefill when ``layer`` is None.  The last position's
    logits are counted with the last layer."""
    if layer is None or layer >= s.layers:
        return sum(prefill_layer_flops(s, 0, prompt, l)
                   for l in range(s.layers)) + head_flops(s)
    return sum(prefill_layer_flops(s, 0, prompt, l)
               for l in range(layer)) + \
        prefill_layer_flops(s, 0, min(done_in_layer, prompt), layer)


def decode_token_flops(s: Shapes, context: int) -> float:
    """One output token at ``context`` tokens of KV (itself included):
    dense matmuls, logits, DSA scoring of every written block, and
    attention over the tokens the DSA budget selects."""
    blocks = -(-context // s.block)
    attended = min(context, s.top_k * s.block)
    total = 0.0
    for layer in range(s.layers):
        c = s.arch.layer_counts(s, layer)
        total += (2.0 * c["matmul_params"] + c["score_ops"] * blocks
                  + c["attend_ops"] * attended)
    return total + head_flops(s)
