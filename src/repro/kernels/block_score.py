"""Block-criticality scoring kernel (DSA select phase, paper §2.2).

Computes the Quest/ArkVale cuboid upper bound for every KV block and every
GQA group, reduced (max) over the query heads of the group:

    score[b, h, n] = max_g  sum_d  max(q[b,h,g,d] * mn[b,h,n,d],
                                       q[b,h,g,d] * mx[b,h,n,d])

Grid: (B, Hkv, NB / nb_tile).  Each step loads the group's query tile and a
tile of block metadata into VMEM; the two einsums hit the MXU with the
block axis as the 128-aligned minor-most dimension.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


def _score_kernel(q_ref, mn_ref, mx_ref, out_ref):
    # q: (1, 1, G, D); mn/mx: (1, 1, NBt, D); out: (1, 1, 1, NBt)
    q = q_ref[0, 0].astype(jnp.float32)                  # (G, D)
    mn = mn_ref[0, 0].astype(jnp.float32)                # (NBt, D)
    mx = mx_ref[0, 0].astype(jnp.float32)
    pos = jnp.maximum(q, 0.0)
    neg = jnp.minimum(q, 0.0)
    s = pos @ mx.T + neg @ mn.T                          # (G, NBt)
    out_ref[0, 0] = jnp.max(s, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("nb_tile", "interpret"))
def block_score(q: jax.Array, meta_min: jax.Array, meta_max: jax.Array, *,
                nb_tile: int = 128, interpret: Optional[bool] = None
                ) -> jax.Array:
    """q: (B, Hq, D); meta_min/max: (B, Hkv, NB, D) -> scores (B, Hkv, NB)."""
    B, Hq, D = q.shape
    _, Hkv, NB, _ = meta_min.shape
    G = Hq // Hkv
    nb_tile = min(nb_tile, NB)
    pad = (-NB) % nb_tile
    if pad:
        # padded blocks score against zero cuboids -> finite; callers mask
        meta_min = jnp.pad(meta_min, ((0, 0), (0, 0), (0, pad), (0, 0)))
        meta_max = jnp.pad(meta_max, ((0, 0), (0, 0), (0, pad), (0, 0)))
    NBp = NB + pad
    qg = q.reshape(B, Hkv, G, D)
    grid = (B, Hkv, NBp // nb_tile)
    out = pl.pallas_call(
        _score_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, n: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, nb_tile, D), lambda b, h, n: (b, h, n, 0)),
            pl.BlockSpec((1, 1, nb_tile, D), lambda b, h, n: (b, h, n, 0)),
        ],
        # a unit sublane axis keeps the output tile's last two dims
        # (1, nb_tile): whole along the sublanes, 128-aligned along lanes
        out_specs=pl.BlockSpec((1, 1, 1, nb_tile),
                               lambda b, h, n: (b, h, 0, n)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, 1, NBp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(qg, meta_min, meta_max)
    return out[:, :, 0, :NB]
