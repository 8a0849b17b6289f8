"""Blocked causal flash attention for prefill segments.

Layer-segmented prefill (paper §3.4) runs ONE layer over the whole prompt
per batch; its attention is a standard causal flash kernel.  Tiling:
grid (B, Hkv, nQ, nK) with the key axis innermost; online-softmax scratch
(m, l, acc) persists across the nK steps of a query tile.  Causal skip:
key tiles strictly above the diagonal are masked (the j-loop upper bound
is handled by masking — the triangular-schedule variant is the §Perf
optimized path at the jnp level).

Validated in interpret mode against ``ref.flash_prefill``.
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


def _make_kernel(scale: float, q_tile: int, k_tile: int, nK: int,
                 q_offset: int, Sk: int):
    def kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref):
        iq = pl.program_id(2)
        jk = pl.program_id(3)

        @pl.when(jk == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0, 0, 0].astype(jnp.float32)       # (G*q_tile, D) flattened
        k = k_ref[0, 0].astype(jnp.float32)          # (k_tile, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (k_tile, Dv)
        G = q.shape[0] // q_tile

        s = (q @ k.T) * scale                        # (G*q_tile, k_tile)
        qpos = (q_offset + iq * q_tile
                + jax.lax.broadcasted_iota(jnp.int32, (G, q_tile, 1), 1))
        kpos = jk * k_tile + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, k_tile), 2)
        mask = ((qpos >= kpos) & (kpos < Sk)).reshape(G * q_tile, k_tile)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + p @ v
        m_ref[...] = m_new

        @pl.when(jk == nK - 1)
        def _finalize():
            l = jnp.maximum(l_ref[...], 1e-30)
            out_ref[0, 0, 0] = (acc_ref[...] / l).astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("scale", "q_tile", "k_tile", "q_offset",
                                    "interpret"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  scale: Optional[float] = None, q_offset: int = 0,
                  q_tile: int = 128, k_tile: int = 128,
                  interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D).  Causal.  -> (B, Sq, Hq, Dv).

    GQA groups are folded into the query tile: the kernel sees
    (G*q_tile, D) query tiles so one key tile serves the whole group."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    q_tile = min(q_tile, Sq)
    k_tile = min(k_tile, Sk)
    pq = (-Sq) % q_tile
    pk = (-Sk) % k_tile
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nQ = (Sq + pq) // q_tile
    nK = (Sk + pk) // k_tile
    # (B, Hkv, nQ, G*q_tile, D): group-major query tiles
    qt = (qp.reshape(B, nQ, q_tile, Hkv, G, D)
          .transpose(0, 3, 1, 4, 2, 5)
          .reshape(B, Hkv, nQ * 1, G * q_tile, D))
    kt = kp.transpose(0, 2, 1, 3)                    # (B, Hkv, Skp, D)
    vt = vp.transpose(0, 2, 1, 3)

    kernel = _make_kernel(scale, q_tile, k_tile, nK, q_offset, Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nQ, nK),
        in_specs=[
            pl.BlockSpec((1, 1, 1, G * q_tile, D),
                         lambda b, h, i, j: (b, h, i, 0, 0)),
            pl.BlockSpec((1, 1, k_tile, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, k_tile, Dv), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, G * q_tile, Dv),
                               lambda b, h, i, j: (b, h, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, nQ, G * q_tile, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * q_tile, 1), jnp.float32),
            pltpu.VMEM((G * q_tile, 1), jnp.float32),
            pltpu.VMEM((G * q_tile, Dv), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt.reshape(B, Hkv, nQ, G * q_tile, D), kt, vt)
    # back to (B, Sq, Hq, Dv)
    out = (out.reshape(B, Hkv, nQ, G, q_tile, Dv)
           .transpose(0, 2, 4, 1, 3, 5)
           .reshape(B, nQ * q_tile, Hq, Dv))
    return out[:, :Sq]
