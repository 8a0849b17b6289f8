"""The plain reference: the configuration's model in straightforward
``jax.numpy``, imported from nothing of the program.

The architecture's layers come from ``bench/archs/<model_type>.py``
(``Shapes.arch``); this module holds what every architecture shares: the
arithmetic of one dtype and precision (``Ops``), RMSNorm, rotary
embedding, and the DSA machinery with its blocking.  The prompt's own
positions attend densely and causally, as a prefill does, ``Q_ROWS``
query rows a block (``prompt_attention``).  Each decode position attends
only to the blocks that cuboid scoring selects: sink and most recent
blocks always, then the best upper bounds up to the token budget
(``cuboid_scores``, ``select_blocks``, ``dsa_attention``, ``DECODE_ROWS``
positions a block).  Both take the softmax scale, keys ``(T, Hk, Dk)``
and values ``(T, Hk, Dv)`` whose width may differ from the keys', and
any number of query heads per key head.

``run`` takes a prompt and the tokens served after it and returns the
logits at every served token's position (teacher forcing: the served
tokens are the inputs), layer by layer and in blocks of query rows, so a
16k prompt fits next to the weights.  ``dtype="float32"`` with
``precision="highest"`` is the reference; ``CONTROLS`` names the
lower-precision control for each stated precision.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional

import numpy as np

from benchkit.model import Shapes

NEG_INF = -1e30


def _precisions():
    import jax
    P = jax.lax.Precision
    return {"highest": P.HIGHEST, "high": P.HIGH, "default": P.DEFAULT}
# the nearest precision below the one a configuration states:
# (dtype, matmul precision) of the control
CONTROLS = {"highest": ("float32", "high"),
            "high": ("bfloat16", "default"),
            "default": ("bfloat16", "default")}
Q_ROWS = 512          # query rows per block of the prompt's attention
DECODE_ROWS = 16      # decode positions per block of the DSA attention


class Ops:
    """The arithmetic of one dtype and matmul precision: ``mm`` and
    ``ein`` round their result to ``dt``, ``f32ein`` keeps float32."""

    def __init__(self, dtype: str, precision: str):
        import jax.numpy as jnp
        self.prec = prec = _precisions()[precision]
        self.dt = dt = jnp.dtype(dtype)

        def mm(a, b):
            return jnp.matmul(a, b, precision=prec).astype(dt)

        def ein(spec, a, b):
            return jnp.einsum(spec, a, b, precision=prec).astype(dt)

        def f32ein(spec, a, b):
            return jnp.einsum(spec, a, b, precision=prec,
                              preferred_element_type=jnp.float32)
        self.mm, self.ein, self.f32ein = mm, ein, f32ein


def rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
    """x (T, H, D) at positions pos (T,): rotate the two halves."""
    import jax.numpy as jnp
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           -1).astype(x.dtype)


def prompt_attention(q, k, v, scale: float, ops: Ops):
    """q (S, H, Dk), k (S, Hk, Dk), v (S, Hk, Dv): dense causal attention,
    ``Q_ROWS`` query rows a block; -> (S, H * Dv)."""
    import jax
    import jax.numpy as jnp
    S, H, D = q.shape
    Hk, Dv = k.shape[1], v.shape[-1]
    G = H // Hk
    nq = -(-S // Q_ROWS)
    qp = jnp.pad(q, ((0, nq * Q_ROWS - S), (0, 0), (0, 0)))
    qb = qp.reshape(nq, Q_ROWS, Hk, G, D)
    kpos = jnp.arange(S)

    def block(i):
        qi = qb[i]
        qpos = i * Q_ROWS + jnp.arange(Q_ROWS)
        sc = ops.f32ein("qhgd,khd->hgqk", qi, k) * scale
        sc = jnp.where(kpos[None, None, None, :]
                       <= qpos[None, None, :, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1).astype(ops.dt)
        return ops.ein("hgqk,khd->qhgd", p, v)
    o = jax.lax.map(block, jnp.arange(nq))
    return o.reshape(nq * Q_ROWS, H * Dv)[:S]


def cuboid_scores(qf, mn, mx, ops: Ops):
    """Per key head and block, the upper bound cuboid metadata gives of a
    query's dot product with the block's keys, the largest over the
    head's query heads: qf (Hk, G, D) float32, mn/mx (Hk, NB, D) the
    blocks' smallest and largest key entries; -> (Hk, NB)."""
    import jax.numpy as jnp
    return (ops.f32ein("hgd,hbd->hgb", jnp.maximum(qf, 0.0), mx)
            + ops.f32ein("hgd,hbd->hgb", jnp.minimum(qf, 0.0), mn)
            ).max(axis=1)


def select_blocks(score, blk, nvalid, s: Shapes):
    """The blocks one decode position attends: of the ``nvalid`` written
    (``blk`` numbers every block, ``arange(NB)``), the first
    ``sink_blocks`` and last ``recent_blocks`` always, then the best
    scores up to ``top_k`` blocks.  -> (idx (Hk, K), sel (Hk, K)): block
    ids, and which of them are selected at all."""
    import jax
    import jax.numpy as jnp
    valid = blk < nvalid
    forced = valid & ((blk < jnp.minimum(s.sink_blocks, nvalid))
                      | (blk >= nvalid - s.recent_blocks))
    score = jnp.where(valid, score, NEG_INF)
    score = jnp.where(forced, jnp.inf, score)
    top, idx = jax.lax.top_k(score, s.top_k)
    sel = top > NEG_INF / 2
    return jnp.where(sel, idx, 0), sel


def dsa_attention(q, k, v, S: int, scale: float, s: Shapes, ops: Ops):
    """DSA decode attention: q (n, H, Dk) at positions S .. S+n-1 over
    k (T, Hk, Dk) and v (T, Hk, Dv) in position order, each position
    over the blocks ``select_blocks`` picks for it, ``DECODE_ROWS``
    positions a block; -> (n, H * Dv)."""
    import jax
    import jax.numpy as jnp
    n, H, D = q.shape
    T, Hk = k.shape[:2]
    Dv = v.shape[-1]
    G = H // Hk
    bs, K = s.block, s.top_k
    NB = -(-T // bs)
    kb = jnp.pad(k, ((0, NB * bs - T), (0, 0), (0, 0))
                 ).reshape(NB, bs, Hk, D).transpose(2, 0, 1, 3)
    vb = jnp.pad(v, ((0, NB * bs - T), (0, 0), (0, 0))
                 ).reshape(NB, bs, Hk, Dv).transpose(2, 0, 1, 3)
    kf = kb.astype(jnp.float32)
    mn, mx = kf.min(axis=2), kf.max(axis=2)          # (Hk, NB, D)
    blk = jnp.arange(NB)
    nd = -(-n // DECODE_ROWS)
    qp = jnp.pad(q, ((0, nd * DECODE_ROWS - n), (0, 0), (0, 0))
                 ).reshape(nd, DECODE_ROWS, Hk, G, D)

    def one(qj, pos):
        cur = pos + 1
        nvalid = -(-cur // bs)
        score = cuboid_scores(qj.astype(jnp.float32), mn, mx, ops)
        idx, sel = select_blocks(score, blk, nvalid, s)
        kg = jnp.take_along_axis(kb, idx[:, :, None, None], axis=1)
        vg = jnp.take_along_axis(vb, idx[:, :, None, None], axis=1)
        tok = idx[:, :, None] * bs + jnp.arange(bs)
        ok = (tok < cur) & sel[:, :, None]                 # (Hk, K, bs)
        sc = ops.f32ein("hgd,hktd->hgkt", qj, kg) * scale
        sc = jnp.where(ok[:, None], sc, NEG_INF).reshape(Hk, G, K * bs)
        p = jax.nn.softmax(sc, axis=-1).astype(ops.dt)
        return ops.ein("hgt,htd->hgd", p, vg.reshape(Hk, K * bs, Dv))

    def block(i):
        pos = S + i * DECODE_ROWS + jnp.arange(DECODE_ROWS)
        return jax.vmap(one)(qp[i], pos)
    o = jax.lax.map(block, jnp.arange(nd))
    return o.reshape(nd * DECODE_ROWS, H * Dv)[:n]


@functools.lru_cache(maxsize=None)
def _layer_fn(s: Shapes, kind, S: int, n: int, dtype: str, precision: str,
              want_kv: bool):
    """One layer of ``kind`` over a prompt of S positions followed by n
    decode positions; jitted once per (shapes, kind, S, n)."""
    import jax
    body = s.arch.reference_layer(s, kind, Ops(dtype, precision), S, n)

    def layer(p, h):
        h, kv = body(p, h)
        if want_kv:
            return h, kv
        return h

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(s: Shapes, dtype: str, precision: str):
    import jax
    return jax.jit(s.arch.reference_head(s, Ops(dtype, precision)))


def run(params, shapes: Shapes, prompt: np.ndarray, served: List[int],
        dtype: str = "float32", precision: str = "highest",
        on_kv: Optional[Callable[[int, Dict[str, np.ndarray]],
                                 None]] = None,
        bucket: int = 1) -> np.ndarray:
    """Logits (len(served), V) float32 at each served token's position:
    row i is what the model predicts for served[i] after the prompt and
    served[:i].  ``on_kv(layer, {name: (S + m - 1, H, D)})`` receives
    each layer's cached state, as the host pool names it (keys after
    rotation and values; a latent alone), at every position whose token
    was an input: the prompt's S and the first m - 1 served tokens', as
    host arrays.

    Padding that cannot change a result keeps the number of compiled
    shapes small: with one served token the prompt is padded at its end
    to a multiple of ``bucket`` (causal: later rows never reach earlier
    ones), and decode positions to a multiple of ``DECODE_ROWS`` by
    repeating the last input."""
    import jax
    import jax.numpy as jnp
    arch = shapes.arch
    S0, m = len(prompt), len(served)
    ids = np.asarray(prompt, np.int32)
    inputs = np.asarray(served[:-1], np.int32)
    if m == 1:
        S = -(-S0 // bucket) * bucket
        ids = np.concatenate([ids, np.zeros(S - S0, np.int32)])
        n = 0
        T0 = S0
    else:
        T0 = S0 + m - 1
        S = S0
        n = -(-(m - 1) // DECODE_ROWS) * DECODE_ROWS
        inputs = np.concatenate(
            [inputs, np.full(n - (m - 1), inputs[-1], np.int32)])
    dt = jnp.dtype(dtype)
    h = arch.embed(params, jnp.asarray(np.concatenate([ids, inputs]))
                   ).astype(dt)
    for l in range(shapes.layers):
        fn = _layer_fn(shapes, arch.layer_kind(shapes, l), S, n, dtype,
                       precision, on_kv is not None)
        p = jax.tree.map(lambda x: x.astype(dt),
                         arch.layer_params(params, l))
        if on_kv is not None:
            h, kv = fn(p, h)
            on_kv(l, {name: np.asarray(x[:T0], np.float32)
                      for name, x in kv.items()})
        else:
            h = fn(p, h)
    head = _head_fn(shapes, dtype, precision)
    pc = jax.tree.map(lambda x: x.astype(dt), arch.head_params(params))
    if m == 1:
        rows = h[S0 - 1:S0]
    else:
        rows = h[S0 - 1:S0 - 1 + m]
    return np.asarray(head(pc, rows), np.float32)
