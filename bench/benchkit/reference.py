"""The plain reference: the configuration's model in straightforward
``jax.numpy``, imported from nothing of the program.

It follows the published architecture (Qwen2: RMSNorm, q/k/v with bias,
rotary embedding on the two halves of each head, grouped-query attention,
SwiGLU, tied or untied head) and the configuration's DSA group for decode
positions: after the prompt, each token attends only to the blocks that
cuboid scoring selects (sink and most recent blocks always, then the best
upper bounds up to the token budget).  The prompt's own positions attend
densely and causally, as a prefill does.

``run`` takes a prompt and the tokens served after it and returns the
logits at every served token's position (teacher forcing: the served
tokens are the inputs), layer by layer and in blocks of query rows, so a
16k prompt fits next to the weights.  ``dtype="float32"`` with
``precision="highest"`` is the reference; ``CONTROLS`` names the
lower-precision control for each stated precision.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional

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


def _ops(dtype, precision):
    import jax
    import jax.numpy as jnp
    prec = _precisions()[precision]
    dt = jnp.dtype(dtype)

    def mm(a, b):
        return jnp.matmul(a, b, precision=prec).astype(dt)

    def ein(spec, a, b):
        return jnp.einsum(spec, a, b, precision=prec).astype(dt)

    def f32ein(spec, a, b):
        return jnp.einsum(spec, a, b, precision=prec,
                          preferred_element_type=jnp.float32)
    return dt, mm, ein, f32ein


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """x (T, H, D) at positions pos (T,): rotate the two halves."""
    import jax.numpy as jnp
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           -1).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _layer_fn(s: Shapes, S: int, n: int, dtype: str, precision: str,
              want_kv: bool):
    """One layer over a prompt of S positions followed by n decode
    positions; jitted once per (shapes, S, n)."""
    import jax
    import jax.numpy as jnp
    dt, mm, ein, f32ein = _ops(dtype, precision)
    H, Hk, D = s.heads, s.kv_heads, s.head_dim
    G = H // Hk
    scale = 1.0 / (D ** 0.5)
    T = S + n
    bs, K = s.block, s.top_k
    NB = -(-T // bs)

    def attn_prompt(q, k, v):
        # q (S, H, D), k/v (S, Hk, D): dense causal, Q_ROWS rows a block
        nq = -(-S // Q_ROWS)
        qp = jnp.pad(q, ((0, nq * Q_ROWS - S), (0, 0), (0, 0)))
        qb = qp.reshape(nq, Q_ROWS, Hk, G, D)
        kpos = jnp.arange(S)

        def block(i):
            qi = qb[i]
            qpos = i * Q_ROWS + jnp.arange(Q_ROWS)
            sc = f32ein("qhgd,khd->hgqk", qi, k) * scale
            sc = jnp.where(kpos[None, None, None, :]
                           <= qpos[None, None, :, None], sc, NEG_INF)
            p = jax.nn.softmax(sc, axis=-1).astype(dt)
            return ein("hgqk,khd->qhgd", p, v)
        o = jax.lax.map(block, jnp.arange(nq))
        return o.reshape(nq * Q_ROWS, H * D)[:S]

    def attn_decode(q, k, v):
        # q (n, H, D) at positions S..T-1; k/v (T, Hk, D) in position order
        kb = jnp.pad(k, ((0, NB * bs - T), (0, 0), (0, 0))
                     ).reshape(NB, bs, Hk, D).transpose(2, 0, 1, 3)
        vb = jnp.pad(v, ((0, NB * bs - T), (0, 0), (0, 0))
                     ).reshape(NB, bs, Hk, D).transpose(2, 0, 1, 3)
        kf = kb.astype(jnp.float32)
        mn, mx = kf.min(axis=2), kf.max(axis=2)          # (Hk, NB, D)
        blk = jnp.arange(NB)
        nd = -(-n // DECODE_ROWS)
        qp = jnp.pad(q, ((0, nd * DECODE_ROWS - n), (0, 0), (0, 0))
                     ).reshape(nd, DECODE_ROWS, Hk, G, D)

        def one(qj, pos):
            cur = pos + 1
            nvalid = -(-cur // bs)
            qf = qj.astype(jnp.float32)
            score = (f32ein("hgd,hbd->hgb", jnp.maximum(qf, 0.0), mx)
                     + f32ein("hgd,hbd->hgb", jnp.minimum(qf, 0.0), mn)
                     ).max(axis=1)                          # (Hk, NB)
            valid = blk < nvalid
            forced = valid & ((blk < jnp.minimum(s.sink_blocks, nvalid))
                              | (blk >= nvalid - s.recent_blocks))
            score = jnp.where(valid, score, NEG_INF)
            score = jnp.where(forced, jnp.inf, score)
            top, idx = jax.lax.top_k(score, K)                 # (Hk, K)
            sel = top > NEG_INF / 2
            idx = jnp.where(sel, idx, 0)
            kg = jnp.take_along_axis(kb, idx[:, :, None, None], axis=1)
            vg = jnp.take_along_axis(vb, idx[:, :, None, None], axis=1)
            tok = idx[:, :, None] * bs + jnp.arange(bs)
            ok = (tok < cur) & sel[:, :, None]                 # (Hk, K, bs)
            sc = f32ein("hgd,hktd->hgkt", qj, kg) * scale
            sc = jnp.where(ok[:, None], sc, NEG_INF).reshape(Hk, G, K * bs)
            p = jax.nn.softmax(sc, axis=-1).astype(dt)
            return ein("hgt,htd->hgd", p, vg.reshape(Hk, K * bs, D))

        def block(i):
            pos = S + i * DECODE_ROWS + jnp.arange(DECODE_ROWS)
            return jax.vmap(one)(qp[i], pos)
        o = jax.lax.map(block, jnp.arange(nd))
        return o.reshape(nd * DECODE_ROWS, H * D)[:n]

    def layer(p, h):
        pos = jnp.arange(T)
        a = p["attn"]
        x = _rms(h, p["attn_norm"], s.eps)
        q, k, v = mm(x, a["wq"]), mm(x, a["wk"]), mm(x, a["wv"])
        if "bq" in a:
            q = q + a["bq"].astype(dt)
            k = k + a["bk"].astype(dt)
            v = v + a["bv"].astype(dt)
        q = _rope(q.reshape(T, H, D), pos, s.rope_theta)
        k = _rope(k.reshape(T, Hk, D), pos, s.rope_theta)
        v = v.reshape(T, Hk, D)
        o = attn_prompt(q[:S], k[:S], v[:S])
        if n:
            o = jnp.concatenate([o, attn_decode(q[S:], k, v)], 0)
        h = h + mm(o, a["wo"])
        f = p["ffn"]
        x = _rms(h, p["ffn_norm"], s.eps)
        g = mm(x, f["w_gate"])
        u = mm(x, f["w_up"])
        h = h + mm((jax.nn.silu(g.astype(jnp.float32))
                    * u.astype(jnp.float32)).astype(dt), f["w_down"])
        if want_kv:
            return h, k, v
        return h

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(s: Shapes, dtype: str, precision: str):
    import jax
    import jax.numpy as jnp
    def head(params, h):
        x = _rms(h, params["final_norm"], s.eps)
        w = params["embed"].T if s.tied else params["lm_head"]
        return jnp.matmul(x, w.astype(x.dtype),
                          precision=_precisions()[precision],
                          preferred_element_type=jnp.float32)
    return jax.jit(head)


def run(params, shapes: Shapes, prompt: np.ndarray, served: List[int],
        dtype: str = "float32", precision: str = "highest",
        on_kv: Optional[Callable[[int, np.ndarray, np.ndarray],
                                 None]] = None,
        bucket: int = 1) -> np.ndarray:
    """Logits (len(served), V) float32 at each served token's position:
    row i is what the model predicts for served[i] after the prompt and
    served[:i].  ``on_kv(layer, k (S + m - 1, Hk, D), v)`` receives each
    layer's keys (after rotation) and values at every position whose
    token was an input: the prompt's S and the first m - 1 served
    tokens', as host arrays.

    Padding that cannot change a result keeps the number of compiled
    shapes small: with one served token the prompt is padded at its end
    to a multiple of ``bucket`` (causal: later rows never reach earlier
    ones), and decode positions to a multiple of ``DECODE_ROWS`` by
    repeating the last input."""
    import jax
    import jax.numpy as jnp
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
    h = params["embed"][jnp.asarray(np.concatenate([ids, inputs]))
                        ].astype(dt)
    fn = _layer_fn(shapes, S, n, dtype, precision, on_kv is not None)
    for l in range(shapes.layers):
        p = jax.tree.map(lambda x: x[l].astype(dt), params["layers"])
        if on_kv is not None:
            h, k, v = fn(p, h)
            on_kv(l, np.asarray(k[:T0], np.float32),
                  np.asarray(v[:T0], np.float32))
        else:
            h = fn(p, h)
    head = _head_fn(shapes, dtype, precision)
    pc = {k: (v.astype(dt) if k in ("final_norm", "embed", "lm_head")
              else v) for k, v in params.items() if k != "layers"}
    if m == 1:
        rows = h[S0 - 1:S0]
    else:
        rows = h[S0 - 1:S0 - 1 + m]
    return np.asarray(head(pc, rows), np.float32)
