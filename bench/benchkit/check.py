"""Whether what the timed path served is correct.

Numbers the check can compare (a cell's ``bench/checks/<cell>.json``
names the ones it uses, each with its limit):

* ``first_gap``: over the checked requests' FIRST tokens (the prefill
  plane's output), the widest gap by which the served token's reference
  logit lies below the reference's best logit at that position;
* ``decode_gap``: the same over every later served token (decode after
  DSA selection, HBM eviction with block drops, FlashH2D restores);
* ``kv_err``: over the checked requests, layers, keys and values, the
  largest |saved - reference| of the prompt KV that FlashD2H saved to the
  host pool, relative to the largest |reference| of that layer and tensor;
* ``kv_err_decode``: over the KV that the decode steps wrote back to the
  host pool for each served token fed back as an input, each position's
  largest |saved - reference| over layers, keys and values (relative as
  above), and of those the 90th percentile over the checked positions.
  In every layer after the first that KV comes from the decode attention
  over the blocks DSA selected, the LRU kept and FlashH2D restored, so a
  wrong or stale block shows here even where the served token stays the
  argmax.  A percentile and not the largest: where two blocks' cuboid
  bounds tie to float32 rounding, the program and the reference may
  each keep another one, and that position's KV then differs by ~1e-3
  in every later layer; such ties are rare, a fault is not.

A number is correct when it is at most its limit.  The gaps are valid for
greedy tokens only; every mix here decodes greedily.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def gaps(ref_logits: np.ndarray, tokens: List[int]) -> np.ndarray:
    """Per position: reference best logit minus the reference logit of
    the token served there."""
    rows = np.arange(len(tokens))
    return ref_logits.max(axis=1) - ref_logits[rows, np.asarray(tokens)]


def control_tokens(control_logits: np.ndarray) -> List[int]:
    """The tokens the control would serve: its own greedy choice."""
    return [int(t) for t in control_logits.argmax(axis=1)]


DECODE_KV_QUANTILE = 0.9


class Readings:
    """Running maxima of the compared numbers over checked requests, and
    per-position errors, which ``close`` reduces to their quantile."""

    def __init__(self):
        self.values: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.positions: Dict[str, Dict[object, np.ndarray]] = {}
        self.per_position: Dict[str, np.ndarray] = {}

    def add_positions(self, name: str, key, errs: np.ndarray) -> None:
        """Per-position errors of one request's layer: kept as the
        largest over that request's layers."""
        d = self.positions.setdefault(name, {})
        d[key] = np.maximum(d[key], errs) if key in d else errs

    def close(self) -> None:
        for name, d in self.positions.items():
            errs = np.concatenate(list(d.values()))
            if errs.size:
                self.values[name] = float(
                    np.quantile(errs, DECODE_KV_QUANTILE))
                self.counts[name] = int(errs.size)
                self.per_position[name] = errs
        self.positions = {}

    def add(self, name: str, xs) -> None:
        xs = np.asarray(xs, np.float64).ravel()
        if xs.size == 0:
            return
        v = float(xs.max())
        self.values[name] = max(self.values.get(name, -np.inf), v)
        self.counts[name] = self.counts.get(name, 0) + int(xs.size)

    def add_tokens(self, ref_logits: np.ndarray, tokens: List[int]) -> None:
        g = gaps(ref_logits, tokens)
        self.add("first_gap", g[:1])
        self.add("decode_gap", g[1:])


def kv_rel_err(saved: np.ndarray, ref: np.ndarray) -> float:
    scale = float(np.abs(ref).max())
    return float(np.abs(saved - ref).max()) / max(scale, 1e-30)


def add_kv(readings: Readings, key, saved: Dict[str, np.ndarray],
           ref: Dict[str, np.ndarray], S: int) -> None:
    """Read one layer's cached state of request ``key`` against the
    reference's, each ``{name: (T, H, D)}`` as the host pool names it
    (``k`` and ``v``, or a latent ``k`` alone): positions before S as
    ``kv_err``, one reading per name, the rest, position by position and
    the largest over names, as ``kv_err_decode``."""
    readings.add("kv_err", [kv_rel_err(saved[name][:S], r[:S])
                            for name, r in ref.items()])
    T = min(len(r) for r in ref.values())
    if T > S:
        errs = [np.abs(saved[name][S:] - r[S:]).max(axis=(1, 2))
                / max(float(np.abs(r[S:]).max()), 1e-30)
                for name, r in ref.items()]
        readings.add_positions("kv_err_decode", key,
                               np.max(np.stack(errs), axis=0))


def pool_kv(pool, layer: int, ref: Dict[str, np.ndarray]
            ) -> Dict[str, np.ndarray]:
    """What a host pool holds of ``layer`` under each name of ``ref``, at
    ref's positions: the pool's (L, H, blocks, block size, D) array of
    that name as (T, H, D)."""
    out = {}
    for name, r in ref.items():
        x = getattr(pool, name)[layer]              # (H, NB, bs, D)
        x = x.reshape(x.shape[0], -1, x.shape[-1])[:, :len(r)]
        out[name] = x.transpose(1, 0, 2)
    return out


def verdict(readings: Readings, limits: Dict[str, Dict]) -> Dict:
    """{name: {"value", "limit", "n"}} for every limited number, and
    whether all hold.  A number the run could not read is a failure."""
    out, ok = {}, True
    for name, spec in limits.items():
        v: Optional[float] = readings.values.get(name)
        lim = float(spec["limit"])
        good = v is not None and np.isfinite(v) and v <= lim
        ok &= bool(good)
        out[name] = {"value": v, "limit": lim,
                     "n": readings.counts.get(name, 0)}
    return {"ok": ok, "numbers": out}
