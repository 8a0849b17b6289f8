"""Unified hybrid-batching plane — prefill + decode in ONE mixed iteration.

Before this module the engine ran its two jitted planes back to back inside
``step``: every staged decode group walked all L layers
(``DevicePoolPlane.step_staged``), then every ``PrefillPlane`` ran its own
(layer, chunk) pass loop — decode rows idled while prefill segments
launched and vice versa, and each plane paid its own per-layer host stage
(separate fused FlashD2H saves, separate LRU/FlashH2D rounds).  The paper
names exactly this — "high HBM demands of hybrid batching" — as the
problem layer-segmented prefill exists to solve: both work kinds must
share one iteration's transfer stages.

``HybridPlane.run_iteration`` walks the model's layers ONCE per engine
iteration, carrying every decode plane's staged pipeline AND every prefill
plane's same-(layer, chunk) segment groups together.  Per model layer *i*:

1. decode ``select`` (attention) or the recurrent stage runs for every
   decode plane — identical jitted stages, identical inputs, identical
   order as ``step_staged``;
2. the layer's prefill groups run (``PrefillPlane.run_layer`` — one
   bucketed launch per (layer, chunk) group, chunks in plan order);
3. ONE ``layer_cb(win)`` fires — the single per-layer host stage.  The
   engine merges decode write-back and the prefill groups' fresh KV into
   ONE fused FlashD2H save, runs the LRU round for decode's selections,
   loads every plane's misses through at most ONE fused FlashH2D, and
   scatters restores into the decode pools BEFORE the attention that
   selected them;
4. decode ``attend`` runs for every decode plane over the restored pools.

After the walk each decode plane takes its logits stage and each prefill
plane its shared finalize — launches stay O(L) per iteration, independent
of how many decode rows and prefill segments are live (see
``plane_contract.mixed_launches_per_iteration``).

Because every launch is the SAME ``StageFns`` jit the split path uses, on
identical per-request inputs (masked-batch exact), mixed greedy tokens are
byte-identical to the split two-plane path — the ``"split"`` oracle knob
on ``EngineConfig.hybrid_plane`` keeps that path alive for equivalence
tests (tests/test_hybrid_plane.py).

``_HybridFns`` is the plane's registry, keyed structurally like
``staged_fns_for``: it COMPOSES the staged decode and prefill registries
rather than wrapping new jits, so the mixed plane adds zero new traces —
the cache-hit invariant of both underlying registries covers it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

import jax.numpy as jnp

from repro.core.device_pool import DevicePoolPlane, staged_fns_for
from repro.core.prefill_plane import (PrefillGroupRun,
                                      PrefillIterationResult, PrefillPlane,
                                      PrefillWalk, prefill_fns_for)
from repro.models import model as M
from repro.obs import tracing


class _HybridFns:
    """Stage registry of the mixed plane: a composition of the staged
    decode registry and the prefill registry (the mixed iteration launches
    exactly their jits, never new ones).  Keyed like ``staged_fns_for`` so
    value-equal configs share the same underlying compile caches."""

    contract_protocol = "hybrid-plane"

    def __init__(self, cfg, attn_impl: str, plane_mesh=None):
        self.cfg = cfg
        self.decode = staged_fns_for(cfg, attn_impl, plane_mesh)
        self.prefill = prefill_fns_for(cfg, plane_mesh)

    @property
    def trace_count(self) -> int:
        return self.decode.trace_count + self.prefill.trace_count

    @property
    def calls(self) -> int:
        return self.decode.calls + self.prefill.calls

    @property
    def shape_signatures(self) -> set:
        return self.decode.shape_signatures | self.prefill.shape_signatures


_HYBRID_FNS: Dict[Any, _HybridFns] = {}


def hybrid_fns_for(cfg, attn_impl: str, plane_mesh=None) -> _HybridFns:
    key = (repr(cfg), attn_impl,
           None if plane_mesh is None else plane_mesh.key())
    if key not in _HYBRID_FNS:
        _HYBRID_FNS[key] = _HybridFns(cfg, attn_impl, plane_mesh)
    return _HYBRID_FNS[key]


@dataclasses.dataclass
class DecodeJob:
    """One decode group's work for the mixed iteration."""
    plane: DevicePoolPlane
    token_by_req: Dict[str, int]


@dataclasses.dataclass
class PrefillJob:
    """One prefill plane's scheduled allowance for the mixed iteration."""
    plane: PrefillPlane
    allowance: Dict[str, int]


@dataclasses.dataclass
class DecodeRun:
    """In-flight staged state of one decode plane during the layer walk —
    the locals ``step_staged`` would keep."""
    plane: DevicePoolPlane
    fns: Any
    req_ids: List[str]
    mask: jax.Array
    x: jax.Array
    layer_params: List[Dict]
    enc_kvs: Any
    prev: Dict[str, int]
    info: Dict[str, Any]
    q: Any = None
    idx: Any = None
    valid: Any = None


@dataclasses.dataclass
class LayerWindow:
    """What ONE per-layer host stage sees: every decode plane's selection
    for this layer plus every prefill group that just ran here.  The
    engine's ``layer_cb`` merges these into one fused FlashD2H and at most
    one fused FlashH2D."""
    layer: int
    kind: str                                     # 'attn' | 'mamba' | 'rwkv'
    selections: List[Tuple[DecodeRun, Optional[np.ndarray]]]
    groups: List[Tuple[PrefillPlane, PrefillGroupRun]]


@dataclasses.dataclass
class MixedIterationResult:
    decode: List[Tuple[DevicePoolPlane, jax.Array, Dict, Dict[str, int]]]
    prefill: List[Tuple[PrefillPlane, PrefillIterationResult]]


class HybridPlane:
    """Mixed-iteration driver over the decode and prefill planes.

    Stateless between iterations apart from counters: the decode planes
    keep their persistent pools and the prefill planes their rows — this
    driver only owns the per-iteration layer walk."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.iterations = 0
        self.stage_timeline: List[Tuple[int, float, float]] = []
        # last iteration's (layer, idx_sync_s, host_stage_s) per layer_cb
        self.dispatch_sync_s = 0.0    # accumulated across iterations
        self.host_stage_s = 0.0       # from the plane.idx_sync /
                                      # plane.host_stage spans
        self.tracer = tracing.DEFAULT  # the engine hands its own

    def run_iteration(self, params: Dict, decode_jobs: List[DecodeJob],
                      prefill_jobs: List[PrefillJob],
                      layer_cb=None) -> MixedIterationResult:
        """Walk model layers 0..L-1 once, interleaving every decode
        plane's staged pipeline with every prefill plane's layer groups.
        ``layer_cb(win)`` fires exactly once per layer, between the
        layer's selections/prefill launches and its decode attends — the
        one per-layer host stage (fused FlashD2H/H2D window)."""
        cfg = self.cfg
        dec: List[DecodeRun] = []
        for job in decode_jobs:
            plane = job.plane
            fns = plane.staged_fns
            tokens = np.zeros((plane.b_cap,), np.int32)
            mask = np.zeros((plane.b_cap,), bool)
            for rid, tok in job.token_by_req.items():
                tokens[plane.rows[rid]] = tok
                mask[plane.rows[rid]] = True
            x = fns.embed(params, jnp.asarray(tokens))
            dec.append(DecodeRun(
                plane=plane, fns=fns, req_ids=list(job.token_by_req),
                mask=jnp.asarray(mask), x=x,
                layer_params=plane._layer_params(params),
                enc_kvs=plane.state["extra"].get("enc_kvs"),
                prev={rid: plane.cur_host[rid] for rid in job.token_by_req},
                info={"selected": {}}))
        pre: List[Tuple[PrefillPlane, PrefillWalk]] = []
        for pj in prefill_jobs:
            pre.append((pj.plane, pj.plane.begin_iteration(pj.allowance)))
        timeline: List[Tuple[int, float, float]] = []
        tr = self.tracer
        for i in range(cfg.num_layers):
            kind = M.layer_kind(cfg, i)
            selections: List[Tuple[DecodeRun, Optional[np.ndarray]]] = []
            t_sync = 0.0
            if kind == "attn":
                for d in dec:
                    st = d.plane.state
                    q, new_cache, idx, valid = d.fns.select(
                        d.layer_params[i], d.x, st["caches"][i],
                        st["cur_len"], d.mask)
                    st["caches"][i] = new_cache
                    if idx is not None:
                        d.info["selected"][i] = idx
                    d.q, d.idx, d.valid = q, idx, valid
                    # np.asarray(idx) is the ONLY host sync per layer (same
                    # as step_staged): it forces select_i — and the still-
                    # queued attend_{i-1} — before the host stage runs
                    with tr.span("plane.idx_sync", layer=i) as s_sync:
                        selections.append(
                            (d, None if idx is None else np.asarray(idx)))
                    t_sync += s_sync.dur
            else:
                for d in dec:
                    st = d.plane.state
                    d.x, new_cache = d.fns._recurrent[kind](
                        d.layer_params[i], d.x, st["caches"][i], d.mask)
                    st["caches"][i] = new_cache
            layer_groups: List[Tuple[PrefillPlane, PrefillGroupRun]] = []
            for plane, walk in pre:
                for g in plane.run_layer(params, i, walk):
                    layer_groups.append((plane, g))
            if layer_cb is not None and (selections or layer_groups):
                with tr.span("plane.host_stage", layer=i,
                             groups=len(layer_groups)) as s_stage:
                    layer_cb(LayerWindow(layer=i, kind=kind,
                                         selections=selections,
                                         groups=layer_groups))
                timeline.append((i, t_sync, s_stage.dur))
            if kind == "attn":
                for d in dec:
                    st = d.plane.state
                    d.x = d.fns.attend(d.layer_params[i], d.x, d.q,
                                       st["caches"][i], st["cur_len"],
                                       d.idx, d.valid,
                                       M.index_enc_kvs(d.enc_kvs, i))
        self.stage_timeline = timeline
        for _, _sync_s, _stage_s in timeline:
            self.dispatch_sync_s += _sync_s
            self.host_stage_s += _stage_s
        out_dec = []
        for d in dec:
            st = d.plane.state
            logits, new_len = d.fns.logits(params, d.x, st["cur_len"],
                                           d.mask)
            st["cur_len"] = new_len
            d.plane.buckets_seen.add((d.plane.b_cap, d.plane.nb_cap))
            d.plane.steps += 1
            for rid in d.req_ids:
                d.plane.cur_host[rid] += 1
            out_dec.append((d.plane, logits, d.info, d.prev))
        out_pre = []
        for plane, walk in pre:
            out_pre.append((plane, plane.finish_iteration(params, walk)))
        self.iterations += 1
        return MixedIterationResult(decode=out_dec, prefill=out_pre)
