"""Batched jitted prefill plane — layer-segmented prefill as a subsystem.

Before this module, layer-segmented prefill (paper §3.4) ran per request:
a batch-1 unjitted Python loop over whole layers (`engine._run_layer_segment`)
with one numpy `save_contiguous` host round-trip per layer per request —
while decode had had a persistent jitted plane since PR 2.  `PrefillPlane`
gives prefill the same treatment, mirroring `DevicePoolPlane`:

* **Admission** — a request entering layer-segmented prefill is admitted
  ONCE into a padded plane row carrying its residual stream (`hidden`
  (B_cap, S_cap, d)), per-layer recurrent states (mamba/rwkv), and whisper
  encoder KV; the segment plan from `layer_prefill.plan_segments` becomes
  the row's cursor.  Freed rows are reused lowest-first.
* **Batched layer launches** — each iteration groups every admitted row's
  next `PrefillSegment` by (layer, chunk_start) and runs each group as ONE
  jitted launch over the padded batch (`models.model.prefill_*_batched`):
  `token_mask` marks each row's real tokens, `step_mask` parks rows whose
  request is not scheduled, token windows and batch rows follow
  `BucketingPolicy` buckets, so retraces stay bounded by distinct shape
  signatures (`_PrefillFns.trace_count == len(shape_signatures)`, the same
  cache-hit invariant as the decode plane).
* **Chunked layer segments** — the intra-layer (layer, chunk) steps that
  `plan_segments` emits are EXECUTED here (the legacy executor only ever
  ran whole layers): chunk c of layer l attends to the layer's earlier
  chunks through the plane's one-layer context buffer `ctx_k/ctx_v`, which
  holds at most ONE layer of KV for the whole batch — the paper's prefill
  HBM bound, now per-batch.  The same buffer is what the engine reads for
  the per-group fused FlashD2H save and the end-of-layer pool builds.
* **Finalize** — rows whose last segment ran this iteration share one
  jitted logits launch (`prefill_logits_batched` gathers each row's last
  real position).

The engine drives this plane by default (`EngineConfig.prefill_exec=
"plane"`); the per-request loop survives as `prefill_exec="legacy"`, the
equivalence oracle.  MLA models run whole-layer segments only (their latent
cache has no chunked-context attention path, matching the chunked
baseline's MLA restriction).
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device_pool import BucketingPolicy, StageFns
from repro.core.layer_prefill import PrefillSegment
from repro.models import model as M
from repro.obs import tracing


class _PrefillFns(StageFns):
    """Per-stage jits for the batched prefill plane: one ATTENTION-layer
    stage, one stage per recurrent layer kind, and the finalize (logits)
    stage.  Every layer stage takes a LAYER's params pytree, so one trace
    serves all structurally identical layers; ``StageFns`` supplies the
    cache-hit invariant ``trace_count == len(shape_signatures)`` tests
    assert (bounded by stage kinds x shape buckets x chunk offsets, never
    the iteration count)."""

    contract_protocol = "prefill-plane"
    jit_prefix = "prefill"

    def __init__(self, cfg, plane_mesh=None):
        super().__init__()
        self.cfg = cfg
        self.plane_mesh = plane_mesh
        wrap = self.wrap

        # with a plane_mesh, attention launches run SEQUENCE-SHARDED over
        # the mesh's model axis (model._prefill_attn_layer_batched_cp):
        # only the window's fresh K/V is all-gathered.  Recurrent layers
        # (sequential scans) and MLA layers stay replicated.
        self.attn = wrap(
            "attn",
            lambda p, h, pos, tmask, smask, ctx, enc, qoff:
            M.prefill_attn_layer_batched(
                p, cfg, h, pos, tmask, smask,
                k_ctx=None if ctx is None else ctx[0],
                v_ctx=None if ctx is None else ctx[1],
                q_offset=qoff, enc_kv=enc, plane_mesh=plane_mesh))
        self.rec = {
            kind: wrap("rec-" + kind,
                       lambda p, h, tmask, smask, state, kind=kind:
                       M.prefill_recurrent_layer_batched(
                           p, cfg, kind, h, tmask, smask, state))
            for kind in ("mamba", "rwkv")}
        self.finalize = wrap(
            "finalize",
            lambda params, h, tok_len:
            M.prefill_logits_batched(params, cfg, h, tok_len))


# keyed structurally like device_pool's registries so value-equal configs
# share one compile cache across engines
_PREFILL_FNS: Dict[Any, _PrefillFns] = {}


def prefill_fns_for(cfg, plane_mesh=None) -> _PrefillFns:
    key = (repr(cfg), None if plane_mesh is None else plane_mesh.key())
    if key not in _PREFILL_FNS:
        _PREFILL_FNS[key] = _PrefillFns(cfg, plane_mesh)
    return _PREFILL_FNS[key]


class _AdmitEmbedFns(StageFns):
    """ONE jitted bucketed embedding launch for a whole admission batch.

    Admission used to embed eagerly one request at a time (one lookup
    launch per admitted request per iteration); the engine now collects
    every pure-text request admitted in an iteration, pads the token ids
    to (batch bucket, token bucket), and runs this single stage —
    ``trace_count == len(shape_signatures)`` bounds compiles by the bucket
    grid, independent of how many requests arrive together."""

    contract_protocol = "admit-embed"
    jit_prefix = "prefill"

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed = self.wrap(
            "admit-embed", lambda params, tokens: params["embed"][tokens])


_ADMIT_EMBED_FNS: Dict[str, _AdmitEmbedFns] = {}


def admit_embed_fns_for(cfg) -> _AdmitEmbedFns:
    key = repr(cfg)
    if key not in _ADMIT_EMBED_FNS:
        _ADMIT_EMBED_FNS[key] = _AdmitEmbedFns(cfg)
    return _ADMIT_EMBED_FNS[key]


@dataclasses.dataclass
class PrefillGroupRun:
    """One executed batched launch: every scheduled row whose next segment
    was (layer, chunk_start), padded to `chunk_cap` tokens."""
    layer: int
    kind: str                               # 'attn' | 'mamba' | 'rwkv'
    chunk_start: int
    chunk_cap: int                          # bucketed token window
    req_ids: List[str]
    segs: Dict[str, PrefillSegment]


@dataclasses.dataclass
class PrefillIterationResult:
    groups: List[PrefillGroupRun]
    finished: List[str]                     # rows whose LAST segment ran
    logits: Optional[jax.Array]             # (B_cap, V) when any finished
    peaks: Dict[str, int]                   # per-row peak resident KV tokens
                                            # of the CURRENT attention layer
                                            # (HBM watermark, token units;
                                            # recurrent layers hold no paged
                                            # KV and count 0)


@dataclasses.dataclass
class PrefillWalk:
    """Budget/progress state of ONE mixed-iteration walk over a plane
    (``begin_iteration`` -> ``run_layer`` per model layer ->
    ``finish_iteration``) — exactly what ``run_iteration``'s pass loop
    keeps in locals."""
    allow: Dict[str, int]
    ran: set = dataclasses.field(default_factory=set)
    finished: List[str] = dataclasses.field(default_factory=list)
    peaks: Dict[str, int] = dataclasses.field(default_factory=dict)
    groups: List[PrefillGroupRun] = dataclasses.field(default_factory=list)


class PrefillPlane:
    """Persistent padded prefill state for one group of batched requests.

    Requests whose whisper encoder KV shapes agree (the engine's prefill
    group key) share one plane.  The plane owns the rows' residual stream
    and recurrent states for the duration of prefill; the engine reads
    per-layer KV out of the context buffer (fused D2H saves + pool builds)
    and extracts recurrent states at finalize."""

    def __init__(self, cfg, policy: Optional[BucketingPolicy] = None,
                 plane_mesh=None):
        self.cfg = cfg
        self.policy = policy or BucketingPolicy()
        self.plane_mesh = plane_mesh
        self.fns = prefill_fns_for(cfg, plane_mesh)
        self.b_cap = 0
        self.s_cap = 0
        self.hidden: Optional[jax.Array] = None      # (B_cap, S_cap, d)
        self.ctx_k: Optional[jax.Array] = None       # (B_cap, S_cap, Hkv, hd)
        self.ctx_v: Optional[jax.Array] = None       # None for MLA
        self.rec: Optional[List[Any]] = None         # per model layer
        self.enc: Optional[List[Tuple[jax.Array, jax.Array]]] = None
        self._tok_len: Optional[jax.Array] = None    # (B_cap,) int32
        self.rows: Dict[str, int] = {}
        self.tok_len: Dict[str, int] = {}            # host mirror
        self.segments: Dict[str, List[PrefillSegment]] = {}
        self.next_idx: Dict[str, int] = {}
        self._free: List[int] = []
        self._ever_used: set = set()
        self._layer_params_cache: Optional[Tuple[Dict, List[Dict]]] = None
        # counters (bench_prefill / tests)
        self.admits = 0
        self.rows_reused = 0
        self.launches = 0                   # batched layer launches, total
        self.chunk_launches = 0             # launches with chunk_start > 0
        self.finalize_launches = 0
        self.iterations = 0
        self.buckets_seen: set = set()      # (b_cap, chunk_cap) launched at
        self.tracer = tracing.DEFAULT       # the engine hands its own

    # -- params ------------------------------------------------------------

    def _layer_params(self, params: Dict) -> List[Dict]:
        """Per-layer param slices, computed once per params object (same
        caching rationale as ``DevicePoolPlane._layer_params``)."""
        hit = self._layer_params_cache
        if hit is not None and hit[0] is params:
            return hit[1]
        layers = [M.get_layer(params, i) for i in range(self.cfg.num_layers)]
        self._layer_params_cache = (params, layers)
        return layers

    # -- capacity ----------------------------------------------------------

    def _place(self, tree):
        """New plane buffers start where the launches leave them:
        replicated on the mesh when there is one, so the first launch and
        every later one share a jit cache entry."""
        if self.plane_mesh is None:
            return tree
        return self.plane_mesh.replicate(tree)

    def _pad_rows(self, v, db):
        return jnp.pad(v, ((0, db),) + ((0, 0),) * (v.ndim - 1))

    def _pad_rows_tokens(self, v, db, ds):
        return jnp.pad(v, ((0, db), (0, ds)) + ((0, 0),) * (v.ndim - 2))

    def _ensure_capacity(self, need_rows: int, need_tokens: int,
                         template_h: jax.Array) -> None:
        b_cap = max(self.b_cap, self.policy.bucket_batch(need_rows))
        s_cap = max(self.s_cap, self.policy.bucket_tokens(need_tokens))
        if self.hidden is None:
            d = template_h.shape[-1]
            self.hidden = self._place(
                jnp.zeros((b_cap, s_cap, d), template_h.dtype))
            self._tok_len = self._place(jnp.zeros((b_cap,), jnp.int32))
            self.rec = self._place(
                M._init_rec_states(self.cfg, b_cap, template_h.dtype))
            self._free = list(range(b_cap))
        elif b_cap != self.b_cap or s_cap != self.s_cap:
            db, ds = b_cap - self.b_cap, s_cap - self.s_cap
            self.hidden = self._pad_rows_tokens(self.hidden, db, ds)
            self._tok_len = self._pad_rows(self._tok_len, db)
            if self.ctx_k is not None:
                self.ctx_k = self._pad_rows_tokens(self.ctx_k, db, ds)
            if self.ctx_v is not None:
                self.ctx_v = self._pad_rows_tokens(self.ctx_v, db, ds)
            self.rec = [None if s is None
                        else jax.tree.map(lambda x: self._pad_rows(x, db), s)
                        for s in self.rec]
            if self.enc is not None:
                self.enc = [tuple(self._pad_rows(a, db) for a in kv)
                            for kv in self.enc]
            for r in range(self.b_cap, b_cap):
                bisect.insort(self._free, r)
        self.b_cap, self.s_cap = b_cap, s_cap

    def _ensure_ctx(self, kv_tail_shapes: Tuple) -> None:
        """Lazily allocate the one-layer KV context buffer from the first
        launch's output shapes ((Hkv, hd) for GQA, (lat,) for MLA)."""
        if self.ctx_k is not None:
            return
        k_tail, v_tail = kv_tail_shapes
        self.ctx_k = self._place(
            jnp.zeros((self.b_cap, self.s_cap) + k_tail, jnp.float32))
        if v_tail is not None:
            self.ctx_v = self._place(
                jnp.zeros((self.b_cap, self.s_cap) + v_tail, jnp.float32))

    # -- slot lifecycle ----------------------------------------------------

    def admit(self, req_id: str, h: jax.Array,
              segments: List[PrefillSegment],
              enc_kvs: Optional[List[Tuple[jax.Array, jax.Array]]] = None
              ) -> int:
        """Admit one request: copy its embedded residual stream (1, S, d)
        into a free row, zero the row's recurrent states, and install its
        segment plan.  The only full-stream copy in the request's prefill
        lifetime."""
        with self.tracer.span("prefill.admit", req=req_id):
            if req_id in self.rows:
                raise ValueError(f"{req_id} already admitted")
            S = int(h.shape[1])
            self._ensure_capacity(len(self.rows) + 1, S, h)
            row = self._free.pop(0)
            if row in self._ever_used:
                self.rows_reused += 1
            self._ever_used.add(row)
            self.hidden = self.hidden.at[row].set(0).at[row, :S].set(h[0])
            self._tok_len = self._tok_len.at[row].set(S)
            for l, s in enumerate(self.rec):
                if s is not None:
                    self.rec[l] = jax.tree.map(lambda x: x.at[row].set(0), s)
            if enc_kvs is not None:
                if self.enc is None:
                    self.enc = [tuple(jnp.zeros((self.b_cap,) + a.shape[1:],
                                                a.dtype) for a in kv)
                                for kv in enc_kvs]
                self.enc = [tuple(dst.at[row].set(src[0])
                                  for dst, src in zip(self.enc[l], enc_kvs[l]))
                            for l in range(len(self.enc))]
            self.rows[req_id] = row
            self.tok_len[req_id] = S
            self.segments[req_id] = list(segments)
            self.next_idx[req_id] = 0
            self.admits += 1
            return row

    def release(self, req_id: str) -> int:
        """Free a finished request's row for reuse."""
        row = self.rows.pop(req_id)
        self.tok_len.pop(req_id)
        self.segments.pop(req_id)
        self.next_idx.pop(req_id)
        bisect.insort(self._free, row)
        return row

    def done(self, req_id: str) -> bool:
        return self.next_idx[req_id] >= len(self.segments[req_id])

    # -- iteration ---------------------------------------------------------

    def run_iteration(self, params: Dict, allowance: Dict[str, int],
                      group_cb=None) -> PrefillIterationResult:
        """Run one engine iteration's worth of prefill segments.

        allowance: per-request token budget for this iteration (within-layer
        token units — one chunk costs its chunk_len).  Every scheduled
        request runs AT LEAST one segment (progress guarantee, like the
        legacy executor's >=1 whole layer per iteration); beyond that,
        segments run while the budget lasts.  Each pass groups the rows'
        next segments by (layer, chunk_start) and runs each group as ONE
        jitted launch; a request's segments always execute in plan order.

        group_cb(group) runs right after each launch — the window in which
        the engine must read the group's KV out of the ONE-layer context
        buffer (fused FlashD2H save, end-of-layer pool build): the next
        layer's launch overwrites it.
        """
        allow = {rid: int(a) for rid, a in allowance.items()
                 if rid in self.rows}
        ran: set = set()
        finished: List[str] = []
        peaks: Dict[str, int] = {}
        groups: List[PrefillGroupRun] = []
        while True:
            pending: Dict[Tuple[int, int], List[str]] = {}
            for rid in sorted(allow, key=lambda r: self.rows[r]):
                idx = self.next_idx[rid]
                segs = self.segments[rid]
                if idx >= len(segs):
                    continue
                if allow[rid] <= 0 and rid in ran:
                    continue
                seg = segs[idx]
                pending.setdefault((seg.layer, seg.chunk_start),
                                   []).append(rid)
            if not pending:
                break
            for key in sorted(pending):
                layer, start = key
                rids = pending[key]
                g = self._run_group(params, layer, start, rids)
                groups.append(g)
                if group_cb is not None:
                    group_cb(g)
                for rid in rids:
                    seg = g.segs[rid]
                    allow[rid] -= seg.chunk_len
                    ran.add(rid)
                    self.next_idx[rid] += 1
                    if g.kind == "attn":
                        # only attention layers hold paged KV; recurrent
                        # segments contribute nothing to the watermark
                        peaks[rid] = max(peaks.get(rid, 0),
                                         seg.chunk_start + seg.chunk_len)
                    if seg.is_last:
                        finished.append(rid)
        # idle resident rows still hold their partially-built layer's KV
        for rid, resident in self.resident_tokens().items():
            peaks[rid] = max(peaks.get(rid, 0), resident)
        logits = None
        if finished:
            logits = self.fns.finalize(params, self.hidden, self._tok_len)
            self.finalize_launches += 1
        self.iterations += 1
        return PrefillIterationResult(groups=groups, finished=finished,
                                      logits=logits, peaks=peaks)

    # -- mixed-iteration walk (core.hybrid_plane) --------------------------

    def begin_iteration(self, allowance: Dict[str, int]) -> "PrefillWalk":
        """Open a mixed-iteration walk over this plane's rows.  The hybrid
        driver (``core.hybrid_plane``) visits model layers 0..L-1 ONCE per
        engine iteration, calling ``run_layer`` at each; the walk carries
        the same per-request budget/progress state ``run_iteration``'s pass
        loop keeps, so both schemes execute the identical segment set."""
        return PrefillWalk(allow={rid: int(a) for rid, a in allowance.items()
                                  if rid in self.rows})

    def run_layer(self, params: Dict, layer: int,
                  walk: "PrefillWalk") -> List[PrefillGroupRun]:
        """Run every segment the walk owes at ``layer``: rows whose NEXT
        segment sits at this layer are grouped by chunk_start (one jitted
        bucketed launch per group, same as ``run_iteration``) and chunks
        execute in plan order until no scheduled row is pending here.
        Because ``plan_segments`` emits segments in non-decreasing layer
        order, exhausting each layer in the ascending walk executes exactly
        the segments ``run_iteration``'s multi-pass loop would."""
        out: List[PrefillGroupRun] = []
        while True:
            pending: Dict[int, List[str]] = {}
            for rid in sorted(walk.allow, key=lambda r: self.rows[r]):
                idx = self.next_idx[rid]
                segs = self.segments[rid]
                if idx >= len(segs):
                    continue
                if walk.allow[rid] <= 0 and rid in walk.ran:
                    continue
                seg = segs[idx]
                if seg.layer != layer:
                    continue
                pending.setdefault(seg.chunk_start, []).append(rid)
            if not pending:
                break
            for start in sorted(pending):
                rids = pending[start]
                g = self._run_group(params, layer, start, rids)
                out.append(g)
                walk.groups.append(g)
                for rid in rids:
                    seg = g.segs[rid]
                    walk.allow[rid] -= seg.chunk_len
                    walk.ran.add(rid)
                    self.next_idx[rid] += 1
                    if g.kind == "attn":
                        walk.peaks[rid] = max(walk.peaks.get(rid, 0),
                                              seg.chunk_start + seg.chunk_len)
                    if seg.is_last:
                        walk.finished.append(rid)
        return out

    def finish_iteration(self, params: Dict,
                         walk: "PrefillWalk") -> PrefillIterationResult:
        """Close a mixed-iteration walk: book idle residency into the
        peaks, run the shared finalize (logits) launch for rows whose last
        segment ran, and bump the iteration counter — the same epilogue
        ``run_iteration`` performs after its pass loop."""
        for rid, resident in self.resident_tokens().items():
            walk.peaks[rid] = max(walk.peaks.get(rid, 0), resident)
        logits = None
        if walk.finished:
            logits = self.fns.finalize(params, self.hidden, self._tok_len)
            self.finalize_launches += 1
        self.iterations += 1
        return PrefillIterationResult(groups=walk.groups,
                                      finished=walk.finished,
                                      logits=logits, peaks=walk.peaks)

    def _run_group(self, params: Dict, layer: int, start: int,
                   rids: List[str]) -> PrefillGroupRun:
        with self.tracer.span("prefill.group", layer=layer,
                              chunk_start=start, rows=len(rids)):
            cfg = self.cfg
            kind = M.layer_kind(cfg, layer)
            segs = {rid: self.segments[rid][self.next_idx[rid]]
                    for rid in rids}
            t_cap = min(self.policy.bucket_tokens(
                max(s.chunk_len for s in segs.values())), self.s_cap - start)
            smask = np.zeros((self.b_cap,), bool)
            tmask = np.zeros((self.b_cap, t_cap), bool)
            for rid in rids:
                row = self.rows[rid]
                smask[row] = True
                tmask[row, :segs[rid].chunk_len] = True
            smask_j = jnp.asarray(smask)
            tmask_j = jnp.asarray(tmask)
            h_win = self.hidden[:, start:start + t_cap]
            p_l = self._layer_params(params)[layer]
            if kind == "attn":
                pos_win = jnp.broadcast_to(
                    jnp.arange(start, start + t_cap, dtype=jnp.int32),
                    (self.b_cap, t_cap))
                ctx = None
                if start > 0:
                    if cfg.attention_type == "mla":
                        raise NotImplementedError(
                            "chunked layer segments are not supported for MLA "
                            "models (no latent-context attention path); plan "
                            "whole-layer segments")
                    ctx = (self.ctx_k[:, :start], self.ctx_v[:, :start])
                enc = self.enc[layer] if self.enc is not None else None
                h_out, kv_out = self.fns.attn(
                    p_l, h_win, pos_win, tmask_j, smask_j, ctx, enc,
                    jnp.asarray(start, jnp.int32))
                rows_arr = jnp.asarray([self.rows[r] for r in rids], jnp.int32)
                if cfg.attention_type == "mla":
                    (latent,) = kv_out
                    self._ensure_ctx((latent.shape[2:], None))
                    self.ctx_k = self.ctx_k.at[
                        rows_arr, start:start + t_cap].set(
                        latent[rows_arr].astype(self.ctx_k.dtype))
                else:
                    k, v = kv_out
                    self._ensure_ctx((k.shape[2:], v.shape[2:]))
                    self.ctx_k = self.ctx_k.at[
                        rows_arr, start:start + t_cap].set(
                        k[rows_arr].astype(self.ctx_k.dtype))
                    self.ctx_v = self.ctx_v.at[
                        rows_arr, start:start + t_cap].set(
                        v[rows_arr].astype(self.ctx_v.dtype))
            else:
                h_out, new_state = self.fns.rec[kind](
                    p_l, h_win, tmask_j, smask_j, self.rec[layer])
                self.rec[layer] = new_state
            self.hidden = self.hidden.at[:, start:start + t_cap].set(h_out)
            self.launches += 1
            if start > 0:
                self.chunk_launches += 1
            self.buckets_seen.add((self.b_cap, t_cap))
            return PrefillGroupRun(layer=layer, kind=kind, chunk_start=start,
                                   chunk_cap=t_cap, req_ids=list(rids),
                                   segs=segs)

    def resident_tokens(self) -> Dict[str, int]:
        """Per-row tokens of CURRENT-layer attention KV held right now —
        the residency a row carries BETWEEN iterations (mid-layer chunk
        progress).  A row whose next segment is chunk c of attention layer
        l holds chunks 0..c-1 (= chunk_start tokens) in the one-layer ctx
        buffer; a row parked before a recurrent layer (or before chunk 0)
        holds nothing — the previous layer was already saved and evicted.
        The engine sums this over every admitted row of every plane (also
        the ones with no scheduled request this iteration) for the batched
        HBM watermark."""
        out: Dict[str, int] = {}
        for rid in self.rows:
            idx = self.next_idx[rid]
            segs = self.segments[rid]
            resident = 0
            if idx < len(segs):
                seg = segs[idx]
                if M.layer_kind(self.cfg, seg.layer) == "attn":
                    resident = seg.chunk_start
            out[rid] = resident
        return out

    # -- data plane readbacks ---------------------------------------------

    def read_group_kv(self, g: PrefillGroupRun
                      ) -> Dict[str, Tuple[np.ndarray,
                                           Optional[np.ndarray]]]:
        """Read the KV stripes a batched ATTENTION launch just produced —
        the FlashD2H phase-1 source: ONE fused device->host readback per
        group, covering every request in the launch.  Returns
        {req_id: (k (Hkv, T, D), v | None)} trimmed to each row's real
        chunk length (MLA: the single latent head)."""
        rows = jnp.asarray([self.rows[r] for r in g.req_ids], jnp.int32)
        sl = slice(g.chunk_start, g.chunk_start + g.chunk_cap)
        k_all = np.asarray(self.ctx_k[rows, sl])
        v_all = (np.asarray(self.ctx_v[rows, sl])
                 if self.ctx_v is not None else None)
        out: Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]] = {}
        for i, rid in enumerate(g.req_ids):
            clen = g.segs[rid].chunk_len
            if k_all.ndim == 3:                    # MLA latent: (R, T, lat)
                k = k_all[i, :clen][None, :, :]    # -> (1, T, lat)
                v = None
            else:                                  # (R, T, Hkv, hd)
                k = np.transpose(k_all[i, :clen], (1, 0, 2))
                v = np.transpose(v_all[i, :clen], (1, 0, 2))
            out[rid] = (k, v)
        return out

    def read_group_kv_async(self, g: PrefillGroupRun):
        """Dispatch the group's fused KV stripe gather WITHOUT a host sync
        and return a zero-arg *finisher*.  The gather (a queued device op
        on value-snapshotted ctx buffers) starts immediately; calling the
        finisher — on the ``HostStageWorker`` — pays the blocking
        ``np.asarray`` plus the per-request trim/transpose and returns
        exactly what ``read_group_kv`` would have."""
        with self.tracer.span("prefill.kv_readback", layer=g.layer):
            rows = jnp.asarray([self.rows[r] for r in g.req_ids], jnp.int32)
            sl = slice(g.chunk_start, g.chunk_start + g.chunk_cap)
            k_dev = self.ctx_k[rows, sl]
            v_dev = self.ctx_v[rows, sl] if self.ctx_v is not None else None
            req_ids = list(g.req_ids)
        chunk_lens = {rid: g.segs[rid].chunk_len for rid in req_ids}

        def finish() -> Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]:
            k_all = np.asarray(k_dev)
            v_all = None if v_dev is None else np.asarray(v_dev)
            out: Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]] = {}
            for i, rid in enumerate(req_ids):
                clen = chunk_lens[rid]
                if k_all.ndim == 3:                # MLA latent: (R, T, lat)
                    k = k_all[i, :clen][None, :, :]
                    v = None
                else:                              # (R, T, Hkv, hd)
                    k = np.transpose(k_all[i, :clen], (1, 0, 2))
                    v = np.transpose(v_all[i, :clen], (1, 0, 2))
                out[rid] = (k, v)
            return out
        return finish

    def layer_ctx(self, req_id: str) -> Tuple:
        """The request's completed CURRENT-layer KV (kv_out form, B=1) —
        what the engine turns into the layer's paged decode pool at the end
        of the layer.  GQA: (k, v) each (1, S, Hkv, hd); MLA: (latent,)."""
        row = self.rows[req_id]
        S = self.tok_len[req_id]
        if self.ctx_v is None:
            return (self.ctx_k[row:row + 1, :S],)
        return (self.ctx_k[row:row + 1, :S], self.ctx_v[row:row + 1, :S])

    def rec_state(self, req_id: str, layer: int):
        """One row's layer recurrent state (B=1) — decode-state assembly at
        finalize."""
        row = self.rows[req_id]
        return jax.tree.map(lambda x: x[row:row + 1], self.rec[layer])

    def device_bytes(self) -> int:
        leaves = [self.hidden, self.ctx_k, self.ctx_v, self._tok_len]
        if self.rec is not None:
            leaves += jax.tree.leaves(self.rec)
        if self.enc is not None:
            leaves += jax.tree.leaves(self.enc)
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in leaves if leaf is not None)
