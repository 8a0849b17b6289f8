"""Real-execution serving engine (runs actual tiny models on CPU-JAX).

This is the system the paper builds: a vLLM-style continuous-batching engine
with

  * dynamic sparse attention decode (select-then-compute, §2.2) executed as
    a STAGED per-layer pipeline over a PERSISTENT shared device pool
    (``repro.core.device_pool.DevicePoolPlane``): requests are admitted into
    padded pool rows once, each attention layer steps through jit-compiled
    bucketed select -> [host restore] -> attend stages (one compile per
    stage per shape bucket, zero per-iteration stack/unstack copies, O(L)
    launches per iteration), and rows are released when requests finish so
    later admissions reuse their slots.  Because a layer's fused FlashH2D
    restores land BETWEEN its DSA selection and its attention, HBM-evicted
    blocks can be physically dropped from the device pool without changing
    outputs (``drop_evicted_device_blocks`` defaults ON here — the paper's
    §3.2 overlap story, end to end).  ``decode_plane="persistent"`` keeps
    the fused one-launch forward over the same plane and
    ``decode_plane="stacked"`` the legacy pad+concat-every-iteration path,
    both as greedy-equivalence oracles; ``batched_decode=False`` is the
    per-request loop,
  * a hierarchical HBM–DRAM KV manager with per-request LRU HBM caches and
    host pools (§3.1 / §3.2 — FlashH2D/D2H accounting on every transfer;
    decode misses load through ONE fused FlashH2D launch per layer per
    iteration whose payloads scatter DIRECTLY into the device plane's
    slots; newly generated KV writes back to DRAM with one fused FlashD2H
    save per layer per iteration),
  * working-set-aware batch size control (Algorithm 1, §3.3),
  * layer-segmented OR chunked prefill (§3.4 vs the baseline).  Layer-
    segmented prefill runs on a batched jitted **PrefillPlane** by default
    (``repro.core.prefill_plane``): requests are admitted once into padded
    plane rows carrying their residual stream, every iteration batches all
    same-(layer, chunk) segments of the prefill batch into ONE jitted
    bucketed launch (token-length + batch buckets, ``step_mask`` parks
    unscheduled rows), each group's KV is saved to DRAM with ONE fused
    FlashD2H call, and the prefill HBM footprint stays bounded by one
    layer of KV for the WHOLE batch.  Chunked intra-layer segments
    (``prefill_max_tokens_per_step``) are executed natively.  The
    per-request whole-layer loop survives as ``prefill_exec="legacy"``,
    the equivalence oracle.  Hybrid iterations interleave plane prefill
    launches with the staged decode plane under the shared HBM budget.

See docs/architecture.md for the decode data plane and the prefill plane
end-to-end.

The CONTROL PLANE is fully real (scheduling, admission, caching, transfer
accounting, prefill segmentation); the MODEL COMPUTE is fully real (actual
forward passes, actual DSA block selections feeding the working-set
estimator).  The engine clock is decided by the platform: on an
accelerator each iteration is charged its measured wall time, ending once
the device has finished the iteration's work; on CPU it is charged from
the analytic cost model, because CPU wall time measures the wrong machine.
``charge_real_time`` overrides the choice (tests use wall clock on CPU for
relative comparisons).

The engine is what `examples/serve_longcontext.py` and the Fig. 8 / Fig. 16
benchmarks drive.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dsa as dsa_mod
from repro.core.device_pool import BucketingPolicy, DevicePoolPlane
from repro.core.host_stage import HostStageWorker
from repro.core.hybrid_plane import (DecodeJob, HybridPlane, LayerWindow,
                                     PrefillJob)
from repro.core.kv_cache import KVCacheManager, KVGeometry, TransferStats
from repro.core.layer_prefill import (LayerPrefillState, hbm_footprint_tokens,
                                      plan_segments)
from repro.core.prefill_plane import PrefillPlane, admit_embed_fns_for
from repro.core.scheduler import BatchPlan, Scheduler, SchedulerConfig
from repro.launch.plane_mesh import PlaneMesh
from repro.models import model as M
from repro.models.common import ModelConfig
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracing import Tracer
from repro.serving import costmodel as cm
from repro.serving.metrics import ServingMetrics, compute_metrics
from repro.serving.request import Phase, Request


@dataclasses.dataclass
class EngineConfig:
    prefill_mode: str = "layer_segmented"    # "chunked" | "layer_segmented"
    prefill_exec: str = "plane"              # layer-segmented executor:
                                             # "plane" (default): batched
                                             # jitted PrefillPlane — one
                                             # bucketed launch per (layer,
                                             # chunk) group per iteration,
                                             # one fused FlashD2H save per
                                             # group; "legacy": the
                                             # per-request whole-layer loop
                                             # (equivalence oracle).
    prefill_max_tokens_per_step: int = 0     # intra-layer chunk size for the
                                             # prefill plane's segments
                                             # (plan_segments granularity;
                                             # 0 = whole layers, the TBT-SLO
                                             # hybrid of §3.4 off).  MLA
                                             # models always run whole
                                             # layers (no latent-context
                                             # attention path).
    chunk_size: int = 2048
    max_inject_tokens: int = 0               # 0 -> chunk_size * L (paper §4.2)
    r_max: int = 8
    t_max: int = 8192
    ws_control: bool = True
    hbm_budget_bytes: int = 1 << 30          # HBM KV-cache budget (M_avl)
    hbm_blocks_per_request: int = 96         # per-request LRU capacity
    attn_impl: str = "ref"                   # "ref" | "kernel"
    charge_real_time: Optional[bool] = None  # None: measured wall time on
                                             # an accelerator, cost model
                                             # on CPU (resolved per engine)
    greedy: bool = True
    seed: int = 0
    batched_decode: bool = True              # ONE decode_step per iteration
                                             # (False: legacy B=1 loop)
    decode_plane: str = "staged"             # "staged" (default): per-layer
                                             # select -> restore -> attend
                                             # pipeline over a
                                             # DevicePoolPlane — H2D
                                             # restores land BEFORE the
                                             # attention that selected them;
                                             # "persistent": the fused
                                             # one-launch forward over the
                                             # same plane; "stacked": legacy
                                             # pad+concat every iteration.
                                             # All three are greedy-token
                                             # equivalent oracles of each
                                             # other (without block drops).
    bucketing: BucketingPolicy = dataclasses.field(
        default_factory=BucketingPolicy)     # device-plane shape buckets
    decode_write_back: bool = True           # FlashD2H: save newly generated
                                             # KV to the host pool each
                                             # iteration (one fused d2h call
                                             # per layer), keeping DRAM a
                                             # superset of device KV
    mesh_spec: Any = None                    # context-parallel plane mesh:
                                             # None (single-device planes),
                                             # "model=K" / int K (local mesh
                                             # with a K-way model axis), a
                                             # jax Mesh, or a PlaneMesh —
                                             # resolved once per engine via
                                             # PlaneMesh.resolve.  Shards
                                             # the staged decode plane's
                                             # pool slots (KV-head- or
                                             # block-mode) and the prefill
                                             # plane's token windows across
                                             # the model axis; requires
                                             # decode_plane="staged" and
                                             # DSA enabled.
    hybrid_plane: str = "mixed"              # "mixed" (default): ONE
                                             # layer-walk iteration carries
                                             # decode rows AND prefill
                                             # segments together
                                             # (core.hybrid_plane) — a
                                             # single per-layer host stage
                                             # fuses both planes' FlashD2H
                                             # and FlashH2D; "split": the
                                             # two-plane path (prefill
                                             # plane, then decode planes),
                                             # kept as the equivalence
                                             # oracle.  Configs the mixed
                                             # walk cannot drive (legacy /
                                             # chunked prefill, non-staged
                                             # or unbatched decode) resolve
                                             # to "split" automatically.
    stage_dispatch: str = "async"            # "async" (default): the
                                             # per-layer host stage hands
                                             # the FlashD2H write-back to a
                                             # HostStageWorker thread and
                                             # never blocks the dispatch
                                             # thread on the device beyond
                                             # np.asarray(selected ids) —
                                             # attend(l) / select(l+1)
                                             # dispatch while layer l's
                                             # stripe conversion + DRAM
                                             # staging run off-thread,
                                             # fenced before any gather of
                                             # the same layer and drained
                                             # before sampling; "sync": the
                                             # fully blocking host stage,
                                             # kept as the equivalence
                                             # oracle (async must be
                                             # greedy-token-identical).
                                             # See docs/architecture.md §10.
    drop_evicted_device_blocks: Optional[bool] = None
    # True: HBM-evicted blocks are physically zeroed on device and restored
    # from the host pool via the fused H2D gather when re-selected.  On the
    # STAGED plane the restore lands between a layer's select and attend
    # stages — before use — so the physical drop is oracle-exact and the
    # knob defaults ON (None -> resolved to decode_plane == "staged").  On
    # the fused "persistent" plane a restore can only land AFTER the forward
    # that re-selected the block, so the forward reads zeros under eviction
    # pressure and outputs diverge — supported for demonstration, default
    # off.  See docs/architecture.md §3.
    offload_quant: str = "none"
    # DRAM offload tier storage format: "none" (default — host pools store
    # fp blocks; every greedy-equivalence oracle runs here) | "int8"
    # (pools store symmetric int8 with one f32 scale per (layer, kv-head,
    # block) per tensor; blocks quantize on the FlashD2H save path and
    # dequantize on the FlashH2D restore path, so D2H+H2D wire bytes —
    # TransferStats, obs spans, and the cost model's per-layer transfer
    # charges — shrink ~dtype_bytes x while decode output stays within the
    # bench_accuracy cosine bound).  See docs/architecture.md §12.


@dataclasses.dataclass
class _ReqState:
    """Engine-side state for one request."""
    req: Request
    tokens: np.ndarray                              # prompt token ids
    inputs_extra: Dict[str, Any]                    # frames / patch_embeds
    decode_state: Optional[Dict] = None             # model DecodeState (B=1;
                                                    # stacked per iteration)
    lp: Optional[LayerPrefillState] = None          # layer-segmented cursor
                                                    # (legacy executor)
    prefill_carry: int = 0                          # plane executor: unspent
                                                    # token-layer budget
                                                    # carried across iters
    chunk_ctx: Optional[List] = None                # chunked: per-layer kv ctx
    chunk_rec: Optional[List] = None                # chunked: recurrent states
    last_logits: Optional[jax.Array] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    num_blocks: int = 0
    group_key: Optional[Tuple] = None               # batched-decode grouping
                                                    # (cached before the plane
                                                    # takes state ownership)


class ServingEngine:
    """Continuous-batching engine over real model forwards."""

    def __init__(self, params: Dict, cfg: ModelConfig, eng: EngineConfig,
                 hw: cm.HardwareSpec = cm.TPU_V5E):
        self.params = params
        self.cfg = cfg
        self.eng = eng
        self.hw = hw
        if eng.decode_plane not in ("staged", "persistent", "stacked"):
            raise ValueError(f"unknown decode_plane {eng.decode_plane!r}; "
                             f"expected 'staged', 'persistent' or 'stacked'")
        if eng.prefill_exec not in ("plane", "legacy"):
            raise ValueError(f"unknown prefill_exec {eng.prefill_exec!r}; "
                             f"expected 'plane' or 'legacy'")
        self.plane_mesh = PlaneMesh.resolve(eng.mesh_spec)
        if self.plane_mesh is not None:
            if not (eng.batched_decode and eng.decode_plane == "staged"):
                raise ValueError(
                    "mesh_spec shards the STAGED decode plane: it requires "
                    "batched_decode=True and decode_plane='staged'")
            if not cfg.dsa.enabled:
                raise ValueError(
                    "mesh_spec requires DSA (cfg.dsa.enabled): the sharded "
                    "attend stage has no dense fallback")
            if eng.attn_impl != "ref":
                raise ValueError(
                    "mesh_spec requires attn_impl='ref': the sharded "
                    "attend stage runs the reference block-sparse "
                    "attention inside shard_map (no Pallas-kernel path)")
        if eng.hybrid_plane not in ("mixed", "split"):
            raise ValueError(f"unknown hybrid_plane {eng.hybrid_plane!r}; "
                             f"expected 'mixed' or 'split'")
        if eng.stage_dispatch not in ("async", "sync"):
            raise ValueError(f"unknown stage_dispatch "
                             f"{eng.stage_dispatch!r}; "
                             f"expected 'async' or 'sync'")
        if eng.offload_quant not in ("none", "int8"):
            raise ValueError(f"unknown offload_quant "
                             f"{eng.offload_quant!r}; "
                             f"expected 'none' or 'int8'")
        if eng.hybrid_plane == "mixed" and not (
                eng.batched_decode and eng.decode_plane == "staged"
                and eng.prefill_mode == "layer_segmented"
                and eng.prefill_exec == "plane"):
            # the mixed walk drives exactly the staged decode plane and
            # the batched prefill plane; every other executor combination
            # falls back to the split two-plane path.  Resolve into a COPY
            # (same rationale as drop_evicted_device_blocks below).
            eng = dataclasses.replace(eng, hybrid_plane="split")
            self.eng = eng
        if eng.prefill_mode == "chunked" and cfg.attention_type == "mla":
            # the chunked baseline carries dense (k, v) context between
            # chunks; MLA's latent cache has no chunked-context path yet
            raise NotImplementedError(
                "chunked prefill does not support MLA models; use "
                "prefill_mode='layer_segmented'")
        if eng.drop_evicted_device_blocks is None:
            # the staged plane restores evicted blocks BEFORE the attention
            # that re-selects them, so the physical drop is oracle-exact
            # there and on by default; everywhere else it would change
            # outputs (or has no device plane to act on).  Resolve into a
            # COPY — mutating the caller's config would leak the resolved
            # value into configs reused for other planes.
            eng = dataclasses.replace(eng, drop_evicted_device_blocks=(
                eng.decode_plane == "staged" and eng.batched_decode
                and eng.decode_write_back))
            self.eng = eng
        if eng.drop_evicted_device_blocks and not eng.decode_write_back:
            raise ValueError(
                "drop_evicted_device_blocks requires decode_write_back: "
                "restores come from the host pool, which is only a superset "
                "of device KV when decode write-back is on")
        if eng.drop_evicted_device_blocks and not (
                eng.batched_decode
                and eng.decode_plane in ("staged", "persistent")):
            raise ValueError(
                "drop_evicted_device_blocks only acts on a device plane "
                "(batched_decode=True, decode_plane='staged' or "
                "'persistent')")
        if eng.charge_real_time is None:
            # the clock follows the platform; resolve into a COPY (same
            # rationale as the knobs above)
            eng = dataclasses.replace(
                eng, charge_real_time=jax.default_backend() != "cpu")
            self.eng = eng
        # spans on the profiler's clock, always on (src/repro/obs/); the
        # counted ones feed this registry
        self.metrics = MetricsRegistry()
        self.tracer = Tracer(self.metrics)
        self.mc = cm.ModelCost.from_config(cfg)
        self.rng = np.random.default_rng(eng.seed)

        L_attn = max(cfg.num_attention_layers(), 1)
        self.geom = KVGeometry(
            num_layers=L_attn, num_kv_heads=max(cfg.num_kv_heads, 1),
            block_size=cfg.dsa.block_size, head_dim=cfg.kv_cache_dim,
            kv_factor=1 if cfg.attention_type == "mla" else 2)
        inject = (eng.max_inject_tokens if eng.max_inject_tokens > 0
                  else eng.chunk_size * cfg.num_layers)
        seg_tokens = (eng.prefill_max_tokens_per_step
                      if (eng.prefill_mode == "layer_segmented"
                          and eng.prefill_exec == "plane"
                          and cfg.attention_type != "mla") else 0)
        self.scheduler = Scheduler(
            SchedulerConfig(
                r_max=eng.r_max, t_max=eng.t_max,
                m_avl_bytes=eng.hbm_budget_bytes if eng.ws_control else 0,
                prefill_mode=eng.prefill_mode, chunk_size=eng.chunk_size,
                max_inject_tokens=inject, segment_tokens=seg_tokens,
                ws_control=eng.ws_control),
            self.geom, cfg.num_layers, cfg.dsa.top_k_blocks)
        self.kv_mgr = KVCacheManager(self.geom, eng.hbm_budget_bytes,
                                     offload_quant=eng.offload_quant)
        self.kv_mgr.tracer = self.tracer
        self.scheduler.tracer = self.tracer
        # wire bytes of one (layer, block) transfer at the offload tier's
        # STORED size — what the cost model charges per moved block (int8
        # payload + scales under offload_quant="int8"; the modeled bf16
        # size otherwise)
        self._offload_block_bytes = cm.offload_block_bytes(
            self.geom.num_kv_heads, self.geom.head_dim,
            self.geom.block_size, kv_factor=self.geom.kv_factor,
            dtype_bytes=self.geom.dtype_bytes, quant=eng.offload_quant)
        self.states: Dict[str, _ReqState] = {}
        self._pending: List[Request] = []      # not yet arrived
        self.now = 0.0
        self.iterations = 0
        self.loads_per_iter: List[int] = []
        self.prefill_hbm_peak_tokens: int = 0    # Fig. 16a rationale metric
        self.decode_step_calls = 0               # model forwards (decode)
        self.decode_tokens = 0                   # tokens those calls produced
        self.stack_calls = 0                     # full-pool stack/unstack
                                                 # round-trips (0 on the
                                                 # persistent plane)
        self.planes: Dict[Tuple, DevicePoolPlane] = {}   # group_key -> plane
        self._req_plane: Dict[str, DevicePoolPlane] = {}
        self.prefill_planes: Dict[Tuple, PrefillPlane] = {}
        self._req_prefill_plane: Dict[str, PrefillPlane] = {}
        self.prefill_launches = 0                # batched plane launches
        self.admit_embed_launches = 0            # batched admission embeds
        self.hybrid = (HybridPlane(cfg)
                       if eng.hybrid_plane == "mixed" else None)
        if self.hybrid is not None:
            self.hybrid.tracer = self.tracer
        # async dispatch pipeline (stage_dispatch="async", the default):
        # per-layer FlashD2H write-back staging runs on this worker so the
        # dispatch thread's only per-layer device block is np.asarray(idx)
        self._stage_async = eng.stage_dispatch == "async"
        self._worker: Optional[HostStageWorker] = None
        self.worker_jobs_run = 0      # folded in from retired workers at
        self.worker_busy_s = 0.0      # close() so stats survive run()
        # per-iteration scheduler/batch gauges (memoized instruments:
        # one .set() per iteration, no name lookups on the hot path)
        _m = self.metrics
        self._g_queue = _m.gauge(
            "sched.queue_depth", "requests waiting for admission")
        self._g_running = _m.gauge(
            "sched.running", "requests admitted (prefill+decode)")
        self._g_batch_decode = _m.gauge(
            "sched.batch_decode_rows", "decode rows this iteration")
        self._g_batch_prefill = _m.gauge(
            "sched.batch_prefill_rows", "prefill rows this iteration")
        self._g_ws_decode = _m.gauge(
            "sched.ws_decode_bytes", "estimated decode working set")
        self._g_ws_prefill = _m.gauge(
            "sched.ws_prefill_bytes", "estimated prefill working set")
        self._g_hbm_used = _m.gauge(
            "kv.hbm_used_bytes", "actual HBM residency after the iteration")
        self._h_iter = _m.histogram(
            "engine.iteration_s", "seconds per engine iteration (the "
            "engine.step span)")
        self.mixed_iter_log: List[Dict[str, Any]] = []
        # per mixed iteration: per-layer fused d2h/h2d call counts, group
        # counts and the measured jitted-launch total — what
        # tests/planeasserts.assert_mixed_launch_invariant checks against
        # plane_contract.mixed_launches_per_iteration
        self._staged_layer_bytes: Dict[int, int] = {}    # model layer ->
                                                         # H2D restore bytes
                                                         # this iteration
                                                         # (staged charging)
        self.staged_probe = None   # test hook: called between a layer's
                                   # restore and attend as probe(engine,
                                   # plane, layer, sts, blocks_by_req) —
                                   # the restore-ordering window
        # model layer -> attn-layer ordinal (hot path: per layer per decode
        # iteration) and its inverse (maps HBMCache eviction keys back to
        # plane cache indices), both precomputed once
        self._layer_to_lidx: Dict[int, int] = {}
        self._lidx_to_layer: Dict[int, int] = {}
        n = 0
        for i in range(cfg.num_layers):
            lidx = min(n, self.geom.num_layers - 1)
            self._layer_to_lidx[i] = lidx
            if M.layer_kind(cfg, i) == "attn":
                self._lidx_to_layer.setdefault(lidx, i)
                n += 1

    # ------------------------------------------------------------------
    # Request intake
    # ------------------------------------------------------------------
    def submit(self, req: Request, tokens: Optional[np.ndarray] = None,
               **inputs_extra) -> None:
        """Register a request with the engine (it joins the scheduler queue
        at ``req.arrival_time``, in engine-clock SECONDS).

        tokens: prompt token ids, length ``req.prompt_len`` (random ids are
        drawn when omitted).  inputs_extra: frontend tensors (``frames`` for
        whisper, ``patch_embeds`` for VLMs), leading batch axis 1.

        Capacity contract: the KV manager registers a host pool sized for
        ``prompt_len + max_new_tokens`` (+ patches) TOKENS — every later
        stage (FlashD2H staging, fused gathers, device-plane restores)
        bounds-checks block ranges against that registration, so exceeding
        it raises instead of corrupting pool state."""
        if tokens is None:
            tokens = self.rng.integers(
                4, self.cfg.vocab_size, size=req.prompt_len).astype(np.int32)
        assert len(tokens) == req.prompt_len
        st = _ReqState(req=req, tokens=np.asarray(tokens, np.int32),
                       inputs_extra=dict(inputs_extra))
        total = req.prompt_len + req.max_new_tokens
        if self.cfg.frontend == "vit_patch_stub":
            total += self.cfg.num_patches
        st.num_blocks = -(-total // self.cfg.dsa.block_size) + 1
        self.states[req.req_id] = st
        self._pending.append(st.req)
        self._pending.sort(key=lambda r: r.arrival_time)
        self.kv_mgr.register(req.req_id, total, self.eng.hbm_blocks_per_request)
        if self.eng.drop_evicted_device_blocks:
            self.kv_mgr.caches[req.req_id].track_evictions = True

    def _admit_arrivals(self) -> None:
        while self._pending and self._pending[0].arrival_time <= self.now:
            self.scheduler.add_request(self._pending.pop(0))

    # ------------------------------------------------------------------
    # Prefill execution
    # ------------------------------------------------------------------
    def _model_inputs(self, st: _ReqState) -> Dict[str, Any]:
        d = {"tokens": jnp.asarray(st.tokens[None, :])}
        d.update({k: jnp.asarray(v) for k, v in st.inputs_extra.items()})
        return d

    def _start_layer_segmented(self, st: _ReqState, tokens_per_step: int):
        h, positions, enc_kvs = M.prefill_embed(
            self.params, self.cfg, self._model_inputs(st))
        segs = plan_segments(st.req.prompt_len, self.cfg.num_layers,
                             tokens_per_step)
        st.lp = LayerPrefillState(segments=segs, hidden=h,
                                  positions=positions, enc_kvs=enc_kvs,
                                  rec_states=M._init_rec_states(
                                      self.cfg, 1, h.dtype))
        # decode-state extra keeps enc_kvs in per-layer LIST form so every
        # leaf's axis 0 is the batch axis (stacked form leads with L, which
        # would break the batched-decode concat)
        enc_list = ([M.index_enc_kvs(enc_kvs, i)
                     for i in range(self.cfg.num_layers)]
                    if enc_kvs is not None else None)
        st.decode_state = {"caches": [None] * self.cfg.num_layers,
                           "cur_len": None,
                           "extra": ({"enc_kvs": enc_list} if enc_list
                                     else {})}

    def _run_layer_segment(self, st: _ReqState) -> bool:
        """Execute the next layer segment.  Returns True when prefill done.

        One segment = one whole layer over the whole prompt (the
        chunk-hybridised variant splits within a layer; we execute whole
        layers here because the residual-carry makes intra-layer chunks of
        *different* layers equivalent work — the scheduler already
        charges token work per segment)."""
        cfg = self.cfg
        seg = st.lp.advance()
        l = seg.layer
        enc_kv = M.index_enc_kvs(st.lp.enc_kvs, l)
        h, kv_out, new_rec = M.prefill_layer(
            self.params, cfg, l, st.lp.hidden, st.lp.positions,
            rec_state=st.lp.rec_states[l], enc_kv=enc_kv,
            moe_drop_free=True)
        st.lp.hidden = h
        st.lp.rec_states[l] = new_rec

        # FlashD2H: save this layer's KV contiguously to the host pool, then
        # evict from HBM — the paper's one-layer HBM bound.
        if kv_out is not None:
            pool_kv, meta = self._kv_to_layer_cache(st, kv_out)
            st.decode_state["caches"][l] = pool_kv
            host = self.kv_mgr.pools.get(st.req.req_id)
            cache = self.kv_mgr.caches.get(st.req.req_id)
            if host is not None:
                k_arr = np.asarray(kv_out[0][0], np.float32)   # (S,Hkv,D)
                if k_arr.ndim == 2:            # MLA latent: (S, lat) -> 1 head
                    k_arr = k_arr[:, None, :]
                lidx = self._attn_layer_index(l)
                v_arr = None
                if len(kv_out) > 1:
                    v_arr = np.transpose(
                        np.asarray(kv_out[1][0], np.float32), (1, 0, 2))
                # plane-contract: allow(fused-transfer) legacy per-request executor; the prefill plane owns the fused path
                host.save_contiguous(lidx, 0,
                                     np.transpose(k_arr, (1, 0, 2)), v_arr)
                host.flush()
            if cache is not None:
                cache.drop_layer(self._attn_layer_index(l))
        else:
            st.decode_state["caches"][l] = new_rec

        if seg.is_last:
            logits = M.prefill_finalize(self.params, cfg, st.lp.hidden)
            st.last_logits = logits
            st.decode_state["cur_len"] = jnp.full(
                (1,), st.lp.hidden.shape[1], jnp.int32)
            st.lp = None
            return True
        return False

    def _attn_layer_index(self, model_layer: int) -> int:
        """Map model layer id -> attention-layer ordinal (geom.num_layers).
        Precomputed in __init__ — called per layer per decode iteration."""
        return self._layer_to_lidx[model_layer]

    def _kv_to_layer_cache(self, st: _ReqState, kv_out: Tuple):
        cfg = self.cfg
        if cfg.attention_type == "mla":
            (latent,) = kv_out
            kpool, meta = M._kv_to_pool(cfg, latent[:, :, None, :],
                                        st.num_blocks, jnp.float32)
            return {"k": kpool, "meta": meta}, meta
        k, v = kv_out
        kpool, meta = M._kv_to_pool(cfg, k, st.num_blocks, jnp.float32)
        vpool, _ = M._kv_to_pool(cfg, v, st.num_blocks, jnp.float32)
        return {"k": kpool, "v": vpool, "meta": meta}, meta

    def _run_chunked_prefill(self, st: _ReqState, inject: int) -> bool:
        """Chunked-prefill baseline: process `inject` new prompt tokens
        through ALL layers, carrying per-layer dense KV context."""
        cfg = self.cfg
        r = st.req
        start = r.prefill_tokens_done
        end = min(start + inject, r.prompt_len)
        chunk_tokens = st.tokens[start:end]

        if st.chunk_ctx is None:
            st.chunk_ctx = [None] * cfg.num_layers
            st.chunk_rec = M._init_rec_states(cfg, 1, jnp.float32)
            if cfg.is_encoder_decoder or cfg.frontend == "vit_patch_stub":
                # run embed of full prompt once is cheating for VLM; for the
                # chunked baseline we only support pure-text archs' frontends
                pass

        h = self.params["embed"][jnp.asarray(chunk_tokens[None, :])]
        positions = jnp.arange(start, end, dtype=jnp.int32)[None, :]
        from repro.models import attention as attn_mod
        from repro.models import ffn as ffn_mod
        for l in range(cfg.num_layers):
            p = M.get_layer(self.params, l)
            kind = M.layer_kind(cfg, l)
            if kind == "attn" and cfg.attention_type != "mla":
                h_in = M._norm(cfg, p["attn_norm"], h)
                ctx = st.chunk_ctx[l]
                out, k, v = attn_mod.gqa_self_attention(
                    p["attn"], cfg, h_in, positions,
                    k_ctx=None if ctx is None else ctx[0],
                    v_ctx=None if ctx is None else ctx[1],
                    q_offset=start, return_kv=True)
                st.chunk_ctx[l] = (
                    k if ctx is None else jnp.concatenate([ctx[0], k], axis=1),
                    v if ctx is None else jnp.concatenate([ctx[1], v], axis=1))
                h = h + out
                h_in = M._norm(cfg, p["ffn_norm"], h)
                if "moe" in p:
                    # drop-free like every serving prefill path: capacity
                    # must not couple chunk size to routing drops
                    f, _ = ffn_mod.moe_apply(p["moe"], cfg, h_in,
                                             drop_free=True)
                else:
                    f = ffn_mod.ffn_apply(p["ffn"], h_in)
                h = h + f
            else:
                # recurrent / MLA layers fall back to full-layer forward
                h, _, _, new_rec = M.layer_forward(
                    p, cfg, h, positions, kind=kind,
                    rec_state=st.chunk_rec[l], return_kv=False,
                    moe_drop_free=True)
                st.chunk_rec[l] = new_rec
        r.prefill_tokens_done = end
        if end >= r.prompt_len:
            st.last_logits = M.lm_head(self.params, cfg, h[:, -1:, :])[:, 0]
            # build the decode state from accumulated ctx
            caches = []
            host = self.kv_mgr.pools.get(r.req_id)
            for l in range(cfg.num_layers):
                kind = M.layer_kind(cfg, l)
                if kind == "attn" and cfg.attention_type != "mla":
                    k, v = st.chunk_ctx[l]
                    kp, meta = M._kv_to_pool(cfg, k, st.num_blocks, jnp.float32)
                    vp, _ = M._kv_to_pool(cfg, v, st.num_blocks, jnp.float32)
                    caches.append({"k": kp, "v": vp, "meta": meta})
                    if host is not None:
                        # FlashD2H: the chunked baseline also leaves a DRAM
                        # copy of the prompt KV (one contiguous save per
                        # layer) so decode-time H2D restores stay exact
                        # plane-contract: allow(fused-transfer) chunked baseline runs one request at a time; nothing to fuse across
                        host.save_contiguous(
                            self._attn_layer_index(l), 0,
                            np.transpose(np.asarray(k[0], np.float32),
                                         (1, 0, 2)),
                            np.transpose(np.asarray(v[0], np.float32),
                                         (1, 0, 2)))
                else:
                    caches.append(st.chunk_rec[l])
            if host is not None:
                host.flush()
            st.decode_state = {
                "caches": caches,
                "cur_len": jnp.full((1,), r.prompt_len, jnp.int32),
                "extra": {}}
            st.chunk_ctx = None
            return True
        return False

    # ------------------------------------------------------------------
    # Prefill plane (batched jitted layer-segmented prefill, the default)
    # ------------------------------------------------------------------
    def _prefill_group_key(self, enc_list) -> Tuple:
        """Requests share a PrefillPlane when their whisper encoder KV
        shapes agree (mirrors the decode plane's grouping)."""
        if not enc_list:
            return ()
        return tuple((tuple(a.shape[1:]), str(a.dtype))
                     for kv in enc_list for a in kv)

    def _batched_admit_embed(self, sts: List[_ReqState]
                             ) -> Dict[str, jax.Array]:
        """{req_id: h (1, S, d)} for an admission batch's pure-text rows,
        embedded in ONE jitted bucketed launch (admission used to embed
        eagerly one request at a time).  Requests with frontend tensors
        (whisper frames, VLM patches) fall back to the per-request
        ``prefill_embed`` inside ``_admit_prefill_plane``."""
        cfg = self.cfg
        text = [st for st in sts
                if not st.inputs_extra and cfg.frontend == "none"
                and not cfg.is_encoder_decoder]
        if not text:
            return {}
        pol = self.eng.bucketing
        n_cap = pol.bucket_batch(len(text))
        s_cap = pol.bucket_tokens(max(len(st.tokens) for st in text))
        toks = np.zeros((n_cap, s_cap), np.int32)
        for i, st in enumerate(text):
            toks[i, :len(st.tokens)] = st.tokens
        h_all = admit_embed_fns_for(cfg).embed(self.params,
                                               jnp.asarray(toks))
        self.admit_embed_launches += 1
        return {st.req.req_id: h_all[i:i + 1, :len(st.tokens)]
                for i, st in enumerate(text)}

    def _admit_prefill_plane(self, st: _ReqState,
                             h: Optional[jax.Array] = None) -> PrefillPlane:
        """Plan the request's (layer, chunk) segments and admit it into its
        group's PrefillPlane row.  ``h``: the admission batch's pre-embedded
        residual stream (``_batched_admit_embed``); None falls back to the
        per-request embed (frontend inputs)."""
        cfg = self.cfg
        if h is None:
            h, _, enc_kvs = M.prefill_embed(self.params, cfg,
                                            self._model_inputs(st))
        else:
            enc_kvs = None
        S = int(h.shape[1])                     # prompt (+ patches)
        step = S
        if (self.eng.prefill_max_tokens_per_step > 0
                and cfg.attention_type != "mla"):
            # MLA keeps whole-layer segments: the latent cache has no
            # chunked-context attention path (same restriction as the
            # chunked baseline)
            step = self.eng.prefill_max_tokens_per_step
        segs = plan_segments(S, cfg.num_layers, step)
        enc_list = ([M.index_enc_kvs(enc_kvs, i)
                     for i in range(cfg.num_layers)]
                    if enc_kvs is not None else None)
        key = self._prefill_group_key(enc_list)
        plane = self.prefill_planes.get(key)
        if plane is None:
            plane = self.prefill_planes[key] = PrefillPlane(
                cfg, self.eng.bucketing, plane_mesh=self.plane_mesh)
            plane.tracer = self.tracer
        plane.admit(st.req.req_id, h, segs, enc_list)
        self._req_prefill_plane[st.req.req_id] = plane
        st.decode_state = {"caches": [None] * cfg.num_layers,
                           "cur_len": None,
                           "extra": ({"enc_kvs": enc_list} if enc_list
                                     else {})}
        return plane

    def _prefill_plane_iteration(self, prefill_reqs
                                 ) -> Tuple[float, List[Request], int]:
        """Run one iteration of batched plane prefill for the scheduled
        requests.  Per executed (layer, chunk) group: ONE jitted bucketed
        launch over the whole batch, ONE fused FlashD2H save of the group's
        KV stripes (``save_new_tokens_fused``), and — at each row's last
        chunk of the layer — the decode pool build plus HBM eviction of the
        layer (the one-layer bound).  Rows whose final segment ran share
        one finalize (logits) launch.

        Returns (modeled time, finished requests, iteration HBM footprint
        in token-layer units summed over every admitted prefill row)."""
        L = self.cfg.num_layers
        t = 0.0
        done: List[Request] = []
        fp = 0
        # batch admission-time embedding: every pure-text request admitted
        # this iteration shares ONE bucketed embedding launch
        pre_h = self._batched_admit_embed(
            [self.states[req.req_id] for req, _ in prefill_reqs
             if req.req_id not in self._req_prefill_plane])
        by_plane: Dict[int, Tuple[PrefillPlane, Dict[str, int]]] = {}
        for req, inject in prefill_reqs:
            st = self.states[req.req_id]
            if req.scheduled_time is None:
                req.scheduled_time = self.now
            plane = self._req_prefill_plane.get(req.req_id)
            if plane is None:
                plane = self._admit_prefill_plane(st,
                                                  h=pre_h.get(req.req_id))
            st.prefill_carry += max(int(inject), 1)
            _, allow = by_plane.setdefault(id(plane), (plane, {}))
            allow[req.req_id] = st.prefill_carry
        for plane, allow in by_plane.values():
            spent: Dict[str, int] = {}
            t_acc = [0.0]

            def group_cb(g, plane=plane, spent=spent, t_acc=t_acc):
                # runs in the window right after the group's launch, while
                # the plane's ONE-layer context still holds this layer
                n_shards, ag_bytes = 1, 0
                if (self.plane_mesh is not None and g.kind == "attn"
                        and self.cfg.attention_type != "mla"):
                    # sequence-sharded launch: attention compute splits
                    # across the model axis; the sharded attention outputs
                    # are re-gathered (charged like one layer of KV)
                    n_shards = self.plane_mesh.model_size
                    tok = sum(g.segs[rid].chunk_len for rid in g.req_ids)
                    ag_bytes = int(tok * self.mc.kv_bytes_per_token
                                   / max(self.geom.num_layers, 1))
                t_acc[0] += cm.batched_prefill_time(
                    self.hw, self.mc,
                    [(g.segs[rid].chunk_len,
                      g.chunk_start + g.segs[rid].chunk_len)
                     for rid in g.req_ids], layers=1,
                    n_shards=n_shards, allgather_bytes=ag_bytes)
                self.prefill_launches += 1
                for rid in g.req_ids:
                    spent[rid] = spent.get(rid, 0) + g.segs[rid].chunk_len
                if g.kind != "attn":
                    return
                lidx = self._attn_layer_index(g.layer)
                # FlashD2H: ONE fused save of the whole group's stripes
                kv_by_req = plane.read_group_kv(g)
                self.kv_mgr.save_new_tokens_fused(lidx, {
                    rid: (g.chunk_start, k, v)
                    for rid, (k, v) in kv_by_req.items()})
                for rid in g.req_ids:
                    pool = self.kv_mgr.pools.get(rid)
                    if pool is not None:
                        pool.flush()
                # end of layer: build the decode pool from the plane's
                # one-layer context, then evict the layer from HBM
                for rid in g.req_ids:
                    if not g.segs[rid].is_last_chunk_of_layer:
                        continue
                    st_r = self.states[rid]
                    pool_kv, _ = self._kv_to_layer_cache(
                        st_r, plane.layer_ctx(rid))
                    st_r.decode_state["caches"][g.layer] = pool_kv
                    cache = self.kv_mgr.caches.get(rid)
                    if cache is not None:
                        cache.drop_layer(lidx)

            res = plane.run_iteration(self.params, allow, group_cb)
            t += t_acc[0]
            for rid in allow:
                st_r = self.states[rid]
                st_r.prefill_carry = max(
                    0, st_r.prefill_carry - spent.get(rid, 0))
                # mirror the plane cursor into the scheduler's pacing state
                req = st_r.req
                if not plane.done(rid):
                    seg = plane.segments[rid][plane.next_idx[rid]]
                    req.prefill_layer = seg.layer
                    req.prefill_layer_tokens_done = min(
                        seg.chunk_start, max(req.prompt_len - 1, 0))
            for rid, peak in res.peaks.items():
                fp += hbm_footprint_tokens(
                    plane.tok_len[rid], "layer_segmented", L,
                    layer_tokens_resident=peak)
            for rid in res.finished:
                st_r = self.states[rid]
                row = plane.rows[rid]
                st_r.last_logits = res.logits[row:row + 1]
                caches = st_r.decode_state["caches"]
                for l in range(L):
                    if caches[l] is None and M.layer_kind(self.cfg,
                                                          l) != "attn":
                        caches[l] = plane.rec_state(rid, l)
                st_r.decode_state["cur_len"] = jnp.full(
                    (1,), plane.tok_len[rid], jnp.int32)
                st_r.req.prefill_layer = L
                st_r.req.prefill_layer_tokens_done = 0
                plane.release(rid)
                self._req_prefill_plane.pop(rid, None)
                done.append(st_r.req)
        # planes with NO scheduled request this iteration still hold their
        # rows' mid-layer chunk residency — count it into the watermark
        for plane in self.prefill_planes.values():
            if id(plane) in by_plane:
                continue
            for rid, resident in plane.resident_tokens().items():
                fp += hbm_footprint_tokens(
                    plane.tok_len[rid], "layer_segmented", L,
                    layer_tokens_resident=resident)
        return t, done, fp

    # ------------------------------------------------------------------
    # Mixed iteration (hybrid plane)
    # ------------------------------------------------------------------
    def _mixed_iteration(self, plan: BatchPlan
                         ) -> Tuple[int, List[Request], int, List[float]]:
        """One MIXED iteration: every decode group's staged pipeline and
        every prefill plane's (layer, chunk) groups ride the SAME layer
        walk (``HybridPlane.run_iteration``), sharing one per-layer host
        stage.  Per attention layer the ``layer_cb`` below does, in order:

        1. ONE merged fused FlashD2H: decode write-back of the layer's
           just-appended KV (every decode plane) PLUS the layer's fresh
           prefill-chunk KV (``read_group_kv`` per group, same-rid chunks
           concatenated — chunks of one layer are contiguous), in a single
           ``save_new_tokens_fused`` call;
        2. LRU residency for every decode plane's selections, then at most
           ONE merged fused FlashH2D (``load_blocks_fused``) covering all
           planes' misses, scattered into each plane's slots BEFORE the
           attention that selected them;
        3. the one-stage-deferred eviction drop (``protect=``) and the
           ``staged_probe`` hook, per decode plane;
        4. prefill end-of-layer pool builds + HBM layer eviction (the
           one-layer bound), exactly as the split path's group callback.

        Returns (blocks loaded, finished prefill requests, iteration HBM
        footprint in token-layer units, per-model-layer modeled prefill
        seconds for ``costmodel.mixed_iteration_time``)."""
        L = self.cfg.num_layers
        done: List[Request] = []
        fp = 0
        drop = self.eng.drop_evicted_device_blocks
        per_block_bytes = self._offload_block_bytes
        prefill_by_layer = [0.0] * L
        loads_total = [0]
        spent: Dict[str, int] = {}

        # prefill jobs (admission mirrors _prefill_plane_iteration)
        tr = self.tracer
        with tr.span("engine.prefill"):
            pre_h = self._batched_admit_embed(
                [self.states[req.req_id] for req, _ in plan.prefill_reqs
                 if req.req_id not in self._req_prefill_plane])
            by_plane: Dict[int, Tuple[PrefillPlane, Dict[str, int]]] = {}
            for req, inject in plan.prefill_reqs:
                st = self.states[req.req_id]
                if req.scheduled_time is None:
                    req.scheduled_time = self.now
                plane = self._req_prefill_plane.get(req.req_id)
                if plane is None:
                    plane = self._admit_prefill_plane(st,
                                                      h=pre_h.get(req.req_id))
                st.prefill_carry += max(int(inject), 1)
                _, allow = by_plane.setdefault(id(plane), (plane, {}))
                allow[req.req_id] = st.prefill_carry
            prefill_jobs = [PrefillJob(plane, allow)
                            for plane, allow in by_plane.values()]

        # decode jobs (grouping mirrors step()'s split decode dispatch)
        groups: Dict[Tuple, List[_ReqState]] = {}
        for req in plan.decode_reqs:
            st = self.states[req.req_id]
            if st.group_key is None:
                st.group_key = self._decode_group_key(st)
            groups.setdefault(st.group_key, []).append(st)
        decode_jobs: List[DecodeJob] = []
        decode_sts: List[List[_ReqState]] = []
        pending_evict: Dict[int, Dict[str, set]] = {}
        sel_pairs: Dict[str, List[Tuple[int, int]]] = {}
        for key, sts in groups.items():
            plane = self._plane_for(key, sts)
            decode_jobs.append(DecodeJob(plane, {
                st.req.req_id: st.out_tokens[-1] for st in sts}))
            decode_sts.append(sts)
            pending_evict[id(plane)] = {st.req.req_id: set() for st in sts}
            sel_pairs.update({st.req.req_id: [] for st in sts})

        entry: Dict[str, Any] = {
            "layers": {}, "decode_planes": len(decode_jobs),
            "decode_rows": len(plan.decode_reqs),
            "prefill_rows": len(plan.prefill_reqs),
            "groups": 0, "finalize": 0, "launches": 0}

        worker = self._stage_worker() if self._stage_async else None

        def _layer_log_and_budget(win: LayerWindow, lidx: int) -> Dict:
            """Shared pure-host head of both layer callbacks: the
            per-layer log entry, modeled prefill launch cost, and the
            prefill token-budget spend."""
            lay_log = {"d2h": 0, "h2d": 0, "groups": len(win.groups),
                       "attn": win.kind == "attn",
                       "decode": bool(win.selections)}
            entry["layers"][win.layer] = lay_log
            for plane, g in win.groups:
                n_shards, ag_bytes = 1, 0
                if (self.plane_mesh is not None and g.kind == "attn"
                        and self.cfg.attention_type != "mla"):
                    n_shards = self.plane_mesh.model_size
                    tok = sum(g.segs[rid].chunk_len for rid in g.req_ids)
                    ag_bytes = int(tok * self.mc.kv_bytes_per_token
                                   / max(self.geom.num_layers, 1))
                prefill_by_layer[win.layer] += cm.batched_prefill_time(
                    self.hw, self.mc,
                    [(g.segs[rid].chunk_len,
                      g.chunk_start + g.segs[rid].chunk_len)
                     for rid in g.req_ids], layers=1,
                    n_shards=n_shards, allgather_bytes=ag_bytes)
                self.prefill_launches += 1
                for rid in g.req_ids:
                    spent[rid] = spent.get(rid, 0) + g.segs[rid].chunk_len
            return lay_log

        def layer_cb_sync(win: LayerWindow) -> None:
            lidx = (self._attn_layer_index(win.layer)
                    if win.kind == "attn" else -1)
            lay_log = _layer_log_and_budget(win, lidx)
            # 1. ONE merged fused FlashD2H: decode write-back + fresh
            #    prefill-chunk KV of THIS layer, single save call
            kv_merge: Dict[str, Tuple[int, Any, Any]] = {}
            for d, sel in win.selections:
                if not self.eng.decode_write_back:
                    continue
                k, v = d.plane.new_token_kv(d.req_ids, d.prev,
                                            layers=[win.layer])[win.layer]
                for i, rid in enumerate(d.req_ids):
                    kv_merge[rid] = (d.prev[rid], k[i][:, None, :],
                                     None if v is None else v[i][:, None, :])
            for plane, g in win.groups:
                if g.kind != "attn":
                    continue
                for rid, (k, v) in plane.read_group_kv(g).items():
                    cur = kv_merge.get(rid)
                    if cur is None:
                        kv_merge[rid] = (g.chunk_start, k, v)
                    else:
                        # same-rid chunks of one layer are contiguous in
                        # plan order: extend the stripe along tokens
                        s0, k0, v0 = cur
                        kv_merge[rid] = (
                            s0, np.concatenate([k0, k], axis=1),
                            None if v is None
                            else np.concatenate([v0, v], axis=1))
            if kv_merge:
                self.kv_mgr.save_new_tokens_fused(lidx, kv_merge)
                lay_log["d2h"] += 1
                for rid in kv_merge:
                    pool = self.kv_mgr.pools.get(rid)
                    if pool is not None:
                        pool.flush()
            # 2. LRU per decode plane, then at most ONE merged FlashH2D
            merged_missing: Dict[str, List[int]] = {}
            rounds = []
            for d, sel in win.selections:
                if sel is None:
                    continue
                blocks_by_req: Dict[str, List[int]] = {}
                for rid in d.req_ids:
                    blocks = dsa_mod.selected_block_ids(
                        sel[d.plane.rows[rid]])
                    blocks_by_req[rid] = blocks
                    sel_pairs[rid].extend((lidx, x) for x in blocks)
                missing_by_req, evicted_by_req = self.kv_mgr.access_layer(
                    lidx, blocks_by_req, drain_evicted=drop)
                pe = pending_evict[id(d.plane)]
                for rid, ev in evicted_by_req.items():
                    pe[rid].update(ev)
                loads_total[0] += sum(len(m)
                                      for m in missing_by_req.values())
                merged_missing.update(missing_by_req)
                rounds.append((d, blocks_by_req, missing_by_req))
            if merged_missing:
                self._staged_layer_bytes[win.layer] = (
                    self._staged_layer_bytes.get(win.layer, 0)
                    + sum(len(m) for m in merged_missing.values())
                    * per_block_bytes)
                payloads = self.kv_mgr.load_blocks_fused(lidx,
                                                         merged_missing)
                lay_log["h2d"] += 1
                if self.eng.decode_write_back:
                    for d, _, missing_by_req in rounds:
                        if missing_by_req:
                            d.plane.restore_blocks_fused(
                                win.layer,
                                {rid: (missing_by_req[rid], k, v)
                                 for rid, (k, v) in payloads.items()
                                 if rid in missing_by_req},
                                before_use=True)
            # 3. deferred eviction drop + probe, per decode plane
            for d, blocks_by_req, _ in rounds:
                sts_d = [self.states[rid] for rid in d.req_ids]
                if drop:
                    self._drop_pending_evictions(
                        d.plane, sts_d, pending_evict[id(d.plane)],
                        protect=(lidx, blocks_by_req))
                if self.staged_probe is not None:
                    self.staged_probe(self, d.plane, win.layer, sts_d,
                                      blocks_by_req)
            # 4. prefill end-of-layer: decode pool builds + HBM layer evict
            for plane, g in win.groups:
                if g.kind != "attn":
                    continue
                for rid in g.req_ids:
                    if not g.segs[rid].is_last_chunk_of_layer:
                        continue
                    st_r = self.states[rid]
                    pool_kv, _ = self._kv_to_layer_cache(
                        st_r, plane.layer_ctx(rid))
                    st_r.decode_state["caches"][g.layer] = pool_kv
                    cache = self.kv_mgr.caches.get(rid)
                    if cache is not None:
                        cache.drop_layer(lidx)

        def layer_cb_async(win: LayerWindow) -> None:
            # The DISPATCH WINDOW (see stage_cb_async): no device sync
            # beyond the selection arrays the driver already converted —
            # counted here as this layer's allowed host syncs.
            for d, sel in win.selections:
                if sel is not None:
                    d.plane.host_syncs += 1
            lidx = (self._attn_layer_index(win.layer)
                    if win.kind == "attn" else -1)
            lay_log = _layer_log_and_budget(win, lidx)
            with jax.transfer_guard_device_to_host("disallow"):
                # 1. ONE merged fused FlashD2H per layer, staged on the
                #    worker: dispatch every decode plane's stripe gather
                #    and every prefill group's chunk gather, submit one
                #    merging job (same single save_new_tokens_fused shape
                #    as the sync path)
                parts: List[Tuple] = []
                finishers: List[Tuple] = []
                for d, sel in win.selections:
                    if not self.eng.decode_write_back:
                        continue
                    kv_dev = d.plane.new_token_kv_async(
                        d.req_ids, d.prev, layers=[win.layer])[win.layer]
                    parts.append((list(d.req_ids), dict(d.prev), kv_dev))
                for plane, g in win.groups:
                    if g.kind != "attn":
                        continue
                    finishers.append(
                        (g.chunk_start, plane.read_group_kv_async(g)))
                if parts or finishers:
                    self._stage_writeback_async_merged(worker, lidx,
                                                       parts, finishers)
                    lay_log["d2h"] += 1
                # 2. LRU per decode plane (dispatch thread: access order
                #    stays byte-identical to sync), then at most ONE
                #    merged FlashH2D behind the per-layer fence
                merged_missing: Dict[str, List[int]] = {}
                rounds = []
                for d, sel in win.selections:
                    if sel is None:
                        continue
                    blocks_by_req: Dict[str, List[int]] = {}
                    for rid in d.req_ids:
                        blocks = dsa_mod.selected_block_ids(
                            sel[d.plane.rows[rid]])
                        blocks_by_req[rid] = blocks
                        sel_pairs[rid].extend((lidx, x) for x in blocks)
                    missing_by_req, evicted_by_req = \
                        self.kv_mgr.access_layer(lidx, blocks_by_req,
                                                 drain_evicted=drop)
                    pe = pending_evict[id(d.plane)]
                    for rid, ev in evicted_by_req.items():
                        pe[rid].update(ev)
                    loads_total[0] += sum(len(m)
                                          for m in missing_by_req.values())
                    merged_missing.update(missing_by_req)
                    rounds.append((d, blocks_by_req, missing_by_req))
                if merged_missing:
                    self._staged_layer_bytes[win.layer] = (
                        self._staged_layer_bytes.get(win.layer, 0)
                        + sum(len(m) for m in merged_missing.values())
                        * per_block_bytes)
                    # restore-before-use fence: this layer's outstanding
                    # merged write-back must land in DRAM before gathering
                    worker.fence(lidx)
                    payloads = self.kv_mgr.load_blocks_fused(
                        lidx, merged_missing)
                    lay_log["h2d"] += 1
                    if self.eng.decode_write_back:
                        for d, _, missing_by_req in rounds:
                            if missing_by_req:
                                d.plane.restore_blocks_fused(
                                    win.layer,
                                    {rid: (missing_by_req[rid], k, v)
                                     for rid, (k, v) in payloads.items()
                                     if rid in missing_by_req},
                                    before_use=True)
                # 3. deferred eviction drop, per decode plane (the probe
                #    runs outside the guard, below)
                for d, blocks_by_req, _ in rounds:
                    if drop:
                        self._drop_pending_evictions(
                            d.plane, [self.states[rid]
                                      for rid in d.req_ids],
                            pending_evict[id(d.plane)],
                            protect=(lidx, blocks_by_req))
                # 4. prefill end-of-layer: decode pool builds (device
                #    slices only, no sync) + HBM layer eviction
                for plane, g in win.groups:
                    if g.kind != "attn":
                        continue
                    for rid in g.req_ids:
                        if not g.segs[rid].is_last_chunk_of_layer:
                            continue
                        st_r = self.states[rid]
                        pool_kv, _ = self._kv_to_layer_cache(
                            st_r, plane.layer_ctx(rid))
                        st_r.decode_state["caches"][g.layer] = pool_kv
                        cache = self.kv_mgr.caches.get(rid)
                        if cache is not None:
                            cache.drop_layer(lidx)
            if self.staged_probe is not None and rounds:
                worker.fence(lidx)   # probes compare device vs host pools
                for d, blocks_by_req, _ in rounds:
                    self.staged_probe(self, d.plane, win.layer,
                                      [self.states[rid]
                                       for rid in d.req_ids],
                                      blocks_by_req)

        involved: Dict[int, Any] = {}
        for job in decode_jobs:
            involved[id(job.plane.staged_fns)] = job.plane.staged_fns
        for pj in prefill_jobs:
            involved[id(pj.plane.fns)] = pj.plane.fns
        calls0 = sum(f.calls for f in involved.values())
        # the one layer walk: decode groups, and any prefill groups riding
        # the same layers
        with tr.span("engine.decode", prefill_jobs=len(prefill_jobs)):
            res = self.hybrid.run_iteration(
                self.params, decode_jobs, prefill_jobs,
                layer_cb_async if worker is not None else layer_cb_sync)
        if worker is not None:
            # iteration fence: every merged write-back has landed before
            # the epilogues sample logits or release DRAM pools
            with tr.span("worker.drain"):
                worker.drain()
        entry["launches"] = sum(f.calls
                                for f in involved.values()) - calls0

        # decode epilogue (mirrors _decode_batch_staged's tail)
        for (plane, logits, _info, _prev), sts in zip(res.decode,
                                                      decode_sts):
            self.decode_step_calls += 1
            self.decode_tokens += len(sts)
            if drop:
                with tr.span("engine.drop_pending"):
                    self._drop_pending_evictions(plane, sts,
                                                 pending_evict[id(plane)])
            for st in sts:
                row = plane.rows[st.req.req_id]
                st.last_logits = logits[row:row + 1]
                st.out_tokens.append(self._sample(st))
                if sel_pairs[st.req.req_id]:
                    self.scheduler.observe_selection(
                        st.req, sel_pairs[st.req.req_id])

        # prefill epilogue (mirrors _prefill_plane_iteration's tail)
        with tr.span("engine.prefill"):
            for plane, pres in res.prefill:
                entry["groups"] += len(pres.groups)
                entry["finalize"] += 1 if pres.finished else 0
                _, allow = by_plane[id(plane)]
                for rid in allow:
                    st_r = self.states[rid]
                    st_r.prefill_carry = max(
                        0, st_r.prefill_carry - spent.get(rid, 0))
                    req = st_r.req
                    if not plane.done(rid):
                        seg = plane.segments[rid][plane.next_idx[rid]]
                        req.prefill_layer = seg.layer
                        req.prefill_layer_tokens_done = min(
                            seg.chunk_start, max(req.prompt_len - 1, 0))
                for rid, peak in pres.peaks.items():
                    fp += hbm_footprint_tokens(
                        plane.tok_len[rid], "layer_segmented", L,
                        layer_tokens_resident=peak)
                for rid in pres.finished:
                    st_r = self.states[rid]
                    row = plane.rows[rid]
                    st_r.last_logits = pres.logits[row:row + 1]
                    caches = st_r.decode_state["caches"]
                    for l in range(L):
                        if caches[l] is None and M.layer_kind(self.cfg,
                                                              l) != "attn":
                            caches[l] = plane.rec_state(rid, l)
                    st_r.decode_state["cur_len"] = jnp.full(
                        (1,), plane.tok_len[rid], jnp.int32)
                    st_r.req.prefill_layer = L
                    st_r.req.prefill_layer_tokens_done = 0
                    plane.release(rid)
                    self._req_prefill_plane.pop(rid, None)
                    done.append(st_r.req)
            for plane in self.prefill_planes.values():
                if id(plane) in by_plane:
                    continue
                for rid, resident in plane.resident_tokens().items():
                    fp += hbm_footprint_tokens(
                        plane.tok_len[rid], "layer_segmented", L,
                        layer_tokens_resident=resident)
        self.mixed_iter_log.append(entry)
        return loads_total[0], done, fp, prefill_by_layer

    # ------------------------------------------------------------------
    # Decode execution
    # ------------------------------------------------------------------
    def _sample(self, st: _ReqState) -> int:
        with self.tracer.span("sampling", req=st.req.req_id):
            logits = np.asarray(st.last_logits, np.float32)[0]
            if self.eng.greedy:
                return int(np.argmax(logits))
            z = logits - logits.max()
            p = np.exp(z) / np.exp(z).sum()
            return int(self.rng.choice(len(p), p=p))

    def _account_selections(self, sts: List[_ReqState],
                            selected: Dict[int, Any],
                            plane: Optional[DevicePoolPlane] = None) -> int:
        """DSA selections -> LRU residency, fused FlashH2D loads, and the
        working-set estimator.

        `selected[l]` is (B, Hkv, K); batch row `b` belongs to ``sts[b]``
        unless `plane` is given, in which case rows follow the plane's slot
        assignment.  For each layer, every request's misses are loaded by
        ONE fused launch (`KVCacheManager.load_blocks_fused`) — h2d_calls
        scale per-layer-per-iteration, not per-request — and, on the
        persistent plane, the gathered payloads are scattered DIRECTLY into
        the requests' device slots (`DevicePoolPlane.restore_blocks`).
        With ``drop_evicted_device_blocks`` the blocks the LRU evicted this
        iteration are then zeroed on device.  Returns blocks loaded."""
        loads = 0
        sel_pairs: Dict[str, List[Tuple[int, int]]] = \
            {st.req.req_id: [] for st in sts}
        evicted: Dict[str, set] = {st.req.req_id: set() for st in sts}
        for l in sorted(selected):
            sel = np.asarray(selected[l])
            lidx = self._attn_layer_index(l)
            blocks_by_req: Dict[str, List[int]] = {}
            for b, st in enumerate(sts):
                row = b if plane is None else plane.rows[st.req.req_id]
                blocks = dsa_mod.selected_block_ids(sel[row])
                blocks_by_req[st.req.req_id] = blocks
                sel_pairs[st.req.req_id].extend((lidx, x) for x in blocks)
            missing_by_req, evicted_by_req = self.kv_mgr.access_layer(
                lidx, blocks_by_req,
                drain_evicted=self.eng.drop_evicted_device_blocks)
            for rid, ev in evicted_by_req.items():
                evicted[rid].update(ev)
            loads += sum(len(m) for m in missing_by_req.values())
            if missing_by_req:
                payloads = self.kv_mgr.load_blocks_fused(lidx, missing_by_req)
                if plane is not None and self.eng.decode_write_back:
                    # FlashH2D lands in the device slots, not a side buffer
                    # — ONE fused scatter per layer covering every request.
                    # Gated on write-back: only then is the host pool a
                    # superset of device KV (scattering stale host data
                    # over decode-appended tokens would corrupt the pool).
                    plane.restore_blocks_fused(
                        l, {rid: (missing_by_req[rid], k, v)
                            for rid, (k, v) in payloads.items()})
        if plane is not None and self.eng.drop_evicted_device_blocks:
            self._drop_pending_evictions(plane, sts, evicted)
        for st in sts:
            if sel_pairs[st.req.req_id]:
                self.scheduler.observe_selection(st.req,
                                                 sel_pairs[st.req.req_id])
        return loads

    def _drop_pending_evictions(self, plane: DevicePoolPlane,
                                sts: List[_ReqState],
                                pending: Dict[str, set],
                                protect: Optional[Tuple[int, Dict[str, List[int]]]] = None) -> None:
        """Physically zero LRU-evicted blocks on device, mutating `pending`
        ((layer, block) keys per request) in place.

        A key is skipped (kept pending) when it was re-loaded since eviction
        (LRU-resident again) — its device data is current — or when
        ``protect`` = (lidx, blocks_by_req) marks it as selected by the
        attention stage ABOUT to run (staged plane: the block was evicted by
        its own access but its device copy is valid and needed now; it is
        dropped at the next stage boundary if still non-resident)."""
        for st in sts:
            rid = st.req.req_id
            cache = self.kv_mgr.caches.get(rid)
            if cache is None:
                pending[rid].clear()
                continue
            keep: set = set()
            by_layer: Dict[int, List[int]] = {}
            for elidx, blk in pending[rid]:
                if cache.resident(elidx, blk):      # re-loaded since
                    continue
                if (protect is not None and elidx == protect[0]
                        and blk in protect[1].get(rid, ())):
                    keep.add((elidx, blk))
                    continue
                by_layer.setdefault(elidx, []).append(blk)
            for elidx, blks in by_layer.items():
                layer = self._lidx_to_layer.get(elidx)
                if layer is not None:
                    plane.drop_blocks(rid, layer, sorted(set(blks)))
            pending[rid] = keep

    # ------------------------------------------------------------------
    # Async host stage (stage_dispatch="async")
    # ------------------------------------------------------------------
    def _stage_worker(self) -> HostStageWorker:
        """The engine's host-stage worker, created lazily (and re-created
        after ``close()``, so a closed engine can still step)."""
        if self._worker is None or self._worker.closed:
            self._worker = HostStageWorker(name=f"host-stage-{id(self):x}",
                                           tracer=self.tracer)
        return self._worker

    def close(self) -> None:
        """Shut down the host-stage worker: drains outstanding write-back
        jobs (re-raising their errors) and joins the thread.  Idempotent;
        ``run()`` calls it on exit.  The worker's job/busy counters fold
        into engine-level totals so ``metrics_snapshot()`` and the overlap
        instruments keep working after shutdown."""
        if self._worker is not None:
            self._worker.close()
            self.worker_jobs_run += self._worker.jobs_run
            self.worker_busy_s += self._worker.busy_s
            self._worker = None

    def _stage_writeback_async(self, worker: HostStageWorker, lidx: int,
                               req_ids: List[str], prev: Dict[str, int],
                               kv_dev: Tuple) -> None:
        """DISPATCH layer ``lidx``'s FlashD2H write-back: the stripe
        conversion (the blocking np.asarray of the device gather) plus
        ``save_new_tokens_fused`` + pool flush run on the host-stage
        worker, off the dispatch thread.  Ordering contract: this is where
        the fused d2h *starts* (plane-contract sequences it like the sync
        save, before any drop); completion is closed by ``fence(lidx)``
        before any same-layer DRAM gather and ``drain()`` before
        sampling/release."""
        k_dev, v_dev = kv_dev

        def job() -> None:
            k = np.asarray(k_dev)
            v = None if v_dev is None else np.asarray(v_dev)
            self.kv_mgr.save_new_tokens_fused(lidx, {
                rid: (prev[rid], k[i][:, None, :],
                      None if v is None else v[i][:, None, :])
                for i, rid in enumerate(req_ids)})
            for rid in req_ids:
                pool = self.kv_mgr.pools.get(rid)
                if pool is not None:
                    pool.flush()
        worker.submit(lidx, job)

    def _stage_writeback_async_merged(self, worker: HostStageWorker,
                                      lidx: int, parts: List[Tuple],
                                      finishers: List[Tuple]) -> None:
        """Mixed-iteration variant of ``_stage_writeback_async``: ONE
        worker job per layer merges every decode plane's stripe
        (``parts``: (req_ids, prev, kv_dev)) with every prefill group's
        fresh-chunk KV (``finishers``: (chunk_start, finish) from
        ``read_group_kv_async``) into a single ``save_new_tokens_fused``
        call — the same one-fused-FlashD2H-per-layer shape as the sync
        path, just converted and staged off-thread."""

        def job() -> None:
            kv_merge: Dict[str, Tuple[int, Any, Any]] = {}
            for req_ids, prev, (k_dev, v_dev) in parts:
                k = np.asarray(k_dev)
                v = None if v_dev is None else np.asarray(v_dev)
                for i, rid in enumerate(req_ids):
                    kv_merge[rid] = (prev[rid], k[i][:, None, :],
                                     None if v is None
                                     else v[i][:, None, :])
            for chunk_start, finish in finishers:
                for rid, (k, v) in finish().items():
                    cur = kv_merge.get(rid)
                    if cur is None:
                        kv_merge[rid] = (chunk_start, k, v)
                    else:
                        # same-rid chunks of one layer are contiguous in
                        # plan order: extend the stripe along tokens
                        s0, k0, v0 = cur
                        kv_merge[rid] = (
                            s0, np.concatenate([k0, k], axis=1),
                            None if v is None
                            else np.concatenate([v0, v], axis=1))
            if kv_merge:
                self.kv_mgr.save_new_tokens_fused(lidx, kv_merge)
                for rid in kv_merge:
                    pool = self.kv_mgr.pools.get(rid)
                    if pool is not None:
                        pool.flush()
        worker.submit(lidx, job)

    def _decode_one(self, st: _ReqState) -> Tuple[int, int]:
        """Legacy sequential decode step (B=1): feed the last generated
        token, sample the next.  Returns (token, blocks_loaded)."""
        tok = st.out_tokens[-1]        # last generated token is the input
        logits, new_state, info = M.decode_step(
            self.params, self.cfg, jnp.asarray([tok], jnp.int32),
            st.decode_state, attn_impl=self.eng.attn_impl, return_info=True)
        self.decode_step_calls += 1
        self.decode_tokens += 1
        st.decode_state = new_state
        st.last_logits = logits
        nxt = self._sample(st)
        st.out_tokens.append(nxt)
        loads = self._account_selections([st], info["selected"])
        return nxt, loads

    def _decode_group_key(self, st: _ReqState) -> Tuple:
        """Requests batch together when their non-pool state agrees in
        every per-request shape except batch (e.g. whisper encoder length);
        pool block counts may differ (padded to the batch max)."""
        extra = st.decode_state.get("extra") or {}
        return tuple((tuple(leaf.shape[1:]), str(leaf.dtype))
                     for leaf in jax.tree.leaves(extra))

    def _decode_batch(self, sts: List[_ReqState]) -> int:
        """Legacy batched path (``decode_plane="stacked"``): ONE batched
        model forward, but per-request KV pools are re-stacked into a fresh
        padded paged pool and unstacked again EVERY iteration — an
        O(batch x pool) device copy per generated token.  Kept as the
        equivalence oracle for the persistent plane.  Returns blocks
        loaded."""
        toks = jnp.asarray([st.out_tokens[-1] for st in sts], jnp.int32)
        batched, layout = M.stack_decode_states(
            [st.decode_state for st in sts])
        self.stack_calls += 1                  # full-pool stack + unstack
        logits, new_state, info = M.decode_step(
            self.params, self.cfg, toks, batched,
            attn_impl=self.eng.attn_impl, return_info=True)
        self.decode_step_calls += 1
        self.decode_tokens += len(sts)
        for st, ns, row in zip(sts, M.unstack_decode_states(new_state, layout),
                               range(len(sts))):
            st.decode_state = ns
            st.last_logits = logits[row:row + 1]
            st.out_tokens.append(self._sample(st))
        return self._account_selections(sts, info["selected"])

    def _plane_for(self, key: Tuple, sts: List[_ReqState]) -> DevicePoolPlane:
        """Get (or create) the group's DevicePoolPlane and admit any of
        `sts` not yet resident — the only full-pool copy in a request's
        decode lifetime; the plane owns the state afterwards."""
        plane = self.planes.get(key)
        if plane is None:
            plane = self.planes[key] = DevicePoolPlane(
                self.cfg, self.eng.bucketing, attn_impl=self.eng.attn_impl,
                plane_mesh=self.plane_mesh)
            plane.tracer = self.tracer
        for st in sts:
            rid = st.req.req_id
            if rid not in plane.rows:
                plane.admit(rid, st.decode_state)
                st.decode_state = None           # the plane owns it now
                self._req_plane[rid] = plane
        return plane

    def _decode_batch_persistent(self, key: Tuple,
                                 sts: List[_ReqState]) -> int:
        """Fused plane: requests live in a persistent ``DevicePoolPlane`` —
        admitted once, stepped via ONE jitted bucketed forward per iteration
        with zero per-iteration stack/unstack copies, released when finished
        (slots reused by later admissions).  Newly generated KV is written
        back to the host pool (fused FlashD2H) and fused FlashH2D payloads
        land directly in device slots — but only AFTER the forward that
        selected them, which is why ``drop_evicted_device_blocks`` is not
        oracle-exact here (use the staged plane).  Returns blocks loaded."""
        plane = self._plane_for(key, sts)
        tok_by_req = {st.req.req_id: st.out_tokens[-1] for st in sts}
        logits, info, prev = plane.step(self.params, tok_by_req)
        self.decode_step_calls += 1
        self.decode_tokens += len(sts)
        if self.eng.decode_write_back:
            self._write_back_new_kv(plane, sts, prev)
        for st in sts:
            row = plane.rows[st.req.req_id]
            st.last_logits = logits[row:row + 1]
            st.out_tokens.append(self._sample(st))
        return self._account_selections(sts, info["selected"], plane=plane)

    def _write_back_new_kv(self, plane: DevicePoolPlane,
                           sts: List[_ReqState],
                           prev: Dict[str, int]) -> None:
        """FlashD2H decode save: this iteration's appended KV goes to the
        host pools with ONE fused d2h call per attention layer, keeping
        DRAM a byte-exact superset of device KV (the invariant that makes
        H2D restores safe to scatter straight into device slots)."""
        req_ids = [st.req.req_id for st in sts]
        payload = plane.new_token_kv(req_ids, prev)
        for l, (k, v) in payload.items():
            lidx = self._attn_layer_index(l)
            kv_by_req = {
                rid: (prev[rid], k[i][:, None, :],
                      None if v is None else v[i][:, None, :])
                for i, rid in enumerate(req_ids)}
            self.kv_mgr.save_new_tokens_fused(lidx, kv_by_req)
        for rid in req_ids:
            pool = self.kv_mgr.pools.get(rid)
            if pool is not None:
                pool.flush()

    def _decode_batch_staged(self, key: Tuple, sts: List[_ReqState]) -> int:
        """Tentpole hot path: the staged per-layer pipeline over the
        persistent device plane — select -> restore -> attend per attention
        layer (``DevicePoolPlane.step_staged``).

        Between a layer's DSA selection and its attention, the stage
        callback below (host side) does, in order:

        1. FlashD2H write-back of THIS layer's just-appended KV (one fused
           save + flush) so DRAM stays a byte-exact superset of device KV
           before any restore of the layer;
        2. LRU residency for the layer's selections
           (``KVCacheManager.access_layer``), ONE fused FlashH2D load of
           the misses, and a fused scatter of the payloads into the plane's
           slots — the restore lands BEFORE the attention that selected the
           blocks, which is what makes ``drop_evicted_device_blocks``
           oracle-exact on this plane;
        3. physical drop of this access round's LRU evictions, except
           blocks the imminent attention selected (deferred one stage).

        Returns blocks loaded; per-layer restore bytes are accumulated in
        ``_staged_layer_bytes`` for the max(compute, transfer) overlap
        charge."""
        plane = self._plane_for(key, sts)
        tok_by_req = {st.req.req_id: st.out_tokens[-1] for st in sts}
        req_ids = [st.req.req_id for st in sts]
        sel_pairs: Dict[str, List[Tuple[int, int]]] = \
            {rid: [] for rid in req_ids}
        pending_evict: Dict[str, set] = {rid: set() for rid in req_ids}
        drop = self.eng.drop_evicted_device_blocks
        per_block_bytes = self._offload_block_bytes
        loads_total = [0]

        worker = self._stage_worker() if self._stage_async else None

        def stage_cb_sync(layer: int, sel: np.ndarray,
                          prev: Dict[str, int]) -> None:
            lidx = self._attn_layer_index(layer)
            if self.eng.decode_write_back:
                # FlashD2H phase for THIS layer only (per-layer pipeline)
                k, v = plane.new_token_kv(req_ids, prev,
                                          layers=[layer])[layer]
                self.kv_mgr.save_new_tokens_fused(lidx, {
                    rid: (prev[rid], k[i][:, None, :],
                          None if v is None else v[i][:, None, :])
                    for i, rid in enumerate(req_ids)})
                for rid in req_ids:
                    pool = self.kv_mgr.pools.get(rid)
                    if pool is not None:
                        pool.flush()
            if sel is None:          # DSA off: nothing to stage or restore
                return
            blocks_by_req: Dict[str, List[int]] = {}
            for st in sts:
                rid = st.req.req_id
                blocks = dsa_mod.selected_block_ids(sel[plane.rows[rid]])
                blocks_by_req[rid] = blocks
                sel_pairs[rid].extend((lidx, x) for x in blocks)
            missing_by_req, evicted_by_req = self.kv_mgr.access_layer(
                lidx, blocks_by_req, drain_evicted=drop)
            for rid, ev in evicted_by_req.items():
                pending_evict[rid].update(ev)
            loads_total[0] += sum(len(m) for m in missing_by_req.values())
            if missing_by_req:
                self._staged_layer_bytes[layer] = (
                    self._staged_layer_bytes.get(layer, 0)
                    + sum(len(m) for m in missing_by_req.values())
                    * per_block_bytes)
                payloads = self.kv_mgr.load_blocks_fused(lidx,
                                                         missing_by_req)
                if self.eng.decode_write_back:
                    plane.restore_blocks_fused(
                        layer, {rid: (missing_by_req[rid], k, v)
                                for rid, (k, v) in payloads.items()},
                        before_use=True)
            if drop:
                self._drop_pending_evictions(plane, sts, pending_evict,
                                             protect=(lidx, blocks_by_req))
            if self.staged_probe is not None:
                self.staged_probe(self, plane, layer, sts, blocks_by_req)

        def stage_cb_async(layer: int, sel: np.ndarray,
                           prev: Dict[str, int]) -> None:
            # The DISPATCH WINDOW: between the driver's np.asarray(idx)
            # and the attend dispatch that follows, nothing here may block
            # on the device (plane-contract: no-sync-in-dispatch-window).
            # The transfer guard turns a stray device->host sync into an
            # error on accelerator backends (on CPU device buffers ARE
            # host memory, so it cannot trip — the analyzer rule and the
            # host_syncs counter pin the invariant there).
            if sel is not None:
                plane.host_syncs += 1     # the driver's idx sync, the ONE
                                          # per-layer block we allow
            lidx = self._attn_layer_index(layer)
            with jax.transfer_guard_device_to_host("disallow"):
                if self.eng.decode_write_back:
                    # FlashD2H: dispatch the stripe gather, stage it on
                    # the worker; the dispatch thread never converts it
                    kv_dev = plane.new_token_kv_async(
                        req_ids, prev, layers=[layer])[layer]
                    self._stage_writeback_async(worker, lidx, req_ids,
                                                dict(prev), kv_dev)
                if sel is None:      # DSA off: nothing to stage or restore
                    return
                blocks_by_req: Dict[str, List[int]] = {}
                for st in sts:
                    rid = st.req.req_id
                    blocks = dsa_mod.selected_block_ids(
                        sel[plane.rows[rid]])
                    blocks_by_req[rid] = blocks
                    sel_pairs[rid].extend((lidx, x) for x in blocks)
                # LRU bookkeeping stays on the dispatch thread (pure host
                # work; keeps access order byte-identical to sync mode)
                missing_by_req, evicted_by_req = self.kv_mgr.access_layer(
                    lidx, blocks_by_req, drain_evicted=drop)
                for rid, ev in evicted_by_req.items():
                    pending_evict[rid].update(ev)
                loads_total[0] += sum(len(m)
                                      for m in missing_by_req.values())
                if missing_by_req:
                    self._staged_layer_bytes[layer] = (
                        self._staged_layer_bytes.get(layer, 0)
                        + sum(len(m) for m in missing_by_req.values())
                        * per_block_bytes)
                    # restore-before-use fence: this layer's outstanding
                    # write-back must land in DRAM before we gather from
                    # it (a 1-block LRU can miss on the block the current
                    # token was just appended to)
                    worker.fence(lidx)
                    payloads = self.kv_mgr.load_blocks_fused(
                        lidx, missing_by_req)
                    if self.eng.decode_write_back:
                        plane.restore_blocks_fused(
                            layer, {rid: (missing_by_req[rid], k, v)
                                    for rid, (k, v) in payloads.items()},
                            before_use=True)
                if drop:
                    self._drop_pending_evictions(
                        plane, sts, pending_evict,
                        protect=(lidx, blocks_by_req))
            if self.staged_probe is not None:
                worker.fence(lidx)   # probes compare device vs host pools
                self.staged_probe(self, plane, layer, sts, blocks_by_req)

        tr = self.tracer
        with tr.span("engine.decode"):
            logits, info, prev = plane.step_staged(
                self.params, tok_by_req,
                stage_cb_async if worker is not None else stage_cb_sync)
        if worker is not None:
            # iteration fence: every write-back has landed before sampling
            # reads logits and before finish/release can retire a DRAM pool
            with tr.span("worker.drain"):
                worker.drain()
        self.decode_step_calls += 1
        self.decode_tokens += len(sts)
        if drop:
            # evictions deferred past their own attend stage: safe to zero
            # now that every layer's compute has run
            with tr.span("engine.drop_pending"):
                self._drop_pending_evictions(plane, sts, pending_evict)
        for st in sts:
            row = plane.rows[st.req.req_id]
            st.last_logits = logits[row:row + 1]
            st.out_tokens.append(self._sample(st))
            if sel_pairs[st.req.req_id]:
                self.scheduler.observe_selection(st.req,
                                                 sel_pairs[st.req.req_id])
        return loads_total[0]

    # ------------------------------------------------------------------
    # Iteration
    # ------------------------------------------------------------------
    def step(self) -> Optional[BatchPlan]:
        """Run ONE engine iteration (hybrid batch).  Returns the executed
        plan, or None when no work remains.

        Order within the iteration: admit arrivals -> schedule (Algorithm 1
        working-set admission) -> prefill segments (layer-segmented prefill
        FlashD2H-saves each layer's KV to DRAM and evicts it from HBM) ->
        batched decode -> sample -> finish/release -> charge time.  On the
        staged plane (default) the decode phase interleaves per attention
        layer: select -> FlashD2H write-back of that layer's new KV -> DSA
        selection accounting (LRU residency; misses load via ONE fused
        FlashH2D, landing in the device plane's slots BEFORE the layer's
        attention) -> attend.  The fused planes run one forward and do
        write-back + selection accounting afterwards.

        Time is charged in engine-clock seconds: measured wall time up to
        the device finishing the iteration (``charge_real_time``, the
        accelerator default) or the analytic cost model; transfer stats
        are in bytes/calls/blocks with each moved block counted exactly
        once (see ``KVCacheManager``).  The iteration is the span
        ``engine.step``, its phases the spans below it
        (docs/architecture.md §11)."""
        tr = self.tracer
        with tr.span("engine.step") as sp:
            self._admit_arrivals()
            plan = self.scheduler.schedule()
            while not plan.decode_reqs and not plan.prefill_reqs:
                if not self._pending:
                    return None
                # idle until the next arrival
                self.now = max(self.now, self._pending[0].arrival_time)
                self._admit_arrivals()
                plan = self.scheduler.schedule()
            sp.set(iteration=self.iterations,
                   decode_rows=len(plan.decode_reqs),
                   prefill_rows=len(plan.prefill_reqs))
            t0 = time.perf_counter()
            iter_loads = 0
            self._staged_layer_bytes = {}
            mixed = self.hybrid is not None

            # --- prefill segments ------------------------------------------
            t_prefill = 0.0
            prefill_by_layer: Optional[List[float]] = None
            prefill_done: List[Request] = []
            iter_prefill_fp = 0          # HBM watermark, token-layer units,
                                         # summed over the iteration's batch
            scheduled_prefill = {req.req_id for req, _ in plan.prefill_reqs}
            if mixed:
                # ONE mixed iteration carries BOTH phases: decode groups and
                # prefill planes share one layer walk and one per-layer host
                # stage (hybrid_plane.HybridPlane); decode sampling and the
                # prefill epilogue already ran inside
                iter_loads, prefill_done, iter_prefill_fp, prefill_by_layer = \
                    self._mixed_iteration(plan)
            elif (self.eng.prefill_mode == "layer_segmented"
                    and self.eng.prefill_exec == "plane"):
                # with no scheduled prefill this still books the watermark
                # of rows parked mid-layer in the planes
                with tr.span("engine.prefill"):
                    t_prefill, prefill_done, iter_prefill_fp = \
                        self._prefill_plane_iteration(plan.prefill_reqs)
            else:
                with tr.span("engine.prefill"):
                    for req, inject in plan.prefill_reqs:
                        st = self.states[req.req_id]
                        if req.scheduled_time is None:
                            req.scheduled_time = self.now
                        if self.eng.prefill_mode == "layer_segmented":
                            if st.lp is None:
                                # whole-layer segments; inject
                                # (token-layers) decides how many run per
                                # iteration
                                self._start_layer_segmented(
                                    st, req.prompt_len)
                            # advance the scheduler cursor by `inject`
                            # token-layers (cursor = source of truth; >=1
                            # whole layer/iteration)
                            req.prefill_layer_tokens_done += max(
                                inject, req.prompt_len)
                            while (req.prefill_layer_tokens_done
                                   >= req.prompt_len
                                   and req.prefill_layer
                                   < self.cfg.num_layers):
                                req.prefill_layer += 1
                                req.prefill_layer_tokens_done -= \
                                    req.prompt_len
                            # run segments to catch the cursor up
                            done = False
                            ran = False
                            while (st.lp is not None and not done
                                   and st.lp.next_idx < req.prefill_layer):
                                done = self._run_layer_segment(st)
                                ran = True
                                t_prefill += cm.batched_prefill_time(
                                    self.hw, self.mc,
                                    [(req.prompt_len, req.prompt_len)],
                                    layers=1)
                            if ran:
                                # the whole layer's KV is live while
                                # segments run
                                iter_prefill_fp += hbm_footprint_tokens(
                                    req.prompt_len, "layer_segmented",
                                    self.cfg.num_layers)
                        else:
                            done = self._run_chunked_prefill(st, inject)
                            ctx = req.prefill_tokens_done
                            t_prefill += cm.prefill_time(
                                self.hw, self.mc, inject, ctx)
                            iter_prefill_fp += hbm_footprint_tokens(
                                req.prompt_len, "chunked",
                                self.cfg.num_layers,
                                req.prefill_tokens_done)
                        if done:
                            prefill_done.append(req)
            # chunked prefill keeps every processed token's KV (all layers)
            # resident BETWEEN iterations too — count unscheduled holders
            for st in self.states.values():
                if (st.chunk_ctx is not None
                        and st.req.req_id not in scheduled_prefill):
                    iter_prefill_fp += hbm_footprint_tokens(
                        st.req.prompt_len, "chunked", self.cfg.num_layers,
                        st.req.prefill_tokens_done)
            self.prefill_hbm_peak_tokens = max(self.prefill_hbm_peak_tokens,
                                               iter_prefill_fp)
            for req in prefill_done:
                st = self.states[req.req_id]
                req.phase = Phase.DECODE
                req.prefill_tokens_done = req.prompt_len
                st.out_tokens.append(self._sample(st))       # the first token
                req.generated = 1
                req.first_token_time = self.now   # charged below
                req.token_times.append(self.now)

            # --- decode steps ----------------------------------------------
            if mixed:
                pass       # decode rode the mixed iteration above
            elif self.eng.batched_decode:
                # ONE scheduler-planned batched forward over all running decode
                # requests (grouped only when per-request extra shapes differ,
                # e.g. whisper encoder lengths)
                groups: Dict[Tuple, List[_ReqState]] = {}
                for req in plan.decode_reqs:
                    st = self.states[req.req_id]
                    if st.group_key is None:
                        st.group_key = self._decode_group_key(st)
                    groups.setdefault(st.group_key, []).append(st)
                for key, sts in groups.items():
                    if self.eng.decode_plane == "staged":
                        # opens engine.decode around its layer walk
                        iter_loads += self._decode_batch_staged(key, sts)
                        continue
                    with tr.span("engine.decode"):
                        if self.eng.decode_plane == "persistent":
                            iter_loads += self._decode_batch_persistent(
                                key, sts)
                        else:
                            iter_loads += self._decode_batch(sts)
            else:
                with tr.span("engine.decode"):
                    for req in plan.decode_reqs:
                        st = self.states[req.req_id]
                        _, loads = self._decode_one(st)
                        iter_loads += loads
            with tr.span("engine.finish"):
                for req in plan.decode_reqs:
                    req.generated += 1
                    req.token_times.append(self.now)
                    if req.generated >= req.max_new_tokens:
                        req.finish_time = self.now
                        self.scheduler.finish_request(req)
                        self.kv_mgr.release(req.req_id)
                        plane = self._req_plane.pop(req.req_id, None)
                        if plane is not None:
                            plane.release(req.req_id)   # device slots reusable

            # --- charge time -------------------------------------------------
            if self.eng.charge_real_time:
                self._wait_device()
                t_iter = time.perf_counter() - t0
            else:
                attended = min(self.cfg.dsa.token_budget, 1 << 30) \
                    if self.cfg.dsa.enabled else 4096
                if mixed:
                    # one shared walk: per layer, the union of decode+prefill
                    # compute overlaps the ONE fused transfer stage
                    n_shards = (self.plane_mesh.model_size
                                if self.plane_mesh is not None else 1)
                    ag_bytes = None
                    if n_shards > 1 and plan.decode_reqs:
                        sel_bytes = (len(plan.decode_reqs)
                                     * self.geom.num_kv_heads
                                     * self.cfg.dsa.top_k_blocks * 4)
                        ag_bytes = [
                            sel_bytes if M.layer_kind(self.cfg, l) == "attn"
                            else 0 for l in range(self.cfg.num_layers)]
                    t_iter = cm.mixed_iteration_time(
                        self.hw, self.mc, len(plan.decode_reqs), attended,
                        [self._staged_layer_bytes.get(l, 0)
                         for l in range(self.cfg.num_layers)],
                        prefill_time_by_layer=prefill_by_layer,
                        n_shards=n_shards, allgather_bytes_by_layer=ag_bytes)
                elif (plan.decode_reqs and self.eng.batched_decode
                        and self.eng.decode_plane == "staged"):
                    # staged pipeline: per layer, H2D restores overlap compute
                    # -> charge max(compute, transfer) per layer, not the sum.
                    # Sharded plane: each shard restores only its own slots
                    # (per-shard transfer), plus one all-gather of the selected
                    # block ids per attention layer (the host needs GLOBAL ids
                    # for the LRU and the FlashH2D staging).
                    n_shards = (self.plane_mesh.model_size
                                if self.plane_mesh is not None else 1)
                    ag_bytes = None
                    if n_shards > 1:
                        sel_bytes = (len(plan.decode_reqs)
                                     * self.geom.num_kv_heads
                                     * self.cfg.dsa.top_k_blocks * 4)
                        ag_bytes = [
                            sel_bytes if M.layer_kind(self.cfg, l) == "attn"
                            else 0 for l in range(self.cfg.num_layers)]
                    t_dec = cm.overlapped_decode_time(
                        self.hw, self.mc, max(len(plan.decode_reqs), 1),
                        attended,
                        [self._staged_layer_bytes.get(l, 0)
                         for l in range(self.cfg.num_layers)],
                        n_shards=n_shards, allgather_bytes_by_layer=ag_bytes)
                    t_iter = t_dec + t_prefill
                else:
                    t_dec = cm.decode_time(
                        self.hw, self.mc, max(len(plan.decode_reqs), 1),
                        attended) if plan.decode_reqs else 0.0
                    t_load = cm.fused_transfer_time(
                        self.hw,
                        iter_loads * self._offload_block_bytes) \
                        if iter_loads else 0.0
                    t_iter = t_dec + t_load + t_prefill
            self.now += max(t_iter, 1e-9)
            # stamp the times that were logically produced "at end of
            # iteration"
            for req in plan.decode_reqs + [r for r, _ in plan.prefill_reqs]:
                if req.token_times and req.token_times[-1] != self.now:
                    req.token_times[-1] = self.now
                if req.first_token_time is not None and req.generated == 1:
                    req.first_token_time = self.now
                if req.finish_time is not None and req.phase == Phase.FINISHED:
                    req.finish_time = self.now
            self.loads_per_iter.append(iter_loads)
            self.iterations += 1
            # gauges: outside the dispatch windows by construction (the planes
            # and the worker have all returned / been drained)
            waiting, running = self.scheduler.queue_depths()
            self._g_queue.set(waiting)
            self._g_running.set(running)
            self._g_batch_decode.set(len(plan.decode_reqs))
            self._g_batch_prefill.set(len(plan.prefill_reqs))
            self._g_ws_decode.set(plan.ws_decode_bytes)
            self._g_ws_prefill.set(plan.ws_prefill_bytes)
            self._g_hbm_used.set(self.kv_mgr.hbm_used_bytes())
        self._h_iter.observe(sp.dur)
        return plan

    def _wait_device(self) -> None:
        """Block until the device has finished everything the iteration
        dispatched.  Sampling already read the logits of rows that
        produced a token; prefill rows parked mid-walk may still have
        work queued on their plane's residual stream and decode pools."""
        with self.tracer.span("engine.wait_device"):
            jax.block_until_ready(
                [p.state for p in self.planes.values()]
                + [p.hidden for p in self.prefill_planes.values()])

    def run(self, max_iters: int = 10_000) -> ServingMetrics:
        """Step until idle (every submitted request finished) or
        ``max_iters`` iterations, then return aggregate metrics (TTFT/TBT
        in engine-clock seconds, token throughput in tokens/s)."""
        try:
            for _ in range(max_iters):
                if self.step() is None:
                    break
        finally:
            self.close()        # joins the host-stage worker; errors from
                                # outstanding write-back jobs surface here
        return compute_metrics([st.req for st in self.states.values()],
                               max(self.now, 1e-9))

    # ------------------------------------------------------------------
    def transfer_stats(self) -> TransferStats:
        return self.kv_mgr.total_stats()

    # ------------------------------------------------------------------
    # Observability surface (src/repro/obs, docs/architecture.md §11)
    # ------------------------------------------------------------------
    def metrics_snapshot(self) -> Dict[str, float]:
        """One flat dict over every subsystem's counters — the single obs
        surface (naming scheme: obs/metrics.py).  Registry instruments
        (sched.* gauges, engine.iteration_s histogram, the counted spans'
        ``*_s``/``*_calls``/``*_self_s``) merge with derived reads of the
        hot counters, which stay where the hot paths already increment
        them.  Blocking-free but not for dispatch windows (the analyzer
        flags it there)."""
        self._g_hbm_used.set(self.kv_mgr.hbm_used_bytes())
        snap = self.metrics.snapshot()
        ts = self.kv_mgr.total_stats()
        snap.update({
            "kv.h2d_calls": float(ts.h2d_calls),
            "kv.h2d_blocks": float(ts.h2d_blocks),
            "kv.h2d_bytes": float(ts.h2d_bytes),
            "kv.d2h_calls": float(ts.d2h_calls),
            "kv.d2h_blocks": float(ts.d2h_blocks),
            "kv.d2h_bytes": float(ts.d2h_bytes),
            "kv.hits": float(ts.hits),
            "kv.misses": float(ts.misses),
            "kv.evictions": float(ts.evictions),
            "kv.hbm_budget_bytes": float(self.eng.hbm_budget_bytes),
            # wire bytes one (layer, block) transfer moves at the offload
            # tier's stored size (int8 + scales when offload_quant="int8")
            "kv.offload_block_bytes": float(self._offload_block_bytes),
            "engine.iterations": float(self.iterations),
            "engine.now_s": float(self.now),
            "engine.decode_step_calls": float(self.decode_step_calls),
            "engine.decode_tokens": float(self.decode_tokens),
            "engine.stack_calls": float(self.stack_calls),
            "engine.prefill_launches": float(self.prefill_launches),
            "engine.admit_embed_launches": float(self.admit_embed_launches),
            "engine.prefill_hbm_peak_tokens":
                float(self.prefill_hbm_peak_tokens),
        })
        host_syncs = d2h_rb = dropped = restored = before = steps = 0
        sync_s = stage_s = 0.0
        fns_seen: Dict[int, Any] = {}      # StageFns are shared per-config;
        for plane in self.planes.values():  # dedup before summing traces
            host_syncs += plane.host_syncs
            d2h_rb += plane.d2h_readback_bytes
            dropped += plane.blocks_dropped
            restored += plane.blocks_restored
            before += plane.blocks_restored_before_use
            steps += plane.steps
            sync_s += plane.dispatch_sync_s
            stage_s += plane.host_stage_s
            fns_seen[id(plane.staged_fns)] = plane.staged_fns
        for pplane in self.prefill_planes.values():
            fns_seen[id(pplane.fns)] = pplane.fns
        if self.hybrid is not None:
            sync_s += self.hybrid.dispatch_sync_s
            stage_s += self.hybrid.host_stage_s
        snap.update({
            "plane.count": float(len(self.planes)),
            "plane.steps": float(steps),
            "plane.host_syncs": float(host_syncs),
            "plane.d2h_readback_bytes": float(d2h_rb),
            "plane.blocks_dropped": float(dropped),
            "plane.blocks_restored": float(restored),
            "plane.blocks_restored_before_use": float(before),
            "plane.trace_count": float(sum(f.trace_count
                                           for f in fns_seen.values())),
            "plane.dispatch_sync_s": sync_s,
            "plane.host_stage_s": stage_s,
        })
        w = self._worker
        live = w is not None and not w.closed
        snap.update({
            "worker.jobs_run": float(self.worker_jobs_run
                                     + (w.jobs_run if live else 0)),
            "worker.busy_s": (self.worker_busy_s
                              + (w.busy_s if live else 0.0)),
        })
        return snap

    def metrics_prometheus(self) -> str:
        """Prometheus text exposition of :meth:`metrics_snapshot`."""
        snap = self.metrics_snapshot()
        reg_keys = set(self.metrics.snapshot())
        extra = {k: v for k, v in snap.items() if k not in reg_keys}
        return self.metrics.prometheus_text(extra)

    def stage_overlap_measured(self) -> Optional[float]:
        """Counter instrument for achieved async overlap: the fraction of
        host-stage work that ran on the HostStageWorker thread,
        ``busy_s / (busy_s + dispatch host_stage_s)``, both read from
        the ``worker.job`` / ``plane.host_stage`` spans.  ``None`` when no
        worker job ran (sync mode, or no staged decode)."""
        w = self._worker
        busy = self.worker_busy_s + (w.busy_s if w is not None
                                     and not w.closed else 0.0)
        if busy <= 0.0:
            return None
        stage_s = sum(p.host_stage_s for p in self.planes.values())
        if self.hybrid is not None:
            stage_s += self.hybrid.host_stage_s
        return busy / (busy + stage_s)
