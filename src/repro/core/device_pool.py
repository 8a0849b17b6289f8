"""Persistent shared device decode pool — the engine's decode data plane.

Before this module, every batched decode iteration re-stacked each running
request's per-layer KV pool into a fresh padded device pool
(``model.stack_decode_states``) and unstacked it afterwards: O(batch x pool)
HBM copies per generated token, exactly the fragmented KV-cache movement
SparseServe's hierarchical HBM/DRAM design is meant to eliminate.

``DevicePoolPlane`` replaces that round-trip with ONE persistent padded
paged pool per layer that lives on device for the lifetime of the decode
batch:

* **Slot lifecycle** — a request joining decode is admitted once
  (``admit``: its prefill-built pools are copied into a free batch row);
  while it decodes, NOTHING is copied per iteration; when it finishes,
  ``release`` frees the row for the next admitted request to reuse.  Freed
  rows are reused lowest-first so replaying a trace is deterministic.
* **Bucketed jit** — the batched ``model.decode_step`` is jit-compiled at
  bucketed shapes (batch rows from ``BucketingPolicy.batch_buckets``, block
  capacity rounded up to ``block_bucket``), so steady-state decode is one
  cached compiled call per bucket instead of a retrace (or an eager
  dispatch storm) per iteration.  Requests scheduled this iteration are
  selected with a ``step_mask`` argument — occupancy changes do NOT change
  shapes, hence do not retrace.  Pool buffers are donated to the jitted
  call on accelerator backends so XLA updates them in place.
* **Staged per-layer pipeline** — ``step_staged`` runs the same forward as
  per-layer select -> [host restore] -> attend stage jits
  (``_StagedDecodeFns``), giving the serving engine a window between a
  layer's DSA selection and its attention in which fused FlashH2D restores
  land in the device pool BEFORE use — the structure that makes
  block-granular HBM eviction oracle-exact (the engine's default
  ``decode_plane="staged"``).  Launches are O(num_layers) per iteration;
  traces stay bounded by (stage kinds x shape buckets).
* **FlashH2D/D2H wiring** — ``restore_blocks`` scatters fused-gather
  payloads from ``KVCacheManager.load_blocks_fused`` directly into device
  slots (the jnp scatter here is the interpret-mode stand-in for
  ``repro.kernels.scatter_blocks``; ``gather_row_blocks`` mirrors
  ``repro.kernels.gather_blocks``), and ``drop_blocks`` zeroes evicted
  blocks so HBM eviction actually destroys device-resident data.  Block
  metadata is never dropped: DSA scoring stays exact while block *data*
  moves through the hierarchy.

The legacy stack/unstack path survives behind
``EngineConfig.decode_plane="stacked"`` as the equivalence oracle.
"""
from __future__ import annotations

import bisect
import dataclasses
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.obs import tracing

# Route row-slot block movement through the Pallas kernels
# (kernels/gather_blocks.py / scatter_blocks.py, _hkv variants).  Default is
# the jnp path.  The kernels compile through Mosaic on TPU and run
# interpreted on CPU (correct but slow); whether the compiled DMA stream
# beats the jnp gather on the chip is not measured yet, so the switch stays
# opt-in until the benchmark decides it.  Read once, at import.
USE_PALLAS_PLANE = os.environ.get("REPRO_PLANE_KERNEL", "0") == "1"


@dataclasses.dataclass(frozen=True)
class BucketingPolicy:
    """Shape-bucketing policy for the jitted batched decode step and the
    batched prefill plane.

    batch_buckets: allowed padded batch-row counts; demand beyond the last
        bucket doubles it (8 -> 16 -> 32 ...).
    block_bucket: pool block capacity is rounded UP to a multiple of this,
        so admitting a slightly-longer request reuses the compiled bucket
        instead of retracing at nb, nb+1, nb+2, ...
    token_bucket: prefill-plane token-length grid — segment windows (and
        row capacities) round up to token_bucket, then DOUBLE (64, 128,
        256, ...), so the number of distinct compiled token lengths is
        logarithmic in the longest prompt.
    """
    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    block_bucket: int = 8
    token_bucket: int = 64

    def bucket_batch(self, n: int) -> int:
        for b in self.batch_buckets:
            if b >= n:
                return b
        b = self.batch_buckets[-1]
        while b < n:
            b *= 2
        return b

    def bucket_blocks(self, nb: int) -> int:
        bb = self.block_bucket
        return max(bb, -(-nb // bb) * bb)

    def bucket_tokens(self, n: int) -> int:
        t = self.token_bucket
        while t < n:
            t *= 2
        return t


class StageFns:
    """Shared plumbing for per-stage jit registries (the staged decode
    plane's ``_StagedDecodeFns`` and the prefill plane's ``_PrefillFns``):
    ``wrap`` builds a jitted stage whose trace-time side effect counts XLA
    compiles and whose call-time hook records (stage, arg pytree
    structure, leaf shapes/dtypes) — so ``trace_count ==
    len(shape_signatures)`` is the cache-hit invariant tests assert for
    every registry.  The pytree STRUCTURE is part of the signature because
    optional args (enc_kv, ctx, DSA idx) may be None: two calls whose
    leaves coincide but whose structures differ trace separately.
    Donation applies on accelerator backends only (CPU buffers are not
    donatable and would only emit a warning per compile).

    Contract metadata: every registry retains each stage's RAW (unjitted)
    callable (``raw_fns``) and the abstract shapes of its first call
    (``abstract_args``, a ShapeDtypeStruct pytree) — what the plane
    sharding-leak pass (tools/analysis, docs/architecture.md §8) lowers
    via ``jax.make_jaxpr`` to check collectives and out-spec replication
    against ``repro.core.plane_contract.sharding_rules``.

    Each stage's program is named ``<jit_prefix>_<stage>`` (the device
    trace shows ``jit_decode_select``, ``jit_prefill_attn``, ...), and each
    launch opens the span ``stage.<stage>`` around the dispatch."""

    contract_protocol = "stage-registry"
    jit_prefix = "stage"

    def __init__(self):
        self.trace_count = 0
        self.calls = 0                      # jitted stage launches, total
        self.shape_signatures: set = set()
        self.raw_fns: Dict[str, Any] = {}   # stage -> unjitted callable
        self.abstract_args: Dict[str, Tuple] = {}   # stage -> SDS pytree
        self.donated: Dict[str, Tuple[int, ...]] = {}  # stage -> donate args
        self._donate_ok = jax.default_backend() != "cpu"
        # whether donation is actually armed on this backend (CPU buffers
        # are not donatable; declaring them would only warn per compile)
        self.donate_active = self._donate_ok

    def wrap(self, stage, f, donate=()):
        self.raw_fns[stage] = f
        self.donated[stage] = tuple(donate)

        def fn(*args):
            self.trace_count += 1           # trace-time side effect only
            return f(*args)
        fn.__name__ = fn.__qualname__ = (
            f"{self.jit_prefix}_{stage}".replace("-", "_"))
        jitted = jax.jit(fn,
                         donate_argnums=donate if self._donate_ok else ())
        span_name = "stage." + stage

        def call(*args):
            with tracing.DEFAULT.span(span_name):
                self.calls += 1
                leaves, treedef = jax.tree.flatten(args)
                self.shape_signatures.add(
                    (stage, str(treedef),
                     tuple((tuple(jnp.shape(leaf)),
                            str(jnp.result_type(leaf)))
                           for leaf in leaves)))
                if stage not in self.abstract_args:
                    self.abstract_args[stage] = jax.tree.map(
                        lambda leaf: jax.ShapeDtypeStruct(
                            jnp.shape(leaf), jnp.result_type(leaf)), args)
                return jitted(*args)
        return call


class _DecodeFn:
    """One jit-compiled batched ``decode_step`` per (model config, impl).

    Shared across every ``DevicePoolPlane`` (and engine instance) built for
    the same config so jax's compilation cache is hit across engines.
    ``trace_count`` increments via a Python side effect that only runs at
    trace time — the exact number of XLA compilations — and
    ``shape_signatures`` records every distinct input-shape signature seen,
    so ``trace_count == len(shape_signatures)`` is the cache-hit invariant
    tests assert (bounded by the bucket count for a bucketed workload).
    """

    def __init__(self, cfg, attn_impl: str):
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.trace_count = 0
        self.calls = 0
        self.shape_signatures: set = set()
        # donation lets XLA reuse the pool buffers in place; CPU buffers are
        # not donatable and would only emit a warning per compile
        donate = (3,) if jax.default_backend() != "cpu" else ()

        def fn(params, tokens, step_mask, state):
            self.trace_count += 1        # trace-time side effect only
            return M.decode_step(params, cfg, tokens, state,
                                 attn_impl=attn_impl, return_info=True,
                                 step_mask=step_mask)

        self._jit = jax.jit(fn, donate_argnums=donate)

    @staticmethod
    def signature(state: Dict) -> Tuple:
        return tuple((tuple(leaf.shape), str(leaf.dtype))
                     for leaf in jax.tree.leaves(state))

    def __call__(self, params, tokens, step_mask, state):
        self.calls += 1
        self.shape_signatures.add(self.signature(state))
        return self._jit(params, tokens, step_mask, state)


# keyed STRUCTURALLY (dataclass repr covers every field, nested configs
# included) so value-equal configs share one _DecodeFn — and hence one XLA
# compile cache — instead of leaking an entry per fresh-but-equal object.
# Entries live for the process (bounded by the number of distinct configs).
_DECODE_FNS: Dict[Tuple[str, str], _DecodeFn] = {}


def decode_fn_for(cfg, attn_impl: str) -> _DecodeFn:
    key = (repr(cfg), attn_impl)
    if key not in _DECODE_FNS:
        _DECODE_FNS[key] = _DecodeFn(cfg, attn_impl)
    return _DECODE_FNS[key]


class _StagedDecodeFns(StageFns):
    """Per-stage jits for the STAGED decode pipeline: embed, per-layer
    select / attend (attention layers), per-layer recurrent (mamba/rwkv),
    and the final logits stage.

    Every stage function takes a LAYER's params pytree, so one trace serves
    all structurally identical layers — per-iteration jitted LAUNCHES are
    O(num_layers) but TRACES stay bounded by (distinct layer structures x
    shape buckets), the same cache-hit invariant as the fused ``_DecodeFn``:
    ``trace_count == len(shape_signatures)`` (see ``StageFns``; pool
    buffers are donated so XLA updates them in place on accelerators).
    """

    contract_protocol = "staged-decode"
    jit_prefix = "decode"

    def __init__(self, cfg, attn_impl: str, plane_mesh=None):
        super().__init__()
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.plane_mesh = plane_mesh
        wrap = self.wrap

        # with a plane_mesh, embed hands the first layer the same
        # replicated sharding every attend stage hands the next layer, so
        # layer 0's select/attend hit the jit cache of the others
        pin = plane_mesh.replicate if plane_mesh is not None else (
            lambda x: x)
        self.embed = wrap("embed",
                          lambda params, tokens:
                          pin(M.decode_embed(params, cfg, tokens)))
        # select consumes and returns the layer's pool cache (arg 2): donate
        # so the append/meta update reuses the buffer instead of copying the
        # full pool per layer per iteration.  With a plane_mesh the pool-
        # touching core of both stages runs under shard_map (KV-head- or
        # block-sharded slots; see launch/plane_mesh.py).
        self.select = wrap("select",
                           lambda p, x, cache, cur_len, mask:
                           M.decode_select_layer(p, cfg, x, cache, cur_len,
                                                 step_mask=mask,
                                                 plane_mesh=plane_mesh),
                           donate=(2,))
        self.attend = wrap("attend",
                           lambda p, x, q, cache, cur_len, idx, valid, enc:
                           M.decode_attend_layer(p, cfg, x, q, cache,
                                                 cur_len, idx, valid,
                                                 enc_kv=enc,
                                                 attn_impl=attn_impl,
                                                 plane_mesh=plane_mesh))
        self._recurrent = {
            kind: wrap("recurrent-" + kind,
                       lambda p, x, cache, mask, kind=kind:
                       M.decode_recurrent_layer(p, cfg, kind, x, cache,
                                                step_mask=mask),
                       donate=(2,))
            for kind in ("mamba", "rwkv")}
        self.logits = wrap("logits",
                           lambda params, x, cur_len, mask:
                           M.decode_logits(params, cfg, x, cur_len,
                                           step_mask=mask))


_STAGED_FNS: Dict[Tuple, _StagedDecodeFns] = {}


def staged_fns_for(cfg, attn_impl: str, plane_mesh=None) -> _StagedDecodeFns:
    key = (repr(cfg), attn_impl,
           None if plane_mesh is None else plane_mesh.key())
    if key not in _STAGED_FNS:
        _STAGED_FNS[key] = _StagedDecodeFns(cfg, attn_impl, plane_mesh)
    return _STAGED_FNS[key]


def gather_row_blocks(pool: jax.Array, row: int, blocks) -> jax.Array:
    """Gather `blocks` of one batch row: (B,H,NB,bs,D) -> (H,K,bs,D).

    FlashH2D direction; with ``REPRO_PLANE_KERNEL=1`` this runs the Pallas
    ``gather_blocks_hkv`` kernel (one launch, one block-granular DMA per
    grid step), otherwise the equivalent jnp gather."""
    idx = jnp.asarray(blocks, jnp.int32)
    if USE_PALLAS_PLANE:
        from repro.kernels import ops
        return ops.gather_blocks_hkv(pool[row], idx)
    return pool[row][:, idx]


def scatter_row_blocks(pool: jax.Array, row: int, blocks,
                       payload: jax.Array) -> jax.Array:
    """Scatter `payload` (H,K,bs,D) into `blocks` of one batch row in place.

    FlashD2H / H2D-restore direction: whole-block granularity, untouched
    blocks preserved.  With ``REPRO_PLANE_KERNEL=1`` this runs the Pallas
    ``scatter_blocks_hkv`` kernel (pool aliased in place), otherwise the
    equivalent jnp scatter."""
    idx = jnp.asarray(blocks, jnp.int32)
    payload = payload.astype(pool.dtype)
    if USE_PALLAS_PLANE:
        from repro.kernels import ops
        new_row = ops.scatter_blocks_hkv(pool[row], payload, idx)
    else:
        new_row = pool[row].at[:, idx].set(payload)
    return pool.at[row].set(new_row)


class DevicePoolPlane:
    """Persistent padded decode state for one group of batched requests.

    Requests whose non-pool decode state agrees in every per-request shape
    (the engine's ``_decode_group_key``) share one plane; pools pad along
    the block axis to the bucketed capacity.  The plane OWNS its requests'
    decode state: after ``admit`` the engine must not keep using the
    per-request state it passed in (``extract`` hands a copy back).
    """

    def __init__(self, cfg, policy: Optional[BucketingPolicy] = None,
                 attn_impl: str = "ref", plane_mesh=None):
        if plane_mesh is not None and not cfg.dsa.enabled:
            raise NotImplementedError(
                "sharded decode plane requires DSA (cfg.dsa.enabled): the "
                "context-parallel attend has no dense fallback")
        self.cfg = cfg
        self.policy = policy or BucketingPolicy()
        self.attn_impl = attn_impl
        self.plane_mesh = plane_mesh
        self.decode_fn = decode_fn_for(cfg, attn_impl)
        self.staged_fns = staged_fns_for(cfg, attn_impl, plane_mesh)
        self.state: Optional[Dict] = None
        self.b_cap = 0
        self.nb_cap = 0
        self.rows: Dict[str, int] = {}            # req_id -> batch row
        self.row_layout: Dict[str, List[Optional[int]]] = {}  # per-layer nb
        self.cur_host: Dict[str, int] = {}        # host mirror of cur_len
        self._free: List[int] = []                # sorted free rows
        self._ever_used: set = set()
        self.buckets_seen: set = set()            # (b_cap, nb_cap) stepped at
        self.steps = 0
        self.admits = 0
        self.rows_reused = 0
        self.blocks_dropped = 0
        self.blocks_restored = 0
        self.blocks_restored_before_use = 0   # landed before the attention
                                              # that selected them (staged)
        self.host_syncs = 0              # async mode: per-layer np.asarray(
                                         # selected ids) — the ONLY blocking
                                         # sync the dispatch thread pays
        self.d2h_readback_bytes = 0      # stripe bytes read back by
                                         # new_token_kv[_async]: pins that
                                         # write-back never copies pool-sized
                                         # buffers to host
        self.stage_timeline: List[Tuple[int, float, float]] = []
        # last iteration's (layer, idx_sync_s, host_stage_s) per stage_cb
        self.dispatch_sync_s = 0.0       # accumulated idx-sync and host-
        self.host_stage_s = 0.0          # stage time, all iterations: the
                                         # plane.idx_sync / plane.host_stage
                                         # spans' own clock reads
        self.tracer = tracing.DEFAULT    # the engine hands its own
        # per-layer param slices for the staged pipeline, cached per params
        # OBJECT (the entry's strong ref keeps the id() stable).  Lives on
        # the plane — not the process-global _StagedDecodeFns — so retired
        # engines' params are reclaimable once their planes go away.
        self._layer_params_cache: Optional[Tuple[Dict, List[Dict]]] = None

    def _layer_params(self, params: Dict) -> List[Dict]:
        """Per-layer param slices (``get_layer``), computed once per params
        object: with stacked layer params each slice is a device op, so
        doing it per layer per iteration would bloat the launch count."""
        hit = self._layer_params_cache
        if hit is not None and hit[0] is params:
            return hit[1]
        layers = [M.get_layer(params, i) for i in range(self.cfg.num_layers)]
        self._layer_params_cache = (params, layers)
        return layers

    # -- capacity ----------------------------------------------------------

    def _alloc(self, template: Dict, b_cap: int, nb_cap: int) -> Dict:
        caches: List[Any] = []
        for c in template["caches"]:
            if M.is_pool_cache(c):
                caches.append({
                    key: jnp.zeros((b_cap,) + v.shape[1:2] + (nb_cap,)
                                   + v.shape[3:], v.dtype)
                    for key, v in c.items()})
            else:
                caches.append(jax.tree.map(
                    lambda x: jnp.zeros((b_cap,) + x.shape[1:], x.dtype), c))
        extra = (jax.tree.map(
            lambda x: jnp.zeros((b_cap,) + x.shape[1:], x.dtype),
            template["extra"]) if template["extra"] else {})
        cur_len = jnp.zeros((b_cap,), jnp.int32)
        if self.plane_mesh is not None:
            # the logits stage hands cur_len back replicated on the mesh:
            # start it there so the first step's stages hit the same jit
            # cache entries as every later step's
            cur_len = self.plane_mesh.replicate(cur_len)
        return {"caches": caches, "cur_len": cur_len, "extra": extra}

    def _grow(self, b_cap: int, nb_cap: int) -> None:
        db = b_cap - self.b_cap
        dnb = nb_cap - self.nb_cap

        def pad_pool(v):
            return jnp.pad(v, ((0, db), (0, 0), (0, dnb))
                           + ((0, 0),) * (v.ndim - 3))

        def pad_rows(v):
            return jnp.pad(v, ((0, db),) + ((0, 0),) * (v.ndim - 1))

        st = self.state
        st["caches"] = [
            ({key: pad_pool(v) for key, v in c.items()}
             if M.is_pool_cache(c) else jax.tree.map(pad_rows, c))
            for c in st["caches"]]
        st["cur_len"] = pad_rows(st["cur_len"])
        if st["extra"]:
            st["extra"] = jax.tree.map(pad_rows, st["extra"])
        for r in range(self.b_cap, b_cap):
            bisect.insort(self._free, r)

    def _ensure_capacity(self, template: Dict, need_rows: int,
                         need_nb: int) -> None:
        b_cap = max(self.b_cap, self.policy.bucket_batch(need_rows))
        nb_cap = max(self.nb_cap, self.policy.bucket_blocks(need_nb))
        if self.plane_mesh is not None:
            # block-sharded pools must divide the model axis evenly
            nb_cap = self.plane_mesh.round_blocks(self.cfg, nb_cap)
        if self.state is None:
            self.state = self._alloc(template, b_cap, nb_cap)
            self._free = list(range(b_cap))
        elif b_cap != self.b_cap or nb_cap != self.nb_cap:
            self._grow(b_cap, nb_cap)
        self.b_cap, self.nb_cap = b_cap, nb_cap

    # -- slot lifecycle ----------------------------------------------------

    def admit(self, req_id: str, state: Dict) -> int:
        """Copy one request's DecodeState (B=1, list-mode caches) into a
        free batch row; returns the row.  The ONLY full-pool copy in a
        request's decode lifetime."""
        with self.tracer.span("plane.admit", req=req_id):
            if req_id in self.rows:
                raise ValueError(f"{req_id} already admitted")
            if not isinstance(state["caches"], list):
                raise ValueError("DevicePoolPlane requires list-mode caches")
            if int(state["cur_len"].shape[0]) != 1:
                raise ValueError("admit expects a single-request state (B=1)")
            nbs = [c["k"].shape[2] if M.is_pool_cache(c) else None
                   for c in state["caches"]]
            nb_req = max((n for n in nbs if n is not None), default=0)
            self._ensure_capacity(state, len(self.rows) + 1, nb_req)
            row = self._free.pop(0)
            if row in self._ever_used:
                self.rows_reused += 1
            self._ever_used.add(row)
            st = self.state
            for l, c in enumerate(state["caches"]):
                if M.is_pool_cache(c):
                    for key, v in c.items():
                        st["caches"][l][key] = \
                            st["caches"][l][key].at[row, :, :nbs[l]].set(v[0])
                else:
                    st["caches"][l] = jax.tree.map(
                        lambda dst, src: dst.at[row].set(src[0]),
                        st["caches"][l], c)
            st["cur_len"] = st["cur_len"].at[row].set(state["cur_len"][0])
            if state["extra"]:
                st["extra"] = jax.tree.map(
                    lambda dst, src: dst.at[row].set(src[0]),
                    st["extra"], state["extra"])
            self.rows[req_id] = row
            self.row_layout[req_id] = nbs
            self.cur_host[req_id] = int(state["cur_len"][0])
            self.admits += 1
            return row

    def release(self, req_id: str) -> int:
        """Free a finished request's row (device slots become reusable —
        this is where a finished request's device memory is dropped)."""
        row = self.rows.pop(req_id)
        self.row_layout.pop(req_id)
        self.cur_host.pop(req_id)
        bisect.insort(self._free, row)
        return row

    # -- iteration ---------------------------------------------------------

    def step(self, params: Dict, token_by_req: Dict[str, int]
             ) -> Tuple[jax.Array, Dict, Dict[str, int]]:
        """ONE jitted batched forward over the plane's padded rows.

        token_by_req: the scheduled requests' input tokens.  Unscheduled
        (or free) rows are masked out via ``step_mask`` — their pools,
        recurrent states and cur_len come back unchanged, and occupancy
        changes never retrace.  Returns (logits (B_cap, V), info,
        {req_id: cur_len BEFORE the step}) — the pre-step lengths are the
        positions where this step's KV landed (FlashD2H write-back needs
        them)."""
        tokens = np.zeros((self.b_cap,), np.int32)
        mask = np.zeros((self.b_cap,), bool)
        for rid, tok in token_by_req.items():
            row = self.rows[rid]
            tokens[row] = tok
            mask[row] = True
        logits, new_state, info = self.decode_fn(
            params, jnp.asarray(tokens), jnp.asarray(mask), self.state)
        self.state = new_state
        self.buckets_seen.add((self.b_cap, self.nb_cap))
        self.steps += 1
        prev = {rid: self.cur_host[rid] for rid in token_by_req}
        for rid in token_by_req:
            self.cur_host[rid] += 1
        return logits, info, prev

    def step_staged(self, params: Dict, token_by_req: Dict[str, int],
                    stage_cb=None) -> Tuple[jax.Array, Dict, Dict[str, int]]:
        """Staged per-layer pipeline: select -> [host restore] -> attend.

        Runs the decode forward ONE layer at a time through the per-stage
        jits (``_StagedDecodeFns``).  For each attention layer *l*:

        1. ``select`` (jitted) appends the new token's KV to layer *l*'s
           pool and emits its DSA block selections;
        2. ``stage_cb(l, sel_np, prev_lens)`` runs on the host — this is
           the window in which the engine writes back layer *l*'s new KV
           (FlashD2H), touches the LRU, and scatters fused FlashH2D restore
           payloads into ``self.state["caches"][l]``;
        3. ``attend`` (jitted) runs block-sparse attention over the now
           restored pool — restores always land BEFORE use, which is what
           makes block-granular device eviction oracle-exact.

        Pipelining: attend_l and select_{l+1} are dispatched back-to-back
        without a host sync (JAX async dispatch) — the host's only per-layer
        block is on the tiny selection tensor it needs for staging, so on an
        accelerator the device queue holds attend_l + select_{l+1} while the
        host does layer l+1's LRU bookkeeping and DRAM gather.  The cost
        model charges this overlap as max(compute, transfer) per layer
        (``costmodel.overlapped_decode_time``).

        Returns (logits, info, prev) exactly like ``step``.
        """
        cfg = self.cfg
        fns = self.staged_fns
        tokens = np.zeros((self.b_cap,), np.int32)
        mask = np.zeros((self.b_cap,), bool)
        for rid, tok in token_by_req.items():
            tokens[self.rows[rid]] = tok
            mask[self.rows[rid]] = True
        tokens = jnp.asarray(tokens)
        mask = jnp.asarray(mask)
        st = self.state
        layer_params = self._layer_params(params)
        enc_kvs = st["extra"].get("enc_kvs")
        prev = {rid: self.cur_host[rid] for rid in token_by_req}
        info: Dict[str, Any] = {"selected": {}}
        timeline: List[Tuple[int, float, float]] = []
        tr = self.tracer

        x = fns.embed(params, tokens)
        for i in range(cfg.num_layers):
            kind = M.layer_kind(cfg, i)
            if kind != "attn":
                x, new_cache = fns._recurrent[kind](
                    layer_params[i], x, st["caches"][i], mask)
                st["caches"][i] = new_cache
                continue
            q, new_cache, idx, valid = fns.select(
                layer_params[i], x, st["caches"][i], st["cur_len"], mask)
            st["caches"][i] = new_cache
            if idx is not None:
                info["selected"][i] = idx
            if stage_cb is not None:
                # np.asarray(idx) is the ONLY host sync per layer: it
                # forces select_i (and the still-queued attend_{i-1});
                # the callback then scatters restores into caches[i].
                # sel is None when DSA is off — the callback still runs
                # (per-layer FlashD2H write-back), it just has no
                # selections to stage.  In async mode the callback must
                # not block on the device again (plane-contract rule
                # no-sync-in-dispatch-window); the wall-clock split
                # between the idx sync and the host stage (the two spans'
                # durations) is recorded so bench_overlap can report
                # ACHIEVED overlap.
                with tr.span("plane.idx_sync", layer=i) as s_sync:
                    sel = None if idx is None else np.asarray(idx)
                with tr.span("plane.host_stage", layer=i) as s_stage:
                    stage_cb(i, sel, prev)
                timeline.append((i, s_sync.dur, s_stage.dur))
            x = fns.attend(layer_params[i], x, q, st["caches"][i],
                           st["cur_len"], idx, valid,
                           M.index_enc_kvs(enc_kvs, i))
        logits, new_len = fns.logits(params, x, st["cur_len"], mask)
        st["cur_len"] = new_len
        self.stage_timeline = timeline
        for _, _sync_s, _stage_s in timeline:
            self.dispatch_sync_s += _sync_s
            self.host_stage_s += _stage_s
        self.buckets_seen.add((self.b_cap, self.nb_cap))
        self.steps += 1
        for rid in token_by_req:
            self.cur_host[rid] += 1
        return logits, info, prev

    # -- data plane: FlashH2D/D2H wiring ----------------------------------

    def pool_layers(self) -> List[int]:
        """Model-layer indices that hold paged attn pools."""
        if self.state is None:
            return []
        return [l for l, c in enumerate(self.state["caches"])
                if M.is_pool_cache(c)]

    def new_token_kv(self, req_ids: List[str], prev_lens: Dict[str, int],
                     layers: Optional[List[int]] = None
                     ) -> Dict[int, Tuple[np.ndarray, Optional[np.ndarray]]]:
        """Read back the KV stripe this iteration appended (FlashD2H phase 1
        source): {model_layer: (k (R,Hkv,D), v (R,Hkv,D) | None)} with rows
        ordered like `req_ids`.  ``layers`` restricts the readback to a
        subset of pool layers — the staged plane saves layer *l* right after
        its select stage (and before its restores), one layer at a time."""
        return {l: (np.asarray(k), None if v is None else np.asarray(v))
                for l, (k, v) in self.new_token_kv_async(
                    req_ids, prev_lens, layers).items()}

    def new_token_kv_async(self, req_ids: List[str],
                           prev_lens: Dict[str, int],
                           layers: Optional[List[int]] = None
                           ) -> Dict[int, Tuple[jax.Array,
                                                Optional[jax.Array]]]:
        """Dispatch the appended-KV stripe gathers WITHOUT a host sync:
        same mapping as ``new_token_kv`` but the values are DEVICE arrays
        (the gather is queued behind this layer's select stage).  Convert
        with ``np.asarray`` off-thread (``HostStageWorker``) — JAX's value
        semantics guarantee the queued gather reads the pool value as of
        dispatch, so later pool-updating stages cannot corrupt the stripe
        even though they reuse (donated) pool buffers."""
        with self.tracer.span("plane.kv_readback",
                              layer=-1 if layers is None else layers[0]):
            bs = self.cfg.dsa.block_size
            rows = jnp.asarray([self.rows[r] for r in req_ids], jnp.int32)
            pos = np.asarray([prev_lens[r] for r in req_ids], np.int64)
            blk = jnp.asarray(pos // bs, jnp.int32)
            slot = jnp.asarray(pos % bs, jnp.int32)
            out: Dict[int, Tuple[jax.Array, Optional[jax.Array]]] = {}
            for l in (self.pool_layers() if layers is None else layers):
                c = self.state["caches"][l]
                k = c["k"][rows, :, blk, slot]                    # (R, Hkv, D)
                v = c["v"][rows, :, blk, slot] if "v" in c else None
                self.d2h_readback_bytes += k.nbytes + (
                    0 if v is None else v.nbytes)
                out[l] = (k, v)
            return out

    def restore_blocks(self, req_id: str, layer: int, blocks: List[int],
                       k_host: np.ndarray,
                       v_host: Optional[np.ndarray]) -> None:
        """Scatter a fused FlashH2D payload (from
        ``KVCacheManager.load_blocks_fused``) directly into this request's
        device slots.  k_host/v_host: (Hkv, K, bs, D)."""
        self.restore_blocks_fused(layer, {req_id: (blocks, k_host, v_host)})

    def restore_blocks_fused(self, layer: int,
                             payload_by_req: Dict[str, Tuple[List[int],
                                                             np.ndarray,
                                                             Any]],
                             before_use: bool = False) -> None:
        """Land one layer's fused FlashH2D payloads for the WHOLE batch in
        a single pool update (mirrors the one-launch-per-layer transfer:
        one device-buffer update per layer per iteration, not one per
        request).  payload_by_req: {req_id: (blocks, k (Hkv,K,bs,D),
        v | None)}.  before_use: the restore lands between this layer's
        select and attend stages (staged plane) — i.e. BEFORE the attention
        that selected the blocks — and is counted separately so the
        restore-ordering rate is observable (bench_overlap)."""
        tr = self.tracer
        c = self.state["caches"][layer]
        H = c["k"].shape[1]
        has_v = "v" in c
        with tr.span("plane.restore", layer=layer) as sp:
            with tr.span("plane.restore.prepare"):
                # host concatenation and the host-to-device copies
                rows_l: List[int] = []
                blks_l: List[int] = []
                ks: List[np.ndarray] = []
                vs: List[np.ndarray] = []
                for req_id, (blocks, k_host, v_host) in \
                        payload_by_req.items():
                    row = self.rows[req_id]
                    rows_l.extend([row] * len(blocks))
                    blks_l.extend(blocks)
                    # MLA: the host pool broadcasts the single latent head
                    # over geom.num_kv_heads; the device pool keeps one —
                    # use the first
                    ks.append(np.asarray(k_host)[:H])
                    if has_v and v_host is not None:
                        vs.append(np.asarray(v_host)[:H])
                if not blks_l:
                    return
                sp.set(blocks=len(blks_l))
                if not USE_PALLAS_PLANE:
                    rows = jnp.asarray(rows_l, jnp.int32)
                    blks = jnp.asarray(blks_l, jnp.int32)
                    # (Hkv, K_total, bs, D) -> (K_total, Hkv, bs, D):
                    # advanced indices at axes 0 and 2 put the gathered
                    # axis first in the update shape
                    k_all = jnp.asarray(
                        np.concatenate(ks, axis=1).transpose(1, 0, 2, 3))
                    v_all = (jnp.asarray(np.concatenate(vs, axis=1)
                                         .transpose(1, 0, 2, 3))
                             if has_v and vs else None)
            with tr.span("plane.restore.scatter"):
                if USE_PALLAS_PLANE:
                    # kernel-demonstration route: per-row Pallas scatters
                    for req_id, (blocks, k_host, v_host) in \
                            payload_by_req.items():
                        row = self.rows[req_id]
                        c["k"] = scatter_row_blocks(c["k"], row, blocks,
                                                    jnp.asarray(k_host[:H]))
                        if has_v and v_host is not None:
                            c["v"] = scatter_row_blocks(
                                c["v"], row, blocks, jnp.asarray(v_host[:H]))
                else:
                    c["k"] = c["k"].at[rows, :, blks].set(
                        k_all.astype(c["k"].dtype))
                    if v_all is not None:
                        c["v"] = c["v"].at[rows, :, blks].set(
                            v_all.astype(c["v"].dtype))
        self.blocks_restored += len(blks_l)
        if before_use:
            self.blocks_restored_before_use += len(blks_l)

    def drop_blocks(self, req_id: str, layer: int,
                    blocks: List[int]) -> None:
        """Zero evicted blocks' device data (HBM eviction -> device memory
        actually dropped).  Block METADATA is kept resident so DSA scoring
        stays exact; re-selected blocks come back via ``restore_blocks``."""
        tr = self.tracer
        row = self.rows[req_id]
        c = self.state["caches"][layer]
        with tr.span("plane.drop", req=req_id, layer=layer,
                     blocks=len(blocks)):
            with tr.span("plane.drop.prepare"):
                idx = jnp.asarray(blocks, jnp.int32)
                zero = jnp.zeros((c["k"].shape[1], len(blocks))
                                 + c["k"].shape[3:], c["k"].dtype)
            with tr.span("plane.drop.scatter"):
                c["k"] = scatter_row_blocks(c["k"], row, idx, zero)
                if "v" in c:
                    c["v"] = scatter_row_blocks(c["v"], row, idx, zero)
        self.blocks_dropped += len(blocks)

    # -- introspection -----------------------------------------------------

    def extract(self, req_id: str) -> Dict:
        """Copy one request's state back out (B=1, pools trimmed to the
        request's own block counts) — tests/debugging, not the hot path."""
        row = self.rows[req_id]
        nbs = self.row_layout[req_id]
        caches: List[Any] = []
        for l, c in enumerate(self.state["caches"]):
            if M.is_pool_cache(c):
                caches.append({key: v[row:row + 1, :, :nbs[l]]
                               for key, v in c.items()})
            else:
                caches.append(jax.tree.map(lambda x: x[row:row + 1], c))
        return {"caches": caches,
                "cur_len": self.state["cur_len"][row:row + 1],
                "extra": (jax.tree.map(lambda x: x[row:row + 1],
                                       self.state["extra"])
                          if self.state["extra"] else {})}

    def device_bytes(self) -> int:
        if self.state is None:
            return 0
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(self.state))
