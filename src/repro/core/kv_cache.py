"""Hierarchical HBM–DRAM KV cache manager (paper §3.1 "KV Cache Manager").

Control plane: block-table bookkeeping, HBM LRU cache, transfer accounting.
Data plane: host-resident block pools (numpy) + device working buffers, with
FlashH2D (fused gather) loading and FlashD2H (contiguous flush + deferred
scatter) saving — `repro.kernels.gather_blocks` / `scatter_blocks`.

Blocks are tracked per (layer, kv_head, block_id) — the paper's per-head
granularity (Fig. 5, (H, N, D) layout) — so transfer sizes and hit rates
match what an A100/v5e deployment would see.

All byte/transfer counters feed the cost model (`serving/costmodel.py`)
and the Fig. 4 / Fig. 14 / Fig. 15 benchmarks.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.obs import tracing


@dataclasses.dataclass
class KVGeometry:
    """Shape of one request's KV cache."""
    num_layers: int          # attention layers only
    num_kv_heads: int
    block_size: int          # tokens per block
    head_dim: int            # cached dim per token per head (MLA: latent)
    dtype_bytes: int = 2     # bf16
    kv_factor: int = 2       # k and v (MLA latent: 1)

    @property
    def block_bytes_per_head(self) -> int:
        """Bytes of ONE block for ONE kv head at the MODELED device dtype
        (``dtype_bytes``, bf16 by default), K and V together
        (``kv_factor``).  This is the deployment-sized unit the cost model
        charges transfers in; it is independent of the f32 numpy pools the
        smoke data plane happens to hold, and of the offload tier's stored
        size (``HostPool.wire_bytes`` for that)."""
        return self.block_size * self.head_dim * self.dtype_bytes * self.kv_factor

    @property
    def block_bytes(self) -> int:
        """One block id across ALL layers and kv heads — the working-set
        unit (bytes per entry of a request's block table).  Scheduler
        admission (M_avl) and working-set estimates use this; per-transfer
        accounting uses the per-(layer, head) slices instead."""
        return self.block_bytes_per_head * self.num_kv_heads * self.num_layers

    def tokens_bytes(self, n_tokens: int) -> int:
        """Logical KV bytes of ``n_tokens`` across all layers/heads at the
        modeled dtype (no block-size round-up)."""
        return (n_tokens * self.head_dim * self.dtype_bytes * self.kv_factor
                * self.num_kv_heads * self.num_layers)


@dataclasses.dataclass
class TransferStats:
    """PCIe/DMA traffic counters, booked exactly once per moved byte.

    Units: ``*_bytes`` are bytes AS STORED IN THE OFFLOAD TIER (the wire
    size of the DMA) — under ``offload_quant="int8"`` that is the int8
    payload plus 4 B per (kv-head, block) scale, NOT the logical fp size;
    with the default fp tier the two coincide.  ``*_calls`` count fused
    kernel launches (one FlashH2D/FlashD2H per layer per iteration under
    batching), ``*_blocks`` count (block x kv-head) units moved.

    Who books what (the staged-vs-accounted split): ``HBMCache.access``
    books residency only (hits/misses/evictions); ``HostPool.stage``
    appends to staging WITHOUT booking (it returns the wire bytes so the
    one fused caller can book them); bytes/calls land at the single fused
    data-plane call (``KVCacheManager.load_blocks_fused`` /
    ``save_new_tokens_fused`` on ``fused_stats``, or the per-request
    ``HostPool.load_blocks`` / ``save_contiguous``); ``HostPool.flush``
    books ``d2h_blocks`` only — a staged byte is never counted twice.
    """
    h2d_bytes: int = 0          # wire bytes (stored size, see above)
    h2d_calls: int = 0          # fused kernel launches (FlashH2D)
    h2d_blocks: int = 0         # fragmented (block x kv-head) units moved
    d2h_bytes: int = 0
    d2h_calls: int = 0
    d2h_blocks: int = 0
    evictions: int = 0
    hits: int = 0
    misses: int = 0

    def merge(self, o: "TransferStats") -> None:
        for f in dataclasses.fields(TransferStats):
            setattr(self, f.name, getattr(self, f.name) + getattr(o, f.name))


class HBMCache:
    """LRU cache of HBM-resident KV blocks for ONE request.

    Keys are (layer, block_id); all kv heads of a block move together (the
    per-head transfer granularity is reflected in byte accounting).  The LRU
    policy exploits the temporal locality of DSA block selection —
    consecutive query tokens select highly-overlapping blocks (§3.1/Fig. 8).
    """

    def __init__(self, geom: KVGeometry, capacity_blocks: int):
        self.geom = geom
        self.capacity = capacity_blocks            # in (layer, block) units
        self._lru: "collections.OrderedDict[Tuple[int,int], bool]" = \
            collections.OrderedDict()
        # eviction keys are recorded only when a consumer drains them
        # (engine with drop_evicted_device_blocks): unconditional recording
        # would grow without bound on the default path
        self.track_evictions = False
        self._evicted: List[Tuple[int, int]] = []  # since last pop_evicted
        self.stats = TransferStats()

    def resident(self, layer: int, block: int) -> bool:
        return (layer, block) in self._lru

    @property
    def num_resident(self) -> int:
        return len(self._lru)

    def access(self, layer: int, blocks: List[int]) -> List[int]:
        """Touch `blocks` for `layer`; return the MISSING block ids (to load).

        Units: `blocks` are block *ids* (``block_size`` tokens each); one
        LRU entry is one (layer, block) key covering all kv heads.  Evicts
        LRU entries beyond capacity (retrievable until the next
        ``pop_evicted``).  Residency accounting ONLY (hits/misses/
        evictions): the actual FlashH2D transfer — and its h2d_* stats —
        happens exactly once, in the data plane (``HostPool.load_blocks`` /
        ``KVCacheManager.load_blocks_fused``), so ``total_stats`` never
        double-counts a transfer.
        """
        missing = []
        for b in blocks:
            key = (layer, b)
            if key in self._lru:
                self._lru.move_to_end(key)
                self.stats.hits += 1
            else:
                missing.append(b)
                self.stats.misses += 1
        for b in missing:
            self._lru[(layer, b)] = True
        self._evict_over_capacity()
        return missing

    def _evict_over_capacity(self) -> None:
        while len(self._lru) > self.capacity:
            key = self._lru.popitem(last=False)[0]
            if self.track_evictions:
                self._evicted.append(key)
            self.stats.evictions += 1

    def pop_evicted(self) -> List[Tuple[int, int]]:
        """Drain the (layer, block) keys evicted since the last call — the
        engine zeroes these device slots when
        ``drop_evicted_device_blocks`` is on (which also sets
        ``track_evictions``; keys are not recorded otherwise)."""
        out, self._evicted = self._evicted, []
        return out

    def insert(self, layer: int, block: int) -> None:
        """Insert a freshly produced block (decode append) without a load."""
        self._lru[(layer, block)] = True
        self._lru.move_to_end((layer, block))
        self._evict_over_capacity()

    def drop_layer(self, layer: int) -> int:
        """Evict all blocks of one layer (layer-segmented prefill §3.4)."""
        keys = [k for k in self._lru if k[0] == layer]
        for k in keys:
            del self._lru[k]
        return len(keys)


QUANT_SCALE_BYTES = 4  # one f32 scale per (kv-head, block) per tensor


def _quantize_block_np(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric int8 quantization of one block, per kv head.

    x: (Hkv, bs, D) fp -> (q (Hkv, bs, D) int8, scales (Hkv,) f32) with
    scale = amax/127 per head; matches ``kernels.ref.quantize_blocks``
    bit-for-bit (``np.rint`` == ``jnp.rint``, round-half-to-even)."""
    xf = x.astype(np.float32)
    amax = np.max(np.abs(xf), axis=(1, 2))
    # the kernels' rounding rule (quant_blocks.int8_scale): scale by the
    # f32 constant 1/127, then reciprocal-multiply — a division would flip
    # exact .5 rounding boundaries vs the kernels
    scales = (amax * np.float32(1.0 / 127.0)).astype(np.float32)
    inv = np.where(scales > 0.0,
                   np.float32(1.0) / np.where(scales > 0.0, scales,
                                              np.float32(1.0)),
                   np.float32(1.0)).astype(np.float32)
    q = np.clip(np.rint(xf * inv[:, None, None]), -127, 127).astype(np.int8)
    return q, scales


def _dequantize_block_np(q: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Inverse of ``_quantize_block_np``: (Hkv, bs, D) int8 + (Hkv,) f32
    -> (Hkv, bs, D) f32."""
    return q.astype(np.float32) * scales[:, None, None]


class HostPool:
    """Host-DRAM block pool for ONE request (data plane).

    Stores K/V blocks as numpy arrays shaped (L, Hkv, NB, bs, D).  Saving
    follows FlashD2H: the contiguous per-iteration KV stripe is appended to
    a staging buffer in one "memcpy" and scattered into blocks lazily
    (``flush``), mirroring the paper's CPU-assisted two-phase save.

    ``quant="int8"`` switches the pool to the quantized offload tier: the
    K/V arrays hold int8 with per-(layer, kv-head, block) f32 scales
    (``k_scale``/``v_scale``), blocks quantize at ``flush`` and dequantize
    at ``gather``, and every byte counter reports the STORED size (int8
    payload + scales), not the logical fp size — so TransferStats, the obs
    spans, and the cost model all see the ~``dtype_bytes``x wire shrink.
    The staging buffer keeps fp stripes in both modes (quantization is
    per-block, so it must wait for the block scatter).
    """

    def __init__(self, geom: KVGeometry, num_blocks: int,
                 quant: str = "none"):
        if quant not in ("none", "int8"):
            raise ValueError(f"HostPool: unknown quant mode {quant!r}")
        g = geom
        self.geom = g
        self.num_blocks = num_blocks
        self.quant = quant
        shape = (g.num_layers, g.num_kv_heads, num_blocks, g.block_size,
                 g.head_dim)
        dt = np.int8 if quant == "int8" else np.float32
        self.k = np.zeros(shape, dt)
        self.v = np.zeros(shape, dt) if g.kv_factor == 2 else None
        if quant == "int8":
            sshape = (g.num_layers, g.num_kv_heads, num_blocks)
            self.k_scale = np.zeros(sshape, np.float32)
            self.v_scale = np.zeros(sshape, np.float32) \
                if self.v is not None else None
        else:
            self.k_scale = self.v_scale = None
        self._staging: List[Tuple[int, int, np.ndarray, Optional[np.ndarray]]] = []
        self.stats = TransferStats()

    def wire_bytes(self, n_blocks: int) -> int:
        """Bytes ``n_blocks`` whole blocks occupy AS STORED in this pool —
        the wire size of moving them (one layer, all kv heads, K+V).

        fp tier: elems x itemsize of the numpy arrays.  int8 tier: 1 B per
        element plus ``QUANT_SCALE_BYTES`` per (kv-head, block) per tensor.
        Every h2d/d2h byte counter for this pool is derived from this."""
        g = self.geom
        elems_per_head = g.block_size * g.head_dim
        if self.quant == "int8":
            per_head = elems_per_head + QUANT_SCALE_BYTES
        else:
            per_head = elems_per_head * self.k.itemsize
        kvf = 2 if self.v is not None else 1
        return n_blocks * g.num_kv_heads * per_head * kvf

    def stage(self, layer: int, start_token: int, k_new: np.ndarray,
              v_new: Optional[np.ndarray]) -> int:
        """Append one contiguous KV stripe to the staging buffer WITHOUT
        booking d2h stats (callers that represent one fused launch across
        many pools — ``KVCacheManager.save_new_tokens_fused`` — account the
        launch themselves; ``save_contiguous`` accounts per-call).

        k_new/v_new: (Hkv, T, D) for T new tokens starting at absolute
        token position ``start_token``.  Bounds contract: the stripe
        [start_token, start_token+T) must fit the pool registered at
        ``KVCacheManager.register`` time — out-of-range stripes raise
        ``ValueError`` immediately rather than corrupting block state.

        Returns the stripe's WIRE byte size for the caller to book: the
        fp stripe bytes (K+V) in the default tier, or — under
        ``quant="int8"`` — the int8 payload plus one scale per touched
        (kv-head, block) per tensor, i.e. the size the D2H DMA actually
        moves when ``quantize_blocks`` is fused into the save path."""
        T = k_new.shape[1]
        end_token = start_token + T
        max_tokens = self.num_blocks * self.geom.block_size
        if start_token < 0 or end_token > max_tokens:
            raise ValueError(
                f"HostPool.stage: tokens [{start_token}, {end_token})"
                f" exceed the registered pool capacity of {max_tokens} tokens"
                f" ({self.num_blocks} blocks x {self.geom.block_size}); "
                f"register the request with a larger max_tokens")
        self._staging.append((layer, start_token, np.asarray(k_new),
                              None if v_new is None else np.asarray(v_new)))
        kvf = 2 if v_new is not None else 1
        if self.quant == "int8" and T > 0:
            bs = self.geom.block_size
            touched = (end_token - 1) // bs - start_token // bs + 1
            elems = T * self.geom.num_kv_heads * k_new.shape[2]
            scale_b = touched * self.geom.num_kv_heads * QUANT_SCALE_BYTES
            return (elems + scale_b) * kvf
        return k_new.nbytes * kvf

    def save_contiguous(self, layer: int, start_token: int, k_new: np.ndarray,
                        v_new: Optional[np.ndarray]) -> None:
        """Phase 1 of FlashD2H: one contiguous D2H transfer into staging.

        k_new/v_new: (Hkv, T, D) for T new tokens starting at start_token.
        Books exactly one ``d2h_calls`` (the contiguous DMA) and its bytes;
        the CPU-side block scatter is deferred to ``flush`` (which books
        ``d2h_blocks`` only — a staged byte is never double-counted)."""
        nbytes = self.stage(layer, start_token, k_new, v_new)
        self.stats.d2h_calls += 1
        self.stats.d2h_bytes += nbytes

    def _store_quant_span(self, layer: int, blk: int, off: int,
                          stripe_k: np.ndarray,
                          stripe_v: Optional[np.ndarray]) -> None:
        """int8-tier block update: dequantize the resident block with its
        current per-head scales, overwrite tokens [off, off+n), then
        requantize the whole block with fresh scales.  Partial-block
        appends therefore requantize previously stored tokens — the drift
        is bounded (each token requantizes at most bs-1 times with scales
        that only grow as the block fills) and covered by the fidelity
        tests in ``tests/test_quant_kv.py``."""
        n = stripe_k.shape[1]
        cur_k = _dequantize_block_np(self.k[layer, :, blk],
                                     self.k_scale[layer, :, blk])
        cur_k[:, off:off + n] = stripe_k
        self.k[layer, :, blk], self.k_scale[layer, :, blk] = \
            _quantize_block_np(cur_k)
        if stripe_v is not None:
            cur_v = _dequantize_block_np(self.v[layer, :, blk],
                                         self.v_scale[layer, :, blk])
            cur_v[:, off:off + n] = stripe_v
            self.v[layer, :, blk], self.v_scale[layer, :, blk] = \
                _quantize_block_np(cur_v)

    def flush(self) -> int:
        """Phase 2 of FlashD2H: CPU-side scatter of staged stripes into the
        per-head block layout.  Returns blocks written (block-boundary
        segments; a stripe spanning two blocks writes two).

        Accounting: books ``d2h_blocks`` ONLY — the stripe's bytes and the
        fused launch were already booked when the stripe was staged
        (``save_contiguous`` / ``save_new_tokens_fused``), so flushing
        never double-counts.  In the int8 tier each touched block is
        (re)quantized here with fresh per-head scales — the numpy twin of
        fusing ``kernels.quantize_blocks`` into the D2H scatter."""
        g = self.geom
        written = 0
        for layer, start, k_new, v_new in self._staging:
            T = k_new.shape[1]
            t0 = 0
            while t0 < T:
                blk = (start + t0) // g.block_size
                off = (start + t0) % g.block_size
                if blk >= self.num_blocks:
                    raise ValueError(
                        f"HostPool.flush: staged token {start + t0} maps to "
                        f"block {blk} but the pool only has "
                        f"{self.num_blocks} blocks")
                # split on block boundaries (start may be mid-block)
                t1 = min(t0 + (g.block_size - off), T)
                if self.quant == "int8":
                    self._store_quant_span(
                        layer, blk, off, k_new[:, t0:t1],
                        None if v_new is None else v_new[:, t0:t1])
                else:
                    self.k[layer, :, blk, off:off + (t1 - t0)] = \
                        k_new[:, t0:t1]
                    if v_new is not None:
                        self.v[layer, :, blk, off:off + (t1 - t0)] = \
                            v_new[:, t0:t1]
                written += 1
                self.stats.d2h_blocks += 1
                t0 = t1
        self._staging.clear()
        return written

    def gather(self, layer: int, blocks: List[int]
               ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Data-plane gather of fragmented blocks — NO accounting.

        Returns (k (Hkv, K, bs, D), v or None), always in the COMPUTE
        dtype: the int8 tier dequantizes here with the stored per-head
        scales (the numpy twin of ``kernels.dequantize_scatter_blocks``),
        so downstream restore-into-device-slots is tier-agnostic.  Callers
        that represent one fused kernel launch record the h2d_* stats
        themselves (either ``load_blocks`` below or
        ``KVCacheManager.load_blocks_fused``) — at ``wire_bytes`` size,
        because the H2D DMA moves the stored payload, not this fp copy."""
        if blocks and (max(blocks) >= self.num_blocks or min(blocks) < 0):
            bad = max(blocks) if max(blocks) >= self.num_blocks \
                else min(blocks)
            raise ValueError(
                f"HostPool.gather: block {bad} out of range "
                f"(pool has {self.num_blocks} blocks)")
        idx = np.asarray(blocks, np.int32)
        k = self.k[layer][:, idx]
        v = None if self.v is None else self.v[layer][:, idx]
        if self.quant == "int8":
            ks = self.k_scale[layer][:, idx]            # (Hkv, K)
            k = k.astype(np.float32) * ks[..., None, None]
            if v is not None:
                vs = self.v_scale[layer][:, idx]
                v = v.astype(np.float32) * vs[..., None, None]
        return k, v

    def load_blocks(self, layer: int, blocks: List[int]
                    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """FlashH2D data plane: ONE fused gather of fragmented blocks.

        Returns (k (Hkv, K, bs, D), v or None) in the compute dtype.
        Books one ``h2d_calls`` launch, ``h2d_blocks`` in (block x
        kv-head) units, and ``h2d_bytes`` at the pool's STORED size
        (``wire_bytes``) — int8 payload + scales under the quantized
        tier, fp bytes otherwise."""
        k, v = self.gather(layer, blocks)
        self.stats.h2d_calls += 1
        self.stats.h2d_blocks += len(blocks) * self.geom.num_kv_heads
        self.stats.h2d_bytes += self.wire_bytes(len(blocks))
        return k, v


class KVCacheManager:
    """System-wide manager: per-request HBM caches + host pools + global
    HBM budget (M_avl feeds the scheduler's Algorithm 1)."""

    def __init__(self, geom: KVGeometry, hbm_budget_bytes: int,
                 host_budget_bytes: Optional[int] = None,
                 offload_quant: str = "none"):
        if offload_quant not in ("none", "int8"):
            raise ValueError(
                f"KVCacheManager: unknown offload_quant {offload_quant!r}")
        self.geom = geom
        self.hbm_budget_bytes = hbm_budget_bytes
        self.host_budget_bytes = host_budget_bytes
        self.offload_quant = offload_quant
        self.caches: Dict[str, HBMCache] = {}
        self.pools: Dict[str, HostPool] = {}
        self._retired_stats = TransferStats()   # stats of released requests
        self.fused_stats = TransferStats()      # batched FlashH2D launches
        self.tracer = tracing.DEFAULT           # the engine hands its own

    # -- lifecycle ---------------------------------------------------------
    def register(self, req_id: str, max_tokens: int,
                 hbm_blocks_per_request: int) -> None:
        nb = -(-max_tokens // self.geom.block_size)
        self.caches[req_id] = HBMCache(self.geom, hbm_blocks_per_request)
        self.pools[req_id] = HostPool(self.geom, nb,
                                      quant=self.offload_quant)

    def release(self, req_id: str) -> None:
        c = self.caches.pop(req_id, None)
        p = self.pools.pop(req_id, None)
        if c is not None:
            self._retired_stats.merge(c.stats)
        if p is not None:
            self._retired_stats.merge(p.stats)

    # -- control plane -----------------------------------------------------
    def access_layer(self, layer: int, blocks_by_req: Dict[str, List[int]],
                     drain_evicted: bool = False
                     ) -> Tuple[Dict[str, List[int]],
                                Dict[str, List[Tuple[int, int]]]]:
        """Touch one layer's selected blocks for every request of a decode
        iteration (LRU residency only — no transfer accounting; see
        ``HBMCache.access``).

        The per-layer unit matches the decode planes: the fused plane calls
        this once per layer after its single forward, the staged plane calls
        it between a layer's select and attend stages so the returned
        ``missing`` can be loaded (``load_blocks_fused``) and restored into
        device slots BEFORE that layer's attention.

        `layer` is the attention-layer ordinal.  Returns
        (missing_by_req, evicted_by_req): the block ids each request must
        load, and — when ``drain_evicted`` — the (layer, block) keys each
        request's LRU evicted during these accesses (``pop_evicted``; empty
        lists otherwise).  Requests without a registered cache are skipped.
        """
        with self.tracer.span("kv.lru", layer=layer):
            missing_by_req: Dict[str, List[int]] = {}
            evicted_by_req: Dict[str, List[Tuple[int, int]]] = {}
            for req_id, blocks in blocks_by_req.items():
                cache = self.caches.get(req_id)
                if cache is None:
                    continue
                missing = cache.access(layer, blocks)
                if missing:
                    missing_by_req[req_id] = missing
                if drain_evicted:
                    evicted_by_req[req_id] = cache.pop_evicted()
            return missing_by_req, evicted_by_req

    # -- data plane --------------------------------------------------------
    def load_blocks_fused(self, layer: int,
                          blocks_by_req: Dict[str, List[int]]
                          ) -> Dict[str, Tuple[np.ndarray,
                                               Optional[np.ndarray]]]:
        """ONE fused FlashH2D launch covering every missing block of `layer`
        across the whole decode batch (batched engine hot path).

        The paper's FlashH2D kernel gathers fragmented blocks from pinned
        DRAM in a single launch; under batched decode the launch amortizes
        over ALL requests in the iteration, so h2d_calls grows
        per-layer-per-iteration, not per-request.  Accounting lives HERE and
        only here for these transfers (``HBMCache.access`` books residency
        only), so each moved block is counted exactly once: h2d_calls in
        fused launches, h2d_blocks in (block x kv-head) units, h2d_bytes in
        K+V payload bytes AT STORED SIZE (``HostPool.wire_bytes`` — int8 +
        scales under ``offload_quant="int8"``, fp bytes otherwise).

        `layer` is the attention-layer ORDINAL (0..geom.num_layers-1), not
        the model layer id; `blocks_by_req` values are block ids, each
        bounds-checked by ``HostPool.gather`` against the pool registered
        at ``register`` time.  Returns {req_id: (k (Hkv,K,bs,D), v|None)} —
        under the persistent decode plane the engine scatters these
        payloads DIRECTLY into the requests' device slots
        (``DevicePoolPlane.restore_blocks``)."""
        with self.tracer.span("kv.h2d_gather", layer=layer) as sp:
            out: Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]] = {}
            total_blocks = 0
            total_bytes = 0
            for req_id, blocks in blocks_by_req.items():
                pool = self.pools.get(req_id)
                if pool is None or not blocks:
                    continue
                k, v = pool.gather(layer, blocks)
                out[req_id] = (k, v)
                total_blocks += len(blocks) * self.geom.num_kv_heads
                total_bytes += pool.wire_bytes(len(blocks))
            if total_blocks:
                self.fused_stats.h2d_calls += 1
                self.fused_stats.h2d_blocks += total_blocks
                self.fused_stats.h2d_bytes += total_bytes
                sp.set(blocks=total_blocks, bytes=total_bytes)
            return out

    def save_new_tokens_fused(self, layer: int,
                              kv_by_req: Dict[str, Tuple[int, np.ndarray,
                                                         Optional[np.ndarray]]]
                              ) -> None:
        """ONE fused FlashD2H save of this iteration's newly produced KV
        for `layer` across a whole batch — the decode planes' per-layer
        write-back AND the prefill plane's per-(layer, chunk)-group save
        (each batched prefill launch saves every request's stripe through
        one call here, replacing the legacy per-request
        ``save_contiguous`` loop).

        kv_by_req: {req_id: (start_token, k (Hkv,T,D), v or None)}.  Under
        batching the stripe is contiguous across the batch, so the paper
        saves it with one D2H DMA per layer per iteration; accordingly
        ``d2h_calls`` is booked ONCE here (on ``fused_stats``) while each
        pool stages its stripe without accounting (``HostPool.stage``).
        The CPU-side scatter into blocks still happens at each pool's
        ``flush``.  With the default fp tier the host pool stays a
        byte-exact superset of device KV; under ``offload_quant="int8"``
        it is a BOUNDED-ERROR superset (per-block per-head scales), and
        either way ``load_blocks_fused`` payloads come back in the compute
        dtype — dequantized at gather — so they stay safe to scatter
        straight into device slots.  Staged bytes are booked at wire size
        (see ``HostPool.stage``)."""
        # in async mode this runs on the HostStageWorker thread, and the
        # span lands on that thread's line of the trace
        with self.tracer.span("kv.d2h_save", layer=layer) as sp:
            total_bytes = 0
            for req_id, (start, k, v) in kv_by_req.items():
                pool = self.pools.get(req_id)
                if pool is None:
                    continue
                total_bytes += pool.stage(layer, start, k, v)
            if total_bytes:
                self.fused_stats.d2h_calls += 1
                self.fused_stats.d2h_bytes += total_bytes
                sp.set(bytes=total_bytes)

    # -- accounting --------------------------------------------------------
    def hbm_used_bytes(self) -> int:
        per_lb = (self.geom.block_bytes_per_head * self.geom.num_kv_heads)
        return sum(c.num_resident * per_lb for c in self.caches.values())

    def total_stats(self) -> TransferStats:
        s = TransferStats()
        s.merge(self._retired_stats)
        s.merge(self.fused_stats)
        for c in self.caches.values():
            s.merge(c.stats)
        for p in self.pools.values():
            s.merge(p.stats)
        return s
