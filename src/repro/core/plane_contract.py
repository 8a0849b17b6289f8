"""The plane contract — machine-readable invariants of the serving planes.

PRs 2-5 built the staged decode plane and the prefill plane around a small
set of invariants the paper's design depends on (restore-before-use, one
fused FlashD2H/H2D per (layer, group), the one-layer prefill ctx lifetime,
O(L) launches per iteration, bounded retraces, and a fixed collective /
replication layout per sharded stage).  Until now each invariant lived
twice: implicitly in the driver code and explicitly in hand-written test
assertions.  This module is the single declarative home for all of them:

* the **effect vocabulary** (``EFFECT_OF_CALL``) names every data-plane
  call a driver may make and classifies it (launch / d2h / h2d / restore /
  drop / LRU touch / ctx read / layer evict);
* **driver specs** (``DEFAULT_DRIVERS``) name the stage-loop drivers and
  the engine callbacks spliced into them, plus which protocol's rules
  apply to each;
* **registry specs** (``DEFAULT_REGISTRIES``) name the per-stage jit
  registries and the shape-relevant fields their cache keys must cover;
* **sharding rules** (``sharding_rules``) list, per (stage, shard mode),
  the collectives a lowered stage jit may contain and the output tree
  paths allowed to stay sharded (everything else must be pinned
  replicated, e.g. via ``PlaneMesh.replicate``);
* **launch-budget helpers** (``staged_launches_per_iteration`` ...) that
  both ``tests/planeasserts.py`` and the analyzer read, so the runtime
  assertions and the static checks can never drift apart.

``tools/analysis/run.py`` consumes all of the above; see
docs/architecture.md §8 for the prose version of the contract.

Waivers: an intentional deviation is annotated in-source as

    # plane-contract: allow(<rule>) <reason>

on the offending line or the line directly above it.  ``collect_waivers``
parses them; the analyzer reports waived findings but does not fail on
them.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Rule ids
# ---------------------------------------------------------------------------

# pass 1 — stage protocol
RULE_RESTORE_BEFORE_USE = "restore-before-use"
RULE_WRITEBACK_BEFORE_DROP = "writeback-before-drop"
RULE_FUSED_TRANSFER = "fused-transfer"
RULE_CTX_LIFETIME = "ctx-lifetime"
RULE_LAUNCHES = "launches-per-iteration"
RULE_NO_SYNC_IN_DISPATCH_WINDOW = "no-sync-in-dispatch-window"
# pass 2 — retrace hazards
RULE_TRACED_BRANCH = "traced-branch"
RULE_TRACER_COERCION = "tracer-coercion"
RULE_NP_IN_JIT = "np-in-jit"
RULE_OBS_IN_JIT = "no-obs-in-jit"
RULE_UNHASHABLE_KEY = "unhashable-key"
RULE_KEY_MISSING_FIELD = "key-missing-field"
# pass 3 — sharding
RULE_COLLECTIVE = "collective-not-allowed"
RULE_SHARDING_LEAK = "sharding-leak"

ALL_RULES = (
    RULE_RESTORE_BEFORE_USE, RULE_WRITEBACK_BEFORE_DROP,
    RULE_FUSED_TRANSFER, RULE_CTX_LIFETIME, RULE_LAUNCHES,
    RULE_NO_SYNC_IN_DISPATCH_WINDOW,
    RULE_TRACED_BRANCH, RULE_TRACER_COERCION, RULE_NP_IN_JIT,
    RULE_OBS_IN_JIT,
    RULE_UNHASHABLE_KEY, RULE_KEY_MISSING_FIELD,
    RULE_COLLECTIVE, RULE_SHARDING_LEAK,
)

# Roots that identify an obs-layer object in source (pass 2's
# no-obs-in-jit): a call like ``tracer.span(...)`` / ``self.metrics.inc()``
# inside a jitted stage body is a host side effect that fires once at
# trace time and never again — spans silently vanish, counters undercount.
# Instrumentation belongs in the plane code, around the stage launches.
OBS_ROOT_NAMES = frozenset({
    "tracer", "_tracer", "tracing", "obs", "_obs", "metrics", "_metrics",
    "metrics_registry", "registry",
})

# ---------------------------------------------------------------------------
# Effect vocabulary (pass 1)
# ---------------------------------------------------------------------------

# callee name (the attribute/function a driver calls) -> (kind, sub).
# Kinds: "launch" (jitted stage launch), "d2h" (FlashD2H save; sub "fused"
# or "unfused"), "lru" (KVCacheManager residency touch), "h2d" (fused
# FlashH2D DRAM gather), "restore" (scatter of H2D payloads into device
# slots), "drop" (physical device drop of evicted blocks), "pool-read"
# (device->host readback of freshly appended KV), "ctx-read" (read of the
# one-layer prefill ctx buffer), "layer-evict" (HBM drop of a finished
# prefill layer).
EFFECT_OF_CALL: Dict[str, Tuple[str, str]] = {
    # jitted stage launches (staged decode plane)
    "embed": ("launch", "embed"),
    "select": ("launch", "select"),
    "attend": ("launch", "attend"),
    "_recurrent": ("launch", "recurrent"),
    "logits": ("launch", "logits"),
    # jitted stage launches (prefill plane)
    "attn": ("launch", "prefill-attn"),
    "rec": ("launch", "prefill-rec"),
    "finalize": ("launch", "finalize"),
    "_run_group": ("launch", "prefill-group"),
    # mixed-iteration walk (core/hybrid_plane.py): the hybrid driver runs
    # a layer's prefill groups / the shared finalize through these
    "run_layer": ("launch", "prefill-group"),
    "finish_iteration": ("launch", "finalize"),
    # FlashD2H
    "save_new_tokens_fused": ("d2h", "fused"),
    "save_contiguous": ("d2h", "unfused"),
    # LRU / FlashH2D / device restore
    "access_layer": ("lru", ""),
    "load_blocks_fused": ("h2d", "fused"),
    "restore_blocks_fused": ("restore", "fused"),
    "restore_blocks": ("restore", "unfused"),
    # quantized offload tier (kernels/quant_blocks.py).  The (de)quant
    # kernels are PART of their fused transfer, not transfers themselves —
    # kind "quant" is deliberately outside the d2h/h2d/restore kinds the
    # fused-transfer window counter sums, so a driver fusing
    # quantize into its one FlashD2H save (or dequantize into its one
    # FlashH2D restore) still shows exactly one fused op per layer.
    # dequantize_scatter_blocks IS the restore (quantized
    # scatter_blocks_hkv), so it counts like restore_blocks_fused: a
    # driver issuing both in one window is a double restore.
    "quantize_blocks": ("quant", "d2h"),
    "dequantize_blocks": ("quant", "h2d"),
    "dequantize_scatter_blocks": ("restore", "fused"),
    # eviction
    "drop_blocks": ("drop", "direct"),
    "_drop_pending_evictions": ("drop", "deferred"),
    "drop_layer": ("layer-evict", ""),
    # readbacks.  sub "" = BLOCKING (np.asarray inside), "async" = the
    # dispatch-only variant (returns device arrays / a finisher for the
    # HostStageWorker), "view" = a device-slice view with no transfer
    "new_token_kv": ("pool-read", ""),
    "new_token_kv_async": ("pool-read", "async"),
    "read_group_kv": ("ctx-read", ""),
    "read_group_kv_async": ("ctx-read", "async"),
    "layer_ctx": ("ctx-read", "view"),
    # async write-back staging: the fused FlashD2H is DISPATCHED here (the
    # conversion + save_new_tokens_fused run on the host-stage worker); for
    # ordering rules it sequences exactly like the sync fused save
    "_stage_writeback_async": ("d2h", "fused"),
    "_stage_writeback_async_merged": ("d2h", "fused"),
    # explicit host-blocking device syncs — forbidden inside an async
    # dispatch window (RULE_NO_SYNC_IN_DISPATCH_WINDOW)
    "asarray": ("sync", "host"),
    "block_until_ready": ("sync", "host"),
    "device_get": ("sync", "host"),
    # blocking obs exports (full-registry walks) — fine between
    # iterations, forbidden inside an async dispatch window for the same
    # reason (they re-serialize the overlap the pipeline exists to
    # create).  Span emission (`tracer.span`) is NOT in this table: it
    # never blocks.
    "metrics_snapshot": ("sync", "obs"),
    "metrics_prometheus": ("sync", "obs"),
    "prometheus_text": ("sync", "obs"),
}

# ---------------------------------------------------------------------------
# Driver specs (pass 1)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CallbackSpec:
    """A host callback spliced into a driver's stage loop at its call
    site: ``local_name`` is the parameter the driver calls; file/qualname
    locate the engine-side body the checker inlines there."""
    local_name: str
    file: str
    qualname: str


@dataclasses.dataclass(frozen=True)
class DriverSpec:
    """One stage-loop driver the protocol checker linearizes.

    protocol selects the rule set (see ``PROTOCOL_RULES``);
    batch_iterables are loop-iterable names that range over REQUESTS —
    a jitted launch inside such a loop breaks the O(L) launch budget."""
    name: str
    file: str
    qualname: str
    protocol: str
    callbacks: Tuple[CallbackSpec, ...] = ()
    batch_iterables: Tuple[str, ...] = ()


PROTOCOL_RULES: Dict[str, Tuple[str, ...]] = {
    # the staged decode window: select -> [cb: d2h, lru, h2d, restore,
    # protected drop] -> attend, per attention layer
    "staged-decode": (RULE_RESTORE_BEFORE_USE, RULE_WRITEBACK_BEFORE_DROP,
                      RULE_FUSED_TRANSFER, RULE_LAUNCHES),
    # the prefill (layer, chunk) group window: launch -> [cb: ctx read,
    # fused d2h, end-of-layer pool build + HBM evict]
    "prefill-plane": (RULE_WRITEBACK_BEFORE_DROP, RULE_FUSED_TRANSFER,
                      RULE_CTX_LIFETIME, RULE_LAUNCHES),
    # the single batched launch that executes one group
    "prefill-group": (RULE_FUSED_TRANSFER, RULE_LAUNCHES),
    # the mixed iteration (decode rows + prefill segments in ONE layer
    # walk): the staged-decode window rules AND the prefill ctx/writeback
    # rules apply together — every pass-1 rule covers this driver
    "hybrid-plane": (RULE_RESTORE_BEFORE_USE, RULE_WRITEBACK_BEFORE_DROP,
                     RULE_FUSED_TRANSFER, RULE_CTX_LIFETIME, RULE_LAUNCHES),
    # the ASYNC dispatch windows (stage_dispatch="async", the default):
    # the base window rules still hold — the d2h is dispatched in the
    # same order, fenced at the gather — PLUS nothing in the callback may
    # block on the device (the driver's np.asarray(idx) is the one
    # allowed per-layer sync, and it happens before the callback runs)
    "staged-decode-async": (RULE_RESTORE_BEFORE_USE,
                            RULE_WRITEBACK_BEFORE_DROP,
                            RULE_FUSED_TRANSFER, RULE_LAUNCHES,
                            RULE_NO_SYNC_IN_DISPATCH_WINDOW),
    "hybrid-plane-async": (RULE_RESTORE_BEFORE_USE,
                           RULE_WRITEBACK_BEFORE_DROP,
                           RULE_FUSED_TRANSFER, RULE_CTX_LIFETIME,
                           RULE_LAUNCHES,
                           RULE_NO_SYNC_IN_DISPATCH_WINDOW),
    # fused decode plane: transfers are per-layer fused, but restores land
    # after the forward (restore-before-use deliberately does NOT apply;
    # that is exactly why drop_evicted_device_blocks needs the staged plane)
    "fused-decode": (RULE_FUSED_TRANSFER, RULE_LAUNCHES),
    # legacy per-request executors: only the fusion rule applies (their
    # per-request saves are waived in-source, never silently accepted)
    "legacy": (RULE_FUSED_TRANSFER,),
}


DEFAULT_DRIVERS: Tuple[DriverSpec, ...] = (
    DriverSpec(
        name="staged-decode",
        file="src/repro/core/device_pool.py",
        qualname="DevicePoolPlane.step_staged",
        protocol="staged-decode",
        callbacks=(CallbackSpec(
            "stage_cb", "src/repro/serving/engine.py",
            "ServingEngine._decode_batch_staged.stage_cb_sync"),),
        batch_iterables=("token_by_req", "req_ids", "sts", "rids"),
    ),
    DriverSpec(
        name="staged-decode-async",
        file="src/repro/core/device_pool.py",
        qualname="DevicePoolPlane.step_staged",
        protocol="staged-decode-async",
        callbacks=(CallbackSpec(
            "stage_cb", "src/repro/serving/engine.py",
            "ServingEngine._decode_batch_staged.stage_cb_async"),),
        batch_iterables=("token_by_req", "req_ids", "sts", "rids"),
    ),
    DriverSpec(
        name="prefill-plane",
        file="src/repro/core/prefill_plane.py",
        qualname="PrefillPlane.run_iteration",
        protocol="prefill-plane",
        callbacks=(CallbackSpec(
            "group_cb", "src/repro/serving/engine.py",
            "ServingEngine._prefill_plane_iteration.group_cb"),),
        batch_iterables=("allow", "rids", "req_ids", "g.req_ids"),
    ),
    DriverSpec(
        name="prefill-group",
        file="src/repro/core/prefill_plane.py",
        qualname="PrefillPlane._run_group",
        protocol="prefill-group",
        batch_iterables=("rids", "req_ids"),
    ),
    DriverSpec(
        name="hybrid-plane",
        file="src/repro/core/hybrid_plane.py",
        qualname="HybridPlane.run_iteration",
        protocol="hybrid-plane",
        callbacks=(CallbackSpec(
            "layer_cb", "src/repro/serving/engine.py",
            "ServingEngine._mixed_iteration.layer_cb_sync"),),
        batch_iterables=("token_by_req", "req_ids", "rids", "sts",
                         "allow"),
    ),
    DriverSpec(
        name="hybrid-plane-async",
        file="src/repro/core/hybrid_plane.py",
        qualname="HybridPlane.run_iteration",
        protocol="hybrid-plane-async",
        callbacks=(CallbackSpec(
            "layer_cb", "src/repro/serving/engine.py",
            "ServingEngine._mixed_iteration.layer_cb_async"),),
        batch_iterables=("token_by_req", "req_ids", "rids", "sts",
                         "allow"),
    ),
    DriverSpec(
        name="hybrid-prefill-layer",
        file="src/repro/core/prefill_plane.py",
        qualname="PrefillPlane.run_layer",
        protocol="prefill-group",
        batch_iterables=("rids", "req_ids", "allow"),
    ),
    DriverSpec(
        name="fused-decode-selections",
        file="src/repro/serving/engine.py",
        qualname="ServingEngine._account_selections",
        protocol="fused-decode",
        batch_iterables=("sts", "req_ids"),
    ),
    DriverSpec(
        name="fused-decode-writeback",
        file="src/repro/serving/engine.py",
        qualname="ServingEngine._write_back_new_kv",
        protocol="fused-decode",
        batch_iterables=("sts", "req_ids"),
    ),
    DriverSpec(
        name="legacy-layer-segment",
        file="src/repro/serving/engine.py",
        qualname="ServingEngine._run_layer_segment",
        protocol="legacy",
    ),
    DriverSpec(
        name="legacy-chunked-prefill",
        file="src/repro/serving/engine.py",
        qualname="ServingEngine._run_chunked_prefill",
        protocol="legacy",
    ),
)

# ---------------------------------------------------------------------------
# Registry specs (pass 2)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RegistrySpec:
    """A per-stage jit registry factory whose cache key must (a) cover
    every shape-relevant factory parameter and (b) never key a config /
    mesh object by identity or unhashable value.  ``wrap_required``
    params must not appear as BARE elements of the key (use repr(cfg),
    plane_mesh.key(), ...)."""
    file: str
    factory: str
    required_params: Tuple[str, ...]
    wrap_required: Tuple[str, ...]


DEFAULT_REGISTRIES: Tuple[RegistrySpec, ...] = (
    RegistrySpec("src/repro/core/device_pool.py", "decode_fn_for",
                 ("cfg", "attn_impl"), ("cfg",)),
    RegistrySpec("src/repro/core/device_pool.py", "staged_fns_for",
                 ("cfg", "attn_impl", "plane_mesh"), ("cfg", "plane_mesh")),
    RegistrySpec("src/repro/core/prefill_plane.py", "prefill_fns_for",
                 ("cfg", "plane_mesh"), ("cfg", "plane_mesh")),
    RegistrySpec("src/repro/core/prefill_plane.py", "admit_embed_fns_for",
                 ("cfg",), ("cfg",)),
    RegistrySpec("src/repro/core/hybrid_plane.py", "hybrid_fns_for",
                 ("cfg", "attn_impl", "plane_mesh"), ("cfg", "plane_mesh")),
)

# files whose jit-wrapped stage bodies pass 2 lints (wrap(...)/jax.jit(...)
# call sites); params bound via a defaulted argument (kind=kind) or named
# here are STATIC — everything else is traced inside the body
DEFAULT_JIT_FILES: Tuple[str, ...] = (
    "src/repro/core/device_pool.py",
    "src/repro/core/prefill_plane.py",
    # composes the two registries above — no jit sites of its own today,
    # listed so any future wrap()/jax.jit added there is linted
    "src/repro/core/hybrid_plane.py",
)
STATIC_PARAM_NAMES = frozenset({"self", "cfg", "kind", "stage"})

# ---------------------------------------------------------------------------
# Sharding rules (pass 3)
# ---------------------------------------------------------------------------

# communication primitives; axis_index is positional, not communication,
# and is always allowed
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter", "pbroadcast",
})


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """What a lowered stage jit may do on the mesh: which collectives its
    jaxpr may contain, and which output tree paths may remain sharded
    (every other output must be pinned replicated)."""
    allowed_collectives: frozenset
    sharded_out_paths: Tuple[str, ...]


_NO_COMM = ShardingRules(frozenset(), ())
# the pool cache leaves a sharded select hands back stay sharded by design
_POOL_PATHS = ("'k'", "'v'", "'meta'")

_SHARDING_RULES: Dict[Tuple[str, str], ShardingRules] = {
    # decode select: head mode is communication-free; block mode
    # all-gathers the (tiny) block scores for the redundant global top-k
    ("select", "heads"): ShardingRules(frozenset(), _POOL_PATHS),
    ("select", "blocks"): ShardingRules(frozenset({"all_gather"}),
                                        _POOL_PATHS),
    # decode attend: head mode local; block mode merges flash partials
    # with a logsumexp pmax/psum
    ("attend", "heads"): _NO_COMM,
    ("attend", "blocks"): ShardingRules(frozenset({"pmax", "psum"}), ()),
    # prefill attention: sequence-sharded queries, replicated K/V — the
    # re-gather is a sharding constraint, not an explicit collective
    ("attn", "seq"): _NO_COMM,
}


def sharding_rules(stage: str, mode: str) -> ShardingRules:
    """Contract rules for one registered stage jit.  Stages without an
    entry (embed, logits, recurrent, rec-*, finalize, admit-embed) are
    replicated: no collectives, no sharded outputs."""
    return _SHARDING_RULES.get((stage, mode), _NO_COMM)


def stage_shard_mode(stage: str, cfg, plane_mesh) -> str:
    """Which sharding mode a stage lowers under for (cfg, plane_mesh)."""
    if plane_mesh is None:
        return "none"
    if stage in ("select", "attend"):
        return plane_mesh.pool_shard_mode(cfg)
    if stage == "attn":
        return "seq"
    return "none"

# ---------------------------------------------------------------------------
# Launch budgets (shared by tests/planeasserts.py and the analyzer)
# ---------------------------------------------------------------------------


def staged_launches_per_iteration(cfg) -> int:
    """Jitted launches ONE staged decode iteration issues: embed + logits
    + (select + attend) per attention layer + one per recurrent layer —
    the O(L) budget the stage-protocol checker proves statically and
    ``tests/planeasserts.py`` asserts at runtime."""
    n_attn = cfg.num_attention_layers()
    return 2 + 2 * n_attn + (cfg.num_layers - n_attn)


def mixed_launches_per_iteration(cfg, n_decode_planes: int, n_groups: int,
                                 n_finalize_planes: int) -> int:
    """Jitted launches ONE mixed iteration of the hybrid plane issues:
    every decode plane pays the full staged budget, plus one bucketed
    launch per executed prefill (layer, chunk) group and one shared
    finalize per prefill plane with finished rows.  Independent of how
    many decode ROWS or prefill requests ride each plane — the O(L)
    budget ``tests/planeasserts.assert_mixed_launch_invariant`` checks
    against the engine's measured ``mixed_iter_log``."""
    return (n_decode_planes * staged_launches_per_iteration(cfg)
            + n_groups + n_finalize_planes)


def staged_host_syncs_per_iteration(cfg) -> int:
    """Blocking device->host syncs ONE async staged decode iteration is
    allowed on the dispatch thread: exactly the np.asarray of the
    selection tensor, once per attention layer (zero with DSA off — then
    there is nothing to stage).  Everything else (stripe conversion, DRAM
    staging) runs on the HostStageWorker; the logits readback at sampling
    happens after the iteration's drain and is not a per-layer cost.
    ``tests/planeasserts.assert_host_sync_invariant`` checks the planes'
    measured ``host_syncs`` counters against this."""
    return cfg.num_attention_layers() if cfg.dsa.enabled else 0


# pool-updating stages that must DECLARE buffer donation (donate_argnums
# on the pool/cache argument) so XLA reuses the buffer in place on
# accelerator backends instead of copying a pool per layer per iteration.
# tests/planeasserts.assert_donation_contract checks a live registry's
# StageFns.donated against this.
STAGED_DONATED_STAGES: Dict[str, Tuple[int, ...]] = {
    "select": (2,),             # consumes + returns the layer pool cache
    "recurrent-mamba": (2,),    # consumes + returns the recurrent state
    "recurrent-rwkv": (2,),
}

# the block-drop program (device_pool._BlockDrop) consumes and returns the
# layer's pools (arg 0): donated so the zeroed row is written in place
# instead of copying the layer's pools per drop.  Checked against its
# ``donated`` by the same assertion.
BLOCK_DROP_DONATED: Tuple[int, ...] = (0,)


def staged_stage_kinds(cfg) -> int:
    """Distinct stage kinds of the staged decode pipeline for ``cfg`` —
    the per-shape-bucket trace budget (embed, select, attend, logits, plus
    each recurrent layer kind present)."""
    from repro.models import model as M
    kinds = {M.layer_kind(cfg, i) for i in range(cfg.num_layers)}
    return 4 + len(kinds - {"attn"})


def iter_registries():
    """The live per-stage jit registries, as (registry_name, fns) pairs —
    what the sharding-leak pass lowers.  Imported lazily so the contract
    itself stays import-light."""
    from repro.core import device_pool, prefill_plane
    # NOTE: the hybrid registry (hybrid_plane._HYBRID_FNS) is deliberately
    # absent — it COMPOSES the staged and prefill registries below without
    # adding jits; lowering it here would double-check every stage.
    for name, reg in (("staged", device_pool._STAGED_FNS),
                      ("prefill", prefill_plane._PREFILL_FNS),
                      ("admit-embed", prefill_plane._ADMIT_EMBED_FNS)):
        for fns in reg.values():
            yield name, fns

# ---------------------------------------------------------------------------
# Waivers
# ---------------------------------------------------------------------------

WAIVER_RE = re.compile(
    r"#\s*plane-contract:\s*allow\(([a-z0-9-]+)\)\s*(.*)$")


def collect_waivers(source: str) -> Dict[int, Tuple[str, str]]:
    """{line_number: (rule, reason)} for every waiver comment in a file.
    A waiver applies to findings of that rule on its own line or the line
    directly below (comment-above style)."""
    out: Dict[int, Tuple[str, str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = WAIVER_RE.search(line)
        if m:
            out[i] = (m.group(1), m.group(2).strip())
    return out


def waiver_for(waivers: Dict[int, Tuple[str, str]], rule: str,
               line: int) -> Optional[str]:
    """The reason string if ``rule`` at ``line`` is waived, else None."""
    for at in (line, line - 1):
        hit = waivers.get(at)
        if hit is not None and hit[0] == rule:
            return hit[1]
    return None

# ---------------------------------------------------------------------------
# Analysis targets
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AnalysisTarget:
    """Everything one ``tools/analysis/run.py`` invocation analyzes.  The
    default target is the real tree; fixture targets under
    ``tools/analysis/fixtures/`` carry one seeded violation each.

    sharding: None (skip pass 3), "default" (lower the live registries
    populated by a smoke workload), or "<file>:<function>" returning a
    list of ``StageLowering``."""
    name: str
    drivers: Tuple[DriverSpec, ...] = ()
    registries: Tuple[RegistrySpec, ...] = ()
    jit_files: Tuple[str, ...] = ()
    sharding: Optional[str] = None


@dataclasses.dataclass
class StageLowering:
    """One stage jit to abstractly lower and check against the sharding
    contract: ``fn(*args)`` is traced via jax.make_jaxpr (args are
    ShapeDtypeStructs recorded by ``StageFns``)."""
    stage: str
    fn: object
    args: Tuple
    rules: ShardingRules
    file: str
    line: int


DEFAULT_TARGET = AnalysisTarget(
    name="tree",
    drivers=DEFAULT_DRIVERS,
    registries=DEFAULT_REGISTRIES,
    jit_files=DEFAULT_JIT_FILES,
    sharding="default",
)
