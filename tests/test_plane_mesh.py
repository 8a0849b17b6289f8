"""Context-parallel plane tests: PlaneMesh resolution + greedy-equivalence
of the SHARDED staged decode plane and SHARDED prefill plane against their
single-device defaults (and the sequential / legacy oracles).

Multi-device cases run IN-PROCESS (no subprocess spawn): they need the
interpreter to have been started with forced host devices, e.g.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 pytest tests/test_plane_mesh.py

which is exactly what the per-PR CI ``multi-device`` job does.  Under the
plain tier-1 run (1 device) those cases skip; the ``model=1`` cases still
execute the full sharded code path (shard_map over a 1-way axis) so it
cannot rot between multi-device CI runs.  Fast cases are unmarked; the
wide arch sweep is ``slow``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.device_pool import staged_fns_for
from repro.launch.plane_mesh import PlaneMesh
from repro.models import model as M
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.request import Request

import planeasserts as pa

N_DEV = len(jax.devices())
needs_multi = pytest.mark.skipif(
    N_DEV < 8, reason="needs 8 forced host devices (CI multi-device job: "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _run_engine(cfg, params, prompts, gen=4, seed=7, **kw):
    eng = ServingEngine(params, cfg, EngineConfig(
        chunk_size=64, r_max=4, **kw))
    rng = np.random.default_rng(seed)
    order = []
    for p in prompts:
        extra = {}
        if cfg.is_encoder_decoder:
            extra["frames"] = np.ones((1, 16, cfg.d_model), np.float32) * .01
        if cfg.frontend == "vit_patch_stub":
            extra["patch_embeds"] = np.ones(
                (1, cfg.num_patches, cfg.d_model), np.float32) * .01
        toks = rng.integers(4, cfg.vocab_size, p).astype(np.int32)
        r = Request(prompt_len=p, max_new_tokens=gen)
        eng.submit(r, tokens=toks, **extra)
        order.append(r.req_id)
    eng.run()
    return eng, [eng.states[rid].out_tokens for rid in order]


# ---------------------------------------------------------------------------
# PlaneMesh resolution / layout rules (no multi-device requirement)
# ---------------------------------------------------------------------------

def test_plane_mesh_resolve_specs():
    assert PlaneMesh.resolve(None) is None
    pm = PlaneMesh.resolve("model=1")
    assert pm.model_size == 1
    assert PlaneMesh.resolve(pm) is pm
    assert PlaneMesh.resolve(1).model_size == 1
    assert PlaneMesh.resolve(pm.mesh).model_axis == "model"
    with pytest.raises(ValueError):
        PlaneMesh.resolve("rings=3")
    with pytest.raises(ValueError):
        PlaneMesh.resolve(N_DEV + 7)          # does not divide the devices


def test_pool_shard_mode_rules(smoke_setup):
    """Head mode needs a dividing KV-head axis; MLA (one latent head) and
    non-dividing GQA head counts fall back to block mode, where the block
    capacity must round up to the model axis."""
    cfg_q, _ = smoke_setup("qwen2-0.5b")        # Hkv=1
    cfg_j, _ = smoke_setup("jamba-v0.1-52b")    # Hkv=2
    cfg_m, _ = smoke_setup("minicpm3-4b")       # MLA
    pm1 = PlaneMesh.resolve("model=1")
    assert pm1.pool_shard_mode(cfg_q) == "heads"     # 1 % 1 == 0
    assert pm1.round_blocks(cfg_m, 5) == 5
    if N_DEV >= 2:
        pm2 = PlaneMesh.resolve("model=2")
        assert pm2.pool_shard_mode(cfg_q) == "blocks"
        assert pm2.pool_shard_mode(cfg_j) == "heads"
        assert pm2.pool_shard_mode(cfg_m) == "blocks"
        assert pm2.round_blocks(cfg_m, 5) == 6


def test_mesh_spec_requires_staged_plane_and_dsa(smoke_setup):
    cfg, params = smoke_setup("qwen2-0.5b")
    with pytest.raises(ValueError, match="staged"):
        ServingEngine(params, cfg, EngineConfig(
            mesh_spec="model=1", decode_plane="persistent"))
    import dataclasses
    cfg_off = dataclasses.replace(
        cfg, dsa=dataclasses.replace(cfg.dsa, enabled=False))
    with pytest.raises(ValueError, match="DSA"):
        ServingEngine(params, cfg_off, EngineConfig(mesh_spec="model=1"))


def test_sharded_code_path_on_one_device(smoke_setup):
    """mesh_spec='model=1' runs the full sharded code path (shard_map over
    a 1-way axis) on any machine — the tier-1 guard that keeps the CP
    plane importable/runnable between multi-device CI runs."""
    cfg, params = smoke_setup("qwen2-0.5b")
    e0, t0 = _run_engine(cfg, params, (48, 72))
    e1, t1 = _run_engine(cfg, params, (48, 72), mesh_spec="model=1")
    assert t1 == t0
    assert e1.plane_mesh is not None and e1.plane_mesh.model_size == 1
    [plane] = e1.planes.values()
    assert plane.plane_mesh is e1.plane_mesh
    pa.assert_cache_hit_invariant(plane.staged_fns)


def test_sharded_drop_keeps_pool_sharding(smoke_setup, monkeypatch):
    """The one-program block drop hands each sharded pool back with the
    sharding it took (so donation aliases the buffer), on the eviction-
    pressure engine of ``test_sharded_staged_eviction_pressure_oracle_exact``
    (a 4-way model axis under the multi-device job, 1-way here)."""
    from repro.core.device_pool import DevicePoolPlane
    cfg, params = smoke_setup("qwen2-0.5b")
    seen = []
    real = DevicePoolPlane.drop_blocks

    def checked(self, req_id, layer, blocks):
        c = self.state["caches"][layer]
        before = {key: c[key].sharding for key in ("k", "v") if key in c}
        real(self, req_id, layer, blocks)
        seen.append((before, {key: c[key].sharding for key in before}))
    monkeypatch.setattr(DevicePoolPlane, "drop_blocks", checked)
    e_c, _ = _run_engine(cfg, params, (64, 64, 64), gen=3,
                         hbm_blocks_per_request=1,
                         mesh_spec=f"model={min(4, N_DEV)}")
    assert e_c.plane_mesh is not None
    assert seen
    for before, after in seen:
        assert after == before
    assert any(isinstance(s, jax.sharding.NamedSharding)
               for before, _ in seen for s in before.values())


# ---------------------------------------------------------------------------
# Sharded staged decode == staged == sequential (forced multi-device CPU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded_runs(smoke_setup):
    """qwen2 smoke (Hkv=1 -> BLOCK-sharded pool) on model=2 and model=8,
    plus the single-device staged default and the sequential oracle."""
    cfg, params = smoke_setup("qwen2-0.5b")
    return {
        "staged": _run_engine(cfg, params, (48, 96, 72), gen=5),
        "cp2": _run_engine(cfg, params, (48, 96, 72), gen=5,
                           mesh_spec="model=2"),
        "cp8": _run_engine(cfg, params, (48, 96, 72), gen=5,
                           mesh_spec="model=8"),
        "sequential": _run_engine(cfg, params, (48, 96, 72), gen=5,
                                  batched_decode=False),
    }


@needs_multi
def test_sharded_staged_matches_default_and_sequential(sharded_runs):
    """Acceptance bar: sharded-staged greedy tokens are identical to the
    single-device staged plane AND the sequential oracle on a forced
    multi-device CPU mesh."""
    _, toks = sharded_runs["staged"]
    for mode in ("cp2", "cp8", "sequential"):
        assert sharded_runs[mode][1] == toks, mode


@needs_multi
def test_sharded_staged_launches_o_num_layers_traces_bounded(sharded_runs):
    """Per-iteration jitted launches stay O(num_layers) on the sharded
    plane (same stage structure), and traces == shape signatures."""
    e, _ = sharded_runs["cp8"]
    cfg = e.cfg
    [plane] = e.planes.values()
    fns = plane.staged_fns
    pa.assert_cache_hit_invariant(fns)
    per_iter = pa.staged_launches_per_iteration(cfg)
    assert fns.calls == per_iter * e.decode_step_calls
    # pool block capacity divides the 8-way model axis (block mode)
    assert plane.nb_cap % 8 == 0


@needs_multi
def test_sharded_staged_transfer_accounting_matches(sharded_runs):
    """Blocks/bytes moved by the hierarchy must not depend on the mesh."""
    (e_s, _), (e_c, _) = sharded_runs["staged"], sharded_runs["cp8"]
    s_s, s_c = e_s.transfer_stats(), e_c.transfer_stats()
    assert s_c.h2d_blocks == s_s.h2d_blocks
    assert s_c.h2d_bytes == s_s.h2d_bytes
    assert s_c.misses == s_s.misses


@needs_multi
def test_sharded_staged_eviction_pressure_oracle_exact(smoke_setup):
    """1-block LRU: >=1 eviction per iteration, physical device drops every
    round, restores landing in the select->attend window of the SHARDED
    pool — greedy tokens still identical to the sequential oracle."""
    cfg, params = smoke_setup("qwen2-0.5b")
    kw = dict(gen=8, hbm_blocks_per_request=1)
    e_c, t_c = _run_engine(cfg, params, (64, 64, 64),
                           mesh_spec="model=4", **kw)
    _, t_s = _run_engine(cfg, params, (64, 64, 64), batched_decode=False,
                         **kw)
    assert t_c == t_s
    assert e_c.eng.drop_evicted_device_blocks      # auto-resolved ON
    s = e_c.transfer_stats()
    assert s.evictions >= e_c.decode_step_calls
    [plane] = e_c.planes.values()
    assert plane.blocks_dropped > 0
    assert plane.blocks_restored > 0
    assert plane.blocks_restored_before_use == plane.blocks_restored


# ---------------------------------------------------------------------------
# Sharded prefill plane == plane == legacy
# ---------------------------------------------------------------------------

@needs_multi
def test_sharded_prefill_plane_matches_plane_and_legacy(smoke_setup):
    """Sequence-sharded prefill launches (incl. intra-layer CHUNKED
    segments, whose windows need not divide the axis) produce greedy
    tokens identical to the single-device plane and the legacy
    per-request executor."""
    cfg, params = smoke_setup("qwen2-0.5b")
    kw = dict(gen=3, prefill_max_tokens_per_step=48)
    e_p, t_p = _run_engine(cfg, params, (48, 96, 80), **kw)
    e_c, t_c = _run_engine(cfg, params, (48, 96, 80), mesh_spec=8, **kw)
    _, t_l = _run_engine(cfg, params, (48, 96, 80), gen=3,
                         prefill_exec="legacy")
    assert t_c == t_p == t_l
    # still one launch per (layer, chunk) group, sharded or not
    for plane_c, plane_p in zip(e_c.prefill_planes.values(),
                                e_p.prefill_planes.values()):
        assert plane_c.launches == plane_p.launches
        assert plane_c.chunk_launches == plane_p.chunk_launches > 0
        pa.assert_cache_hit_invariant(plane_c.fns)


@needs_multi
def test_sharded_prefill_attn_layer_pads_nondividing_window(smoke_setup):
    """Unit check of the sequence-sharded layer body on a window that does
    NOT divide the model axis (36 tokens, 8 shards): outputs match the
    replicated path to numerical tolerance."""
    cfg, params = smoke_setup("qwen2-0.5b")
    p0 = M.get_layer(params, 0)
    pm = PlaneMesh.resolve("model=8")
    B, T = 2, 36
    h = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.d_model),
                          jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    tmask = jnp.ones((B, T), bool)
    smask = jnp.ones((B,), bool)
    x_ref, (k_ref, v_ref) = M.prefill_attn_layer_batched(
        p0, cfg, h, pos, tmask, smask)
    x_cp, (k_cp, v_cp) = M.prefill_attn_layer_batched(
        p0, cfg, h, pos, tmask, smask, plane_mesh=pm)
    np.testing.assert_allclose(np.asarray(x_cp), np.asarray(x_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(k_cp), np.asarray(k_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(v_cp), np.asarray(v_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Hybrid (jamba): HEAD-sharded pools + sequence-sharded prefill smoke
# ---------------------------------------------------------------------------

@needs_multi
def test_jamba_hybrid_sharded_smoke(smoke_setup):
    """jamba smoke (Hkv=2, model=2 -> HEAD-sharded decode pool; mamba
    stages replicated): sharded staged decode + sharded prefill plane
    match the single-device default end to end."""
    cfg, params = smoke_setup("jamba-v0.1-52b")
    pm = PlaneMesh.resolve("model=2")
    assert pm.pool_shard_mode(cfg) == "heads"
    _, t0 = _run_engine(cfg, params, (48, 64))
    e2, t2 = _run_engine(cfg, params, (48, 64), mesh_spec="model=2")
    assert t2 == t0
    [plane] = e2.planes.values()
    assert plane.staged_fns is staged_fns_for(cfg, "ref", pm)
    pa.assert_cache_hit_invariant(plane.staged_fns)


# ---------------------------------------------------------------------------
# Wide sweep (slow)
# ---------------------------------------------------------------------------

@needs_multi
@pytest.mark.slow
@pytest.mark.parametrize("arch,mesh", [
    ("qwen2-0.5b", "model=2"),          # GQA, block mode
    ("jamba-v0.1-52b", "model=2"),      # hybrid, head mode
    ("minicpm3-4b", "model=2"),         # MLA latent pool, block mode
    ("kimi-k2-1t-a32b", "model=2"),     # MoE epilogue under sharded attn
    ("whisper-small", "model=2"),       # enc-dec cross-attn in the window
    ("qwen2-0.5b", "model=8"),
])
def test_sharded_planes_greedy_sweep(smoke_setup, arch, mesh):
    cfg, params = smoke_setup(arch)
    _, t0 = _run_engine(cfg, params, (48, 64, 72), gen=5)
    _, tc = _run_engine(cfg, params, (48, 64, 72), gen=5, mesh_spec=mesh)
    _, ts = _run_engine(cfg, params, (48, 64, 72), gen=5,
                        batched_decode=False)
    assert tc == t0 == ts
