"""Real-execution serving engine integration tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import model as M
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.request import Phase, Request


@pytest.fixture()
def setup(smoke_setup):
    return smoke_setup("qwen2-0.5b")


def run_engine(cfg, params, mode, n=3, prompt=96, gen=5, **kw):
    eng = ServingEngine(params, cfg, EngineConfig(
        prefill_mode=mode, chunk_size=64, r_max=4, **kw))
    for _ in range(n):
        eng.submit(Request(prompt_len=prompt, max_new_tokens=gen))
    m = eng.run()
    return eng, m


@pytest.mark.parametrize("mode", ["layer_segmented", "chunked"])
def test_engine_completes_all_requests(setup, mode):
    cfg, params = setup
    eng, m = run_engine(cfg, params, mode)
    assert m.num_finished == 3
    for st in eng.states.values():
        assert st.req.phase == Phase.FINISHED
        assert len(st.out_tokens) == st.req.max_new_tokens


def test_layer_segmented_prefill_equals_plain(setup):
    cfg, params = setup
    tokens = np.arange(7, 103, dtype=np.int32)
    lg_plain, _ = M.prefill(params, cfg,
                            {"tokens": jnp.asarray(tokens[None])}, 5,
                            cache_dtype=jnp.float32)
    eng = ServingEngine(params, cfg, EngineConfig())
    r = Request(prompt_len=96, max_new_tokens=2)
    eng.submit(r, tokens=tokens)
    while r.phase != Phase.DECODE:
        assert eng.step() is not None
    st = eng.states[r.req_id]
    np.testing.assert_allclose(np.asarray(st.last_logits),
                               np.asarray(lg_plain), rtol=1e-4, atol=1e-4)


def test_chunked_prefill_equals_plain(setup):
    cfg, params = setup
    tokens = np.arange(7, 103, dtype=np.int32)
    lg_plain, _ = M.prefill(params, cfg,
                            {"tokens": jnp.asarray(tokens[None])}, 5,
                            cache_dtype=jnp.float32)
    eng = ServingEngine(params, cfg, EngineConfig(prefill_mode="chunked",
                                                  chunk_size=32))
    r = Request(prompt_len=96, max_new_tokens=2)
    eng.submit(r, tokens=tokens)
    while r.phase != Phase.DECODE:
        assert eng.step() is not None
    st = eng.states[r.req_id]
    np.testing.assert_allclose(np.asarray(st.last_logits),
                               np.asarray(lg_plain), rtol=1e-3, atol=1e-3)


def test_both_prefill_modes_generate_same_tokens(setup):
    """End-to-end: greedy generation must not depend on the prefill mode."""
    cfg, params = setup
    outs = {}
    for mode in ["layer_segmented", "chunked"]:
        eng = ServingEngine(params, cfg, EngineConfig(
            prefill_mode=mode, chunk_size=48))
        r = Request(prompt_len=96, max_new_tokens=6)
        eng.submit(r, tokens=np.arange(7, 103, dtype=np.int32))
        eng.run()
        outs[mode] = eng.states[r.req_id].out_tokens
    assert outs["layer_segmented"] == outs["chunked"]


def test_transfer_stats_flow(setup):
    cfg, params = setup
    eng, _ = run_engine(cfg, params, "layer_segmented",
                        hbm_blocks_per_request=2)
    ts = eng.transfer_stats()
    assert ts.d2h_calls > 0           # FlashD2H saves during prefill
    assert ts.misses > 0              # tiny cache -> misses
    assert ts.h2d_bytes > 0
    assert sum(eng.loads_per_iter) > 0


def test_bigger_cache_fewer_loads(setup):
    """More HBM per request -> strictly fewer block loads (LRU locality)."""
    cfg, params = setup
    loads = {}
    for cap in (2, 64):
        eng, _ = run_engine(cfg, params, "layer_segmented",
                            hbm_blocks_per_request=cap, n=2, gen=8)
        loads[cap] = sum(eng.loads_per_iter)
    assert loads[64] < loads[2]


def test_ws_control_rejections(setup):
    """With a tiny M_avl the WS controller must reject requests."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, EngineConfig(
        ws_control=True, hbm_budget_bytes=1, r_max=4))
    for _ in range(3):
        eng.submit(Request(prompt_len=64, max_new_tokens=3))
    plan = eng.scheduler.schedule()
    assert plan.rejected > 0 or (not plan.decode_reqs
                                 and not plan.prefill_reqs)


def test_hybrid_batching(setup):
    """Decode and prefill coexist in one iteration once a request decodes."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, EngineConfig(r_max=4))
    r1 = Request(prompt_len=64, max_new_tokens=8)
    eng.submit(r1)
    # run r1 to decode
    while r1.phase != Phase.DECODE:
        eng.step()
    r2 = Request(prompt_len=64, max_new_tokens=8)
    eng.submit(r2)
    plan = eng.step()
    assert plan is not None
    assert plan.decode_reqs and plan.prefill_reqs   # hybrid batch


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "jamba-v0.1-52b",
                                  "whisper-small", "internvl2-2b",
                                  "minicpm3-4b", "kimi-k2-1t-a32b"])
def test_engine_on_nontrivial_arch_families(arch, smoke_setup):
    """The serving engine runs end-to-end on SSM / hybrid / enc-dec / VLM /
    MLA / MoE smoke variants, not just dense GQA.  Batched decode is the
    default path, so this also covers batch assembly per arch family."""
    cfg, params = smoke_setup(arch)
    eng = ServingEngine(params, cfg, EngineConfig(r_max=2))
    extra = {}
    if cfg.is_encoder_decoder:
        extra["frames"] = np.ones((1, 16, cfg.d_model), np.float32) * .01
    if cfg.frontend == "vit_patch_stub":
        extra["patch_embeds"] = np.ones(
            (1, cfg.num_patches, cfg.d_model), np.float32) * .01
    r = Request(prompt_len=64, max_new_tokens=4)
    eng.submit(r, **extra)
    m = eng.run()
    assert m.num_finished == 1
    assert len(eng.states[r.req_id].out_tokens) == 4


def test_engine_clock_follows_the_platform(setup, monkeypatch):
    """Measured wall time on an accelerator, the cost model on CPU; the
    caller's config is resolved into a copy, never mutated."""
    cfg, params = setup
    ec = EngineConfig()
    assert ServingEngine(params, cfg, ec).eng.charge_real_time is False
    assert ec.charge_real_time is None
    assert ServingEngine(params, cfg, EngineConfig(
        charge_real_time=True)).eng.charge_real_time is True
    import repro.serving.engine as engine_mod
    monkeypatch.setattr(engine_mod.jax, "default_backend", lambda: "tpu")
    assert ServingEngine(params, cfg, ec).eng.charge_real_time is True
