"""Roofline extraction + sharding-rule unit tests (no devices needed:
AbstractMesh supplies axis names/sizes)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.roofline.analysis import (_shape_bytes, collective_bytes_from_hlo, model_flops)


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
HloModule jit_step, entry_computation_layout={...}

ENTRY %main (p0: bf16[8,64]) -> bf16[8,1024] {
  %p0 = bf16[8,64]{1,0} parameter(0)
  %c = f32[4,4]{1,0} constant({...})
  %ag = bf16[8,1024]{1,0} all-gather(%p0), channel_id=1, dimensions={1}
  %conv = f32[8,1024]{1,0} convert(%ag)
  %ar = f32[8,1024]{1,0} all-reduce(%conv), channel_id=2, to_apply=%add
  %a2a = f32[8,1024]{1,0} all-to-all(%ar), channel_id=3, dimensions={0}
  %cp = f32[8,1024]{1,0} collective-permute(%a2a), channel_id=4
  %start = (f32[8,1024], f32[8,1024]) all-reduce-start(%cp), channel_id=5, to_apply=%add
  %done = f32[8,1024]{1,0} all-reduce-done(%start)
  ROOT %out = bf16[8,1024]{1,0} convert(%done)
}
"""


def test_shape_bytes():
    assert _shape_bytes("bf16", "8,64") == 8 * 64 * 2
    assert _shape_bytes("f32", "") == 4            # scalar
    assert _shape_bytes("pred", "16") == 16


def test_collective_parse_counts_and_bytes():
    out = collective_bytes_from_hlo(HLO_SAMPLE)
    c = out["counts"]
    assert c["all-gather"] == 1
    assert c["all-reduce"] == 2        # plain + -start (done skipped)
    assert c["all-to-all"] == 1
    assert c["collective-permute"] == 1
    b = out["per_kind_bytes"]
    assert b["all-gather"] == 8 * 64 * 2             # operand %p0
    f32row = 8 * 1024 * 4
    assert b["all-reduce"] == 2 * f32row             # %conv + %cp
    assert out["bytes_per_device"] == sum(b.values())


def test_collective_parse_empty():
    out = collective_bytes_from_hlo("ENTRY %m { ROOT %x = f32[] constant(0) }")
    assert out["bytes_per_device"] == 0


# ---------------------------------------------------------------------------
# model_flops (6ND / 2ND accounting)
# ---------------------------------------------------------------------------

def test_model_flops_moe_uses_active_params():
    from repro.configs import get_config
    kimi = get_config("kimi-k2-1t-a32b")
    total, active = kimi.param_count(), kimi.active_param_count()
    assert active < total / 5                      # 32B active of 1T
    f = model_flops(kimi, "decode", 32768, 128)
    assert f == 2.0 * active * 128


def test_model_flops_train_is_6nd(tiny_cfg):
    n = tiny_cfg.active_param_count()
    assert model_flops(tiny_cfg, "train", 128, 4) == 6.0 * n * 512


# ---------------------------------------------------------------------------
# Sharding rules (AbstractMesh — no real devices)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def pod_mesh():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def specs_of(tree):
    return jax.tree.map(lambda s: s.spec, tree,
                        is_leaf=lambda x: hasattr(x, "spec"))


def test_param_sharding_rules(mesh):
    from repro.launch.sharding import param_shardings
    from repro.launch.steps import abstract_params
    from repro.configs import get_config
    cfg = get_config("qwen2.5-3b")      # heads=16 divisible by 16
    shapes = abstract_params(cfg)
    sh = param_shardings(shapes, mesh)
    specs = specs_of(sh)
    assert specs["embed"] == P("model", None)
    assert specs["lm_head"] == P(None, "model")
    layers = specs["layers"]            # stacked: leading None
    assert layers["attn"]["wq"] == P(None, None, "model")
    assert layers["attn"]["wo"] == P(None, "model", None)
    assert layers["ffn"]["w_gate"] == P(None, None, "model")
    assert layers["ffn"]["w_down"] == P(None, "model", None)


def test_param_sharding_moe_expert_parallel(mesh):
    from repro.launch.sharding import param_shardings
    from repro.launch.steps import abstract_params
    from repro.configs import get_config
    cfg = get_config("kimi-k2-1t-a32b")   # 384 experts
    sh = param_shardings(abstract_params(cfg), mesh)
    specs = specs_of(sh)
    moe = specs["layers"]["moe"]
    assert moe["w_gate"] == P(None, "model", None, None)   # (L, E, d, f)
    assert moe["router"] == P(None, None, None)            # replicated


def test_param_sharding_nondivisible_replicates(mesh):
    """Dims not divisible by the 16-way model axis must replicate rather
    than produce an invalid sharding."""
    from repro.launch.sharding import param_shardings
    shapes = {"layers": [{"attn": {
        "wq": jax.ShapeDtypeStruct((100, 37), jnp.float32),   # 37 % 16 != 0
        "wo": jax.ShapeDtypeStruct((37, 100), jnp.float32),
    }}]}
    specs = specs_of(param_shardings(shapes, mesh))
    assert specs["layers"][0]["attn"]["wq"] == P(None, None)
    assert specs["layers"][0]["attn"]["wo"] == P(None, None)
    # divisible dims do shard
    shapes2 = {"layers": [{"attn": {
        "wq": jax.ShapeDtypeStruct((100, 64), jnp.float32)}}]}
    specs2 = specs_of(param_shardings(shapes2, mesh))
    assert specs2["layers"][0]["attn"]["wq"] == P(None, "model")


def test_state_sharding_pools(mesh):
    from repro.launch.sharding import state_shardings
    from repro.launch.steps import abstract_decode_state
    from repro.configs import get_config
    cfg = get_config("qwen2.5-3b")
    st = abstract_decode_state(cfg, 128, 32768)
    sh = state_shardings(st, mesh)
    specs = specs_of(sh)
    # stacked pools (L, B, Hkv, NB, bs, D): batch on data, blocks on model
    assert specs["caches"]["k"] == P(None, "data", None, "model", None, None)
    assert specs["cur_len"] == P("data")


def test_batch_sharding_multipod(pod_mesh):
    from repro.launch.sharding import batch_shardings
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    sh = batch_shardings(batch, pod_mesh)
    assert sh["tokens"].spec == P(("pod", "data"), None)
    # batch=1 (long_500k) cannot shard -> replicated
    sh1 = batch_shardings({"tokens": jax.ShapeDtypeStruct((1,), jnp.int32)},
                          pod_mesh)
    assert sh1["tokens"].spec == P(None)


def test_input_specs_cover_all_shapes():
    from repro.launch.steps import SHAPES, step_and_specs
    from repro.configs import ASSIGNED_ARCHS, get_config
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES:
            fn, args, kind = step_and_specs(cfg, shape)
            leaves = jax.tree.leaves(args)
            assert leaves, (arch, shape)
            assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
