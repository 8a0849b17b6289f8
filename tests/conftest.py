"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the single real CPU device; only dryrun.py requests 512 placeholders.

Model/param construction is session-scoped and shared across modules
(``smoke_setup``) so the tier-1 suite initializes each smoke architecture
once instead of once per module — part of keeping the CPU run under the
10-minute budget."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M


@pytest.fixture(scope="session")
def tiny_cfg(smoke_setup):
    return smoke_setup("qwen2-0.5b")[0]


@pytest.fixture(scope="session")
def tiny_params(smoke_setup):
    return smoke_setup("qwen2-0.5b")[1]


@pytest.fixture(scope="session")
def smoke_setup():
    """get(arch) -> (smoke cfg, float32 params), cached for the session."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_smoke_config(arch)
            cache[arch] = (cfg, M.init_params(cfg, jax.random.PRNGKey(0),
                                              jnp.float32))
        return cache[arch]

    return get


@pytest.fixture(scope="session")
def tiny_params_list(tiny_cfg):
    return M.init_params(tiny_cfg, jax.random.PRNGKey(0), jnp.float32,
                         stacked=False)


def make_batch(cfg, B=2, S=64, key=3):
    batch = {"tokens": jnp.full((B, S), key, jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.ones((B, 32, cfg.d_model), jnp.float32) * 0.01
    if cfg.frontend == "vit_patch_stub":
        batch["patch_embeds"] = jnp.ones(
            (B, cfg.num_patches, cfg.d_model), jnp.float32) * 0.01
    return batch
