"""DevicePoolPlane unit tests: slot lifecycle, bucketed jit retraces,
step_mask row parking, and the drop/restore block data plane."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.device_pool import (BucketingPolicy, DevicePoolPlane,
                                    block_drop, gather_row_blocks,
                                    scatter_row_blocks)
from repro.models import model as M

import planeasserts as pa


def _prefill_state(cfg, params, S, nb, seed=0):
    """One request's list-mode DecodeState (the engine's representation)."""
    toks = jax.random.randint(jax.random.PRNGKey(seed), (1, S), 4,
                              cfg.vocab_size)
    _, st = M.prefill(params, cfg, {"tokens": toks}, nb,
                      cache_dtype=jnp.float32)
    if isinstance(st["caches"], dict):         # stacked scan caches -> list
        st["caches"] = [jax.tree.map(lambda x, i=i: x[i], st["caches"])
                        for i in range(cfg.num_layers)]
    return st


def _assert_states_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_bucketing_policy():
    p = BucketingPolicy(batch_buckets=(1, 2, 4, 8), block_bucket=8)
    assert [p.bucket_batch(n) for n in (1, 2, 3, 5, 8, 9, 17)] == \
        [1, 2, 4, 8, 8, 16, 32]
    assert [p.bucket_blocks(n) for n in (1, 8, 9, 16)] == [8, 8, 16, 16]


def test_admit_extract_roundtrip(smoke_setup):
    cfg, params = smoke_setup("qwen2-0.5b")
    plane = DevicePoolPlane(cfg)
    states = {f"r{i}": _prefill_state(cfg, params, S, nb, seed=i)
              for i, (S, nb) in enumerate(((40, 4), (64, 6)))}
    for rid, st in states.items():
        plane.admit(rid, st)
    for rid, st in states.items():
        _assert_states_equal(plane.extract(rid), st)


def test_slot_reuse_and_bucket_growth(smoke_setup):
    cfg, params = smoke_setup("qwen2-0.5b")
    plane = DevicePoolPlane(cfg, BucketingPolicy(batch_buckets=(1, 2, 4)))
    st = _prefill_state(cfg, params, 40, 4)
    plane.admit("a", st)
    plane.admit("b", _prefill_state(cfg, params, 33, 4, seed=1))
    assert plane.b_cap == 2
    freed = plane.release("a")
    plane.admit("c", _prefill_state(cfg, params, 48, 4, seed=2))
    assert plane.rows["c"] == freed            # freed slot reused in place
    assert plane.rows_reused == 1
    assert plane.b_cap == 2                    # no growth for the reuse
    plane.admit("d", _prefill_state(cfg, params, 40, 4, seed=3))
    assert plane.b_cap == 4                    # next batch bucket
    _assert_states_equal(plane.extract("c"),
                         _prefill_state(cfg, params, 48, 4, seed=2))


def test_drop_then_restore_from_host_copy(smoke_setup):
    """HBM eviction drops device block data; a fused-H2D restore puts the
    host copy back bit-for-bit (metadata stays resident throughout)."""
    cfg, params = smoke_setup("qwen2-0.5b")
    plane = DevicePoolPlane(cfg)
    st = _prefill_state(cfg, params, 64, 4)
    plane.admit("a", st)
    layer = plane.pool_layers()[0]
    blocks = [0, 2]
    row = plane.rows["a"]
    c = plane.state["caches"][layer]
    host_k = np.asarray(gather_row_blocks(c["k"], row, blocks))
    host_v = np.asarray(gather_row_blocks(c["v"], row, blocks))
    plane.drop_blocks("a", layer, blocks)
    dropped = plane.extract("a")["caches"][layer]
    assert float(np.abs(np.asarray(dropped["k"][0, :, blocks])).sum()) == 0.0
    assert plane.blocks_dropped == 2
    plane.restore_blocks("a", layer, blocks, host_k, host_v)
    _assert_states_equal(plane.extract("a"), st)
    assert plane.blocks_restored == 2


def test_step_mask_parks_unscheduled_rows(smoke_setup):
    """Stepping a subset must leave parked rows byte-for-byte unchanged
    (pools, metadata, recurrent state, cur_len)."""
    cfg, params = smoke_setup("qwen2-0.5b")
    plane = DevicePoolPlane(cfg)
    plane.admit("a", _prefill_state(cfg, params, 40, 4))
    plane.admit("b", _prefill_state(cfg, params, 33, 4, seed=1))
    before_b = plane.extract("b")
    logits, info, prev = plane.step(params, {"a": 7})
    assert prev == {"a": 40}
    _assert_states_equal(plane.extract("b"), before_b)
    assert int(plane.extract("a")["cur_len"][0]) == 41


def test_stepped_subset_matches_solo_decode(smoke_setup):
    """A row stepped inside a padded, partially-active batch produces the
    same logits and cache updates as decoding it alone."""
    cfg, params = smoke_setup("qwen2-0.5b")
    st_solo = _prefill_state(cfg, params, 40, 4)
    lg_solo, ns_solo = M.decode_step(params, cfg,
                                     jnp.asarray([7], jnp.int32), st_solo)
    plane = DevicePoolPlane(cfg)
    plane.admit("a", _prefill_state(cfg, params, 40, 4))
    plane.admit("b", _prefill_state(cfg, params, 33, 4, seed=1))
    logits, _, _ = plane.step(params, {"a": 7})
    row = plane.rows["a"]
    np.testing.assert_allclose(np.asarray(logits[row]),
                               np.asarray(lg_solo[0]), rtol=1e-5, atol=1e-5)
    # jit (plane) vs eager (solo) may differ in float low bits
    for x, y in zip(jax.tree.leaves(plane.extract("a")),
                    jax.tree.leaves(ns_solo)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)


def test_staged_step_matches_fused_step(smoke_setup):
    """The staged per-layer pipeline (select -> attend per layer, separate
    jits) computes the same logits and state updates as the fused one-launch
    ``step`` — the numeric backbone of the plane-equivalence guarantee."""
    cfg, params = smoke_setup("qwen2-0.5b")
    pf, ps = DevicePoolPlane(cfg), DevicePoolPlane(cfg)
    for plane in (pf, ps):
        plane.admit("a", _prefill_state(cfg, params, 40, 4))
        plane.admit("b", _prefill_state(cfg, params, 33, 4, seed=1))
    lg_f, info_f, prev_f = pf.step(params, {"a": 7, "b": 9})
    lg_s, info_s, prev_s = ps.step_staged(params, {"a": 7, "b": 9})
    assert prev_f == prev_s
    assert sorted(info_f["selected"]) == sorted(info_s["selected"])
    np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_s),
                               rtol=1e-5, atol=1e-5)
    for rid in ("a", "b"):
        for x, y in zip(jax.tree.leaves(pf.extract(rid)),
                        jax.tree.leaves(ps.extract(rid))):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-5)


def test_staged_restore_lands_between_select_and_attend(smoke_setup):
    """Dropped device blocks restored in the stage callback are read by the
    SAME iteration's attention: a step over a pool with blocks zeroed +
    in-window restores matches a step over the never-dropped pool."""
    cfg, params = smoke_setup("qwen2-0.5b")
    clean, dropped = DevicePoolPlane(cfg), DevicePoolPlane(cfg)
    for plane in (clean, dropped):
        plane.admit("a", _prefill_state(cfg, params, 64, 4))
    layers = dropped.pool_layers()
    blocks = [0, 1]           # full blocks (cur_len=64 appends to block 2)
    host = {}                 # the DRAM copies the restores come from
    row = dropped.rows["a"]
    for l in layers:
        c = dropped.state["caches"][l]
        host[l] = (np.asarray(gather_row_blocks(c["k"], row, blocks)),
                   np.asarray(gather_row_blocks(c["v"], row, blocks)))
        dropped.drop_blocks("a", l, blocks)

    def stage_cb(layer, sel, prev):
        k, v = host[layer]
        dropped.restore_blocks_fused(
            layer, {"a": (blocks, k, v)}, before_use=True)

    lg_clean, _, _ = clean.step_staged(params, {"a": 7})
    lg_drop, _, _ = dropped.step_staged(params, {"a": 7}, stage_cb)
    np.testing.assert_allclose(np.asarray(lg_drop), np.asarray(lg_clean),
                               rtol=1e-5, atol=1e-5)
    assert dropped.blocks_restored_before_use == len(layers) * len(blocks)
    _assert_states_equal(dropped.extract("a"), clean.extract("a"))


def test_staged_launches_o_num_layers_traces_bounded(smoke_setup):
    """Per-iteration launch count is exactly embed + 2 x attn layers +
    recurrent layers + logits; traces stay one per (stage, shape bucket)."""
    cfg, params = smoke_setup("qwen2-0.5b")
    cfg = dataclasses.replace(cfg, name=cfg.name + "-staged-retrace")
    plane = DevicePoolPlane(cfg, BucketingPolicy(batch_buckets=(1, 2, 4),
                                                 block_bucket=4))
    fns = plane.staged_fns
    assert fns.calls == 0 and fns.trace_count == 0
    per_iter = pa.staged_launches_per_iteration(cfg)
    plane.admit("a", _prefill_state(cfg, params, 40, 4))
    for tok in (5, 6, 7):
        plane.step_staged(params, {"a": tok})
    assert fns.calls == 3 * per_iter
    n_stage_kinds = pa.staged_stage_kinds(cfg)
    assert fns.trace_count == n_stage_kinds          # one bucket so far
    plane.admit("b", _prefill_state(cfg, params, 33, 4, seed=1))
    plane.step_staged(params, {"a": 5, "b": 6})
    plane.step_staged(params, {"b": 6})     # occupancy change: no retrace
    assert fns.trace_count == 2 * n_stage_kinds      # b_cap=2 bucket
    pa.assert_cache_hit_invariant(fns)
    assert fns.calls == 5 * per_iter                 # 5 steps total


def test_jit_retraces_bounded_by_buckets(smoke_setup):
    """The cache-hit invariant: one XLA trace per distinct shape bucket,
    never per iteration or per occupancy change."""
    cfg, params = smoke_setup("qwen2-0.5b")
    # the decode-fn cache is keyed structurally, so give this test its own
    # entry (fresh counters) via a distinct name
    cfg = dataclasses.replace(cfg, name=cfg.name + "-retrace")
    plane = DevicePoolPlane(cfg, BucketingPolicy(batch_buckets=(1, 2, 4),
                                                 block_bucket=4))
    fn = plane.decode_fn
    assert fn.trace_count == 0
    plane.admit("a", _prefill_state(cfg, params, 40, 4))
    for tok in (5, 6, 7):
        plane.step(params, {"a": tok})
    assert fn.trace_count == 1                     # b_cap=1 bucket
    plane.admit("b", _prefill_state(cfg, params, 33, 4, seed=1))
    plane.step(params, {"a": 5, "b": 6})
    plane.step(params, {"b": 6})                   # occupancy change: no trace
    assert fn.trace_count == 2                     # b_cap=2 bucket
    plane.release("a")
    plane.admit("c", _prefill_state(cfg, params, 48, 4, seed=2))
    plane.step(params, {"b": 5, "c": 6})           # same buckets: cache hit
    assert fn.trace_count == 2
    pa.assert_cache_hit_invariant(fn)
    n_buckets = len({1, 2}) * 1                    # batch buckets x nb buckets
    assert fn.trace_count <= n_buckets


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "minicpm3-4b"])
@pytest.mark.parametrize("case", ["one", "unsorted", "row", "capacity",
                                  "empty"])
def test_drop_blocks_matches_eager_scatter(smoke_setup, arch, case):
    """The one-program drop zeroes exactly what the eager
    ``scatter_row_blocks(pool, row, idx, zeros)`` zeroes, byte for byte,
    in k and (where the layer has one; MLA has not) v; the other row is
    untouched; it never reads the device back; an empty list dispatches
    nothing."""
    cfg, params = smoke_setup(arch)
    plane = DevicePoolPlane(cfg, BucketingPolicy(block_bucket=12))
    # 120 tokens put data in each of the 4 blocks (32 tokens a block)
    plane.admit("a", _prefill_state(cfg, params, 120, 4))
    plane.admit("b", _prefill_state(cfg, params, 120, 4, seed=1))
    layer = plane.pool_layers()[0]
    c = plane.state["caches"][layer]
    assert ("v" in c) == (cfg.attention_type != "mla")
    # "row": every block the prefill filled; "capacity": every block the
    # padded row holds, the longest list a drop can get
    blocks = {"one": [2], "unsorted": [3, 0, 2], "row": list(range(4)),
              "capacity": list(range(plane.nb_cap))[::-1],
              "empty": []}[case]
    row = plane.rows["a"]
    before = {key: c[key] for key in ("k", "v") if key in c}
    expect = {}
    for key, pool in before.items():
        zero = jnp.zeros((pool.shape[1], len(blocks)) + pool.shape[3:],
                         pool.dtype)
        expect[key] = np.asarray(scatter_row_blocks(pool, row, blocks, zero))
    before = {key: np.asarray(pool) for key, pool in before.items()}
    for key, pool in before.items():       # every listed filled block
        assert np.all(np.abs(pool[row][:, [b for b in blocks if b < 4]])
                      .sum(axis=(0, 2, 3)) > 0), key
    calls = block_drop().calls
    with jax.transfer_guard_device_to_host("disallow"):
        plane.drop_blocks("a", layer, blocks)
    assert block_drop().calls == calls + (1 if blocks else 0)
    assert plane.blocks_dropped == len(blocks)
    for key, want in expect.items():
        got = np.asarray(c[key])
        assert got.dtype == want.dtype
        assert got.tobytes() == want.tobytes(), key
        other = 1 - row
        assert got[other].tobytes() == before[key][other].tobytes(), key
        if not blocks:
            assert got.tobytes() == before[key].tobytes(), key


def test_drop_blocks_traces_once_per_pool_shape(smoke_setup):
    """Every drop count on one plane runs one program, traced once; a
    plane with another block capacity traces it once more."""
    cfg, params = smoke_setup("qwen2-0.5b")
    fn = block_drop()
    # block capacities no other test's plane has, so these traces are new
    planes = []
    for bucket in (13, 17):
        plane = DevicePoolPlane(cfg, BucketingPolicy(block_bucket=bucket))
        plane.admit("a", _prefill_state(cfg, params, 40, 4))
        planes.append(plane)
    traces = fn.trace_count
    first = planes[0]
    for layer in first.pool_layers()[:2]:
        for n in range(1, first.nb_cap + 1):
            first.drop_blocks("a", layer, list(range(n)))
    assert fn.trace_count == traces + 1
    second = planes[1]
    for n in (1, second.nb_cap):
        second.drop_blocks("a", second.pool_layers()[0], list(range(n)))
    assert fn.trace_count == traces + 2
    pa.assert_donation_contract(first.staged_fns)
