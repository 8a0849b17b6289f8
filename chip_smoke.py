#!/usr/bin/env python3
"""Drive the served path once on a TPU and check what comes out.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --chips 4     # the context-parallel plane, 4 chips

One chip: qwen2-0.5b at its published widths (24 layers, d=896, 14/2
heads, vocab 151936; random weights from ``--seed``) behind the default
``EngineConfig`` -- staged decode plane, mixed hybrid plane, async stage
dispatch, layer-segmented prefill plane.  Four requests with 4.5k-7.7k
token prompts and 16 new tokens each: the prompts outgrow the per-request
HBM LRU (96 blocks) and the DSA token budget (2048), so FlashD2H saves,
LRU evictions and FlashH2D restores all run.  Checks, each a hard failure:

* the engine's logits against a plain one-shot ``M.prefill`` of the
  prompt followed by ``M.decode_step`` fed the same tokens (cosine >=
  COS_MIN for the first token, and for each later one while both made
  the same DSA selections -- see ``compare_runs``);
* ``sparse_decode_attention``, ``gather_blocks_hkv`` and
  ``scatter_blocks_hkv`` compiled on the chip against their ``ref.py``
  oracles;
* the engine with ``attn_impl="kernel"`` against the engine above.

``--chips 4`` runs only the same requests through ``mesh_spec="model=4"``
(two KV heads over four chips: block-sharded pool slots) and the
single-device engine in the same process, and checks that the slots span
four devices and that both engines' logits agree (cosine >= COS_MIN).

Each phase prints one JSON line: wall time on the host clock around work
that ends in a host read of the sampled tokens, compile time (a cold run
minus a warm run of the same shapes), tokens/s and device memory.  The
last line is ``{"ok": true, "device": {...}}``; with no TPU, or when any
phase fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-0.5b"
PROMPT_LENS = (4500, 7700, 4500, 7700)
GEN = 16
COS_MIN = 0.999          # logits agreement, per generated token
KERNEL_TOL = 2e-2        # attention kernel vs f32 oracle, relative max err


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def cosine(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


# ---------------------------------------------------------------------------
# phases (importable: the same functions run at smoke size on CPU)
# ---------------------------------------------------------------------------

def make_prompts(cfg, lens, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


class Run:
    """One serving run's outputs, per request in submission order:
    sampled ``tokens``, the ``logits`` each was sampled from, and the DSA
    ``selections`` of every decode step (a set of (attention layer,
    block) per step; step i produced token i + 1)."""

    def __init__(self, tokens, logits, selections):
        self.tokens, self.logits, self.selections = (tokens, logits,
                                                     selections)


def run_engine(params, cfg, prompts, gen: int, **eng_kw):
    """Serve ``prompts`` through ``ServingEngine`` and return (engine,
    its metrics, a ``Run``, wall seconds).  The wall time ends with the
    host read of the last sampled token."""
    import numpy as np
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.request import Request

    eng = ServingEngine(params, cfg, EngineConfig(**eng_kw))
    logits, sels = {}, {}
    sample = eng._sample
    observe = eng.scheduler.observe_selection

    def recording_sample(st):
        logits.setdefault(st.req.req_id, []).append(
            np.asarray(st.last_logits, np.float32)[0])
        return sample(st)

    def recording_observe(req, pairs):
        sels.setdefault(req.req_id, []).append(frozenset(pairs))
        return observe(req, pairs)

    eng._sample = recording_sample
    eng.scheduler.observe_selection = recording_observe
    rids = []
    for toks in prompts:
        req = Request(prompt_len=len(toks), max_new_tokens=gen)
        eng.submit(req, tokens=toks)
        rids.append(req.req_id)
    t0 = time.perf_counter()
    m = eng.run()
    wall = time.perf_counter() - t0
    check(m.num_finished == len(prompts),
          f"{m.num_finished}/{len(prompts)} requests finished")
    tokens = [list(eng.states[r].out_tokens) for r in rids]
    for r, t in zip(rids, tokens):
        check(len(t) == gen and len(logits[r]) == gen
              and len(sels.get(r, ())) == gen - 1,
              f"{r}: {len(t)} tokens, {len(logits[r])} logits, "
              f"{len(sels.get(r, ()))} selections, want {gen}")
        check(all(np.isfinite(x).all() for x in logits[r]),
              f"{r}: non-finite logits")
    return eng, m, Run(tokens, [logits[r] for r in rids],
                       [sels[r] for r in rids]), wall


def reference_run(params, cfg, prompt, tokens, num_blocks: int):
    """Plain one-shot ``M.prefill`` of the prompt, then ``M.decode_step``
    fed the engine's own ``tokens``: (the logits each of them was sampled
    from, the DSA selections of each step), in the engine's float32 KV
    and with no HBM/DRAM tier."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.dsa import selected_block_ids
    from repro.models import model as M

    prefill = jax.jit(lambda p, t: M.prefill(
        p, cfg, {"tokens": t}, num_blocks, cache_dtype=jnp.float32))
    step = jax.jit(lambda p, t, s: M.decode_step(p, cfg, t, s,
                                                 return_info=True))
    logits, state = prefill(params, jnp.asarray(prompt)[None])
    out, sels = [np.asarray(logits, np.float32)[0]], []
    for tok in tokens[:-1]:
        logits, state, info = step(params, jnp.asarray([tok], jnp.int32),
                                   state)
        out.append(np.asarray(logits, np.float32)[0])
        sels.append(frozenset(
            (layer, b) for layer, sel in info["selected"].items()
            for b in selected_block_ids(np.asarray(sel)[0])))
    return out, sels


def compare_runs(name, a: Run, b: Run):
    """Per-token logits cosine of two runs of the same prompts.  Token 0
    (from prefill) is always compared.  Token i > 0 is compared while
    both runs fed the same tokens and made the same DSA selections in
    every step up to i: from there on equal logits are required.  Once
    the selections differ (a near-tie in the block scores resolved the
    other way under different rounding) the two runs attend different
    KV, so their later logits are reported, not gated.  Returns a summary
    for the phase's line."""
    worst, n, stop, cos = 1.0, 0, [], []
    for r in range(len(a.logits)):
        cs = [cosine(x, y) for x, y in zip(a.logits[r], b.logits[r])]
        cos.append([round(c, 6) for c in cs])
        upto = 1
        while (upto < len(cs)
               and a.tokens[r][upto - 1] == b.tokens[r][upto - 1]
               and a.selections[r][upto - 1] == b.selections[r][upto - 1]):
            upto += 1
        stop.append(upto)
        for i in range(upto):
            check(cs[i] >= COS_MIN, f"{name}: request {r} token {i} logits "
                                    f"cosine {cs[i]:.6f} < {COS_MIN} with "
                                    f"equal inputs and selections")
            worst, n = min(worst, cs[i]), n + 1
    diff = [[len(x ^ y) for x, y in zip(sa, sb)]
            for sa, sb in zip(a.selections, b.selections)]
    return {"worst_gated_cosine": worst, "tokens_gated": n,
            "gated_upto": stop, "cosine_by_token": cos,
            "selection_diff_by_step": diff, "cos_min": COS_MIN,
            "tokens_equal": a.tokens == b.tokens}


def kernel_phase(cfg, seed: int):
    """The three main-path kernels, compiled for the default backend, at
    ``cfg``'s widths against their ``ref.py`` oracles."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    B, Hq, Hkv, D = 4, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bs, K, NB = cfg.dsa.block_size, cfg.dsa.top_k_blocks, 256
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (B, Hq, D), jnp.float32)
    kp = jax.random.normal(keys[1], (B, Hkv, NB, bs, D), jnp.float32)
    vp = jax.random.normal(keys[2], (B, Hkv, NB, bs, D), jnp.float32)
    idx = jnp.stack([jax.random.permutation(k, NB)[:K] for k in
                     jax.random.split(keys[3], B * Hkv)]
                    ).reshape(B, Hkv, K).astype(jnp.int32)
    valid = jnp.ones((B, Hkv, K), jnp.int32).at[:, :, -3:].set(0)
    cur = jnp.asarray([NB * bs - 7, NB * bs // 2, 5000, NB * bs],
                      jnp.int32)
    out = {}
    got = ops.sparse_decode_attention(q, kp, vp, idx, valid, cur)
    with jax.default_matmul_precision("highest"):
        want = ref.sparse_decode_attention(q, kp, vp, idx, valid, cur)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    check(err <= KERNEL_TOL, f"sparse_decode_attention relative max error "
                             f"{err:.3e} > {KERNEL_TOL}")
    out["sparse_decode_attention_rel_err"] = err

    pool = kp[0]                                          # (Hkv, NB, bs, D)
    sel = idx[0, 0]
    got = ops.gather_blocks_hkv(pool, sel)
    check(bool(jnp.array_equal(got, ref.gather_blocks_hkv(pool, sel))),
          "gather_blocks_hkv differs from ref.gather_blocks_hkv")
    new = jax.random.normal(keys[4], (Hkv, K, bs, D), jnp.float32)
    want = ref.scatter_blocks_hkv(pool, new, sel)
    got = ops.scatter_blocks_hkv(pool, new, sel)
    check(bool(jnp.array_equal(got, want)),
          "scatter_blocks_hkv differs from ref.scatter_blocks_hkv")
    out["gather_scatter_exact"] = True
    return out


def device_memory():
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

def one_chip(params, cfg, seed: int) -> None:
    prompts = make_prompts(cfg, PROMPT_LENS, seed)
    n_tok = GEN * len(prompts)

    eng, _, run, cold = run_engine(params, cfg, prompts, GEN)
    s = eng.metrics_snapshot()
    emit(phase="engine_ref_cold", wall_s=cold,
         prompt_tokens=[len(p) for p in prompts], new_tokens=n_tok,
         clock_measured=bool(eng.eng.charge_real_time),
         d2h_saves=s["kv.d2h_calls"], d2h_blocks=s["kv.d2h_blocks"],
         evictions=s["kv.evictions"], h2d_restores=s["kv.h2d_calls"],
         h2d_blocks=s["kv.h2d_blocks"],
         blocks_dropped=s["plane.blocks_dropped"],
         blocks_restored=s["plane.blocks_restored"],
         hbm_hit_rate=s["kv.hits"] / max(s["kv.hits"] + s["kv.misses"], 1))
    check(s["kv.h2d_blocks"] > 0 and s["plane.blocks_restored"] > 0,
          "no FlashH2D restore ran")
    check(s["kv.evictions"] > 0 and s["kv.d2h_calls"] > 0,
          "no LRU eviction or FlashD2H save ran")
    num_blocks = [st.num_blocks for st in eng.states.values()]

    eng2, m, run2, warm = run_engine(params, cfg, prompts, GEN)
    check(run2.tokens == run.tokens, "a warm rerun sampled different tokens")
    emit(phase="engine_ref_warm", wall_s=warm, compile_s=cold - warm,
         tokens_per_s=n_tok / warm, mean_ttft_s=m.mean_ttft,
         mean_tbt_s=m.mean_tbt, iterations=eng2.iterations)

    t0 = time.perf_counter()
    refs = [reference_run(params, cfg, p, t, nb)
            for p, t, nb in zip(prompts, run.tokens, num_blocks)]
    ref = Run(run.tokens, [r[0] for r in refs], [r[1] for r in refs])
    emit(phase="reference", wall_s=time.perf_counter() - t0,
         **compare_runs("engine vs M.prefill+decode_step", run, ref))

    t0 = time.perf_counter()
    res = kernel_phase(cfg, seed)
    emit(phase="kernels", wall_s=time.perf_counter() - t0, **res)

    _, _, krun, kwall = run_engine(params, cfg, prompts, GEN,
                                   attn_impl="kernel")
    emit(phase="engine_kernel_cold", wall_s=kwall,
         **compare_runs("attn_impl=kernel vs ref engine", krun, run))


def four_chips(params, cfg, seed: int) -> None:
    import jax
    prompts = make_prompts(cfg, PROMPT_LENS, seed)
    _, _, run, wall1 = run_engine(params, cfg, prompts, GEN)
    emit(phase="engine_single_device", wall_s=wall1)
    eng, _, srun, wall4 = run_engine(params, cfg, prompts, GEN,
                                     mesh_spec="model=4")
    check(eng.plane_mesh is not None and eng.plane_mesh.model_size == 4,
          "the engine did not build a 4-way model mesh")
    mode = eng.plane_mesh.pool_shard_mode(cfg)
    check(mode == "blocks", f"{cfg.num_kv_heads} KV heads on 4 chips "
                            f"should shard pool blocks, not {mode}")
    pools = [c["k"] for plane in eng.planes.values()
             for c in plane.state["caches"]]
    devs = set().union(*(p.sharding.device_set for p in pools))
    check(len(devs) == 4, f"pool slots span {len(devs)} devices, want 4")
    shard_shapes = {(s.data.shape, p.shape) for p in pools
                    for s in p.addressable_shards}
    check(all(sh[2] * 4 == full[2] for sh, full in shard_shapes),
          f"pool shards {shard_shapes} do not split the block axis 4 ways")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    check(all(b and b > 0 for b in in_use[:4]),
          f"device memory in use {in_use}")
    s = eng.metrics_snapshot()
    check(s["kv.h2d_blocks"] > 0, "no FlashH2D restore ran (sharded)")
    emit(phase="engine_model4", wall_s=wall4, pool_shard_mode=mode,
         shard_and_pool_shapes=sorted(shard_shapes),
         pool_devices=len(devs), bytes_in_use=in_use,
         h2d_blocks=s["kv.h2d_blocks"],
         **compare_runs("model=4 vs single device", srun, run))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    check(len(devices) >= args.chips,
          f"{len(devices)} devices, --chips {args.chips}")
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as M
    import jax.numpy as jnp

    cache = Path(enable_compile_cache())
    emit(phase="setup", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), jax=jax.__version__, compile_cache=str(cache),
         cache_entries_before=(sum(1 for _ in cache.iterdir())
                               if cache.is_dir() else 0))
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed), jnp.float32)
    jax.block_until_ready(params)
    emit(phase="init_params", wall_s=time.perf_counter() - t0, arch=ARCH,
         layers=cfg.num_layers, d_model=cfg.d_model, heads=cfg.num_heads,
         kv_heads=cfg.num_kv_heads, vocab=cfg.vocab_size)
    if args.chips == 4:
        four_chips(params, cfg, args.seed)
    else:
        one_chip(params, cfg, args.seed)
    emit(phase="memory", peak_bytes_in_use=device_memory())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
