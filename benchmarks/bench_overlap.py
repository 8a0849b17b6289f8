"""Paper Fig. 8 (selection overlap) + the staged-vs-fused decode plane.

fig8: runs the REAL tiny model: decode steps with DSA selection enabled,
then for each window size w computes the mean fraction of step-t selections
already present in the union of the previous w steps' selections — the
temporal locality that justifies the working-set estimator (w=12 plateaus).

overlap_plane: runs the REAL engine under eviction pressure (1-block LRU)
on the staged per-layer pipeline vs the fused persistent plane and reports,
per plane: jitted launches per decode iteration (staged pays O(num_layers)
launches to buy the restore window), the restore-before-use rate (fraction
of H2D block restores that landed between select and attend — 1.0 on the
staged plane, 0.0 on the fused plane, where restores can only land after
the forward), and the MODELED per-iteration decode time under the fused
plane's sum charging (compute + all transfers serial) vs the staged
pipeline's per-layer max(compute, transfer) overlap charging
(``modeled_*`` fields — cost-model numbers, not wall clock).

achieved_overlap: the MEASURED counterpart — runs the real engine under
the same 1-block-LRU pressure with ``stage_dispatch="sync"`` vs
``"async"`` (the default) and reports wall-clock tokens/s plus the
per-layer dispatch timeline the planes record (`stage_timeline`): how
much of each layer's host stage the async pipeline moved off the
dispatch thread (`measured_overlap_fraction`), next to the cost model's
max(compute, transfer) bound for reference.
"""
from __future__ import annotations

import os as _os
import sys as _sys

_R = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_sys.path[:0] = [p for p in (_R, _os.path.join(_R, "src"))
                 if p not in _sys.path]

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, header
from repro.configs import get_smoke_config
from repro.models import model as M


def fig8_section() -> None:
    header("fig8_overlap: selection overlap vs window size (real decode)")
    base = get_smoke_config("qwen2-0.5b")
    # small budget so selection is actually sparse (8 of 24 blocks)
    cfg = dataclasses.replace(
        base, dsa=dataclasses.replace(base.dsa, token_budget=8 * 32))
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    S, steps = 736, 48
    toks = np.random.default_rng(0).integers(4, cfg.vocab_size, S)
    nb = S // cfg.dsa.block_size + 4
    logits, state = M.prefill(params, cfg,
                              {"tokens": jnp.asarray(toks[None])}, nb,
                              cache_dtype=jnp.float32)
    history = []          # per step: set of (layer, block)
    tok = int(jnp.argmax(logits[0]))
    for _ in range(steps):
        logits, state, info = M.decode_step(
            params, cfg, jnp.asarray([tok], jnp.int32), state,
            return_info=True)
        sel = set()
        for l, s in info["selected"].items():
            for b in np.asarray(s[0]).ravel():
                sel.add((int(l), int(b)))
        history.append(sel)
        tok = int(jnp.argmax(logits[0]))

    for w in (1, 2, 4, 8, 12, 16):
        ratios = []
        for t in range(w, len(history)):
            union = set()
            for s in history[t - w:t]:
                union |= s
            if history[t]:
                ratios.append(len(history[t] & union) / len(history[t]))
        emit("fig8", window=w, overlap=round(float(np.mean(ratios)), 4))


def staged_vs_fused_section() -> None:
    """Real-engine comparison of the staged per-layer pipeline against the
    fused persistent plane under eviction pressure (see module docstring
    for the emitted fields)."""
    from repro.core.device_pool import decode_fn_for, staged_fns_for
    from repro.serving import costmodel as cm
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.request import Request

    header("overlap_plane: staged vs fused decode plane "
           "(real engine, 1-block LRU eviction pressure)")
    cfg = get_smoke_config("qwen2-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    hw = cm.TPU_V5E
    for mode in ("staged", "persistent"):
        eng = ServingEngine(params, cfg, EngineConfig(
            chunk_size=64, r_max=4, decode_plane=mode,
            hbm_blocks_per_request=1))
        fns = staged_fns_for(cfg, "ref")
        fused = decode_fn_for(cfg, "ref")
        calls0 = fns.calls if mode == "staged" else fused.calls
        rng = np.random.default_rng(3)
        for _ in range(3):
            eng.submit(Request(prompt_len=64, max_new_tokens=12),
                       tokens=rng.integers(4, cfg.vocab_size,
                                           64).astype(np.int32))
        eng.run()
        iters = max(eng.decode_step_calls, 1)
        calls = (fns.calls if mode == "staged" else fused.calls) - calls0
        [plane] = eng.planes.values()
        rate = plane.blocks_restored_before_use \
            / max(plane.blocks_restored, 1)
        # modeled per-iteration decode time from the measured mean restore
        # traffic: fused = compute + all transfer serial; staged = per-layer
        # max(compute, transfer) with the traffic split across attn layers
        mean_loads = sum(eng.loads_per_iter) / max(len(eng.loads_per_iter), 1)
        bytes_per_iter = mean_loads * eng.geom.block_bytes_per_head \
            * eng.geom.num_kv_heads
        attended = min(cfg.dsa.token_budget, 1 << 30)
        t_sum = cm.decode_time(hw, eng.mc, 3, attended) \
            + cm.fused_transfer_time(hw, int(bytes_per_iter))
        n_attn = cfg.num_attention_layers()
        per_layer = [int(bytes_per_iter // n_attn)
                     if M.layer_kind(cfg, l) == "attn" else 0
                     for l in range(cfg.num_layers)]
        t_overlap = cm.overlapped_decode_time(hw, eng.mc, 3, attended,
                                              per_layer)
        emit("overlap_plane", mode=mode,
             launches_per_iter=round(calls / iters, 2),
             restore_before_use_rate=round(rate, 3),
             blocks_dropped=plane.blocks_dropped,
             modeled_t_iter_sum_ms=round(t_sum * 1e3, 4),
             modeled_t_iter_overlap_ms=round(t_overlap * 1e3, 4),
             modeled_overlap_speedup=round(t_sum / max(t_overlap, 1e-12), 3))


def achieved_overlap_section() -> None:
    """Measured (wall-clock) async-dispatch overlap: the same engine and
    eviction pressure as ``overlap_plane``, sync vs async stage dispatch.

    Per mode: end-to-end wall seconds and decode tokens/s, plus the
    last-iteration per-layer dispatch timeline the staged plane records —
    ``dispatch_sync_ms`` (the driver's np.asarray of the selection
    tensor, the one allowed per-layer block) and ``host_stage_ms`` (the
    stage callback: FlashD2H write-back, LRU, FlashH2D restores).  The
    summary row's ``measured_overlap_fraction`` is worker ``busy_s`` over
    (busy_s + the plane's accumulated ``host_stage_s``), the fraction of
    host-stage work that ran off the dispatch thread
    (``engine.stage_overlap_measured()``); ``host_stage_shrink_fraction``
    keeps the cross-run view (how much the dispatch-thread host stage
    shrank vs sync).  Wall speedups stay informational on CPU smoke
    hardware (noise); with ``REPRO_TRACE_DIR`` set the async run is
    served under a ``jax.profiler`` capture written to
    ``<dir>/fig8_achieved_overlap`` (the nightly artifact next to
    BENCH_*.json: the ``repro:`` spans of the dispatch and worker
    threads, and a Perfetto-loadable ``perfetto_trace.json.gz``)."""
    from benchmarks.common import Timer
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.request import Request

    header("achieved_overlap: sync vs async stage dispatch "
           "(real engine wall clock, 1-block LRU eviction pressure)")
    cfg = get_smoke_config("qwen2-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    stage_ms = {}
    wall = {}
    engines = {}

    def engine(mode):
        eng = ServingEngine(params, cfg, EngineConfig(
            chunk_size=64, r_max=4, hybrid_plane="split",
            hbm_blocks_per_request=1, stage_dispatch=mode))
        rng = np.random.default_rng(3)
        for _ in range(3):
            eng.submit(Request(prompt_len=64, max_new_tokens=12),
                       tokens=rng.integers(4, cfg.vocab_size,
                                           64).astype(np.int32))
        return eng

    for mode in ("sync", "async"):
        eng = engine(mode)
        with Timer() as t:
            eng.run()
        engines[mode] = eng
        [plane] = eng.planes.values()
        tl = plane.stage_timeline            # last decode iteration
        sync_ms = sum(s for _, s, _ in tl) * 1e3
        host_ms = sum(h for _, _, h in tl) * 1e3
        toks = eng.decode_tokens
        wall[mode] = t.dt
        stage_ms[mode] = host_ms
        emit("achieved_overlap", mode=mode,
             wall_s=round(t.dt, 3),
             decode_tok_per_s=round(toks / max(t.dt, 1e-9), 2),
             dispatch_sync_ms=round(sync_ms, 4),
             host_stage_ms=round(host_ms, 4),
             host_syncs=plane.host_syncs,
             timeline_layers=len(tl))
    a = engines["async"]
    measured = a.stage_overlap_measured()
    emit("achieved_overlap", mode="summary",
         measured_overlap_fraction=(round(measured, 6)
                                    if measured is not None else None),
         worker_jobs_run=a.worker_jobs_run,
         host_stage_shrink_fraction=round(
             max(0.0, 1.0 - stage_ms["async"] / max(stage_ms["sync"],
                                                    1e-12)), 3),
         async_wall_speedup=round(wall["sync"] / max(wall["async"], 1e-12),
                                  3))
    tdir = _os.environ.get("REPRO_TRACE_DIR", "")
    if tdir:
        # a run of its own, so the capture's cost stays out of the
        # wall-clock rows above
        trace_dir = _os.path.join(tdir, "fig8_achieved_overlap")
        with jax.profiler.trace(trace_dir, create_perfetto_trace=True):
            engine("async").run()
        emit("achieved_overlap", mode="trace", path=trace_dir)


def main() -> None:
    fig8_section()
    staged_vs_fused_section()
    achieved_overlap_section()


if __name__ == "__main__":
    main()
