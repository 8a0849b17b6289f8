"""Serving launcher: drive the real engine with a synthetic LongBench trace.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --requests 8 --prompt 192 --gen 8 [--prefill chunked] [--no-ws] \
        [--trace-out DIR] [--prom]

Prints TTFT/TBT/throughput and the hierarchical-KV transfer statistics
(FlashH2D/D2H calls, hit rates) — the numbers the paper's Figs. 10–16
track — all read from ``engine.metrics_snapshot()``, the one obs
surface.  ``--trace-out DIR`` serves under a ``jax.profiler`` capture
written to DIR: the program's ``repro:`` spans beside the runtime's host
events and the device's operations, with a ``perfetto_trace.json.gz``
that https://ui.perfetto.dev loads; ``--prom`` dumps the Prometheus text
exposition.
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.request import Request


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=192)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--rate", type=float, default=100.0)
    ap.add_argument("--prefill", default="layer_segmented",
                    choices=["layer_segmented", "chunked"])
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--no-ws", action="store_true")
    ap.add_argument("--cache-blocks", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default="",
                    help="serve under a jax.profiler capture written to "
                         "this directory (open its perfetto_trace.json.gz "
                         "in ui.perfetto.dev)")
    ap.add_argument("--prom", action="store_true",
                    help="print the Prometheus text exposition of the "
                         "final metrics snapshot")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed), jnp.float32)
    eng = ServingEngine(params, cfg, EngineConfig(
        prefill_mode=args.prefill, chunk_size=args.chunk,
        ws_control=not args.no_ws,
        hbm_blocks_per_request=args.cache_blocks, seed=args.seed))

    rng = np.random.default_rng(args.seed)
    t = 0.0
    for _ in range(args.requests):
        t += rng.exponential(1.0 / args.rate)
        req = Request(prompt_len=args.prompt, max_new_tokens=args.gen,
                      arrival_time=t)
        extra = {}
        if cfg.is_encoder_decoder:
            extra["frames"] = np.ones((1, 16, cfg.d_model), np.float32) * .01
        if cfg.frontend == "vit_patch_stub":
            extra["patch_embeds"] = np.ones(
                (1, cfg.num_patches, cfg.d_model), np.float32) * .01
        eng.submit(req, **extra)

    if args.trace_out:
        with jax.profiler.trace(args.trace_out, create_perfetto_trace=True):
            m = eng.run()
        print(f"trace -> {args.trace_out}")
    else:
        m = eng.run()
    s = eng.metrics_snapshot()
    print(f"arch={cfg.name} prefill={args.prefill} ws={not args.no_ws}")
    print(f"finished={m.num_finished}/{args.requests} "
          f"iters={s['engine.iterations']:.0f}")
    clock = ("measured" if eng.eng.charge_real_time
             else "modeled (cost model)")
    print(f"mean TTFT {m.mean_ttft*1e3:.2f} ms | mean TBT "
          f"{m.mean_tbt*1e3:.3f} ms | {m.token_throughput:.1f} tok/s "
          f"[{clock}]")
    print(f"FlashH2D: {s['kv.h2d_calls']:.0f} fused launches, "
          f"{s['kv.h2d_blocks']:.0f} blocks, {s['kv.h2d_bytes']/1e6:.2f} MB")
    print(f"FlashD2H: {s['kv.d2h_calls']:.0f} saves, "
          f"{s['kv.d2h_blocks']:.0f} blocks, {s['kv.d2h_bytes']/1e6:.2f} MB")
    tot = max(s["kv.hits"] + s["kv.misses"], 1)
    print(f"HBM cache: {s['kv.hits']:.0f} hits / {s['kv.misses']:.0f} "
          f"misses ({100*s['kv.hits']/tot:.1f}% hit rate), "
          f"{s['kv.evictions']:.0f} evictions")
    overlap = eng.stage_overlap_measured()
    if overlap is not None:
        print(f"async host-stage overlap: {100*overlap:.1f}% of host-stage "
              f"work off-thread ({s['worker.jobs_run']:.0f} worker jobs)")
    if args.prom:
        print(eng.metrics_prometheus(), end="")
    return 0


if __name__ == "__main__":
    sys.exit(main())
