"""A smoke-size benchmark root for the CPU tests of ``bench/``.

``make(tmp)`` writes a ``BENCHMARK.json`` with two cells on a two-layer
configuration of the qwen2 file's keys, traffic and check files beside
it, and links the real metric readers, architecture modules, peaks table
and program, so a
test drives the harness's own code at a size a test run can hold.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

SMOKE = {"hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "vocab_size": 512}


def make(root: Path, limits=None) -> Path:
    root = Path(root)
    for d in ("configs", "traffic", "checks"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    for name in ("metrics", "archs", "peaks.json"):
        os.symlink(BENCH / name, root / "bench" / name)
    os.symlink(REPO / "src", root / "src")
    cfg = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    cfg.update(SMOKE)
    # the CPU computes float32 matmuls in full at any stated precision, so
    # the smoke cells state the default one, whose control is bfloat16
    cfg["matmul_precision"] = "default"
    cfg["dsa"].update(block_size=8, token_budget=64)
    # a 6-pair LRU, so eviction, block drops and restores all run
    cfg["engine"] = {"prefill_max_tokens_per_step": 64,
                     "hbm_blocks_per_request": 6}
    (root / "bench/configs/smoke.json").write_text(json.dumps(cfg))
    dec, pre = ["smoke.decode"], ["smoke.prefill"]

    def metric(name, unit, moves=None, cells=None, **kw):
        m = {"name": name, "unit": unit, "better": "lower",
             "source": "host_clock", **kw}
        if moves:
            m.update(layer="smoke", moves=moves)
        if cells:
            m["workloads"] = cells
        return m
    bm = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
          "run_seconds": 1,
          "configs": [{"name": "smoke", "source": "smoke",
                       "file": "bench/configs/smoke.json", "reduced": [],
                       "why": "smoke"}],
          "workloads": [
              {"name": "smoke.decode", "config": "smoke",
               "traffic": "decode", "chips": 1, "why": "smoke"},
              {"name": "smoke.prefill", "config": "smoke",
               "traffic": "prefill", "chips": 1, "why": "smoke"}],
          "end_to_end": [
              metric("output_tok_s", "tokens/s", cells=dec),
              metric("tbt_p95_ms", "ms", cells=dec),
              metric("ttft_p50_s", "s", cells=pre),
              metric("prompt_tok_s", "tokens/s", cells=pre),
              metric("setup_s", "s")],
          "per_layer": [
              metric("hbm_hit_rate.decode", "%", "output_tok_s", dec),
              metric("mfu.prefill", "%", "ttft_p50_s", pre)]}
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    # decode sessions outlast a one-second window on any CPU (a fast one
    # takes ~80 steps in it), as the decode cells' sessions outlast theirs
    traffic = {
        "decode": {"clients": 3, "prompt_tokens": {"fixed": 200},
                   "max_new_tokens": 256, "next_on": "finish",
                   "setup": {"then_steps": 2}},
        "prefill": {"clients": 2, "prompt_tokens": {"stratified": {
            "n": 8, "clip": [40, 300],
            "mixture": [[100, 0.6], [200, 0.5]]}},
            "max_new_tokens": 1, "next_on": "first_token",
            "setup": {"warm": [{"lengths": ["min", "min"], "wave": 2},
                               {"lengths": "all", "wave": 2}]}}}
    for name, t in traffic.items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(t))
    # on the CPU both sides compute float32 at full precision, so the
    # program reads exactly or at rounding; these limits sit far above
    # that and far below what a wrong token or a bfloat16 control reads
    lim = limits or {"gap": 1e-3, "kv": 1e-4}
    checks = {
        "smoke.decode": {"sample": 3, "tokens": "all", "limits": {
            "first_gap": {"limit": lim["gap"]},
            "decode_gap": {"limit": lim["gap"]},
            "kv_err": {"limit": lim["kv"]},
            "kv_err_decode": {"limit": lim["kv"]}}},
        "smoke.prefill": {"sample": 3, "tokens": "first",
                          "prompt_bucket": 64, "limits": {
                              "first_gap": {"limit": lim["gap"]},
                              "kv_err": {"limit": lim["kv"]}}}}
    for name, c in checks.items():
        (root / f"bench/checks/{name}.json").write_text(json.dumps(c))
    return root


def run(root: Path, cell: str, seed: int = 1234567890123, seconds=1.0,
        control=False):
    """One harness run of a smoke cell on whatever devices JAX has."""
    import time
    import jax
    from benchkit import runner
    from benchkit.spec import Cell
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    return runner.run_cell(Cell(cell, root), seed, seconds, False,
                           jax.devices(), time.perf_counter(),
                           peaks["TPU v5 lite"], control=control)
