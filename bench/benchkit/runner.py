"""One benchmark run: set-up, the measured window, metrics, the check.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` loads the cell's files by name, makes the weights on the
device, builds the program's ``ServingEngine``, runs the mix's set-up,
measures for ``--seconds`` with the host clock, then compares what was
served with the plain reference.  Its last stdout line is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` with ``--trace 1``), and last ``check``: each compared
number beside its limit, which also ends stderr.
"""
from __future__ import annotations

import argparse
import gc
import json
import logging
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from benchkit import check as C
from benchkit import flops as F
from benchkit import timeline as TL
from benchkit.spec import ROOT, Cell, SpecError, load_json

CACHE_DIR = ROOT / ".jax_cache"


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def note(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileMonitor:
    """Counts JAX's compile events from the moment it is reset: traces,
    programs requested from the compiler (``programs``, with their
    seconds), and of those the persistent cache's hits and misses (a
    miss is a fresh compile).  One per process: JAX's listeners cannot
    be removed."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "programs",
              "/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        from jax import monitoring
        self.counts: Dict[str, float] = {}
        self.names: Dict[str, int] = {}
        self.reset()
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(
            lambda name, *a, **k: self._hit(name))
        # JAX logs each program it lowers at DEBUG: keep the names here,
        # and pass nothing on, so the run's stderr stays as it was
        log = logging.getLogger("jax._src.interpreters.pxla")
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(_Names(self.names))

    def _hit(self, name: str) -> None:
        key = self.EVENTS.get(name)
        if key is not None:
            self.counts[key] += 1

    def _duration(self, name: str, secs: float, *a, **k) -> None:
        self._hit(name)
        if self.EVENTS.get(name) == "programs":
            self.counts["programs_s"] += secs

    def reset(self) -> Dict[str, float]:
        """The counts since the last reset, with the ten programs lowered
        most often under ``top``; starts counting anew."""
        old: Dict[str, Any] = dict(self.counts)
        if old:
            old["top"] = sorted(self.names.items(), key=lambda kv: -kv[1]
                                )[:10]
        self.counts = {v: 0 for v in self.EVENTS.values()}
        self.counts["programs_s"] = 0.0
        self.names.clear()
        return old


class _Names(logging.Handler):
    """Counts the programs JAX lowers, by name and argument shapes, and
    passes warnings on to the root logger."""

    def __init__(self, names: Dict[str, int]):
        super().__init__()
        self.names = names

    def emit(self, record):
        if str(record.msg).startswith("Compiling %s with global"):
            name, avals = record.args[0], record.args[1]
            key = f"{name}{[a.str_short() for a in avals]}"
            self.names[key] = self.names.get(key, 0) + 1
        elif record.levelno >= logging.WARNING:
            logging.getLogger().handle(record)


_MONITOR: Optional[CompileMonitor] = None


def compile_monitor() -> CompileMonitor:
    global _MONITOR
    if _MONITOR is None:
        _MONITOR = CompileMonitor()
    _MONITOR.reset()
    return _MONITOR


def set_precision(cfg: Dict[str, Any]) -> None:
    """The matmul precision the configuration states, for every program
    traced from here on (the program's and the reference's)."""
    import jax
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])


def enable_cache(path: Path) -> None:
    """JAX's persistent cache at ``path``, for every program however fast
    it compiles (the program's many small eager programs included), and
    with no size limit: a limit makes JAX list and stat every entry on
    each write, which with thousands of entries costs minutes."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def install_spans(eng, spans) -> None:
    """Harness spans around the program's layers, for a traced run."""
    spans.wrap(eng, "_sample", "sampling")
    spans.wrap(eng, "_wait_device", "engine.wait_device")
    spans.wrap(eng.scheduler, "schedule", "scheduler.schedule")
    for attr, name in (("access_layer", "kv.lru"),
                       ("load_blocks_fused", "kv.h2d_gather"),
                       ("save_new_tokens_fused", "kv.d2h_save")):
        spans.wrap(eng.kv_mgr, attr, name)


def install_class_spans(spans) -> None:
    """Spans on the planes' classes and their per-stage jits; must run
    before the engine builds its planes."""
    from repro.core import device_pool as dp
    from repro.core import prefill_plane as pp
    for attr, name in (("restore_blocks_fused", "plane.restore"),
                       ("drop_blocks", "plane.drop"),
                       ("admit", "plane.admit"),
                       ("new_token_kv_async", "plane.kv_readback")):
        spans.wrap(dp.DevicePoolPlane, attr, name)
    for attr, name in (("admit", "prefill.admit"),
                       ("read_group_kv_async", "prefill.kv_readback")):
        spans.wrap(pp.PrefillPlane, attr, name)
    wrap = dp.StageFns.wrap

    def wrapped(self, stage, f, donate=()):
        call = wrap(self, stage, f, donate)

        def annotated(*a):
            with spans.span("stage." + stage):
                return call(*a)
        return annotated
    dp.StageFns.wrap = wrapped


def _memory(devices, key: str) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get(key) for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def memory_peak(devices) -> Optional[int]:
    """The process's peak on the fullest chip (set-up's included)."""
    return _memory(devices, "peak_bytes_in_use")


def memory_in_use(devices) -> Optional[int]:
    """What the fullest chip holds now."""
    return _memory(devices, "bytes_in_use")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, t_process0: float, peaks: Dict[str, float],
             clock=time.perf_counter, control: bool = False
             ) -> Dict[str, Any]:
    """One run of ``cell``.  ``control`` also reads the lower-precision
    control on the same requests (``calibrate.py``; never in a benchmark
    run)."""
    import jax
    from benchkit import model as MD
    from benchkit import loop as LP
    from benchkit import trace as TR
    from benchkit import warm as WM
    from benchkit.traffic import Traffic
    from repro.serving.engine import ServingEngine

    mon = compile_monitor()
    setup: Dict[str, float] = {"imports_s": clock() - t_process0}
    shapes = cell.shapes
    set_precision(cell.config)
    spans = TR.Spans() if trace else None
    if spans is not None:
        install_class_spans(spans)

    t = clock()
    params = MD.init_weights(shapes, seed)
    jax.block_until_ready(params)
    setup["init_s"] = clock() - t

    t = clock()
    mcfg = MD.model_config(cell.config, cell.config_entry["name"],
                           cell.root)
    ecfg = MD.engine_config(cell.config)
    eng = ServingEngine(params, mcfg, ecfg)
    traffic = Traffic(cell.traffic, shapes.vocab, seed)
    loop = LP.Loop(eng, traffic, clock, log=lambda m: note(
        f"{m}; compiles so far {mon.counts}"))
    retained: Dict[str, Any] = {}
    if "kv_err" in cell.check["limits"]:
        release = eng.kv_mgr.release

        def keep_then_release(rid):
            pool = eng.kv_mgr.pools.get(rid)
            rec = loop.records.get(rid)
            if pool is not None and rec is not None and rec.client is not None:
                retained[rid] = pool
            return release(rid)
        eng.kv_mgr.release = keep_then_release
    if spans is not None:
        install_spans(eng, spans)
    setup["engine_s"] = clock() - t

    note(f"weights and engine ready in {clock() - t_process0:.1f} s")
    warmed: Dict[str, int] = {}

    def warm_blocks():
        warmed.update(WM.warm(list(eng.planes.values()),
                              eng.eng.hbm_blocks_per_request))
    setup.update(LP.setup(loop, cell.traffic.get("setup", {}),
                          warm=warm_blocks))
    setup_compiles = mon.reset()
    note(f"set-up done in {clock() - t_process0:.1f} s: {setup}")

    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        cap = TR.capture(log_dir)
        cap.__enter__()
    snap0 = eng.metrics_snapshot()
    in_use0 = memory_in_use(devices[:cell.chips])
    loop.mark(0)
    mon.reset()
    t0 = clock()
    setup_s = t0 - t_process0
    win = spans.span("window") if spans is not None else None
    if win is not None:
        win.__enter__()
    while clock() - t0 < seconds:
        if not loop.step():
            raise RuntimeError("the engine ran out of work in the window")
    t1 = clock()
    if win is not None:
        win.__exit__(None, None, None)
    window_compiles = mon.reset()
    note(f"window closed after {t1 - t0:.1f} s, {loop.steps} steps in all")
    loop.mark(1)
    snap1 = eng.metrics_snapshot()
    in_use1 = memory_in_use(devices[:cell.chips])
    if trace:
        cap.__exit__(None, None, None)
    mem_peak = memory_peak(devices[:cell.chips])
    window_s = t1 - t0

    records = list(loop.records.values())
    delta = {k: snap1[k] - snap0.get(k, 0.0) for k in snap1
             if isinstance(snap1[k], (int, float))}
    emit(setup={**setup, "setup_s": setup_s}, warmed=warmed,
         setup_compiles=setup_compiles)
    emit(window={"seconds": window_s, "steps": delta.get(
        "engine.iterations"), "plane_traces": delta.get("plane.trace_count"),
        **window_compiles},
         memory={"peak_bytes": mem_peak, "in_use_bytes_at_open": in_use0,
                 "in_use_bytes_at_close": in_use1})
    if window_compiles["programs"]:
        note(f"the window compiled or loaded {window_compiles['programs']} "
             f"programs ({window_compiles['cache_misses']} fresh): "
             f"{window_compiles['top']}")

    reduced = None
    if trace:
        raw = TR.read(log_dir)
        reduced = TR.reduce(raw["devices"], raw["spans"], raw["window"],
                            raw["programs"])
        emit(trace_read={"path_bytes": os.path.getsize(raw["path"]),
                         "devices": sorted(raw["devices"]),
                         "spans_wrapped": spans.wrapped,
                         "idle_gap_count": reduced["idle_gap_count"]})
        import shutil
        shutil.rmtree(log_dir, ignore_errors=True)

    ctx = context(cell, shapes, records, delta, t0, t1, setup_s, reduced,
                  peaks)
    metrics = {}
    for m in cell.metrics(trace):
        v = cell.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    attempted = [r for r in records if r.client is not None
                 and r.sent <= t1 and (r.first is None or r.first > t0
                                       or r.done is None or r.done > t0)]
    failed = [r for r in attempted if r.done is not None and not r.stamps]

    # -- the check, once the program's state is freed ---------------------
    served = {rid: list(eng.states[rid].out_tokens) for rid in loop.records}
    if "kv_err" in cell.check["limits"]:
        for rid, rec in loop.records.items():
            pool = eng.kv_mgr.pools.get(rid)
            if pool is not None and rec.client is not None and rec.stamps:
                retained.setdefault(rid, pool)
    eng.close()
    del eng, loop, params
    gc.collect()
    t = clock()
    verdict = check_outputs(cell, shapes, seed, traffic, records, served,
                            retained, control)
    note(f"reference check took {clock() - t:.1f} s")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell.chips,
              "memory_peak_bytes": mem_peak}
    out = {"correct": verdict["ok"], "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    if control:
        out["control"] = verdict["control"]
        out["fault"] = verdict["fault"]
        out["largest"] = verdict["largest"]
    out["window_compiles"] = {k: window_compiles[k]
                              for k in ("programs", "cache_misses")}
    out["check"] = verdict["numbers"]
    return out


def context(cell, shapes, records, delta, t0, t1, setup_s, reduced,
            peaks) -> Dict[str, Any]:
    """What the metric readers read."""
    dec_flops = 0.0
    for r in records:
        for i, ts in enumerate(r.stamps):
            if i > 0 and t0 < ts <= t1:
                dec_flops += F.decode_token_flops(shapes, r.prompt_len + i)
    pre_flops = 0.0
    for r in records:
        pre_flops += (F.prefill_flops(shapes, r.prompt_len, *r.cursor1)
                      - F.prefill_flops(shapes, r.prompt_len, *r.cursor0))
    return {"window_s": t1 - t0, "setup_s": setup_s, "delta": delta,
            "iterations": delta.get("engine.iterations", 0.0),
            "output_tokens": TL.tokens_in(records, t0, t1),
            "gaps": TL.gaps_in(records, t0, t1),
            "ttfts": TL.ttfts(records, t0, t1),
            "prompt_tokens": TL.prompt_tokens_in(records),
            "decode_flops": dec_flops, "prefill_flops": pre_flops,
            "peak": peaks, "trace": reduced, "shapes": shapes,
            "records": records, "t0": t0, "t1": t1}


def sample(cands: List, k: int, seed: int) -> List:
    """Up to k of the candidates, drawn from the seed, always with the
    longest prompt (most served tokens among equals) in it."""
    if not cands:
        return []
    longest = max(cands, key=lambda r: (r.prompt_len, len(r.stamps)))
    rest = [r for r in cands if r is not longest]
    rng = np.random.default_rng([int(seed), 2])
    n = min(max(k - 1, 0), len(rest))
    return [longest] + [rest[i] for i in
                        sorted(rng.choice(len(rest), n, replace=False))]


def check_outputs(cell, shapes, seed, traffic, records, served, retained,
                  control: bool = False) -> Dict:
    """Run the reference over a seeded sample of served requests and
    compare (see ``check``).  With ``control``, the reference one
    precision below the configuration's (``reference.CONTROLS``) is read
    on the same requests too: its own greedy token at each position and
    its prompt KV, against the reference; and the fault of a token altered
    where it is produced, on the same reference logits."""
    from benchkit import model as MD
    from benchkit import reference as R
    spec = cell.check
    kv = "kv_err" in spec["limits"]
    first_only = spec.get("tokens") == "first"
    cands = [r for r in records if r.client is not None and r.stamps
             and (r.rid in retained or not kv)]
    pick = sample(cands, int(spec.get("sample", len(cands))), seed)
    bucket = int(spec.get("prompt_bucket", 1))
    readings, ctl, fault = C.Readings(), C.Readings(), C.Readings()
    low_dtype, low_prec = R.CONTROLS[cell.config["matmul_precision"]]
    params = MD.init_weights(shapes, seed)
    for r in pick:
        prompt, _ = traffic.request(r.client, r.index)
        S = len(prompt)
        toks = served[r.rid][:len(r.stamps)]
        if first_only:
            toks = toks[:1]
        ref_kv: Dict[int, Dict[str, np.ndarray]] = {}
        kv_cb = None
        if kv:
            pool = retained[r.rid]
            pool.flush()

            def kv_cb(layer, ref, pool=pool, S=S, rid=r.rid):
                # each (T, H, D): the prompt's S positions, then those
                # the decode steps wrote back
                C.add_kv(readings, rid, C.pool_kv(pool, layer, ref), ref, S)
                if control:
                    ref_kv[layer] = ref
        logits = R.run(params, shapes, prompt, toks, on_kv=kv_cb,
                       bucket=bucket)
        readings.add_tokens(logits, toks)
        if control:
            def ctl_kv(layer, low, S=S, rid=r.rid):
                C.add_kv(ctl, rid, low, ref_kv[layer], S)
            low = R.run(params, shapes, prompt, toks, dtype=low_dtype,
                        precision=low_prec,
                        on_kv=ctl_kv if kv else None, bucket=bucket)
            ctl.add_tokens(logits, C.control_tokens(low))
            # the fault "a token altered where it is produced", read on
            # the same reference logits
            fault.add_tokens(logits, [(t + 1) % shapes.vocab
                                      for t in toks])
    del params
    gc.collect()
    for rd in (readings, ctl, fault):
        rd.close()
    for name, errs in readings.per_position.items():
        lim = float(spec["limits"].get(name, {}).get("limit", np.inf))
        note(f"{name}: {C.DECODE_KV_QUANTILE:.0%} of {errs.size} positions "
             f"read at most {readings.values[name]:.4g}; the largest "
             f"{np.sort(errs)[-5:].tolist()}, {int((errs > lim).sum())} "
             f"over {lim:g}")
    v = C.verdict(readings, spec["limits"])
    v["checked"] = len(pick)
    if control:
        v["control"] = C.verdict(ctl, spec["limits"])["numbers"]
        v["largest"] = {f"{who}.{name}": float(errs.max())
                        for who, rd in (("program", readings),
                                        ("control", ctl))
                        for name, errs in rd.per_position.items()}
        v["fault"] = C.verdict(fault, {
            k: spec["limits"][k] for k in ("first_gap", "decode_gap")
            if k in spec["limits"]})["numbers"]
    return v


def parse(argv):
    ap = argparse.ArgumentParser(description="on-chip benchmark run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_process0: float) -> int:
    args = parse(argv)
    try:
        cell = Cell(args.workload)
    except (SpecError, OSError, KeyError, ValueError) as e:
        note(f"cannot load the cell: {e}")
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        note(f"no program under {ROOT / 'src'}: run from a checkout")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    enable_cache(CACHE_DIR)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        note(f"no TPU: JAX found {devices[0].platform!r}")
        return 3
    if len(devices) < cell.chips:
        note(f"{len(devices)} devices, the cell needs {cell.chips}")
        return 3
    table = load_json(ROOT / "bench" / "peaks.json")["devices"]
    kind = devices[0].device_kind
    if kind not in table:
        note(f"device kind {kind!r} is not in bench/peaks.json")
        return 3
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices, t_process0, table[kind])
    for name, n in out["check"].items():
        print(f"check {name} = {n['value']} limit {n['limit']} "
              f"(over {n['n']})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
