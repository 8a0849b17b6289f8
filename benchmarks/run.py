"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,fig8,...] [--list]

Also works as a plain script from ANY working directory (no PYTHONPATH
needed — the repo root and src/ are put on sys.path automatically):

    python benchmarks/run.py --only fig1

Every bench module is equally invocable on its own, either way:

    PYTHONPATH=src python -m benchmarks.bench_batch_size
    python benchmarks/bench_batch_size.py

Output is ``name,key=value,...`` CSV lines per figure on stdout (see
benchmarks/README.md for each module's output schema and the paper
artifact it reproduces).  Flags:

    --only   comma-separated subset of the names below (default: all)
    --list   print the available names and their modules, then exit
    --json   ALSO write every emitted data point to PATH as JSON
             ({"schema": 1, "benchmarks": {name: {"status", "seconds",
             "rows": [{"series", "section", ...fields}]}}}) — the
             machine-readable artifact nightly CI uploads so the perf
             trajectory accumulates (benchmarks/README.md §JSON schema)
"""
from __future__ import annotations

import argparse
import json
import os as _os
import sys
import time

_R = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
sys.path[:0] = [p for p in (_R, _os.path.join(_R, "src"))
                if p not in sys.path]

MODULES = {
    "fig1": "benchmarks.bench_batch_size",
    "fig4_14": "benchmarks.bench_transfer",
    "fig8": "benchmarks.bench_overlap",
    "fig10_12": "benchmarks.bench_e2e",
    "fig13": "benchmarks.bench_goodput",
    "fig15": "benchmarks.bench_ws_control",
    "fig16": "benchmarks.bench_prefill",
    "table1": "benchmarks.bench_accuracy",
    "roofline": "benchmarks.bench_roofline",
}


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Run the paper-figure benchmarks (see benchmarks/"
                    "README.md for per-figure output schemas)")
    ap.add_argument("--only", default="",
                    help=f"comma list of {list(MODULES)} (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="list available benchmarks and exit")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write the emitted data points to PATH as "
                         "JSON (schema: benchmarks/README.md)")
    args = ap.parse_args()
    if args.list:
        for name, mod in MODULES.items():
            print(f"{name:10s} {mod}")
        return
    names = [n.strip() for n in args.only.split(",") if n.strip()] \
        or list(MODULES)
    unknown = [n for n in names if n not in MODULES]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; choose from "
                 f"{list(MODULES)}")
    import importlib

    from benchmarks import common
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    t0 = time.perf_counter()
    failures = []
    results = {}
    for name in names:
        t = time.perf_counter()
        common.begin_capture()
        err = ""
        try:
            mod = importlib.import_module(MODULES[name])
            mod.main()
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            err = f"{type(e).__name__}: {e}"
            print(f"BENCH FAIL {name}: {err}", flush=True)
        dt = time.perf_counter() - t
        results[name] = {"status": "fail" if err else "ok",
                         "seconds": round(dt, 2),
                         "rows": common.end_capture()}
        if err:
            results[name]["error"] = err
        print(f"[{name} done in {dt:.1f}s]", flush=True)
    if args.json:
        out_dir = _os.path.dirname(_os.path.abspath(args.json))
        _os.makedirs(out_dir, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"schema": 1, "benchmarks": results}, f, indent=1)
        print(f"wrote {args.json}", flush=True)
    print(f"\nall benchmarks done in {time.perf_counter()-t0:.1f}s; "
          f"{len(failures)} failed {failures or ''}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
