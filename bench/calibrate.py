#!/usr/bin/env python3
"""Readings for setting a cell's output-check limits.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3

Runs the cell once per seed in ONE process (compiled programs are shared
between seeds) and prints, per seed, the program's compared numbers and
those of the control: the reference one precision below the
configuration's, on the same prompts and served tokens.  The limits in ``bench/checks/<cell>.json`` lie between
the program's largest reading and the control's smallest.  The benchmark's
own runs never run the control.

``--fault restore`` plants a fault in the program first: the FlashH2D
restores of the middle layer are left out, so that layer attends
over dropped or stale device blocks.
"""
import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchkit import runner  # noqa: E402
from benchkit.spec import ROOT, Cell, load_json  # noqa: E402


def plant(fault: str, setattr_=setattr) -> None:
    """Plant ``fault`` in the program (``setattr_`` as monkeypatch's)."""
    from repro.core import device_pool as dp
    if fault != "restore":
        raise ValueError(f"unknown fault {fault!r}")
    restore = dp.DevicePoolPlane.restore_blocks_fused

    def left_out(self, layer, payload_by_req, *a, **k):
        if layer == (self.cfg.num_layers - 1) // 2:
            return None
        return restore(self, layer, payload_by_req, *a, **k)
    setattr_(dp.DevicePoolPlane, "restore_blocks_fused", left_out)


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=("restore",))
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    if args.fault:
        plant(args.fault)
    runner.enable_cache(runner.CACHE_DIR)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform!r}", file=sys.stderr)
        return 3
    peaks = load_json(ROOT / "bench" / "peaks.json")["devices"][
        devices[0].device_kind]
    t0 = T_PROCESS0
    for seed in args.seeds:
        out = runner.run_cell(cell, seed, args.seconds, False, devices, t0,
                              peaks, control=True)
        print(json.dumps({"seed": seed, "fault_planted": args.fault,
                          "program": out["check"],
                          "control": out["control"],
                          "fault": out["fault"],
                          "largest": out["largest"],
                          "metrics": out["metrics"],
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"]}),
              flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
