"""Set-up that compiles the programs a decode window's block counts pick.

The program's decode planes update their device pools with eager JAX
operations whose shapes follow the number of blocks moved: a FlashH2D
restore lands K blocks of the whole batch in one scatter, a block drop
zeroes K blocks of one row.  Each K compiles, or loads from the
persistent cache, about a dozen small programs, so a window that meets a
K set-up never saw compiles inside it.

``warm`` drives the planes' own ``restore_blocks_fused`` and
``drop_blocks`` on stand-ins (copies of one layer of each plane's pools)
for every count a step can ask at the plane's capacity:

* restore: 1 .. rows x min(blocks, KV heads x blocks in the DSA budget);
* drop: 1 .. min(blocks, twice that per-row bound + the LRU's pairs),
  since one layer's pending drops are blocks the step selected, blocks
  the LRU held before it, and blocks kept back from the last stage.

Each stand-in records its spans with a ``Tracer`` of its own, so the
warm-up's restores and drops stay out of the engine's counters.
"""
from __future__ import annotations

import copy
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple


def bounds(plane, lru_pairs: int) -> Tuple[int, int]:
    """(largest restore, largest drop) block count one step can ask of
    ``plane`` at its capacity."""
    c = plane.state["caches"][plane.pool_layers()[0]]
    heads = int(c["k"].shape[1])
    per_row = min(plane.nb_cap, heads * plane.cfg.dsa.top_k_blocks)
    return (plane.b_cap * per_row,
            min(plane.nb_cap, 2 * per_row + int(lru_pairs)))


def warm(planes: List, lru_pairs: int, threads: int = 0) -> Dict[str, int]:
    """Run every restore and drop count on stand-ins of ``planes``, the
    counts spread over ``threads`` (compiles run in parallel); returns
    how many counts of each were run."""
    import jax
    import numpy as np
    from repro.core import device_pool as dp
    from repro.obs.tracing import Tracer
    threads = threads or min(8, os.cpu_count() or 1)
    done = {"restore": 0, "drop": 0}
    for plane in planes:
        if plane.state is None:
            continue
        layer = plane.pool_layers()[0]
        c = plane.state["caches"][layer]
        _, H, _, bs, D = (int(x) for x in c["k"].shape)
        has_v = "v" in c
        dtype = np.dtype(c["k"].dtype)
        kmax_restore, kmax_drop = bounds(plane, lru_pairs)

        def counts(ks: List[int]) -> None:
            sb = copy.copy(plane)
            sb.tracer = Tracer()
            sb.state = {"caches": {layer: {key: v.copy()
                                           for key, v in c.items()}}}
            sb.rows = {f"warm{r}": r for r in range(plane.b_cap)}
            for k in ks:
                if k <= kmax_restore:
                    # k blocks over as many rows as they need
                    payload = {}
                    for r in range(0, k, plane.nb_cap):
                        n = min(plane.nb_cap, k - r)
                        kv = np.zeros((H, n, bs, D), dtype)
                        payload[f"warm{r // plane.nb_cap}"] = (
                            list(range(n)), kv, kv if has_v else None)
                    dp.DevicePoolPlane.restore_blocks_fused(sb, layer,
                                                            payload)
                if k <= kmax_drop:
                    dp.DevicePoolPlane.drop_blocks(sb, "warm0", layer,
                                                   list(range(k)))
            jax.block_until_ready(sb.state["caches"][layer])
        top = max(kmax_restore, kmax_drop)
        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(counts, [list(range(1 + i, top + 1, threads))
                                   for i in range(threads)]))
        done["restore"] += kmax_restore
        done["drop"] += kmax_drop
    return done
