"""The per-layer readers of the program's counted spans, on a synthetic
context and on the counters a tiny engine really exports: each reads its
counter's window delta per iteration, and reads nothing (None) where the
program has no such counter."""
import time

import numpy as np
import pytest

import benchsmoke
from benchkit.spec import load_reader

# metric -> (counter it reads, scale to the metric's unit)
READERS = {
    "drop_ms_per_step.decode": ("plane.drop_s", 1000.0),
    "drop_calls_per_step.decode": ("plane.drop_calls", 1.0),
    "restore_ms_per_step.decode": ("plane.restore_s", 1000.0),
    "kv_readback_ms_per_step.decode": ("plane.kv_readback_s", 1000.0),
    "step_self_ms.decode": ("engine.step_self_s", 1000.0),
}


def _read(metric, delta, iterations):
    return load_reader(benchsmoke.REPO, metric)(
        {"delta": delta, "iterations": iterations})


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reads_its_counter_per_iteration(metric):
    counter, scale = READERS[metric]
    delta = {counter: 2.5, "engine.iterations": 5.0, "other": 7.0}
    assert _read(metric, delta, 5.0) == pytest.approx(2.5 * scale / 5.0)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reads_nothing_without_its_counter(metric):
    """A program without the span (the parent of the change that added
    it) gives no value, and no error."""
    assert _read(metric, {"engine.iterations": 5.0}, 5.0) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reads_nothing_without_iterations(metric):
    counter, _ = READERS[metric]
    assert _read(metric, {counter: 1.0}, 0.0) is None


@pytest.fixture(scope="module")
def window(tiny_cfg, tiny_params):
    """Counter deltas over a few decode steps of a tiny engine under
    eviction pressure (a one-block LRU, so drops and restores run)."""
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.request import Request
    eng = ServingEngine(tiny_params, tiny_cfg, EngineConfig(
        chunk_size=64, r_max=4, hbm_blocks_per_request=1))
    rng = np.random.default_rng(3)
    for _ in range(2):
        eng.submit(Request(prompt_len=64, max_new_tokens=8),
                   tokens=rng.integers(4, tiny_cfg.vocab_size,
                                       64).astype(np.int32))
    while eng.states and not all(
            st.req.generated >= 2 for st in eng.states.values()):
        eng.step()
    snap0 = eng.metrics_snapshot()
    t0 = time.perf_counter()
    for _ in range(3):
        eng.step()
    seconds = time.perf_counter() - t0
    snap1 = eng.metrics_snapshot()
    eng.close()
    delta = {k: snap1[k] - snap0.get(k, 0.0) for k in snap1}
    return delta, delta["engine.iterations"], seconds


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reads_the_programs_counter(window, metric):
    delta, iterations, _ = window
    assert iterations == 3
    v = _read(metric, delta, iterations)
    assert v is not None and v > 0


def test_span_times_fit_in_the_window(window):
    """drop + restore + readback + step self time stay below the
    window's milliseconds per step: they are disjoint parts of it."""
    delta, iterations, seconds = window
    parts = sum(_read(m, delta, iterations) for m in READERS
                if m.startswith(("drop_ms", "restore", "kv_readback",
                                 "step_self")))
    assert parts < 1000.0 * seconds / iterations


def test_warm_up_stays_out_of_the_engines_counters(tiny_cfg, tiny_params):
    """Set-up's block-count warm-up drives the planes' restores and drops
    on stand-ins with recorders of their own: the engine's counted spans
    do not see them."""
    from benchkit import warm as WM
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.request import Request
    eng = ServingEngine(tiny_params, tiny_cfg, EngineConfig(
        chunk_size=64, r_max=4, hbm_blocks_per_request=1))
    rng = np.random.default_rng(5)
    eng.submit(Request(prompt_len=64, max_new_tokens=8),
               tokens=rng.integers(4, tiny_cfg.vocab_size,
                                   64).astype(np.int32))
    while eng.states and not all(
            st.req.generated >= 2 for st in eng.states.values()):
        eng.step()
    names = ("plane.drop_calls", "plane.drop_s", "plane.restore_s")
    snap0 = eng.metrics_snapshot()
    done = WM.warm(list(eng.planes.values()),
                   eng.eng.hbm_blocks_per_request, threads=2)
    snap1 = eng.metrics_snapshot()
    eng.close()
    assert done["restore"] > 0 and done["drop"] > 0
    assert {n: snap1[n] for n in names} == {n: snap0[n] for n in names}
