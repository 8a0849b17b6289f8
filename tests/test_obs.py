"""Obs layer (src/repro/obs): the span recorder, the metrics registry, and
the engine-level guarantees — every layer boundary opens a ``repro:`` span
that a ``jax.profiler`` capture holds on the thread that ran it, children
nest inside their parents, counted spans feed the registry, the stage
programs carry their stage names, and a capture does not perturb greedy
tokens."""
import glob
import threading
import time
import types

import numpy as np
import pytest

import jax

from repro.obs import tracing
from repro.obs.metrics import MetricsRegistry, _prom_name
from repro.obs.tracing import COUNTED, Tracer


def _capture_spans(log_dir):
    """The ``repro:`` spans of the newest capture under ``log_dir``, one
    list per host thread line: (name, start_ns, end_ns, args)."""
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(sorted(paths)[-1])
    lines, modules = [], set()
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
                if e.name.startswith(tracing.PREFIX):
                    evs.append((e.name[len(tracing.PREFIX):], e.start_ns,
                                e.end_ns, stats))
            if evs:
                lines.append(evs)
    return lines, modules


# ---------------------------------------------------------------------------
# The recorder
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        perf_counter=c))
    return c


# (seconds before each child, [(child seconds, [grandchild seconds])],
# seconds after) -> engine.step's self time
SELF_CASES = {
    "no children": ((4.0, [], 0.0), 4.0),
    "two children": ((1.0, [(3.0, []), (2.0, [])], 4.0), 6.0),
    "grandchildren are the child's": ((1.0, [(3.0, [1.0, 1.5])], 1.0), 2.0),
}


@pytest.mark.parametrize("case", sorted(SELF_CASES))
def test_self_time_on_synthetic_nested_spans(clock, case):
    (before, children, after), want = SELF_CASES[case]
    tr = Tracer()
    with tr.span("engine.step") as step:
        for secs, grand in children:
            clock.t += before
            with tr.span("engine.decode") as child:
                for g in grand:
                    with tr.span("plane.drop"):
                        clock.t += g
                clock.t += secs - sum(grand)
            assert child.dur == pytest.approx(secs)
        clock.t += after if children else before
    snap = tr.metrics.snapshot()
    assert snap["engine.step_self_s"] == pytest.approx(want)
    assert step.dur - step.child_s == pytest.approx(want)


def test_a_span_on_another_thread_is_no_child(clock):
    tr = Tracer()
    with tr.span("engine.step"):
        clock.t += 1.0

        def other():
            with tr.span("worker.job"):
                clock.t += 2.0
        th = threading.Thread(target=other)
        th.start()
        th.join()
    assert tr.metrics.snapshot()["engine.step_self_s"] == pytest.approx(3.0)


@pytest.mark.parametrize("name", sorted(COUNTED))
def test_counted_spans_feed_their_counters(clock, name):
    tr = Tracer()
    for secs in (0.5, 1.5):
        with tr.span(name):
            clock.t += secs
    snap = tr.metrics.snapshot()
    kinds = COUNTED[name]
    assert {k for k in snap if k.startswith(name + "_")} == {
        f"{name}_{k}" for k in kinds}
    want = {"s": 2.0, "self_s": 2.0, "calls": 2}
    for kind in kinds:
        assert snap[f"{name}_{kind}"] == pytest.approx(want[kind])


def test_uncounted_spans_feed_nothing(clock):
    tr = Tracer()
    before = tr.metrics.snapshot()
    with tr.span("kv.lru", layer=2):
        clock.t += 1.0
    assert tr.metrics.snapshot() == before


def test_spans_land_in_a_capture_with_their_args(tmp_path):
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("plane.drop", req="r0", layer=3) as sp:
            sp.set(blocks=5)
    [line] = _capture_spans(tmp_path)[0]
    [(name, t0, t1, args)] = line
    assert name == "plane.drop" and t1 >= t0
    assert args == {"req": "r0", "layer": 3, "blocks": 5}


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("a.count", "help")
        c.inc()
        c.inc(2)
        g = reg.gauge("a.depth", "help")
        g.set(5)
        g.inc()
        g.dec(2)
        h = reg.histogram("a.lat_s", "help")
        for v in (1.0, 3.0, 2.0):
            h.observe(v)
        s = reg.snapshot()
        assert s["a.count"] == 3
        assert s["a.depth"] == 4
        assert s["a.lat_s_count"] == 3
        assert s["a.lat_s_sum"] == pytest.approx(6.0)
        assert s["a.lat_s_min"] == 1.0 and s["a.lat_s_max"] == 3.0
        assert s["a.lat_s_mean"] == pytest.approx(2.0)

    def test_instruments_memoized(self):
        reg = MetricsRegistry()
        assert reg.counter("x", "h") is reg.counter("x", "h")
        assert reg.gauge("y", "h") is reg.gauge("y", "h")
        assert reg.histogram("z", "h") is reg.histogram("z", "h")

    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("kv.h2d_calls", "fused H2D launches").inc(4)
        reg.histogram("engine.iteration_s", "iter wall").observe(0.5)
        txt = reg.prometheus_text(extra={"plane.count": 2})
        assert "# HELP kv_h2d_calls fused H2D launches" in txt
        assert "# TYPE kv_h2d_calls counter" in txt
        assert "kv_h2d_calls 4" in txt
        assert "engine_iteration_s_count 1" in txt
        assert "engine_iteration_s_sum 0.5" in txt
        assert "plane_count 2" in txt

    def test_prom_name_sanitization(self):
        assert _prom_name("a.b.c") == "a_b_c"
        assert _prom_name("9lives") == "_9lives"
        assert _prom_name("sp ace-y") == "sp_ace_y"


# ---------------------------------------------------------------------------
# Worker-thread spans (HostStageWorker alone)
# ---------------------------------------------------------------------------

class TestWorkerSpans:
    def test_worker_emits_spans_on_own_tid(self, tmp_path):
        import time
        from repro.core.host_stage import HostStageWorker
        tr = Tracer()
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("engine.step"):
                w = HostStageWorker(name="obs-test-hsw", tracer=tr)
                try:
                    for i in range(4):
                        w.submit(i % 2, time.sleep, 0.001)
                    w.drain()
                finally:
                    w.close()
        lines = _capture_spans(tmp_path)[0]
        by_name = {n: i for i, line in enumerate(lines) for n, *_ in line}
        assert by_name["worker.job"] != by_name["engine.step"]
        jobs = [s for s in lines[by_name["worker.job"]]
                if s[0] == "worker.job"]
        assert len(jobs) == 4 == w.jobs_run
        assert [s[3]["key"] for s in jobs] == [0, 1, 0, 1]   # FIFO
        assert w.busy_s >= 4 * 0.001

    def test_worker_alone_uses_the_default_recorder(self):
        from repro.core.host_stage import HostStageWorker
        w = HostStageWorker(name="obs-test-own")
        try:
            w.submit(0, lambda: None)
            w.drain()
        finally:
            w.close()
        assert w.tracer is tracing.DEFAULT
        assert w.jobs_run == 1 and w.busy_s >= 0.0


# ---------------------------------------------------------------------------
# Engine-level guarantees (tiny real model)
# ---------------------------------------------------------------------------

# keys the snapshot must keep exposing — launch/serve.py, benchmarks, the
# benchmark's readers and the nightly asserts consume these; renaming one
# is an API break
SNAPSHOT_REQUIRED_KEYS = frozenset({
    "engine.iterations", "engine.decode_tokens", "engine.decode_step_calls",
    "engine.prefill_launches", "engine.iteration_s_count",
    "engine.iteration_s_sum", "engine.step_self_s",
    "kv.h2d_calls", "kv.h2d_blocks", "kv.h2d_bytes",
    "kv.d2h_calls", "kv.d2h_blocks", "kv.d2h_bytes",
    "kv.hits", "kv.misses", "kv.evictions", "kv.hbm_used_bytes",
    "kv.hbm_budget_bytes",
    "sched.queue_depth", "sched.running",
    "plane.count", "plane.steps", "plane.host_syncs",
    "plane.dispatch_sync_s", "plane.host_stage_s",
    "plane.drop_s", "plane.drop_calls", "plane.restore_s",
    "plane.kv_readback_s", "plane.blocks_dropped",
    "worker.jobs_run", "worker.busy_s",
})

# span -> the thread it runs on, in the default engine (mixed hybrid plane,
# async host stage) serving prompts and decodes under a one-block LRU
SPAN_THREADS = {
    **{name: "dispatch" for name in (
        "engine.step", "scheduler.schedule", "engine.prefill",
        "engine.decode", "worker.drain", "engine.drop_pending", "sampling",
        "engine.finish", "engine.wait_device",
        "stage.embed", "stage.select", "stage.attend", "stage.logits",
        "stage.admit-embed", "stage.attn", "stage.finalize",
        "plane.idx_sync", "plane.host_stage", "plane.admit",
        "plane.kv_readback", "plane.restore", "plane.restore.prepare",
        "plane.restore.scatter", "plane.drop", "plane.drop.prepare",
        "plane.drop.scatter", "kv.lru", "kv.h2d_gather",
        "prefill.admit", "prefill.kv_readback", "prefill.group")},
    "worker.job": "worker",
    "kv.d2h_save": "worker",
}

# child -> the spans it must lie inside (any one of them), same thread
SPAN_PARENTS = {
    **{name: ("engine.step",) for name in (
        "scheduler.schedule", "engine.prefill", "engine.decode",
        "worker.drain", "engine.drop_pending", "sampling", "engine.finish",
        "engine.wait_device", "plane.admit")},
    **{name: ("engine.decode",) for name in (
        "stage.embed", "stage.select", "stage.attend", "stage.logits",
        "plane.idx_sync", "plane.host_stage", "prefill.group")},
    **{name: ("engine.prefill",) for name in (
        "stage.admit-embed", "prefill.admit")},
    **{name: ("plane.host_stage",) for name in (
        "kv.lru", "kv.h2d_gather", "plane.restore", "plane.kv_readback",
        "prefill.kv_readback")},
    "plane.drop": ("plane.host_stage", "engine.drop_pending"),
    "plane.restore.prepare": ("plane.restore",),
    "plane.restore.scatter": ("plane.restore",),
    "plane.drop.prepare": ("plane.drop",),
    "plane.drop.scatter": ("plane.drop",),
    "stage.attn": ("prefill.group",),
    "kv.d2h_save": ("worker.job",),
}

STAGE_PROGRAMS = ("jit_decode_embed", "jit_decode_select",
                  "jit_decode_attend", "jit_decode_logits",
                  "jit_prefill_admit_embed", "jit_prefill_attn",
                  "jit_prefill_finalize")


def _serve(params, cfg, log_dir=None, n=2, prompt=64, gen=6, seed=7):
    """The default engine with a one-block LRU (drops and restores every
    step) and the measured clock (so the device wait runs too); under a
    profiler capture when ``log_dir`` is given.  Counts drop_blocks
    calls and the blocks they were given."""
    from repro.core.device_pool import DevicePoolPlane
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.request import Request
    eng = ServingEngine(params, cfg, EngineConfig(
        chunk_size=64, r_max=4, hbm_blocks_per_request=1,
        charge_real_time=True))
    rng = np.random.default_rng(seed)
    for _ in range(n):
        eng.submit(Request(prompt_len=prompt, max_new_tokens=gen),
                   tokens=rng.integers(4, cfg.vocab_size,
                                       prompt).astype(np.int32))
    drops = {"calls": 0, "blocks": 0}
    real = DevicePoolPlane.drop_blocks

    def counted(self, req_id, layer, blocks):
        drops["calls"] += 1
        drops["blocks"] += len(blocks)
        return real(self, req_id, layer, blocks)
    DevicePoolPlane.drop_blocks = counted
    try:
        if log_dir is None:
            eng.run()
        else:
            with jax.profiler.trace(str(log_dir)):
                eng.run()
    finally:
        DevicePoolPlane.drop_blocks = real
    return eng, drops


@pytest.fixture(scope="module")
def served(tiny_cfg, tiny_params, tmp_path_factory):
    """One run without and one under a profiler capture, same workload."""
    log_dir = tmp_path_factory.mktemp("obs_capture")
    plain = _serve(tiny_params, tiny_cfg)
    traced = _serve(tiny_params, tiny_cfg, log_dir)
    lines, modules = _capture_spans(log_dir)
    thread = {}
    for i, line in enumerate(lines):
        names = {s[0] for s in line}
        if "engine.step" in names:
            thread["dispatch"] = i
        if "worker.job" in names:
            thread["worker"] = i
    return {"plain": plain, "traced": traced, "lines": lines,
            "thread": thread, "modules": modules}


class TestEngineObs:
    @pytest.mark.parametrize("name", sorted(SPAN_THREADS))
    def test_span_in_capture_on_its_thread(self, served, name):
        line = served["lines"][served["thread"][SPAN_THREADS[name]]]
        assert any(s[0] == name for s in line), name

    def test_trace_valid_and_has_expected_lanes(self, served):
        """The worker's spans are on a line of their own, and some overlap
        an engine.step in time: the async pipeline ran work concurrently
        with dispatch."""
        lines, thread = served["lines"], served["thread"]
        assert thread["dispatch"] != thread["worker"]
        steps = [(a, b) for n, a, b, _ in lines[thread["dispatch"]]
                 if n == "engine.step"]
        jobs = [(a, b) for n, a, b, _ in lines[thread["worker"]]
                if n == "worker.job"]
        assert any(a < jb and ja < b for ja, jb in jobs for a, b in steps)

    @pytest.mark.parametrize("child", sorted(SPAN_PARENTS))
    def test_children_nest_inside_their_parent(self, served, child):
        line = served["lines"][served["thread"][SPAN_THREADS[child]]]
        parents = [(a, b) for n, a, b, _ in line if n in SPAN_PARENTS[child]]
        kids = [(a, b) for n, a, b, _ in line if n == child]
        assert kids
        for a, b in kids:
            assert any(pa <= a and b <= pb for pa, pb in parents), \
                (child, a, b)

    @pytest.mark.parametrize("program", STAGE_PROGRAMS)
    def test_stage_programs_carry_their_stage_names(self, served, program):
        assert program in served["modules"]

    def test_no_anonymous_stage_program(self, served):
        assert "jit_fn" not in served["modules"]

    def test_obs_does_not_perturb_greedy_tokens(self, served):
        (plain, _), (traced, _) = served["plain"], served["traced"]
        assert ([st.out_tokens for st in plain.states.values()]
                == [st.out_tokens for st in traced.states.values()])

    @pytest.mark.parametrize("which", ["plain", "traced"])
    def test_drop_counters_count_the_calls(self, served, which):
        eng, drops = served[which]
        s = eng.metrics_snapshot()
        assert drops["calls"] > 0
        assert s["plane.drop_calls"] == drops["calls"]
        assert s["plane.blocks_dropped"] == drops["blocks"]

    def test_blocks_dropped_unchanged_by_a_capture(self, served):
        (plain, _), (traced, _) = served["plain"], served["traced"]
        assert (plain.metrics_snapshot()["plane.blocks_dropped"]
                == traced.metrics_snapshot()["plane.blocks_dropped"])

    @pytest.mark.parametrize("counter", [
        "plane.drop_s", "plane.restore_s", "plane.kv_readback_s",
        "engine.step_self_s"])
    def test_counted_seconds_lie_inside_the_iterations(self, served,
                                                       counter):
        s = served["plain"][0].metrics_snapshot()
        assert 0.0 < s[counter] < s["engine.iteration_s_sum"]

    def test_snapshot_keys_stable(self, served):
        for eng, _ in (served["plain"], served["traced"]):
            s = eng.metrics_snapshot()
            missing = SNAPSHOT_REQUIRED_KEYS - set(s)
            assert not missing, f"snapshot lost keys: {sorted(missing)}"
            assert all(isinstance(v, (int, float)) for v in s.values())

    def test_worker_counters_survive_close(self, served):
        eng, _ = served["traced"]
        s = eng.metrics_snapshot()
        assert s["worker.jobs_run"] > 0
        eng.close()
        s2 = eng.metrics_snapshot()
        assert s2["worker.jobs_run"] == s["worker.jobs_run"]

    def test_prometheus_exposition(self, served):
        eng, _ = served["traced"]
        txt = eng.metrics_prometheus()
        assert "# TYPE engine_iteration_s summary" in txt
        assert "# TYPE plane_drop_calls counter" in txt
        assert "kv_h2d_calls" in txt

    def test_span_overhead_under_5_percent(self, served, tiny_cfg,
                                           tiny_params, monkeypatch):
        """Tier-1 perf guard: the engine with its spans (always on, no
        capture running) within 5% of the same workload with every span
        cut to its two clock reads, plus an absolute epsilon for timer
        noise on sub-second runs.  The module fixture warmed the jit
        caches, so this times the steady-state dispatch path; the two
        sides alternate so drift hits both."""
        def wall():
            t0 = time.perf_counter()
            _serve(tiny_params, tiny_cfg)
            return time.perf_counter() - t0

        on, off = [], []
        for _ in range(3):
            on.append(wall())
            with monkeypatch.context() as m:
                m.setattr(tracing, "Span", _BareSpan)
                off.append(wall())
        assert min(on) <= min(off) * 1.05 + 0.25, (on, off)


class _BareSpan:
    """A span cut to its clock reads: no TraceAnnotation, no parent
    stack, no counters — the floor the spans' cost is measured from."""

    def __init__(self, name, args, count=None):
        self.name = name
        self.t0 = self.dur = self.child_s = 0.0

    def set(self, **args):
        pass

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self.t0
        return False
