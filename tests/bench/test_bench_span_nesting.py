"""The harness's ``bench:`` spans and the program's own ``repro:`` spans in
one profiler capture of a tiny engine: every span the harness opens
around a layer (all but the window) holds a program span of the same
name on the same thread, so the breakdown can later be read from the
program's spans by changing only the prefix."""
import glob

import numpy as np
import pytest

import jax

import benchsmoke  # noqa: F401  (puts bench/ on sys.path)
from benchkit import runner
from benchkit import trace as TR


def _lines(log_dir):
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(sorted(paths)[-1])
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events
                       if e.name.startswith((TR.SPAN_PREFIX, "repro:"))]
                if evs:
                    out.append(evs)
    return out


@pytest.fixture(scope="module")
def capture(tiny_cfg, tiny_params, tmp_path_factory):
    """A tiny engine built and served with the harness's spans installed
    (the classes and registries the harness patches are restored after)."""
    from repro.core import device_pool as dp
    from repro.core import hybrid_plane as hp
    from repro.core import prefill_plane as pp
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.request import Request
    mp = pytest.MonkeyPatch()
    for attr in ("restore_blocks_fused", "drop_blocks", "admit",
                 "new_token_kv_async"):
        mp.setattr(dp.DevicePoolPlane, attr,
                   getattr(dp.DevicePoolPlane, attr))
    for attr in ("admit", "read_group_kv_async"):
        mp.setattr(pp.PrefillPlane, attr, getattr(pp.PrefillPlane, attr))
    mp.setattr(dp.StageFns, "wrap", dp.StageFns.wrap)
    # fresh stage registries, so the harness's stage spans reach no other
    # test's engines
    for mod, name in ((dp, "_STAGED_FNS"), (pp, "_PREFILL_FNS"),
                      (pp, "_ADMIT_EMBED_FNS"), (hp, "_HYBRID_FNS")):
        mp.setattr(mod, name, {})
    log_dir = tmp_path_factory.mktemp("bench_spans")
    try:
        spans = TR.Spans()
        runner.install_class_spans(spans)
        eng = ServingEngine(tiny_params, tiny_cfg, EngineConfig(
            chunk_size=64, r_max=4, hbm_blocks_per_request=1,
            charge_real_time=True))
        runner.install_spans(eng, spans)
        rng = np.random.default_rng(5)
        for _ in range(2):
            eng.submit(Request(prompt_len=64, max_new_tokens=5),
                       tokens=rng.integers(4, tiny_cfg.vocab_size,
                                           64).astype(np.int32))
        with jax.profiler.trace(str(log_dir)):
            with spans.span("window"):
                eng.run()
    finally:
        mp.undo()
    return _lines(log_dir)


# the harness's spans that this engine's steps open
HARNESS_SPANS = ("sampling", "engine.wait_device", "scheduler.schedule",
                 "kv.lru", "kv.h2d_gather", "kv.d2h_save",
                 "plane.restore", "plane.drop", "plane.admit",
                 "plane.kv_readback", "prefill.admit",
                 "prefill.kv_readback", "stage.embed", "stage.select",
                 "stage.attend", "stage.logits", "stage.attn",
                 "stage.finalize", "stage.admit-embed")


@pytest.mark.parametrize("name", HARNESS_SPANS)
def test_each_harness_span_holds_the_programs_span(capture, name):
    seen = 0
    for line in capture:
        mine = [(a, b) for n, a, b in line if n == "repro:" + name]
        for n, a, b in line:
            if n != TR.SPAN_PREFIX + name:
                continue
            seen += 1
            assert any(a <= ma and mb <= b for ma, mb in mine), (name, a)
    assert seen, name
