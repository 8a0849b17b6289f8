"""The benchmark harness's own arithmetic, on the CPU: finding cells by
name, traffic, end-to-end numbers from host stamps, the trace reduction,
operation counts, and refusing to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import benchsmoke  # noqa: F401  (puts bench/ on sys.path)
from benchkit import flops as F
from benchkit import timeline as TL
from benchkit import trace as TR
from benchkit.model import Shapes
from benchkit.spec import Cell, load_json
from benchkit.traffic import Traffic, prompt_lengths

REPO = benchsmoke.REPO
BM = load_json(REPO / "BENCHMARK.json")


@pytest.mark.parametrize("name", [w["name"] for w in BM["workloads"]])
def test_cell_files_found_by_name(name):
    cell = Cell(name)
    assert cell.config["source"] == cell.config_entry["source"]
    Shapes(cell.config)
    assert cell.traffic["clients"] >= 1
    assert cell.check["limits"]
    for trace in (False, True):
        ms = cell.metrics(trace)
        assert ms, (name, trace)
        for m in ms:
            assert callable(cell.reader(m["name"]))
    assert "setup_s" in {m["name"] for m in cell.metrics(False)}


def test_every_metric_has_a_reader_and_every_cell_reports():
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    for n in names:
        assert (REPO / "bench" / "metrics" / f"{n}.py").is_file(), n
    for w in BM["workloads"]:
        cell = Cell(w["name"])
        assert len(cell.metrics(False)) >= 2
        assert cell.metrics(True)


def test_config_reduced_lists_what_changed():
    for c in BM["configs"]:
        cfg = load_json(REPO / c["file"])
        assert cfg["reduced"] == c["reduced"]
        assert set(c["reduced"]) <= set(cfg["published"])
        for k in c["reduced"]:
            assert cfg[k] != cfg["published"][k]


def test_adding_a_cell_takes_only_new_files(tmp_path):
    root = tmp_path / "co"
    shutil.copytree(REPO / "bench", root / "bench")
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench/traffic/tiny_mix.json").write_text(json.dumps(
        {"clients": 2, "prompt_tokens": {"fixed": 100},
         "max_new_tokens": 4, "next_on": "finish"}))
    cfg = load_json(REPO / "bench/configs/qwen2-0.5b.json")
    cfg["num_hidden_layers"] = 2
    (root / "bench/configs/new-model.json").write_text(json.dumps(cfg))
    (root / "bench/checks/new-model.tiny_mix.json").write_text(json.dumps(
        {"limits": {"first_gap": {"limit": 0.1}}}))
    (root / "bench/metrics/queue_depth.tiny.py").write_text(
        "def read(ctx):\n    return ctx['delta'].get('sched.queue_depth')\n")
    bm["configs"].append({"name": "new-model", "source": "x",
                          "file": "bench/configs/new-model.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "new-model.tiny_mix",
                            "config": "new-model", "traffic": "tiny_mix",
                            "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "queue_depth.tiny", "unit": "req",
                            "better": "lower", "source": "program_counter",
                            "layer": "scheduler", "moves": "output_tok_s",
                            "workloads": ["new-model.tiny_mix"]})
    bm["end_to_end"][0]["workloads"].append("new-model.tiny_mix")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = Cell("new-model.tiny_mix", root)
    assert cell.config["num_hidden_layers"] == 2
    assert [m["name"] for m in cell.metrics(True)] == ["queue_depth.tiny"]
    assert cell.reader("queue_depth.tiny")(
        {"delta": {"sched.queue_depth": 3.0}}) == 3.0
    assert {m["name"] for m in cell.metrics(False)} == {
        "output_tok_s", "setup_s"}


def test_a_mix_with_output_lengths_of_its_own_is_data_only(tmp_path):
    """A mix whose requests draw their output lengths from a stratified
    set, as a chat or long-document mix would, is one new JSON file."""
    root = tmp_path / "co"
    shutil.copytree(REPO / "bench", root / "bench")
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    mix = {"clients": 8, "prompt_tokens": {"stratified": {
        "n": 8, "clip": [1024, 16384], "mixture": [[4000, 0.6]]}},
        "max_new_tokens": {"stratified": {
            "n": 4, "clip": [64, 384], "mixture": [[150, 0.5]]}},
        "next_on": "finish"}
    (root / "bench/traffic/longbench_mixed.json").write_text(json.dumps(mix))
    mix = load_json(root / "bench/traffic/longbench_mixed.json")
    outs = prompt_lengths(mix["max_new_tokens"])
    assert len(outs) == 4 and 64 <= min(outs) < max(outs) <= 384
    for seed in (3, 2 ** 33 + 1):
        t = Traffic(mix, 1000, seed)
        assert t.max_new_tokens == max(outs)
        for c in range(t.clients):
            # each client cycles through the whole output set
            assert sorted(t.request(c, i)[1] for i in range(4)) == outs
        assert Traffic(mix, 1000, seed).request(5, 9)[1] == \
            t.request(5, 9)[1]


# -- traffic ------------------------------------------------------------------

def test_traffic_is_a_function_of_the_seed():
    mix = load_json(REPO / "bench/traffic/prefill_16k.json")
    a, b = Traffic(mix, 151936, 2 ** 33 + 5), Traffic(mix, 151936, 2 ** 33 + 5)
    for c, i in ((0, 0), (3, 7), (1, 40)):
        ta, na = a.request(c, i)
        tb, nb = b.request(c, i)
        assert np.array_equal(ta, tb) and na == nb == 1
    assert not np.array_equal(a.request(0, 0)[0][:50],
                              Traffic(mix, 151936, 7).request(0, 0)[0][:50])


def test_stratified_lengths_are_the_same_for_every_seed():
    mix = load_json(REPO / "bench/traffic/prefill_16k.json")
    lens = prompt_lengths(mix["prompt_tokens"])
    assert lens == [1534, 3049, 4962, 7315, 9853, 12718, 16384, 16384]
    assert sum(1 for n in lens if n == 16384) == 2      # 25% at the clip
    assert abs(np.mean(lens) - 9024.875) < 1e-9
    seen = set()
    for seed in (1, 2, 3 ** 20):
        t = Traffic(mix, 151936, seed)
        assert t.lengths == lens
        for c in range(t.clients):
            # each client cycles through the whole set, in its own order
            assert sorted(t.length(c, i) for i in range(8)) == sorted(lens)
            seen.add(tuple(t.orders[c]))
    assert len(seen) == 12


def test_decode_mix_is_fixed_length():
    t = Traffic(load_json(REPO / "bench/traffic/decode_16k.json"), 100, 9)
    assert t.lengths == [16384] and t.max_new_tokens == 8192
    toks, _ = t.request(3, 0)
    assert toks.shape == (16384,) and toks.max() < 100


# -- end-to-end arithmetic -----------------------------------------------------

def _records():
    R = TL.Record
    return [R("a", 0, 0, 10, 0.0, [5.0, 12.0, 15.0, 25.0]),
            R("b", 1, 0, 10, 11.0, []),          # still waiting at t1
            R("c", 2, 0, 10, 8.0, [13.0]),
            R("d", 3, 0, 10, 1.0, [9.0]),        # first token before t0
            R("e", 0, 1, 10, 21.0, []),          # sent after t1
            R("w", None, 1, 10, 0.0, [11.0])]    # a set-up request


def test_window_edges():
    recs = _records()
    assert TL.tokens_in(recs, 10.0, 20.0) == 4       # 12, 15, 13, 11
    assert TL.tokens_in(recs, 12.0, 20.0) == 2       # (t0, t1]: not 12
    assert sorted(TL.gaps_in(recs, 10.0, 20.0)) == [3.0, 7.0]
    assert TL.gaps_in(recs, 10.0, 14.0) == [7.0]


def test_ttft_censors_requests_still_waiting():
    assert sorted(TL.ttfts(_records(), 10.0, 20.0)) == [5.0, 9.0]
    assert TL.percentile([5.0, 9.0], 50) == 7.0
    assert TL.percentile([], 95) is None


def test_prompt_progress_counts_partial_prompts():
    recs = _records()
    recs[0].progress0, recs[0].progress1 = 4.0, 10.0
    recs[1].progress1 = 2.5
    assert TL.prompt_tokens_in(recs) == 8.5


# -- trace reduction ------------------------------------------------------------

def test_trace_busy_union_and_idle_share():
    ops = [(0.0, 1.0, "fusion.1"), (0.5, 2.0, "fusion.22"),
           (3.0, 4.0, "scatter.3"), (6.0, 7.0, "outside")]
    assert TR.merge(ops, 0.0, 5.0) == [(0.0, 2.0), (3.0, 4.0)]
    assert TR.busy_seconds(ops, 0.0, 5.0) == 3.0
    assert TR.busy_seconds(ops, 0.5, 3.5) == 2.0
    assert TR.idle_gaps(ops, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert TR.top_ops(ops, 0.0, 5.0) == [("fusion", 2.5), ("scatter", 1.0)]


def test_trace_gaps_labelled_by_innermost_span():
    ops = [(0.0, 1.0, "a"), (3.0, 4.0, "b")]
    spans = [(0.0, 5.0, TR.WINDOW_SPAN),
             (0.5, 4.8, "bench:engine.step"),
             (1.2, 2.5, "bench:kv.lru")]
    r = TR.reduce({"/device:TPU:0": ops}, spans, (0.0, 5.0))
    assert r["busy_s"] == 2.0 and r["window_s"] == 5.0
    # gap (1, 3): midpoint 2.0 in kv.lru; gap (4, 5): midpoint 4.5 in
    # engine.step
    assert r["idle_gaps"] == [["kv.lru", 2.0], ["engine.step", 1.0]]
    assert TR.labels_at(spans, [4.9]) == ["(no span)"]


def test_a_span_on_a_missing_attribute_is_an_error():
    class Layer:
        def step(self):
            return 7
    spans, obj = TR.Spans(), Layer()
    spans.wrap(obj, "step", "layer.step")
    assert obj.step() == 7 and spans.wrapped == ["layer.step"]
    with pytest.raises(AttributeError, match="drop_blocks"):
        spans.wrap(obj, "drop_blocks", "plane.drop")


def test_trace_busy_averages_over_devices():
    devs = {"/device:TPU:0": [(0.0, 2.0, "x")],
            "/device:TPU:1": [(0.0, 1.0, "x")]}
    assert TR.reduce(devs, [], (0.0, 4.0))["busy_s"] == 1.5


# -- operation counts ------------------------------------------------------------

def _shapes(name):
    return Shapes(load_json(REPO / "bench/configs" / f"{name}.json"))


def test_flops_qwen2_0_5b_by_hand():
    s = _shapes("qwen2-0.5b")
    # q 896x896, k and v 896x128 each, o 896x896, gate/up/down 896x4864
    assert F.dense_params_per_layer(s) == 14_909_440
    assert F.head_flops(s) == 2 * 896 * 151_936
    # 16,385 tokens of context: 513 blocks scored, 2,048 tokens attended
    per_layer = 2 * 14_909_440 + 4 * 14 * 64 * 513 + 4 * 14 * 64 * 2048
    assert per_layer == 38_997_504
    assert F.decode_token_flops(s, 16_385) == 24 * 38_997_504 + 272_269_312
    # a 1,024-token prompt: causal attention over 1024*1025/2 pairs
    want = 24 * (2 * 14_909_440 * 1024 + 4 * 896 * 524_800) + 272_269_312
    assert F.prefill_flops(s, 1024) == want
    # half way through layer 3 of a 4,096-token prompt
    lay = 2 * 14_909_440 * 4096 + 4 * 896 * (4096 * 4097 // 2)
    part = 2 * 14_909_440 * 2048 + 4 * 896 * (2048 * 2049 // 2)
    assert F.prefill_flops(s, 4096, 3, 2048) == 3 * lay + part


def test_flops_qwen2_5_3b_by_hand():
    s = _shapes("qwen2.5-3b")
    # 77.1M matmul weights per layer: 2048x(2048+256+256) + 2048x2048
    # + 3 x 2048x11008
    assert F.dense_params_per_layer(s) == 77_070_336
    per_layer = 2 * 77_070_336 + 4 * 16 * 128 * 513 + 4 * 16 * 128 * 2048
    assert F.decode_token_flops(s, 16_385) == \
        12 * per_layer + 2 * 2048 * 151_936


# -- refusing to run -------------------------------------------------------------

def _run_cli(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BM["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_refuses_a_cpu():
    r = _run_cli(REPO, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
    assert "no TPU" in r.stderr


def test_run_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BM["paths"]:
        shutil.copytree(REPO / p, tmp_path / p)
    r = _run_cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and not r.stdout.strip()
