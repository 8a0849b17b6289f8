"""The benchmark harness's own arithmetic, on the CPU: finding cells and
architectures by name, traffic, end-to-end numbers from host stamps, the
trace reduction, operation counts, and refusing to run without a TPU."""
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
from benchkit.spec import Cell, SpecError, load_json
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


# -- architectures ------------------------------------------------------------

TOY_ARCH = '''
"""A toy architecture with what DeepSeek-V2-Lite brings: layers of two
kinds (a dense FFN in layer 0 only), weights in list mode, a latent-only
cache (one key head, values the latent's first part), 4 query heads per
key head and a softmax scale of its own."""
import jax
import jax.numpy as jnp
from benchkit import reference as R


def read_shapes(s, cfg):
    s.heads = int(cfg["num_attention_heads"])
    s.latent = int(cfg["kv_lora_rank"])
    s.rope_dim = int(cfg["qk_rope_head_dim"])
    s.ff = int(cfg["intermediate_size"])


def model_config(s, cfg, name, dsa):
    return {"arch": "toy", "name": name, "block_size": dsa.block_size}


def weights(s, key, dt):
    ks = iter(jax.random.split(key, 8 * s.layers + 2))
    dk = s.latent + s.rope_dim

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(dt)
    layers = []
    for l in range(s.layers):
        p = {"norm": 1.0 + normal((s.d,), 0.1),
             "w_kv": normal((s.d, dk), s.d ** -0.5),
             "w_q": normal((s.d, s.heads * dk), s.d ** -0.5),
             "w_o": normal((s.heads * s.latent, s.d), 0.1)}
        if layer_kind(s, l) == "dense":
            p["w_up"] = normal((s.d, s.ff), s.d ** -0.5)
            p["w_down"] = normal((s.ff, s.d), s.ff ** -0.5)
        layers.append(p)
    return {"embed": normal((s.vocab, s.d), 0.02), "layers": layers}


def embed(params, ids):
    return params["embed"][ids]


def layer_params(params, layer):
    return params["layers"][layer]


def head_params(params):
    return {"embed": params["embed"]}


def layer_kind(s, layer):
    return "dense" if layer == 0 else "attn_only"


def reference_layer(s, kind, ops, S, n):
    H, Dv = s.heads, s.latent
    Dk = Dv + s.rope_dim
    scale = 0.7 ** 2 * Dk ** -0.5
    T = S + n

    def layer(p, h):
        pos = jnp.arange(T)
        x = R.rms(h, p["norm"], s.eps)
        c = ops.mm(x, p["w_kv"]).reshape(T, 1, Dk)
        c = jnp.concatenate([c[..., :Dv], R.rope(c[..., Dv:], pos, 1e4)],
                            -1)
        q = ops.mm(x, p["w_q"]).reshape(T, H, Dk)
        o = R.prompt_attention(q[:S], c[:S], c[:S, :, :Dv], scale, ops)
        if n:
            o = jnp.concatenate([o, R.dsa_attention(
                q[S:], c, c[:, :, :Dv], S, scale, s, ops)], 0)
        h = h + ops.mm(o, p["w_o"])
        if kind == "dense":
            h = h + ops.mm(jax.nn.silu(ops.mm(x, p["w_up"])), p["w_down"])
        return h, {"k": c}
    return layer


def reference_head(s, ops):
    def head(params, h):
        return jnp.matmul(h, params["embed"].T, precision=ops.prec,
                          preferred_element_type=jnp.float32)
    return head


def layer_counts(s, layer):
    dk = s.latent + s.rope_dim
    mm = s.d * dk + s.d * s.heads * dk + s.heads * s.latent * s.d
    if layer_kind(s, layer) == "dense":
        mm += 2 * s.d * s.ff
    return {"matmul_params": mm, "pair_ops": 2.0 * s.heads * (dk + s.latent),
            "score_ops": 4.0 * s.heads * dk,
            "attend_ops": 2.0 * s.heads * (dk + s.latent)}
'''

TOY_CFG = {"source": "toy", "model_type": "toy", "hidden_size": 32,
           "num_hidden_layers": 3, "num_attention_heads": 4,
           "kv_lora_rank": 16, "qk_rope_head_dim": 8,
           "intermediate_size": 48, "vocab_size": 64,
           "tie_word_embeddings": True, "rms_norm_eps": 1e-6,
           "torch_dtype": "float32", "matmul_precision": "highest",
           "reduced": [], "published": {},
           "dsa": {"block_size": 4, "token_budget": 16,
                   "metadata": "cuboid", "sink_blocks": 1,
                   "recent_blocks": 2}}


def _files(root):
    return {p.relative_to(root): p.read_bytes()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_adding_an_architecture_takes_only_new_files(tmp_path, monkeypatch):
    import jax
    from benchkit import model as MD
    from benchkit import reference as R
    root = tmp_path / "co"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(root / "bench")
    (root / "bench/archs/toy.py").write_text(TOY_ARCH)
    (root / "bench/configs/toy.json").write_text(json.dumps(TOY_CFG))
    (root / "bench/checks/toy.decode_16k.json").write_text(json.dumps(
        {"limits": {"first_gap": {"limit": 0.1}}}))
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "toy", "source": "toy",
                          "file": "bench/configs/toy.json", "reduced": [],
                          "why": "x"})
    bm["workloads"].append({"name": "toy.decode_16k", "config": "toy",
                            "traffic": "decode_16k", "chips": 1,
                            "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    after = _files(root / "bench")
    # no file the harness had was edited; three were added
    assert {k: after[k] for k in before} == before
    assert sorted(map(str, set(after) - set(before))) == [
        "archs/toy.py", "checks/toy.decode_16k.json", "configs/toy.json"]

    cell = Cell("toy.decode_16k", root)
    toy = cell.shapes.arch
    assert toy.__file__ == str((root / "bench/archs/toy.py").resolve())
    s = MD.Shapes(cell.config, root)
    assert s.arch.__file__ == toy.__file__
    assert (s.latent, s.rope_dim, s.heads) == (16, 8, 4)
    assert MD.model_config(cell.config, "toy", root) == {
        "arch": "toy", "name": "toy", "block_size": 4}
    params = MD.init_weights(s, 2 ** 33 + 3)
    assert isinstance(params["layers"], list)
    assert "w_up" in params["layers"][0] and "w_up" not in params["layers"][1]
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()), params,
        MD.init_weights(s, 2 ** 33 + 3)))

    kinds = []
    layer_fn = R._layer_fn

    def spy(s_, kind, *a):
        kinds.append(kind)
        return layer_fn(s_, kind, *a)
    monkeypatch.setattr(R, "_layer_fn", spy)
    seen = {}
    prompt = np.arange(37) % 64
    served = [5, 9, 2, 60, 1, 7]
    logits = R.run(params, s, prompt, served,
                   on_kv=lambda l, kv: seen.__setitem__(l, kv))
    assert logits.shape == (6, 64) and np.isfinite(logits).all()
    # the latent alone is cached: one key head, keys 24 wide
    assert sorted(seen) == [0, 1, 2]
    assert {n: x.shape for n, x in seen[1].items()} == {"k": (42, 1, 24)}
    # each layer ran its own kind's reference program
    assert kinds == ["dense", "attn_only", "attn_only"]
    # teacher forcing: the first served token's logits are the prompt's
    one = R.run(params, s, prompt, served[:1])
    np.testing.assert_allclose(one[0], logits[0], rtol=1e-5, atol=1e-5)

    # the operation counts follow the toy's, layer by layer
    dk, mm0 = 24, 32 * 24 + 32 * 4 * 24 + 4 * 16 * 32
    assert F.dense_params_per_layer(s, 0) == mm0 + 2 * 32 * 48
    assert F.dense_params_per_layer(s, 1) == mm0
    ctx = 100                       # 25 blocks, 16 tokens attended
    per = 2.0 * 4 * (dk + 16) * 16 + 4.0 * 4 * dk * 25
    assert F.decode_token_flops(s, ctx) == (
        2.0 * (3 * mm0 + 2 * 32 * 48) + 3 * per + 2 * 32 * 64)
    assert F.prefill_flops(s, 10) == (
        2.0 * (3 * mm0 + 2 * 32 * 48) * 10 + 3 * 2.0 * 4 * (dk + 16) * 55
        + 2 * 32 * 64)


@pytest.mark.parametrize("model_type", ["nope", "../benchkit/model"])
def test_an_unknown_model_type_is_refused(model_type):
    from benchkit.model import Shapes, model_config
    cfg = load_json(REPO / "bench/configs/qwen2-0.5b.json")
    cfg["model_type"] = model_type
    with pytest.raises(SpecError, match=r"known: \[.*'qwen2'"):
        Shapes(cfg)
    with pytest.raises(SpecError, match="model_type"):
        model_config(cfg, "x")


def test_qwen2_weights_are_pinned():
    """The smoke-size Qwen2 weights of one seed, as the harness drew them
    before the architectures moved to ``bench/archs/`` (sum, sum of
    magnitudes, one entry): the draw order is the seed's contract."""
    from benchkit.model import init_weights
    cfg = load_json(REPO / "bench/configs/qwen2-0.5b.json")
    cfg.update(benchsmoke.SMOKE)
    p = init_weights(Shapes(cfg), 1234567890123)
    pins = {
        "embed": (p["embed"], -2.5734274248884077, 521.6073382349034,
                  -0.012490804307162762),
        "final_norm": (p["final_norm"], 64.10613864660263,
                       64.10613864660263, 1.0779674053192139),
        "wq": (p["layers"]["attn"]["wq"], 13.88115406269435,
               812.4488248499015, -0.0027783620171248913),
        "bv": (p["layers"]["attn"]["bv"], -0.5505619855830446,
               4.530983036966063, 0.0511765219271183),
        "w_down": (p["layers"]["ffn"]["w_down"], -12.576468884418318,
                   1153.0914727999343, 0.15686339139938354)}
    for name, (x, total, mag, entry) in pins.items():
        x = np.asarray(x, np.float64)
        assert float(x.ravel()[7]) == entry, name
        assert float(x.sum()) == pytest.approx(total, rel=1e-9, abs=1e-9)
        assert float(np.abs(x).sum()) == pytest.approx(mag, rel=1e-9)


@pytest.mark.parametrize("where", ["none", "prompt", "decode"])
@pytest.mark.parametrize("names", [("k",), ("k", "v")])
def test_kv_check_reads_each_cached_name(names, where):
    """The KV check reads what the host pool holds under each name the
    reference gives: a latent-only pool (``v`` None) as well as k and v."""
    from types import SimpleNamespace
    from benchkit import check as C
    rng = np.random.default_rng(3)
    L, H, NB, bs, D, S, T = 2, 1 if names == ("k",) else 2, 6, 4, 24, 12, 19
    pool = SimpleNamespace(v=None)
    for n in names:
        setattr(pool, n, rng.normal(size=(L, H, NB, bs, D)).astype(np.float32))
    layer = 1
    ref = C.pool_kv(pool, layer, {n: np.zeros((T, H, D)) for n in names})
    assert {n: x.shape for n, x in ref.items()} == {n: (T, H, D)
                                                   for n in names}
    ref = {n: x.copy() for n, x in ref.items()}
    pos = {"prompt": 5, "decode": 15}.get(where)
    if pos is not None:
        blk, slot = divmod(pos, bs)
        getattr(pool, names[-1])[layer, 0, blk, slot, 3] += 0.05
    rd = C.Readings()
    C.add_kv(rd, "r0", C.pool_kv(pool, layer, ref), ref, S)
    rd.close()
    v = C.verdict(rd, {"kv_err": {"limit": 1e-5},
                       "kv_err_decode": {"limit": 1e-5}})
    nums = v["numbers"]
    assert nums["kv_err"]["n"] == len(names)
    assert nums["kv_err_decode"]["n"] == T - S
    assert v["ok"] is (where == "none")
    if where == "none":
        assert nums["kv_err"]["value"] == 0 and \
            nums["kv_err_decode"]["value"] == 0
    else:
        bad = "kv_err" if where == "prompt" else "kv_err_decode"
        assert nums[bad]["value"] > nums[bad]["limit"]
        assert nums[{"kv_err": "kv_err_decode",
                     "kv_err_decode": "kv_err"}[bad]]["value"] == 0


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
        9 * per_layer + 2 * 2048 * 151_936


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
