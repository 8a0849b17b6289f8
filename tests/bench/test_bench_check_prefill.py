"""The output check of the prefill cell, driven through the harness at
smoke size on the CPU: the program passes, and the check comes out false
for the bfloat16 control and for prompt KV altered where FlashD2H saves
it."""
import pytest

import benchsmoke


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchsmoke.make(tmp_path_factory.mktemp("bench_prefill"))


@pytest.fixture(scope="module")
def served(root):
    return benchsmoke.run(root, "smoke.prefill", control=True)


def test_program_passes_its_check(served):
    assert served["correct"] is True
    nums = served["check"]
    n = nums["first_gap"]["n"]
    assert n >= 1
    assert nums["kv_err"]["n"] == n * 2 * 2      # requests x layers x k/v
    assert served["metrics"]["prompt_tok_s"]["value"] > 0
    assert served["metrics"]["ttft_p50_s"]["value"] > 0


def test_bfloat16_control_fails_the_check(served):
    ctl = served["control"]
    assert ctl["kv_err"]["value"] > ctl["kv_err"]["limit"], ctl


def test_saved_kv_altered_fails(root, monkeypatch):
    from repro.core.kv_cache import KVCacheManager
    save = KVCacheManager.save_new_tokens_fused

    def skewed(self, layer, kv_by_req):
        return save(self, layer, {
            rid: (start, k * 1.01, v) for rid, (start, k, v)
            in kv_by_req.items()})
    monkeypatch.setattr(KVCacheManager, "save_new_tokens_fused", skewed)
    out = benchsmoke.run(root, "smoke.prefill", seed=78)
    assert out["correct"] is False
    assert out["check"]["kv_err"]["value"] > out["check"]["kv_err"]["limit"]
