"""The output check of a decode cell, driven through the harness at smoke
size on the CPU: the program passes, and the check comes out false for
the bfloat16 control, for a token altered where it is produced, and for
FlashH2D restores left out of one layer."""
import pytest

import benchsmoke


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchsmoke.make(tmp_path_factory.mktemp("bench_decode"))


@pytest.fixture(scope="module")
def served(root):
    return benchsmoke.run(root, "smoke.decode", control=True)


def test_program_passes_its_check(served):
    assert served["correct"] is True
    nums = served["check"]
    # every session is checked: its first token and each later one
    assert nums["first_gap"]["n"] == 3 and nums["decode_gap"]["n"] >= 3
    assert nums["decode_gap"]["value"] <= nums["decode_gap"]["limit"]
    # the decode steps' KV, written back for every fed token, is compared
    assert nums["kv_err_decode"]["n"] >= 3 * 2 * 2
    assert served["metrics"]["output_tok_s"]["value"] > 0
    assert served["attempted"] == 3 and served["failed"] == 0
    # set-up ran every block count a step can move: nothing compiled or
    # loaded in the window
    assert served["window_compiles"] == {"programs": 0, "cache_misses": 0}
    assert list(served)[-1] == "check"


def test_bfloat16_control_fails_the_check(served):
    ctl = served["control"]
    assert any(n["value"] > n["limit"] for n in ctl.values()), ctl
    # the decode part alone separates the control too
    assert ctl["kv_err_decode"]["value"] > ctl["kv_err_decode"]["limit"]
    # the altered-token fault, read on the same reference logits
    assert all(n["value"] > n["limit"] for n in served["fault"].values())


def test_a_token_altered_where_produced_fails(root, monkeypatch):
    from repro.serving.engine import ServingEngine
    sample = ServingEngine._sample

    def wrong(self, st):
        return (sample(self, st) + 1) % self.cfg.vocab_size
    monkeypatch.setattr(ServingEngine, "_sample", wrong)
    out = benchsmoke.run(root, "smoke.decode", seed=77)
    assert out["correct"] is False
    assert out["check"]["first_gap"]["value"] > \
        out["check"]["first_gap"]["limit"]
    assert out["check"]["decode_gap"]["value"] > \
        out["check"]["decode_gap"]["limit"]


def test_restores_left_out_of_a_layer_fail(root, monkeypatch):
    import calibrate
    calibrate.plant("restore", monkeypatch.setattr)
    out = benchsmoke.run(root, "smoke.decode", seed=79)
    assert out["correct"] is False
    nums = out["check"]
    assert nums["kv_err_decode"]["value"] > nums["kv_err_decode"]["limit"]
    # the prompt's KV never passes through a restore
    assert nums["kv_err"]["value"] <= nums["kv_err"]["limit"]
