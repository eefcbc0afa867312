"""`correct` has been shown to fail: the control (the reference in the next
precision down, in the program's place) and each fault a cell can have, at a
size a test run can hold. These tests skip the harness's look for a chip
(`--rehearse`) and drive the rest of a run, with the timed path of the
program broken underneath."""

import json

import numpy as np
import pytest

import run


def drive(capsys, *argv):
    """One whole run of the command in this process; its notes and result."""
    run.main(list(argv) + ["--rehearse", "--trace", "0"])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return json.loads(lines[-2])["notes"], json.loads(lines[-1])


TRAIN = ("--workload", "gpt2m_train_1k", "--seed", str(2 ** 31 + 11),
         "--seconds", "1")
CHAT = ("--workload", "gpt2xl_chat", "--seed", str(2 ** 31 + 12),
        "--seconds", "2")


def test_training_sound_run_is_correct(capsys):
    _, result = drive(capsys, *TRAIN)
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert all(k.startswith("rehearsal.") for k in result["metrics"])
    assert list(result)[-1] == "checks"         # the record comes last


@pytest.mark.parametrize("control", ["fp8", "half_batch"])
def test_training_control_in_the_programs_place_is_not_correct(capsys,
                                                               control):
    """The reference in float8 products, and the reference on half of each
    batch, go through the run's own checks and fail them; the program's own
    reading, on the notes line, stays under the limits."""
    notes, result = drive(capsys, *TRAIN, "--control", control)
    assert result["correct"] is False
    held = {k: v[0] for k, v in result["checks"].items()}
    limits = {k: v[1] for k, v in result["checks"].items()}
    assert held["grad_norm_gap"] == notes["control"]["grad_norm_gap"]
    assert held["grad_norm_gap"] > limits["grad_norm_gap"]
    ours = notes["compared"]
    assert ours["grad_norm_gap"] <= limits["grad_norm_gap"]
    assert ours["change_norm_gap"] <= limits["change_norm_gap"]
    assert held["grad_norm_gap"] > 3 * ours["grad_norm_gap"]


def test_a_control_the_configuration_does_not_state_is_refused():
    with pytest.raises(SystemExit, match="states the controls"):
        run.main([*CHAT, "--rehearse", "--control", "fp8"])


def test_training_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    from bigdl_tpu.optim.method import Adam
    monkeypatch.setattr(Adam, "update",
                        lambda self, params, grads, slots, lr, step:
                        (params, slots))
    _, result = drive(capsys, *TRAIN)
    assert result["correct"] is False
    assert result["checks"]["change_norm_gap"][0] == pytest.approx(1.0)
    assert result["checks"]["grad_norm_gap"][0] == pytest.approx(1.0)


def test_training_on_half_of_each_batch_is_not_correct(capsys, monkeypatch):
    import bigdl_tpu.nn as nn
    whole = nn.TimeDistributedMaskCriterion.forward

    def half(self, input, target):
        n = input.shape[0] // 2     # the mean taken over the rest
        return whole(self, input[:n], target[:n])
    monkeypatch.setattr(nn.TimeDistributedMaskCriterion, "forward", half)
    _, result = drive(capsys, *TRAIN)
    assert result["correct"] is False
    value, limit = result["checks"]["grad_norm_gap"]
    assert value > limit


def test_serving_sound_run_is_correct(capsys):
    notes, result = drive(capsys, *CHAT)
    assert result["correct"] is True and result["failed"] == 0
    assert notes["checked"]["tokens"] >= 100
    assert list(result)[-1] == "checks"         # the record comes last


def test_serving_with_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from bigdl_tpu.serve.decode import DecodeEntry
    sound = DecodeEntry.run_decode

    def altered(self, caches, tokens_last, *rest):
        nxt, caches = sound(self, caches, tokens_last, *rest)
        nxt = np.array(nxt)
        nxt[0] = (nxt[0] + 1) % (self.vocab_size - 1)      # slot 0 lies
        return nxt, caches
    monkeypatch.setattr(DecodeEntry, "run_decode", altered)
    _, result = drive(capsys, *CHAT)
    assert result["correct"] is False
    value, limit = result["checks"]["served_gap_sq_mean"]
    assert value > limit


def test_serving_control_in_bfloat16_in_the_programs_place_is_not_correct(
        capsys):
    """The reference in bfloat16 at the served positions of the same
    requests goes through the run's own check and fails it, where the
    program's own reading (notes) passes (the chip readings at the cell's
    own size are in PERF.md)."""
    notes, result = drive(capsys, *CHAT, "--control", "bfloat16")
    assert result["correct"] is False
    value, limit = result["checks"]["served_gap_sq_mean"]
    checked = notes["checked"]
    assert value == checked["control"]["gap_sq_mean"] > limit
    assert checked["control"]["tokens"] == checked["tokens"]
    assert checked["gap_sq_mean"] <= limit


def test_served_gaps_agree_with_the_logits_taken_whole():
    """The on-device reduction reads what the full logits say."""
    import common as C
    import reference
    gpt2 = C.plug_in((C.HERE,), "families", "gpt2", "family")
    cfg = {"vocab_size": 512, "n_positions": 32, "n_embd": 32, "n_head": 4,
           "n_layer": 2, "layer_norm_epsilon": 1e-5}
    w = gpt2.stacked(3, cfg)
    rng = np.random.default_rng(3)
    prompt, served = rng.integers(0, 511, 9).tolist(), \
        rng.integers(0, 511, 7).tolist()
    g = reference.served_gaps_of(gpt2, cfg, "bfloat16")(
        w, prompt, served, 32)
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :16] = prompt + served
    ref = np.asarray(gpt2.logits(w, cfg, tokens, "float32"))[0]
    low = np.asarray(gpt2.logits(
        w, cfg, tokens, "bfloat16"))[0].astype(np.float32)
    at = np.arange(8, 15)
    assert np.allclose(g["gap"], ref[at].max(-1) - ref[at, served], atol=1e-6)
    every = ref[:15].max(-1) - ref[np.arange(15), low[:15].argmax(-1)]
    assert np.allclose(g["control_gap"], every[at], atol=1e-6)
    assert (g["gap"] > 0).any()         # random tokens are not the argmax
