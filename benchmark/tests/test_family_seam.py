"""A model family is files found by name: the GPT-2 family computes what the
parent tree computed before it was moved (weights bit for bit, FLOP counts to
the digit), a family the tree does not hold runs both kinds of cell from a
temporary directory, and a name the benchmark does not hold is refused."""

import hashlib
import json
import os
import shutil

import numpy as np
import pytest

import common as C
import reduce as R
import run
from test_correct import drive

HERE = os.path.dirname(os.path.abspath(__file__))
GPT2 = C.plug_in((C.HERE,), "families", "gpt2", "family")
# Written by a script outside the repo that ran in the parent tree (commit
# 5791374, where these functions lived in `weights.py` and `reduce.py`):
# the SHA-256 of every leaf of both layouts at both configurations' `tiny`
# sizes, and the shape functions at the published sizes on `fixed_records`.
RECORDED = C.load_json(HERE, "recorded_gpt2_family.json")
CONFIGS = ("gpt2-xl-serve", "gpt2-medium-train")


def config(name, tiny=False):
    cfg = C.load_json(C.HERE, "configs", name + ".json")
    return dict(cfg, **cfg["tiny"]) if tiny else cfg


def leaf_hashes(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)

    def sha(a):
        a = np.asarray(a)
        return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode()
                              + a.tobytes()).hexdigest()
    return {"/".join(str(getattr(k, "key", k)) for k in path): sha(v)
            for path, v in flat}


def fixed_records():
    recs = []
    for i in range(48):
        sent = 100.0 + 1.25 * i
        first = sent + 0.07 + 0.001 * (i % 5)
        recs.append({"sent": sent, "prompt_len": 8 + (37 * i) % 89,
                     "stamps": [first + 0.05 * k
                                for k in range(16 + (29 * i) % 49)]})
    return recs


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 5])
@pytest.mark.parametrize("name", CONFIGS)
def test_the_moved_weights_are_the_parents_bit_for_bit(name, seed):
    want = RECORDED["weights"][f"{name}/{seed}"]
    cfg = config(name, tiny=True)
    assert leaf_hashes(GPT2.program_params(seed, cfg)) == \
        want["program_params"]
    assert leaf_hashes(GPT2.stacked(seed, cfg)) == want["stacked"]


@pytest.mark.parametrize("name", CONFIGS)
def test_the_moved_shape_functions_count_what_the_parents_did(name):
    want, cfg = RECORDED["flops"][name], config(name)
    assert GPT2.parameters(cfg) == want["parameters"]
    for position, logits, flops in want["serve_token_flops"]:
        assert GPT2.serve_token_flops(cfg, position, logits) == flops
    for seq, flops in want["train_token_flops"]:
        assert GPT2.train_token_flops(cfg, seq) == flops
    per_token = lambda p, logits: GPT2.serve_token_flops(cfg, p, logits)  # noqa: E731
    for t0, seconds, flops in want["serve_window_flops"]:
        assert R.serve_window_flops(per_token, fixed_records(), t0,
                                    seconds) == flops


# ------------------------------------------- a family the tree does not hold
TOY = {
    "family": "toyllama", "vocab_size": 2048, "max_position_embeddings": 256,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "hidden_act": "silu",
    "tie_word_embeddings": False, "weights_dtype": "float32",
    "init": {"std": 0.1, "embedding_std": 0.1}, "tiny": {}}
TOY_SERVE = dict(TOY, kind="serve", controls=["bfloat16"],
                 limits={"served_gap_sq_mean": 1e-9},
                 register={"num_slots": 8, "max_seq_len": 192,
                           "kv_pool_blocks": 96, "kv_block": 16,
                           "prefill_chunk": 64, "paged": True})
TOY_TRAIN = dict(TOY, kind="train", controls=["half_batch"],
                 limits={"grad_norm_gap": 0.02, "change_norm_gap": 0.035},
                 trainer=dict(config("gpt2-medium-train", tiny=True)["trainer"]))


@pytest.fixture
def toy_benchmark(tmp_path, monkeypatch):
    """A checkout of its own: `BENCHMARK.json`, and under its one path the
    family, two configurations, and copies of the data files they name."""
    bench = C.load_json(C.ROOT, "BENCHMARK.json")
    rename = {"gpt2xl_chat": "toy_chat", "gpt2m_train_1k": "toy_train"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    bench.update(paths=["b"], configs=[
        {"name": n, "file": f"b/configs/{n}.json"}
        for n in ("toy-serve", "toy-train")], workloads=[
        {"name": "toy_chat", "config": "toy-serve", "traffic": "chat",
         "chips": 1},
        {"name": "toy_train", "config": "toy-train", "traffic": "train_1k",
         "chips": 1}])
    b = tmp_path / "b"
    for folder in ("traffic", "layer_metrics"):
        shutil.copytree(os.path.join(C.HERE, folder), b / folder)
    (b / "families").mkdir()
    shutil.copy(os.path.join(HERE, "toy_family.py"),
                b / "families" / "toyllama.py")
    (b / "configs").mkdir()
    (b / "configs" / "toy-serve.json").write_text(json.dumps(TOY_SERVE))
    (b / "configs" / "toy-train.json").write_text(json.dumps(TOY_TRAIN))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    before = sorted(os.listdir(C.HERE))
    yield b
    assert sorted(os.listdir(C.HERE)) == before     # nothing written there
    assert not os.path.exists(os.path.join(C.HERE, "families",
                                           "toyllama.py"))


def test_a_family_is_files_a_serving_cell(capsys, toy_benchmark):
    notes, result = drive(capsys, "--workload", "toy_chat", "--seed",
                          str(2 ** 31 + 21), "--seconds", "2")
    assert result["correct"] is True and result["failed"] == 0
    assert notes["checked"]["tokens"] >= 100
    assert result["metrics"]["rehearsal.serve_tokens_per_s"]["value"] > 0


def test_a_family_is_files_its_control_is_not_correct(capsys, toy_benchmark):
    notes, result = drive(capsys, "--workload", "toy_chat", "--seed",
                          str(2 ** 31 + 22), "--seconds", "2",
                          "--control", "bfloat16")
    assert result["correct"] is False
    assert notes["checked"]["gap_sq_mean"] <= 1e-9 \
        < notes["checked"]["control"]["gap_sq_mean"]


def test_a_family_is_files_a_training_cell(capsys, toy_benchmark):
    notes, result = drive(capsys, "--workload", "toy_train", "--seed",
                          str(2 ** 31 + 23), "--seconds", "1")
    assert result["correct"] is True
    assert set(result["checks"]) >= {"grad_norm_gap", "change_norm_gap"}
    assert result["metrics"]["rehearsal.train_tokens_per_s"]["value"] > 0


# ------------------------------------------------------------ refusals
def test_a_family_the_tree_does_not_hold_is_refused_with_those_it_holds():
    with pytest.raises(C.Refused, match=r"family 'olmo'.*\['gpt2'\]"):
        C.family_of({"family": "olmo"})


def test_a_configuration_its_family_does_not_implement_is_refused():
    cfg = dict(config("gpt2-xl-serve"), tie_word_embeddings=False)
    with pytest.raises(C.Refused, match="tie_word_embeddings=False"):
        C.family_of(cfg)


def test_a_control_the_familys_reference_lacks_is_refused(toy_benchmark):
    """The toy family's reference has no float8 products: a configuration
    that states `fp8` all the same gets no run."""
    path = toy_benchmark / "configs" / "toy-serve.json"
    path.write_text(json.dumps(dict(TOY_SERVE, controls=["fp8"])))
    with pytest.raises(SystemExit, match=r"control 'fp8'.*'bfloat16'"):
        run.main(["--workload", "toy_chat", "--seed", "1", "--seconds", "1",
                  "--rehearse", "--control", "fp8"])
    assert C.control_of(GPT2, "fp8") == "fp8"
    assert C.control_of(GPT2, "half_batch", ("half_batch",)) == "half_batch"
    with pytest.raises(C.Refused, match="half_batch"):
        C.control_of(GPT2, "half_batch")
