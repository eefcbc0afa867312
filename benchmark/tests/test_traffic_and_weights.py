"""The traffic generator and the seeded weights."""

import numpy as np
import pytest

import common as C
import trafficgen
import weights

GPT2 = C.plug_in((C.HERE,), "families", "gpt2", "family")

TINY = {"vocab_size": 97, "n_positions": 64, "n_embd": 32, "n_head": 4,
        "n_layer": 3}


def _sizes(mix, seed):
    r = trafficgen.Requests(mix, seed, 50257, 50256, 512)
    return sorted(zip(r.prompt_len.tolist(), r.out_len.tolist()))


@pytest.mark.parametrize("name", ["chat", "doc", "chat_open", "sessions"])
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    mix = trafficgen.load_mix(name)
    a = trafficgen.Requests(mix, 1, 50257, 50256, 512)
    b = trafficgen.Requests(mix, 2 ** 31 + 12345, 50257, 50256, 512)
    assert _sizes(mix, 1) == _sizes(mix, 2 ** 31 + 12345)
    assert a.prompt_len.tolist() != b.prompt_len.tolist()
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert a.prompt_len.min() == lo and a.prompt_len.max() == hi
    first = a[0]
    assert first == trafficgen.Requests(mix, 1, 50257, 50256, 512)[0]
    assert 50256 not in first["prompt"] and max(first["prompt"]) < 50257
    assert a[0]["prompt"] != a[1]["prompt"] != b[0]["prompt"]


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 99])
def test_every_block_of_requests_holds_the_same_spread_of_prompts(seed):
    mix = trafficgen.load_mix("chat")
    r = trafficgen.Requests(mix, seed, 50257, 50256, 512)
    n, block = trafficgen.POOL, trafficgen.BLOCK
    ranks = np.sort(trafficgen.quantiles(mix["prompt_tokens"], n))
    strata = ranks.reshape(block, n // block)
    for b in r.prompt_len.reshape(n // block, block):
        b = np.sort(b)          # one length out of each stratum
        assert np.all((strata[:, 0] <= b) & (b <= strata[:, -1]))
    two_chunks = (r.prompt_len.reshape(-1, block) > 64).sum(1)
    assert two_chunks.max() - two_chunks.min() <= 1
    with pytest.raises(ValueError, match="whole blocks"):
        trafficgen.stratified_order(np.random.default_rng(0), 100, 32)


def test_log_uniform_leans_short_and_uniform_does_not():
    chat = trafficgen.quantiles({"dist": "log_uniform", "min": 8, "max": 96},
                                256)
    doc = trafficgen.quantiles({"dist": "uniform", "min": 192, "max": 384},
                               256)
    assert np.median(chat) < (8 + 96) / 2 - 15
    assert abs(np.median(doc) - 288) <= 1


def test_open_loop_arrivals_are_the_same_gaps_in_another_order():
    mix = trafficgen.load_mix("chat_open")
    a = trafficgen.Requests(mix, 5, 50257, 50256, 512)
    b = trafficgen.Requests(mix, 6, 50257, 50256, 512)
    assert sorted(a.gaps) == sorted(b.gaps) and list(a.gaps) != list(b.gaps)
    assert np.mean(a.gaps) == pytest.approx(1 / mix["rate_per_s"], rel=0.02)
    due = a.due_times(30.0)
    assert all(0 < x < 30.0 for x in due) and due == sorted(due)
    assert len(due) == pytest.approx(30.0 * mix["rate_per_s"], rel=0.25)


def test_shared_prefixes_come_from_a_small_pool():
    mix = trafficgen.load_mix("sessions")
    r = trafficgen.Requests(mix, 9, 50257, 50256, 512)
    n = mix["prefix_pool"]
    assert len(r.prefixes) == n
    p0, p1 = r[0]["prompt"], r[n]["prompt"]
    k = len(r.prefixes[0])
    assert 128 <= k <= 256 and p0[:k] == p1[:k] == r.prefixes[0]
    assert p0[k:] != p1[k:]


def test_a_mix_that_cannot_fit_a_slot_is_refused():
    with pytest.raises(ValueError, match="positions"):
        trafficgen.Requests(trafficgen.load_mix("doc"), 1, 50257, 50256, 256)


def test_both_layouts_hold_the_same_weights_bit_for_bit():
    import jax
    seed = 2 ** 31 + 7
    prog = GPT2.program_params(seed, TINY)
    again = GPT2.program_tree(GPT2.stacked(seed, TINY))
    a, b = jax.tree.leaves(prog), jax.tree.leaves(again)
    assert jax.tree.structure(prog) == jax.tree.structure(again)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))
    other = GPT2.program_params(seed + 1, TINY)
    assert not np.array_equal(np.asarray(prog["wte"]),
                              np.asarray(other["wte"]))
    assert not np.array_equal(np.asarray(prog["h0"]["attn"]["wq"]),
                              np.asarray(prog["h1"]["attn"]["wq"]))


def test_a_stated_embedding_spread_changes_the_two_tables_and_nothing_else():
    import jax
    plain = GPT2.stacked(11, TINY)
    assert weights.init_std(TINY) == (weights.STD, weights.STD)
    small = GPT2.stacked(11, dict(TINY, init={"embedding_std": 0.002}))
    ratio = np.asarray(small["wte"]) / np.asarray(plain["wte"])
    assert np.allclose(ratio, 0.1, rtol=1e-5)
    assert float(np.std(np.asarray(small["wpe"]))) == pytest.approx(
        0.001, rel=0.1)
    for a, b in zip(jax.tree.leaves(plain["layers"]),
                    jax.tree.leaves(small["layers"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(plain["lnf_w"]),
                          np.asarray(small["lnf_w"]))


def test_training_rows_all_differ_and_follow_the_seed():
    t = weights.train_tokens(2 ** 31 + 3, 32, 16, 97)
    assert t.shape == (32, 17) and t.dtype == np.int32
    assert len({row.tobytes() for row in t}) == 32
    assert np.array_equal(t, weights.train_tokens(2 ** 31 + 3, 32, 16, 97))
    assert not np.array_equal(t, weights.train_tokens(4, 32, 16, 97))
