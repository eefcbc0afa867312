"""Latent (MLA) attention with its learned sparse indexer over paged pools,
the gated experts a chip holds a share of, and the model made of them on the
decode engine, each against the plain reference of
`benchmark/families/glm_moe_dsa.py` (nothing of the program imported there).
Widths of a few dozen, seeded weights, float32 so that the comparison is
tight, and contexts several times the tiny `index_topk`, so that the
selection is never idle."""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.experts import GatedExperts
from bigdl_tpu.nn.latent_attention import (admitted_mask, select_topk)
from bigdl_tpu.serve.decode import DecodeEntry, DecodeScheduler

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def family():
    """The family's module, loaded from its file as the harness loads it
    (it imports the benchmark's `reference` and `weights`)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "glm_moe_dsa_family", os.path.join(BENCH, "families",
                                               "glm_moe_dsa.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        import reference
        module.served_gaps_of = reference.served_gaps_of
    finally:
        sys.path.remove(BENCH)
    return module


TOPK = 8
CFG = {
    "model_type": "glm_moe_dsa", "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "rope_interleave": True, "indexer_rope_interleave": True,
    "vocab_size": 96, "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 3, "first_hidden_layer": 1,
    "indexer_types": ["full", "full", "shared", "full", "shared"],
    "mlp_layer_types": ["dense", "dense", "sparse", "sparse", "sparse"],
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "qk_head_dim": 16, "v_head_dim": 12, "index_n_heads": 2,
    "index_head_dim": 16, "index_topk": TOPK, "moe_intermediate_size": 16,
    "n_routed_experts": 6, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 8000000},
    "max_position_embeddings": 96,
    "published": {"n_routed_experts": 16, "vocab_size": 256},
    "share": {"experts_first": 4, "vocab_first": 32},
    "weights_dtype": "float32", "init": {"std": 0.2},
}
SLOTS, BLOCK, POOL_BLOCKS, CHUNK = 3, 4, 60, 8
# float32 sums in another order (the program absorbs W_uk into the query in
# another association than the reference, and sums experts by token), through
# three layers, on logits of spread ~1. bfloat16 activations read 1e-2 here.
ATOL = 2e-4


@pytest.fixture(scope="module")
def lm(family):
    model, eos = family.build_model(CFG)
    assert eos == CFG["vocab_size"] - 1 == model.vocab_size - 1
    return model, family.program_params(11, CFG), family.stacked(11, CFG)


def _entry(lm, name="glm", **kw):
    model, params, _ = lm
    kw = dict(dict(num_slots=SLOTS, max_seq_len=96, kv_block=BLOCK,
                   kv_pool_blocks=POOL_BLOCKS, prefill_chunk=CHUNK,
                   prefix_cache=True, prefix_cache_blocks=POOL_BLOCKS), **kw)
    return DecodeEntry(name, model, params, **kw)


def _tokens(n, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"] - 1, (n, length)).astype(np.int32)


# ------------------------------------------------ the paths of attention
T = 32                                  # four times index_topk


@pytest.fixture(scope="module")
def sequences(family, lm):
    """Two sequences and the reference's logits of them."""
    tokens = _tokens(2, T)
    return tokens, np.asarray(jax.jit(
        lambda w, t: family.logits(w, CFG, t))(lm[2], jnp.asarray(tokens)))


def _paged_logits(model, params, tokens, chunks, steps):
    """Logits of every position through the pools: the first `chunks`
    tokens in prompt chunks of CHUNK, the next `steps` one decode step
    each; two rows whose blocks interleave in the pool."""
    N = tokens.shape[0]
    caches = model.make_paged_slot_caches(params, POOL_BLOCKS, BLOCK)
    table = np.full((N, 96 // BLOCK), -1, np.int32)
    for n in range(N):
        table[n, :T // BLOCK] = np.arange(T // BLOCK) * N + n
    table = jnp.asarray(table)

    @jax.jit(static_argnames="decode")
    def run(caches, chunk, first, decode):
        C = chunk.shape[1]
        x, caches = model.paged_hidden(
            params, caches, chunk,
            jnp.broadcast_to(first + jnp.arange(C), (N, C)), table,
            jnp.full((N,), C, jnp.int32), decode=decode)
        return model._logits(params, x), caches
    out = []
    spans = [(lo, lo + CHUNK, False) for lo in range(0, chunks, CHUNK)] \
        + [(t, t + 1, True) for t in range(chunks, chunks + steps)]
    for lo, hi, decode in spans:
        logits, caches = run(caches, jnp.asarray(tokens[:, lo:hi]), lo,
                             decode=decode)
        out.append(logits)
    return jnp.concatenate(out, axis=1), caches


@pytest.mark.parametrize("chunks,steps", [(None, None), (T, 0), (0, T),
                                          (T - 8, 8)],
                         ids=["apply", "chunks", "steps",
                              "chunks_then_steps"])
def test_every_path_equals_the_reference(lm, sequences, chunks, steps):
    """`apply` (keys and values expanded a head, masked), prompt chunks (the
    slot's context under a mask, absorbed), decode steps (the chosen rows
    gathered, absorbed) and one after the other give the logits of the
    reference's full forward pass, at contexts up to four times
    `index_topk`."""
    model, params, _ = lm
    tokens, want = sequences
    if chunks is None:
        got = jax.jit(lambda p, t: model.apply(p, {}, t)[0])(
            params, jnp.asarray(tokens))
    else:
        got, caches = _paged_logits(model, params, tokens, chunks, steps)
        # what the expert layers counted: 2 sparse layers a token
        pairs, loads, through = np.asarray(caches[-1])
        assert through == 2 * T * 2 and 0 < loads <= pairs <= through * 4
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)


def test_mask_and_positions_admit_the_same_set():
    """The prompt chunk's threshold mask and the decode step's `top_k`
    positions are one selection, ties and all."""
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(6, 40)).astype(np.float32)
    scores[:, ::3] = 0.25               # ties, some of them at the threshold
    scores[2] = 1.0                     # a row that is one tie
    positions = jnp.asarray([39, 20, 39, 7, 3, 30])
    mask = np.asarray(admitted_mask(jnp.asarray(scores), positions, TOPK))
    idx = np.asarray(select_topk(jnp.asarray(scores), positions, TOPK))
    for t, p in enumerate(np.asarray(positions)):
        assert set(np.flatnonzero(mask[t])) == set(idx[t][idx[t] <= p])
        assert mask[t].sum() == min(TOPK, p + 1)


def test_a_shared_layer_admits_what_the_full_layer_before_it_admitted(lm):
    model, params, _ = lm
    blocks = dict(model._blocks())
    assert [b.indexer for b in blocks.values()] == ["full", "shared",
                                                   "full"]
    shared, p = blocks["l1"].children()["attn"], params["l1"]["attn"]
    assert shared.indexer is None and "iq" not in p
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 32))
    with pytest.raises(ValueError, match="selection of the full layer"):
        shared.dense(p, x)
    causal = jnp.tril(jnp.ones((1, 24, 24), bool))
    recent = causal & ~jnp.tril(jnp.ones((1, 24, 24), bool), -TOPK)
    everything, handed = shared.dense(p, x, causal)
    windowed, _ = shared.dense(p, x, recent)
    assert handed is causal
    assert float(jnp.abs(everything - windowed)[0, TOPK:].max()) > 1e-3
    np.testing.assert_allclose(np.asarray(everything[0, :TOPK]),
                               np.asarray(windowed[0, :TOPK]), atol=1e-6)


# ------------------------------------------------------------ the experts
def _experts(share=None, top_k=2, seed=0):
    layer = GatedExperts(16, 12, 8, top_k, expert_share=share, scaling=2.5)
    full = GatedExperts(16, 12, 8, top_k, scaling=2.5)
    params, _ = full.init(jax.random.PRNGKey(seed))
    params = jax.tree.map(lambda a: a + 0.3 * jax.random.normal(
        jax.random.PRNGKey(a.size), a.shape), params)
    if share is not None:
        a, n = share
        params = dict(params, **{k: params[k][a:a + n]
                                 for k in ("gate", "up", "down")})
    return layer, params


def _expert(params, e, x):
    return (jax.nn.silu(x @ params["gate"][e]) * (x @ params["up"][e])) \
        @ params["down"][e]


def test_routing_that_sends_every_token_to_one_expert_drops_none():
    layer, params = _experts()
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 16))
    params["router_bias"] = jnp.zeros(8).at[jnp.asarray([2, 5])].set(10.0)
    y, counts = layer.mixed(params, x)
    s = jax.nn.sigmoid(x @ params["router"])[:, jnp.asarray([2, 5])]
    w = s / s.sum(-1, keepdims=True) * 2.5
    shared = layer.apply(dict(params, gate=0 * params["gate"]), {}, x)[0]
    want = w[:, :1] * _expert(params, 2, x) + w[:, 1:] * _expert(params, 5, x)
    np.testing.assert_allclose(np.asarray(y - shared), np.asarray(want),
                               atol=1e-4)
    assert counts.tolist() == [80, 2]   # every token's two pairs, 2 experts
    # tokens left out (a padded tail) have no pair and no routed part
    valid = jnp.arange(40) < 30
    y2, counts = layer.mixed(params, x, valid)
    assert counts.tolist() == [60, 2]
    np.testing.assert_allclose(np.asarray(y2[30:]), np.asarray(shared[30:]),
                               atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """The partial results of all shares of a layer, the shared expert
    counted once, are the uncut layer's result."""
    whole, params = _experts(top_k=3)
    x = jax.random.normal(jax.random.PRNGKey(2), (24, 16))
    want, counts = whole.mixed(params, x)
    assert counts[0] == 24 * 3
    shared = whole.apply(dict(params, gate=0 * params["gate"]), {}, x)[0]
    total, pairs = shared, 0
    for share in ((0, 3), (3, 3), (6, 2)):
        part, cut = _experts(share, top_k=3)
        y, c = part.mixed(cut, x)
        total, pairs = total + (y - shared), pairs + int(c[0])
    assert pairs == 24 * 3
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4)


def test_the_vocabulary_slice_is_a_smaller_vocabulary(family, lm):
    """Rows [32, 128) of a model over 256 ids: its logits are the whole
    model's over those ids, for tokens of the slice."""
    sliced, params, _ = lm
    assert sliced.vocab_share == (32, 96) and sliced.vocab_size == 96
    whole_cfg = dict(CFG, vocab_size=256, share={"experts_first": 4})
    whole, _ = family.build_model(whole_cfg)
    table = jax.random.normal(jax.random.PRNGKey(3), (2, 256, 32)) * 0.2
    big = dict(params, embed=table[0], lm_head=table[1])
    small = dict(params, embed=table[0, 32:128], lm_head=table[1, 32:128])
    tokens = _tokens(1, 20, seed=2)
    got = jax.jit(lambda p, t: sliced.apply(p, {}, t)[0])(
        small, jnp.asarray(tokens))
    want = jax.jit(lambda p, t: whole.apply(p, {}, t)[0])(
        big, jnp.asarray(tokens + 32))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[..., 32:128]), atol=1e-5)


# ------------------------------------------- through the decode scheduler
def _drive(sched, reps):
    steps = 0
    while not all(r.done() for r in reps):
        sched.step_once()
        steps += 1
        assert steps < 400


@pytest.fixture(scope="module")
def served(family, lm):
    """A document asked once (a miss), then twice more (hits) with other
    questions and once with the same, through one scheduler."""
    entry = _entry(lm)
    entry.precompile()
    sched = DecodeScheduler(entry, name="glm", start=False)
    doc = _tokens(1, 42, seed=5)[0]
    ask = [np.concatenate([doc, q]) for q in _tokens(3, 7, seed=6)]
    ask.append(ask[0])
    first = sched.submit(ask[0], 12, eos_id=-1)
    _drive(sched, [first])
    misses = sched.stats()["prefix_misses"]
    rest = [sched.submit(p, 12, eos_id=-1) for p in ask[1:]]
    _drive(sched, rest)
    out = {"prompts": ask, "stats": sched.stats(), "misses_alone": misses,
           "tokens": [r.result(timeout=1) for r in [first] + rest]}
    sched.close(drain=False)
    return out


@pytest.mark.parametrize("i", range(4))
def test_served_tokens_lie_on_the_references_best_logit(family, lm, served,
                                                        i):
    """Prefill chunks then decode steps through the scheduler, contexts of
    48-60 tokens against an `index_topk` of 8, a miss (0) and prefix hits
    (1-3): every served token is the reference's first choice along the
    served sequence (its reference logit lies less than ATOL below the
    best)."""
    gaps = family.served_gaps_of(family, CFG)(
        lm[2], list(served["prompts"][i]), list(served["tokens"][i]), 64)
    assert gaps["finite"] and len(gaps["gap"]) == 12
    assert float(gaps["gap"].max()) <= ATOL


def test_a_prefix_hit_gives_the_tokens_of_a_miss(served):
    """The same prompt served from the prefix cache (its 10 whole blocks
    of document, through the latent and the indexer pools alike) and
    computed from nothing."""
    np.testing.assert_array_equal(served["tokens"][3], served["tokens"][0])
    st = served["stats"]
    assert served["misses_alone"] == 12         # (49 - 1) // 4 whole blocks
    # 42 tokens of document = 10 whole blocks a hit; the same prompt 12
    assert st["prefix_hits"] == 10 + 10 + 12


def test_counters_of_the_selection_and_the_experts(served):
    st = served["stats"]
    computed = st["prefill_tokens"] + st["tokens"]
    assert st["prefill_tokens"] == 48 + 8 + 8 + 0
    assert st["expert_tokens"] == computed * 2
    assert 0 < st["expert_loads"] <= st["expert_pairs"] <= computed * 2 * 4
    # every computed token sits at position >= 40: it attends index_topk
    assert st["attended_tokens"] == TOPK * (computed - 48) + sum(
        min(p + 1, TOPK) for p in range(48))
    assert st["context_tokens"] > 5 * st["attended_tokens"]
    assert 0 < st["step_context_tokens"] < st["context_tokens"]
    assert st["kv_pool_bytes"] == POOL_BLOCKS * BLOCK * 4 * (
        3 * 128 + 2 * 128) + 12     # rows of whole 128-lane tiles


# ------------------------------- a question's chunk rides the decode step
@pytest.fixture(scope="module")
def sampled_entry(lm):
    return _entry(lm, "ride", sampling=True)


def _staggered(entry, name, submits):
    """`submits` = [(iteration at which it is sent, prompt, max_new,
    sampling keywords)] through one scheduler -> (tokens, stats)."""
    sched = DecodeScheduler(entry, name=name, start=False)
    reps, at = [None] * len(submits), 0
    while not all(r is not None and r.done() for r in reps):
        for i, (when, prompt, new, kw) in enumerate(submits):
            if when == at:
                reps[i] = sched.submit(prompt, new, eos_id=-1, **kw)
        sched.step_once()
        at += 1
        assert at < 400
    out = [r.result(timeout=1) for r in reps], sched.stats()
    sched.close(drain=False)
    return out


@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_questions_that_ride_the_steps_get_their_own_tokens(sampled_entry,
                                                            mode):
    """Questions over one document and a longer prompt, sent while other
    slots decode: their chunks ride the decode steps (the selection by
    positions for the step's rows and by mask for the chunk's in one pass,
    the experts over both), and each request gets the tokens it gets alone,
    its document from the prefix cache or not."""
    doc = _tokens(1, 42, seed=5)[0]
    prompts = [np.concatenate([doc, q]) for q in _tokens(3, 7, seed=6)] \
        + [_tokens(1, 21, seed=9)[0]]
    subs = [(at, p, new, {} if mode == "greedy" else dict(
        temperature=1.3, top_k=24, top_p=0.9, seed=70 + i))
        for i, (at, p, new) in enumerate(zip((0, 8, 9, 11), prompts,
                                             (14, 16, 16, 8)))]
    outs, stats = _staggered(sampled_entry, f"ride-{mode}", subs)
    assert stats["prefill_carried"] >= 4 and stats["prefix_hits"] >= 20
    assert stats["prefill_tokens"] == 48 + 8 + 8 + 20
    for (_, prompt, new, kw), got in zip(subs, outs):
        alone, st = _staggered(sampled_entry, f"alone-{mode}",
                               [(0, prompt, new, kw)])
        np.testing.assert_array_equal(got, alone[0])


def test_a_short_question_rides_padded_to_the_smallest_carried_bucket(lm):
    """Under the smallest carried bucket a chunk is padded up to it and
    masked by its length: the pools hold what the exact bucket's one-row
    call leaves, and the experts count its valid tokens alone."""
    entry = _entry(lm, "pad")
    assert entry.carried == (CHUNK,)
    r = np.random.default_rng(3)
    dirty = jax.tree.map(
        lambda a: jnp.asarray(r.standard_normal(a.shape), a.dtype)
        if a.ndim > 1 else a, entry.make_caches())
    table = np.full((SLOTS, entry.blocks_per_slot), -1, np.int32)
    table[:, :4] = np.arange(4 * SLOTS).reshape(SLOTS, 4)
    s, fed, n = 1, 9, 2

    def chunk(bucket):
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n] = [5, 9]
        return (tokens, fed + np.arange(bucket, dtype=np.int32)[None],
                table[[s]], np.asarray([n], np.int32),
                np.asarray([s], np.int32))
    call = entry.run_prefill(dirty, *chunk(2))
    idle = np.zeros((SLOTS,), np.int32)
    _, rode = entry.run_decode(dirty, idle, idle, np.zeros((SLOTS,), bool),
                               table, *chunk(CHUNK))
    for a, b in zip(jax.tree.leaves(call), jax.tree.leaves(rode)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-6, atol=1e-6)
    assert int(rode[-1][2]) == n * 2        # tokens through expert layers


def test_kv_shard_shards_every_pool_along_its_own_block_axis(lm):
    """Pools of another shape than K/V (blocks lead) take the block-dim
    sharding where their blocks lie; the counts are replicated; decoding is
    what it is unsharded."""
    from bigdl_tpu.parallel.mesh import create_mesh
    from jax.sharding import PartitionSpec
    mesh = create_mesh(jax.devices()[:4], data=4, drop_trivial_axes=True)
    entry = _entry(lm, "shard", mesh=mesh, kv_shard=True, prefill_chunk=4)
    entry.precompile()                  # runs _assert_pool_sharding
    caches = entry.make_caches()
    assert entry._pool_sharding.spec == PartitionSpec(entry._shard_axis)
    for pools in caches[:-1]:
        assert all(a.sharding.spec == PartitionSpec(entry._shard_axis)
                   for a in pools.values())
    assert caches[-1].sharding.is_fully_replicated
    prompt = _tokens(1, 21, seed=9)[0]
    got = []
    for e in (entry, _entry(lm, "whole", prefill_chunk=4)):
        sched = DecodeScheduler(e, name=e.name, start=False)
        rep = sched.submit(prompt, 5, eos_id=-1)
        _drive(sched, [rep])
        got.append(rep.result(timeout=1))
        sched.close(drain=False)
    np.testing.assert_array_equal(got[0], got[1])
