"""The gated delta-rule layer, the hybrid model that alternates it with full
attention, and the decode engine's second kind of cache leaf (a recurrent
state resident by slot beside the paged KV pool), each against the plain
reference of `benchmark/families/olmo_hybrid.py`: the recurrence token by
token, nothing of the program imported. One period of layers at widths of a
few dozen, seeded weights, float32 so that the comparison is tight."""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import observe
from bigdl_tpu.nn.linear_attention import (GatedDeltaNet, gated_delta_chunk,
                                           gated_delta_step)
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
            "olmo_hybrid_family", os.path.join(BENCH, "families",
                                               "olmo_hybrid.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


CFG = {
    "model_type": "olmo_hybrid", "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False,
    "vocab_size": 128, "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "max_position_embeddings": 96,
    "rms_norm_eps": 1e-6, "rope_parameters": {"rope_theta": None},
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 12,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "weights_dtype": "float32", "init": {"std": 0.2},
    "register": {"prefill_chunk": 16},
}
EOS = CFG["vocab_size"] - 1
SLOTS, BLOCK, POOL_BLOCKS, CHUNK = 4, 8, 40, 16
ATOL = 2e-4         # float32 sums in another order, through four layers


@pytest.fixture(scope="module")
def lm(family):
    model, eos = family.build_model(CFG)
    assert eos == EOS
    return model, family.program_params(11, CFG), family.stacked(11, CFG)


def _entry(lm, name="hyb", **kw):
    model, params, _ = lm
    kw = dict(dict(num_slots=SLOTS, max_seq_len=96, kv_block=BLOCK,
                   kv_pool_blocks=POOL_BLOCKS, prefill_chunk=CHUNK,
                   paged=True), **kw)
    return DecodeEntry(name, model, params, **kw)


# ------------------------------------------------------------- the layer
def _recurrence_inputs(C, beta_top, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    N, H, dk, dv = 2, 3, 8, 12
    k = jax.random.normal(ks[1], (N, H, C, dk))
    return dict(
        S=jax.random.normal(ks[5], (N, H, dk, dv)),
        q=jax.random.normal(ks[0], (N, H, C, dk)),
        k=k / jnp.linalg.norm(k, axis=-1, keepdims=True),
        v=jax.random.normal(ks[2], (N, H, C, dv)),
        g=-jax.random.uniform(ks[3], (N, H, C)),
        beta=beta_top * jax.random.uniform(ks[4], (N, H, C)))


def _token_by_token(S, q, k, v, g, beta):
    outs = []
    for t in range(q.shape[2]):
        S, o = gated_delta_step(S, q[:, :, t], k[:, :, t], v[:, :, t],
                                g[:, :, t], beta[:, :, t])
        outs.append(o)
    return S, jnp.stack(outs, axis=2)


@pytest.mark.parametrize("C,beta_top,valid", [
    (16, 1.0, None), (16, 2.0, None), (64, 2.0, None), (1, 2.0, None),
    (16, 2.0, 11)], ids=["beta<=1", "beta>1", "chunk64", "one-token",
                         "valid-11-of-16"])
def test_chunk_form_equals_the_recurrence(C, beta_top, valid):
    """The WY form of a whole chunk against the recurrence as written,
    with `beta` above 1 (the negative eigenvalues) and with a padded tail
    that `beta = 0, g = 0` leaves out of state and outputs alike."""
    x = _recurrence_inputs(C, beta_top)
    if beta_top > 1.0:
        assert float(x["beta"].max()) > 1.0
    n = C if valid is None else valid
    if valid is not None:
        live = jnp.arange(C) < valid
        x["beta"], x["g"] = x["beta"] * live, x["g"] * live
    S_want, O_want = _token_by_token(**{
        a: (b if a == "S" else b[:, :, :n]) for a, b in x.items()})
    S, O = gated_delta_chunk(**x)
    np.testing.assert_allclose(S, S_want, atol=2e-5)
    np.testing.assert_allclose(O[:, :, :n], O_want, atol=2e-5)


@pytest.fixture(scope="module")
def layer(family):
    """One linear block's mixer with the family's weights, and its
    reference on a (2, 45, d) input."""
    lw = family._layer(jax.random.PRNGKey(5), family.LINEAR, CFG,
                       jnp.float32)
    m = family.build_model(CFG)[0].children()["l0"].children()["mixer"]
    params = family.to_program(lw, family.LINEAR)["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 45, 32))
    return m, params, x, family._linear_mixer(x, lw, CFG, "float32")


def test_layer_apply_equals_the_reference(layer):
    """`apply`: whole sequences, chunk by chunk under `lax.scan` (45 tokens
    are two chunks of 32 and a padded tail)."""
    m, params, x, want = layer
    short = GatedDeltaNet(32, 4, 8, 12, chunk=32)
    np.testing.assert_allclose(short.apply(params, {}, x)[0], want,
                               atol=ATOL)
    np.testing.assert_allclose(m.apply(params, {}, x)[0], want, atol=ATOL)


def test_layer_prefill_then_decode_equals_the_reference(layer):
    """Prefill in uneven chunks (valid lengths that are not their
    buckets'), then one token a step, on slots that held another request:
    row 0 starts at position 0 over a dirty state, row 1 joins a chunk
    later; a row of length 0 gets its state back bit for bit."""
    m, params, x, want = layer
    prefill, decode = jax.jit(m.prefill_step), jax.jit(m.decode_step)
    dirty = jax.tree.map(lambda a: a + 3.0, m.make_state(3, x.dtype))
    state, out = dirty, [[], []]
    fed = [0, 0]
    plan = [((16, 16), (0, 0)), ((16, 11), (16, 16)), ((8, 5), (8, 3)),
            ((1, 1), (0, 0))]                 # (bucket, valid) for each row
    for (c0, n0), (c1, n1) in plan:
        C = max(c0, c1)
        xs = jnp.zeros((3, C, 32))
        lengths = [n0, n1, 0]
        for r, n in enumerate((n0, n1)):
            xs = xs.at[r, :n].set(x[r, fed[r]:fed[r] + n])
        pos = jnp.asarray([fed[0], fed[1], 7])[:, None] + jnp.arange(C)
        o, new = prefill(params, xs, state, pos, jnp.asarray(lengths))
        for leaf in ("S", "conv"):      # the idle row, and a row not fed
            kept = [2] + [r for r, n in enumerate((n0, n1)) if n == 0]
            np.testing.assert_array_equal(new[leaf][jnp.asarray(kept)],
                                          state[leaf][jnp.asarray(kept)])
        state = new
        for r, n in enumerate((n0, n1)):
            out[r].append(o[r, :n])
            fed[r] += n
    assert fed == [33, 19]
    for t in range(33, 45):             # row 0 decodes, row 1 stands still
        o, new = decode(
            params, jnp.stack([x[0, t], x[1, 0], x[1, 0]])[:, None], state,
            jnp.asarray([t, 0, 0]), jnp.asarray([True, False, False]))
        for leaf in ("S", "conv"):
            np.testing.assert_array_equal(new[leaf][1:], state[leaf][1:])
        state = new
        out[0].append(o[0])
    np.testing.assert_allclose(jnp.concatenate(out[0]), want[0], atol=ATOL)
    np.testing.assert_allclose(jnp.concatenate(out[1]), want[1, :19],
                               atol=ATOL)


def test_decode_at_position_zero_starts_from_a_zero_state(layer):
    """A prompt of one token is never prefilled: its first decode step, at
    position 0, is what resets the slot."""
    m, params, x, want = layer
    dirty = jax.tree.map(lambda a: a + 3.0, m.make_state(2, x.dtype))
    o, _ = m.decode_step(params, x[:, :1], dirty, jnp.asarray([0, 0]),
                         jnp.asarray([True, True]))
    np.testing.assert_allclose(o, want[:, :1], atol=ATOL)


# ------------------------------------------------------------- the model
def test_model_apply_equals_the_familys_logits(family, lm):
    model, params, w = lm
    tokens = np.random.default_rng(3).integers(0, EOS, size=(2, 70))
    got, _ = model.apply(params, {}, jnp.asarray(tokens))
    want = family.logits(w, CFG, jnp.asarray(tokens), "float32")
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert float(jnp.abs(want).max()) > 0.5     # the logits are not flat


def test_program_params_and_stacked_are_equal_leaf_by_leaf(family, lm):
    model, params, w = lm
    tree = family.program_tree(w)
    assert jax.tree.structure(tree) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    want, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, want) == \
        jax.tree.map(lambda a: a.shape, params)


def test_paged_contract_equals_the_reference_on_logits(family, lm):
    """Prefill in uneven chunks through the paged contract (the hidden
    states in chunk form, then one token a step and the head, composed
    as DecodeEntry._build composes them), the step's logits at every
    position, against the reference's full
    forward pass: two rows at different offsets, two idle slots."""
    model, params, w = lm
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, EOS, size=n) for n in (61, 40)]
    want = [family.logits(w, CFG, jnp.asarray(s)[None], "float32")[0]
            for s in seqs]
    caches = model.make_paged_slot_caches(params, POOL_BLOCKS, BLOCK,
                                          num_slots=SLOTS)
    table = np.full((SLOTS, 12), -1, np.int32)
    table[1, :8], table[2, :8] = np.arange(8), np.arange(20, 28)
    rows, fed = (1, 2), [0, 0]
    prefill = jax.jit(lambda p, c, t, pos, bt, ln: model.paged_hidden(
        p, c, t, pos, bt, ln, decode=False)[1])

    @jax.jit
    def decode(p, c, t, pos, a, bt):
        x, c = model.paged_hidden(p, c, t[:, None], pos[:, None], bt,
                                  a.astype(jnp.int32), decode=True)
        return model.head_logits(p, x), c
    for chunk in ((16, 16), (16, 7), (8, 8), (4, 0)):
        C = max(chunk)
        toks = np.zeros((SLOTS, C), np.int32)
        pos = np.zeros((SLOTS, C), np.int32)
        lengths = np.zeros((SLOTS,), np.int32)
        for r, s, n, i in zip(rows, seqs, chunk, (0, 1)):
            toks[r, :n] = s[fed[i]:fed[i] + n]
            pos[r] = fed[i] + np.arange(C)
            lengths[r] = n
            fed[i] += n
        caches = prefill(params, caches, toks, pos, table, lengths)
    assert fed == [44, 31]
    for step in range(17):
        toks = np.zeros((SLOTS,), np.int32)
        pos = np.zeros((SLOTS,), np.int32)
        active = np.zeros((SLOTS,), bool)
        for r, s, i in zip(rows, seqs, (0, 1)):
            if fed[i] < len(s):
                toks[r], pos[r], active[r] = s[fed[i]], fed[i], True
        logits, caches = decode(params, caches, toks, pos, active, table)
        for r, i in zip(rows, (0, 1)):
            if active[r]:
                np.testing.assert_allclose(logits[r], want[i][fed[i]],
                                           atol=ATOL)
                fed[i] += 1
    assert fed == [61, 40]


# ---------------------------------------------------------- the scheduler
# (join step, prompt length, max_new): 7 requests through 4 slots
STAGGERED = [(0, 3, 10), (0, 37, 10), (1, 12, 6), (3, 1, 10), (6, 21, 8),
             (8, 4, 10), (9, 50, 10)]


def _run(entry, submits):
    sched = DecodeScheduler(entry, name=entry.name, start=False)
    replies, step = [None] * len(submits), 0
    while True:
        for i, (at, prompt, max_new) in enumerate(submits):
            if at == step:
                replies[i] = sched.submit(prompt, max_new)
        worked = sched.step_once()
        step += 1
        if not worked and all(r is not None and r.done() for r in replies):
            break
        assert step < 500, "the scheduler did not converge"
    out = [r.result(timeout=1) for r in replies]
    stats = sched.stats()
    sched.close(drain=False)
    return out, stats


@pytest.fixture(scope="module")
def staggered(lm):
    rng = np.random.default_rng(7)
    submits = [(at, rng.integers(0, EOS, size=p).astype(np.int32), new)
               for at, p, new in STAGGERED]
    entry = _entry(lm, "stag")
    outs, stats = _run(entry, submits)
    return entry, submits, outs, stats


@pytest.mark.parametrize("i", range(len(STAGGERED)))
def test_staggered_joins_and_leaves_equal_each_sequence_alone(staggered, i):
    """Concurrent decoding with staggered joins and leaves, slots handed
    on with their old state in them, gives each sequence what it gives
    alone on a fresh scheduler (the parity tests/test_decode.py holds for
    GPT-2)."""
    entry, submits, outs, _ = staggered
    _, prompt, max_new = submits[i]
    alone, _ = _run(entry, [(0, prompt, max_new)])
    np.testing.assert_array_equal(outs[i], alone[0])


def test_served_tokens_lie_on_the_references_best_logit(family, lm,
                                                        staggered):
    """Prefill in uneven chunks then decoding through `DecodeScheduler`
    against the reference's full forward pass, on logits: each served
    token's reference logit is the reference's best but for rounding."""
    _, submits, outs, _ = staggered
    gaps_of = family.reference.served_gaps_of(family, CFG)
    for (_, prompt, _), served in zip(submits, outs):
        g = gaps_of(lm[2], prompt, served, 64)
        assert g["finite"] and float(g["gap"].max()) < ATOL


def test_slot_handed_on_after_a_dropped_row_starts_from_a_zero_state(
        staggered):
    """A sequence that ends by value while its row of the next step is in
    flight leaves one stray update in its slot's state. The request that
    takes the slot next (a prompt of one token: no prefill, its first
    decode step at position 0) decodes what it decodes alone."""
    entry, submits, outs, _ = staggered
    (_, first, new), (_, follower, new_f) = submits[0], submits[3]
    assert len(follower) == 1
    eos = int(outs[0][2])
    stop = int(np.argmax(outs[0] == eos)) + 1
    sched = DecodeScheduler(entry, name="handon", start=False)
    rep = sched.submit(first, new, eos_id=eos)
    while not rep.done():
        sched.step_once()
    np.testing.assert_array_equal(rep.result(timeout=1), outs[0][:stop])
    assert sched._in_flight is not None          # the stray row, unfetched
    rep2 = sched.submit(follower, new_f)
    while not rep2.done():
        sched.step_once()
    assert rep2.result(timeout=1).shape == outs[3].shape
    np.testing.assert_array_equal(rep2.result(timeout=1), outs[3])
    stats = sched.stats()
    assert stats["rows_dropped"] == 1 and stats["state_resets"] == 2
    sched.close(drain=False)


def test_state_counters_and_gauges(staggered):
    entry, submits, outs, stats = staggered
    assert stats["state"] == "kv+recurrent"
    assert stats["state_resets"] == len(submits)
    assert stats["prefill_tokens"] == sum(len(p) - 1 for _, p, _ in submits)
    assert stats["state_bytes"] == entry.state_bytes
    assert stats["kv_pool_bytes"] == entry.kv_pool_bytes
    snap = observe.metrics.registry().snapshot()
    assert snap["gauges"]["serve/stag/decode/state_bytes"] == \
        entry.state_bytes
    assert snap["gauges"]["serve/stag/decode/kv_pool_bytes"] == \
        entry.kv_pool_bytes
    assert snap["counters"]["serve/stag/decode/state_resets"] >= len(submits)
    assert snap["counters"]["serve/stag/decode/prefill_tokens"] >= \
        stats["prefill_tokens"]


# -------------------------------------------------------------- the entry
def test_entry_sizes_pool_plus_state_in_closed_form(lm):
    entry = _entry(lm, "size")
    H, dk, dv = 4, 8, 12
    state = 3 * SLOTS * (H * dk * dv * 4 + 3 * (2 * H * dk + H * dv) * 4)
    pool = 1 * 4 * POOL_BLOCKS * BLOCK * 2 * 8 * 4      # heads x ... x 2 hd
    assert entry.state_bytes == state and entry.kv_pool_bytes == pool
    assert entry.kv_cache_bytes == pool + state
    assert entry.slot_state and entry.prefix_cache is False
    sched = DecodeScheduler(entry, name="size", start=False)
    from bigdl_tpu.observe import memz
    owners = memz.ledger().owners()
    assert owners["serve/size/kv_pool"]["bytes"] == pool
    assert owners["serve/size/kv_pool"]["meta"]["bytes_per_block"] == \
        pool // POOL_BLOCKS
    assert owners["serve/size/slot_state"]["bytes"] == state
    assert owners["serve/size/slot_state"]["kind"] == "slot_state"
    sched.close(drain=False)
    assert "serve/size/slot_state" not in memz.ledger().owners()


def test_entry_refuses_the_prefix_cache_by_name(lm):
    with pytest.raises(ValueError, match="recurrent state no KV block"):
        _entry(lm, "pfx", prefix_cache=True)
    assert _entry(lm, "pfx", prefix_cache=False).prefix_cache is False


def test_entry_refuses_the_dense_bucket_by_name(lm):
    with pytest.raises(ValueError, match="dense slot bucket was removed; "
                                         "OlmoHybridLM"):
        _entry(lm, "dense", paged=False)


def test_a_model_without_slot_state_is_sized_as_before():
    from bigdl_tpu.serve.decode import decode_demo_model
    model, params, _ = decode_demo_model()
    entry = DecodeEntry("demo", model, params, num_slots=2, max_seq_len=32,
                        kv_block=8, kv_pool_blocks=8, paged=True)
    assert not entry.slot_state and entry.state_bytes == 0
    assert entry.kv_pool_bytes == entry.kv_cache_bytes
    assert entry.state_kind == "kv"
    assert entry.split_caches(entry.make_caches())[1] == []


def test_kv_shard_shards_the_pool_and_replicates_the_state(lm):
    """Under a mesh with `kv_shard`, the pooled leaves take the block-dim
    sharding and the leaves resident by slot stay replicated."""
    from bigdl_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(jax.devices()[:4], data=4, drop_trivial_axes=True)
    entry = _entry(lm, "shard", mesh=mesh, kv_shard=True)
    caches = entry.make_caches()
    pooled, by_slot = entry.split_caches(caches)
    assert len(pooled) == 1 and len(by_slot) == 6
    assert all(a.sharding.spec == entry._pool_sharding.spec for a in pooled)
    assert all(a.sharding.is_fully_replicated for a in by_slot)
    sched = DecodeScheduler(entry, name="shard", start=False)
    rep = sched.submit(np.arange(2, 22, dtype=np.int32), 5)
    while not rep.done():
        sched.step_once()
    alone, _ = _run(_entry(lm, "unsharded"),
                    [(0, np.arange(2, 22, dtype=np.int32), 5)])
    np.testing.assert_array_equal(rep.result(), alone[0])
    sched.close(drain=False)
