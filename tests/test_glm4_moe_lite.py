"""GLM-4.7-Flash on the decode engine: dense latent (MLA) attention over the
paged latent pool (its decode-step kernel against its XLA form), the
multi-token prediction (MTP) module's rows and drafts, and the step that
verifies one drafted token a slot, each against the plain reference of
`benchmark/families/glm4_moe_lite.py` (nothing of the program imported
there) or against the serial greedy loop. Widths of a few dozen, seeded
weights, float32 so that the comparisons are tight.

The acceptance of drafts is set by weights alone, the program unchanged:
every output projection zeroed and `W_eh = [I, 0]` make the main model and
the MTP module both predict from the token alone (all drafts accepted);
seeded random weights accept about none; the same with a share of the
hidden state in `W_eh` accepts some."""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.kernels.paged_latent import NEG_INF, _xla, paged_latent_attend
from bigdl_tpu.serve.decode import DecodeEntry, DecodeScheduler, PrefixCache

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def family():
    """The family's module, loaded from its file as the harness loads it
    (it imports the benchmark's `reference` and `weights`)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "glm4_moe_lite_family", os.path.join(BENCH, "families",
                                                 "glm4_moe_lite.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        import reference
        module.served_gaps_of = reference.served_gaps_of
    finally:
        sys.path.remove(BENCH)
    return module


CFG = {
    "model_type": "glm4_moe_lite", "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "partial_rotary_factor": 1, "rope_scaling": None,
    "num_nextn_predict_layers": 1, "vocab_size": 96, "hidden_size": 32,
    "intermediate_size": 48, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "moe_intermediate_size": 16, "n_routed_experts": 6,
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 1.8, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
    "max_position_embeddings": 128, "weights_dtype": "float32",
    "init": {"std": 0.2},
}
SLOTS, BLOCK, POOL_BLOCKS, CHUNK = 3, 4, 120, 8
# float32 sums in another order (the program absorbs W_uk into the query
# and reads the context a page at a time, the reference expands keys and
# values a head), through three layers and the MTP block, on logits of
# spread ~1; bfloat16 activations read 1e-2 here
ATOL = 2e-4


@pytest.fixture(scope="module")
def lm(family):
    model, eos = family.build_model(CFG)
    assert eos == CFG["vocab_size"] - 1
    return model, family.program_params(11, CFG), family.stacked(11, CFG)


def _tokens(n, length, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"] - 1, (n, length)).astype(np.int32)


def _accepting(params, share=0.0):
    """Every output projection of the main blocks and the MTP block zeroed,
    so that each predicts from its input alone, `W_eh = [I, share W_h]`
    and the MTP module's norms those of the main model's head: with
    `share` 0 the MTP module drafts what the main model will say of the
    draft's own token."""
    p = jax.tree.map(lambda a: a, params)
    p["mtp_enorm"]["weight"] = jnp.ones_like(p["mtp_enorm"]["weight"])
    p["mtp_norm"]["weight"] = p["norm"]["weight"]
    for name in [k for k in p if k[1:].isdigit()] + ["mtp"]:
        blk = p[name]
        blk["attn"]["o"]["weight"] = 0 * blk["attn"]["o"]["weight"]
        if "experts" in blk:
            blk["experts"]["down"] = 0 * blk["experts"]["down"]
            blk["experts"]["shared_down"]["weight"] *= 0
        else:
            blk["down"]["weight"] = 0 * blk["down"]["weight"]
    d = CFG["hidden_size"]
    p["mtp_eh"]["weight"] = jnp.concatenate(
        [jnp.eye(d), share * params["mtp_eh"]["weight"][d:]], axis=0)
    return p


# ---------------------------------------------------- the decode kernel
# runs of 4 blocks of 8 rows (32 keys); the slots' lengths, the table's
# width in blocks
KERNEL_CASES = {
    "crosses_at_a_runs_first_block": ((37, 5, 65), 12),
    "crosses_at_a_middle_block": ((43, 19, 83), 12),
    "crosses_at_a_runs_last_block": ((61, 30, 95), 12),
    "whole_runs": ((32, 64, 96), 12),
    "table_not_whole_runs": ((80, 73, 33), 10),
    "empty_slots": ((0, 45, 0, 20), 12),
}


@pytest.mark.parametrize("rows", [20, 40])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernel_is_its_xla_form(case, rows):
    """The block-table walk (Pallas, interpreted here) and the gathered
    context give the same sums: a slot's last run crossing its length at
    each kind of block or at none, a table the run does not divide, slots
    with nothing to read among others (the next slot's copies still
    start), blocks scattered over the pool, one query row a slot's head or
    two (a draft's)."""
    lengths, M = KERNEL_CASES[case]
    S, W, B, P = len(lengths), 128, 8, 64
    r = np.random.default_rng(rows)
    q = jnp.asarray(r.standard_normal((S, rows, W)), jnp.float32)
    pool = jnp.asarray(r.standard_normal((P, B, W)), jnp.float32)
    table = np.full((S, M), -1, np.int32)
    taken = r.permutation(P)
    for s, n in enumerate(lengths):
        used = -(-n // B)
        table[s, :used], taken = taken[:used], taken[used:]
    table = jnp.asarray(table)
    lens = jnp.asarray(lengths, jnp.int32)
    # scores of spread ~3, so that the sums, added in another order (a
    # run's tile, then the runs), stay a few units large
    got = paged_latent_attend(q, pool, lens, table, scale=0.25, v_width=64,
                              pages=4, interpret=True)
    want = _xla(q, pool, lens, table, 0.25, 64)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for s, n in enumerate(lengths):
        if n == 0:                              # nothing read
            assert float(jnp.abs(got[0][s]).max()) == 0.0
            assert float(got[2][s].max()) == 0.0
            assert float(got[1][s].max()) == np.float32(NEG_INF)


# ------------------------------------------- paged paths vs the reference
def test_paged_main_and_draft_logits_equal_the_reference(family, lm):
    """Prompt chunks that write the MTP rows of their pairs (the token
    after each handed in), then steps of two rows a slot (the last token
    and the next, as a verifying step has them) through the main model and
    the MTP module: every main logit equals the reference's `logits`, every
    draft logit its `mtp_logits`, teacher-forced."""
    model, params, w = lm
    N, T, C = 2, 40, 16
    tokens = _tokens(N, T)
    want = np.asarray(family.logits(w, CFG, jnp.asarray(tokens)))
    want_mtp = np.asarray(family.mtp_logits(w, CFG, jnp.asarray(tokens)))
    caches = model.make_paged_slot_caches(params, POOL_BLOCKS, BLOCK)
    table = np.full((N, 128 // BLOCK), -1, np.int32)
    for n in range(N):
        table[n, :T // BLOCK] = np.arange(T // BLOCK) * N + n
    table = jnp.asarray(table)
    full = jnp.full((N,), 2, jnp.int32)

    @jax.jit
    def prompt(caches, lo):
        pos = jnp.broadcast_to(lo + jnp.arange(C), (N, C))
        x, caches = model.paged_hidden(
            params, caches, jax.lax.dynamic_slice_in_dim(tokens, lo, C, 1),
            pos, table, jnp.full((N,), C, jnp.int32),
            follow=jax.lax.dynamic_slice_in_dim(tokens, lo + 1, C, 1))
        return model._logits(params, x), caches

    @jax.jit
    def step(caches, at):
        pos = jnp.broadcast_to(at + jnp.arange(2), (N, 2))
        x, caches = model.paged_hidden(
            params, caches, jax.lax.dynamic_slice_in_dim(tokens, at, 2, 1),
            pos, table, full, decode=True)
        g, caches = model.paged_draft(
            params, caches, x,
            jax.lax.dynamic_slice_in_dim(tokens, at + 1, 2, 1), pos, table,
            full)
        return model._logits(params, x), model._draft(params, g), caches

    with jax.default_matmul_precision("highest"):
        got, drafts = [], []
        for lo in range(0, 2 * C, C):
            out, caches = prompt(caches, lo)
            got.append(out)
        for at in range(2 * C, T - 2, 2):
            out, draft, caches = step(caches, at)
            got.append(out)
            drafts.append(draft)
    got = np.asarray(jnp.concatenate(got, axis=1))
    np.testing.assert_allclose(got, want[:, :got.shape[1]], atol=ATOL)
    drafts = np.asarray(jnp.concatenate(drafts, axis=1))
    np.testing.assert_allclose(drafts, want_mtp[:, 2 * C:2 * C + 6],
                               atol=ATOL)


# ----------------------------------------------- through the scheduler
@pytest.fixture(scope="module")
def entries(lm):
    model, params, _ = lm
    kw = dict(num_slots=SLOTS, max_seq_len=128, kv_block=BLOCK,
              kv_pool_blocks=POOL_BLOCKS, prefill_chunk=CHUNK,
              prefix_cache=True, prefix_cache_blocks=POOL_BLOCKS)
    return {spec: DecodeEntry(f"glm4-{spec}", model, params,
                              speculative_tokens=spec, **kw)
            for spec in (0, 1)}


def _serve(entry, params, submits, name, eos=-1):
    """`submits` = [(iteration at which it is sent, prompt, max_new)]
    through one scheduler of `entry` over `params` -> (tokens, stats)."""
    entry.params, entry._placed = params, None
    sched = DecodeScheduler(entry, name=name, start=False)
    reps, at = [None] * len(submits), 0
    while not all(r is not None and r.done() for r in reps):
        for i, (when, prompt, new) in enumerate(submits):
            if when == at:
                reps[i] = sched.submit(prompt, new, eos_id=eos)
        sched.step_once()
        at += 1
        assert at < 600
    out = [list(r.result(timeout=1)) for r in reps], sched.stats()
    sched.close(drain=False)
    return out


def _staggered(seed=5):
    """Four requests over one shared document and a longer prompt, sent
    while others decode: prefix hits, chunks carried by steps, a step
    always in flight."""
    doc = _tokens(1, 26, seed=seed)[0]
    prompts = [np.concatenate([doc, q]) for q in _tokens(3, 5, seed + 1)] \
        + [_tokens(1, 19, seed + 2)[0]]
    return list(zip((0, 4, 5, 9), prompts, (14, 17, 9, 12)))


@pytest.mark.parametrize("acceptance", ["all", "none", "some"])
def test_speculation_serves_the_greedy_tokens(lm, entries, acceptance):
    """The tokens of a drafting scheduler are those of the plain one, bit
    for bit, concurrently with staggered joins, a step in flight and
    prompt chunks that ride steps, whatever share of drafts is
    accepted."""
    _, params, _ = lm
    params = {"all": _accepting(params), "none": params,
              "some": _accepting(params, 0.5)}[acceptance]
    subs = _staggered()
    plain, st0 = _serve(entries[0], params, subs, f"plain-{acceptance}")
    spec, st1 = _serve(entries[1], params, subs, f"spec-{acceptance}")
    assert spec == plain
    assert st1["tokens"] == st0["tokens"] == sum(n for *_, n in subs)
    assert st1["prefill_carried"] >= 2 and st1["prefix_hits"] >= 12
    rate = st1["draft_accepted"] / st1["draft_rows"]
    assert st1["draft_rows"] >= 20 and st0["draft_rows"] == 0
    if acceptance == "all":
        assert rate == 1.0 and st1["steps"] < 0.7 * st0["steps"]
    elif acceptance == "none":
        assert rate < 0.1
    else:
        assert 0.1 < rate < 0.9


def test_served_tokens_lie_on_the_references_best_logit(family, lm,
                                                        entries):
    """Through the drafting scheduler on random weights: every served
    token is the reference's first choice along the served sequence."""
    _, params, w = lm
    subs = _staggered(seed=8)
    outs, _ = _serve(entries[1], params, subs, "spec-ref")
    gaps = family.served_gaps_of(family, CFG)
    for (_, prompt, new), got in zip(subs, outs):
        g = gaps(w, list(prompt), got, 64)
        assert g["finite"] and len(g["gap"]) == new
        assert float(g["gap"].max()) <= ATOL


@pytest.mark.parametrize("end", ["eos", "max_new"])
def test_a_sequence_ends_on_the_first_of_two_accepted_tokens(lm, entries,
                                                             end):
    """All drafts accepted: from its second step on, a step yields two
    tokens. An EOS, or the last token `max_new_tokens` allows, that is the
    first of two ends the sequence there; the second is not delivered."""
    _, params, _ = lm
    params = _accepting(params)
    prompt = _tokens(1, 11, seed=3)[0]
    free, st = _serve(entries[1], params, [(0, prompt, 12)], "spec-free")
    assert st["draft_accepted"] == st["draft_rows"] > 0
    # one token from the first step, then pairs: token 3 opens a pair
    stop = free[0][3]
    assert stop not in free[0][:3]
    kw = ({"eos": stop} if end == "eos" else {})
    new = 12 if end == "eos" else 4
    got, st = _serve(entries[1], params, [(0, prompt, new)],
                     f"spec-{end}", **kw)
    assert got[0] == free[0][:4]
    assert st["tokens"] == 4 and st["draft_accepted"] == 2


def test_a_shared_block_is_keyed_by_the_token_after_it(lm, entries):
    """The MTP row of the pair (h_j, t_j+1) lies at j, so a block's last
    row depends on the token after the block: two prompts that share a
    whole block and differ in the next token share no entry for it, and
    the second's rows there are its own."""
    model, params, _ = lm
    doc = _tokens(1, 8, seed=11)[0]                     # two whole blocks
    a = np.concatenate([doc, [7, 9, 4, 2, 6]])
    b = np.concatenate([doc, [8, 9, 4, 2, 6]])
    keys_a = PrefixCache.chain_keys(a, BLOCK, len(a) - 1, 1)
    keys_b = PrefixCache.chain_keys(b, BLOCK, len(b) - 1, 1)
    assert keys_a[0] == keys_b[0] and keys_a[1] != keys_b[1]
    assert PrefixCache.chain_keys(a, BLOCK, len(a) - 1)[1] == \
        PrefixCache.chain_keys(b, BLOCK, len(b) - 1)[1]
    entry = entries[1]
    entry.params, entry._placed = params, None
    sched = DecodeScheduler(entry, name="keyed", start=False)
    first = sched.submit(a, 4, eos_id=-1)
    while not first.done():
        sched.step_once()
    second = sched.submit(b, 4, eos_id=-1)
    sched.step_once()                           # admitted, prefilled
    assert sched.stats()["prefix_hits"] == 1    # block 0 alone
    req = next(r for r in sched._slots if r is not None)
    rows = sched._tables[req.slot, :2]
    mtp = np.asarray(sched._caches[model.num_layers]["latent"])
    got = mtp[rows].reshape(2 * BLOCK, -1)
    # the rows the module writes for b's own pairs, from nothing
    x = model._hidden(params, jnp.asarray(b[None, :2 * BLOCK + 1]))
    blk = model.children()["mtp"]
    u = model._mtp_input(params, x[:, :2 * BLOCK],
                         jnp.asarray(b[None, 1:2 * BLOCK + 1]))
    want = blk.children()["attn"]._latent(
        params["mtp"]["attn"], blk.normed(params["mtp"], u),
        jnp.arange(2 * BLOCK)[None])[0]
    np.testing.assert_allclose(got[:, :want.shape[-1]], np.asarray(want),
                               atol=1e-5)
    while not second.done():
        sched.step_once()
    sched.close(drain=False)
    alone, _ = _serve(entries[0], params, [(0, b, 4)], "keyed-alone")
    assert list(second.result(timeout=1)) == alone[0]


def test_registration_refuses_what_it_cannot_draft(lm):
    """A model without the draft piece, sampling, or more than one draft."""
    from bigdl_tpu.serve.decode import decode_demo_model
    demo, p, _ = decode_demo_model()
    with pytest.raises(TypeError, match="draft piece"):
        DecodeEntry("no-mtp", demo, p, num_slots=2, max_seq_len=64,
                    speculative_tokens=1)
    model, params, _ = lm
    with pytest.raises(ValueError, match="greedy"):
        DecodeEntry("sampled", model, params, num_slots=2, max_seq_len=64,
                    speculative_tokens=1, sampling=True)
    with pytest.raises(ValueError, match="one drafted token"):
        DecodeEntry("two", model, params, num_slots=2, max_seq_len=64,
                    speculative_tokens=2)
