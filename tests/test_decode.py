"""Iteration-level continuous batching for autoregressive decode
(ISSUE 14; serve/decode.py, docs/serving.md "Autoregressive decode").

The acceptance core is the PARITY ORACLE: N sequences decoded
concurrently through the engine — staggered joins, EOS retirement
mid-batch, slot reuse — are BIT-IDENTICAL to each sequence run alone
through `model.generate(kv_cache=True, beam_size=1)`. The scheduler's
iteration core (`step_once`) is driven synchronously (the batcher.py
fake-clock discipline) so join/leave timing is exact; thread coverage
rides the engine tests and the CLI smoke. Pad-poison bit-identity and
the zero-fresh-compiles-after-precompile counter assert round out the
ISSUE 14 acceptance criteria."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import observe
from bigdl_tpu.serve import (Closed, Overloaded, ServeEngine)
from bigdl_tpu.serve.decode import (DecodeEntry, DecodeScheduler,
                                    decode_demo_model, prefill_buckets)

VOCAB, EOS = 32, 1


@pytest.fixture(scope="module")
def lm():
    """One tiny GPT2LM shared by the whole module (compiles are the
    expensive part of these tests)."""
    model, params, state = decode_demo_model(
        vocab_size=VOCAB, n_positions=64, d_model=16, num_heads=4,
        num_layers=2, eos_id=EOS, seed=0)
    return model, params, state


@pytest.fixture(scope="module")
def entry(lm):
    """One precompiled DecodeEntry (4 slots x 32) shared by the
    synchronous scheduler tests — schedulers own their caches, the
    entry only owns params + executables."""
    model, params, _ = lm
    e = DecodeEntry("par", model, params, num_slots=4, max_seq_len=32,
                    prefill_chunk=8)
    e.precompile()
    return e


def oracle(lm, prompt, max_new, eos_id=EOS):
    """The isolated reference: generate(kv_cache=True) with beam 1."""
    model, params, state = lm
    seqs, _ = model.generate(params, state, prompt[None, :],
                             max_new_tokens=max_new, beam_size=1,
                             eos_id=eos_id, kv_cache=True)
    return np.asarray(seqs)[0, 0, prompt.shape[0]:]


def check_vs_oracle(lm, prompt, got, max_new, eos_id=EOS, want=None):
    """Engine output == oracle tokens (`want`, where the caller holds
    them already); the oracle pads with eos after a stop, the engine
    stops emitting — both checked."""
    if want is None:
        want = oracle(lm, prompt, max_new, eos_id)
    n = got.shape[0]
    np.testing.assert_array_equal(got, want[:n])
    if n < max_new:
        assert got[-1] == eos_id
        assert np.all(want[n:] == eos_id)


# ------------------------------------------------------------ primitives
def test_prefill_bucket_ladder():
    assert prefill_buckets(1) == (1,)
    assert prefill_buckets(8) == (1, 2, 4, 8)
    assert prefill_buckets(6) == (1, 2, 4, 6)
    with pytest.raises(ValueError):
        prefill_buckets(0)


def test_slot_cached_attend_bitwise_matches_scalar_start():
    """Per-row starts == per-row scalar cached_attend calls, bitwise —
    including the grouped-KV (GQA) width."""
    from bigdl_tpu.nn.attention import cached_attend, slot_cached_attend
    r = np.random.RandomState(0)
    N, H, Hc, T, hd, L = 3, 4, 2, 2, 8, 16
    q = jnp.asarray(r.randn(N, H, T, hd).astype(np.float32))
    k = jnp.asarray(r.randn(N, T, Hc, hd).astype(np.float32))
    v = jnp.asarray(r.randn(N, T, Hc, hd).astype(np.float32))
    ck = jnp.asarray(r.randn(N, L, Hc, hd).astype(np.float32))
    cv = jnp.asarray(r.randn(N, L, Hc, hd).astype(np.float32))
    starts = np.array([0, 5, 11], np.int32)
    positions = jnp.asarray(starts[:, None] + np.arange(T)[None, :],
                            dtype=jnp.int32)
    a, nck, ncv = slot_cached_attend(q, k, v, ck, cv, positions)
    for i, s in enumerate(starts):
        ai, cki, cvi = cached_attend(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                     ck[i:i + 1], cv[i:i + 1], int(s))
        np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(ai[0]))
        np.testing.assert_array_equal(np.asarray(nck[i]),
                                      np.asarray(cki[0]))
        np.testing.assert_array_equal(np.asarray(ncv[i]),
                                      np.asarray(cvi[0]))


def test_rotary_embedding_per_row_positions():
    """(B, T) positions row-match independent 1-D-position calls."""
    from bigdl_tpu.nn.attention import rotary_embedding
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(3, 2, 4, 8).astype(np.float32))
    pos = np.array([[0, 1, 2, 3], [7, 8, 9, 10], [3, 4, 5, 6]],
                   np.int32)
    out = rotary_embedding(x, 10000.0, jnp.asarray(pos))
    for i in range(3):
        ref = rotary_embedding(x[i:i + 1], 10000.0,
                               jnp.asarray(pos[i]))
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(ref[0]))


def test_greedy_generate_matches_beam1(lm):
    """nn/recurrent.greedy_generate == generate(beam_size=1) token
    streams (the bench baseline's single-call form)."""
    model, params, state = lm
    from bigdl_tpu.nn.recurrent import greedy_generate
    r = np.random.RandomState(2)
    prompt = r.randint(2, VOCAB, (2, 5)).astype(np.int32)
    P, new = 5, 8
    H = model.children()["h0"].attn.num_heads
    hd = model.d_model // H

    def make_caches():
        z = lambda: jnp.zeros((2, P + new, H, hd), jnp.float32)
        return (tuple(z() for _ in range(model.num_layers)),
                tuple(z() for _ in range(model.num_layers)))

    def fwd(tokens, caches, start):
        return model._cached_forward(params, tokens, caches, start)

    seqs = greedy_generate(fwd, make_caches, jnp.asarray(prompt),
                           max_new_tokens=new, eos_id=EOS)
    want, _ = model.generate(params, state, jnp.asarray(prompt),
                             max_new_tokens=new, beam_size=1,
                             eos_id=EOS, kv_cache=True)
    np.testing.assert_array_equal(np.asarray(seqs),
                                  np.asarray(want)[:, 0])


# ------------------------------------------------ the parity acceptance
def _staggered_run(entry, submits, poison=False, name="stag"):
    """Drive a synchronous scheduler through a staggered schedule:
    `submits` = [(step_at_which_to_submit, prompt, max_new, eos[, sampling
    keywords])]. Returns the per-request generated arrays (submission
    order)."""
    sched = DecodeScheduler(entry, name=name, start=False)
    replies = [None] * len(submits)
    step = 0
    while True:
        for i, (at, prompt, max_new, eos, *kw) in enumerate(submits):
            if at == step:
                replies[i] = sched.submit(prompt, max_new, eos_id=eos,
                                          **(kw[0] if kw else {}))
        worked = sched.step_once()
        if poison:
            # poison every FREE cache region (the unallocated pool
            # blocks): stale content from retired sequences can never
            # leak into live ones
            free = list(sched._pool._free)
            if free:
                # blocks lie along the pool's block axis
                from bigdl_tpu.nn.attention import PAGED_POOL_BLOCK_AXIS
                idx = ((slice(None),) * PAGED_POOL_BLOCK_AXIS
                       + (jnp.asarray(free),))
                sched._caches = jax.tree.map(
                    lambda a: a.at[idx].set(1e30), sched._caches)
        step += 1
        if not worked and all(r is not None and r.done()
                              for r in replies):
            break
        assert step < 500, "scheduler failed to converge"
    out = [r.result(timeout=1) for r in replies]
    _staggered_run.stats = sched.stats()
    sched.close(drain=False)
    return out


# (join step, prompt length, max_new) of the staggered schedule
STAGGERED = [(0, 3, 10), (0, 7, 10), (1, 12, 6), (3, 5, 10),
             (6, 9, 8), (8, 4, 10), (9, 6, 10)]


@pytest.fixture(scope="module")
def staggered_submits(lm):
    """7 requests through 4 slots, staggered joins; request 0's eos is
    ENGINEERED to be a token its own oracle emits by step 3, so an EOS
    retirement mid-batch (slot freed + reused) is guaranteed. Built once
    for the module: the six users each paid that oracle run."""
    r = np.random.RandomState(7)
    subs = [[at, r.randint(2, VOCAB, p).astype(np.int32), new, EOS]
            for at, p, new in STAGGERED]
    pre = oracle(lm, subs[0][1], subs[0][2], eos_id=EOS)
    subs[0][3] = int(pre[2])          # retire request 0 at step <= 3
    return [tuple(s) for s in subs]


@pytest.fixture(scope="module")
def staggered_outs(entry, staggered_submits):
    """The schedule decoded once through the shared entry."""
    return _staggered_run(entry, staggered_submits)


@pytest.fixture(scope="module")
def staggered_oracle(lm, staggered_submits):
    """`want(i)`: request i of the schedule decoded alone, run once and
    kept (each isolated oracle compiles at its own prompt length, about
    3 s apiece)."""
    kept = {}

    def want(i):
        if i not in kept:
            _, prompt, max_new, eos = staggered_submits[i]
            kept[i] = oracle(lm, prompt, max_new, eos_id=eos)
        return kept[i]
    return want


@pytest.mark.parametrize("i", range(len(STAGGERED)))
def test_staggered_joins_eos_retirement_bit_identical(
        lm, staggered_submits, staggered_outs, staggered_oracle, i):
    """ISSUE 14 acceptance: concurrent iteration-level decode with
    staggered joins/leaves and EOS retirement mid-batch is BIT-IDENTICAL
    to each sequence decoded alone via generate(kv_cache=True). One case
    a request: each pays its own oracle run."""
    _, prompt, max_new, eos = staggered_submits[i]
    check_vs_oracle(lm, prompt, staggered_outs[i], max_new, eos_id=eos,
                    want=staggered_oracle(i))


def test_staggered_schedule_retires_on_eos_mid_batch(staggered_submits,
                                                     staggered_outs):
    """The seeded schedule actually exercises EOS retirement mid-batch
    (slots freed and re-used: 7 requests through 4 slots)."""
    stopped_early = sum(got.shape[0] < max_new for (_, _, max_new, _), got
                        in zip(staggered_submits, staggered_outs))
    assert stopped_early >= 1
    assert sum(o.shape[0] for o in staggered_outs) > 0


def test_cache_pad_poison_bit_identity(entry, staggered_submits,
                                       staggered_outs):
    """Poisoning every free pool block (1e30) between iterations
    changes NOTHING: inactive rows never touch the pool and masked
    entries contribute exactly zero — stale KV can never leak across
    slot reuse."""
    poisoned = _staggered_run(entry, staggered_submits, poison=True)
    for a, b in zip(staggered_outs, poisoned):
        np.testing.assert_array_equal(a, b)


def test_chunked_prefill_buckets_and_long_prompt(lm, entry):
    """A prompt longer than the prefill chunk streams through multiple
    length-bucketed chunks and still decodes bit-identically."""
    r = np.random.RandomState(9)
    prompt = r.randint(2, VOCAB, 21).astype(np.int32)   # 20 > chunk 8
    outs = _staggered_run(entry, [(0, prompt, 8, EOS)])
    check_vs_oracle(lm, prompt, outs[0], 8)


# ----------------------------------- a prefill call over the streaming rows
def _tiny_hybrid():
    """A two-layer OlmoHybridLM (one linear layer with state by slot, one
    full layer over the pool) and its parameters."""
    from bigdl_tpu.interop.olmo_hybrid import FULL, LINEAR, OlmoHybridLM
    model = OlmoHybridLM(
        VOCAB, 16, 4, 24, [LINEAR, FULL],
        dict(num_heads=4, key_dim=8, value_dim=12, conv_kernel=4,
             allow_neg_eigval=True), 32, eos_id=EOS)
    return model, model.init(jax.random.PRNGKey(5))[0]


@pytest.mark.parametrize("family", ["GPT2LM", "OlmoHybridLM"])
def test_one_row_prefill_equals_the_all_rows_prefill_of_that_row(lm, family):
    """The program over the rows of the slots that stream, given one slot,
    leaves in that slot's blocks and state what the `num_slots`-row program
    leaves there with only that row active (a padded tail, a chunk that
    continues a prompt), and every other slot's state and every other block
    bit for bit as they were: it never read them."""
    model, params = lm[:2] if family == "GPT2LM" else _tiny_hybrid()
    e = DecodeEntry(f"row{family}", model, params, num_slots=4,
                    max_seq_len=32, prefill_chunk=8, kv_block=8)
    r = np.random.RandomState(2)
    dirty = jax.tree.map(
        lambda a: jnp.asarray(r.randn(*a.shape), a.dtype), e.make_caches())
    s, C, fed, n = 2, 8, 8, 5
    table = np.full((4, e.blocks_per_slot), -1, np.int32)
    table[:, :2] = np.arange(8).reshape(4, 2)
    tokens = np.zeros((4, C), np.int32)
    positions = np.zeros((4, C), np.int32)
    lengths = np.zeros((4,), np.int32)
    tokens[s, :n] = r.randint(2, VOCAB, n)
    positions[s] = fed + np.arange(C)
    lengths[s] = n
    every = e.run_prefill(dirty, tokens, positions, table, lengths)
    one = e.run_prefill(dirty, tokens[[s]], positions[[s]], table[[s]],
                        lengths[[s]], np.asarray([s], np.int32))
    mask = jax.tree.leaves(e._slot_mask) if e.slot_state else None
    by_slot = 0
    for i, (was, a, b) in enumerate(zip(*map(jax.tree.leaves,
                                             (dirty, every, one)))):
        was, a, b = (np.asarray(x) for x in (was, a, b))
        np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-6)
        assert not np.array_equal(b, was)            # the chunk was written
        if mask is not None and mask[i]:
            by_slot += 1
            others = [j for j in range(4) if j != s]
            np.testing.assert_array_equal(b[others], was[others])
        else:
            # the pool: only lanes 0..n-1 of the slot's second block moved
            blk = int(table[s, 1])
            rest = np.delete(b, blk, axis=1), np.delete(was, blk, axis=1)
            np.testing.assert_array_equal(*rest)
            np.testing.assert_array_equal(b[:, blk, n:], was[:, blk, n:])
    assert by_slot == (2 if family == "OlmoHybridLM" else 0)


def test_the_all_rows_prefill_call_comes_from_half_the_slots_on(lm):
    model, params, _ = lm
    of8 = DecodeEntry("rule8", model, params, num_slots=8, max_seq_len=32,
                      prefill_chunk=8)
    assert [of8.prefill_rows(8, k) for k in range(1, 9)] == [1] * 3 + [8] * 5
    assert of8.prefill_rows(4, 8) == 1        # a short bucket: always by row
    alone = DecodeEntry("rule1", model, params, num_slots=1, max_seq_len=32,
                        prefill_chunk=8)
    assert alone.prefill_rows(8, 1) == 1


@pytest.mark.parametrize("picked,want", [
    ([1], (1, 1)), ([3, 5], (2, 2)), ([1, 2], (1, 4)),
    ([1, 2, 4, 6], (1, 4))],
    ids=["one", "two-in-a-short-bucket", "half-the-slots", "every-slot"])
def test_prefill_calls_follow_the_slots_that_stream(
        lm, entry, staggered_submits, staggered_oracle, picked, want):
    """The slots that stream a chunk of one bucket in one iteration get a
    one-row call each; from half the slots on, in the full chunk's bucket,
    the one `num_slots`-row call. Calls made and rows computed are
    counted, and the tokens are the isolated oracle's either way."""
    sched = DecodeScheduler(entry, name="rows" + "".join(map(str, picked)),
                            start=False)
    replies = [sched.submit(staggered_submits[i][1], staggered_submits[i][2],
                            eos_id=staggered_submits[i][3]) for i in picked]
    sched.step_once()
    calls, rows = _counter(sched, "prefill_calls"), _counter(
        sched, "prefill_rows")
    assert (calls, rows) == want
    outs = _run_until_done(sched, replies)
    stats = sched.stats()
    assert stats["prefill_calls"] == _counter(sched, "prefill_calls") >= calls
    assert stats["prefill_rows"] == _counter(sched, "prefill_rows")
    assert stats["prefill_tokens"] == sum(
        len(staggered_submits[i][1]) - 1 for i in picked)
    for i, got in zip(picked, outs):
        _, prompt, max_new, eos = staggered_submits[i]
        check_vs_oracle(lm, prompt, got, max_new, eos_id=eos,
                        want=staggered_oracle(i))
    sched.close(drain=False)


def test_submit_validation_and_admission(entry):
    sched = DecodeScheduler(entry, name="adm", max_queue=2, start=False)
    with pytest.raises(ValueError):
        sched.submit(np.zeros((0,), np.int32), 4)
    with pytest.raises(ValueError):
        sched.submit([2, 3], 0)
    with pytest.raises(ValueError):               # budget over the cache
        sched.submit(np.arange(2, 30, dtype=np.int32), 32)
    sched.submit([2, 3], 2)
    sched.submit([2, 3], 2)
    with pytest.raises(Overloaded):               # queue at bound
        sched.submit([2, 3], 2)
    shed0 = observe.registry().counter("serve/shed").value
    assert shed0 >= 1
    sched.close(drain=False)
    with pytest.raises(Closed):
        sched.submit([2, 3], 2)


def _run_until_done(sched, replies, limit=300):
    steps = 0
    while not all(r.done() for r in replies):
        sched.step_once()
        steps += 1
        assert steps < limit, "scheduler failed to converge"
    return [r.result(timeout=1) for r in replies]


def _counter(sched, name):
    return observe.registry().counter(
        f"serve/{sched.name}/decode/{name}").value


def test_decode_step_is_one_host_sync(entry, monkeypatch):
    """The pipeline's invariant: over K iterations with continuing slots
    there are exactly K jax.device_get calls (the next-token fetch), and
    each comes AFTER the dispatch of the step that follows the one it
    fetches: the host never waits on the device with nothing enqueued
    behind."""
    sched = DecodeScheduler(entry, name="sync", start=False)
    replies = [sched.submit([2, 3], 12, eos_id=-1) for _ in range(3)]
    sched.step_once()         # admit, prefill, first step: nothing to fetch
    assert sched._in_flight is not None
    assert [len(r.generated) for r in sched._in_flight.rows] == [0, 0, 0]
    calls = []
    real_get, real_run = jax.device_get, entry.run_decode

    def counting_get(v):
        calls.append("get")
        return real_get(v)

    def counting_run(*a):
        calls.append("run")
        return real_run(*a)
    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(entry, "run_decode", counting_run)
    K = 8
    for _ in range(K):
        assert sched.step_once()
    monkeypatch.undo()
    assert calls == ["run", "get"] * K
    assert all(len(r.generated) == K for r in sched._in_flight.rows)
    assert _counter(sched, "steps_ahead") == K
    for got in _run_until_done(sched, replies):
        assert got.shape == (12,)
    assert sched._in_flight is None
    sched.close(drain=False)


LEAVES = ("admit", "await_taker", "prefill", "dispatch", "fetch", "deliver")


def test_iteration_is_tiled_by_its_leaf_spans(entry, monkeypatch):
    """Under the fake clock: every iteration is one `serve/decode/iteration`
    span whose leaves cover at least 99 % of it (summed over the run) when
    the fetch waits 4 ms on the device, as a chip's 12-30 ms step makes it,
    with what lies between the leaves under 100 us an iteration; `dispatch`
    names the program it enqueued, and `host_ms` + the waits in `fetch` and
    `await_taker` = `iteration_ms`, exactly."""
    import time
    from bigdl_tpu.observe import trace as obs_trace
    fetch = DecodeScheduler._fetch

    def device_step(step):
        time.sleep(0.004)
        return fetch(step)
    monkeypatch.setattr(DecodeScheduler, "_fetch", staticmethod(device_step))
    clock = [0.0]
    sched = DecodeScheduler(entry, name="tiled", start=False,
                            clock=lambda: clock[0])
    replies = [sched.submit([2, 3, 4, 5, 6], 10, eos_id=-1)
               for _ in range(3)]
    ring = obs_trace.get_tracer()
    ring.clear()
    _run_until_done(sched, replies)
    sched.close(drain=False)
    evs = [e for e in ring.events() if e[0] == "X"
           and e[1].startswith("serve/decode/")]
    iters = [e for e in evs if e[1] == "serve/decode/iteration"]
    leaves = [e for e in evs if e[1].rsplit("/", 1)[1] in LEAVES]
    assert len(iters) >= 10
    covered, total = sum(e[5] for e in leaves), sum(e[5] for e in iters)
    assert 0.99 * total <= covered <= total
    assert (total - covered) / len(iters) < 100e3
    for it in iters:                     # every leaf inside an iteration
        inner = [e for e in leaves if it[4] <= e[4]
                 and e[4] + e[5] <= it[4] + it[5]]
        assert {e[1].rsplit("/", 1)[1] for e in inner} >= {
            "admit", "await_taker", "prefill", "dispatch", "deliver"}
    programs = {e[6].get("program") for e in evs
                if e[1] == "serve/decode/dispatch"} - {None}
    assert programs and programs <= {entry.step_program(False),
                                     entry.step_program(True)}
    assert entry.step_program(False) == "jit__step"
    snap = observe.registry().snapshot()["histograms"]
    it_ms = snap["serve/tiled/decode/iteration_ms"]
    host_ms = snap["serve/tiled/decode/host_ms"]
    waits = sum(e[5] for e in evs if e[1] in ("serve/decode/fetch",
                                               "serve/decode/await_taker"))
    assert it_ms["count"] == host_ms["count"] == len(iters)
    assert it_ms["sum"] == pytest.approx(sum(e[5] for e in iters) * 1e-6)
    assert host_ms["sum"] + waits * 1e-6 == pytest.approx(it_ms["sum"])


@pytest.mark.parametrize("arrives", [True, False])
def test_free_slot_waits_for_its_taker_while_a_step_is_in_flight(
        entry, monkeypatch, arrives):
    """With a slot free, nobody queued and a step in flight, the iteration
    waits for a request until that step is done: one that arrives meanwhile
    is admitted in the same iteration and its prompt chunk rides the decode
    step that iteration enqueues, no program of its own, and that step
    its first token; if none arrives, the step's end lets the iteration go
    on."""
    import threading
    sched = DecodeScheduler(entry, name=f"taker-{arrives}", start=False)
    first = sched.submit([2, 3], 6, eos_id=-1)
    sched.step_once()
    assert sched._in_flight is not None and sched.active_slots == 1
    calls = []
    real_prefill, real_run = entry.run_prefill, entry.run_decode
    monkeypatch.setattr(entry, "run_prefill", lambda *a: (
        calls.append("prefill"), real_prefill(*a))[1])
    monkeypatch.setattr(entry, "run_decode", lambda *a: (
        calls.append("decode"), real_run(*a))[1])
    polls = []

    def done(step):            # a device that stays busy: for ever where a
        polls.append(1)        # taker comes, else for three looks
        return not arrives and len(polls) > 3
    monkeypatch.setattr(DecodeScheduler, "_done", staticmethod(done))
    reply = []

    def taker():
        reply.append(sched.submit([2, 3, 4, 5], 3, eos_id=-1))
    timer = threading.Timer(0.05, taker)
    if arrives:
        timer.start()
    sched.step_once()
    timer.cancel()
    monkeypatch.undo()
    if arrives:
        assert calls == ["decode"] and sched.active_slots == 2
        assert _counter(sched, "prefill_carried") == 1
        # and completed its prompt: the same step computes its first token
        assert len(sched._in_flight.rows) == 2
        assert _run_until_done(sched, reply)[0].shape == (3,)
    else:
        assert calls == ["decode"] and len(polls) == 4
    assert _run_until_done(sched, [first])[0].shape == (6,)
    sched.close(drain=False)


@pytest.mark.parametrize("how", ["eos", "cancel"])
def test_row_in_flight_of_a_sequence_that_ended_is_dropped(lm, entry, how):
    """A sequence that ends by value (EOS), or is cancelled, while its row
    of the next step is in flight: the reply holds no token past its end,
    `rows_dropped` gains 1, its blocks return to the pool, and the request
    admitted to that slot next decodes bit-identically to itself alone."""
    sched = DecodeScheduler(entry, name=f"drop-{how}", start=False)
    r = np.random.RandomState(21)
    prompt = r.randint(2, VOCAB, 6).astype(np.int32)
    alone, = _staggered_run(entry, [(0, prompt, 10, -1)])
    if how == "eos":
        eos = int(alone[3])
        stop = int(np.argmax(alone == eos)) + 1       # its first occurrence
        rep = sched.submit(prompt, 10, eos_id=eos)
        got = _run_until_done(sched, [rep])[0]
        np.testing.assert_array_equal(got, alone[:stop])
        assert sched._in_flight is not None      # the stray row, unfetched
    else:
        rep = sched.submit(prompt, 10, eos_id=-1)
        while rep._tokens.qsize() < 3:
            sched.step_once()
        assert sched._in_flight is not None
        rep.cancel()
        sched.step_once()             # sweeps, then fetches the stray row
        got = rep.result(timeout=1)
        np.testing.assert_array_equal(got, alone[:3])
        assert sched._in_flight is None
    p = sched._pool                   # its blocks are back, all of them
    assert p.free == p.total and p.live == 0 and p.reserved == 0
    follower = r.randint(2, VOCAB, 9).astype(np.int32)
    rep2 = sched.submit(follower, 8)  # the same slot, the same blocks
    assert sched._queue[0].slot is None
    sched.step_once()
    assert sched._slots[0] is not None and sched._slots[0].reply is rep2
    check_vs_oracle(lm, follower, _run_until_done(sched, [rep2])[0], 8)
    assert _counter(sched, "rows_dropped") == 1
    assert sched.stats()["rows_dropped"] == 1
    assert p.free == p.total and p.live == 0 and p.reserved == 0
    sched.close(drain=False)


@pytest.mark.parametrize("how", ["drain", "close"])
def test_drain_and_close_deliver_the_last_steps_tokens(entry, how):
    """A step in flight is work: the thread neither sleeps nor exits before
    the last step's tokens are fetched and delivered; and over 3 concurrent
    sequences of 16 tokens nearly every step is enqueued ahead."""
    sched = DecodeScheduler(entry, name=f"last-{how}", start=True)
    r = np.random.RandomState(31)
    prompts = [r.randint(2, VOCAB, n).astype(np.int32) for n in (3, 5, 4)]
    want = _staggered_run(entry, [(0, p, 16, -1) for p in prompts])
    replies = [sched.submit(p, 16, eos_id=-1) for p in prompts]
    if how == "drain":
        assert sched.drain(timeout=60)
        with pytest.raises(Closed):
            sched.submit([2, 3], 2)
    sched.close(drain=True, timeout=60)
    for w, rep in zip(want, replies):
        assert w.shape == (16,)
        np.testing.assert_array_equal(rep.result(timeout=1), w)
    assert sched._in_flight is None
    st = sched.stats()
    assert st["rows_dropped"] == 0
    assert st["steps_ahead"] / st["steps"] >= 0.9


def test_failed_fetch_fails_every_reply_with_the_real_error(entry,
                                                            monkeypatch):
    """An exception at the fetch of a step in flight reaches every active
    and queued reply as itself, and the thread ends."""
    sched = DecodeScheduler(entry, name="boom", start=False)
    replies = [sched.submit([2, 3, 4], 8, eos_id=-1) for _ in range(5)]
    sched.step_once()
    assert sched._in_flight is not None and sched.queued == 1

    def broken(step):
        raise RuntimeError("fetch failed")
    monkeypatch.setattr(DecodeScheduler, "_fetch", staticmethod(broken))
    thread = sched.start()._thread
    for rep in replies:
        with pytest.raises(RuntimeError, match="fetch failed"):
            rep.result(timeout=30)
    thread.join(timeout=30)
    assert not thread.is_alive() and sched._in_flight is None


# ---------------------------- one program an iteration: the carrying step
@pytest.mark.parametrize("chunk,want", [
    (8, (8,)), (64, (64,)), (96, (64, 96)), (256, (64, 128, 256))])
def test_carried_buckets_are_the_ladders_upper_end(chunk, want):
    from bigdl_tpu.serve.decode import carried_buckets
    assert carried_buckets(prefill_buckets(chunk)) == want


def _family(lm, family):
    """A demo model of a served family and its parameters."""
    if family == "GPT2LM":
        return lm[:2]
    if family == "OlmoHybridLM":
        return _tiny_hybrid()
    from bigdl_tpu.interop.huggingface import LlamaLM
    model = LlamaLM(VOCAB, 16, 4, 2, 32, 2, eos_id=EOS)
    return model, model.init(jax.random.PRNGKey(1))[0]


@pytest.fixture(scope="module")
def family_entries(lm):
    """family -> its entry with the sampling step (which serves greedy
    requests too), built once: the cases share its programs."""
    kept = {}

    def entry_of(family):
        if family not in kept:
            model, params = _family(lm, family)
            kept[family] = DecodeEntry(
                f"carry{family}", model, params, num_slots=4,
                max_seq_len=32, prefill_chunk=8, kv_block=8, sampling=True)
        return kept[family]
    return entry_of


# (join step, prompt length, max_new): every prompt after the first streams
# while another slot decodes, one of them in three chunks
CARRIED = [(0, 3, 12), (1, 12, 6), (2, 21, 6), (3, 5, 8), (5, 9, 6)]


@pytest.mark.parametrize("mode", ["greedy", "sampled"])
@pytest.mark.parametrize("family", ["GPT2LM", "LlamaLM", "OlmoHybridLM"])
def test_carrying_iteration_gives_each_sequence_its_own_tokens(
        lm, family_entries, family, mode):
    """Staggered requests whose prompt chunks ride the decode steps of the
    slots that decode get the tokens each gets alone (when nothing decodes
    beside it, its chunks are calls of their own), greedy and sampled; for
    GPT-2, greedy, those of `generate` too."""
    e = family_entries(family)
    assert e.carried == (8,)
    r = np.random.RandomState(13)
    subs = [(at, r.randint(2, VOCAB, p).astype(np.int32), new, -1,
             {} if mode == "greedy" else dict(
                 temperature=1.5, top_k=12, top_p=0.9, seed=40 + i))
            for i, (at, p, new) in enumerate(CARRIED)]
    outs = _staggered_run(e, subs, name=f"{family}-{mode}")
    stats = _staggered_run.stats
    assert stats["prefill_carried"] >= 6
    assert stats["prefill_tokens"] == sum(len(s[1]) - 1 for s in subs)
    for sub, got in zip(subs, outs):
        alone, = _staggered_run(e, [(0,) + sub[1:]], name=f"{family}-alone")
        assert _staggered_run.stats["prefill_carried"] == 0
        np.testing.assert_array_equal(got, alone)
        if (family, mode) == ("GPT2LM", "greedy"):
            check_vs_oracle(lm, sub[1], got, sub[2], eos_id=-1)


@pytest.fixture(scope="module")
def entry8(lm):
    """8 slots, so that two and three slots streaming a full chunk are under
    half of them (the burst rule's switch)."""
    model, params, _ = lm
    return DecodeEntry("rule", model, params, num_slots=8, max_seq_len=32,
                       prefill_chunk=8)


def _spied(monkeypatch, entry):
    """The programs the scheduler enqueues, in order: ("prefill", rows) a
    prefill call, ("step", slot its chunk is of or None) a decode step."""
    calls = []
    real_prefill, real_run = entry.run_prefill, entry.run_decode

    def prefill(caches, tokens, *rest):
        calls.append(("prefill", tokens.shape[0]))
        return real_prefill(caches, tokens, *rest)

    def run(caches, tokens, *rest):
        calls.append(("step", int(rest[-1][0]) if len(rest) > 3 else None))
        return real_run(caches, tokens, *rest)
    monkeypatch.setattr(entry, "run_prefill", prefill)
    monkeypatch.setattr(entry, "run_decode", run)
    return calls


@pytest.mark.parametrize("streaming", [2, 3])
def test_an_iteration_that_decodes_runs_one_program(lm, entry8, monkeypatch,
                                                    streaming):
    """Slots streaming prompts while another decodes: every iteration
    enqueues ONE program, the decode step, which carries the chunk of the
    slot admitted first; the others keep their `fed` and ride later steps,
    first come first served; no prefill call is made; `prefill_carried`
    counts each advance, like `prefill_calls`, `_rows` and `_tokens`."""
    sched = DecodeScheduler(entry8, name=f"one{streaming}", start=False)
    r = np.random.RandomState(17)
    first = sched.submit(r.randint(2, VOCAB, 9).astype(np.int32), 20,
                         eos_id=-1)
    sched.step_once()                 # alone: its chunk a call, then a step
    assert _counter(sched, "prefill_calls") == 1
    calls = _spied(monkeypatch, entry8)
    # prompts of 17 tokens: two full chunks each
    prompts = [r.randint(2, VOCAB, 17).astype(np.int32)
               for _ in range(streaming)]
    reps = [sched.submit(p, 4, eos_id=-1) for p in prompts]
    reqs = list(sched._queue)
    # its slot, chunk by chunk
    want = [i + 1 for i in range(streaming) for _ in range(2)]
    for n, slot in enumerate(want):
        before = [q.fed for q in reqs]
        sched.step_once()
        assert calls[-1] == ("step", slot) and len(calls) == n + 1
        moved = [q.fed - b for q, b in zip(reqs, before)]
        assert [m > 0 for m in moved] == [q.slot == slot for q in reqs]
        assert _counter(sched, "prefill_carried") == n + 1
    assert _counter(sched, "prefill_calls") == 1 + len(want)
    assert _counter(sched, "prefill_rows") == 1 + len(want)
    assert _counter(sched, "prefill_tokens") == 8 + sum(
        len(p) - 1 for p in prompts)
    assert sched.stats()["prefill_carried"] == len(want)
    outs = _run_until_done(sched, [first] + reps)
    assert all(c[0] == "step" for c in calls)
    monkeypatch.undo()
    for p, got in zip(prompts, outs[1:]):
        check_vs_oracle(lm, p, got, 4, eos_id=-1)
    sched.close(drain=False)


@pytest.mark.parametrize("case,want", [
    ("no-decode-row", [("prefill", 1), ("prefill", 1), ("step", None)]),
    ("burst", [("prefill", 8), ("step", None)])])
def test_prefill_calls_where_the_rule_leaves_them(lm, entry8, monkeypatch,
                                                  case, want):
    """An iteration with no decode row still runs a one-row call for every
    streaming slot, then a step over the prompts they completed; a burst,
    from half the slots on streaming a full chunk, still takes the one
    `num_slots`-row call and a plain step, beside slots that decode too."""
    sched = DecodeScheduler(entry8, name=f"left-{case}", start=False)
    r = np.random.RandomState(19)
    if case == "burst":
        sched.submit(r.randint(2, VOCAB, 9).astype(np.int32), 8, eos_id=-1)
        sched.step_once()
        n, length = 4, 17
    else:
        n, length = 2, 9
    calls = _spied(monkeypatch, entry8)
    prompts = [r.randint(2, VOCAB, length).astype(np.int32)
               for _ in range(n)]
    reps = [sched.submit(p, 3, eos_id=-1) for p in prompts]
    sched.step_once()
    assert calls == want
    assert _counter(sched, "prefill_carried") == 0
    monkeypatch.undo()
    for p, got in zip(prompts, _run_until_done(sched, reps)):
        check_vs_oracle(lm, p, got, 3, eos_id=-1)
    sched.close(drain=False)


@pytest.mark.parametrize("family", ["GPT2LM", "OlmoHybridLM"])
def test_a_chunk_under_the_smallest_carried_bucket_is_padded_and_masked(
        lm, family):
    """The ladder of carried buckets is the upper end of the ladder of
    buckets (here the full chunk alone): a shorter row rides padded up to
    the smallest of them and masked by its length, and leaves in the pool
    and in the state resident by slot what the one-row call of its own,
    exact bucket leaves, every other block and slot as it was."""
    model, params = _family(lm, family)
    e = DecodeEntry(f"pad{family}", model, params, num_slots=4,
                    max_seq_len=32, prefill_chunk=8, kv_block=8)
    assert e.buckets == (1, 2, 4, 8) and e.carried == (8,)
    r = np.random.RandomState(23)
    dirty = jax.tree.map(
        lambda a: jnp.asarray(r.randn(*a.shape), a.dtype), e.make_caches())
    s, fed, n, exact, C = 2, 8, 2, 2, 8
    table = np.full((4, e.blocks_per_slot), -1, np.int32)
    table[:, :2] = np.arange(8).reshape(4, 2)

    def chunk(bucket):
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n] = [5, 9]
        return (tokens, fed + np.arange(bucket, dtype=np.int32)[None],
                table[[s]], np.asarray([n], np.int32),
                np.asarray([s], np.int32))
    call = e.run_prefill(dirty, *chunk(exact))
    idle = np.zeros((4,), np.int32)
    _, rode = e.run_decode(dirty, idle, idle, np.zeros((4,), bool), table,
                           *chunk(C))
    for was, a, b in zip(*map(jax.tree.leaves, (dirty, call, rode))):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-6, atol=1e-6)
        assert not np.array_equal(np.asarray(b), np.asarray(was))
        # what the call left as it was, the carried chunk did too
        same = np.asarray(a) == np.asarray(was)
        np.testing.assert_array_equal(np.asarray(b)[same],
                                      np.asarray(was)[same])


@pytest.mark.parametrize("family", ["GPT2LM", "OlmoHybridLM"])
def test_the_step_that_carries_a_prompts_last_chunk_decodes_its_slot_too(
        lm, family):
    """The chunk's part goes first: the slot whose prompt the carried chunk
    completes is a row of the same step, its last prompt token at the
    position behind the chunk (in the same block of the pool, after the
    chunk's update of the state). Tokens and caches are those of the one-row
    call followed by the plain step, for that slot and for one that decodes
    beside it."""
    model, params = _family(lm, family)
    e = DecodeEntry(f"both{family}", model, params, num_slots=4,
                    max_seq_len=32, prefill_chunk=8, kv_block=8)
    r = np.random.RandomState(41)
    caches = e.make_caches()
    table = np.full((4, e.blocks_per_slot), -1, np.int32)
    table[:, :2] = np.arange(8).reshape(4, 2)
    # slot 0 decodes at position 3 (its prompt went in before); slot 2 has
    # 9 tokens in and streams its last 5, then its last prompt token
    def prompt(s, n):
        tokens = np.zeros((1, 16), np.int32)
        tokens[0, :n] = r.randint(2, VOCAB, n)
        return (tokens[:, :8 * -(-n // 8)], np.arange(8 * -(-n // 8),
                dtype=np.int32)[None], table[[s]], np.asarray([n], np.int32),
                np.asarray([s], np.int32))
    caches = e.run_prefill(caches, *prompt(0, 3))
    caches = e.run_prefill(caches, *prompt(2, 8))
    s, fed, n = 2, 8, 5
    tokens = np.zeros((1, 8), np.int32)
    tokens[0, :n] = r.randint(2, VOCAB, n)
    chunk = (tokens, fed + np.arange(8, dtype=np.int32)[None], table[[s]],
             np.asarray([n], np.int32), np.asarray([s], np.int32))
    last = np.asarray([7, 0, 11, 0], np.int32)
    positions = np.asarray([3, 0, fed + n, 0], np.int32)
    active = np.asarray([True, False, True, False])
    want, after = e.run_decode(e.run_prefill(caches, *chunk), last,
                               positions, active, table)
    got, rode = e.run_decode(caches, last, positions, active, table, *chunk)
    np.testing.assert_array_equal(np.asarray(got)[active],
                                  np.asarray(want)[active])
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(rode)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


class _TwoCallModel:
    """A served model that implements the three-name contract and not the
    optional carrying form."""

    def __init__(self, model):
        self._model = model
        self.vocab_size, self.eos_id = model.vocab_size, model.eos_id
        self.n_positions = model.n_positions
        self.make_paged_slot_caches = model.make_paged_slot_caches
        self.head_logits = model.head_logits

    def paged_hidden(self, params, caches, tokens, positions, block_table,
                     lengths, decode=False):
        return self._model.paged_hidden(params, caches, tokens, positions,
                                        block_table, lengths, decode)


def test_a_model_without_the_carrying_form_keeps_the_two_call_iteration(
        lm, monkeypatch):
    model, params, _ = lm
    e = DecodeEntry("twocall", _TwoCallModel(model), params, num_slots=4,
                    max_seq_len=32, prefill_chunk=8)
    assert e.carried == () and e._jit_carry is None
    assert "carry8" not in e.precompile()
    sched = DecodeScheduler(e, name="twocall", start=False)
    r = np.random.RandomState(29)
    first = sched.submit(r.randint(2, VOCAB, 3).astype(np.int32), 8,
                         eos_id=-1)
    sched.step_once()
    calls = _spied(monkeypatch, e)
    prompt = r.randint(2, VOCAB, 12).astype(np.int32)
    rep = sched.submit(prompt, 5, eos_id=-1)
    sched.step_once()
    sched.step_once()
    assert calls == [("prefill", 1), ("step", None)] * 2
    monkeypatch.undo()
    check_vs_oracle(lm, prompt, _run_until_done(sched, [first, rep])[1], 5,
                    eos_id=-1)
    assert sched.stats()["prefill_carried"] == 0
    sched.close(drain=False)


@pytest.mark.parametrize("how", ["cancel", "eos"])
def test_a_slot_ends_while_a_chunk_rides_the_step_in_flight(lm, entry, how):
    """`cancel`: the slot whose chunk rides the step in flight is cancelled;
    its blocks return, the slot's next owner and the slot that decoded
    beside it get their own tokens. `eos`: a decoding slot ends by value in
    step N while step N+1, which carries another slot's chunk and its own
    stray row, is in flight: the row is dropped, the chunk stands."""
    sched = DecodeScheduler(entry, name=f"ride-{how}", start=False)
    r = np.random.RandomState(37)
    a, b, c = (r.randint(2, VOCAB, n).astype(np.int32) for n in (4, 20, 9))
    alone_a, alone_b, alone_c = _staggered_run(
        entry, [(0, a, 10, -1)]) + _staggered_run(
        entry, [(0, b, 6, -1)]) + _staggered_run(entry, [(0, c, 6, -1)])
    eos = int(alone_a[2]) if how == "eos" else -1
    stop = int(np.argmax(alone_a == eos)) + 1 if how == "eos" else 10
    rep_a = sched.submit(a, 10, eos_id=eos)
    sched.step_once()
    while len(rep_a._tokens.queue) < min(stop - 1, 3):
        sched.step_once()             # `eos`: A's last token is in flight
    rep_b = sched.submit(b, 6, eos_id=-1)
    sched.step_once()                 # B's first chunk rides a step
    assert _counter(sched, "prefill_carried") == 1
    assert sched._in_flight is not None
    if how == "cancel":
        rep_b.cancel()
        sched.step_once()             # sweeps B; its chunk's step is fetched
        assert rep_b.result(timeout=1).shape == (0,)
        rep_c = sched.submit(c, 6, eos_id=-1)     # B's slot, B's blocks
        got_a, got_c = _run_until_done(sched, [rep_a, rep_c])
        np.testing.assert_array_equal(got_c, alone_c)
        assert _counter(sched, "rows_dropped") == 0
    else:
        assert rep_a.done()           # ended by value at that fetch
        got_a, got_b = _run_until_done(sched, [rep_a, rep_b])
        np.testing.assert_array_equal(got_b, alone_b)
        assert _counter(sched, "rows_dropped") == 1
    np.testing.assert_array_equal(got_a, alone_a[:stop])
    p = sched._pool
    assert p.free + sched._prefix.cached_count() == p.total
    assert p.live == 0 and p.reserved == 0
    sched.close(drain=False)


# ------------------------------------ paged KV pool & prefix cache (r21)
def _paged_entry(lm, name="pg", **kw):
    model, params, _ = lm
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("prefill_chunk", 8)
    return DecodeEntry(name, model, params, paged=True, **kw)


@pytest.mark.parametrize("block", [1, 7, 16])
def test_paged_block_sizes_bit_identical_to_isolated_generate(
        lm, staggered_submits, staggered_oracle, block):
    """ISSUE 20 acceptance: the paged block pool — staggered joins,
    mid-batch EOS retirement, slot reuse — is BIT-IDENTICAL to each
    sequence decoded alone over its own dense cache
    (generate(kv_cache=True)) at block sizes 1, odd, and the default 16
    (frontier-masked stale pages contribute exactly zero)."""
    paged = _paged_entry(lm, name=f"pg{block}", kv_block=block)
    outs = _staggered_run(paged, staggered_submits)
    for i, ((_, prompt, max_new, eos), got) in enumerate(
            zip(staggered_submits, outs)):
        check_vs_oracle(lm, prompt, got, max_new, eos_id=eos,
                        want=staggered_oracle(i))


def test_prefix_cache_hit_cow_and_refcounts(lm):
    """Shared-prefix reuse: a repeat prompt takes its whole prefill
    region from cached blocks (hits == full block count, prefill
    skipped ahead), a prompt diverging INSIDE block 2 takes only the
    two genuinely-shared blocks (block-granular COW — the divergence
    block stays private), and both decode bit-identically to the
    isolated oracle. Retired requests decref; refs==0 blocks stay
    cached and the pool invariant free + live + cached == total
    holds."""
    entry = _paged_entry(lm, name="pfx", kv_block=4, kv_pool_blocks=24)
    assert entry.prefix_cache
    sched = DecodeScheduler(entry, name="pfx", start=False)
    r = np.random.RandomState(11)
    shared = r.randint(2, VOCAB, 13).astype(np.int32)  # 3 whole blocks

    def run(prompt):
        rep = sched.submit(prompt, 6)
        steps = 0
        while not rep.done():
            sched.step_once()
            steps += 1
            assert steps < 200
        return rep.result(timeout=1)

    a = run(shared)
    check_vs_oracle(lm, shared, a, 6)
    assert sched._prefix.hits == 0          # cold: all misses
    assert sched._pool.cached_count() >= 3  # committed + retired
    b = run(shared)                          # identical prompt
    assert sched._prefix.hits == 3           # whole prefill region hit
    np.testing.assert_array_equal(a, b)
    div = shared.copy()
    div[9] = 2 if div[9] != 2 else 3         # diverge inside block 2
    h0 = sched._prefix.hits
    c = run(div)
    assert sched._prefix.hits - h0 == 2      # blocks 0,1 shared only
    check_vs_oracle(lm, div, c, 6)
    p = sched._pool
    assert p.live == 0 and p.reserved == 0   # all retired -> only cache
    assert p.free + p.cached_count() == p.total
    sched.close(drain=False)


def test_prefix_cache_cap_evicts_lru(lm):
    """Distinct prompts overflow the cached-block cap: LRU refs==0
    entries are evicted back to the free list, the eviction counter
    moves, and the accounting invariant survives."""
    entry = _paged_entry(lm, name="evc", kv_block=4, kv_pool_blocks=16,
                         prefix_cache_blocks=4)
    sched = DecodeScheduler(entry, name="evc", start=False)
    r = np.random.RandomState(13)
    for _ in range(4):                       # 4 prompts x 2 blocks > cap
        rep = sched.submit(r.randint(2, VOCAB, 9).astype(np.int32), 4)
        while not rep.done():
            sched.step_once()
    pf, p = sched._prefix, sched._pool
    assert pf.evictions >= 1
    assert p.cached_count() <= 4             # cap enforced
    assert p.free + p.cached_count() == p.total
    sched.close(drain=False)


def test_pool_exhaustion_refusal_and_clean_retry(lm):
    """A request that can NEVER fit the pool is refused at submit with
    a block-level CapacityError and leaves no partial state; fitting
    requests queue and complete — including two that must serialize
    through the 2-block pool."""
    from bigdl_tpu.observe.memz import CapacityError
    entry = _paged_entry(lm, name="cap", kv_block=4, kv_pool_blocks=2,
                         prefix_cache=False)
    sched = DecodeScheduler(entry, name="cap", start=False)
    with pytest.raises(CapacityError) as ei:
        sched.submit(np.arange(2, 8, dtype=np.int32), 8)   # 4 blocks
    assert "block" in str(ei.value)
    assert sched._pool.free == 2 and sched._pool.reserved == 0
    r1 = sched.submit([2, 3, 4], 4)                        # 2 blocks
    r2 = sched.submit([2, 3, 4], 4)   # queues: pool holds one at a time
    steps = 0
    while not (r1.done() and r2.done()):
        sched.step_once()
        steps += 1
        assert steps < 200
    np.testing.assert_array_equal(r1.result(timeout=1),
                                  r2.result(timeout=1))
    assert sched._pool.free == 2
    sched.close(drain=False)


def test_sampling_deterministic_and_greedy_parity(lm, entry):
    """temperature=0 through the sampling program == the greedy oracle
    bit-for-bit; a fixed seed reproduces the identical stream whether
    decoded solo or packed in a batch (position-keyed stateless rng);
    hot temperatures actually move tokens off the argmax path. A model
    compiled WITHOUT sampling refuses temperature > 0 at submit."""
    smp = _paged_entry(lm, name="smp", sampling=True)
    sched = DecodeScheduler(smp, name="smp", start=False)
    prompt = np.asarray([2, 5, 9, 4], np.int32)

    def run(batch):
        reps = [sched.submit(prompt, 12, **kw) for kw in batch]
        steps = 0
        while not all(r.done() for r in reps):
            sched.step_once()
            steps += 1
            assert steps < 300
        return [r.result(timeout=1) for r in reps]

    greedy, = run([dict(temperature=0.0)])
    check_vs_oracle(lm, prompt, greedy, 12)
    hot = dict(temperature=2.0, top_k=16, top_p=0.95, seed=42)
    solo, = run([hot])
    packed = run([hot, hot, dict(temperature=0.0)])
    np.testing.assert_array_equal(solo, packed[0])   # solo == batched
    np.testing.assert_array_equal(solo, packed[1])   # slot-independent
    np.testing.assert_array_equal(greedy, packed[2])
    others = run([dict(temperature=2.0, seed=s) for s in (1, 2, 3)])
    assert any(o.shape != solo.shape or not np.array_equal(o, solo)
               for o in others)
    sched.close(drain=False)
    plain = DecodeScheduler(entry, name="nosmp", start=False)
    with pytest.raises(ValueError):
        plain.submit(prompt, 4, temperature=0.7)
    plain.close(drain=False)


def test_kv_shard_pool_sharding_asserted(lm):
    """kv_shard=True: the pool's block dim is sharded over the mesh
    (NamedSharding asserted on the AOT executables' input shardings,
    pool size rounded up to axis divisibility) and decode stays
    bit-identical to the isolated oracle."""
    from bigdl_tpu.parallel.mesh import create_mesh
    from jax.sharding import PartitionSpec
    mesh = create_mesh(drop_trivial_axes=True)
    if mesh is None or len(mesh.devices.flat) < 2:
        pytest.skip("needs a multi-device mesh")
    model, params, _ = lm
    e = DecodeEntry("shrd", model, params, mesh=mesh, num_slots=4,
                    max_seq_len=32, prefill_chunk=8, paged=True,
                    kv_shard=True)
    e.precompile()                    # runs _assert_pool_sharding
    assert e._pool_sharding is not None
    from bigdl_tpu.nn.attention import PAGED_POOL_BLOCK_AXIS
    assert e._pool_sharding.spec == PartitionSpec(
        *[None] * PAGED_POOL_BLOCK_AXIS, e._shard_axis)
    assert e.pool_blocks % mesh.shape[e._shard_axis] == 0
    sched = DecodeScheduler(e, name="shrd", start=False)
    prompt = np.asarray([2, 3, 4, 5], np.int32)
    rep = sched.submit(prompt, 6)
    steps = 0
    while not rep.done():
        sched.step_once()
        steps += 1
        assert steps < 200
    check_vs_oracle(lm, prompt, rep.result(timeout=1), 6)
    sched.close(drain=False)


def test_paged_stats_and_ledger_surface(lm):
    """stats() carries the block-pool economics (totals, free, cached,
    utilization, prefix hit rate) and the ledger owns
    serve/<m>/kv_pool with live blocks_free meta (the /memz + headroom
    surface)."""
    from bigdl_tpu.observe import memz
    entry = _paged_entry(lm, name="stt", kv_block=4, kv_pool_blocks=16)
    sched = DecodeScheduler(entry, name="stt", start=False)
    rep = sched.submit(np.asarray([2, 3, 4, 5, 6], np.int32), 4)
    while not rep.done():
        sched.step_once()
    st = sched.stats()
    assert st["kv_block"] == 4
    assert st["kv_blocks_total"] == 16
    assert (st["kv_blocks_free"] + st["kv_blocks_live"]
            + st["kv_blocks_cached"] == 16)
    assert "prefix_hit_rate" in st
    row = memz.ledger().owners().get("serve/stt/kv_pool")
    assert row is not None and row["kind"] == "kv_pool"
    assert row["meta"]["blocks"] == 16
    assert row["meta"]["blocks_free"] == sched._pool.free
    sched.close(drain=False)


# ------------------------------------------------------- engine (threads)
@pytest.fixture(scope="module")
def engine(lm):
    model, params, state = lm
    eng = ServeEngine()
    eng.register("lm", model, params, state, decode=True, num_slots=4,
                 max_seq_len=32, prefill_chunk=8)
    yield eng
    eng.shutdown()


def test_engine_concurrent_generate_parity(lm, engine):
    """Real-thread engine: concurrent submits, all bit-identical to the
    isolated oracle."""
    r = np.random.RandomState(3)
    prompts = [r.randint(2, VOCAB, p).astype(np.int32)
               for p in (3, 8, 12, 5, 9, 4)]
    replies = [engine.submit_generate("lm", p, 10) for p in prompts]
    for p, rep in zip(prompts, replies):
        check_vs_oracle(lm, p, rep.result(timeout=60), 10)


def test_zero_fresh_compiles_after_precompile(engine):
    """ISSUE 14 acceptance: the warm serving path compiles NOTHING —
    decode step, the one-row prefill program of every bucket and the
    `num_slots`-row program of the full chunk are AOT executable hits."""
    sched = engine._decoders["lm"]
    dec = sched.entry
    assert sorted(dec._aot_prefill) == list(dec.buckets) == [1, 2, 4, 8]
    assert sorted(dec._aot_carry) == list(dec.carried) == [8]
    assert dec._aot_prefill_all is not None and dec._aot_decode is not None
    compiles = observe.registry().counter("jit/compiles")
    c0 = compiles.value
    calls0, rows0, carried0 = (_counter(sched, n) for n in (
        "prefill_calls", "prefill_rows", "prefill_carried"))
    r = np.random.RandomState(4)
    with sched._cv:     # all eight queued before the first is admitted
        reps = [engine.submit_generate("lm", r.randint(2, VOCAB, p), 6)
                for p in (2, 5, 9, 13, 7, 3, 11, 6)]
    for rep in reps:
        rep.result(timeout=60)
    assert compiles.value == c0
    # both kinds of prefill call ran: 9 and 13, half the slots, met in the
    # full chunk's bucket
    calls = _counter(sched, "prefill_calls") - calls0
    assert calls < _counter(sched, "prefill_rows") - rows0 < 4 * calls
    assert dec._aot_prefill_all is not None and len(dec._aot_prefill) == 4
    # and prompts streamed beside slots that decoded (the tail of 13, a
    # request admitted once another retired): those chunks rode steps,
    # through AOT executables too
    assert _counter(sched, "prefill_carried") - carried0 >= 2
    assert len(dec._aot_carry) == 1


def test_streaming_reply_yields_before_completion(engine):
    """GenReply.stream() delivers tokens at iteration cadence — the
    first token arrives while the request is still decoding."""
    rep = engine.submit_generate("lm", [2, 3, 4], 10)
    it = rep.stream(timeout=60)
    first = next(it)
    assert isinstance(first, int)
    rest = list(it)
    got = np.asarray([first] + rest, np.int32)
    np.testing.assert_array_equal(got, rep.result(timeout=60))


def test_engine_stats_and_statusz_decode_section(engine):
    st = engine.stats()
    d = st["lm"]["decode"]
    assert d["slots"] == 4 and d["max_seq_len"] == 32
    assert d["requests"] >= 1 and d["tokens"] >= 1
    assert 0.0 < d["slot_occupancy_mean"] <= 1.0
    assert d["ttft_p99_ms"] >= d["ttft_p50_ms"] > 0
    from bigdl_tpu.observe import statusz
    payload = statusz.status_payload()
    assert payload["decode"]["lm"]["tokens"] == d["tokens"]
    assert payload["serve"]["lm"]["decode"]["slots"] == 4
    # the chunks that rode a step stand beside the steps enqueued ahead
    assert 0 < d["prefill_carried"] <= d["prefill_calls"]
    assert payload["decode"]["lm"]["prefill_carried"] == d["prefill_carried"]
    assert payload["decode"]["lm"]["steps_ahead"] == d["steps_ahead"]


def test_generate_for_unregistered_model_raises(engine):
    with pytest.raises(KeyError):
        engine.submit_generate("nope", [2, 3], 4)


def test_decode_rejects_non_contract_model():
    import bigdl_tpu.nn as nn
    model = nn.Sequential(nn.Linear(4, 4))
    params, state = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine()
    try:
        with pytest.raises(TypeError):
            eng.register("mlp", model, params, state, decode=True)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("missing", ["make_paged_slot_caches",
                                     "paged_hidden", "head_logits"])
def test_model_lacking_a_contract_method_is_refused_with_all_three(
        lm, missing):
    """What a served model provides is three methods; one that lacks any
    of them is refused with the whole list and the one it lacks."""
    model, params, _ = lm

    class Partial:
        vocab_size, n_positions, eos_id = VOCAB, 64, EOS

    for name in ("make_paged_slot_caches", "paged_hidden", "head_logits"):
        if name != missing:
            setattr(Partial, name, staticmethod(getattr(model, name)))
    with pytest.raises(TypeError) as err:
        DecodeEntry("part", Partial(), params, num_slots=2, max_seq_len=16)
    msg = str(err.value)
    assert ("('make_paged_slot_caches', 'paged_hidden', 'head_logits')"
            in msg)
    assert f"Partial lacks ['{missing}']" in msg


@pytest.mark.parametrize("family", ["GPT2LM", "LlamaLM"])
def test_register_refuses_the_dense_bucket_by_name(lm, family):
    """`paged` survives on register() for the benchmark's configurations:
    None and True are the one layout, False names what was removed."""
    if family == "GPT2LM":
        model, params, state = lm
    else:
        from bigdl_tpu.interop.huggingface import LlamaLM
        model = LlamaLM(VOCAB, 16, 4, 2, 32, 2, eos_id=EOS)
        params, state = model.init(jax.random.PRNGKey(1))
    eng = ServeEngine()
    try:
        with pytest.raises(ValueError, match="dense slot bucket was "
                                             f"removed; {family}"):
            eng.register("dn", model, params, state, decode=True,
                         num_slots=2, max_seq_len=16, paged=False)
        assert "dn" not in eng.stats()
        kw = dict(decode=True, num_slots=2, max_seq_len=16,
                  prefill_chunk=4, precompile_decode=False)
        a = eng.register("a", model, params, state, paged=None, **kw)
        b = eng.register("b", model, params, state, paged=True, **kw)
        assert (a.decode.pool_blocks, a.decode.prefix_cache) == \
            (b.decode.pool_blocks, b.decode.prefix_cache)
    finally:
        eng.shutdown()


# ----------------------------------------------------- llama / GQA path
def test_llama_engine_parity():
    """The grouped-KV (GQA + RoPE) decode path through the real engine
    is bit-identical to LlamaLM.generate(kv_cache=True)."""
    from bigdl_tpu.interop.huggingface import LlamaLM
    model = LlamaLM(VOCAB, 16, 4, 2, 32, 2, eos_id=EOS)
    params, state = model.init(jax.random.PRNGKey(1))
    eng = ServeEngine()
    try:
        eng.register("llama", model, params, state, decode=True,
                     num_slots=2, max_seq_len=24, prefill_chunk=4)
        r = np.random.RandomState(5)
        prompts = [r.randint(2, VOCAB, p).astype(np.int32)
                   for p in (3, 7, 5)]
        replies = [eng.submit_generate("llama", p, 6) for p in prompts]
        for p, rep in zip(prompts, replies):
            check_vs_oracle((model, params, state), p,
                            rep.result(timeout=60), 6)
    finally:
        eng.shutdown()


# ------------------------------------------------------- observability
def test_serve_watchdog_decode_step_attribution():
    """The ServeWatchdog watches decode latency p99 and attributes a
    regression whose growth sits in per-token step time to step_ms
    (queue-vs-prefill-vs-step decomposition)."""
    from bigdl_tpu.observe import doctor as obs_doctor
    from bigdl_tpu.serve.batcher import LATENCY_MS_BOUNDS
    lat = observe.histogram("serve/dm/decode/latency_ms",
                            LATENCY_MS_BOUNDS)
    qw = observe.histogram("serve/dm/decode/queue_wait_ms",
                           LATENCY_MS_BOUNDS)
    pf = observe.histogram("serve/dm/decode/prefill_ms",
                           LATENCY_MS_BOUNDS)
    stp = observe.histogram("serve/dm/decode/step_ms",
                            LATENCY_MS_BOUNDS)
    swd = obs_doctor.ServeWatchdog(pct=50.0, window=8, sustain=1)

    def window(lat_ms, step_ms):
        for _ in range(3):
            lat.record(lat_ms)
            qw.record(0.5)
            pf.record(2.0)
            stp.record(step_ms)
        return swd.observe_snapshot()

    for _ in range(6):
        assert window(10.0, 1.0) == []
    opened = window(150.0, 140.0)
    assert len(opened) == 1
    inc = opened[0]
    assert inc["model"] == "dm/decode"
    assert inc["phase"] == "step_ms"
    assert set(inc["deltas"]) == {"queue_wait_ms", "prefill_ms",
                                  "step_ms"}


def test_batcher_records_per_model_batch_fill():
    """The batch-fill fix: _run_batch records the per-model
    serve/<model>/batch_fill histogram (bucket fill), distinct from
    decode slot occupancy, and stats() surfaces it per model."""
    from bigdl_tpu.serve.batcher import ContinuousBatcher
    name = "fillm"
    b = ContinuousBatcher(lambda xs, n: xs, [8], name=name, start=False)
    for _ in range(2):
        b.submit(np.ones((2, 3), np.float32))
    b._run_batch(b._take())
    h = observe.registry().histogram(f"serve/{name}/batch_fill")
    assert h.count == 1
    assert h.sum == pytest.approx(0.5)        # 4 rows in the 8 bucket


def test_decode_knobs_registered():
    from bigdl_tpu.utils import config
    knobs = config.knobs()
    for name in ("SERVE_DECODE_SLOTS", "SERVE_PREFILL_CHUNK",
                 "SERVE_MAX_SEQ_LEN", "SERVE_KV_BLOCK", "SERVE_KV_POOL_BLOCKS",
                 "SERVE_PREFIX_CACHE", "SERVE_PREFIX_CACHE_BLOCKS",
                 "SERVE_SAMPLING", "SERVE_KV_SHARD"):
        assert name in knobs and knobs[name].doc
    assert config.get("SERVE_DECODE_SLOTS") >= 1
    assert config.get("SERVE_MAX_SEQ_LEN") >= 1
    assert config.get("SERVE_KV_BLOCK") >= 1


# ----------------------------------------------------------------- CLI
def test_cli_decode_smoke(capsys):
    from bigdl_tpu.serve.__main__ import main
    rc = main(["--decode", "--smoke", "--slots", "4", "--max-seq-len",
               "64", "--prefill-chunk", "8", "--smoke-threads", "2",
               "--smoke-requests", "3", "--max-new", "8"])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(out)
    assert rc == 0
    assert rec["mode"] == "decode-smoke"
    assert rec["requests_ok"] == rec["requests_sent"] == 6
    assert rec["errors"] == []
    assert rec["slots"] == 4
    assert rec["tokens"] >= rec["retired"] >= 6
    assert 0.0 < rec["slot_occupancy_mean"] <= 1.0


def test_cli_decode_smoke_default_lengths(capsys):
    """`python -m bigdl_tpu.serve --decode --smoke` as documented: with
    no --max-seq-len the slot cache follows the demo model's 256
    positions instead of outrunning them with the knob's 1024."""
    from bigdl_tpu.serve.__main__ import main
    from bigdl_tpu.utils import config
    assert config.get("SERVE_MAX_SEQ_LEN") > 256
    rc = main(["--decode", "--smoke", "--smoke-threads", "2",
               "--smoke-requests", "2", "--max-new", "4",
               "--prefill-chunk", "4"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and rec["errors"] == []
    assert rec["requests_ok"] == rec["requests_sent"] == 4
