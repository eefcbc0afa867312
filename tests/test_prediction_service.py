"""PredictionService under concurrency (VERDICT r3 next #8; reference:
optim/PredictionService.scala:56-66 — a BlockingQueue of `instanceNum`
shallow model copies serves concurrent requests; here pure jitted
functions are reentrant, so the contract to prove is: many threads with
mixed batch sizes all get THEIR OWN correct rows back, and the
power-of-two bucketing keeps the compile count bounded)."""

import threading

import numpy as np

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.optim.predictor import PredictionService

MAX_BATCH = 64


def _service():
    model = nn.Sequential(nn.Linear(12, 32), nn.Tanh(), nn.Linear(32, 5))
    params, state = model.init(jax.random.PRNGKey(0))
    svc = PredictionService(model, params, state, instance_num=4,
                            max_batch=MAX_BATCH)
    ref = jax.jit(lambda x: model.apply(params, state, x,
                                        training=False)[0])
    return svc, ref


def test_threaded_stress_mixed_batch_sizes():
    svc, ref = _service()
    r = np.random.RandomState(0)
    n_threads, per_thread = 8, 25
    requests = [[r.randn(int(r.randint(1, 41)), 12).astype(np.float32)
                 for _ in range(per_thread)] for _ in range(n_threads)]
    expected = [[np.asarray(ref(jnp.asarray(q))) for q in qs]
                for qs in requests]

    errors = []
    results = [[None] * per_thread for _ in range(n_threads)]

    def client(ti):
        try:
            for qi, q in enumerate(requests[ti]):
                results[ti][qi] = svc.predict(q)
        except Exception as exc:           # surfaced after join
            errors.append((ti, repr(exc)))

    threads = [threading.Thread(target=client, args=(ti,))
               for ti in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors

    for ti in range(n_threads):
        for qi in range(per_thread):
            got = results[ti][qi]
            want = expected[ti][qi]
            assert got.shape == want.shape
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                       err_msg=f"thread {ti} req {qi}")


def test_compile_count_stays_bounded():
    """Power-of-two padding means at most log2(max_batch)+1 distinct
    shapes ever reach XLA, no matter what request sizes arrive."""
    svc, _ = _service()
    r = np.random.RandomState(1)
    for _ in range(50):
        svc.predict(r.randn(int(r.randint(1, MAX_BATCH + 1)), 12)
                    .astype(np.float32))
    # jax's jit cache counts one entry per distinct padded shape
    n_compiles = svc._fn._cache_size()
    import math
    assert n_compiles <= int(math.log2(MAX_BATCH)) + 1, n_compiles


def test_oversized_request_chunks_correctly():
    """Requests larger than max_batch stream through in max_batch chunks
    and still return every row."""
    svc, ref = _service()
    r = np.random.RandomState(2)
    x = r.randn(3 * MAX_BATCH + 7, 12).astype(np.float32)
    got = svc.predict(x)
    want = np.asarray(ref(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_empty_request_raises():
    """ISSUE 8 satellite: an n=0 request is a client error, not a silent
    trip through the batch loop."""
    svc, _ = _service()
    import pytest
    with pytest.raises(ValueError, match="empty request"):
        svc.predict(np.zeros((0, 12), np.float32))
    with pytest.raises(ValueError):
        svc.predict(np.float32(3.0))       # scalar stays an error too


def test_pad_is_zero_pad_and_content_cannot_leak():
    """ISSUE 8 satellite: padding is zeros + valid mask (PR 5 trick), not
    repeat-last — the bucket program's output on the VALID rows is
    bitwise independent of the pad content."""
    svc, _ = _service()
    r = np.random.RandomState(3)
    x = r.randn(5, 12).astype(np.float32)
    entry = svc._entry
    bucket = svc._bucket(5)
    valid = np.zeros((bucket,), bool)
    valid[:5] = True
    clean = np.zeros((bucket, 12), np.float32)
    clean[:5] = x
    poison = np.full((bucket, 12), 3e8, np.float32)
    poison[:5] = x
    out_clean = np.asarray(entry._jitted(svc.params, svc.state,
                                         clean, valid))
    out_poison = np.asarray(entry._jitted(svc.params, svc.state,
                                          poison, valid))
    np.testing.assert_array_equal(out_clean, out_poison)
    # and the service's live answer IS those valid rows
    np.testing.assert_array_equal(svc.predict(x), out_clean[:5])


def test_predictor_zero_pads_tail():
    """Predictor._pad_to zero-pads (replicated last rows used to run
    real forward math and skew batch-coupled statistics)."""
    from bigdl_tpu.optim.predictor import Predictor, _pad_to
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    padded = _pad_to(x, 5)
    np.testing.assert_array_equal(padded[:2], x)
    np.testing.assert_array_equal(padded[2:], 0.0)

    model = nn.Sequential(nn.Linear(12, 32), nn.Tanh(), nn.Linear(32, 5))
    params, state = model.init(jax.random.PRNGKey(0))
    pred = Predictor(model, params, state, batch_size=8)
    r = np.random.RandomState(4)
    q = r.randn(13, 12).astype(np.float32)   # 8 + padded tail of 5
    got = pred.predict(q)
    want = np.asarray(model.apply(params, state, jnp.asarray(q),
                                  training=False)[0])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_int8_llama_serving_under_concurrency():
    """Serving composition: a quantized (int8 SwiGLU) LLaMA behind
    PredictionService under threaded clients — per-request rows match
    the single-shot int8 forward, and argmax agrees with fp32."""
    import threading
    from bigdl_tpu.interop.huggingface import LlamaLM
    from bigdl_tpu.nn.quantized import quantize
    from bigdl_tpu.optim.predictor import PredictionService

    model = LlamaLM(48, 32, 4, 2, 48, 2, tied=True)
    params, state = model.init(jax.random.PRNGKey(0))
    qmod, qparams = quantize(model, params)
    svc = PredictionService(qmod, qparams, state, max_batch=16)

    r = np.random.RandomState(0)
    reqs = [r.randint(0, 48, (n, 12)).astype(np.int32)
            for n in (1, 3, 7, 2, 5, 4)]
    # (the single-shot forwards jitted: one program a batch size, where
    # the eager forward compiles every op anew at each of the six sizes)
    single_shot = jax.jit(lambda q: qmod.apply(qparams, state, q)[0])
    want = [np.asarray(single_shot(jnp.asarray(q))) for q in reqs]

    results = [None] * len(reqs)
    def client(i):
        results[i] = svc.predict(reqs[i])
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for got, exp in zip(results, want):
        np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-5)

    fp_logits = jax.jit(lambda q: model.apply(params, state, q)[0])(
        jnp.asarray(reqs[2]))
    agree = (results[2].argmax(-1)
             == np.asarray(fp_logits).argmax(-1)).mean()
    assert agree > 0.9, agree
