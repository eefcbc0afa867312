"""HuggingFace bridge goldens — GPT-2 weights onto our primitives, logits
parity vs the torch `transformers` forward (parity-plus interop; weights
are random-init because the environment has no network, which exercises
the exact same conversion path as pretrained checkpoints)."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from bigdl_tpu.interop.huggingface import from_gpt2           # noqa: E402


def _tiny_gpt2(seed=0, **kw):
    from transformers import GPT2Config, GPT2LMHeadModel
    torch.manual_seed(seed)
    cfg = GPT2Config(vocab_size=101, n_positions=32, n_embd=48,
                     n_layer=3, n_head=4, resid_pdrop=0.0,
                     embd_pdrop=0.0, attn_pdrop=0.0, **kw)
    return GPT2LMHeadModel(cfg).eval()


def test_gpt2_logits_parity():
    hf = _tiny_gpt2()
    module, params, state = from_gpt2(hf)
    toks = np.random.RandomState(0).randint(0, 101, (2, 16))
    with torch.no_grad():
        want = hf(torch.from_numpy(toks)).logits.numpy()
    got, _ = module.apply(params, state, jnp.asarray(toks),
                          training=False)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4)


def test_gpt2_bare_model_and_serialization(tmp_path):
    """GPT2Model (no LM head wrapper) converts too, and the converted
    module survives the durable format."""
    from transformers import GPT2Config, GPT2Model
    from bigdl_tpu.utils.serializer import load_module, save_module
    torch.manual_seed(1)
    cfg = GPT2Config(vocab_size=67, n_positions=16, n_embd=32, n_layer=2,
                     n_head=2, resid_pdrop=0.0, embd_pdrop=0.0,
                     attn_pdrop=0.0)
    hf = GPT2Model(cfg).eval()
    module, params, state = from_gpt2(hf)
    toks = np.random.RandomState(1).randint(0, 67, (1, 8))
    want, _ = module.apply(params, state, jnp.asarray(toks))

    path = str(tmp_path / "gpt2.bigdl-tpu")
    save_module(path, module, params, state)
    m2, p2, s2 = load_module(path)
    got, _ = m2.apply(p2, s2, jnp.asarray(toks))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)


def test_gpt2_fine_tunes_with_optimizer():
    """The imported model is trainable through the standard facade
    (set_initial + Optimizer), like every other importer output."""
    from bigdl_tpu import optim
    from bigdl_tpu.dataset.core import IteratorDataSet, MiniBatch
    import bigdl_tpu.nn as nn

    hf = _tiny_gpt2(seed=2)
    module, params, state = from_gpt2(hf)
    r = np.random.RandomState(2)
    toks = np.stack([(np.arange(17) + i) % 101 for i in range(8)])
    x, y = toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)

    def epoch():
        yield MiniBatch(x, y)

    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                       size_average=True)
    opt = (optim.Optimizer(module, IteratorDataSet(epoch), crit,
                           optim.Adam(3e-3), seed=4)
           .set_initial(params, state)
           .set_end_when(optim.Trigger.max_iteration(30)))
    p2, _ = opt.optimize()
    assert opt.state["loss"] < 3.0, opt.state["loss"]


def test_gpt2_untied_head_converts():
    from transformers import GPT2Config, GPT2LMHeadModel
    torch.manual_seed(3)
    cfg = transformers.GPT2Config(
        vocab_size=53, n_positions=16, n_embd=32, n_layer=2, n_head=2,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
        tie_word_embeddings=False)
    hf = GPT2LMHeadModel(cfg).eval()
    with torch.no_grad():                 # make head visibly != wte
        hf.lm_head.weight.add_(0.5)
    module, params, state = from_gpt2(hf)
    assert not module.tied and "lm_head" in params
    toks = np.random.RandomState(3).randint(0, 53, (2, 8))
    with torch.no_grad():
        want = hf(torch.from_numpy(toks)).logits.numpy()
    got, _ = module.apply(params, state, jnp.asarray(toks))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4)


def test_old_pickle_without_bias_attr_still_loads():
    """Class-level bias default keeps pre-bias-option pickles working."""
    from bigdl_tpu.nn.attention import MultiHeadAttention
    m = MultiHeadAttention(16, 2)
    del m.__dict__["bias"]                # simulate an old pickle
    params, state = m.init(jax.random.PRNGKey(0))
    assert set(params) == {"wq", "wk", "wv", "wo"}
    out, _ = m.apply(params, state,
                     jnp.zeros((1, 4, 16), jnp.float32))
    assert out.shape == (1, 4, 16)


def test_bert_last_hidden_state_parity():
    """BERT (post-LN encoder) parity incl. a real padding mask and token
    types."""
    from transformers import BertConfig, BertModel
    from bigdl_tpu.interop.huggingface import from_bert
    torch.manual_seed(4)
    cfg = BertConfig(vocab_size=71, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=24, type_vocab_size=2,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    hf = BertModel(cfg).eval()
    module, params, state = from_bert(hf)

    r = np.random.RandomState(4)
    toks = r.randint(0, 71, (2, 12))
    mask = np.ones((2, 12), np.int32)
    mask[0, 8:] = 0                       # padded tail on row 0
    types = r.randint(0, 2, (2, 12))
    with torch.no_grad():
        want = hf(torch.from_numpy(toks),
                  attention_mask=torch.from_numpy(mask),
                  token_type_ids=torch.from_numpy(types)
                  ).last_hidden_state.numpy()
    got, _ = module.apply(params, state, jnp.asarray(toks),
                          jnp.asarray(mask), jnp.asarray(types))
    # positions attending only to real tokens must match everywhere
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4)


def test_gpt2_generate_beam1_matches_greedy_rollout():
    """beam_size=1 generation == hand-rolled greedy argmax decoding, and
    the HF model's own greedy generate() agrees token for token. The
    eos default comes from the converted config."""
    hf = _tiny_gpt2(seed=5, eos_token_id=100)
    module, params, state = from_gpt2(hf)
    assert module.eos_id == 100
    prompt = np.random.RandomState(5).randint(1, 100, (2, 4)).astype(np.int32)
    n_new = 6

    seqs, scores = module.generate(params, state, jnp.asarray(prompt),
                                   n_new, beam_size=1)
    assert seqs.shape == (2, 1, 4 + n_new)

    # hand greedy (jitted: one program per length, where the eager
    # forward compiles every op anew at each of the six lengths)
    forward = jax.jit(lambda toks: module.apply(params, state, toks))
    cur = prompt.copy()
    for _ in range(n_new):
        logits, _ = forward(jnp.asarray(cur))
        nxt = np.asarray(jnp.argmax(logits[:, -1, :], -1), np.int32)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    # pin the semantics: no eos emitted in this deterministic rollout, so
    # frozen-beam padding never kicks in and HF's stopping never differs
    assert not (cur[:, 4:] == 100).any()
    np.testing.assert_array_equal(np.asarray(seqs[:, 0]), cur)

    with torch.no_grad():
        hf_out = hf.generate(torch.from_numpy(prompt.astype(np.int64)),
                             max_new_tokens=n_new, do_sample=False,
                             num_beams=1, pad_token_id=0)
    np.testing.assert_array_equal(np.asarray(seqs[:, 0]),
                                  hf_out.numpy().astype(np.int32))


def test_gpt2_generate_kv_cache_matches_recompute():
    """KV-cached decoding is an exact program transform: sequences AND
    beam scores match the full-recompute path, beams > 1 included (the
    cache tensors reorder per beam through beam_search's state)."""
    hf = _tiny_gpt2(seed=6, eos_token_id=100)
    module, params, state = from_gpt2(hf)
    prompt = np.random.RandomState(6).randint(1, 100, (2, 5)).astype(np.int32)
    for K in (1, 3):
        s_a, sc_a = module.generate(params, state, jnp.asarray(prompt), 7,
                                    beam_size=K, kv_cache=False)
        s_b, sc_b = module.generate(params, state, jnp.asarray(prompt), 7,
                                    beam_size=K, kv_cache=True)
        np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_b))
        np.testing.assert_allclose(np.asarray(sc_a), np.asarray(sc_b),
                                   rtol=1e-4, atol=1e-5)


def _tiny_llama(seed=0, kv_heads=2, tie=False):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(seed)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                      intermediate_size=96, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=kv_heads,
                      max_position_embeddings=64, rms_norm_eps=1e-6,
                      rope_theta=10000.0, tie_word_embeddings=tie,
                      attn_implementation="eager")
    return LlamaForCausalLM(cfg).eval()


def test_llama_logits_parity_gqa():
    """LLaMA-architecture bridge: RMSNorm + rotary + grouped-query
    attention + SwiGLU, logits-parity vs the real transformers model
    (num_kv_heads=2 < heads=4 exercises the GQA repeat)."""
    import torch
    from bigdl_tpu.interop.huggingface import from_llama
    hf = _tiny_llama(seed=0, kv_heads=2)
    module, params, state = from_llama(hf)
    tokens = np.random.RandomState(0).randint(0, 128, (2, 11)).astype(np.int32)
    with torch.no_grad():
        want = hf(torch.from_numpy(tokens.astype(np.int64))).logits.numpy()
    got, _ = module.apply(params, state, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_llama_mha_full_heads_and_tied():
    """kv_heads == heads (vanilla MHA path) and tied embeddings."""
    import torch
    from bigdl_tpu.interop.huggingface import from_llama
    hf = _tiny_llama(seed=1, kv_heads=4, tie=True)
    module, params, state = from_llama(hf)
    assert "lm_head" not in params
    tokens = np.random.RandomState(1).randint(0, 128, (1, 7)).astype(np.int32)
    with torch.no_grad():
        want = hf(torch.from_numpy(tokens.astype(np.int64))).logits.numpy()
    got, _ = module.apply(params, state, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_llama_fine_tunes_and_serializes(tmp_path):
    """The converted model composes with jit/grad and the durable
    format."""
    from bigdl_tpu.interop.huggingface import from_llama
    from bigdl_tpu.utils.serializer import load_module, save_module
    hf = _tiny_llama(seed=2)
    module, params, state = from_llama(hf)
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, 128, (2, 9)), jnp.int32)

    @jax.jit
    def loss_fn(p):
        logits, _ = module.apply(p, state, tokens[:, :-1])
        lp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(
            lp, tokens[:, 1:, None], axis=-1).mean()

    l0 = float(loss_fn(params))
    g = jax.jit(jax.grad(loss_fn))
    p = params
    for _ in range(20):
        p = jax.tree.map(lambda a, b: a - 0.5 * b, p, g(p))
    assert float(loss_fn(p)) < l0 - 0.5

    path = tmp_path / "llama.bigdl-tpu"
    save_module(str(path), module, params, state)
    m2, p2, s2 = load_module(str(path))
    out_a, _ = module.apply(params, state, tokens)
    out_b, _ = m2.apply(p2, s2, tokens)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=1e-6)


def test_llama_generate_matches_hf_greedy():
    """LlamaLM.generate beam=1 == real transformers greedy decode; the
    refuse-loudly config guards raise on unmodeled fields."""
    import torch
    import pytest
    from transformers import LlamaConfig, LlamaForCausalLM
    from bigdl_tpu.interop.huggingface import from_llama

    hf = _tiny_llama(seed=3)
    hf.config.eos_token_id = 127
    module, params, state = from_llama(hf)
    # 1..120: token 0 is HF generate's pad_token_id — a 0 in the prompt
    # would be attention-masked by HF but not by us
    prompt = np.random.RandomState(3).randint(1, 120, (2, 5)).astype(np.int32)
    seqs, _ = module.generate(params, state, jnp.asarray(prompt), 6,
                              beam_size=1, eos_id=127)
    with torch.no_grad():
        want = hf.generate(torch.from_numpy(prompt.astype(np.int64)),
                           max_new_tokens=6, do_sample=False, num_beams=1,
                           pad_token_id=0).numpy().astype(np.int32)
    got = np.asarray(seqs[:, 0])
    # compare each row up to (and including) the first eos — after an
    # eos, frozen-beam padding may legitimately differ from HF's
    for r in range(got.shape[0]):
        hits = np.where((got[r] == 127) | (want[r] == 127))[0]
        end = int(hits[0]) + 1 if hits.size else got.shape[1]
        np.testing.assert_array_equal(got[r, :end], want[r, :end])

    torch.manual_seed(0)
    bad = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=48,
                      num_hidden_layers=1, num_attention_heads=4,
                      attention_bias=True)
    with pytest.raises(NotImplementedError, match="attention_bias"):
        from_llama(LlamaForCausalLM(bad))
    bad2 = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=48,
                       num_hidden_layers=1, num_attention_heads=4,
                       hidden_act="gelu")
    with pytest.raises(NotImplementedError, match="hidden_act"):
        from_llama(LlamaForCausalLM(bad2))


def test_llama_generate_kv_cache_matches_recompute():
    """Grouped-KV cached decoding is an exact transform of the
    recompute path: sequences and scores match for beams 1 and 3."""
    from bigdl_tpu.interop.huggingface import from_llama
    hf = _tiny_llama(seed=4, kv_heads=2)
    hf.config.eos_token_id = 127
    module, params, state = from_llama(hf)
    prompt = np.random.RandomState(4).randint(1, 120, (2, 5)).astype(np.int32)
    for K in (1, 3):
        s_a, sc_a = module.generate(params, state, jnp.asarray(prompt), 6,
                                    beam_size=K, eos_id=127,
                                    kv_cache=False)
        s_b, sc_b = module.generate(params, state, jnp.asarray(prompt), 6,
                                    beam_size=K, eos_id=127,
                                    kv_cache=True)
        np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_b))
        np.testing.assert_allclose(np.asarray(sc_a), np.asarray(sc_b),
                                   rtol=1e-4, atol=1e-5)


def test_vit_parity_and_pooler():
    """ViT bridge: patchify conv + CLS + positions + pre-LN blocks match
    the real transformers ViTModel (NHWC inputs here vs NCHW there),
    last hidden AND pooled output."""
    from transformers import ViTConfig, ViTModel
    from bigdl_tpu.interop.huggingface import from_vit
    torch.manual_seed(8)
    cfg = ViTConfig(hidden_size=48, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=96,
                    image_size=32, patch_size=8, num_channels=3,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    hf = ViTModel(cfg).eval()
    module, params, state = from_vit(hf)

    imgs = np.random.RandomState(8).randn(2, 32, 32, 3).astype(np.float32)
    with torch.no_grad():
        out = hf(torch.from_numpy(imgs.transpose(0, 3, 1, 2)))
    got, _ = module.apply(params, state, jnp.asarray(imgs))
    np.testing.assert_allclose(np.asarray(got),
                               out.last_hidden_state.numpy(),
                               rtol=1e-4, atol=1e-4)
    pooled, _ = module.apply(params, state, jnp.asarray(imgs), pool=True)
    np.testing.assert_allclose(np.asarray(pooled),
                               out.pooler_output.numpy(),
                               rtol=1e-4, atol=1e-4)


def test_vit_fine_tunes_as_classifier():
    """The converted ViT trains as an image classifier head-to-toe
    through jit/grad (pooled CLS -> linear head)."""
    from transformers import ViTConfig, ViTModel
    from bigdl_tpu.interop.huggingface import from_vit
    torch.manual_seed(9)
    cfg = ViTConfig(hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=4, intermediate_size=48,
                    image_size=16, patch_size=8, num_channels=1)
    hf = ViTModel(cfg).eval()
    module, params, state = from_vit(hf)
    r = np.random.RandomState(9)
    x = r.randn(16, 16, 16, 1).astype(np.float32)
    y = (x.mean((1, 2, 3)) > 0).astype(np.int32)
    head = jnp.zeros((32, 2))
    packed = {"vit": params, "head": head}

    @jax.jit
    def loss_fn(pk):
        pooled, _ = module.apply(pk["vit"], state, jnp.asarray(x),
                                 pool=True)
        lp = jax.nn.log_softmax(pooled @ pk["head"])
        return -jnp.take_along_axis(lp, jnp.asarray(y)[:, None], 1).mean()

    l0 = float(loss_fn(packed))
    g = jax.jit(jax.grad(loss_fn))
    for _ in range(60):
        gr = g(packed)
        packed = jax.tree.map(lambda a, b: a - 0.5 * b, packed, gr)
    l1 = float(loss_fn(packed))
    assert l1 < l0 * 0.5, (l0, l1)


def test_vit_classifier_wrapper_and_guards():
    """ViTForImageClassification converts (no pooler -> pool=True
    raises clearly); unmodeled config fields refuse loudly."""
    from transformers import ViTConfig, ViTForImageClassification
    from bigdl_tpu.interop.huggingface import from_vit
    torch.manual_seed(10)
    cfg = ViTConfig(hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=4, intermediate_size=48,
                    image_size=16, patch_size=8, num_channels=1,
                    num_labels=3)
    hf = ViTForImageClassification(cfg).eval()
    module, params, state = from_vit(hf)
    assert not module.has_pooler and "pooler" not in params
    imgs = np.random.RandomState(10).randn(2, 16, 16, 1).astype(np.float32)
    with torch.no_grad():
        want = hf.vit(torch.from_numpy(imgs.transpose(0, 3, 1, 2))
                      ).last_hidden_state.numpy()
    got, _ = module.apply(params, state, jnp.asarray(imgs))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4)
    with pytest.raises(ValueError, match="no pooler"):
        module.apply(params, state, jnp.asarray(imgs), pool=True)

    from transformers import ViTModel
    bad = ViTConfig(hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=4, intermediate_size=48,
                    image_size=16, patch_size=8, num_channels=1,
                    qkv_bias=False)
    with pytest.raises(NotImplementedError, match="qkv_bias"):
        from_vit(ViTModel(bad))


def test_llama_flash_attention_backend_and_int8():
    """The converted LLaMA runs with the Pallas flash kernel as its
    attention backend (matching dense logits), and quantize() swaps the
    SwiGLU Linears to int8 with argmax agreement — BigQuant-style int8
    on a modern decoder."""
    from bigdl_tpu.interop.huggingface import from_llama
    from bigdl_tpu.kernels.flash_attention import PallasFlashAttention
    from bigdl_tpu.nn.quantized import QuantizedLinear, quantize

    hf = _tiny_llama(seed=5, kv_heads=2)
    module, params, state = from_llama(hf)
    toks = jnp.asarray(
        np.random.RandomState(5).randint(0, 128, (2, 64)), jnp.int32)
    # (the three forwards jitted: one program each, not one per eager op)
    want, _ = jax.jit(module.apply)(params, state, toks)

    flash = from_llama(hf, attn_impl=PallasFlashAttention(
        block_q=32, block_k=32, interpret=True))[0]
    got, _ = jax.jit(flash.apply)(params, state, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-3)

    qmod, qparams = quantize(module, params)
    blk = qmod.children()["l0"].children()
    assert isinstance(blk["gate"], QuantizedLinear)
    assert isinstance(blk["down"], QuantizedLinear)
    qlogits, _ = jax.jit(qmod.apply)(qparams, state, toks)
    agree = float((np.asarray(qlogits).argmax(-1)
                   == np.asarray(want).argmax(-1)).mean())
    assert agree > 0.97, agree


def test_llama_tensor_parallel_training():
    """LlamaLM trains over a dp x tp mesh with llama_tp_rules: the
    attention/SwiGLU weights actually shard over the 'model' axis, the
    sharded forward matches the unsharded one, and the loss falls."""
    from jax.sharding import PartitionSpec as P
    from bigdl_tpu.interop.huggingface import LlamaLM, llama_tp_rules
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh
    from bigdl_tpu.optim.method import Adam
    from bigdl_tpu.optim.trigger import Trigger
    import bigdl_tpu.nn as nn

    model = LlamaLM(64, 32, 4, 2, 48, 2, tied=True)
    params0, state0 = model.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = np.stack([(np.arange(13) * 5 + i) % 64 for i in range(8)])
    toks, labels = x[:, :-1].astype(np.int32), x[:, 1:].astype(np.int32)

    mesh = create_mesh(data=4, model=2, drop_trivial_axes=False)
    rules = llama_tp_rules()
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                       size_average=True)
    opt = DistriOptimizer(model, [(toks, labels)], crit, Adam(3e-3),
                          mesh=mesh, rules=rules)
    opt.set_initial(params0, state0)
    opt.set_end_when(Trigger.max_iteration(40))
    params, _ = opt.optimize()
    assert opt.state["loss"] < 2.5, opt.state["loss"]
    assert params["l0"]["attn"]["wq"].sharding.spec == P(None, "model")
    assert params["l0"]["down"]["weight"].sharding.spec == P("model", None)

    # sharded-params forward == plain forward on the initial weights
    # (jitted: the eager sharded forward partitions op by op)
    forward = jax.jit(
        lambda p: model.apply(p, state0, jnp.asarray(toks))[0])
    want = forward(params0)
    from bigdl_tpu.parallel.sharding import shard_tree
    got = forward(shard_tree(params0, mesh, rules.tree_specs(params0)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_llama_ring_attention_sequence_parallel():
    """The converted LLaMA runs ring-attention sequence-parallel: a
    from_llama(attn_impl=RingAttention) module inside shard_map over a
    seq-sharded mesh produces EXACTLY the dense full-sequence logits
    (RoPE offsets per shard; GQA repeat before the ring)."""
    from bigdl_tpu.interop.huggingface import from_llama, llama_sp_apply
    from bigdl_tpu.parallel import create_mesh
    from bigdl_tpu.parallel.ring import RingAttention

    hf = _tiny_llama(seed=6, kv_heads=2)
    dense, params, state = from_llama(hf)
    ring = from_llama(hf, attn_impl=RingAttention(axis_name="seq"))[0]

    toks = jnp.asarray(
        np.random.RandomState(6).randint(0, 128, (2, 32)), jnp.int32)
    want, _ = dense.apply(params, state, toks)

    mesh = create_mesh(seq=4, drop_trivial_axes=True)
    got = llama_sp_apply(ring, params, toks, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # composes with data parallelism: batch over 'data', seq over 'seq'
    mesh2 = create_mesh(data=2, seq=4, drop_trivial_axes=False)
    got2 = llama_sp_apply(ring, params, toks, mesh2)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_llama_sp_apply_refuses_dense_backend():
    """Passing a non-ring module to llama_sp_apply raises instead of
    silently attending only within shards."""
    from bigdl_tpu.interop.huggingface import from_llama, llama_sp_apply
    from bigdl_tpu.parallel import create_mesh
    hf = _tiny_llama(seed=7)
    dense, params, state = from_llama(hf)
    toks = jnp.zeros((1, 32), jnp.int32)
    mesh = create_mesh(seq=4, drop_trivial_axes=True)
    with pytest.raises(ValueError, match="RingAttention"):
        llama_sp_apply(dense, params, toks, mesh)


def _tp_case_gpt2():
    from bigdl_tpu.interop.huggingface import GPT2LM, gpt2_tp_rules
    gpt = GPT2LM(31, 16, 16, 2, 1)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 31, (2, 8)),
                       jnp.int32)
    return (gpt, jax.random.PRNGKey(0), (toks,), gpt2_tp_rules(),
            [(("h0", "attn", "wq"), (None, "model")),
             (("h0", "ffn", "w2", "weight"), ("model", None))])


def _tp_case_bert():
    from bigdl_tpu.interop.huggingface import BertEncoder, encoder_tp_rules
    bert = BertEncoder(31, 16, 2, 16, 2, 1, 32)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 31, (2, 8)),
                       jnp.int32)
    mask = jnp.ones((2, 8), jnp.int32)
    types = jnp.zeros((2, 8), jnp.int32)
    return (bert, jax.random.PRNGKey(1), (toks, mask, types),
            encoder_tp_rules(),
            [(("attn0", "wq"), (None, "model")),
             (("ffn0", "w1", "weight"), (None, "model"))])


def _tp_case_vit():
    from bigdl_tpu.interop.huggingface import ViTEncoder, encoder_tp_rules
    vit = ViTEncoder(16, 8, 1, 16, 2, 32, 1)
    imgs = jnp.asarray(np.random.RandomState(2).randn(2, 16, 16, 1),
                       jnp.float32)
    return (vit, jax.random.PRNGKey(2), (imgs,), encoder_tp_rules(),
            [(("h0", "attn", "wq"), (None, "model"))])


@pytest.mark.parametrize("case", [_tp_case_gpt2, _tp_case_bert,
                                  _tp_case_vit],
                         ids=["gpt2", "bert", "vit"])
def test_gpt2_and_encoder_tp_rules_shard_and_match(case):
    """Megatron TP rules for the other bridges: GPT-2, BERT, and ViT
    params shard over 'model', and the sharded forward equals the
    unsharded one (both jitted: one program each, where the eager
    forward partitions and compiles op by op)."""
    from jax.sharding import PartitionSpec as P
    from bigdl_tpu.parallel import create_mesh
    from bigdl_tpu.parallel.sharding import shard_tree

    mesh = create_mesh(data=4, model=2, drop_trivial_axes=False)
    module, key, inputs, rules, expected = case()
    params, state = module.init(key)
    forward = jax.jit(lambda p: module.apply(p, state, *inputs)[0])
    want = forward(params)
    specs = rules.tree_specs(params)
    for path, spec in expected:
        leaf = specs
        for k in path:
            leaf = leaf[k]
        assert leaf == P(*spec), (path, leaf)
    got = forward(shard_tree(params, mesh, specs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_llama_remat_grads_identical():
    """remat=True recomputes block activations in the backward without
    changing ANY gradient (jax.checkpoint is numerics-neutral)."""
    from bigdl_tpu.interop.huggingface import LlamaLM

    plain = LlamaLM(48, 32, 4, 2, 48, 2, tied=True)
    params, state = plain.init(jax.random.PRNGKey(0))
    remat = LlamaLM(48, 32, 4, 2, 48, 2, tied=True, remat=True)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 48, (2, 10)),
                       jnp.int32)

    def loss(m):
        def f(p):
            logits, _ = m.apply(p, state, toks[:, :-1])
            lp = jax.nn.log_softmax(logits)
            return -jnp.take_along_axis(lp, toks[:, 1:, None], -1).mean()
        return f

    ga = jax.grad(loss(plain))(params)
    gb = jax.grad(loss(remat))(params)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
