"""PTB-style language-model training + beam-search generation
(reference: example/languagemodel — PTB LM with an LSTM or Transformer,
models/rnn/ PTBWordLM; generation via nn/SequenceBeamSearch.scala).

Hermetic: a synthetic Markov corpus stands in for the PTB download
(zero-egress image); pass --model transformer for the attention variant.

    JAX_PLATFORMS=cpu python examples/language_model.py
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np                                           # noqa: E402
import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import bigdl_tpu.nn as nn                                    # noqa: E402
from bigdl_tpu.dataset import ArrayDataSet                   # noqa: E402
from bigdl_tpu.models import rnn as rnn_zoo                  # noqa: E402
from bigdl_tpu.nn.recurrent import beam_search               # noqa: E402
from bigdl_tpu.optim.local import Optimizer                  # noqa: E402
from bigdl_tpu.optim.method import Adam                      # noqa: E402
from bigdl_tpu.optim.trigger import Trigger                  # noqa: E402

VOCAB, SEQ = 64, 24
EOS = 1


def make_corpus(n=512, seed=0):
    """First-order Markov chains: token t+1 ≡ (2*t + noise) mod VOCAB —
    learnable structure with a closed-form 'good continuation'."""
    r = np.random.RandomState(seed)
    xs = np.zeros((n, SEQ + 1), np.int32)
    xs[:, 0] = r.randint(2, VOCAB, n)
    for t in range(SEQ):
        step = (2 * xs[:, t] + r.randint(0, 2, n)) % VOCAB
        xs[:, t + 1] = np.maximum(step, 2)      # keep 0/1 for pad/eos
    return xs[:, :-1], xs[:, 1:]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("lstm", "transformer"),
                    default="lstm")
    ap.add_argument("--epochs", type=int, default=8)
    args = ap.parse_args(argv)

    x, y = make_corpus()
    if args.model == "lstm":
        model = rnn_zoo.build_lstm(VOCAB, embed_dim=64, hidden_size=64,
                                   num_layers=1)
        criterion = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    else:
        model = rnn_zoo.build_transformer(VOCAB, d_model=64, num_heads=4,
                                          d_ff=128, num_layers=2,
                                          max_len=SEQ)
        criterion = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())

    opt = Optimizer(model, ArrayDataSet(x, y, 64, drop_last=True),
                    criterion, Adam(3e-3))
    opt.set_end_when(Trigger.max_epoch(args.epochs))
    params, state = opt.optimize()

    # perplexity on held-out chains
    xv, yv = make_corpus(128, seed=1)
    out, _ = model.apply(params, state, jnp.asarray(xv))
    if args.model == "lstm":                      # log-probs already
        logp = out
    else:
        logp = jax.nn.log_softmax(out, -1)
    nll = -jnp.take_along_axis(
        logp, jnp.asarray(yv)[..., None], -1).mean()
    print(f"validation perplexity: {float(jnp.exp(nll)):.2f} "
          f"(uniform would be {VOCAB})")

    # beam-search continuation of a prompt. Scan state must be fixed-shape:
    # a length-(prompt+gen) token buffer plus a position counter; the LM
    # re-reads the buffer each step (O(T^2) total — fine for a demo) and
    # causality makes the positions past `pos` irrelevant to its logits.
    prompt = jnp.asarray(xv[:2, :4])
    B, K = prompt.shape[0], 3
    gen_len = 8
    plen = prompt.shape[1]

    def step_fn(last_tokens, st):
        buf, pos = st                       # pos: (B*K,) — beam_search
        p = pos[0]                          # reorders per-beam leaves
        buf = jax.lax.dynamic_update_slice(buf, last_tokens[:, None], (0, p))
        out, _ = model.apply(params, state, buf)
        logits = jnp.take_along_axis(
            out, jnp.full((buf.shape[0], 1, 1), p).repeat(out.shape[-1], 2),
            axis=1)[:, 0]
        return logits, (buf, pos + 1)

    from bigdl_tpu.nn.recurrent import tile_beam
    buf0 = jnp.zeros((B * K, plen + gen_len), jnp.int32)
    buf0 = buf0.at[:, :plen].set(tile_beam(prompt, K))
    pos0 = jnp.full((B * K,), plen - 1, jnp.int32)
    seqs, scores = beam_search(step_fn, (buf0, pos0), prompt[:, -1],
                               beam_size=K, vocab_size=VOCAB,
                               max_len=gen_len, eos_id=EOS)
    print("prompt:", np.asarray(prompt).tolist())
    print("top-beam continuations:", np.asarray(seqs)[:, 0].tolist())
    print("beam scores:", np.round(np.asarray(scores), 2).tolist())

    if args.model == "transformer":
        # the zoo Transformer also ships KV-cached generate() — O(T) per
        # step instead of the O(T^2) buffer recipe above, same results
        full, cscores = model.generate(params, state, prompt, gen_len,
                                       beam_size=K, eos_id=EOS)
        np.testing.assert_array_equal(np.asarray(full[:, 0, plen:]),
                                      np.asarray(seqs)[:, 0])
        print("kv-cached generate() agrees with the buffer recipe")


if __name__ == "__main__":
    main()
