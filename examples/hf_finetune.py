"""HuggingFace-to-bigdl_tpu fine-tune tour: convert a `transformers`
GPT-2 onto this framework's primitives, verify logits parity against the
torch forward, fine-tune it on a tiny corpus with the standard Optimizer
facade, and save/reload through the durable model format.

    JAX_PLATFORMS=cpu python examples/hf_finetune.py

(The model is random-init because this environment has no network; with
downloads available, `GPT2LMHeadModel.from_pretrained("gpt2")` drops in
unchanged.)"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np                                            # noqa: E402
import torch                                                  # noqa: E402
import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
from transformers import GPT2Config, GPT2LMHeadModel          # noqa: E402

import bigdl_tpu.nn as nn                                     # noqa: E402
from bigdl_tpu import optim                                   # noqa: E402
from bigdl_tpu.dataset.core import IteratorDataSet, MiniBatch  # noqa: E402
from bigdl_tpu.interop.huggingface import from_gpt2           # noqa: E402
from bigdl_tpu.utils.serializer import load_module, save_module  # noqa: E402


def main():
    torch.manual_seed(0)
    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=64, n_layer=2,
                     n_head=4, resid_pdrop=0.0, embd_pdrop=0.0,
                     attn_pdrop=0.0)
    hf = GPT2LMHeadModel(cfg).eval()
    module, params, state = from_gpt2(hf)

    toks = np.random.RandomState(0).randint(0, 97, (2, 24))
    with torch.no_grad():
        want = hf(torch.from_numpy(toks)).logits.numpy()
    got, _ = module.apply(params, state, jnp.asarray(toks))
    err = float(np.abs(np.asarray(got) - want).max())
    print(f"[convert] GPT-2 logits parity vs torch: max |err| = {err:.2e}")
    assert err < 1e-3

    # fine-tune on a deterministic toy corpus (next-token prediction)
    seqs = np.stack([(np.arange(25) * 3 + i) % 97 for i in range(16)])
    x, y = seqs[:, :-1].astype(np.int32), seqs[:, 1:].astype(np.int32)

    def epoch():
        yield MiniBatch(x, y)

    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                       size_average=True)
    opt = (optim.Optimizer(module, IteratorDataSet(epoch), crit,
                           optim.Adam(3e-3), seed=1)
           .set_initial(params, state)
           .set_end_when(optim.Trigger.max_iteration(60)))
    p2, s2 = opt.optimize()
    print(f"[finetune] loss -> {opt.state['loss']:.3f} "
          f"(ppl ~ {np.exp(opt.state['loss']):.1f})")
    assert opt.state["loss"] < 2.0

    # the converted+tuned model survives the durable format
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "gpt2-tuned.bigdl-tpu")
        save_module(path, module, p2, s2)
        m3, p3, s3 = load_module(path)
        a, _ = module.apply(p2, s2, jnp.asarray(x[:2]))
        b, _ = m3.apply(p3, s3, jnp.asarray(x[:2]))
        assert np.allclose(np.asarray(a), np.asarray(b))
    print("[save] durable-format round trip exact")
    print("hf fine-tune tour complete")


if __name__ == "__main__":
    main()
