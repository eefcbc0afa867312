"""Training CLIs for the model zoo — the analogue of each model's
`Train.scala` + scopt `Options.scala` (reference: models/lenet/Train.scala:35,
models/resnet/Train.scala, models/inception/TrainInceptionV1.scala,
models/rnn/Train.scala, models/vgg/Train.scala; perf harness
models/utils/DistriOptimizerPerf.scala).

    python -m bigdl_tpu.models.train lenet  --max-epoch 5
    python -m bigdl_tpu.models.train resnet --depth 20 --batch-size 128
    python -m bigdl_tpu.models.train ptb    --model lstm
    python -m bigdl_tpu.models.train inception --batch-size 32 --max-iter 20

Each reproduces a BASELINE.json config. Without real data folders the
hermetic synthetic datasets are used so every CLI runs anywhere."""

from __future__ import annotations

import argparse
import logging
import os
import sys

import numpy as np


def _seed_of(args) -> int:
    """--seed wins, else the BIGDL_TPU_SEED knob — the CLI trainers
    thread every PRNGKey from here (TPU-LINT004: no baked-in seeds)."""
    s = getattr(args, "seed", None)
    if s is not None:
        return int(s)
    from bigdl_tpu.utils import config
    return int(config.get("SEED"))


def _common(p: argparse.ArgumentParser):
    p.add_argument("-f", "--folder", default=None, help="dataset folder")
    p.add_argument("--data", default=None,
                   help="record-shard glob (bigdl_tpu.dataset.sharded) — "
                        "the ImageNet seq-file path; overrides --folder")
    p.add_argument("--data-val", default=None, help="validation shard glob")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--workers", type=int, default=None,
                   help="data-loader decode threads")
    p.add_argument("--crop", type=int, default=None,
                   help="input crop size for shard datasets (default 224)")
    p.add_argument("-b", "--batch-size", type=int, default=None)
    p.add_argument("-e", "--max-epoch", type=int, default=None)
    p.add_argument("--max-iter", type=int, default=None)
    p.add_argument("--learning-rate", type=float, default=None)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--summary-dir", default=None)
    p.add_argument("--synthetic-size", type=int, default=512)
    p.add_argument("--optimizer", default=None,
                   help="sgd|adam|rmsprop (model default otherwise)")
    p.add_argument("--seed", type=int, default=None,
                   help="init/shuffle RNG seed (default: BIGDL_TPU_SEED)")
    p.add_argument("--slices", type=int, default=None,
                   help="two-tier data parallelism: split the batch "
                        "axis into a ('slice','data') mesh of this many "
                        "slices (BIGDL_TPU_SLICES) — arms in-run slice "
                        "failover; see docs/resilience.md")
    p.add_argument("--steps-per-call", type=int, default=None,
                   help="fused dispatch: optimizer steps per jitted call "
                        "(lax.scan over the train step; default "
                        "BIGDL_TPU_STEPS_PER_CALL — see "
                        "docs/performance.md)")
    p.add_argument("--accum-steps", type=int, default=None,
                   help="gradient accumulation: microbatches per "
                        "optimizer step (batch size must divide; default "
                        "BIGDL_TPU_ACCUM_STEPS)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest committed snapshot under "
                        "--checkpoint before training (uncommitted/corrupt "
                        "snapshots are skipped; mesh-shape-agnostic — an "
                        "8-device snapshot resumes on 4 devices. "
                        "docs/resilience.md)")
    p.add_argument("--checkpoint-every", type=int, default=None,
                   help="checkpoint every N iterations instead of every "
                        "epoch (fires at the next steps-per-call K "
                        "boundary)")
    p.add_argument("--sync-checkpoint", action="store_true",
                   help="write snapshots inline instead of in the "
                        "background thread (BIGDL_TPU_CHECKPOINT_ASYNC=0)")
    p.add_argument("--checkpoint-keep-n", type=int, default=None,
                   help="retention: keep only the newest N committed "
                        "snapshots (BIGDL_TPU_CHECKPOINT_KEEP_N)")
    p.add_argument("--precompile", action="store_true",
                   help="AOT warmup: compile the train/eval programs "
                        "from shape specs before the first batch "
                        "(BIGDL_TPU_PRECOMPILE; logs XLA cost analysis "
                        "per program)")
    p.add_argument("--trace-dir", default=None,
                   help="flight recorder: dump the span ring as "
                        "Chrome/Perfetto trace JSON here at the end of "
                        "training (BIGDL_TPU_TRACE — "
                        "docs/observability.md)")
    p.add_argument("--metrics-jsonl", default=None,
                   help="structured run log: append one JSON metrics "
                        "snapshot per flush; render with `python -m "
                        "bigdl_tpu.observe <file>` "
                        "(BIGDL_TPU_METRICS_JSONL)")
    p.add_argument("--statusz-port", type=int, default=None,
                   help="live telemetry plane: serve the in-process "
                        "/healthz /metrics /statusz /tracez /profilez "
                        "HTTP endpoints on this port "
                        "(BIGDL_TPU_STATUSZ_PORT; 0 = off — "
                        "docs/observability.md)")


def _end_trigger(args, default_epochs):
    from bigdl_tpu.optim.trigger import Trigger
    if args.max_iter:
        return Trigger.max_iteration(args.max_iter)
    return Trigger.max_epoch(args.max_epoch or default_epochs)


def _finish(opt, args, model, app):
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu import visualization as viz
    if getattr(args, "trace_dir", None):
        import os
        os.environ["BIGDL_TPU_TRACE"] = args.trace_dir
    if getattr(args, "metrics_jsonl", None):
        import os
        os.environ["BIGDL_TPU_METRICS_JSONL"] = args.metrics_jsonl
    if getattr(args, "statusz_port", None):
        import os
        os.environ["BIGDL_TPU_STATUSZ_PORT"] = str(args.statusz_port)
    if getattr(args, "precompile", False):
        import os
        os.environ["BIGDL_TPU_PRECOMPILE"] = "1"
    if getattr(args, "steps_per_call", None):
        opt.set_steps_per_call(args.steps_per_call)
    if getattr(args, "accum_steps", None):
        opt.set_accum_steps(args.accum_steps)
    if args.checkpoint:
        import os
        if getattr(args, "sync_checkpoint", False):
            os.environ["BIGDL_TPU_CHECKPOINT_ASYNC"] = "0"
        if getattr(args, "checkpoint_keep_n", None):
            os.environ["BIGDL_TPU_CHECKPOINT_KEEP_N"] = \
                str(args.checkpoint_keep_n)
        every = getattr(args, "checkpoint_every", None)
        opt.set_checkpoint(args.checkpoint,
                           Trigger.several_iteration(every) if every
                           else Trigger.every_epoch())
        if getattr(args, "resume", False):
            opt.resume(args.checkpoint)
    if args.summary_dir:
        opt.set_train_summary(viz.TrainSummary(args.summary_dir, app))
    params, state = opt.optimize()
    print(f"{app}: finished at iter {opt.state['neval']} "
          f"loss {opt.state.get('loss', float('nan')):.4f}")
    return params, state


def _method(args, default):
    from bigdl_tpu.optim.method import SGD, Adam, RMSprop
    lr = args.learning_rate
    if args.optimizer == "adam":
        return Adam(lr or 1e-3)
    if args.optimizer == "rmsprop":
        return RMSprop(lr or 1e-3)
    if args.optimizer == "sgd":
        return SGD(lr or 0.01, momentum=0.9)
    # --learning-rate alone keeps the model's tuned default method
    # (schedule, weight decay) and only overrides the base LR
    if lr is not None:
        default.learning_rate = lr
    return default


def train_lenet(args):
    """(reference: models/lenet/Train.scala:35-102 — BASELINE config 1)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet, mnist
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.metrics import Top1Accuracy
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.models import lenet

    x, y = mnist.load(args.folder, train=True,
                      n_synthetic=args.synthetic_size)
    x = mnist.normalize(x).reshape(-1, 28, 28, 1)
    bs = args.batch_size or 128
    ds = ArrayDataSet(x, y, bs, drop_last=True)
    val = ArrayDataSet(x, y, bs, shuffle=False)
    model = lenet.build(10)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(),
                    _method(args, SGD(0.05, momentum=0.9)))
    opt.set_end_when(_end_trigger(args, 5))
    opt.set_validation(Trigger.every_epoch(), val, [Top1Accuracy()])
    return _finish(opt, args, model, "lenet")


def _sharded_imagenet(args, bs, crop=None):
    """(train_ds, val_ds|None) from record shards — the reference's
    SeqFileFolder ImageNet ingestion (dataset/DataSet.scala:326-660)."""
    crop = crop or getattr(args, "crop", None) or 224
    from bigdl_tpu.dataset.prefetch import PrefetchDataSet
    from bigdl_tpu.dataset.sharded import (ShardedRecordDataset,
                                           imagenet_eval_transform,
                                           imagenet_train_transform)
    train = PrefetchDataSet(ShardedRecordDataset(
        args.data, bs, transform=imagenet_train_transform(crop),
        num_workers=args.workers))
    val = None
    if args.data_val:
        val = PrefetchDataSet(ShardedRecordDataset(
            args.data_val, bs, transform=imagenet_eval_transform(crop),
            shuffle=False, drop_last=False, num_workers=args.workers))
    return train, val


def train_resnet_imagenet(args):
    """ResNet-50 on ImageNet record shards (reference:
    models/resnet/TrainImageNet.scala — the BASELINE north-star config)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.metrics import Top1Accuracy, Top5Accuracy
    from bigdl_tpu.optim.schedule import Poly
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.models import resnet

    bs = args.batch_size or 64
    ds, val = _sharded_imagenet(args, bs)
    model = resnet.build(depth=args.depth if args.depth >= 18 else 50,
                         class_num=args.num_classes)
    method = _method(args, SGD(0.1, momentum=0.9, weight_decay=1e-4,
                               learning_rate_schedule=Poly(2.0, 90000)))
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), method)
    opt.set_end_when(_end_trigger(args, 1))
    if val is not None:
        opt.set_validation(Trigger.every_epoch(), val,
                           [Top1Accuracy(), Top5Accuracy()])
    return _finish(opt, args, model, "resnet-imagenet")


def train_resnet(args):
    """(reference: models/resnet/Train.scala — BASELINE config 2:
    ResNet on CIFAR-10; with --data, the ImageNet shard path)."""
    if args.data:
        return train_resnet_imagenet(args)
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet, cifar
    from bigdl_tpu.dataset.vision import (ChannelNormalize, HFlip, ImageFrame,
                                          PaddedRandomCrop, Pipeline)
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.metrics import Top1Accuracy
    from bigdl_tpu.optim.schedule import MultiStep
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.models import resnet

    x, y = cifar.load(args.folder, train=True,
                      n_synthetic=args.synthetic_size)
    frame = ImageFrame.from_arrays(x, y)
    frame.transform(Pipeline(
        PaddedRandomCrop(32, 32, pad=4, seed=1), HFlip(seed=2),
        ChannelNormalize(cifar.TRAIN_MEAN, cifar.TRAIN_STD)))
    aug = np.stack([f.floats for f in frame])
    bs = args.batch_size or 128
    ds = ArrayDataSet(aug, y, bs, drop_last=True)
    model = resnet.build_cifar(depth=args.depth, class_num=10)
    method = _method(args, SGD(0.1, momentum=0.9, weight_decay=1e-4,
                               learning_rate_schedule=MultiStep(
                                   [80, 120], 0.1)))
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), method)
    opt.set_end_when(_end_trigger(args, 10))
    opt.set_validation(Trigger.every_epoch(),
                       ArrayDataSet(aug, y, bs, shuffle=False),
                       [Top1Accuracy()])
    return _finish(opt, args, model, "resnet-cifar")


def train_inception(args):
    """(reference: models/inception/TrainInceptionV1.scala — BASELINE
    config 3; synthetic stand-in for the ImageNet seq-file pipeline)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.schedule import Poly
    from bigdl_tpu.optim.metrics import Top1Accuracy, Top5Accuracy
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.models import inception

    bs = args.batch_size or 8
    if args.data:
        ds, val = _sharded_imagenet(args, bs)
        classes = args.num_classes
    else:
        n = min(args.synthetic_size, 64)
        r = np.random.RandomState(0)
        x = r.randn(n, 224, 224, 3).astype(np.float32)
        y = r.randint(0, 1000, n).astype(np.int32)
        ds, val, classes = ArrayDataSet(x, y, bs, drop_last=True), None, 1000
    v2 = getattr(args, "v2", False)
    model = inception.build_v2(classes) if v2 else inception.build(classes)
    method = _method(args, SGD(
        0.0898, momentum=0.9, weight_decay=1e-4,
        learning_rate_schedule=Poly(0.5, 62000)))
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), method)
    opt.set_end_when(_end_trigger(args, 1))
    if args.data and val is not None:
        opt.set_validation(Trigger.every_epoch(), val,
                           [Top1Accuracy(), Top5Accuracy()])
    return _finish(opt, args, model, "inception-v2" if v2 else
                   "inception-v1")


def train_vgg(args):
    """(reference: models/vgg/Train.scala — VGG on CIFAR-10)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet, cifar
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.models import vgg

    x, y = cifar.load(args.folder, train=True,
                      n_synthetic=args.synthetic_size)
    xn = cifar.normalize(x)
    bs = args.batch_size or 64
    ds = ArrayDataSet(xn, y, bs, drop_last=True)
    model = vgg.build_cifar(10)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(),
                    _method(args, SGD(0.01, momentum=0.9,
                                      weight_decay=5e-4)))
    opt.set_end_when(_end_trigger(args, 2))
    return _finish(opt, args, model, "vgg-cifar")


def train_ptb(args):
    """(reference: models/rnn/Train.scala + example/languagemodel/
    PTBWordLM.scala — BASELINE config 4)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import text as T
    from bigdl_tpu.dataset.core import IteratorDataSet, MiniBatch
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import Adam
    from bigdl_tpu.models import rnn

    words = T.ptb_raw(args.folder, "train")
    d = T.Dictionary([words], vocab_size=args.vocab_size - 1)
    bs = args.batch_size or 20
    xs, ys = T.ptb_batches(words, d, bs, args.num_steps)

    def epoch():
        for i in range(xs.shape[0]):
            yield MiniBatch(xs[i], ys[i])

    ds = IteratorDataSet(epoch)
    chosen = [f for f in ("pipeline_stages", "seq_parallel", "moe_experts")
              if getattr(args, f, 0) and getattr(args, f) > 1]
    if len(chosen) > 1:
        raise SystemExit(f"--{' / --'.join(c.replace('_', '-') for c in chosen)} "
                         f"are mutually exclusive (pick one parallelism "
                         f"for this CLI; compose them via the library API)")
    if args.pipeline_stages and args.pipeline_stages > 1:
        return _train_ptb_pipelined(args, d, xs, ys)
    if args.seq_parallel and args.seq_parallel > 1:
        return _train_ptb_seq_parallel(args, d, xs, ys)
    if args.moe_experts and args.moe_experts > 1:
        return _train_ptb_moe(args, d, xs, ys)
    if args.model == "llama":
        # modern decoder (RMSNorm + RoPE + GQA + SwiGLU) from the HF
        # bridge's architecture class, trained like any zoo model
        from bigdl_tpu.interop.huggingface import LlamaLM
        model = LlamaLM(d.vocab_size, args.hidden, 4, args.kv_heads,
                        args.hidden * 4, args.layers, tied=True)
    elif args.model == "transformer":
        model = rnn.build_transformer(d.vocab_size, d_model=args.hidden,
                                      num_heads=4, d_ff=args.hidden * 4,
                                      num_layers=args.layers, dropout=0.0)
    else:
        model = rnn.build_lstm(d.vocab_size, embed_dim=args.hidden,
                               hidden_size=args.hidden,
                               num_layers=args.layers)
    # build_lstm ends in LogSoftMax (ClassNLL input); the Transformer LM
    # returns tied-embedding logits (CrossEntropy input)
    inner = (nn.CrossEntropyCriterion()
             if args.model in ("transformer", "llama")
             else nn.ClassNLLCriterion())
    crit = nn.TimeDistributedCriterion(inner, size_average=True)
    opt = Optimizer(model, ds, crit, _method(args, Adam(1e-3)))
    opt.set_end_when(_end_trigger(args, 1))
    params, state = _finish(opt, args, model, f"ptb-{args.model}")
    print(f"ptb perplexity ~ {np.exp(opt.state['loss']):.1f}")
    return params, state


def _ptb_loop(args, xs, ys, step, tag, summary):
    """Shared step loop for the custom-parallelism PTB paths.
    `step(xb, yb, lr) -> (loss, suffix)`; prints every 10 iters."""
    import jax.numpy as jnp
    lr = args.learning_rate or 1e-3
    max_iter = args.max_iter or (xs.shape[0] * (args.max_epoch or 1))
    first = last = None
    it = 0
    while it < max_iter:
        for i in range(xs.shape[0]):
            loss, suffix = step(jnp.asarray(xs[i]), jnp.asarray(ys[i]), lr)
            first = loss if first is None else first
            last = loss
            it += 1
            if it % 10 == 0 or it >= max_iter:
                print(f"{tag} iter {it} loss {loss:.4f} "
                      f"(ppl ~ {np.exp(loss):.1f}{suffix})")
            if it >= max_iter:
                break
    print(f"{summary}: loss {first:.3f} -> {last:.3f}, "
          f"perplexity ~ {np.exp(last):.1f}")


def _train_ptb_pipelined(args, d, xs, ys):
    """PTB transformer with the block stack pipeline-parallel over the
    'pipe' mesh axis (models/pipelined_lm.py; 1F1B end to end). Uses its
    own step loop — pipeline training updates the boundary params with
    gradients the Pipeline streams out, which the Optimizer facade's
    single-tree step does not model."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models.pipelined_lm import PipelinedLM
    from bigdl_tpu.parallel.mesh import create_mesh

    S = args.pipeline_stages
    if args.model != "transformer":
        raise SystemExit("--pipeline-stages needs --model transformer "
                         "(the LSTM's recurrence does not pipeline)")
    bs = args.batch_size or 20
    micro = 2 * S
    if bs % micro:
        raise SystemExit(
            f"--pipeline-stages {S} runs {micro} microbatches (2x stages); "
            f"--batch-size {bs} must be a multiple of {micro}")
    mesh = create_mesh(pipe=S, drop_trivial_axes=True)
    lm = PipelinedLM(d.vocab_size, d_model=args.hidden, num_heads=4,
                     num_layers=args.layers, n_stages=S,
                     n_microbatches=micro)
    rng = jax.random.PRNGKey(_seed_of(args))
    st = lm.init(rng, mesh)
    holder = {"st": st, "rng": rng}

    def step(xb, yb, lr):
        holder["rng"], sub = jax.random.split(holder["rng"])
        holder["st"], loss = lm.train_step(holder["st"], xb, yb, mesh,
                                           lr=lr, rng=sub)
        return loss, ""
    _ptb_loop(args, xs, ys, step, "pipelined-ptb",
              f"ptb pipelined x{S}")
    return holder["st"], None


def _train_ptb_seq_parallel(args, d, xs, ys):
    """PTB transformer with the sequence dimension sharded over a 'seq'
    mesh axis and ring attention (models/long_context_lm.py) — the
    long-context configuration; each device holds T/N of every
    activation."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models.long_context_lm import SeqParallelLM
    from bigdl_tpu.parallel.mesh import create_mesh

    S = args.seq_parallel
    if args.model != "transformer":
        raise SystemExit("--seq-parallel needs --model transformer")
    if args.num_steps % S:
        raise SystemExit(f"--num-steps {args.num_steps} must divide by "
                         f"--seq-parallel {S} (sequence sharding)")
    if len(jax.devices()) < S:
        raise SystemExit(f"--seq-parallel {S} needs {S} devices, have "
                         f"{len(jax.devices())} (on CPU set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={S})")
    mesh = create_mesh(jax.devices()[:S], seq=S, drop_trivial_axes=True)
    lm = SeqParallelLM(d.vocab_size, d_model=args.hidden, num_heads=4,
                      num_layers=args.layers)
    params = lm.init(jax.random.PRNGKey(_seed_of(args)))
    holder = {"p": params}

    def step(xb, yb, lr):
        holder["p"], loss = lm.train_step(holder["p"], xb, yb, mesh, lr=lr)
        return loss, ""
    _ptb_loop(args, xs, ys, step, "seq-parallel-ptb",
              f"ptb seq-parallel x{S} (ring attention)")
    return holder["p"], None


def _train_ptb_moe(args, d, xs, ys):
    """PTB transformer with Switch-style MoE FFNs, experts (and the
    batch) sharded over an 'expert' mesh axis (models/moe_lm.py)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models.moe_lm import MoELM
    from bigdl_tpu.parallel.mesh import create_mesh

    E = args.moe_experts
    if args.model != "transformer":
        raise SystemExit("--moe-experts needs --model transformer")
    if len(jax.devices()) < E:
        raise SystemExit(f"--moe-experts {E} needs {E} devices, have "
                         f"{len(jax.devices())} (on CPU set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={E})")
    bs = args.batch_size or 20
    if bs % E:
        raise SystemExit(f"--batch-size {bs} must divide by "
                         f"--moe-experts {E} (batch rides the expert "
                         f"axis)")
    mesh = create_mesh(jax.devices()[:E], expert=E, drop_trivial_axes=True)
    lm = MoELM(d.vocab_size, d_model=args.hidden, num_heads=4,
               num_layers=args.layers, n_experts=E)
    params = lm.init(jax.random.PRNGKey(_seed_of(args)))
    holder = {"p": params}

    def step(xb, yb, lr):
        holder["p"], ce, aux = lm.train_step(holder["p"], xb, yb, mesh,
                                             lr=lr)
        return ce, f", lb {aux['load_balance']:.2f}"
    _ptb_loop(args, xs, ys, step, "moe-ptb",
              f"ptb moe x{E} experts")
    return holder["p"], None


def main(argv=None):
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    # structured [p<index> <run-id>] prefix on every bigdl_tpu log line —
    # multihost workers' interleaved stdout stays attributable
    from bigdl_tpu.utils.runtime import install_log_prefix
    install_log_prefix()
    ap = argparse.ArgumentParser(prog="bigdl_tpu.models.train")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("lenet", help="LeNet-5 on MNIST")
    _common(p)

    p = sub.add_parser("resnet", help="ResNet on CIFAR-10")
    _common(p)
    p.add_argument("--depth", type=int, default=20)

    p = sub.add_parser("inception", help="Inception-v1/v2 on ImageNet")
    _common(p)
    p.add_argument("--v2", action="store_true",
                   help="BN-Inception (Inception_v2.scala)")

    p = sub.add_parser("vgg", help="VGG on CIFAR-10")
    _common(p)

    p = sub.add_parser("ptb", help="PTB language model")
    _common(p)
    p.add_argument("--model", choices=["lstm", "transformer", "llama"],
                   default="lstm")
    p.add_argument("--kv-heads", type=int, default=2,
                   help="grouped-query KV heads for --model llama")
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--num-steps", type=int, default=20)
    p.add_argument("--vocab-size", type=int, default=10000)
    p.add_argument("--pipeline-stages", type=int, default=0,
                   help="train the transformer body pipeline-parallel "
                        "over a 'pipe' mesh axis of this size (1F1B; "
                        "embedding/head replicated outside the pipe)")
    p.add_argument("--seq-parallel", type=int, default=0,
                   help="shard the sequence over a 'seq' mesh axis of "
                        "this size with ring attention (long-context)")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="Switch-style MoE FFNs with this many experts, "
                        "expert-parallel over an 'expert' mesh axis")

    args = ap.parse_args(argv)
    if getattr(args, "slices", None):
        # before any mesh exists: Engine.mesh()/create_mesh() read the
        # knob when the trainer is constructed
        os.environ["BIGDL_TPU_SLICES"] = str(args.slices)
    fn = {"lenet": train_lenet, "resnet": train_resnet,
          "inception": train_inception, "vgg": train_vgg,
          "ptb": train_ptb}[args.cmd]
    return fn(args)


if __name__ == "__main__":
    # the process entry turns the persistent XLA cache on (at
    # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache —
    # docs/compile_cache.md); main() called in-process leaves jax's
    # configuration to its caller
    from bigdl_tpu import compilecache
    compilecache.enable()
    sys.exit(0 if main() is not None else 1)
