"""Zoo perf harness CLI (reference: models/utils/DistriOptimizerPerf.scala:32
+ LocalOptimizerPerf.scala + nn/mkldnn/Perf.scala:125-126 — per-model
train-step throughput on synthetic data).

    python -m bigdl_tpu.models.perf --model resnet50 --batch-size 128
    python -m bigdl_tpu.models.perf --model inception-v2 --dtype bf16

Timing uses the chained-dispatch + wait-for-completion protocol of
`utils/sync.py`."""

from __future__ import annotations

import argparse
import functools
import sys


def _model(name: str, class_num: int):
    """Returns (model, input_shape, kind) — kind drives data synthesis:
    'image' float NHWC, 'tokens' int ids with LM loss."""
    from bigdl_tpu.models import (autoencoder, inception, lenet, resnet,
                                  rnn, vgg)
    builders = {
        "lenet": lambda: (lenet.build(10), (28, 28, 1), "image"),
        "resnet50": lambda: (resnet.build(50, class_num), (224, 224, 3),
                             "image"),
        "resnet20-cifar": lambda: (resnet.build_cifar(20, 10), (32, 32, 3),
                                   "image"),
        "inception-v1": lambda: (inception.build(class_num), (224, 224, 3),
                                 "image"),
        "inception-v2": lambda: (inception.build_v2(class_num),
                                 (224, 224, 3), "image"),
        "vgg16": lambda: (vgg.build(16, class_num), (224, 224, 3), "image"),
        "autoencoder": lambda: (autoencoder.build(), (28, 28, 1), "image"),
        "ptb-lstm": lambda: (rnn.build_lstm(), (64,), "tokens"),
        "ptb-transformer": lambda: (rnn.build_transformer(), (64,),
                                    "tokens"),
    }
    if name not in builders:
        raise SystemExit(f"unknown model {name!r}; one of {sorted(builders)}")
    return builders[name]()


def _synth_batch(model_name, kind, spatial, batch_size, class_num,
                 autoenc):
    """Synthetic (x, y, criterion) for a model kind — shared by the
    single-device and scaling benches so the two can never diverge."""
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.nn.criterion import (ClassNLLCriterion,
                                        CrossEntropyCriterion, MSECriterion)
    r = np.random.RandomState(0)
    if kind == "tokens":
        vocab = 10000
        x = jnp.asarray(r.randint(0, vocab, (batch_size,) + spatial),
                        jnp.int32)
        y = jnp.asarray(r.randint(0, vocab, (batch_size,) + spatial),
                        jnp.int32)
        # both criterions handle (B, T, V) with (B, T) targets natively —
        # TimeDistributedCriterion would trace an unrolled T-loop under jit
        criterion = ClassNLLCriterion() if model_name == "ptb-lstm" \
            else CrossEntropyCriterion()
    else:
        x = jnp.asarray(r.randn(batch_size, *spatial).astype(np.float32))
        y = x.reshape(batch_size, -1) if autoenc else \
            jnp.asarray(r.randint(0, class_num, size=batch_size), jnp.int32)
        criterion = MSECriterion() if autoenc else ClassNLLCriterion()
    return x, y, criterion


def _resolve_seed(seed):
    """Explicit seed > BIGDL_TPU_SEED — the bench stays deterministic by
    default but the seed is threaded, not baked in (TPU-LINT004)."""
    if seed is not None:
        return int(seed)
    from bigdl_tpu.utils import config
    return int(config.get("SEED"))


def _make_step(model, criterion, method, compute_dtype, seed):
    """The jitted SGD train step shared by run() and run_scaling()."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.core.module import cast_floating
    # distinct stream from the init key (same fold discipline as the
    # trainers' per-step rng threading)
    rng = jax.random.fold_in(jax.random.PRNGKey(seed), 7)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, slots, model_state, x, y):
        def loss_fn(p):
            pc = cast_floating(p, compute_dtype) if compute_dtype else p
            xc = (x.astype(compute_dtype)
                  if compute_dtype and jnp.issubdtype(x.dtype, jnp.floating)
                  else x)
            out, ns = model.apply(pc, model_state, xc, training=True,
                                  rng=rng)
            return criterion.forward(out.astype(jnp.float32), y), ns
        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        if compute_dtype:
            grads = cast_floating(grads, jnp.float32)
        new_p, new_s = method.update(params, grads, slots,
                                     jnp.float32(0.1), jnp.int32(0))
        return new_p, new_s, ns, loss
    return step


def _time_step(step, params, slots, state, x, y, warmup, iters,
               batch_size):
    from bigdl_tpu.utils.sync import time_steps

    def adapt(carry):
        out = step(*carry, x, y)
        return out[:3], out
    sec, _ = time_steps(adapt, (params, slots, state), warmup, iters)
    return batch_size / sec


def run(model_name: str, batch_size: int, iters: int, warmup: int,
        dtype: str, class_num: int, seed: int = None) -> float:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.optim.method import SGD

    seed = _resolve_seed(seed)
    model, spatial, kind = _model(model_name, class_num)
    autoenc = model_name == "autoencoder"
    method = SGD(0.1, momentum=0.9)
    compute_dtype = {"bf16": jnp.bfloat16, "fp32": None}[dtype]
    params, state = model.init(jax.random.PRNGKey(seed))
    slots = method.init_slots(params)
    x, y, criterion = _synth_batch(model_name, kind, spatial, batch_size,
                                   class_num, autoenc)
    step = _make_step(model, criterion, method, compute_dtype, seed)
    return _time_step(step, params, slots, state, x, y, warmup, iters,
                      batch_size)


def run_scaling(model_name: str, batch_per_device: int, iters: int,
                warmup: int, dtype: str, class_num: int,
                device_counts=None, seed: int = None) -> dict:
    """Data-parallel throughput at 1/2/4/... devices (whitepaper.md:160-164
    scaling-table culture; on the virtual CPU mesh this measures the SPMD
    plumbing's scaling, not chip FLOPs — the JSON labels the backend)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.parallel.mesh import create_mesh
    from bigdl_tpu.parallel.sharding import batch_spec

    ndev = len(jax.devices())
    if device_counts is None:
        device_counts = [n for n in (1, 2, 4, 8, 16, 32, 64) if n <= ndev]
        if ndev not in device_counts:    # non-power-of-2 topologies
            device_counts.append(ndev)
    seed = _resolve_seed(seed)
    compute_dtype = {"bf16": jnp.bfloat16, "fp32": None}[dtype]
    model, spatial, kind = _model(model_name, class_num)
    autoenc = model_name == "autoencoder"
    method = SGD(0.1, momentum=0.9)
    results = {}
    for n in device_counts:
        mesh = create_mesh(jax.devices()[:n], drop_trivial_axes=True)
        bs = batch_per_device * n
        params, state = model.init(jax.random.PRNGKey(seed))
        slots = method.init_slots(params)
        x, y, criterion = _synth_batch(model_name, kind, spatial, bs,
                                       class_num, autoenc)
        rep = NamedSharding(mesh, P())
        x = jax.device_put(x, NamedSharding(mesh, batch_spec(mesh, x.ndim)))
        y = jax.device_put(y, NamedSharding(mesh, batch_spec(mesh, y.ndim)))
        place = lambda t, s: jax.tree.map(lambda a: jax.device_put(a, s), t)
        params, slots, state = (place(params, rep), place(slots, rep),
                                place(state, rep))
        step = _make_step(model, criterion, method, compute_dtype, seed)
        results[n] = _time_step(step, params, slots, state, x, y, warmup,
                                iters, bs)
    base = results[device_counts[0]] / device_counts[0]
    return {
        "model": model_name, "dtype": dtype,
        "batch_per_device": batch_per_device,
        "backend": jax.default_backend(),
        "throughput_rec_per_sec": {str(n): round(v, 2)
                                   for n, v in results.items()},
        "scaling_efficiency": {str(n): round(results[n] / (n * base), 3)
                               for n in device_counts},
    }


def run_loader(batch_size: int, n_images: int = 512, size: int = 224,
               n_batches: int = 20, shard_dir=None,
               compare_model=None, dtype: str = "bf16",
               class_num: int = 1000) -> dict:
    """Input-pipeline throughput on ImageNet-shaped JPEG shards with
    prefetch_to_device, vs the train step it must outrun
    (reference: dataset/DataSet.scala:326-660
    cached-partition feeding)."""
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from bigdl_tpu.dataset.prefetch import prefetch_to_device
    from bigdl_tpu.dataset.sharded import (ShardedRecordDataset,
                                           generate_synthetic,
                                           imagenet_train_transform)

    made_dir = shard_dir is None
    if made_dir:
        shard_dir = tempfile.mkdtemp(prefix="perf_shards_")
        # at least warm-up + 2 timed batches worth of records
        n_images = max(n_images, 3 * batch_size)
        generate_synthetic(shard_dir, n_images, num_shards=8, height=size,
                           width=size, classes=class_num, encoding="jpeg")
    try:
        ds = ShardedRecordDataset(shard_dir, batch_size=batch_size,
                                  shuffle=True, seed=0,
                                  transform=imagenet_train_transform(size))
        if len(ds) < 2:
            raise SystemExit(
                f"loader bench needs >= 2 batches: {ds.num_records()} "
                f"records at batch_size {batch_size} yield {len(ds)}")
        it = prefetch_to_device(iter(ds))
        next(it)                 # warm: first batch pays worker spin-up
        t0 = _time.time()
        done = 0
        for _ in range(min(n_batches, len(ds) - 1)):
            b = next(it, None)
            if b is None:
                break
            jax.block_until_ready(b[0] if isinstance(b, tuple) else b)
            done += 1
        dt = _time.time() - t0
        loader_ips = done * batch_size / max(dt, 1e-9)
    finally:
        if made_dir:
            import shutil
            shutil.rmtree(shard_dir, ignore_errors=True)
    out = {"loader_imgs_per_sec": round(loader_ips, 1),
           "batch_size": batch_size, "image_size": size,
           "encoding": "jpeg", "backend": jax.default_backend()}
    if compare_model:
        step_ips = run(compare_model, batch_size, iters=3, warmup=1,
                       dtype=dtype, class_num=class_num)
        out["step_imgs_per_sec"] = round(step_ips, 1)
        out["loader_vs_step"] = round(loader_ips / step_ips, 2)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(prog="bigdl_tpu.models.perf")
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--dtype", choices=("bf16", "fp32"), default="bf16")
    ap.add_argument("--class-num", type=int, default=1000)
    ap.add_argument("--scaling", action="store_true",
                    help="1/2/4/.. device data-parallel scaling curve")
    ap.add_argument("--loader", action="store_true",
                    help="input-pipeline imgs/sec on JPEG shards")
    ap.add_argument("--compare-step", action="store_true",
                    help="with --loader: also time --model's train step "
                         "and report loader_vs_step")
    args = ap.parse_args(argv)
    import json

    import jax
    on_tpu = jax.default_backend() != "cpu"
    bs = args.batch_size if args.batch_size is not None \
        else (128 if on_tpu else 4)
    iters = args.iters if args.iters is not None else (20 if on_tpu else 2)
    warmup = args.warmup if args.warmup is not None \
        else (3 if on_tpu else 1)
    if args.scaling:
        rec = run_scaling(args.model, bs, iters, warmup, args.dtype,
                          args.class_num)
        print(json.dumps(rec))
        return
    if args.loader:
        rec = run_loader(
            bs, compare_model=args.model if args.compare_step else None,
            dtype=args.dtype, class_num=args.class_num)
        print(json.dumps(rec))
        return
    ips = run(args.model, bs, iters, warmup, args.dtype, args.class_num)
    print(f"{args.model} [{args.dtype}] batch {bs}: {ips:.1f} records/sec "
          f"({jax.default_backend()})")


if __name__ == "__main__":
    from bigdl_tpu import compilecache
    compilecache.enable()               # docs/compile_cache.md
    sys.exit(main())
