"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: train, then serve
    python chip_smoke.py --chips 4    # four chips: the dp x tp trainer only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
                                      # the same code at a tiny size

One process, the only one to touch JAX, drives the two main paths through
the entry points a user calls, at the published width of a model:

  * train — `optim.local.Optimizer` on ResNet-50 (1000 classes), 224x224x3
    NHWC, bf16 compute over fp32 master weights, SGD+momentum, batch 64,
    seeded synthetic images through `ArrayDataSet`, `Trigger.max_iteration`;
  * serve — `ServeEngine.register(decode=True)` on `GPT2LM` at the GPT-2 XL
    widths (vocab 50257, 1024 positions, d 1600, 25 heads, 48 layers),
    weights from `model.init(PRNGKey(seed))`, paged KV, behind
    `ServeFront(LocalBackend(engine))` on an ephemeral port, asked over
    real sockets (`/v1/generate`, some streamed over SSE, overlapping);
  * --chips 4 — `parallel.DistriOptimizer` on a 2x2 (data x model) mesh,
    ZeRO-1, bf16, on a Llama decoder (`LLAMA`), against the same model,
    seed and global batch through the one-device `Optimizer` on chip 0.

Any phase that raises, or any check that fails, ends the run with a
non-zero exit code; so does a run that finds no TPU (`--rehearse` alone
lifts that, and says so in its last line). The last line of a run that
passed is `{"ok": true, "device": {...}}` with the device as JAX reports
it. Sizes of the KV pool follow the chip's compiler (PERF.md, Findings).
"""

import argparse
import json
import logging
import sys
import threading
import time
import urllib.request

import numpy as np

SEED = 0
RESNET = dict(depth=50, classes=1000, image=224, batch=64, steps=6)
GPT2_XL = dict(vocab_size=50257, n_positions=1024, d_model=1600,
               num_heads=25, num_layers=48)
# 8 slots of up to 512 positions over a pool of 96 16-token blocks: what
# fits one 16 GB chip beside 6.2 GB of fp32 weights, because the decode
# program keeps ~5x the pool in temporaries (tests/test_chip_compile.py
# compiles exactly this)
SERVE_KV = dict(num_slots=8, max_seq_len=512, kv_pool_blocks=96)
# (prompt tokens, new tokens, streamed over SSE); more requests than slots
REQUESTS = [(5, 24, False), (300, 16, True), (37, 24, False),
            (180, 16, False), (9, 32, True), (64, 16, False),
            (257, 8, False), (20, 24, False), (120, 12, False),
            (3, 40, False)]
ORACLE = 2              # the request compared with isolated generate()
TWIN = 1                # the streamed request asked again, not streamed
LLAMA = dict(vocab=32000, d=768, heads=12, kv_heads=4, layers=12,
             batch=8, seq=1024, steps=4)

TINY = dict(
    RESNET=dict(depth=18, classes=10, image=32, batch=8, steps=6),
    GPT2_XL=dict(vocab_size=97, n_positions=128, d_model=32, num_heads=4,
                 num_layers=2),
    SERVE_KV=dict(num_slots=4, max_seq_len=96, kv_pool_blocks=16),
    REQUESTS=[(5, 8, False), (60, 6, True), (17, 8, False), (33, 6, False),
              (9, 10, True), (3, 12, False)],
    LLAMA=dict(vocab=64, d=32, heads=4, kv_heads=2, layers=2, batch=8,
               seq=16, steps=4))

# two runs of one bf16 program that differ only in where partial sums are
# rounded (tensor-parallel contractions, reduce-scatter order) — measured
# spread on the chip is in PERF.md
LOSS_RTOL = 2e-2
# a served token may differ from isolated generate() only where the
# reference itself cannot tell the two apart: fp32 logits from one-pass
# bf16 MXU products carry ~2^-8 relative error at |logit| of a few units
TIE_ATOL = 2e-2


class Failed(Exception):
    pass


def check(ok, what):
    if not ok:
        raise Failed(what)


def say(phase, **kv):
    print(json.dumps({"phase": phase, **kv}), flush=True)


class CompileLog(logging.Handler):
    """Names of the programs JAX lowers for XLA (`jit_<function>`), read
    from the DEBUG record pxla writes for each one."""

    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []
        log = logging.getLogger(self.LOGGER)
        log.setLevel(logging.DEBUG)
        log.propagate = False       # its DEBUG records stop here ...
        log.addHandler(self)

    def emit(self, record):
        if record.msg.startswith("Compiling %s with global shapes"):
            self.names.append(str(record.args[0]))
        elif record.levelno >= logging.WARNING:     # ... warnings do not
            print(f"{record.name}: {record.getMessage()}", file=sys.stderr)


class Meter:
    """Wall, compile and peak-memory bookkeeping of one phase."""

    def __init__(self, dev):
        from bigdl_tpu import observe
        self.dev = dev
        self.compile = observe.counter("jit/compile_seconds")
        self.t0, self.c0 = time.perf_counter(), self.compile.value

    def done(self):
        stats = self.dev.memory_stats() or {}
        wall = time.perf_counter() - self.t0
        comp = self.compile.value - self.c0
        return {"compile_s": round(comp, 1), "run_s": round(wall - comp, 1),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


class Losses:
    """The `set_train_summary` seam: every step's loss."""

    def __init__(self):
        self.by_step = {}

    def add_scalar(self, name, value, step):
        if name == "Loss":
            self.by_step[int(step)] = float(value)

    def array(self, steps):
        check(sorted(self.by_step) == list(range(1, steps + 1)),
              f"losses for steps {sorted(self.by_step)}, asked {steps}")
        out = np.array([self.by_step[i] for i in range(1, steps + 1)])
        check(np.isfinite(out).all(), f"non-finite loss: {out}")
        return out


def on_devices(tree, devices, what):
    import jax
    for leaf in jax.tree.leaves(tree):
        check(set(leaf.devices()) <= set(devices),
              f"{what}: leaf on {leaf.devices()}, expected {devices}")


# ------------------------------------------------------------------ train
def train_phase(cfg, dev, compiles):
    import jax
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.models import resnet
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger

    meter = Meter(dev)
    c = cfg["RESNET"]
    rs = np.random.RandomState(SEED)
    n = c["batch"] * c["steps"]
    x = rs.randn(n, c["image"], c["image"], 3).astype(np.float32)
    y = rs.randint(0, c["classes"], n).astype(np.int32)
    model = resnet.build(depth=c["depth"], class_num=c["classes"])
    p0, s0 = model.init(jax.random.PRNGKey(SEED))
    losses = Losses()
    opt = Optimizer(model, ArrayDataSet(x, y, c["batch"], seed=SEED,
                                        drop_last=True),
                    nn.ClassNLLCriterion(), SGD(0.05, momentum=0.9),
                    seed=SEED, compute_dtype=jnp.bfloat16)
    opt.set_initial(p0, s0).set_train_summary(losses)
    opt.set_end_when(Trigger.max_iteration(c["steps"]))
    before = len(compiles.names)
    params, _ = opt.optimize()

    loss = losses.array(c["steps"])
    steps = [n for n in compiles.names[before:] if "bigdl_train_step" in n]
    check(len(steps) == 1,
          f"train step compiled {len(steps)} times, expected once")
    on_devices((params, opt.slots), [dev], "trained trees")
    moved = [float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(jax.tree.leaves(params), jax.tree.leaves(p0))]
    check(all(np.isfinite(moved)) and max(moved) > 0,
          "parameters did not change")
    n_params = sum(a.size for a in jax.tree.leaves(params))
    say("train", model=f"resnet{c['depth']}", params=int(n_params),
        batch=c["batch"], image=c["image"], compute="bfloat16",
        steps=c["steps"], loss=[round(float(v), 4) for v in loss],
        train_step_compiles=len(steps),
        max_param_change=max(moved), **meter.done())


# ------------------------------------------------------------------ serve
def _open(url, body, timeout=600.0):
    return urllib.request.urlopen(urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}), timeout=timeout)


def _post(url, body):
    with _open(url, body) as resp:
        return json.loads(resp.read().decode())


def _post_sse(url, body):
    """Tokens of a streamed generate, and the client's clock at each."""
    tokens, stamps = [], []
    with _open(url, {**body, "stream": True}) as resp:
        event = "message"
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("event:"):
                event = line.split(":", 1)[1].strip()
                if event == "done":
                    break
            elif line.startswith("data:"):
                payload = json.loads(line.split(":", 1)[1])
                check(event != "error", f"SSE error event: {payload}")
                tokens.append(int(payload["token"]))
                stamps.append(time.perf_counter())
    return tokens, stamps


def serve_phase(cfg, dev, compiles):
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.interop.huggingface import GPT2LM
    from bigdl_tpu.serve.engine import ServeEngine
    from bigdl_tpu.serve.net import LocalBackend, ServeFront

    meter = Meter(dev)
    g, reqs = cfg["GPT2_XL"], cfg["REQUESTS"]
    model = GPT2LM(**g, eos_id=g["vocab_size"] - 1)
    params, state = model.init(jax.random.PRNGKey(SEED))
    on_devices(params, [dev], "served weights")
    rs = np.random.RandomState(SEED + 1)
    prompts = [[int(t) for t in rs.randint(0, g["vocab_size"] - 1, p)]
               for p, _, _ in reqs]
    name = "gpt2"
    engine = ServeEngine()
    front = None
    try:
        engine.register(name, model, params, state, decode=True,
                        **cfg["SERVE_KV"])
        front = ServeFront(LocalBackend(engine), port=0)
        url = front.url + "/v1/generate"
        before = len(compiles.names)
        got = [None] * len(reqs)
        span = [None] * len(reqs)
        errors = []

        def client(i):
            body = {"model": name, "prompt": prompts[i],
                    "max_new_tokens": reqs[i][1], "eos_id": -1}
            try:
                time.sleep(0.05 * i)          # staggered joins
                t0 = time.perf_counter()
                if reqs[i][2]:
                    got[i], stamps = _post_sse(url, body)
                    # streamed, not buffered: the first token is on the
                    # wire before the last one is made
                    check(stamps[0] < stamps[-1], "SSE arrived in one piece")
                else:
                    got[i] = _post(url, body)["tokens"]
                span[i] = (t0, time.perf_counter())
            except Exception as e:            # noqa: BLE001 — re-raised below
                errors.append(f"request {i}: {e!r}")

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        check(not errors, "; ".join(errors))
        for i, (_, n_new, _) in enumerate(reqs):
            check(len(got[i]) == n_new,
                  f"request {i}: {len(got[i])} tokens, asked {n_new}")
        overlaps = sum(1 for i in range(len(reqs)) for j in range(i)
                       if span[i][0] < span[j][1] and span[j][0] < span[i][1])
        check(overlaps >= 1, "no two requests overlapped in time")

        # the streamed request again, whole: same tokens, and its prompt
        # blocks come from the prefix cache this time
        twin = _post(url, {"model": name, "prompt": prompts[TWIN],
                           "max_new_tokens": reqs[TWIN][1], "eos_id": -1})
        check(twin["tokens"] == got[TWIN],
              "streamed and non-streamed tokens differ")
        stats = engine.stats()[name]["decode"]
        check(stats["retired"] == len(reqs) + 1, f"retired: {stats}")
        check(stats["tokens"] == sum(r[1] for r in reqs) + reqs[TWIN][1],
              f"token count: {stats}")
        check(stats["prefix_hits"] >= 1, f"no prefix-cache hit: {stats}")
        fresh = [n for n in compiles.names[before:] if "lambda" in n]
        check(not fresh, f"decode programs compiled under traffic: {fresh}")
    finally:
        if front is not None:
            front.close()
        engine.shutdown()

    # one request against the repo's own isolated greedy decode
    prompt = jnp.asarray([prompts[ORACLE]], jnp.int32)
    n_new = reqs[ORACLE][1]
    seqs, _ = model.generate(params, state, prompt, max_new_tokens=n_new,
                             beam_size=1, eos_id=-1, kv_cache=True)
    want = [int(t) for t in np.asarray(seqs)[0, 0, prompt.shape[1]:]]
    served = got[ORACLE]
    agree = next((k for k in range(n_new) if served[k] != want[k]), n_new)
    # teacher-forced reference logits along the SERVED sequence: how far
    # each served token sits below the reference's own best choice
    full = jnp.asarray([prompts[ORACLE] + served], jnp.int32)
    logits = np.asarray(jax.jit(lambda p, t: model.apply(p, state, t)[0])(
        params, full)[0, prompt.shape[1] - 1:-1], np.float32)
    gaps = logits.max(-1) - logits[np.arange(n_new), served]
    check(np.isfinite(logits).all(), "non-finite reference logits")
    if agree < n_new:
        # past a near-tie the two sequences legitimately part ways, so
        # only the first difference is judged against isolated generate()
        tie = abs(float(logits[agree, served[agree]]
                        - logits[agree, want[agree]]))
        check(tie <= TIE_ATOL,
              f"served token {agree} = {served[agree]}, isolated generate "
              f"says {want[agree]}; reference logits differ by {tie}")
    check(float(gaps.max()) <= TIE_ATOL,
          f"a served token is {gaps.max()} below the reference argmax")
    say("serve", model="gpt2", **g, weights=str(params["wte"].dtype),
        **cfg["SERVE_KV"], requests=len(reqs) + 1, streamed=sum(
            1 for r in reqs if r[2]), overlapping_pairs=overlaps,
        tokens=stats["tokens"], prefix_hits=stats["prefix_hits"],
        slot_occupancy_mean=stats["slot_occupancy_mean"],
        step_p50_ms=stats["step_p50_ms"], ttft_p50_ms=stats["ttft_p50_ms"],
        tokens_equal_isolated_generate=agree, of=n_new,
        max_gap_below_reference_argmax=float(gaps.max()),
        **meter.done())


# ------------------------------------------------------------- four chips
def four_chip_phase(cfg, devices, compiles):
    import jax
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.interop.huggingface import LlamaLM, llama_tp_rules
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import Adam
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh

    meter = Meter(devices[0])
    c = cfg["LLAMA"]
    model = LlamaLM(c["vocab"], c["d"], c["heads"], c["kv_heads"],
                    4 * c["d"], c["layers"], tied=True)
    p0, s0 = model.init(jax.random.PRNGKey(SEED))
    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, c["vocab"],
                      (c["batch"] * c["steps"], c["seq"] + 1)).astype(np.int32)
    x, y = toks[:, :-1], toks[:, 1:]

    def run(make):
        losses = Losses()
        opt = make(ArrayDataSet(x, y, c["batch"], seed=SEED, drop_last=True),
                   # one flattened cross-entropy: TimeDistributedCriterion
                   # unrolls over the 1024 positions (PERF.md, Findings)
                   nn.TimeDistributedMaskCriterion(
                       nn.CrossEntropyCriterion(), padding_value=-1),
                   Adam(3e-4))
        opt.set_initial(p0, s0).set_train_summary(losses)
        opt.set_end_when(Trigger.max_iteration(c["steps"]))
        opt.optimize()
        return opt, losses.array(c["steps"])

    mesh = create_mesh(devices[:4], data=2, model=2, drop_trivial_axes=False)
    dist, loss4 = run(lambda ds, crit, m: DistriOptimizer(
        model, ds, crit, m, mesh=mesh, rules=llama_tp_rules(), zero1=True,
        compute_dtype=jnp.bfloat16, seed=SEED))
    one, loss1 = run(lambda ds, crit, m: Optimizer(
        model, ds, crit, m, seed=SEED, compute_dtype=jnp.bfloat16))
    on_devices((one.params, one.slots), [devices[0]], "one-chip trees")

    # parameters, optimizer slots and the batch are really spread
    def spread(tree):
        """{(global shape, one device's shard shape): leaves}, after
        checking every leaf spans all four chips with equal shards."""
        out = {}
        for leaf in jax.tree.leaves(tree):
            check(len(leaf.sharding.device_set) == 4,
                  f"leaf on {len(leaf.sharding.device_set)} devices")
            shapes = {s.data.shape for s in leaf.addressable_shards}
            check(len(leaf.addressable_shards) == 4 and len(shapes) == 1,
                  f"uneven shards {shapes}")
            key = f"{tuple(leaf.shape)}->{shapes.pop()}"
            out[key] = out.get(key, 0) + 1
        return out

    def per_device_share(tree):
        return sum(s.data.size for leaf in jax.tree.leaves(tree)
                   for s in leaf.addressable_shards[:1]) / \
            sum(leaf.size for leaf in jax.tree.leaves(tree))

    d, ff = c["d"], 4 * c["d"]
    p_spread = spread(dist.params)
    wq = dist.params["l0"]["attn"]["wq"]
    down = dist.params["l0"]["down"]["weight"]
    check(wq.addressable_shards[0].data.shape == (d, d // 2),
          f"wq shard {wq.addressable_shards[0].data.shape}")
    check(down.addressable_shards[0].data.shape == (ff // 2, d),
          f"down shard {down.addressable_shards[0].data.shape}")
    s_spread = spread(dist.slots["m"])
    share_p = per_device_share(dist.params)
    share_s = per_device_share(dist.slots["m"])
    # tensor parallelism halves the ruled weights (not the embedding or
    # the norms); ZeRO-1 splits every slot over the data axis, whatever
    # its parameter's rule (parallel/sharding.py zero1_spec)
    check(share_p < 0.75, f"params: one device holds {share_p:.2f} of all")
    check(share_s < 0.51, f"slots: one device holds {share_s:.2f} of all")
    xb, yb = dist._place_batch(x[:c["batch"]], y[:c["batch"]])
    for b in (xb, yb):
        check(len(b.sharding.device_set) == 4
              and b.addressable_shards[0].data.shape
              == (c["batch"] // 2, c["seq"]),
              f"batch shard {b.addressable_shards[0].data.shape}")

    rel = np.abs(loss4 - loss1) / np.abs(loss1)
    check(rel.max() <= LOSS_RTOL,
          f"dp x tp loss {loss4} vs one chip {loss1}: rel {rel}")
    say("four_chips", model="llama", **c, mesh=dict(mesh.shape),
        zero1=True, compute="bfloat16",
        loss_dp_tp=[round(float(v), 4) for v in loss4],
        loss_one_chip=[round(float(v), 4) for v in loss1],
        max_rel_loss_diff=float(rel.max()), device_set_size=4,
        param_shards=p_spread, slot_shards=s_spread,
        batch_shard=list(xb.addressable_shards[0].data.shape),
        param_share_per_device=round(share_p, 3),
        slot_share_per_device=round(share_s, 3), **meter.done())


# ------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp x tp trainer and its one-chip "
                         "comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX finds (a CPU "
                         "rehearsal of the control flow, not a chip run)")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")

    import jax
    from bigdl_tpu import compilecache, observe
    cache = compilecache.enable()
    observe.ensure_started()
    compiles = CompileLog()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"chip_smoke: no TPU chip — JAX found {len(devices)} x "
                 f"{dev.platform} ({dev.device_kind})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devices)}")
    cfg = TINY if args.rehearse else dict(
        RESNET=RESNET, GPT2_XL=GPT2_XL, SERVE_KV=SERVE_KV,
        REQUESTS=REQUESTS, LLAMA=LLAMA)
    say("start", platform=dev.platform, device_kind=dev.device_kind,
        count=len(devices), compile_cache=cache, rehearsal=args.rehearse)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chip_phase(cfg, devices, compiles)
        else:
            train_phase(cfg, dev, compiles)
            serve_phase(cfg, dev, compiles)
    except Failed as e:
        sys.exit(f"chip_smoke: FAILED — {e}")
    say("end", seconds=round(time.perf_counter() - t0, 1),
        compile_cache=cache,
        cache_hits=int(observe.counter("jit/cache_hits").value),
        cache_misses=int(observe.counter("jit/cache_misses").value))
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(devices)}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
