"""A training cell: `Optimizer.optimize()` under a trigger that ends it by
the clock; its first three steps against the plain reference."""

import numpy as np

import common as C
import weights

BETA1 = 0.9                 # Adam's, as the configuration's method has it
FAULTS = ("half_batch",)    # what `--control` can plant besides a mode


def trainer_parts(t):
    """The trainer, method and criterion that a configuration's `trainer`
    block names. Each table holds what the harness can drive and the plain
    reference can follow; another entry (`DistriOptimizer` over a mesh,
    another method) is code here and in `reference.py`, not data."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import method
    from bigdl_tpu.optim.local import Optimizer
    entry = C.named({"optim.local.Optimizer": Optimizer}, t["entry"],
                    "trainer.entry")
    make_method = C.named({"Adam": method.Adam}, t["method"],
                          "trainer.method")
    criterion = C.named(
        {"TimeDistributedMaskCriterion(CrossEntropyCriterion)":
         lambda: nn.TimeDistributedMaskCriterion(nn.CrossEntropyCriterion(),
                                                 padding_value=-1)},
        t["criterion"], "trainer.criterion")
    return entry, make_method(float(t["learning_rate"])), criterion()


class Losses:
    """The trainer's `set_train_summary` seam: every step's loss."""

    def __init__(self):
        self.by_step = {}

    def add_scalar(self, name, value, step):
        if name == "Loss":
            self.by_step[int(step)] = float(value)


def make_window_trigger(base, *, start_at, seconds, drain_every, on_start,
                        trace_steps=0, name="train"):
    """The harness's own `Trigger`: lets `start_at` steps pass, waits for the
    device, opens the window, and ends the run once `seconds` have gone by.
    Every `drain_every` steps it waits for the device, so that the host is
    never more than that many steps ahead of what has really been trained
    and the window closes near its length."""
    import jax
    import jax.numpy as jnp

    tick = jax.jit(lambda a: a + 1)
    one = jnp.zeros((), jnp.int32)
    jax.block_until_ready(tick(one))                # compiled before the window

    def drain():
        # the device runs programs in the order given: when this one is
        # done, so is every step dispatched before it
        jax.block_until_ready(tick(one))

    class Window(base):
        t0 = n0 = t1 = None
        trace = trace_path = None
        trace_done = False
        traced = None           # (steps, seconds) between the trace's drains

        def __call__(self, st):
            n = st["neval"]
            if self.t0 is None:
                if n >= start_at:
                    drain()
                    on_start()
                    self.t0, self.n0 = C.now(), n
                return False
            k = n - self.n0
            if trace_steps and self.trace_path is None and k >= 2:
                drain()
                self.trace_path = C.start_trace(name)
                self._tr = (C.now(), n)
            elif trace_steps and self.trace_path and not self.trace_done \
                    and n - self._tr[1] >= trace_steps:
                drain()
                self.traced = (n - self._tr[1], C.now() - self._tr[0])
                self.trace = C.stop_trace(self.trace_path)
                self.trace_done = True
            elif k % drain_every == 0:
                drain()
            return C.now() - self.t0 >= seconds

    return Window()


def _continue_from_here(opt):
    """Let the next `optimize()` go on from where the last one ended, with
    its optimizer state: the trainer's own resume seam, fed from memory
    instead of from a snapshot on disk. The trees go through the host, as
    they would on a resume, and leave the device meanwhile."""
    import jax
    trees = jax.device_get({"params": opt.params,
                            "model_state": opt.model_state,
                            "slots": opt.slots})
    opt.params = opt.model_state = opt.slots = None
    opt._resume_trees = trees


def worst_leaf_gap(ours, ref, leaves=None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    names = sorted(ref) if leaves is None else sorted(leaves)
    median = float(np.median([ref[n] for n in sorted(ref)]))
    worst, at = 0.0, None
    for n in names:
        gap = abs(ours[n] - ref[n]) / max(ref[n], median, 1e-30)
        if not gap <= worst:            # NaN is the worst of all
            worst, at = gap, n
    return float(worst), at


def moved_leaves(ref_grad_norm):
    """Leaves that Adam moves by more than round-off: those whose reference
    gradient is at least a thousandth of the median leaf's."""
    median = float(np.median(list(ref_grad_norm.values())))
    return [n for n, g in ref_grad_norm.items() if g >= 1e-3 * median]


def compare(ours, ref):
    """The numbers of a training cell (see PERF.md, section 2)."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(ours["loss"], ref["loss"]))
    grad_gap, grad_at = worst_leaf_gap(ours["grad_norm"], ref["grad_norm"])
    change_gap, change_at = worst_leaf_gap(
        ours["change_norm"], ref["change_norm"],
        moved_leaves(ref["grad_norm"]))
    return {"loss_gap": float(loss_gap), "grad_norm_gap": grad_gap,
            "grad_norm_gap_at": grad_at, "change_norm_gap": change_gap,
            "change_norm_gap_at": change_at}


def run(env, cell):
    import jax
    import jax.numpy as jnp
    import reference
    from bigdl_tpu import observe
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.trigger import Trigger

    cfg, mix, seconds = cell["sizes"], cell["mix"], env["seconds"]
    t = cfg["trainer"]
    B, T, lr = int(t["batch"]), int(t["sequence"]), float(t["learning_rate"])
    checked, warm = int(mix["checked_steps"]), int(mix["warmup_steps"])
    seed, dev = env["seed"], env["device"]

    family = C.family_of(cfg, cell["dirs"])
    control = C.control_of(family, env.get("control"), FAULTS)
    model, _ = family.build_model(cfg)
    wdtype = jnp.dtype(cfg["weights_dtype"])
    params = family.program_params(seed, cfg, wdtype)
    C.layout_matches(model, params)
    host_params = jax.device_get(params)        # the trainer places its own
    del params
    _, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))  # no leaves
    toks = weights.train_tokens(seed, int(mix["dataset_batches"]) * B, T,
                                family.sizes(cfg)["vocab"])
    x, y = toks[:, :-1], toks[:, 1:]
    losses = Losses()
    trainer, method, criterion = trainer_parts(t)
    opt = trainer(
        model, ArrayDataSet(x, y, B, shuffle=False, drop_last=True),
        criterion, method, seed=seed & 0x7FFFFFFF,
        compute_dtype=jnp.dtype(t["compute_dtype"]))
    opt.set_initial(host_params, state).set_train_summary(losses)
    del host_params

    norms = jax.jit(reference.norms)
    norms_of_change = jax.jit(lambda a, b: reference.norms(
        jax.tree.map(jnp.subtract, a, b)))

    # ---- the first steps, through the window's own call and feed
    opt.set_end_when(Trigger.max_iteration(1))
    opt.optimize()
    m = reference.named(norms(opt.slots["m"]))
    ours = {"grad_norm": {k: v / (1.0 - BETA1) for k, v in m.items()}}
    _continue_from_here(opt)
    opt.set_end_when(Trigger.max_iteration(checked))
    opt.optimize()
    ours["change_norm"] = reference.named(norms_of_change(
        opt.params, family.program_params(seed, cfg, wdtype)))
    _continue_from_here(opt)

    # ---- the window
    snap = {}
    compiles = env["compiles"]

    def on_start():
        snap["before"] = observe.metrics.registry().snapshot()
        snap["compiled0"] = compiles.n
        snap["setup_s"] = C.now() - env["t_start"]

    trigger = make_window_trigger(
        Trigger, start_at=checked + warm, seconds=seconds,
        drain_every=int(mix["drain_every"]), on_start=on_start,
        trace_steps=6 if env["trace"] else 0, name=cell["name"])
    opt.set_end_when(trigger)
    opt.optimize()
    jax.block_until_ready(opt.params)
    t1 = C.now()
    if trigger.trace_path and not trigger.trace_done:   # a window too short
        trigger.trace = C.stop_trace(trigger.trace_path)
    after = observe.metrics.registry().snapshot()
    compiled_in_window = compiles.n - snap["compiled0"]
    steps = opt.state["neval"] - trigger.n0
    window_s = t1 - trigger.t0
    device = C.device_block(dev, env["count"], trigger.trace)
    ours["loss"] = [losses.by_step.get(i, float("nan"))
                    for i in range(1, checked + 1)]
    all_losses = list(losses.by_step.values())
    opt.params = opt.model_state = opt.slots = None
    del opt
    C.free_device_memory()

    # ---- the reference follows the first steps, once the window has closed
    batches = [(x[i * B:(i + 1) * B], y[i * B:(i + 1) * B])
               for i in range(checked)]
    rows = int(mix.get("reference_rows", 2))

    def follow(mode, batches=batches):
        return reference.train_trajectory(
            family, family.stacked(seed, cfg), cfg, batches, lr=lr,
            mode=mode, rows=min(rows, B), steps=checked)
    t_check = C.now()
    ref = follow("float32")
    got = compare(ours, ref)
    check_s = C.now() - t_check
    notes = {"steps": steps, "window_s": window_s, "compared": got,
             "check_s": check_s,
             "loss": ours["loss"], "reference_loss": ref["loss"]}
    if control == "half_batch":
        # the fault, planted in the reference put in the program's place
        half = [(bx[:B // 2], by[:B // 2]) for bx, by in batches]
        got = notes["control"] = compare(follow("float32", half), ref)
    elif control:
        # the reference in the lower precision, in the program's place
        got = notes["control"] = compare(follow(control), ref)

    checks = C.Checks()
    limits = cfg["limits"]
    # the loss's gap is printed, not held: it has no upper reading
    # (PERF.md, section 2)
    for name in ("grad_norm_gap", "change_norm_gap"):
        checks.at_most(name, got[name], limits[name])
    checks.at_most("nonfinite_losses",
                   int(sum(not np.isfinite(v) for v in all_losses)), 0)
    checks.at_most("compiles_in_window", compiled_in_window, 0)

    tokens_per_s = steps * B * T / window_s
    token_flops = family.train_token_flops(cfg, T)
    ctx = {"before": snap["before"], "after": after, "trace": trigger.trace,
           "peaks": env["peaks"], "window_s": window_s, "cfg": cfg,
           "family": family, "dirs": cell["dirs"],
           "needed_flops": steps * B * T * token_flops}
    if trigger.traced:          # the traced steps alone, for the traced run
        ctx["window_s"] = trigger.traced[1]
        ctx["needed_flops"] = trigger.traced[0] * B * T * token_flops
        ctx["traced_work"] = {"train_tokens": trigger.traced[0] * B * T,
                              "sequence": T}
    return {"attempted": steps, "failed": 0,
            "end_to_end": {"setup_s": snap["setup_s"],
                           "train_tokens_per_s": tokens_per_s},
            "ctx": ctx, "device": device, "checks": checks, "notes": notes}
