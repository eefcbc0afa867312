"""Single-host trainer — the analogue of `LocalOptimizer`
(reference: optim/LocalOptimizer.scala:45-160) and of the public `Optimizer`
builder facade (reference: optim/Optimizer.scala:602-686).

TPU-first design: the reference clones the model per core and threads
mini-batch stacks through a pool (`Engine.default.invokeAndWait2`); here one
jitted train step owns the whole chip — XLA parallelizes internally. The
distributed variant (optim/distri.py) shares this class and swaps the step
builder for a mesh-sharded one.
"""

from __future__ import annotations

import logging
import time
from collections import deque
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu import observe
from bigdl_tpu.core.module import Criterion, Module
from bigdl_tpu.optim.method import OptimMethod, SGD
from bigdl_tpu.optim.metrics import ValidationMethod, ValidationResult
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.utils import checkpoint as ckpt

log = logging.getLogger("bigdl_tpu")


class NonFiniteLossError(RuntimeError):
    """Training aborted: BIGDL_TPU_MAX_NONFINITE consecutive non-finite
    training steps. The fused path masks each bad step's update (params/
    slots hold their last good values), so the state at abort time is
    the last finite state — the retry loop can resume from the latest
    snapshot, or the operator can inspect it directly."""


# ------------------------------------------------- gradient processors
class GradientProcessor:
    """Pluggable gradient transform (reference: parameters/
    ParameterOperations.scala — ConstantClippingProcessor,
    L2NormClippingProcessor)."""

    def __call__(self, grads, params):
        return grads


class ConstantClipping(GradientProcessor):
    def __init__(self, min_value: float, max_value: float):
        self.min_value, self.max_value = min_value, max_value

    def __call__(self, grads, params):
        return jax.tree.map(
            lambda g: jnp.clip(g, self.min_value, self.max_value), grads)


class L2NormClipping(GradientProcessor):
    """Global-norm clip (reference: L2NormClippingProcessor —
    the cross-node sqsum is free here: grads are already global)."""

    def __init__(self, max_norm: float):
        self.max_norm = max_norm

    def __call__(self, grads, params):
        sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
        norm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, self.max_norm / (norm + 1e-12))
        return jax.tree.map(lambda g: g * scale, grads)


class _StepEntry:
    """One built train/eval program: the jitted callable plus, after
    `precompile()`, its AOT-compiled executable. Calling the entry
    prefers the AOT executable (zero trace, zero compile on first use);
    an argument-spec mismatch falls back to the jitted path once and
    logs — the mismatch TypeError is raised during argument checking,
    before any donation happens, so the inputs are still alive."""

    __slots__ = ("jitted", "aot")

    def __init__(self, jitted):
        self.jitted = jitted
        self.aot = None

    def __call__(self, *args):
        if self.aot is not None:
            try:
                return self.aot(*args)
            except TypeError as e:
                log.warning(
                    "precompiled executable rejected the live inputs "
                    "(%s); falling back to the jitted path", e)
                self.aot = None
        return self.jitted(*args)


class Optimizer:
    """Training facade. Usage mirrors the reference:

        opt = Optimizer(model, dataset, criterion, SGD(0.01))
        opt \
           .set_validation(Trigger.every_epoch(), val_dataset, [Top1Accuracy()]) \
           .set_checkpoint("/tmp/ck", Trigger.every_epoch()) \
           .set_end_when(Trigger.max_epoch(10))
        params, model_state = opt.optimize()

    `dataset` is any object with `__iter__` yielding (x, y) numpy/jnp batches
    per epoch (see bigdl_tpu.dataset). All batches must share one shape —
    XLA compiles one program (use the pipeline's fixed-size batcher).
    """

    _live_instances = 0

    def __init__(self, model: Module, dataset, criterion: Criterion,
                 optim_method: Optional[OptimMethod] = None,
                 seed: Optional[int] = None,
                 steps_per_call: Optional[int] = None,
                 accum_steps: Optional[int] = None,
                 compute_dtype=None):
        from bigdl_tpu.utils import config
        if seed is None:
            seed = config.get("SEED")
        if compute_dtype is None \
                and config.get("COMPUTE_DTYPE") == "bfloat16":
            # bf16 forward/backward over fp32 master weights (reference:
            # FP16 wire compression + fp32 master copy)
            compute_dtype = jnp.bfloat16
        self.compute_dtype = compute_dtype
        if steps_per_call is None:
            steps_per_call = config.get("STEPS_PER_CALL")
        if accum_steps is None:
            accum_steps = config.get("ACCUM_STEPS")
        if steps_per_call < 1 or accum_steps < 1:
            raise ValueError(
                f"steps_per_call ({steps_per_call}) and accum_steps "
                f"({accum_steps}) must be >= 1")
        self.steps_per_call = int(steps_per_call)
        self.accum_steps = int(accum_steps)
        Optimizer._live_instances += 1
        if config.get("CHECK_SINGLETON") and Optimizer._live_instances > 1:
            log.warning(
                "multiple Optimizer instances in one process "
                "(BIGDL_TPU_CHECK_SINGLETON is set; reference: "
                "bigdl.check.singleton)")
        self.model, self.dataset, self.criterion = model, dataset, criterion
        self.method = optim_method or SGD(1e-2)
        self.end_when: Trigger = Trigger.max_epoch(1)
        self.val_trigger: Optional[Trigger] = None
        self.val_dataset = None
        self.val_methods: Sequence[ValidationMethod] = ()
        self.ckpt_path: Optional[str] = None
        self.ckpt_trigger: Optional[Trigger] = None
        self.grad_processors: List[GradientProcessor] = []
        self.seed = seed
        self.state: Dict = {"epoch": 0, "neval": 0, "records": 0,
                            "batch_in_epoch": 0}
        from bigdl_tpu.utils import config as _config
        self._log_every = max(1, _config.get("LOG_THROUGHPUT_EVERY"))
        self._summary = None
        self._val_summary = None
        # built-program cache (compile-latency subsystem,
        # docs/compile_cache.md): resume/retry and repeated optimize()
        # calls reuse the SAME jitted objects — a fresh jax.jit per
        # optimize() used to retrace and recompile programs the trainer
        # already had. Keyed by the config that shapes the program;
        # builder setters that change a captured closure clear it.
        self._built_steps: Dict[tuple, _StepEntry] = {}
        self._valid_masks: Dict[tuple, object] = {}
        # non-finite step guard (docs/resilience.md): consecutive bad
        # steps observed at flush time; abort past the knob's budget
        self._max_nonfinite = _config.get("MAX_NONFINITE")
        self._nonfinite_run = 0
        # in-run slice failover (resilience/failover.py): a pending
        # ("lose", idx) / ("grow", None) event the epoch loop applies at
        # the K-boundary it was detected on
        self._failover_pending = None

    # ------------------------------------------------------------- builders
    def set_optim_method(self, method: OptimMethod):
        self.method = method
        self._built_steps.clear()        # method is a closure capture
        return self

    def set_end_when(self, trigger: Trigger):
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset,
                       methods: Sequence[ValidationMethod]):
        self.val_trigger, self.val_dataset, self.val_methods = \
            trigger, dataset, list(methods)
        return self

    def set_checkpoint(self, path: str, trigger: Trigger):
        self.ckpt_path, self.ckpt_trigger = path, trigger
        return self

    def set_gradient_clipping_by_l2_norm(self, max_norm: float):
        self.grad_processors.append(L2NormClipping(max_norm))
        self._built_steps.clear()        # processors are closure captures
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float):
        self.grad_processors.append(ConstantClipping(min_v, max_v))
        self._built_steps.clear()
        return self

    def set_steps_per_call(self, k: int):
        """Fused dispatch: run K optimizer steps per jitted call via
        lax.scan (BIGDL_TPU_STEPS_PER_CALL). Triggers and counters advance
        in K-sized strides — validation/checkpoint/end_when fire at the
        next K boundary after their nominal iteration (documented in
        docs/performance.md). K=1 keeps today's per-step dispatch
        bit-identical."""
        if k < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {k}")
        self.steps_per_call = int(k)
        return self

    def set_accum_steps(self, m: int):
        """Gradient accumulation: split each batch into M microbatches
        inside the jitted step, average their gradients, apply one
        optimizer update (BIGDL_TPU_ACCUM_STEPS). The batch dimension must
        divide by M. Composes with steps_per_call — both run in the same
        jitted program."""
        if m < 1:
            raise ValueError(f"accum_steps must be >= 1, got {m}")
        self.accum_steps = int(m)
        return self

    def set_train_summary(self, summary):
        self._summary = summary
        return self

    def set_val_summary(self, summary):
        self._val_summary = summary
        return self

    # ------------------------------------------------------------ step build
    def _make_step(self, compute_dtype=None) -> Callable:
        """The un-jitted train-step body, shared by the local and
        distributed trainers (parallel.DistriOptimizer only adds mesh
        shardings around it). `compute_dtype` enables bf16 forward/backward
        with fp32 master weights — the TPU-native form of the reference's
        FP16 wire compression (parameters/FP16CompressedTensor.scala)."""
        from bigdl_tpu.core.module import cast_floating
        model, criterion = self.model, self.criterion
        processors = list(self.grad_processors)
        frozen = any(m._frozen for m in model.modules())
        exchange = self._grad_exchange_fn()
        method_update = self._resolve_update_fn()

        def step(params, model_state, slots, x, y, lr, step_num, rng):
            def loss_fn(p):
                pc = cast_floating(p, compute_dtype) if compute_dtype else p
                xc = (x.astype(compute_dtype)
                      if compute_dtype and jnp.issubdtype(x.dtype, jnp.floating)
                      else x)
                out, new_ms = model.apply(pc, model_state, xc,
                                          training=True, rng=rng)
                if compute_dtype:
                    out = jax.tree.map(
                        lambda o: o.astype(jnp.float32)
                        if jnp.issubdtype(o.dtype, jnp.floating) else o, out)
                return criterion.forward(out, y), new_ms

            (loss, new_ms), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if compute_dtype:
                grads = cast_floating(grads, jnp.float32)
            grads = exchange(grads)
            for proc in processors:
                grads = proc(grads, params)
            if not frozen:
                new_params, new_slots = method_update(params, grads, slots,
                                                      lr, step_num)
            else:
                # Restore frozen leaves after the update so weight decay /
                # momentum cannot move them either (freeze must win over
                # every update rule).
                tm = model.trainable_mask(params)
                old_params = params
                new_params, new_slots = method_update(params, grads, slots,
                                                      lr, step_num)
                new_params = jax.tree.map(
                    lambda trainable, new, old: new if trainable is True
                    else (old if trainable is False
                          else jnp.where(trainable, new, old)),
                    tm, new_params, old_params)
            return new_params, new_ms, new_slots, loss

        # the jitted name lands in the persistent compile-cache key
        # (jit_bigdl_train_step-<hash>), so `compilecache stats` and the
        # bench can count train-step program variants by name
        step.__name__ = "bigdl_train_step"
        step.__qualname__ = "bigdl_train_step"
        return step

    def _make_accum_step(self, accum_steps: int, compute_dtype=None) -> Callable:
        """Gradient-accumulation variant of `_make_step`: the batch is
        split into `accum_steps` microbatches, an inner `lax.scan` averages
        their gradients (model_state threaded sequentially, so BN running
        stats see every microbatch), then ONE optimizer update is applied —
        the reference's mini-batch aggregation (DistriOptimizer sums
        sub-batch gradients before the update). Same signature as the
        `_make_step` body, so the fused dispatcher scans over either.
        Per-microbatch rng is `fold_in(rng, microbatch_index)` (dropout
        masks differ across microbatches)."""
        from bigdl_tpu.core.module import cast_floating
        model, criterion = self.model, self.criterion
        processors = list(self.grad_processors)
        frozen = any(m._frozen for m in model.modules())
        exchange = self._grad_exchange_fn()
        method_update = self._resolve_update_fn()
        M = accum_steps

        def step(params, model_state, slots, x, y, lr, step_num, rng):
            if x.shape[0] % M:
                raise ValueError(
                    f"batch of {x.shape[0]} rows does not divide into "
                    f"accum_steps={M} microbatches")
            xs = x.reshape((M, x.shape[0] // M) + x.shape[1:])
            ys = y.reshape((M, y.shape[0] // M) + y.shape[1:])

            def grad_one(ms, xm, ym, r):
                def loss_fn(p):
                    pc = cast_floating(p, compute_dtype) if compute_dtype \
                        else p
                    xc = (xm.astype(compute_dtype)
                          if compute_dtype
                          and jnp.issubdtype(xm.dtype, jnp.floating)
                          else xm)
                    out, new_ms = model.apply(pc, ms, xc,
                                              training=True, rng=r)
                    if compute_dtype:
                        out = jax.tree.map(
                            lambda o: o.astype(jnp.float32)
                            if jnp.issubdtype(o.dtype, jnp.floating) else o,
                            out)
                    return criterion.forward(out, ym), new_ms

                (loss, new_ms), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                if compute_dtype:
                    grads = cast_floating(grads, jnp.float32)
                return loss, new_ms, grads

            def body(carry, inp):
                ms, gsum, lsum = carry
                xm, ym, m_idx = inp
                loss, new_ms, grads = grad_one(
                    ms, xm, ym, jax.random.fold_in(rng, m_idx))
                gsum = jax.tree.map(jnp.add, gsum, grads)
                return (new_ms, gsum, lsum + loss), None

            (new_ms, gsum, lsum), _ = jax.lax.scan(
                body,
                (model_state, jax.tree.map(jnp.zeros_like, params),
                 jnp.float32(0.0)),
                (xs, ys, jnp.arange(M)))
            # equal-sized microbatches: mean of per-microbatch mean losses
            # and gradients equals the full-batch mean
            grads = jax.tree.map(lambda g: g / M, gsum)
            loss = lsum / M
            grads = exchange(grads)
            for proc in processors:
                grads = proc(grads, params)
            if not frozen:
                new_params, new_slots = method_update(params, grads, slots,
                                                      lr, step_num)
            else:
                tm = model.trainable_mask(params)
                old_params = params
                new_params, new_slots = method_update(params, grads, slots,
                                                      lr, step_num)
                new_params = jax.tree.map(
                    lambda trainable, new, old: new if trainable is True
                    else (old if trainable is False
                          else jnp.where(trainable, new, old)),
                    tm, new_params, old_params)
            return new_params, new_ms, new_slots, loss

        return step

    def _make_fused_step(self, accum_steps: int = 1,
                         compute_dtype=None) -> Callable:
        """One XLA program that runs K optimizer steps back-to-back:
        `lax.scan` over the per-step body (plain `_make_step` when
        accum_steps == 1, the accumulating body otherwise). Inputs are the
        K-stacked (xs, ys) super-batch plus per-step (lr, neval, rng)
        threaded as scan inputs AND a per-step `valid` mask; output is
        the K-stacked per-step losses, which ride the existing
        `_pending`/`_flush_metrics` buffering unchanged.

        Single-variant shape bucketing: epoch tails used to stream with
        leading dim 1, compiling a SECOND program variant per config and
        paying its cold compile on the first short epoch. Now the tail
        is padded to the same [K, ...] super-batch with `valid[i]=False`
        on the pad rows: a masked step takes the `lax.cond` skip branch,
        so it contributes zero gradient, does not advance params/
        model_state/slots, and costs no compute at runtime (cond is a
        real branch inside the scan loop, not a select). Each trainer
        config therefore compiles exactly ONE train-step program —
        tail epochs included.

        Non-finite step guard: each live step's loss and UPDATED trees
        are probed with a cheap device-side all-finite reduce (the
        updated params embed the gradients, so a NaN/Inf anywhere in
        loss or grads trips it); a bad step's update is MASKED — params/
        model_state/slots keep their previous values, exactly as if the
        step were skipped — while its (non-finite) loss still flows to
        the host, where `_flush_metrics` counts `train/nonfinite_steps`
        and aborts after BIGDL_TPU_MAX_NONFINITE consecutive bad steps
        instead of silently training on NaNs. An all-finite step takes
        the jnp.where true-branch bitwise unchanged, so the unfused
        -oracle equivalence is preserved."""
        body_step = (self._make_step(compute_dtype) if accum_steps == 1
                     else self._make_accum_step(accum_steps, compute_dtype))

        def bigdl_fused_train_step(params, model_state, slots,
                                   xs, ys, lrs, step_nums, rngs, valid):
            def body(carry, inp):
                x, y, lr, n, r, v = inp

                def run(c):
                    p0, ms0, sl0 = c
                    p1, ms1, sl1, loss = body_step(p0, ms0, sl0, x, y,
                                                   lr, n, r)
                    ok = jnp.isfinite(loss)
                    for leaf in jax.tree.leaves(p1):
                        if jnp.issubdtype(leaf.dtype, jnp.inexact):
                            ok = jnp.logical_and(
                                ok, jnp.all(jnp.isfinite(leaf)))

                    def pick(new, old):
                        return jax.tree.map(
                            lambda a, b: jnp.where(ok, a, b), new, old)

                    return (pick(p1, p0), pick(ms1, ms0),
                            pick(sl1, sl0)), loss

                def skip(c):
                    return c, jnp.float32(0.0)

                return jax.lax.cond(v, run, skip, carry)

            (params, model_state, slots), losses = jax.lax.scan(
                body, (params, model_state, slots),
                (xs, ys, lrs, step_nums, rngs, valid))
            return params, model_state, slots, losses

        return bigdl_fused_train_step

    def _resolve_update_fn(self) -> Callable:
        """The optimizer-update callable captured at step-build time:
        `method.update` (the tree-map oracle — bit-identical to every
        pre-fused-kernel build), or the fused one-pass kernel
        (kernels/fused_update.py) when BIGDL_TPU_FUSED_UPDATE=1 and the
        method has a fused form (Adam/AdamW/SGD). An unsupported method
        under the flag logs once and keeps the oracle — turning the
        knob on can never change which methods train correctly."""
        from bigdl_tpu.kernels import fused_update as _fu
        mode = _fu.configured_mode()
        if mode is None:
            return self.method.update
        opts = self._fused_update_opts()
        if mode in ("flat", "leaf"):     # explicit layout override
            opts["layout"] = mode
        fn = _fu.make_update_fn(self.method, **opts)
        if fn is None:
            if not getattr(self, "_warned_fused_update", False):
                self._warned_fused_update = True
                log.warning(
                    "BIGDL_TPU_FUSED_UPDATE=1 but %s has no fused kernel "
                    "(supported: Adam/AdamW/SGD) — using the tree-map "
                    "update", type(self.method).__name__)
            return self.method.update
        return fn

    def _fused_update_opts(self) -> Dict:
        """Layout options for the fused update — the local trainer lets
        the kernel pick (flat+Pallas on TPU, leaf elsewhere);
        DistriOptimizer overrides to preserve ZeRO-1/TP shardings
        (parallel/distri.py)."""
        return {"layout": "auto"}

    def _build_step(self) -> Callable:
        return jax.jit(self._make_step(self.compute_dtype),
                       donate_argnums=(0, 1, 2))

    def _build_fused_step(self) -> Callable:
        # local trainer: jit with donation; the distributed trainer
        # overrides this with mesh shardings for the stacked batches
        return jax.jit(
            self._make_fused_step(self.accum_steps, self.compute_dtype),
            donate_argnums=(0, 1, 2))

    # ------------------------------------------------- built-program cache
    def _step_key(self, kind: str) -> tuple:
        """Cache key for a built program: everything a builder closure
        captures that can change between builds of one trainer instance.
        Model/criterion/mesh are fixed per instance; the optim method is
        handled by set_optim_method clearing the cache."""
        from bigdl_tpu.kernels import fused_update as _fu
        dcn = self._dcn_config()
        return (kind, self.steps_per_call, self.accum_steps,
                str(self.compute_dtype),
                tuple(id(p) for p in self.grad_processors),
                any(m._frozen for m in self.model.modules()),
                # env-read at build: a test/process flipping the knob
                # between optimize() calls must not reuse a stale program
                _fu.configured_mode(),
                # DCN exchange config (parallel/dcn.py): the slice count
                # changes on failover, so the key re-derives it from the
                # live mesh and the rebuild compiles for the new S
                dcn.key if dcn is not None else None)

    def _get_built(self, kind: str) -> _StepEntry:
        """Memoized build of the 'step' / 'fused' / 'eval_jit' program.
        resume()/optimize_with_retry() re-enter optimize() with the same
        config — they must reuse the jitted objects, not rebuild them
        (a rebuild retraces and recompiles; the jit-compile counter made
        this cost visible)."""
        key = self._step_key(kind)
        entry = self._built_steps.get(key)
        if entry is None:
            builder = {"step": self._build_step,
                       "fused": self._build_fused_step,
                       "dcn_step": getattr(self, "_build_dcn_step", None),
                       "dcn_fused": getattr(self, "_build_dcn_fused_step",
                                            None),
                       "eval_jit": self._build_eval_jit}[kind]
            if builder is None:
                raise RuntimeError(
                    f"{kind} program requested on a trainer without the "
                    f"DCN exchange leg (parallel.DistriOptimizer only)")
            entry = _StepEntry(builder())
            self._built_steps[key] = entry
        return entry

    # ----------------------------------------------------- placement hooks
    # Overridden by parallel.DistriOptimizer to lay trees/batches out on the
    # mesh; the local trainer leaves placement to jit's defaults.
    def _place_trees(self, params, model_state, slots):
        self._ledger_register_trees(params, model_state, slots)
        return params, model_state, slots

    def _ledger_register_trees(self, params, model_state, slots):
        """Account the trainer's long-lived device trees in the memory
        ledger (observe/memz.py): `trainer/{params,slots,model_state}`
        owners, weakref-finalized against this trainer so the bytes are
        released with it. Called from `_place_trees` (both trainers), so
        a failover re-shard re-measures through the same seam. Bytes
        come from shapes host-side — no device syncs."""
        from bigdl_tpu.observe import memz as _memz
        led = _memz.ledger()
        led.register("trainer/params", params, anchor=self,
                     kind="params", note=type(self).__name__)
        led.register("trainer/slots", slots, anchor=self,
                     kind="optim_slots", note=type(self.method).__name__)
        led.register("trainer/model_state", model_state, anchor=self,
                     kind="state")

    def _grad_exchange_fn(self):
        """Seam for the cross-slice gradient exchange, captured at step
        -build time (a failover rebuild rebinds it to the new mesh) —
        identity on the local trainer; DistriOptimizer routes it through
        parallel.mesh.cross_slice_exchange."""
        return lambda grads: grads

    def _supports_failover(self) -> bool:
        """Whether this trainer can re-shard in-run on a slice event —
        the local trainer cannot (no mesh); DistriOptimizer can when its
        mesh is two-tier and the driver is single-process."""
        return False

    # ------------------------------------------------- DCN-tier exchange
    def _dcn_config(self):
        """Armed accumulate-locally / exchange-every-T configuration
        (parallel/dcn.py DcnConfig) or None. The local trainer has no
        slices to exchange across — DistriOptimizer overrides; a set
        knob on a slice-less trainer warns once and stays off."""
        from bigdl_tpu.utils import config as _cfg
        if int(_cfg.get("SLICE_EXCHANGE_EVERY")) > 1 \
                and not getattr(self, "_warned_dcn_local", False):
            self._warned_dcn_local = True
            log.warning(
                "BIGDL_TPU_SLICE_EXCHANGE_EVERY > 1 needs a two-tier "
                "('slice', 'data') DistriOptimizer mesh — the local "
                "trainer exchanges nothing, knob ignored")
        return None

    def _place_exchange_state(self, state):
        """Device placement for the DCN exchange state; the distributed
        trainer lays the per-slice accumulator rows over 'slice'."""
        return jax.tree.map(jnp.asarray, state)

    def _init_dcn_state(self, cfg):
        """Host-side exchange state for this run: resumed from the
        snapshot's `exchange` tree when present and row-compatible
        (kill-and-resume mid-window is then exact — the accumulator
        picks the window up at the same pending count), else fresh
        zeros. A mismatched slice count (snapshot from a different
        topology) warns loudly and drops the in-window contribution."""
        import numpy as _np
        from bigdl_tpu.parallel import dcn as _dcn
        rt = getattr(self, "_resume_trees", None)
        if rt is not None and "exchange" in rt:
            ex = jax.tree.map(lambda a: _np.array(a), rt["exchange"])
            lead = {leaf.shape[0]
                    for leaf in jax.tree.leaves(ex.get("acc", {}))}
            meta_t = self.state.get("exchange_every")
            if meta_t is not None and int(meta_t) != cfg.every:
                log.warning(
                    "resume: snapshot exchange_every=%s but "
                    "BIGDL_TPU_SLICE_EXCHANGE_EVERY=%d — window "
                    "boundaries shift; keep T fixed across a "
                    "kill/resume pair for exactness", meta_t, cfg.every)
            if lead == {cfg.slices}:
                has_outer = bool(ex.get("outer")) \
                    == (cfg.outer == "nesterov")
                if has_outer:
                    return ex
                log.warning(
                    "resume: snapshot outer-optimizer state does not "
                    "match BIGDL_TPU_SLICE_OUTER=%r — outer state "
                    "restarts fresh", cfg.outer)
                fresh = _dcn.init_exchange_state(
                    jax.eval_shape(self.model.init,
                                   jax.random.PRNGKey(0))[0], cfg)  # tpu-lint: disable=004
                return {**fresh, "acc": ex["acc"],
                        "residual_norm": ex.get(
                            "residual_norm", _np.float32(0.0))}
            log.warning(
                "resume: snapshot accumulator has %s slice rows but the "
                "mesh has %d — starting the exchange window fresh (the "
                "in-window contribution is dropped)",
                sorted(lead), cfg.slices)
        params_s, _ = jax.eval_shape(
            self.model.init, jax.random.PRNGKey(0))  # tpu-lint: disable=004
        return _dcn.init_exchange_state(params_s, cfg)

    def _place_batch(self, x, y):
        with observe.phase("data/placement", cat="data"):
            xd, yd = jnp.asarray(x), jnp.asarray(y)
        observe.counter("data/h2d_bytes").inc(xd.nbytes + yd.nbytes)
        return xd, yd

    def _place_stacked_batch(self, xs, ys):
        """Place a K-stacked super-batch ([K, batch, ...]) in ONE H2D
        transfer. The distributed trainer overrides this to shard the
        batch dim (dim 1) over the mesh's data axis."""
        with observe.phase("data/placement", cat="data"):
            xd, yd = jnp.asarray(xs), jnp.asarray(ys)
        observe.counter("data/h2d_bytes").inc(xd.nbytes + yd.nbytes)
        return xd, yd

    def _make_service(self):
        """The streaming input service feeding this trainer
        (dataset/service.py: background read-ahead → echo →
        [stacking →] double-buffered H2D), or None when
        BIGDL_TPU_DATA_SERVICE=0 or the dataset already places its own
        batches (PrefetchDataSet). Built per epoch pass — knob flips
        between optimize() calls must take effect (tests toggle them)."""
        from bigdl_tpu.dataset import service as _svc
        from bigdl_tpu.dataset.prefetch import PrefetchDataSet
        if isinstance(self.dataset, PrefetchDataSet) \
                or not _svc.service_enabled():
            return None
        return _svc.InputService(self.dataset,
                                 echo=getattr(self, "_echo", 1),
                                 seed=self.seed)

    def _echoed(self, it):
        """Apply data echoing to a host-batch stream on the legacy
        (service-off) feed path — echo semantics must not depend on the
        service knob. Consumes the one-shot resume echo offset."""
        echo = getattr(self, "_echo", 1)
        tr = getattr(self.dataset, "echo_transform", None)
        if echo <= 1 and tr is None:
            return it
        from bigdl_tpu.dataset import service as _svc
        skip, self._echo_skip = getattr(self, "_echo_skip", 0), 0
        return _svc.echo_batches(it, echo, skip_first=skip, transform=tr,
                                 seed=self.seed, epoch=self.state["epoch"],
                                 start_index=getattr(self, "_echo_start", 0))

    def _batch_iter(self, epoch_iter):
        """Stream (x, y) batches through the input service (background
        read-ahead + echo + double-buffered placement —
        dataset/service.py) or, with BIGDL_TPU_DATA_SERVICE=0, the
        legacy host→device prefetch so the H2D copy of batch k+1 still
        overlaps step k's compute (BIGDL_TPU_PREFETCH_SIZE=0 disables
        that too). Batch content is identical on every path."""
        from bigdl_tpu.dataset.prefetch import (PrefetchDataSet,
                                                prefetch_to_device)
        from bigdl_tpu.utils import config
        svc = self._make_service()
        if svc is not None:
            skip, self._echo_skip = getattr(self, "_echo_skip", 0), 0
            return svc.batches(
                epoch_iter, lambda b: self._place_batch(*b),
                epoch=self.state["epoch"], echo_skip=skip,
                start_index=getattr(self, "_echo_start", 0))
        size = config.get("PREFETCH_SIZE")
        it = self._echoed(epoch_iter)
        if (not size or size <= 0
                or isinstance(self.dataset, PrefetchDataSet)):
            # disabled, or the dataset already prefetches — a second
            # layer would double-buffer and double-place every batch
            return (self._place_batch(x, y) for x, y in it)
        return prefetch_to_device(
            it, size, place_fn=lambda b: self._place_batch(*b))

    def _fused_batch_iter(self, epoch_iter):
        """K-grouped variant of `_batch_iter` for the fused dispatch path:
        host batches are stacked into [K, batch, ...] super-batches BEFORE
        placement (dataset/prefetch.py stack_batches), so the K batches
        ride one H2D transfer instead of K. Yields (xs, ys, n_valid)
        triples — the epoch tail is PADDED to the same [K, ...] shape
        with n_valid < K (single-variant shape bucketing; the pad steps
        are masked out device-side). With the input service on, decode
        runs ahead on a reader thread and placement of super-batch N+1
        is double-buffered against compute of N (dataset/service.py)."""
        from bigdl_tpu.dataset.prefetch import (prefetch_to_device,
                                                stack_batches)
        from bigdl_tpu.utils import config

        def place(b):
            return self._place_stacked_batch(b[0], b[1]) + (b[2],)

        svc = self._make_service()
        if svc is not None:
            skip, self._echo_skip = getattr(self, "_echo_skip", 0), 0
            return svc.fused_batches(
                epoch_iter, self.steps_per_call, place,
                epoch=self.state["epoch"], echo_skip=skip,
                start_index=getattr(self, "_echo_start", 0))
        grouped = stack_batches(self._echoed(epoch_iter),
                                self.steps_per_call)
        size = config.get("PREFETCH_SIZE")
        if not size or size <= 0:
            return (place(b) for b in grouped)
        return prefetch_to_device(grouped, size, place_fn=place)

    def _fused_epoch_source(self):
        """The iterable the fused path stacks from. A PrefetchDataSet
        already device-places every batch — stacking those would bounce
        each batch device→host→device, so unwrap to its inner host-side
        dataset (counters/fast-forward delegate through __getattr__, so
        resume bookkeeping is unaffected)."""
        from bigdl_tpu.dataset.prefetch import PrefetchDataSet
        if isinstance(self.dataset, PrefetchDataSet):
            return self.dataset.dataset
        return self.dataset

    def _build_eval_jit(self):
        model = self.model

        def bigdl_eval_step(p, s, x):
            return model.apply(p, s, x, training=False)[0]

        return jax.jit(bigdl_eval_step)

    def _build_eval_fn(self):
        # memoized: a resume/retry re-entry of optimize() must reuse the
        # compiled eval program (DistriOptimizer wraps this with its
        # data-axis padding, sharing the same cached inner jit)
        return self._get_built("eval_jit")

    def _eval_pad_rows(self, n: int) -> int:
        """Rows the eval program is compiled for, given an n-row batch
        (DistriOptimizer pads validation batches to the data axis)."""
        return n

    # ---------------------------------------------------------- precompile
    def precompile(self, sample_batch=None, val_batch=None) -> Dict:
        """AOT warmup (docs/compile_cache.md): compile the train-step —
        and, when validation is configured, the eval — programs from
        shape specs BEFORE the first batch arrives, via
        `jit(...).lower(specs).compile()`. The compiled executables are
        attached to the built-step cache, so the first real iteration
        dispatches a ready program: zero trace, zero compile on the hot
        path. With the persistent compile cache enabled, a warm machine
        pays only deserialization here.

        Shapes come from `jax.eval_shape` on the model/optimizer init
        (no device work) plus ONE peeked host batch (`sample_batch`
        overrides the peek for datasets that cannot be re-iterated).
        XLA cost analysis per program (flops, bytes accessed, peak
        memory) is logged through the observe metrics registry
        (`compile/<program>/...`) and returned.

        CLI: `--precompile`; knob: BIGDL_TPU_PRECOMPILE (optimize()
        then calls this automatically)."""
        import numpy as _np
        from bigdl_tpu.compilecache import (key_sds, log_cost, scalar_sds,
                                            sds_like)
        if self._dcn_config() is not None:
            # the DCN step's exchange-state specs are not AOT-pinned —
            # the program compiles on first dispatch instead (served
            # warm from the persistent cache like any other program)
            log.warning("precompile: DCN exchange mode is armed — "
                        "skipping AOT warmup; the exchange step "
                        "compiles on first dispatch")
            self._precompiled = True
            return {}
        observe.ensure_started()
        use_fused = self.steps_per_call > 1 or self.accum_steps > 1
        if sample_batch is None:
            src = (self._fused_epoch_source() if use_fused
                   else self.dataset)
            sample_batch = next(iter(src))
        x, y = sample_batch[0], sample_batch[1]
        x_sds, y_sds = sds_like(x), sds_like(y)

        params_s, ms_s = jax.eval_shape(
            self.model.init, jax.random.PRNGKey(0))  # tpu-lint: disable=004
        slots_s = jax.eval_shape(self.method.init_slots, params_s)
        k_sds = key_sds()
        results: Dict = {}

        with observe.phase("compile/precompile", cat="jit"):
            t0 = time.perf_counter()
            if use_fused:
                K = self.steps_per_call
                entry = self._get_built("fused")
                stack = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
                    (K,) + tuple(s.shape), s.dtype)
                specs = self._annotate_aot_specs("fused", (
                    params_s, ms_s, slots_s, stack(x_sds), stack(y_sds),
                    jax.ShapeDtypeStruct((K,), jnp.float32),
                    jax.ShapeDtypeStruct((K,), jnp.int32),
                    stack(k_sds),
                    jax.ShapeDtypeStruct((K,), jnp.bool_)))
            else:
                entry = self._get_built("step")
                specs = self._annotate_aot_specs("step", (
                    params_s, ms_s, slots_s, x_sds, y_sds,
                    scalar_sds(jnp.float32), scalar_sds(jnp.int32),
                    k_sds))
            compiled = entry.jitted.lower(*specs).compile()
            entry.aot = compiled
            results["train_step"] = log_cost(
                "train_step", compiled, time.perf_counter() - t0)

            if val_batch is None and self.val_dataset is not None:
                val_batch = next(iter(self.val_dataset))
            if val_batch is not None:
                vx = _np.asarray(val_batch[0])
                rows = self._eval_pad_rows(vx.shape[0])
                vx_sds = jax.ShapeDtypeStruct(
                    (rows,) + tuple(vx.shape[1:]), vx.dtype)
                t0 = time.perf_counter()
                e2 = self._get_built("eval_jit")
                specs = self._annotate_aot_specs(
                    "eval_jit", (params_s, ms_s, vx_sds))
                e2.aot = e2.jitted.lower(*specs).compile()
                results["eval_step"] = log_cost(
                    "eval_step", e2.aot, time.perf_counter() - t0)

        self._precompiled = True
        return results

    def _annotate_aot_specs(self, kind: str, specs: tuple) -> tuple:
        """Hook for subclasses to pin device layouts onto the AOT shape
        specs (the local trainer compiles for jit's default placement;
        DistriOptimizer annotates mesh shardings so the precompiled
        executable accepts the live sharded trees)."""
        return specs

    # --------------------------------------------------------------- resume
    def resume(self, path: str) -> bool:
        """Load latest snapshot under `path` (mid-epoch counters included) —
        reference: DistriOptimizer retry/recovery (:886-963). The
        within-epoch batch cursor (`batch_in_epoch`) rides the snapshot
        meta, so optimize() fast-forwards the epoch's iterator instead of
        replaying finished iterations (reference:
        optim/DistriOptimizer.scala:124-134,466-474
        `recordsProcessedThisEpoch` resume).

        Exactness caveat: the cursor is a RECORD COUNT. For single-threaded
        unshuffled streams the skipped prefix is exactly the records the
        crashed run trained on; under shuffle or multi-worker decode the
        stream order differs run-to-run, so the resumed epoch may re-see
        some trained records and miss others (same contract as
        ShardedDataset.fast_forward_batches — see its docstring).

        Recovery hygiene: an in-flight background snapshot write is
        joined first (it may BE the latest snapshot), and candidates are
        CRC-validated against their manifest — uncommitted or corrupt
        snapshots are skipped, falling back to the previous good one.
        Restore is mesh-shape-agnostic: v2 shards reassemble into global
        host arrays here and optimize()'s _place_trees lays them out
        under whatever mesh is CURRENT (including re-sharding ZeRO-1
        slots), so an 8-device snapshot resumes on 4 devices and vice
        versa (resilience/elastic.py)."""
        w = getattr(self, "_ckpt_writer", None)
        if w is not None:
            w.drain()               # a failed write just means older snap
        snap = ckpt.latest_checkpoint(path, validate=True)
        if snap is None:
            return False
        trees, meta = ckpt.load_checkpoint(snap)
        self._resume_trees = trees
        meta.pop("epoch_finished", None)  # don't re-fire per-epoch triggers
        # pipeline state (dataset/service.py): the batch cursor drives
        # the fast-forward below; the rest is cross-checked against the
        # LIVE pipeline so a changed echo factor or dataset seed — which
        # would silently break the sample-exact resume contract — is at
        # least loud
        data_state = meta.pop("data_state", None)
        if data_state is not None:
            from bigdl_tpu.dataset import service as _svc
            from bigdl_tpu.utils import config as _cfg
            for problem in _svc.validate_state(
                    self.dataset, data_state,
                    max(1, int(_cfg.get("DATA_ECHO")))):
                log.warning("resume data_state: %s", problem)
        # counters rewind on resume — the validate/checkpoint dedup marks
        # from the failed run must not suppress the replayed iterations
        self.__dict__.pop("_last_val_neval", None)
        self.__dict__.pop("_last_ckpt_neval", None)
        self.state.update(meta)
        log.info("resumed from %s at %s", snap, meta)
        return True

    def set_initial(self, params, model_state=None) -> "Optimizer":
        """Start training from given (imported / pre-trained) trees instead
        of fresh init — the facade for fine-tuning importer outputs
        (reference: Optimizer takes the user's model instance with its
        current weights).

        Donation safety: optimize() copies these trees before handing them
        to the donating jitted step, so the caller's buffers survive.
        With `model_state` omitted, a fresh state skeleton is initialised
        from the model (containers index per-child state — an empty dict
        would KeyError at the first forward)."""
        if model_state is None:
            _, model_state = self.model.init(jax.random.PRNGKey(self.seed))
        self._initial_trees = {"params": params, "model_state": model_state}
        self._resume_trees = dict(self._initial_trees)
        return self

    def _observed_batches(self, it):
        """Yield batches, timing the train loop's wait on each one (span
        `train/data_wait`). With prefetch on this is pure queue wait —
        host pipeline + H2D run in the worker thread and show up in the
        trace as `data/placement` spans on that thread; with prefetch off
        it includes the inline decode + placement.

        The `train/step_wall_s` histogram records the FULL period between
        successive batch requests (data wait + everything the loop body
        did with the previous batch) — the honest denominator for the
        data-wait fraction (observe.metrics.data_wait_fraction): summing
        only the instrumented phases would drop uninstrumented loop time
        and overstate the fraction."""
        it = iter(it)
        phase = observe.phase
        wall = observe.histogram("train/step_wall_s")
        last = None
        while True:
            now = time.perf_counter()
            if last is not None:
                wall.record(now - last)
            last = now
            with phase("train/data_wait"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            yield batch

    # -------------------------------------------------------------- optimize
    def optimize(self) -> Tuple[Dict, Dict]:
        """Run training to `end_when`. Crash forensics seam
        (observe/doctor.py): a NonFiniteLossError or any other unhandled
        training exception dumps a self-contained forensics bundle
        (ring spans, metrics snapshot, statusz JSON, live config, the
        trainer state + data_state) before propagating — the retry loop
        and the operator both get the post-mortem for free."""
        try:
            return self._optimize_impl()
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            from bigdl_tpu.observe import doctor as _doctor
            from bigdl_tpu.observe import memz as _memz
            extra = {"trainer": type(self).__name__}
            try:
                extra.update(self._snapshot_extra_meta())
            except Exception:          # noqa: BLE001 — forensics is best-effort
                pass
            # a device allocation failure gets its own reason so the
            # bundle's memory.json + memory.prof (OOM forensics,
            # observe/memz.py) lead the post-mortem
            if isinstance(e, NonFiniteLossError):
                reason = "nonfinite-loss"
            elif _memz.is_oom(e):
                reason = "resource-exhausted"
            else:
                reason = "optimize-exception"
            _doctor.dump_forensics(
                reason, exc=e, state=dict(self.state), extra=extra)
            raise

    def _optimize_impl(self) -> Tuple[Dict, Dict]:
        # flight recorder (observe/): knob-gated trace spans + metrics
        # exporters + the statusz live telemetry plane; a disabled
        # recorder costs one attribute check per span site
        # (BIGDL_TPU_TRACE / _METRICS_* / _STATUSZ_PORT —
        # docs/observability.md)
        observe.ensure_started()
        # run-shape gauges for /statusz (host-side ints, no syncs)
        observe.gauge("train/steps_per_call").set(self.steps_per_call)
        # compile-latency subsystem (docs/compile_cache.md): optional
        # AOT warmup (the persistent cache is the entry point's to
        # enable — compilecache.enable())
        from bigdl_tpu.utils import config as _cfg
        if _cfg.get("PRECOMPILE") and not getattr(self, "_precompiled",
                                                  False):
            self.precompile()
        # a retry re-entry must not replay a slice event or a non-finite
        # run that died with the previous attempt
        self._failover_pending = None
        self._nonfinite_run = 0
        # data echoing factor (dataset/service.py; Choi et al.): read
        # once per optimize() so the cursor math below and the snapshot
        # data_state agree for the whole run
        self._echo = max(1, int(_cfg.get("DATA_ECHO")))
        rng = jax.random.PRNGKey(self.seed)
        # disjoint key namespace from the 0xBD1 init fold below — a step
        # key derived straight from (rng, neval) would collide with the
        # init key at iteration 0xBD1
        step_rng = jax.random.fold_in(rng, 0x57E9)
        if hasattr(self, "_resume_trees"):
            # copy before handing to the donating step: _resume_trees (and
            # any caller alias of it) must survive the donation. HOST-side
            # copy (np, not jnp): resume trees are npz-loaded numpy
            # already, and a device-side jnp.array copy would compile one
            # tiny convert program per leaf shape — the retry/resume
            # re-entry must stay at zero fresh compiles
            # (tests/test_compile_cache.py retrace-hygiene contract)
            import numpy as _np
            copy = lambda t: jax.tree.map(lambda a: _np.array(a), t)  # noqa: E731
            params = copy(self._resume_trees["params"])
            model_state = copy(self._resume_trees["model_state"])
            slots = copy(self._resume_trees["slots"]) \
                if "slots" in self._resume_trees \
                else self.method.init_slots(params)
        else:
            params, model_state = self.model.init(
                jax.random.fold_in(rng, 0xBD1))
            slots = self.method.init_slots(params)
        params, model_state, slots = self._place_trees(params, model_state, slots)
        # DCN-tier exchange (parallel/dcn.py): arm the per-slice
        # accumulator + outer state when the knobs and mesh call for it;
        # refreshed after a failover re-shard (_apply_failover)
        self._dcn_cfg = self._dcn_config()
        if self._dcn_cfg is not None:
            from bigdl_tpu.parallel import dcn as _dcn
            self._dcn_state = self._place_exchange_state(
                self._init_dcn_state(self._dcn_cfg))
            self._dcn_wire_bytes = _dcn.wire_bytes_per_exchange(
                params, self._dcn_cfg.compress)
            observe.gauge("exchange/window").set(self._dcn_cfg.every)
            observe.gauge("exchange/pending_steps").set(
                self.state.get("neval", 0) % self._dcn_cfg.every)
        else:
            self._dcn_state = None
        self._step_rng = step_rng
        # steps_per_call == accum_steps == 1 takes the pre-existing
        # per-step dispatch path bit-identically (same step builder, same
        # loop); anything else compiles the fused K-step scan program.
        # Programs come from the built-step cache: a resume/retry
        # re-entry reuses the jitted callables instead of rebuilding
        # them (retrace hygiene — docs/compile_cache.md)
        use_fused = self.steps_per_call > 1 or self.accum_steps > 1
        st = self.state

        # Losses are NOT fetched per step: pending (iter, lr, loss) tuples
        # buffer the device values and are flushed to host on the log
        # cadence (or right before validation/checkpoint), so step
        # dispatches run back-to-back and the chip never idles on a
        # Python-side sync. (The reference's driver logs from returned
        # accumulators, not per-replica syncs —
        # optim/DistriOptimizer.scala:410-418.)
        self._pending: List[tuple] = []
        self._window_t0 = time.time()
        self._window_records = 0
        # bounded: long runs used to grow this list forever; the full
        # distribution lives in the phase/train/checkpoint log-bucket
        # histogram (observe/metrics.py), this deque keeps only the
        # newest samples
        self._ckpt_stalls: "deque[float]" = deque(maxlen=256)
        if self.ckpt_path is not None:
            from bigdl_tpu.utils import config as _cfg
            if _cfg.get("CHECKPOINT_ON_PREEMPT"):
                # SIGTERM (TPU-VM preemption notice) -> one final
                # checkpoint at the next step/K boundary, clean stop
                from bigdl_tpu.resilience import faults as _faults
                _faults.install_sigterm_handler()

        while not self.end_when(st):
            # built programs are looked up per epoch pass, not hoisted:
            # a slice failover (resilience/failover.py) invalidates the
            # built-step cache mid-run, and the re-entered pass must
            # pick up the programs compiled for the NEW topology
            dcn = self._dcn_state is not None
            step = None if use_fused else self._get_built(
                "dcn_step" if dcn else "step")
            fused_step = self._get_built(
                "dcn_fused" if dcn else "fused") if use_fused else None
            self._eval_fn = self._build_eval_fn()
            epoch_start = time.time()
            epoch_records = 0
            ended_mid_epoch = False
            # keep the dataset's shuffle epoch in lockstep with the trainer
            # (a freshly constructed dataset starts at epoch 0; after a
            # resume the permutation must match the interrupted epoch)
            if hasattr(self.dataset, "set_epoch"):
                self.dataset.set_epoch(st["epoch"])
            # mid-epoch resume: skip the already-trained batches instead of
            # replaying them (the per-step rng is derived from neval, so
            # the surviving iterations see the same stream a crash-free run
            # would). Datasets exposing fast_forward_batches skip at the
            # record-reader level (no decode); others consume and discard.
            # the cursor counts TRAINED batches; with data echoing each
            # dataset batch trains _echo times, so the dataset skip is
            # cursor // echo and the current batch resumes at its
            # cursor % echo-th echo (the snapshot data_state's echo
            # counter — dataset/service.py)
            skip = st.get("batch_in_epoch", 0)
            echo = getattr(self, "_echo", 1)
            ds_skip, self._echo_skip = (divmod(skip, echo) if echo > 1
                                        else (skip, 0))
            self._echo_start = ds_skip
            if ds_skip > 0:
                log.info("mid-epoch resume: fast-forwarding %d dataset "
                         "batches of epoch %d (cursor %d%s)",
                         ds_skip, st["epoch"], skip,
                         f", echo offset {self._echo_skip}"
                         if echo > 1 else "")
                if hasattr(self.dataset, "fast_forward_batches"):
                    self.dataset.fast_forward_batches(ds_skip)
                    ds_skip = 0
            epoch_iter = (iter(self._fused_epoch_source()) if use_fused
                          else iter(self.dataset))
            if ds_skip > 0:
                # consume-and-discard fallback: decodes every skipped
                # batch, so a late-epoch resume can cost close to a full
                # epoch replay — datasets wanting cheap resume implement
                # fast_forward_batches (record-level skip, no decode)
                t_ff = time.time()
                skipped = 0
                for _ in range(ds_skip):
                    try:
                        next(epoch_iter)
                    except StopIteration:
                        break
                    skipped += 1
                log.info("fast-forward consumed %d/%d batches in %.1fs",
                         skipped, ds_skip, time.time() - t_ff)
            # nan@step:N injection (resilience/faults.py): wrap the raw
            # stream AFTER the cursor skip so batch i trains iteration
            # neval + i + 1 — identity when no nan event is armed
            from bigdl_tpu.resilience import faults as _faults
            epoch_iter = _faults.poison_nan_stream(epoch_iter, st["neval"])
            if use_fused:
                (params, model_state, slots, epoch_records,
                 ended_mid_epoch) = self._fused_epoch(
                    fused_step, epoch_iter, params, model_state, slots, st)
            for xd, yd in (() if use_fused else
                           self._observed_batches(
                               self._batch_iter(epoch_iter))):
                lr = self.method.current_lr(st)
                sub = jax.random.fold_in(step_rng, st["neval"])
                if self._param_summary_enabled():
                    # batch refs only (never donated) — lets the Parameters
                    # summary recompute gradients on its cadence
                    self._last_batch = (xd, yd, sub)
                with observe.phase("train/dispatch"):
                    # async dispatch latency: the time Python takes to
                    # hand XLA the step, NOT device compute (which the
                    # flush span pays when it fetches the losses)
                    if self._dcn_state is not None:
                        # accumulator threaded through every call — the
                        # exchange fires inside the program on window
                        # boundaries (no extra host syncs)
                        (params, model_state, slots, self._dcn_state,
                         loss) = step(
                            params, model_state, slots, self._dcn_state,
                            xd, yd, jnp.float32(lr),
                            jnp.int32(st["neval"]), sub)
                    else:
                        params, model_state, slots, loss = step(
                            params, model_state, slots, xd, yd,
                            jnp.float32(lr), jnp.int32(st["neval"]), sub)
                # GLOBAL batch dim (multi-host _place_batch assembles the
                # global array): records/throughput count the whole job's
                # progress, the reference's recordsProcessedThisEpoch
                # semantic — and every process agrees on the count, so
                # triggers fire in lockstep
                n = xd.shape[0]
                st["neval"] += 1
                st["records"] += n
                st["batch_in_epoch"] = st.get("batch_in_epoch", 0) + 1
                # st["loss"] stays the last *flushed* float — storing the
                # device value here would let loss-based triggers force a
                # per-step sync. min_loss stopping granularity is therefore
                # the log cadence.
                epoch_records += n
                self._window_records += n
                self._pending.append((st["neval"], lr, loss))
                if st["neval"] % self._log_every == 0:
                    self._flush_metrics(st)
                self._maybe_param_summary(params, model_state, st)
                self._maybe_validate(params, model_state, st)
                self._maybe_checkpoint(params, model_state, slots, st)
                if self._check_resilience(params, model_state, slots, st):
                    ended_mid_epoch = True
                    break
                if self.end_when(st):
                    ended_mid_epoch = True
                    break
            self._flush_metrics(st)
            if self._failover_pending is not None:
                # in-run slice failover (resilience/failover.py): re-shard
                # onto the new topology at this K-boundary and RE-ENTER
                # the epoch at the batch cursor — the while loop's
                # fast-forward path re-groups the remaining batches, so
                # the run loses nothing past the last completed boundary
                params, model_state, slots = self._apply_failover(
                    params, model_state, slots, st)
                continue
            if ended_mid_epoch:
                # partial epoch: don't advance counters or fire per-epoch
                # triggers — a resume picks the epoch up at batch_in_epoch
                break
            st["epoch"] += 1
            st["batch_in_epoch"] = 0
            st["epoch_finished"] = True
            dur = time.time() - epoch_start
            observe.instant("train/epoch_end", cat="train",
                            args={"epoch": st["epoch"] - 1,
                                  "records": epoch_records})
            log.info("epoch %d done: %d records in %.1fs (%.1f rec/s)",
                     st["epoch"] - 1, epoch_records, dur, epoch_records / max(dur, 1e-9))
            self._maybe_param_summary(params, model_state, st)
            self._maybe_validate(params, model_state, st)
            self._maybe_checkpoint(params, model_state, slots, st)
            st["epoch_finished"] = False

        self._flush_metrics(st)
        self._finish_checkpoints()         # join any background snapshot

        trace_path = observe.finish()      # dump trace + final export flush
        if trace_path:
            log.info("flight-recorder trace -> %s "
                     "(chrome://tracing / ui.perfetto.dev)", trace_path)

        self._last_batch = None            # release pinned device buffers
        self.params, self.model_state, self.slots = params, model_state, slots
        return params, model_state

    # ------------------------------------------------- fused dispatch path
    def _fused_inputs(self, st, k):
        """Stack the next k steps' (lr, neval, rng) host-side. Schedules
        are arbitrary Python (reference: optim/SGD.scala hyper-parameter
        handling), so lrs are computed here per sub-step — the sub-step
        state advances `neval` only; loss/score-driven schedules (Plateau,
        min_loss) see values as of the last flush for all k steps. The rng
        stream is exactly the unfused path's: fold_in(step_rng, neval)."""
        lr_list, nevals = [], []
        for i in range(k):
            sub_state = dict(st)
            sub_state["neval"] = st["neval"] + i
            lr_list.append(self.method.current_lr(sub_state))
            nevals.append(st["neval"] + i)
        # ONE dispatch derives all k step keys (vmapped fold_in computes
        # the identical per-step keys) — k eager fold_in calls would hand
        # back most of the per-step dispatch cost the fusion just removed
        fns = self.__dict__.setdefault("_fold_keys_fns", {})
        fold_keys = fns.get(k)
        if fold_keys is None:
            def bigdl_fold_keys(key, start):
                return jax.vmap(
                    lambda i: jax.random.fold_in(key, i))(
                        start + jnp.arange(k))
            fold_keys = jax.jit(bigdl_fold_keys)
            fns[k] = fold_keys
        rngs = fold_keys(self._step_rng, jnp.int32(st["neval"]))
        return (jnp.asarray(lr_list, jnp.float32),
                jnp.asarray(nevals, jnp.int32),
                rngs, lr_list)

    def _valid_mask(self, k: int, k_valid: int):
        """[K] bool mask with the first k_valid steps live — the
        single-variant bucketing input. Cached per (K, k_valid): an
        epoch sees at most two distinct masks (full groups + one tail)."""
        m = self._valid_masks.get((k, k_valid))
        if m is None:
            import numpy as _np
            m = _np.zeros((k,), _np.bool_)
            m[:k_valid] = True
            self._valid_masks[(k, k_valid)] = m
        return m

    def _fused_epoch(self, fused_step, epoch_iter, params, model_state,
                     slots, st):
        """One epoch through the fused dispatcher: one jitted call runs K
        optimizer steps, so counters, the metric buffer, and trigger
        checks advance in K-sized strides. Validation/checkpoint/end_when
        are evaluated once per call — a trigger nominally matching
        iteration i fires at the next K boundary >= i
        (fire-at-next-K-boundary; asserted by tests/test_fused_dispatch.py).
        Checkpoints therefore always land on K boundaries (modulo the
        epoch tail), so a mid-epoch resume's batch cursor re-aligns with
        the K-grouping automatically: the surviving run re-groups whatever
        batches remain.

        Shape bucketing: every call — tail groups included — carries the
        same [K, batch, ...] super-batch; the tail's pad steps arrive
        masked (valid[i]=False) and are skipped device-side, so host
        bookkeeping advances by k_valid, not K. The tail stride's
        boundary is the epoch end, so a trigger nominally firing inside
        the tail fires there (same fire-at-next-boundary semantics —
        nothing is skipped or double-fired)."""
        epoch_records = 0
        ended_mid_epoch = False
        W = self._log_every
        for xs, ys, k_valid in self._observed_batches(
                self._fused_batch_iter(epoch_iter)):
            k = int(xs.shape[0])
            k_valid = int(k_valid)
            lrs, nevals, rngs, lr_list = self._fused_inputs(st, k)
            valid = self._valid_mask(k, k_valid)
            if self._param_summary_enabled():
                self._last_batch = (xs[k_valid - 1], ys[k_valid - 1],
                                    rngs[k_valid - 1])
            with observe.phase("train/dispatch"):
                # one span covers the whole K-step scan dispatch — divide
                # by k_valid when comparing against per-step numbers
                if self._dcn_state is not None:
                    # DCN exchange: the accumulator rides the scan carry
                    # AND the program boundary, so T > K windows span
                    # calls without extra host syncs (parallel/dcn.py)
                    (params, model_state, slots, self._dcn_state,
                     losses) = fused_step(
                        params, model_state, slots, self._dcn_state,
                        xs, ys, lrs, nevals, rngs, valid)
                else:
                    params, model_state, slots, losses = fused_step(
                        params, model_state, slots, xs, ys, lrs, nevals,
                        rngs, valid)
            n = int(xs.shape[1])           # GLOBAL batch rows per step
            start = st["neval"]
            for i in range(k_valid):
                # per-step losses are lazy slices of the stacked device
                # array — they ride _pending/_flush_metrics unchanged
                # (pad-step losses are never appended)
                self._pending.append((start + i + 1, lr_list[i], losses[i]))
            st["neval"] += k_valid
            st["records"] += k_valid * n
            st["batch_in_epoch"] = st.get("batch_in_epoch", 0) + k_valid
            epoch_records += k_valid * n
            self._window_records += k_valid * n
            if st["neval"] // W != start // W:   # crossed a log boundary
                self._flush_metrics(st)
            # fire-at-next-K-boundary: a per-iteration trigger whose
            # nominal iteration fell INSIDE this stride (e.g.
            # several_iteration(5) at neval 5 with K=2 landing on 6) must
            # not be skipped — probe every sub-step's neval
            if self._param_summary_enabled():
                trig = self._summary.get_summary_trigger("Parameters")
                self._maybe_param_summary(
                    params, model_state, st,
                    fired=self._stride_fired(trig, st, start, k_valid))
            self._maybe_validate(
                params, model_state, st,
                fired=self._stride_fired(self.val_trigger, st, start,
                                         k_valid))
            self._maybe_checkpoint(
                params, model_state, slots, st,
                fired=self._stride_fired(self.ckpt_trigger, st, start,
                                         k_valid))
            # faults/preemption are probed at the K boundary — the
            # preempt contract is "final checkpoint at the NEXT
            # steps_per_call boundary"
            if self._check_resilience(params, model_state, slots, st):
                ended_mid_epoch = True
                break
            if self.end_when(st):
                ended_mid_epoch = True
                break
        return params, model_state, slots, epoch_records, ended_mid_epoch

    @staticmethod
    def _stride_fired(trigger, st, start, k):
        """Would `trigger` have fired at ANY iteration in (start, start+k]?
        Probes sub-states advancing neval only — loss/score fields hold
        their last-flushed values for the whole stride."""
        if trigger is None:
            return False
        for i in range(1, k + 1):
            sub = dict(st)
            sub["neval"] = start + i
            if trigger(sub):
                return True
        return False

    # ------------------------------------------------------------- internals
    def _flush_metrics(self, st):
        """Fetch pending device losses (blocks only until the last dispatched
        step completes), emit the log line + summary scalars, and reset the
        throughput window."""
        pending = getattr(self, "_pending", None)
        if not pending:
            return
        dt = time.time() - self._window_t0
        rate = self._window_records / max(dt, 1e-9)
        with observe.phase("train/flush"):
            # the ONE host sync of the loop: blocks until the last
            # dispatched step's losses land — device compute backlog
            # shows up here, which is exactly what the span shows
            from bigdl_tpu.analysis.sancov import sanctioned_sync
            items = [p[2] for p in pending]
            dcn_state = getattr(self, "_dcn_state", None)
            if dcn_state is not None:
                # the compression-residual norm rides the same fetch —
                # DCN telemetry adds no extra host syncs
                items = items + [dcn_state["residual_norm"]]
            with sanctioned_sync("flush-cadence loss fetch"):
                fetched = jax.device_get(items)
        import numpy as _np
        dcn_resid = (float(fetched[-1]) if dcn_state is not None
                     else None)
        losses = fetched[:len(pending)]
        # DCN mode records the PER-SLICE loss vector per step — the
        # scalar views below use the cross-slice mean, and the last
        # vector feeds the per-slice loss-spread gauge (/statusz)
        loss_vecs = [_np.asarray(l) for l in losses]
        losses = [float(v.mean()) if v.ndim else float(v)
                  for v in loss_vecs]
        last_iter, last_lr = pending[-1][0], pending[-1][1]
        st["loss"] = float(losses[-1])
        # non-finite step accounting: the fused path already MASKED each
        # bad step's update device-side (the guard in _make_fused_step),
        # so a transient NaN batch costs one skipped step; here the bad
        # losses are counted and a consecutive run past the budget
        # aborts loudly instead of training on NaNs. Detection rides the
        # flush cadence — no extra host syncs. (A per-slice loss vector
        # folds in through its mean: any non-finite slice poisons it.)
        bad_run = self._nonfinite_run
        for (it_num, _, _), loss_f in zip(pending, losses):
            if _np.isfinite(loss_f):
                bad_run = 0
                continue
            bad_run += 1
            observe.counter("train/nonfinite_steps").inc()
            if self._max_nonfinite and bad_run >= self._max_nonfinite:
                self._nonfinite_run = bad_run
                self._pending = []
                raise NonFiniteLossError(
                    f"non-finite loss at iteration {it_num} — "
                    f"{bad_run} consecutive non-finite steps "
                    f"(BIGDL_TPU_MAX_NONFINITE={self._max_nonfinite}); "
                    f"aborting instead of training on NaNs. Params/"
                    f"slots hold the last finite state (fused-path "
                    f"updates were masked); resume from the latest "
                    f"snapshot or inspect the input pipeline.")
        self._nonfinite_run = bad_run
        # registry updates ride this existing cadence with values already
        # on host — observability adds NO per-step syncs (asserted by
        # tests/test_observe.py)
        g = observe.gauge
        g("train/neval").set(last_iter)
        g("train/epoch").set(st["epoch"])
        g("train/loss").set(st["loss"])
        g("train/lr").set(last_lr)
        g("train/throughput").set(rate)
        # heartbeat for /healthz: a live statusz server with a growing
        # last-step age means the loop is stalled (observe/statusz.py)
        g("train/last_flush_unix").set(time.time())
        observe.counter("train/records").inc(self._window_records)
        # step-time anomaly watchdog (observe/doctor.py): same window
        # wall + step count the throughput line above used — host-side
        # floats only, riding this existing cadence
        from bigdl_tpu.observe import doctor as _doctor
        _doctor.watchdog().observe(last_iter, dt, len(pending))
        # DCN-exchange telemetry (docs/observability.md `exchange/*`):
        # boundary counts are host math over the flushed iteration
        # numbers, the residual norm landed with the loss fetch above
        cfg = getattr(self, "_dcn_cfg", None)
        if cfg is not None and dcn_state is not None:
            T = cfg.every
            n_ex = sum(1 for (it_num, _, _) in pending if it_num % T == 0)
            observe.counter("exchange/count").inc(n_ex)
            observe.counter("exchange/skipped_steps").inc(
                len(pending) - n_ex)
            observe.counter("exchange/wire_bytes").inc(
                n_ex * getattr(self, "_dcn_wire_bytes", 0))
            observe.gauge("exchange/pending_steps").set(last_iter % T)
            observe.gauge("exchange/residual_norm").set(dcn_resid)
            if loss_vecs[-1].ndim:
                observe.gauge("exchange/loss_spread").set(
                    float(loss_vecs[-1].max() - loss_vecs[-1].min()))
        log.info("epoch %d iter %d loss %.4f lr %.5f %.1f rec/s",
                 st["epoch"], last_iter, st["loss"], last_lr, rate)
        if self._summary is not None:
            for (neval, lr, _), loss_f in zip(pending, losses):
                self._summary.add_scalar("Loss", float(loss_f), neval)
                self._summary.add_scalar("LearningRate", lr, neval)
                self._summary.add_scalar("Throughput", rate, neval)
        self._pending = []
        self._window_t0 = time.time()
        self._window_records = 0

    def _param_summary_enabled(self) -> bool:
        return self._summary is not None and getattr(
            self._summary, "get_summary_trigger",
            lambda _n: None)("Parameters") is not None

    def _maybe_param_summary(self, params, model_state, st, fired=None):
        """Per-parameter histogram dumps when the train summary carries a
        'Parameters' trigger (reference: optim/AbstractOptimizer.scala:47-91
        — trainSummary.setSummaryTrigger("Parameters", ...) dumps the
        parameter table). Costs a device→host fetch of every param; gate it
        on a sparse trigger like the reference warns.

        Gradients are recomputed at the CURRENT (post-update) params on the
        most recent batch — one lr-step later than the reference's
        gradWeight, but a quantity the current program actually defines
        (params and model_state are the post-step outputs, whose buffers
        have not yet been donated to the next step)."""
        if not self._param_summary_enabled():
            return
        if fired is None:
            trig = self._summary.get_summary_trigger("Parameters")
            fired = bool(trig(st))
        if not fired:
            return
        if getattr(self, "_last_hist_neval", -1) == st["neval"]:
            return
        self._last_hist_neval = st["neval"]
        import numpy as _np

        grads = None
        if getattr(self, "_last_batch", None) is not None:
            # one extra fwd+bwd on the histogram cadence — the reference
            # dumps gradWeight alongside weight (AbstractOptimizer.scala:47).
            # Mirrors the training step's gradient path exactly: same
            # compute dtype, gradient processors, and frozen mask — a
            # divergent recompute would mislead anyone debugging
            # exploding/vanishing gradients from these histograms.
            if not hasattr(self, "_hist_grad_fn"):
                from bigdl_tpu.core.module import cast_floating
                model, criterion = self.model, self.criterion
                compute_dtype = self.compute_dtype
                processors = list(self.grad_processors)
                frozen = any(m._frozen for m in model.modules())

                def gfn(p, ms, x, y, rng):
                    def loss_fn(pp):
                        pc = cast_floating(pp, compute_dtype) \
                            if compute_dtype else pp
                        xc = (x.astype(compute_dtype)
                              if compute_dtype
                              and jnp.issubdtype(x.dtype, jnp.floating)
                              else x)
                        out, _ = model.apply(pc, ms, xc, training=True,
                                             rng=rng)
                        if compute_dtype:
                            out = jax.tree.map(
                                lambda o: o.astype(jnp.float32)
                                if jnp.issubdtype(o.dtype, jnp.floating)
                                else o, out)
                        return criterion.forward(out, y)
                    g = jax.grad(loss_fn)(p)
                    if compute_dtype:
                        g = cast_floating(g, jnp.float32)
                    for proc in processors:
                        g = proc(g, p)
                    if frozen:
                        tm = model.trainable_mask(p)
                        g = jax.tree.map(
                            lambda gg, m: jnp.where(m, gg, 0.0), g, tm)
                    return g
                self._hist_grad_fn = jax.jit(gfn)
            x, y, sub = self._last_batch
            grads = self._hist_grad_fn(params, model_state, x, y, sub)

        def walk(tree, gtree, prefix):
            for k, v in tree.items():
                path = f"{prefix}.{k}" if prefix else str(k)
                g = None if gtree is None else gtree.get(k)
                if isinstance(v, dict):
                    walk(v, g, path)
                else:
                    self._summary.add_histogram(
                        path, _np.asarray(jax.device_get(v)), st["neval"])
                    if g is not None:
                        self._summary.add_histogram(
                            f"{path}.grad",
                            _np.asarray(jax.device_get(g)), st["neval"])
        from bigdl_tpu.analysis.sancov import sanctioned_sync
        with sanctioned_sync("trigger-gated parameter-histogram fetch"):
            walk(params, grads, "")

    def _maybe_validate(self, params, model_state, st, fired=None):
        # `fired` overrides the trigger check — the fused dispatcher
        # probes every sub-step of its K-stride (fire-at-next-K-boundary)
        if fired is None:
            fired = self.val_trigger is not None and self.val_trigger(st)
        if not fired:
            return
        # a trigger can match both on an epoch's last iteration and again at
        # epoch end — don't run validation twice for the same step
        if getattr(self, "_last_val_neval", -1) == st["neval"]:
            return
        self._last_val_neval = st["neval"]
        self._flush_metrics(st)
        from bigdl_tpu.optim.metrics import evaluate
        totals = evaluate(self.model, params, model_state, self.val_dataset,
                          self.val_methods, apply_fn=self._eval_fn)
        for name, res in totals.items():
            log.info("validation %s = %s", name, res)
            st[f"val_{name}"] = res.result
            if self._val_summary is not None:
                self._val_summary.add_scalar(name, res.result, st["neval"])
        if self.val_methods:
            st["score"] = totals[self.val_methods[0].name].result

    def _maybe_checkpoint(self, params, model_state, slots, st, fired=None):
        if fired is None:
            fired = self.ckpt_trigger is not None and self.ckpt_trigger(st)
        if not fired:
            return
        if getattr(self, "_last_ckpt_neval", -1) == st["neval"]:
            return
        self._last_ckpt_neval = st["neval"]
        self._flush_metrics(st)
        path = f"{self.ckpt_path}/snapshot-{st['neval']}"
        meta = {k: v for k, v in st.items()
                if isinstance(v, (int, float, bool, str))}
        meta.update(self._snapshot_extra_meta())
        trees = {"params": params, "model_state": model_state,
                 "slots": slots}
        if getattr(self, "_dcn_state", None) is not None:
            # accumulator + outer state ride the snapshot next to the
            # slots, so a kill-and-resume mid-T-window is exact
            # (parallel/dcn.py; the clone/persist path is tree-generic)
            trees["exchange"] = self._dcn_state
        t0 = time.perf_counter()
        from bigdl_tpu.utils import config
        with observe.phase("train/checkpoint"):
            if config.get("CHECKPOINT_FORMAT") == 1:
                # legacy v1: synchronous gather-to-host-0 single npz
                ckpt.save_checkpoint(path, trees, meta)
            else:
                self._checkpointer().save(path, trees, meta,
                                          root=self.ckpt_path)
        # per-save blocking stall: newest samples ride the bounded deque,
        # the full run's distribution lives in the phase/train/checkpoint
        # log-bucket histogram
        self._ckpt_stalls.append(time.perf_counter() - t0)
        log.info("checkpoint -> %s (%.1f ms stall)", path,
                 self._ckpt_stalls[-1] * 1e3)

    def _checkpointer(self):
        """Lazy per-trainer AsyncCheckpointer (format v2) — knobs
        BIGDL_TPU_CHECKPOINT_ASYNC / _KEEP_N read at first checkpoint."""
        if getattr(self, "_ckpt_writer", None) is None:
            from bigdl_tpu.resilience.snapshot import AsyncCheckpointer
            self._ckpt_writer = AsyncCheckpointer()
        return self._ckpt_writer

    def _snapshot_extra_meta(self) -> Dict:
        """Provenance recorded into the snapshot meta; the distributed
        trainer adds its mesh layout (elastic restores log what the
        source slice looked like). `data_state` is the resumable
        iterator-state protocol (dataset/service.py pipeline_state):
        epoch + batch cursor + echo counter + the dataset's own state,
        so `resume()` restores the PIPELINE, not just params."""
        from bigdl_tpu.dataset import service as _svc
        meta = {"steps_per_call": self.steps_per_call,
                "accum_steps": self.accum_steps,
                "data_state": _svc.pipeline_state(
                    self.dataset, self.state.get("batch_in_epoch", 0),
                    getattr(self, "_echo", 1))}
        cfg = getattr(self, "_dcn_cfg", None)
        if cfg is not None:
            # provenance for the exchange tree: resume validates T and
            # shows where inside the window the snapshot was taken
            meta.update({
                "exchange_every": cfg.every,
                "exchange_pending": self.state.get("neval", 0) % cfg.every,
                "slice_grad_compress": cfg.compress,
                "slice_outer": cfg.outer,
            })
        return meta

    def _finish_checkpoints(self):
        """Join the in-flight background snapshot write (shutdown /
        end-of-optimize barrier); surfaces a deferred write failure."""
        w = getattr(self, "_ckpt_writer", None)
        if w is not None:
            w.wait()

    # --------------------------------------------------------- resilience
    def _check_resilience(self, params, model_state, slots, st) -> bool:
        """Per-boundary fault/preemption probe (resilience/faults.py):
        called after each step (or each K-stride in the fused path).
        Injected crashes raise out to the retry loop; a SIGTERM
        preemption request writes ONE final checkpoint at this boundary
        and returns True so the epoch loop stops cleanly; a slice
        loss/gain request (faults.request_slice_loss / the
        slice:I@step:N spec) is recorded for the epoch loop to apply at
        THIS boundary — optimize() re-shards and continues instead of
        stopping (resilience/failover.py)."""
        from bigdl_tpu.resilience import faults
        faults.check_step(st["neval"])
        ev = faults.take_slice_event()
        if ev is not None:
            if self._supports_failover():
                self._failover_pending = ev
                return True
            log.warning(
                "slice %s requested at iteration %d but this trainer "
                "has no two-tier mesh to re-shard — ignored (arrange "
                "checkpoint-restart via resilience/elastic.py instead)",
                ev[0], st["neval"])
        if not faults.preempt_requested():
            return False
        faults.clear_preempt()
        if self.ckpt_path is not None:
            self.__dict__.pop("_last_ckpt_neval", None)
            self._maybe_checkpoint(params, model_state, slots, st,
                                   fired=True)
            self._finish_checkpoints()
        st["preempted"] = True
        log.warning("preempted at iteration %d — final checkpoint %s; "
                    "stopping cleanly", st["neval"],
                    "written" if self.ckpt_path else "skipped (no "
                    "set_checkpoint)")
        return True

    def _apply_failover(self, params, model_state, slots, st):
        """Re-shard onto the pending slice event's topology — only the
        mesh-aware DistriOptimizer implements this; the base trainer
        never records a pending event (_supports_failover is False)."""
        raise RuntimeError(
            "slice failover requested on a trainer without a mesh")

    # -------------------------------------------------------------- retry
    def optimize_with_retry(self, retries: Optional[int] = None,
                            window_s: Optional[float] = None,
                            backoff_s: Optional[float] = None):
        """Driver-side failure recovery (reference:
        optim/DistriOptimizer.scala:886-963): on an exception, reload the
        latest VALIDATED checkpoint under `ckpt_path` and retry, up to
        BIGDL_TPU_FAILURE_RETRY_TIMES attempts within a
        BIGDL_TPU_FAILURE_RETRY_INTERVAL_S sliding window with
        BIGDL_TPU_FAILURE_RETRY_BACKOFF_S exponential backoff. The loop
        is resilience.RetryPolicy — shared verbatim by LocalOptimizer and
        DistriOptimizer (this method is inherited). Requires
        `set_checkpoint` to have been called (no snapshot → no recovery)."""
        from bigdl_tpu.resilience.retry import RetryPolicy
        if self.ckpt_path is None:
            raise RuntimeError("optimize_with_retry needs set_checkpoint() "
                               "so there is a snapshot to recover from")

        def recover(_e):
            # resume() drains the in-flight background write and resumes
            # from the latest snapshot that passes manifest validation
            if not self.resume(self.ckpt_path):
                # no snapshot yet — discard the mutated counters from the
                # failed run so triggers/progress restart from scratch;
                # user-supplied initial trees (set_initial) are restored,
                # NOT thrown away — a pre-snapshot failure must not turn
                # fine-tuning into from-scratch training
                log.warning("no usable snapshot; retrying from %s",
                            "initial trees"
                            if hasattr(self, "_initial_trees")
                            else "scratch")
                self.state = {"epoch": 0, "neval": 0, "records": 0,
                              "batch_in_epoch": 0}
                if hasattr(self, "_initial_trees"):
                    self._resume_trees = dict(self._initial_trees)
                else:
                    self.__dict__.pop("_resume_trees", None)
                self.__dict__.pop("_last_val_neval", None)
                self.__dict__.pop("_last_ckpt_neval", None)

        return RetryPolicy(retries, window_s, backoff_s).run(
            self.optimize, recover)


LocalOptimizer = Optimizer
