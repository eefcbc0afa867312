"""Pipeline parallelism over a 'pipe' mesh axis (no reference equivalent:
SURVEY.md §2.13 marks PP as absent in BigDL; this is a deliberate TPU-native
extension designed per the scaling-book recipe: stage params live
one-per-device on the pipe axis, activations hop stages via `lax.ppermute`
over ICI).

Two layers of API:

1. `pipeline_apply(stage_fn, stacked, x, mesh, M)` — uniform stages with a
   stacked leading stage axis, GPipe schedule, differentiable end-to-end
   (autodiff through the ppermute chain yields the reverse schedule).

2. `Pipeline([stage0, stage1, ...])` — heterogeneous stage modules. Each
   stage's param tree is flattened into one padded f32 row; the (S, L) row
   matrix is sharded over 'pipe' so every device holds exactly its own
   stage's weights, and `lax.switch` on the stage index dispatches to the
   right unflatten+forward. Constraints: every stage must map a microbatch
   to the same shape/dtype (put embedding/head OUTSIDE the pipeline — the
   same rule production TPU pipelines impose).

   - `apply` — forward with the GPipe diagonal. The input batch is sharded
     over the pipe axis and STREAMED to stage 0 one microbatch per tick
     through a backward ppermute chain (no device ever materializes the
     full batch — fixes the round-1 design that replicated the input
     everywhere).
   - `train_step` — a true 1F1B (one-forward-one-backward) schedule:
     each tick runs one forward and one backward sub-step per device, with
     the backward implemented as recompute-VJP from a 2S-slot activation
     ring buffer (stage inputs only — rematerialization, the TPU-standard
     FLOPs-for-HBM trade). fwd(m, s) fires at tick m+s; bwd(m, s) at tick
     2(S-1)-s+m, so the last stage backpropagates a microbatch the same
     tick it finishes its forward and at most 2S activations are ever live
     per device — vs M under GPipe-then-backprop. Labels stream to the
     last stage through a forward ppermute chain; each device accumulates
     gradients for its own stage locally (exactly where its optimizer
     shard lives).

   Mutable stage state (e.g. BatchNorm running stats) is threaded through
   the schedule in execution order and saved pre-tick in the ring buffer so
   the recompute sees the same statistics the forward saw.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from bigdl_tpu.parallel.mesh import PIPE_AXIS


# --------------------------------------------------------- uniform (GPipe)
def stack_stage_params(stage_params: Sequence) -> object:
    """Stack per-stage param pytrees along a new leading 'stage' axis —
    shard that axis over 'pipe' so each device holds exactly its stage."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params)


def stage_spec(tree) -> object:
    """PartitionSpecs sharding the leading stage axis over the pipe axis."""
    return jax.tree.map(
        lambda x: P(PIPE_AXIS, *([None] * (jnp.ndim(x) - 1))), tree)


def pipeline_apply(stage_fn: Callable, stacked_params, x, mesh: Mesh,
                   n_microbatches: int, axis_name: str = PIPE_AXIS):
    """Run S uniform pipeline stages over the batch with M microbatches.

    x: (batch, ...) — batch must divide by n_microbatches. Returns the
    final-stage output with the same batch shape. Differentiable end-to-end
    (grads flow back through the ppermute chain)."""
    n_stages = mesh.shape[axis_name]
    stage_dims = {int(l.shape[0]) for l in jax.tree.leaves(stacked_params)}
    if stage_dims and stage_dims != {n_stages}:
        raise ValueError(
            f"stacked params have stage axis {sorted(stage_dims)} but the "
            f"'{axis_name}' mesh axis has {n_stages} devices — each device "
            f"must own exactly one stage")
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} must divide microbatches "
                         f"{n_microbatches}")
    mb = b // n_microbatches
    xs = x.reshape((n_microbatches, mb) + x.shape[1:])

    p_params = stage_spec(stacked_params)
    in_specs = (p_params, P())
    out_specs = P(axis_name)

    def shard_fn(params_stage, xs):
        params_local = jax.tree.map(lambda a: a[0], params_stage)
        s = lax.axis_index(axis_name)
        ticks = n_microbatches + n_stages - 1
        h_shape = xs.shape[1:]

        def tick(t, carry):
            buf, outs = carry
            m_idx = jnp.clip(t, 0, n_microbatches - 1)
            inp = jnp.where(s == 0, lax.dynamic_index_in_dim(
                xs, m_idx, keepdims=False), buf)
            h = stage_fn(params_local, inp)
            active = (t >= s) & (t - s < n_microbatches)
            h = jnp.where(active, h, jnp.zeros_like(h))
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
            is_out = (s == n_stages - 1) & (t >= n_stages - 1)
            cur = lax.dynamic_index_in_dim(outs, out_idx, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(is_out, h, cur), out_idx, 0)
            buf = lax.ppermute(
                h, axis_name,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, outs

        buf0 = jnp.zeros(h_shape, x.dtype)
        outs0 = jnp.zeros((n_microbatches,) + h_shape, x.dtype)
        _, outs = lax.fori_loop(0, ticks, tick, (buf0, outs0))
        return outs[None]

    outs = shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)(
        stacked_params, xs)
    return outs[-1].reshape((b,) + x.shape[1:])


# ----------------------------------------------------- flat-row packing
class _StageMeta:
    """Static description of one stage's param/state trees so a padded
    f32 row can be unflattened back inside a `lax.switch` branch."""

    def __init__(self, tree):
        leaves, self.treedef = jax.tree.flatten(tree)
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.total = sum(self.sizes)

    def flatten(self, tree, width: int):
        leaves = jax.tree.leaves(tree)
        if not leaves:
            return jnp.zeros((width,), jnp.float32)
        flat = jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in leaves])
        return jnp.pad(flat, (0, width - flat.shape[0]))

    def unflatten(self, row):
        out, off = [], 0
        for shape, dtype, size in zip(self.shapes, self.dtypes, self.sizes):
            out.append(lax.slice_in_dim(row, off, off + size)
                       .reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(self.treedef, out)


def _ring_fwd(n):
    return [(i, (i + 1) % n) for i in range(n)]


def _ring_bwd(n):
    return [(i, (i - 1) % n) for i in range(n)]


class Pipeline:
    """Heterogeneous pipeline over Modules.

        pipe = Pipeline([stage0, stage1, stage2, stage3], n_microbatches=8)
        pv = pipe.init(rng)                       # {"flat": (S,L), "state": (S,Ls)}
        pv = pipe.shard(pv, mesh)
        y = pipe.apply(pv, x, mesh)
        loss, grads, pv2 = pipe.train_step(pv, x, y, loss_fn, mesh)

    Uniform sugar: `Pipeline(block, n_stages=4, n_microbatches=8)` builds 4
    independently-initialized copies of `block`'s structure."""

    def __init__(self, stages, n_stages: Optional[int] = None,
                 n_microbatches: int = 8):
        if not isinstance(stages, (list, tuple)):
            if n_stages is None:
                raise ValueError("single-module Pipeline needs n_stages")
            stages = [stages] * n_stages
        self.stages: List = list(stages)
        self.n_stages = len(self.stages)
        self.n_microbatches = n_microbatches
        if n_microbatches % self.n_stages:
            raise ValueError(
                f"n_microbatches {n_microbatches} must divide by "
                f"n_stages {self.n_stages} (contiguous input sharding)")
        self._p_meta: List[_StageMeta] = []
        self._s_meta: List[_StageMeta] = []
        # stable closures + compiled programs, keyed on call signature —
        # rebuilding them per call would defeat jit's trace cache and
        # recompile the whole tick schedule every step
        self._fwd_b = {}
        self._vjp_b = None
        self._compiled = {}

    # ------------------------------------------------------------- params
    def init(self, rng, dtype=None):
        rows_p, rows_s = [], []
        trees = []
        self._p_meta, self._s_meta = [], []
        self._fwd_b, self._vjp_b, self._compiled = {}, None, {}
        for i, stage in enumerate(self.stages):
            p, s = stage.init(jax.random.fold_in(rng, i), dtype=dtype)
            trees.append((p, s))
            self._p_meta.append(_StageMeta(p))
            self._s_meta.append(_StageMeta(s))
        lp = max(m.total for m in self._p_meta) or 1
        ls = max(m.total for m in self._s_meta) or 1
        for (p, s), pm, sm in zip(trees, self._p_meta, self._s_meta):
            rows_p.append(pm.flatten(p, lp))
            rows_s.append(sm.flatten(s, ls))
        return {"flat": jnp.stack(rows_p), "state": jnp.stack(rows_s)}

    def shard(self, pv, mesh: Mesh):
        from bigdl_tpu.parallel.mesh import host_rows_to_global
        return {k: host_rows_to_global(np.asarray(v), mesh, PIPE_AXIS)
                for k, v in pv.items()}

    def stage_params(self, pv, i: int):
        """Unpack stage i's param tree from the row matrix (host-side)."""
        return self._p_meta[i].unflatten(pv["flat"][i])

    # ---------------------------------------------------------- dispatch
    def _fwd_branches(self, training: bool):
        if training in self._fwd_b:
            return self._fwd_b[training]
        branches = []
        for stage, pm, sm in zip(self.stages, self._p_meta, self._s_meta):
            def fwd(prow, srow, h, key, stage=stage, pm=pm, sm=sm):
                p = pm.unflatten(prow)
                s = sm.unflatten(srow)
                out, new_s = stage.apply(p, s, h, training=training,
                                         rng=key)
                return out, sm.flatten(new_s, srow.shape[0])
            branches.append(fwd)
        self._fwd_b[training] = branches
        return branches

    def _vjp_branches(self):
        if self._vjp_b is not None:
            return self._vjp_b
        branches = []
        for stage, pm, sm in zip(self.stages, self._p_meta, self._s_meta):
            def bwd(prow, srow, h, g, key, stage=stage, pm=pm, sm=sm):
                def f(row, hh):
                    out, _ = stage.apply(pm.unflatten(row), sm.unflatten(srow),
                                         hh, training=True, rng=key)
                    return out
                _, pull = jax.vjp(f, prow, h)
                d_row, d_h = pull(g)
                return d_row, d_h
            branches.append(bwd)
        self._vjp_b = branches
        return branches

    def _prep(self, x):
        S, M = self.n_stages, self.n_microbatches
        b = x.shape[0]
        if b % M:
            raise ValueError(f"batch {b} must divide microbatches {M}")
        mb = b // M
        # contiguous microbatch sharding: device d owns mbs [d*M/S, ...)
        xs = x.reshape((S, M // S, mb) + x.shape[1:])
        return xs, mb

    @staticmethod
    def _dp(mesh) -> Optional[str]:
        """The composed data axis, when the mesh carries one — batch
        (microbatch rows) shards over it while stages shard over 'pipe'
        (dp×pp, the hierarchical layout real slices use: dp over DCN,
        pp over ICI)."""
        from bigdl_tpu.parallel.mesh import composed_data_axis
        return composed_data_axis(mesh)

    @classmethod
    def _globalize(cls, arr, mesh):
        """Multi-host-safe placement of a stage-major host array: stage
        dim over 'pipe', microbatch rows over 'data' when composed."""
        if jax.process_count() == 1 and mesh.devices.ndim == 1:
            return arr                     # jit's in_specs place it
        from bigdl_tpu.parallel.mesh import host_array_to_global
        dp = cls._dp(mesh)
        arr = np.asarray(arr)
        spec = P(PIPE_AXIS, None, dp,
                 *([None] * (arr.ndim - 3)))
        return host_array_to_global(arr, mesh, spec)

    def _check(self, mb_shape, dtype):
        sd = jax.ShapeDtypeStruct(mb_shape, dtype)
        for i, (stage, pm, sm) in enumerate(
                zip(self.stages, self._p_meta, self._s_meta)):
            out, _ = jax.eval_shape(
                lambda p, s, h, st=stage: st.apply(p, s, h),
                jax.tree.unflatten(pm.treedef, [
                    jax.ShapeDtypeStruct(sh, dt)
                    for sh, dt in zip(pm.shapes, pm.dtypes)]),
                jax.tree.unflatten(sm.treedef, [
                    jax.ShapeDtypeStruct(sh, dt)
                    for sh, dt in zip(sm.shapes, sm.dtypes)]), sd)
            if out.shape != mb_shape or out.dtype != dtype:
                raise ValueError(
                    f"pipeline stage {i} maps {mb_shape}/{dtype} → "
                    f"{out.shape}/{out.dtype}; every stage must preserve "
                    f"the microbatch shape (run embedding/head outside "
                    f"the pipeline)")

    # ------------------------------------------------------------ forward
    def apply(self, pv, x, mesh: Mesh, training: bool = False, rng=None):
        S, M = self.n_stages, self.n_microbatches
        xs, mb = self._prep(x)
        base_key = rng if rng is not None else jax.random.PRNGKey(0)  # tpu-lint: disable=004
        sig = ("apply", training, xs.shape, str(x.dtype), mesh)
        fn = self._compiled.get(sig)
        if fn is None:
            self._check(xs.shape[2:], x.dtype)
            fn = self._build_apply(xs, x.dtype, mesh, training)
            self._compiled[sig] = fn
        outs, new_state = fn(pv["flat"], pv["state"],
                             self._globalize(xs, mesh), base_key)
        out = outs.reshape((x.shape[0],) + xs.shape[3:])
        if training:
            return out, {"flat": pv["flat"], "state": new_state}
        return out

    def _build_apply(self, xs_proto, dtype, mesh, training):
        S, M = self.n_stages, self.n_microbatches
        fwd_branches = self._fwd_branches(training)
        per_dev = M // S

        def shard_fn(flat, state, xs, key):
            prow = flat[0]
            srow = state[0]
            local_x = xs[0]                  # (M/S, mb, ...)
            d = lax.axis_index(PIPE_AXIS)
            ticks = M + S - 1
            h_shape = local_x.shape[1:]

            def tick(t, carry):
                h_buf, in_tb, srow, outs = carry
                # --- input streaming toward stage 0
                m_here = t + d
                li = jnp.clip(m_here - d * per_dev, 0, per_dev - 1)
                inject = (m_here >= d * per_dev) & \
                    (m_here < (d + 1) * per_dev)
                in_tb = jnp.where(
                    inject,
                    lax.dynamic_index_in_dim(local_x, li, keepdims=False),
                    in_tb)
                # --- forward sub-step
                m_f = t - d
                active = (m_f >= 0) & (m_f < M)
                inp = jnp.where(d == 0, in_tb, h_buf)
                k = jax.random.fold_in(
                    jax.random.fold_in(key, jnp.clip(m_f, 0, M - 1)), d)
                h, new_srow = lax.switch(d, fwd_branches, prow, srow, inp, k)
                h = jnp.where(active, h, jnp.zeros_like(h))
                if training:
                    srow = jnp.where(active, new_srow, srow)
                # --- collect outputs at the last stage
                out_idx = jnp.clip(m_f, 0, M - 1)
                is_out = (d == S - 1) & active
                cur = lax.dynamic_index_in_dim(outs, out_idx, keepdims=False)
                outs = lax.dynamic_update_index_in_dim(
                    outs, jnp.where(is_out, h, cur), out_idx, 0)
                # --- rotate
                h_buf = lax.ppermute(h, PIPE_AXIS, _ring_fwd(S))
                in_tb = lax.ppermute(in_tb, PIPE_AXIS, _ring_bwd(S))
                return h_buf, in_tb, srow, outs

            z = jnp.zeros(h_shape, dtype)
            outs0 = jnp.zeros((M,) + h_shape, dtype)
            _, _, srow, outs = lax.fori_loop(
                0, ticks, tick, (z, z, srow, outs0))
            if training and dp is not None:
                # same reduction the train path does: each dp group saw
                # different rows, so state (e.g. BN stats) must agree
                srow = lax.pmean(srow, dp)
            # only the last stage filled outs — psum broadcasts it so the
            # result is replicated (and host-readable under multi-host,
            # where a stage-sharded output's first rows live remotely)
            return lax.psum(outs, PIPE_AXIS), srow[None]

        dp = self._dp(mesh)
        return jax.jit(shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(PIPE_AXIS, None), P(PIPE_AXIS, None),
                      P(PIPE_AXIS, None, dp), P()),
            out_specs=(P(None, dp), P(PIPE_AXIS, None)),
            check_vma=False))

    # ------------------------------------------------- 1F1B training step
    def train_step(self, pv, x, y, loss_fn: Callable, mesh: Mesh,
                   rng=None):
        """One 1F1B fwd+bwd pass. `loss_fn(h_mb, y_mb) -> scalar` (mean
        over the microbatch). Returns (mean_loss, grads, new_pv) where
        grads matches pv["flat"] (S, L) — each device's row holds its own
        stage's gradient, ready for a pipe-sharded optimizer update."""
        loss, grads, _, _, new_pv = self._train_common(
            pv, x, y, loss_fn, mesh, rng, None, full=False)
        return loss, grads, new_pv

    def train_step_full(self, pv, x, y, loss_fn: Callable, mesh: Mesh,
                        rng=None, loss_params=None):
        """End-to-end 1F1B: like train_step, but ALSO differentiates the
        pipeline boundary so embedding/head living outside the pipe train
        too. `loss_fn(h_mb, y_mb, loss_params) -> scalar`.

        Returns (mean_loss, stage_grads, d_x, d_loss_params, new_pv):
          d_x            — gradient wrt the pipeline input x (same shape),
                           produced by stage 0's backward and streamed out;
                           feed it to the embedding's VJP.
          d_loss_params  — gradient of the head/loss parameter pytree,
                           accumulated on the last stage and psum-shared.
        """
        if loss_params is None:
            raise ValueError("train_step_full needs loss_params (use "
                             "train_step when the loss has no parameters)")
        return self._train_common(pv, x, y, loss_fn, mesh, rng,
                                  loss_params, full=True)

    def _train_common(self, pv, x, y, loss_fn, mesh, rng, loss_params,
                      full):
        S, M = self.n_stages, self.n_microbatches
        xs, mb = self._prep(x)
        ys = y.reshape((S, M // S, mb) + y.shape[1:])
        base_key = rng if rng is not None else jax.random.PRNGKey(0)  # tpu-lint: disable=004
        lp = loss_params if full else jnp.zeros((), jnp.float32)
        sig = ("train", full, xs.shape, str(x.dtype), ys.shape,
               str(y.dtype), loss_fn, mesh)
        fn = self._compiled.get(sig)
        if fn is None:
            self._check(xs.shape[2:], x.dtype)
            fn = self._build_train(x.dtype, y.dtype, loss_fn, mesh, full)
            self._compiled[sig] = fn
        loss, grads, new_state, dx, dlp = fn(
            pv["flat"], pv["state"], self._globalize(xs, mesh),
            self._globalize(ys, mesh), base_key, lp)
        d_x = (dx.reshape(x.shape) if full else None)
        return (loss, grads, d_x, (dlp if full else None),
                {"flat": pv["flat"], "state": new_state})

    def _build_train(self, x_dtype, y_dtype, loss_fn, mesh, full=False):
        S, M = self.n_stages, self.n_microbatches
        fwd_branches = self._fwd_branches(True)
        vjp_branches = self._vjp_branches()
        per_dev = M // S
        ring = 2 * S

        def shard_fn(flat, state, xs, ys, key, lp):
            prow, srow = flat[0], state[0]
            local_x, local_y = xs[0], ys[0]
            d = lax.axis_index(PIPE_AXIS)
            ticks = M + 2 * S - 2
            h_shape = local_x.shape[1:]
            y_shape = local_y.shape[1:]

            def stage_key(m):
                return jax.random.fold_in(
                    jax.random.fold_in(key, jnp.clip(m, 0, M - 1)), d)

            def tick(t, carry):
                (h_buf, g_buf, in_tb, lb_tb, srow, act_ring, st_ring,
                 grad_acc, loss_acc, dx_buf, lp_acc) = carry
                # --- input streaming toward stage 0
                m_in = t + d
                li = jnp.clip(m_in - d * per_dev, 0, per_dev - 1)
                take = (m_in >= d * per_dev) & (m_in < (d + 1) * per_dev)
                in_tb = jnp.where(
                    take, lax.dynamic_index_in_dim(local_x, li,
                                                   keepdims=False), in_tb)
                # --- label streaming toward stage S-1
                m_lb = t - d
                lj = jnp.clip(m_lb - d * per_dev, 0, per_dev - 1)
                take_l = (m_lb >= d * per_dev) & (m_lb < (d + 1) * per_dev)
                lb_tb = jnp.where(
                    take_l, lax.dynamic_index_in_dim(local_y, lj,
                                                     keepdims=False), lb_tb)
                # --- forward sub-step: fwd(m_f, d) at tick m_f + d
                m_f = t - d
                act_f = (m_f >= 0) & (m_f < M)
                inp = jnp.where(d == 0, in_tb, h_buf)
                slot_f = jnp.clip(m_f, 0, M - 1) % ring
                cur_a = lax.dynamic_index_in_dim(act_ring, slot_f,
                                                 keepdims=False)
                cur_s = lax.dynamic_index_in_dim(st_ring, slot_f,
                                                 keepdims=False)
                act_ring = lax.dynamic_update_index_in_dim(
                    act_ring, jnp.where(act_f, inp, cur_a), slot_f, 0)
                st_ring = lax.dynamic_update_index_in_dim(
                    st_ring, jnp.where(act_f, srow, cur_s), slot_f, 0)
                h, new_srow = lax.switch(d, fwd_branches, prow, srow, inp,
                                         stage_key(m_f))
                h = jnp.where(act_f, h, jnp.zeros_like(h))
                srow = jnp.where(act_f, new_srow, srow)
                # --- last stage: per-microbatch loss + grad seed
                is_last = d == S - 1
                if full:
                    (loss_m, (g_seed, g_lp)) = jax.value_and_grad(
                        loss_fn, argnums=(0, 2))(h, lb_tb, lp)
                    lp_acc = jax.tree.map(
                        lambda acc, g: acc + jnp.where(act_f & is_last,
                                                       g, 0.0),
                        lp_acc, g_lp)
                else:
                    loss_m, g_seed = jax.value_and_grad(loss_fn)(h, lb_tb)
                loss_acc = loss_acc + jnp.where(act_f & is_last, loss_m, 0.0)
                # --- backward sub-step: bwd(m_b, d) at tick 2(S-1)-d+m_b
                m_b = t - 2 * (S - 1) + d
                act_b = (m_b >= 0) & (m_b < M)
                slot_b = jnp.clip(m_b, 0, M - 1) % ring
                saved_in = lax.dynamic_index_in_dim(act_ring, slot_b,
                                                    keepdims=False)
                saved_st = lax.dynamic_index_in_dim(st_ring, slot_b,
                                                    keepdims=False)
                g_in = jnp.where(is_last, g_seed, g_buf)
                d_row, d_h = lax.switch(d, vjp_branches, prow, saved_st,
                                        saved_in, g_in, stage_key(m_b))
                grad_acc = grad_acc + jnp.where(act_b, d_row,
                                                jnp.zeros_like(d_row))
                d_h = jnp.where(act_b, d_h, jnp.zeros_like(d_h))
                if full:
                    # stage 0's input gradient IS dL/dx for microbatch m_b
                    slot_x = jnp.clip(m_b, 0, M - 1)
                    cur_dx = lax.dynamic_index_in_dim(dx_buf, slot_x,
                                                      keepdims=False)
                    dx_buf = lax.dynamic_update_index_in_dim(
                        dx_buf, jnp.where(act_b & (d == 0), d_h, cur_dx),
                        slot_x, 0)
                # --- rotate transit buffers
                h_buf = lax.ppermute(h, PIPE_AXIS, _ring_fwd(S))
                g_buf = lax.ppermute(d_h, PIPE_AXIS, _ring_bwd(S))
                in_tb = lax.ppermute(in_tb, PIPE_AXIS, _ring_bwd(S))
                lb_tb = lax.ppermute(lb_tb, PIPE_AXIS, _ring_fwd(S))
                return (h_buf, g_buf, in_tb, lb_tb, srow, act_ring, st_ring,
                        grad_acc, loss_acc, dx_buf, lp_acc)

            z = jnp.zeros(h_shape, x_dtype)
            carry0 = (z, z, z, jnp.zeros(y_shape, y_dtype), srow,
                      jnp.zeros((ring,) + h_shape, x_dtype),
                      jnp.zeros((ring,) + srow.shape, srow.dtype),
                      jnp.zeros_like(prow), jnp.asarray(0.0, jnp.float32),
                      # dx collection buffer only exists in the full path
                      jnp.zeros(((M if full else 1),) + h_shape, x_dtype),
                      jax.tree.map(jnp.zeros_like, lp))
            out = lax.fori_loop(0, ticks, tick, carry0)
            srow, grad_acc, loss_acc = out[4], out[7], out[8]
            dx_buf, lp_acc = out[9], out[10]
            loss = lax.psum(loss_acc, PIPE_AXIS) / M
            # only stage 0 filled dx_buf / only the last stage lp_acc —
            # psum shares them (all other shards contribute zeros)
            dx = lax.psum(dx_buf, PIPE_AXIS) / M
            d_lp = jax.tree.map(lambda g: lax.psum(g, PIPE_AXIS) / M,
                                lp_acc)
            grads = grad_acc[None] / M
            if dp is not None:
                # dp×pp composition: loss_fn saw only the local microbatch
                # rows — average loss/grads/head-grads over the data axis.
                # dx stays data-sharded (each group owns its rows) but the
                # per-row scale must match the GLOBAL-mean loss: the local
                # mean over mb/n_dp rows makes each row's grad n_dp× too
                # large.
                n_dp = lax.psum(1, dp)
                loss = lax.pmean(loss, dp)
                grads = lax.pmean(grads, dp)
                d_lp = jax.tree.map(lambda g: lax.pmean(g, dp), d_lp)
                srow = lax.pmean(srow, dp)
                dx = dx / n_dp
            # loss/dx/d_lp are psum'd → uniform across shards → returned
            # replicated, so they stay host-readable under multi-host
            return (loss, grads, srow[None], dx, d_lp)

        dp = self._dp(mesh)
        return jax.jit(shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(PIPE_AXIS, None), P(PIPE_AXIS, None),
                      P(PIPE_AXIS, None, dp), P(PIPE_AXIS, None, dp),
                      P(), P()),
            out_specs=(P(), P(PIPE_AXIS, None),
                       P(PIPE_AXIS, None), P(None, dp), P()),
            check_vma=False))
