"""Fused optimizer update — the whole `OptimMethod.update` body (grad
weight-decay + slot update + param update + dtype cast) in ONE pass over
flat parameter blocks.

Why: the tree-map update (optim/method.py) emits ~10 elementwise ops per
parameter leaf; inside the K-fused scan (PR 2) every one of the K inner
steps round-trips each Adam/ZeRO-1 slot leaf through HBM, and a
many-leaf model additionally pays per-fusion launch overhead on every
leaf. Here the leaves are flattened into one lane-tiled block stream and
the entire update is a single kernel:

  * **Pallas engine** (TPU): grid walk over ``(block_rows, 128)`` fp32
    tiles; params and every slot buffer are donated via
    ``input_output_aliases`` so the update is in-place in HBM — traffic
    is exactly one read + one write of (p, slots) plus one read of g.
    ``block_rows`` comes from the shape-keyed autotuner
    (kernels/autotune.py).
  * **XLA engine** (everywhere else, and the distributed leaf layout):
    the same math as one fused elementwise expression — on the flat
    layout a whole model's update is ~15 ops instead of ~10 x n_leaves.

Layouts:
  * ``flat``  — concatenate all float leaves (cast to fp32), update the
    one flat vector through the Pallas kernel, split back (per-leaf
    dtype cast fused into the epilogue). This is the TPU layout: the
    win is ONE kernel launch instead of ~n_leaves and donated in-place
    slot buffers. The assembly (concat/split) costs one gather+scatter
    of the state per step, so it only pays where launch overhead
    dominates — i.e. on the real chip with many leaves.
  * ``leaf``  — identical fused math applied leaf-wise in the leaf's
    native dtype, no assembly copies. On CPU (where XLA's loop fusion
    already folds the tree-map update into one pass per leaf, so the
    flat assembly copies buy nothing) and on ZeRO-1/TP-sharded trees (a concat would
    re-gather exactly the state the sharding distributed) this is the
    right engine, and it is bitwise identical to the oracle.
  * ``auto``  — flat+Pallas on a TPU backend, leaf elsewhere. The
    trainers' default.

Semantics: bit-identical to `method.update` for fp32 trees (same
elementwise expressions in the same order; flattening does not change
per-element math); for low-precision trees the flat layout computes in
fp32 and casts back — inside the `mxu_ref.py` envelope. Supported
methods: Adam, AdamW, SGD (any momentum/dampening/nesterov). Anything
else returns None from `make_update_fn` and the trainer keeps the
tree-map path (optim/local.py logs the fallback once).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:                                    # pltpu only imports on TPU builds
    from jax.experimental.pallas import tpu as pltpu
except Exception:                       # pragma: no cover
    pltpu = None

_LANE = 128
_SUBLANE = 8


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


# ------------------------------------------------------------- descriptors
def describe(method) -> Optional[Tuple[str, Dict]]:
    """(kind, hyper) for a supported OptimMethod instance, else None.
    EXACT type checks: a user subclass overriding `update` must not be
    silently rerouted through the fused math."""
    from bigdl_tpu.optim.method import SGD, Adam, AdamW
    t = type(method)
    if t is AdamW:
        return "adamw", {"b1": method.beta1, "b2": method.beta2,
                         "eps": method.epsilon, "wd": method.weight_decay}
    if t is Adam:                        # ParallelAdam is an alias of Adam
        return "adam", {"b1": method.beta1, "b2": method.beta2,
                        "eps": method.epsilon, "wd": method.weight_decay}
    if t is SGD:
        return "sgd", {"mu": method.momentum, "damp": method.dampening,
                       "nesterov": method.nesterov,
                       "wd": method.weight_decay}
    return None


def supports(method) -> bool:
    return describe(method) is not None


def configured_mode() -> Optional[str]:
    """BIGDL_TPU_FUSED_UPDATE, normalized: None (off — the default),
    'auto' (1/true/on), or a forced 'flat' / 'leaf' layout."""
    from bigdl_tpu.utils import config
    raw = str(config.get("FUSED_UPDATE")).strip().lower()
    if raw in ("", "0", "false", "off", "no"):
        return None
    if raw in ("flat", "leaf"):
        return raw
    return "auto"


def slot_names(kind: str, hyper: Dict) -> Tuple[str, ...]:
    if kind in ("adam", "adamw"):
        return ("m", "v")
    return ("velocity",) if hyper["mu"] != 0.0 else ()


def bench_hyper(kind: str) -> Dict:
    """Representative hyperparameters for autotune's synthetic search
    runs (block-size timing is insensitive to their values)."""
    if kind in ("adam", "adamw"):
        return {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "wd": 0.0}
    return {"mu": 0.9, "damp": 0.9, "nesterov": False, "wd": 0.0}


# ------------------------------------------------------------------- math
def _bias_corrections(kind: str, hyper: Dict, step):
    """The step-dependent scalars, computed OUTSIDE the kernel (they are
    per-call, not per-element) with the same expression method.update
    uses, so `b1 ** t`'s promotion behavior matches bitwise."""
    if kind in ("adam", "adamw"):
        t = step + 1
        return 1 - hyper["b1"] ** t, 1 - hyper["b2"] ** t
    return jnp.float32(1.0), jnp.float32(1.0)


def _math(kind: str, hyper: Dict, p, g, slots, lr, bc1, bc2):
    """One optimizer update, shape-polymorphic and elementwise — the
    single source of truth shared by the XLA engine, the leaf layout,
    and the Pallas kernel body. Mirrors optim/method.py expression for
    expression (the equivalence tests hold it to that)."""
    if kind in ("adam", "adamw"):
        b1, b2, eps, wd = (hyper["b1"], hyper["b2"], hyper["eps"],
                           hyper["wd"])
        m, v = slots
        if kind == "adam" and wd:
            g = g + wd * p
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        p_new = p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        if kind == "adamw" and wd:
            p_new = p_new - lr * wd * p
        return p_new, (m, v)
    mu, damp, nesterov, wd = (hyper["mu"], hyper["damp"],
                              hyper["nesterov"], hyper["wd"])
    if wd:
        g = g + wd * p
    if not slots:                        # plain SGD — no state
        return p - lr * g, ()
    (v,) = slots
    v = mu * v + (1 - damp) * g
    upd = g + mu * v if nesterov else v
    return p - lr * upd, (v,)


# ---------------------------------------------------------- pallas engine
def _fused_kernel(scal_ref, p_ref, g_ref, *refs, kind, hyper, n_slots):
    """One (block_rows, 128) tile: read p/g/slots, write p'/slots'.
    scal carries the per-call scalars (lr, bc1, bc2) in one SMEM-sized
    lane tile; outputs alias the p/slot inputs (donated buffers)."""
    lr = scal_ref[0, 0]
    bc1 = scal_ref[0, 1]
    bc2 = scal_ref[0, 2]
    slots_in = tuple(r[:] for r in refs[:n_slots])
    outs = refs[n_slots:]
    p_new, slots_new = _math(kind, hyper, p_ref[:], g_ref[:], slots_in,
                             lr, bc1, bc2)
    outs[0][:] = p_new
    for r, s in zip(outs[1:], slots_new):
        r[:] = s


def _pallas_flat(kind, hyper, p, g, slots, lr, bc1, bc2, block_rows,
                 interpret):
    """The flat fp32 vectors through the Pallas kernel: pad to a
    lane-tiled (rows, 128) layout, walk it in block_rows-row tiles."""
    n = p.shape[0]
    rows = _round_up(max(n, 1), _LANE) // _LANE
    br = _round_up(min(block_rows, _round_up(rows, _SUBLANE)), _SUBLANE)
    rows_p = _round_up(rows, br)
    total = rows_p * _LANE

    def shape2d(x):
        return jnp.pad(x, (0, total - n)).reshape(rows_p, _LANE)

    p2, g2 = shape2d(p), shape2d(g)
    slots2 = tuple(shape2d(s) for s in slots)
    scal = (jnp.zeros((_SUBLANE, _LANE), jnp.float32)
            .at[0, 0].set(lr).at[0, 1].set(bc1).at[0, 2].set(bc2))

    bs = pl.BlockSpec((br, _LANE), lambda i: (i, 0))
    sbs = pl.BlockSpec((_SUBLANE, _LANE), lambda i: (0, 0))
    n_slots = len(slots2)
    n_out = 1 + n_slots
    kernel = functools.partial(_fused_kernel, kind=kind, hyper=hyper,
                               n_slots=n_slots)
    outs = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((rows_p, _LANE), jnp.float32)
                   ] * n_out,
        grid=(rows_p // br,),
        in_specs=[sbs, bs, bs] + [bs] * n_slots,
        out_specs=[bs] * n_out,
        # donate p and every slot buffer: input i=1 -> output 0 (params),
        # input 3+j -> output 1+j (slot j). g is read-only.
        input_output_aliases={1: 0, **{3 + j: 1 + j
                                       for j in range(n_slots)}},
        interpret=interpret,
    )(scal, p2, g2, *slots2)
    flat = [o.reshape(-1)[:n] for o in outs]
    return flat[0], tuple(flat[1:])


def flat_update(kind: str, hyper: Dict, p, g, slots, lr, step, *,
                block_rows: Optional[int] = None,
                use_pallas: Optional[bool] = None,
                interpret: bool = False):
    """One fused update over flat fp32 vectors: `p`, `g` (n,), `slots` a
    tuple of (n,) — (m, v) for adam/adamw, (velocity,) or () for sgd.
    Returns (p_new, slots_new). Engine: Pallas on TPU (or when forced
    with `use_pallas=True, interpret=True` for CPU tests), plain fused
    XLA math otherwise."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and pltpu is not None
    bc1, bc2 = _bias_corrections(kind, hyper, step)
    if not use_pallas:
        return _math(kind, hyper, p, g, slots, lr, bc1, bc2)
    if block_rows is None:
        from bigdl_tpu.kernels import autotune
        block_rows = autotune.lookup(
            "fused_update",
            {"kind": kind, "n": int(p.shape[0]), "dtype": "float32"},
            autotune._DEFAULTS["fused_update"])["block_rows"]
    return _pallas_flat(kind, hyper, p, g, slots, jnp.float32(lr),
                        jnp.float32(bc1), jnp.float32(bc2),
                        int(block_rows), interpret)


# --------------------------------------------------------- tree-level API
def make_update_fn(method, *, layout: str = "auto",
                   use_pallas: Optional[bool] = None,
                   interpret: bool = False,
                   block_rows: Optional[int] = None) -> Optional[Callable]:
    """A drop-in replacement for `method.update` (same
    ``(params, grads, slots, lr, step) -> (new_params, new_slots)``
    signature) running the fused kernel, or None when the method has no
    fused form. `layout`: 'flat' (concat all float leaves — the Pallas
    engine's form), 'leaf' (per-leaf, native dtype — sharded trees and
    CPU), or 'auto' (flat on a TPU backend, leaf elsewhere)."""
    desc = describe(method)
    if desc is None:
        return None
    if layout == "auto":
        on_tpu = jax.default_backend() == "tpu" and pltpu is not None
        layout = "flat" if (use_pallas or (use_pallas is None and on_tpu)) \
            else "leaf"
    if layout not in ("flat", "leaf"):
        raise ValueError(f"unknown fused-update layout {layout!r}")
    kind, hyper = desc
    names = slot_names(kind, hyper)

    def update(params, grads, slots, lr, step):
        from bigdl_tpu import observe
        leaves_p, treedef = jax.tree.flatten(params)
        leaves_g = treedef.flatten_up_to(grads)
        slot_leaves = [treedef.flatten_up_to(slots[nm]) for nm in names]
        active = [i for i, l in enumerate(leaves_p)
                  if jnp.issubdtype(l.dtype, jnp.inexact)]
        if not active:
            return params, slots
        bc1, bc2 = _bias_corrections(kind, hyper, step)

        new_p = list(leaves_p)
        new_slots = [list(sl) for sl in slot_leaves]
        with observe.phase("kernel/fused_update", cat="kernel"):
            if layout == "leaf":
                for i in active:
                    pn, sn = _math(kind, hyper, leaves_p[i], leaves_g[i],
                                   tuple(sl[i] for sl in slot_leaves),
                                   lr, bc1, bc2)
                    new_p[i] = pn
                    for j, s in enumerate(sn):
                        new_slots[j][i] = s
            else:
                shapes = [leaves_p[i].shape for i in active]
                sizes = [leaves_p[i].size for i in active]

                def flat(leaves):
                    return jnp.concatenate(
                        [leaves[i].astype(jnp.float32).ravel()
                         for i in active])

                fp = flat(leaves_p)
                fg = flat(leaves_g)
                fslots = tuple(flat(sl) for sl in slot_leaves)
                pn, sn = flat_update(kind, hyper, fp, fg, fslots, lr,
                                     step, block_rows=block_rows,
                                     use_pallas=use_pallas,
                                     interpret=interpret)

                offs = []
                acc = 0
                for s in sizes[:-1]:
                    acc += s
                    offs.append(acc)

                def split_back(fvec, out_list):
                    # the per-leaf dtype cast is the kernel's epilogue:
                    # fp32 compute, leaf-native storage
                    parts = jnp.split(fvec, offs) if offs else [fvec]
                    for j, i in enumerate(active):
                        out_list[i] = parts[j].reshape(shapes[j]).astype(
                            out_list[i].dtype)

                split_back(pn, new_p)
                for j, s in enumerate(sn):
                    split_back(s, new_slots[j])

        out_slots = slots
        if names:
            out_slots = dict(slots)
            for j, nm in enumerate(names):
                out_slots[nm] = treedef.unflatten(new_slots[j])
        return treedef.unflatten(new_p), out_slots

    update.__name__ = f"fused_{kind}_update"
    return update
