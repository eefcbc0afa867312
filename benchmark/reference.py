"""The plain reference, as far as it is the same for every family.

A family (`benchmark/families/<name>`) writes its forward pass from the
published equations in `jax.numpy`: `logits(w, cfg, tokens, mode)`, with no
cache, no batching tricks, no kernels and nothing imported from the program.
What is made of it is here, so that every family is held to the same
comparison: the gaps of a served sequence, the next-token cross-entropy and
its gradients row block by row block, Adam (Kingma & Ba 2015), the norms by
leaf, and the trainer's first steps followed.

`mode` chooses the arithmetic; a family lists the ones its forward pass can
be computed in under `MODES`:
  * "float32"  — the reference: float32 throughout, matmul precision highest;
  * "bfloat16" — the control of a float32 configuration: weights and every
    activation in bfloat16;
  * "fp8"      — the control of a bfloat16 configuration: both operands of
    every matrix product rounded to float8_e4m3fn (straight-through in the
    backward pass), the rest in float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def round_fp8(x):
    q = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


def mm(mode):
    """The matrix product of a mode: (..., k) @ (k, n)."""
    if mode == "float32":
        return functools.partial(jnp.matmul,
                                 precision=jax.lax.Precision.HIGHEST)
    if mode == "bfloat16":
        return jnp.matmul
    if mode != "fp8":
        raise ValueError(f"the reference has no mode {mode!r}")
    return lambda a, b: jnp.matmul(round_fp8(a), round_fp8(b),
                                   precision=jax.lax.Precision.HIGHEST)


# ------------------------------------------------------------- serving
def served_gaps_of(family, cfg, control_mode=None):
    """-> `gaps(w, prompt, served, pad_to)`: one finished request against
    the family's reference, teacher-forced along the served sequence.
    Returns per served token how far its reference logit lies below the
    reference's best. With `control_mode` also, at the same positions of
    the same prompt and tokens, the same for the token that the lower
    precision puts first there (it need not decode)."""

    @jax.jit
    def _row_gaps(w, tokens, following):
        """Per position of one row. Reduced on the device: the logits of a
        row are tens of MB, the gaps a few hundred numbers."""
        ref = family.logits(w, cfg, tokens, "float32")[0].astype(jnp.float32)
        best = ref.max(-1)
        pick = lambda ids: jnp.take_along_axis(ref, ids[:, None], -1)[:, 0]  # noqa: E731
        low = None
        if control_mode:
            low = best - pick(family.logits(w, cfg, tokens,
                                            control_mode)[0].argmax(-1))
        return jnp.isfinite(ref).all(), best - pick(following), low

    def gaps(w, prompt, served, pad_to):
        n, P = len(served), len(prompt)
        seq = list(prompt) + list(served)
        tokens = np.zeros((1, pad_to), np.int32)    # causal: the tail is unseen
        tokens[0, :len(seq)] = seq
        following = np.zeros((pad_to,), np.int32)
        following[:len(seq) - 1] = seq[1:]
        finite, gap, low = _row_gaps(w, tokens, following)
        at = np.arange(P - 1, P - 1 + n)        # position that predicts token k
        out = {"finite": bool(finite), "gap": np.asarray(gap)[at]}
        if control_mode:
            out["control_gap"] = np.asarray(low)[at]
        return out

    return gaps


# ------------------------------------------------------------ training
def _block_loss(w, x, y, *, family, cfg, mode, denom):
    """Sum of next-token cross-entropies of a block of rows, over `denom`
    (the whole batch's tokens), so that blocks add up to the batch mean."""
    logits = family.logits(w, cfg, x, mode)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return jnp.sum(nll) / denom


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def loss_and_grads_of(family, cfg, mode, denom, rows=2):
    """-> `loss_and_grads(w, x, y)`: mean cross-entropy of the batch (x, y)
    of `denom` tokens and its gradient, `rows` rows at a time so that the
    activations of one block are all that is alive."""
    _block_grad = jax.jit(jax.value_and_grad(functools.partial(
        _block_loss, family=family, cfg=cfg, mode=mode, denom=denom)))

    def loss_and_grads(w, x, y):
        loss, grads = 0.0, None
        for r in range(0, x.shape[0], rows):
            l, g = _block_grad(w, x[r:r + rows], y[r:r + rows])
            loss += float(l)
            grads = g if grads is None else _tree_add(grads, g)
        return loss, grads

    return loss_and_grads


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def adam(w, g, m, v, t, *, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam with bias correction; `t` counts from 1."""
    m = jax.tree.map(lambda mm, gg: b1 * mm + (1 - b1) * gg, m, g)
    v = jax.tree.map(lambda vv, gg: b2 * vv + (1 - b2) * gg * gg, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    w = jax.tree.map(
        lambda p, mm, vv: p - lr * (mm / c1) / (jnp.sqrt(vv / c2) + eps),
        w, m, v)
    return w, m, v


def norms(tree):
    """L2 norm of every leaf."""
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


@functools.partial(jax.jit, static_argnames=("layout",))
def stacked_norms(tree, minus=None, *, layout):
    """L2 norm of every leaf of a tree in the reference's layout (or of
    `tree - minus`), in the program's layout (`layout`: the family's
    `program_tree`) so that names line up."""
    if minus is not None:
        tree = jax.tree.map(
            lambda p, q: p.astype(jnp.float32) - q.astype(jnp.float32),
            tree, minus)
    return norms(layout(tree))


def named(tree):
    """{"h3/attn/wq": float, ...} from a tree of scalars."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for path, v in flat}


def train_trajectory(family, w, cfg, batches, *, lr, mode="float32", rows=2,
                     steps=3):
    """Follow the trainer through its first `steps` steps on `batches`
    ((x, y) pairs of one shape). Returns each step's loss, the first step's
    gradient norms and the norms of the parameters' change after the last
    step, each by leaf under the program's names. `w` is used up (donated)."""
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    x0 = batches[0][0]
    loss_and_grads = loss_and_grads_of(
        family, cfg, mode, float(x0.shape[0] * x0.shape[1]), rows)
    w0 = copy(w)
    m, v = zeros(w), zeros(w)
    losses, grad_norms = [], None
    for t in range(1, steps + 1):
        x, y = batches[t - 1]
        loss, g = loss_and_grads(w, jnp.asarray(x), jnp.asarray(y))
        losses.append(loss)
        if t == 1:
            grad_norms = named(stacked_norms(g, layout=family.program_tree))
        w, m, v = adam(w, g, m, v, t, lr=lr)
        del g
    change = named(stacked_norms(w, w0, layout=family.program_tree))
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}
