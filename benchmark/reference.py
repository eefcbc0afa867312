"""The plain reference: GPT-2 in `jax.numpy`, float32, highest precision.

No cache, no batching tricks, no kernels, nothing imported from the
program. Forward pass, next-token cross-entropy, its gradients row block by
row block, and Adam, written from the published equations (Radford et al.
2019; Kingma & Ba 2015). Layers are stacked and scanned so that the 48-layer
model compiles in seconds, and each block is rematerialised in the backward
pass so that the float32 reference fits beside nothing else on one chip.

`mode` chooses the arithmetic:
  * "float32"  — the reference: float32 throughout, matmul precision highest;
  * "bfloat16" — the control of a float32 configuration: weights and every
    activation in bfloat16;
  * "fp8"      — the control of a bfloat16 configuration: both operands of
    every matrix product rounded to float8_e4m3fn (straight-through in the
    backward pass), the rest in float32.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

def _round_fp8(x):
    q = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


def _mm(mode):
    """The matrix product of a mode: (..., k) @ (k, n)."""
    if mode == "float32":
        return functools.partial(jnp.matmul,
                                 precision=jax.lax.Precision.HIGHEST)
    if mode == "bfloat16":
        return jnp.matmul
    if mode != "fp8":
        raise ValueError(f"the reference has no mode {mode!r}")
    return lambda a, b: jnp.matmul(_round_fp8(a), _round_fp8(b),
                                   precision=jax.lax.Precision.HIGHEST)


def _ln(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lw, n_head, eps, mode):
    mm = _mm(mode)
    B, T, d = x.shape
    hd = d // n_head
    h = _ln(x, lw["ln1_w"], lw["ln1_b"], eps)
    split = lambda a: a.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)  # noqa: E731
    q = split(mm(h, lw["wq"]) + lw["bq"])
    k = split(mm(h, lw["wk"]) + lw["bk"])
    v = split(mm(h, lw["wv"]) + lw["bv"])
    if mode == "fp8":
        q, k, v = _round_fp8(q), _round_fp8(k), _round_fp8(v)
    prec = None if mode == "bfloat16" else jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    if mode == "fp8":
        p = _round_fp8(p)
    a = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=prec)
    a = a.transpose(0, 2, 1, 3).reshape(B, T, d)
    x = x + mm(a, lw["wo"]) + lw["bo"]
    h = _ln(x, lw["ln2_w"], lw["ln2_b"], eps)
    f = _gelu_new(mm(h, lw["w1"]) + lw["b1"])
    return x + mm(f, lw["w2"]) + lw["b2"]


def logits_fn(w, tokens, *, n_head, eps, mode="float32"):
    """tokens (B, T) int32 -> (B, T, vocab) logits, head tied to `wte`."""
    dtype = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    T = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:T]
    body = jax.checkpoint(
        lambda x, lw: (_block(x, lw, n_head, eps, mode), None))
    x, _ = jax.lax.scan(body, x, w["layers"])
    x = _ln(x, w["lnf_w"], w["lnf_b"], eps)
    return _mm(mode)(x, w["wte"].T)


# ------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("n_head", "eps", "control_mode"))
def _row_gaps(w, tokens, following, *, n_head, eps, control_mode):
    """Per position of one row: how far below the reference's best logit lie
    the token that follows in the sequence and (with `control_mode`) the
    token the lower precision puts first. Reduced on the device: the logits
    of a row are tens of MB, the gaps a few hundred numbers."""
    kw = dict(n_head=n_head, eps=eps)
    ref = logits_fn(w, tokens, mode="float32", **kw)[0].astype(jnp.float32)
    best = ref.max(-1)
    pick = lambda ids: jnp.take_along_axis(ref, ids[:, None], -1)[:, 0]  # noqa: E731
    low = None
    if control_mode:
        low = best - pick(logits_fn(w, tokens, mode=control_mode,
                                    **kw)[0].argmax(-1))
    return jnp.isfinite(ref).all(), best - pick(following), low


def served_gaps(w, cfg, prompt, served, pad_to, control_mode=None):
    """One finished request against the reference, teacher-forced along the
    served sequence. Returns per served token how far its reference logit
    lies below the reference's best. With `control_mode` also, at the same
    positions of the same prompt and tokens, the same for the token that
    the lower precision puts first there (it need not decode)."""
    n, P = len(served), len(prompt)
    seq = list(prompt) + list(served)
    tokens = np.zeros((1, pad_to), np.int32)    # causal: the tail is unseen
    tokens[0, :len(seq)] = seq
    following = np.zeros((pad_to,), np.int32)
    following[:len(seq) - 1] = seq[1:]
    finite, gap, low = _row_gaps(
        w, tokens, following, n_head=cfg["n_head"],
        eps=cfg["layer_norm_epsilon"], control_mode=control_mode)
    at = np.arange(P - 1, P - 1 + n)            # position that predicts token k
    out = {"finite": bool(finite), "gap": np.asarray(gap)[at]}
    if control_mode:
        out["control_gap"] = np.asarray(low)[at]
    return out


# ------------------------------------------------------------ training
def _block_loss(w, x, y, *, n_head, eps, mode, denom):
    """Sum of next-token cross-entropies of a block of rows, over `denom`
    (the whole batch's tokens), so that blocks add up to the batch mean."""
    logits = logits_fn(w, x, n_head=n_head, eps=eps, mode=mode)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return jnp.sum(nll) / denom


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "mode",
                                             "denom"))
def _block_grad(w, x, y, *, n_head, eps, mode, denom):
    return jax.value_and_grad(_block_loss)(
        w, x, y, n_head=n_head, eps=eps, mode=mode, denom=denom)


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def loss_and_grads(w, cfg, x, y, mode="float32", rows=2):
    """Mean cross-entropy of the batch (x, y) and its gradient, `rows` rows
    at a time so that the activations of one block are all that is alive."""
    kw = dict(n_head=cfg["n_head"], eps=cfg["layer_norm_epsilon"], mode=mode,
              denom=float(x.shape[0] * x.shape[1]))
    loss, grads = 0.0, None
    for r in range(0, x.shape[0], rows):
        l, g = _block_grad(w, x[r:r + rows], y[r:r + rows], **kw)
        loss += float(l)
        grads = g if grads is None else _tree_add(grads, g)
    return loss, grads


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


@jax.jit
def stacked_norms(tree, minus=None):
    """L2 norm of every leaf of a stacked tree (or of `tree - minus`), one
    norm a layer, in the program's layout so that names line up."""
    from weights import program_tree
    if minus is not None:
        tree = jax.tree.map(
            lambda p, q: p.astype(jnp.float32) - q.astype(jnp.float32),
            tree, minus)
    return norms(program_tree(tree))


def named(tree):
    """{"h3/attn/wq": float, ...} from a tree of scalars."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for path, v in flat}


def train_trajectory(w, cfg, batches, *, lr, mode="float32", rows=2,
                     steps=3):
    """Follow the trainer through its first `steps` steps on `batches`
    ((x, y) pairs). Returns each step's loss, the first step's gradient
    norms and the norms of the parameters' change after the last step, each
    by leaf under the program's names. `w` is used up (donated)."""
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    w0 = copy(w)
    m, v = zeros(w), zeros(w)
    losses, grad_norms = [], None
    for t in range(1, steps + 1):
        x, y = batches[t - 1]
        loss, g = loss_and_grads(w, cfg, jnp.asarray(x), jnp.asarray(y),
                                 mode=mode, rows=rows)
        losses.append(loss)
        if t == 1:
            grad_norms = named(stacked_norms(g))
        w, m, v = adam(w, g, m, v, t, lr=lr)
        del g
    change = named(stacked_norms(w, w0))
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}
