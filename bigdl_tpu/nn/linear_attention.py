"""Linear attention with a recurrent state: the gated delta rule (no
reference analogue; Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464).

Where softmax attention keeps every key and value, this layer keeps one
matrix per head, `S` in R^(dk x dv), and rewrites it token by token:

    S' = exp(g_t) S_(t-1)                 decay, g_t <= 0
    u_t = beta_t (v_t - S'^T k_t)         what the state has wrong about k_t
    S_t = S' + k_t u_t^T                  the delta rule
    o_t = S_t^T q_t

with `q`, `k`, `v` from a short causal depthwise convolution over time and
SiLU, `q` and `k` L2-normalised per head, `beta_t = sigmoid(W_b x_t)` (twice
that with `allow_neg_eigval`, so the state's transition can reflect as well
as shrink) and `g_t = -exp(A_log) softplus(W_a x_t + dt_bias)`. The output
is `W_o [RMSNorm(o_t) * silu(W_g x_t)]`, the norm per head.

The same function is computed three ways, each where it is cheapest:

  * `gated_delta_step` - one token, the recurrence as written: elementwise
    products and sums on the state, for the decode step;
  * `gated_delta_chunk` - a whole chunk of C tokens in matrix products (the
    paper's WY form): with gamma_i the decays cumulated inside the chunk and
    A_ij = beta_i exp(gamma_i - gamma_j) (k_i . k_j) for j < i,
        U = (I + A)^-1 [beta (V - exp(gamma) K S_0)]
        O = exp(gamma) Q S_0 + tril(Q K^T exp(gamma_i - gamma_j)) U
        S_C = exp(gamma_C) S_0 + (exp(gamma_C - gamma) K)^T U
    one unit lower-triangular solve per head, for prefill and `apply`;
  * `lax.scan` over chunks carries the state between them.

What the state is made of, and in which precision: `S`, the cumulated
decays, every product that touches `S` and the norms' statistics are
float32 (precision highest on the matrix products) whatever the weights
are; the carried convolution inputs have the activations' dtype. The
state's axis order is decided here and nowhere else: `(slots, heads, dk,
dv)`, the order both forms use it in, so that a donated state is rewritten
where it lies (tests/test_chip_compile.py holds the compiled programs to
it).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core import init as initializers
from bigdl_tpu.core.module import Module, ParamSpec
from bigdl_tpu.nn.attention import join_rows, split_rows
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.normalization import RMSNorm

_HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def gated_delta_step(S, q, k, v, g, beta):
    """One token of the recurrence. S (..., dk, dv) float32; q, k (..., dk);
    v (..., dv); g, beta (...). Returns (S_t, o_t (..., dv))."""
    S = S * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.sum(S * k[..., :, None], axis=-2))
    S = S + k[..., :, None] * u[..., None, :]
    return S, jnp.sum(S * q[..., :, None], axis=-2)


def gated_delta_chunk(S, q, k, v, g, beta):
    """C tokens at once, equal to C calls of `gated_delta_step`. S (..., dk,
    dv); q, k (..., C, dk); v (..., C, dv); g, beta (..., C); all float32.
    Returns (S_C, O (..., C, dv)). A token with beta = 0 and g = 0 leaves
    the state as it was, which is how a padded tail is left out."""
    C, dv = v.shape[-2], v.shape[-1]
    gamma = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((C, C), bool))
    # exp(gamma_i - gamma_j) for j <= i only: above the diagonal the
    # difference is positive and may overflow, so it is masked first
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], 0.0)), 0.0)
    kk = jnp.einsum("...id,...jd->...ij", k, k, precision=_HI)
    A = jnp.where(jnp.tril(lower, -1), beta[..., :, None] * decay * kk, 0.0)
    eg = jnp.exp(gamma)[..., None]
    rhs = beta[..., None] * jnp.concatenate([v, eg * k], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=A.dtype), rhs, lower=True, unit_diagonal=True)
    U = sol[..., :dv] - jnp.matmul(sol[..., dv:], S, precision=_HI)
    qk = jnp.einsum("...id,...jd->...ij", q, k, precision=_HI)
    O = jnp.matmul(eg * q, S, precision=_HI) \
        + jnp.matmul(decay * qk, U, precision=_HI)
    last = gamma[..., -1:]
    S = jnp.exp(last)[..., None] * S + jnp.einsum(
        "...jd,...jv->...dv", jnp.exp(last - gamma)[..., None] * k, U,
        precision=_HI)
    return S, O


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


class GatedDeltaNet(Module):
    """The gated delta-rule mixer of one block: x (B, T, d_model) ->
    (B, T, d_model). `apply` runs whole sequences from a zero state;
    `prefill_step` and `decode_step` run a slot batch against a carried
    state `{"S": (slots, heads, dk, dv) float32, "conv": (slots, kernel-1,
    channels)}` (`make_state`) for the decode engine (serve/decode.py)."""

    def __init__(self, d_model: int, num_heads: int, key_dim: int,
                 value_dim: int, conv_kernel: int = 4,
                 allow_neg_eigval: bool = True, eps: float = 1e-6,
                 chunk: int = 64, name: Optional[str] = None):
        super().__init__(name or "GatedDeltaNet")
        self.d_model, self.num_heads = d_model, num_heads
        self.key_dim, self.value_dim = key_dim, value_dim
        self.conv_kernel, self.chunk = conv_kernel, chunk
        self.beta_scale = 2.0 if allow_neg_eigval else 1.0
        H = num_heads
        self.channels = 2 * H * key_dim + H * value_dim
        for name_, width in (("q", H * key_dim), ("k", H * key_dim),
                             ("v", H * value_dim), ("g", H * value_dim),
                             ("a", H), ("b", H)):
            self.add_child(name_, Linear(d_model, width, bias=False))
        self.add_child("o", Linear(H * value_dim, d_model, bias=False))
        self.add_child("norm", RMSNorm(value_dim, eps=eps))

    def param_specs(self):
        H = self.num_heads
        return {
            # out[t] = sum_i conv[:, i] * in[t - (kernel-1) + i]
            "conv": ParamSpec((self.channels, self.conv_kernel),
                              initializers.random_uniform()),
            # the publication's initialisation: the convolution uniform in
            # +-kernel^-1/2; A in [1, 16) -> its log; dt_bias the inverse
            # softplus of a dt in [0.001, 0.1)
            "A_log": ParamSpec((H,), _init_A_log),
            "dt_bias": ParamSpec((H,), _init_dt_bias),
        }

    # ------------------------------------------------------------ pieces
    def make_state(self, num_slots: int, dtype):
        """Zero carried state for `num_slots` independent sequences."""
        return {"S": jnp.zeros((num_slots, self.num_heads, self.key_dim,
                                self.value_dim), jnp.float32),
                "conv": jnp.zeros((num_slots, self.conv_kernel - 1,
                                   self.channels), dtype)}

    def _project(self, params, x):
        """x (N, T, d) -> the convolution's inputs (N, T, channels), and
        beta, g (N, T, H) float32."""
        run = lambda name: self.children()[name].apply(        # noqa: E731
            params[name], {}, x)[0]
        pre = jnp.concatenate([run("q"), run("k"), run("v")], axis=-1)
        f32 = jnp.float32
        beta = self.beta_scale * jax.nn.sigmoid(run("b").astype(f32))
        g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(
            run("a").astype(f32) + params["dt_bias"].astype(f32))
        return pre, beta, g

    def _conv_qkv(self, params, window):
        """window (N, kernel-1+T, channels): the carried inputs then the
        chunk's -> q, k (N, T, H, dk), v (N, T, H, dv), float32."""
        K = self.conv_kernel
        T = window.shape[1] - (K - 1)
        w = params["conv"].astype(jnp.float32)
        window = window.astype(jnp.float32)
        y = jax.nn.silu(sum(window[:, i:i + T] * w[:, i] for i in range(K)))
        N, H, dk = y.shape[0], self.num_heads, self.key_dim
        q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)
        q = _l2norm(q.reshape(N, T, H, dk)) * (1.0 / math.sqrt(dk))
        k = _l2norm(k.reshape(N, T, H, dk))
        return q, k, v.reshape(N, T, H, self.value_dim)

    def _finish(self, params, x, o):
        """o (N, T, H, dv) float32 -> the mixer's output (N, T, d)."""
        c = self.children()
        o, _ = c["norm"].apply(params["norm"], {}, o)
        gate, _ = c["g"].apply(params["g"], {}, x)
        N, T = x.shape[:2]
        o = o.reshape(N, T, -1) * jax.nn.silu(gate.astype(jnp.float32))
        return c["o"].apply(params["o"], {}, o.astype(x.dtype))[0]

    def _chunks(self, S, q, k, v, g, beta):
        """(N, T, H, .) inputs through `gated_delta_chunk`, `self.chunk`
        tokens at a time; a T that is no multiple is padded with tokens that
        change nothing. Returns (S, o (N, T, H, dv))."""
        T, C = q.shape[1], self.chunk
        heads = lambda a: jnp.moveaxis(a, 1, 2)     # noqa: E731 (N, H, T, .)
        q, k, v, g, beta = (heads(a) for a in (q, k, v, g, beta))
        if T <= C:
            S, o = gated_delta_chunk(S, q, k, v, g, beta)
            return S, heads(o)
        n = -(-T // C)
        pad = lambda a: jnp.pad(                               # noqa: E731
            a, [(0, 0), (0, 0), (0, n * C - T)] + [(0, 0)] * (a.ndim - 3))
        # (n, N, H, C, .): chunks lead for the scan
        split = lambda a: jnp.moveaxis(                        # noqa: E731
            pad(a).reshape(a.shape[:2] + (n, C) + a.shape[3:]), 2, 0)
        S, o = jax.lax.scan(
            lambda S, xs: gated_delta_chunk(S, *xs), S,
            tuple(split(a) for a in (q, k, v, g, beta)))
        o = jnp.moveaxis(o, 0, 2).reshape(o.shape[1:3] + (n * C, -1))
        return S, heads(o[:, :, :T])

    # -------------------------------------------------------- entry points
    def _apply(self, params, state, x, *, training=False, rng=None):
        """Whole sequences from a zero state, chunk by chunk."""
        N = x.shape[0]
        pre, beta, g = self._project(params, x)
        zero = self.make_state(N, pre.dtype)
        q, k, v = self._conv_qkv(
            params, jnp.concatenate([zero["conv"], pre], axis=1))
        _, o = self._chunks(zero["S"], q, k, v, g, beta)
        return self._finish(params, x, o), state

    def prefill_step(self, params, x, carried, positions, lengths):
        """One prompt chunk a slot: x (N, C, d); positions (N, C) int32,
        consecutive from `positions[:, 0]`; lengths (N,) int32 = the valid
        leading tokens of each row (0 = inactive). A row whose chunk starts
        at position 0 starts from a zero state, whatever its slot held. A
        token past a row's length changes nothing, and the carried
        convolution inputs are the last valid ones. Returns (out (N, C, d),
        new state); a row of length 0 gets its state back bit for bit."""
        o, new = self._chunk_form(params, *self._project(params, x),
                                  carried, positions, lengths)
        return self._finish(params, x, o), new

    def _chunk_form(self, params, pre, beta, g, carried, positions, lengths):
        """`prefill_step` between its projections and `_finish`: ->
        (o (N, C, H, dv) float32, new state)."""
        C = pre.shape[1]
        active = lengths > 0
        S, conv = _zero_rows(carried, active & (positions[:, 0] == 0))
        valid = (jnp.arange(C) < lengths[:, None])[..., None]
        beta, g = jnp.where(valid, beta, 0.0), jnp.where(valid, g, 0.0)
        window = jnp.concatenate([conv, pre.astype(conv.dtype)], axis=1)
        q, k, v = self._conv_qkv(params, window)
        S, o = self._chunks(S, q, k, v, g, beta)
        # the inputs at chunk indices lengths-3 .. lengths-1, which are the
        # window's rows lengths .. lengths+2
        at = lengths[:, None] + jnp.arange(self.conv_kernel - 1)
        conv = jnp.take_along_axis(window, at[..., None], axis=1)
        return o, _keep_idle_rows(active, S, conv, carried)

    def decode_step(self, params, x, carried, positions, active):
        """One token a slot, the recurrence as written: x (N, 1, d);
        positions (N,) int32; active (N,) bool. A row at position 0 starts
        from a zero state. Returns (out (N, 1, d), new state); inactive
        rows get their state back bit for bit."""
        o, new = self._token_form(params, *self._project(params, x),
                                  carried, positions, active)
        return self._finish(params, x, o), new

    def _token_form(self, params, pre, beta, g, carried, positions, active):
        """`decode_step` between its projections and `_finish`: ->
        (o (N, 1, H, dv) float32, new state)."""
        S, conv = _zero_rows(carried, active & (positions == 0))
        window = jnp.concatenate([conv, pre.astype(conv.dtype)], axis=1)
        q, k, v = self._conv_qkv(params, window)
        S, o = gated_delta_step(S, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0])
        return o[:, None], _keep_idle_rows(active, S, window[:, 1:], carried)

    def parts_step(self, params, x, carried, parts):
        """Rows of several shapes in one pass (nn/attention.carried_rows):
        x (1, n, d) their joined tokens. The projections in and out are one
        product over all of them; each part's tokens then go through its
        own form, the recurrence (`decode`) or the chunk form, against the
        state of its slots, `part.slots`' rows being taken out of `carried`
        and put back in place. Returns (out (1, n, d), new state)."""
        outs = []
        for part, mine in zip(parts, split_rows(
                parts, *self._project(params, x))):
            own = carried if part.slots is None else jax.tree.map(
                lambda a: a.at[part.slots].get(mode="promise_in_bounds"),
                carried)
            if part.decode:
                o, own = self._token_form(params, *mine, own,
                                          part.positions[:, 0],
                                          part.lengths > 0)
            else:
                o, own = self._chunk_form(params, *mine, own,
                                          part.positions, part.lengths)
            carried = own if part.slots is None else jax.tree.map(
                lambda a, new: a.at[part.slots].set(
                    new, mode="promise_in_bounds", unique_indices=True),
                carried, own)
            outs.append(o)
        return self._finish(params, x, join_rows(outs)), carried


def _zero_rows(carried, fresh):
    """(S, conv) of a carried state, zero in the rows where `fresh`."""
    return (jnp.where(fresh[:, None, None, None], 0.0, carried["S"]),
            jnp.where(fresh[:, None, None], 0, carried["conv"]))


def _keep_idle_rows(active, S, conv, carried):
    """The new state: rows that are not `active` as they were carried."""
    return {"S": jnp.where(active[:, None, None, None], S, carried["S"]),
            "conv": jnp.where(active[:, None, None], conv, carried["conv"])}


def _init_A_log(rng, shape, dtype=jnp.float32, fan_in=None, fan_out=None):
    return jnp.log(jax.random.uniform(rng, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def _init_dt_bias(rng, shape, dtype=jnp.float32, fan_in=None, fan_out=None):
    dt = jnp.exp(jax.random.uniform(rng, shape, jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
