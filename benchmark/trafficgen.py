"""The one traffic generator: a mix file and a seed give the requests.

A mix is a data file (`benchmark/traffic/<name>.json`) with a `kind`:
`closed_loop` (`clients` callers, each sending its next request when the
last one is answered), `open_loop` (`rate_per_s`, Poisson arrivals) or
`train` (read by the training cell, not here). Lengths are
`{"dist": "uniform"|"log_uniform", "min", "max"}`. `shared_prefix_tokens`
with `prefix_pool` > 0 puts one of a few seeded prefixes before every
prompt.

Every seed gets the SAME set of sizes and the SAME set of arrival gaps, in
another order: the sizes are the quantiles of the distribution on a fixed
grid of `pool` points, and the seed only permutes them and draws the token
ids. The order is dealt in blocks (`stratified_order`): every `block`
requests in a row hold one prompt length out of every `pool / block`
neighbouring ones, so a window that ends anywhere has sent the same spread of
prompts whatever the seed. So two seeds differ in which request meets which,
not in how much work there is. This file imports nothing but numpy, so the
client process can use it without touching JAX.
"""

import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
POOL = 256
BLOCK = 32


def read_mix(path):
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("closed_loop", "open_loop", "train"):
        raise ValueError(f"traffic {path}: unknown kind {mix.get('kind')!r}")
    return mix


def load_mix(name):
    return read_mix(os.path.join(HERE, "traffic", name + ".json"))


def quantiles(dist, n):
    """`n` whole lengths on the fixed grid (i + 1/2) / n of `dist`."""
    if isinstance(dist, (int, float)):
        return np.full(n, int(dist), np.int64)
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["min"]), float(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + u * (hi + 1 - lo)
    elif dist["dist"] == "log_uniform":
        x = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
    else:
        raise ValueError(f"unknown dist {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def stratified_order(rng, n, block):
    """A seeded order of the ranks 0..n-1 of the sorted sizes: ranks
    j * n/block .. (j + 1) * n/block - 1 are stratum j, each stratum deals
    one rank to each of the n/block blocks, and each block is shuffled."""
    per = n // block
    if per * block != n:
        raise ValueError(f"a pool of {n} sizes is not whole blocks of {block}")
    dealt = rng.permuted(np.arange(n).reshape(block, per), axis=1)
    return rng.permuted(dealt.T, axis=1).reshape(-1)


class Requests:
    """The requests of one run, by index, made on demand from the seed."""

    def __init__(self, mix, seed, vocab, eos_id, max_seq_len):
        self.mix, self.seed = mix, int(seed)
        self.vocab, self.eos_id = int(vocab), int(eos_id)
        n = int(mix.get("pool", POOL))
        fixed = np.random.default_rng(0x5EED)       # the same for every seed
        prompt = quantiles(mix["prompt_tokens"], n)
        out = fixed.permutation(quantiles(mix["output_tokens"], n))
        self.n_prefix = int(mix.get("prefix_pool", 0) or 0)
        if self.n_prefix:
            plen = fixed.permutation(
                quantiles(mix["shared_prefix_tokens"], self.n_prefix))
            self.prefixes = [self._ids((5, j), int(plen[j]))
                             for j in range(self.n_prefix)]
        else:
            self.prefixes = []
        order = stratified_order(np.random.default_rng([self.seed, 1]), n,
                                 int(mix.get("block", math.gcd(BLOCK, n))))
        self.prompt_len, self.out_len = prompt[order], out[order]
        longest = max((len(p) for p in self.prefixes), default=0) \
            + int(prompt.max()) + int(out.max())
        if longest > max_seq_len:
            raise ValueError(
                f"a request of this mix needs {longest} positions, a slot "
                f"holds {max_seq_len}: no operation may fail by design")
        if mix["kind"] == "open_loop":
            gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / float(
                mix["rate_per_s"])
            self.gaps = gaps[np.random.default_rng(
                [self.seed, 2]).permutation(n)]

    def _ids(self, what, length):
        rng = np.random.default_rng([self.seed, 3] + [int(w) for w in what])
        ids = rng.integers(0, self.vocab - 1, size=length)
        # every id but the eos id
        return [int(t) + (int(t) >= self.eos_id) for t in ids]

    def __getitem__(self, i):
        """Request `i` (any whole number; sizes repeat every `pool`)."""
        k = i % len(self.prompt_len)
        own = self._ids((7, i), int(self.prompt_len[k]))
        prefix = self.prefixes[i % self.n_prefix] if self.n_prefix else []
        return {"prompt": prefix + own, "max_new_tokens": int(self.out_len[k])}

    def due_times(self, seconds):
        """Arrival times of an open loop inside [0, seconds)."""
        t, out, i = 0.0, [], 0
        while True:
            t += float(self.gaps[i % len(self.gaps)])
            if t >= seconds:
                return out
            out.append(t)
            i += 1
