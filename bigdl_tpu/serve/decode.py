"""Iteration-level continuous batching for autoregressive decode.

PR 8's `ContinuousBatcher` packs *whole stateless requests* — for an
autoregressive LM that recomputes the entire prefix every token and
holds the batch fixed until the slowest sequence finishes (head-of-line
blocking). This module is the decode-native path (Orca-style
iteration-level scheduling + vLLM-style slot KV management, scaled to
this codebase's discipline):

  * **paged KV pool** — the KV cache is one shared pool of fixed-size
    blocks a layer (`BIGDL_TPU_SERVE_KV_BLOCK` tokens each, in the
    layout of nn/attention.make_paged_kv_pool), allocated ONCE per model
    and donated across steps (TPU: the step writes in place; CPU:
    donation is a no-op), plus per-slot int32 block tables (vLLM's
    PagedAttention discipline). Each of the S slots is an independent
    sequence at its own absolute offset; the programs write a chunk's
    K/V into the slot's blocks in place and attend over the pool where
    it lies, under a mask of which slot owns which block
    (nn/attention.paged_slot_cached_attend), so no per-slot copy of the
    cache is ever made. HBM cost follows LIVE sequences, not the
    (num_slots x max_seq_len) worst case: slots acquire blocks lazily
    as their frontier crosses a block boundary and retire returns them
    to the free list; admission refuses with a block-level
    `CapacityError` capacity report when a request can never fit the
    pool.
  * **fused decode step** — ONE AOT-precompiled program
    `(params, caches, tokens_last, positions, active, block_table) ->
    (next_tokens, caches)` over the ragged active set: inactive rows
    and a rounded-up bucket's padded tail are left out of the pool
    write (pad-poison can never leak, PR 5/8), and entries past a row's
    frontier are masked to NEG_INF pre-softmax, so stale pool content
    contributes exactly zero.
  * **chunked prefill** — prompts stream into their slot's cache
    through power-of-two length-bucketed AOT prefill programs
    (`BIGDL_TPU_SERVE_PREFILL_CHUNK` caps the chunk), so a long prompt
    stalls concurrent decode for at most one chunk and the program
    count stays O(log chunk). A call computes the rows that stream a
    prompt: every bucket's program is over ONE row (the slot's index,
    its block-table row, its row of a state resident by slot, put back
    in place), and the full chunk keeps a `num_slots`-row program for
    the burst in which half the slots or more stream a full chunk in one
    iteration (`DecodeEntry.prefill_rows`). A call of its own is what a
    chunk costs only while nothing decodes: an iteration that dispatches
    a decode step runs ONE program, the step, which CARRIES the chunk of
    one streaming slot, the one admitted first (for each bucket of
    `carried_buckets`, those of 64 tokens and more, a program `(step's
    arguments, chunk_tokens (1, b), chunk_positions (1, b), chunk_table
    (1, M), chunk_length (1,), chunk_slot (1,)) -> (next_tokens,
    caches)`; a shorter chunk is padded up to the smallest carried bucket
    and masked by its length). The step's rows and the chunk's tokens go
    through every product of the model as one operand, so the weights,
    which bound both programs, are read once and not twice; the chunk's
    part goes first wherever rows are told apart, so the slot whose
    prompt it completes is a row of the same step; every other
    streaming slot keeps its place and rides a later step, first come
    first served, so no token of a decoding slot waits behind a second
    program, and two chunks cost two carrying steps (and two tokens a
    decoding slot) where they cost a step and a call. A model without
    the carrying form keeps the call beside the step.
  * **iteration-level scheduler** — clock-injectable (the batcher.py
    fake-clock testing discipline): every iteration first admits
    queued requests into free slots (prefill), then runs one fused step
    over whatever is active; finished sequences (EOS or
    max_new_tokens) retire IMMEDIATELY and free their slot. O(L) per
    token per sequence instead of O(L²), no head-of-line blocking.
  * **one decode step in flight** — an iteration enqueues step N+1
    BEFORE it fetches step N's tokens (the iteration's single host
    sync): a slot that continues feeds N+1 its own output of N on the
    device (a tiny merge program picks, row by row, that or the token
    the host knows), and position, block table and sampling arguments
    never needed the token, nor does the prompt chunk the step carries
    (a request whose last chunk rides step N is a decode row of step N
    too, with its last prompt token, which the program computes behind
    the chunk: its first token comes out of the carrying step). So the
    fetch, the push to the streams,
    retirement, the gauges, the cancel sweep, admission and the next
    prefill chunks run beside the chip's step and not between two of
    them; device order (one stream, the donated caches threaded
    through) keeps every write behind the reads it must follow. A
    sequence that ends by count is known ahead and simply has no row
    in N+1. One that ends by VALUE (EOS) or is cancelled while its row
    of N+1 is in flight costs that row: its output is dropped at the
    fetch (requests are matched by identity, a slot may have changed
    hands), its stray KV write lies inside its own reservation, in
    blocks any later owner overwrites by programs enqueued after it,
    and a recurrent state restarts at position 0. A slot cancelled
    while its chunk rides the step in flight is the same case: the
    chunk's write lies in blocks it had reserved. The tokens are those
    of the serial loop, bit for bit. What a step in flight costs is
    paid by a request that arrives: its prefill queues behind every
    step already enqueued. So while a slot is free and nobody is
    queued, the iteration waits for the taker (until the step in
    flight is done, never longer) before it enqueues the next step:
    the request a closed-loop caller sends when its last one retires
    lands a few ms after the fetch, and its prefill then runs behind
    one step and not behind two.

The model contract is duck-typed and names what a model owns
(`_PAGED_CONTRACT`; docs/serving.md "What a served model provides"):
its cache pytree (`make_paged_slot_caches`), its hidden-state function
over a slot batch (`paged_hidden`) and its final norm and head
(`head_logits`), plus `vocab_size`, (default) `eos_id` and, where the
cache holds more than keys and values, `slot_resident`. The prefill
program, the step and the choice of token (argmax or nn/sampling.py)
are composed from them in `DecodeEntry._build`. One optional argument,
`paged_hidden(..., chunk=)`, is the carrying form: the hidden states of
a step's rows with a prompt chunk of streaming slots computed in the same
pass (nn/attention.carried_rows). GPT2LM and LlamaLM
(interop/huggingface.py), OlmoHybridLM (interop/olmo_hybrid.py) and
GlmMoeDsaLM (interop/glm_moe_dsa.py) provide all of it. A second optional
piece, the draft piece (`_DRAFT_CONTRACT`, Glm4MoeLiteLM's multi-token
prediction module, interop/glm4_moe_lite.py), is served under
`speculative_tokens=1`: every step verifies the draft the step before it
made for a slot beside the slot's last token, yields one token or two, and
drafts anew (`DecodeEntry._build` `_verify`); the tokens are those of the
serial greedy loop.

On top of the block table sits the **prefix cache**: whole prompt
blocks finished by prefill are published under a chained token-hash
key (stage-at-admit / commit-as-the-frontier-passes), so N requests
sharing a system prompt pay its prefill once; entries are refcounted,
copy-on-write never triggers (matching is block-granular, the divergence block is
always private), and unreferenced entries are retained up to a cap,
evicted LRU on demand and swept wholesale under memory-watchdog
pressure.

Decode greedy semantics mirror `model.generate(kv_cache=True,
beam_size=1)` exactly: prefill the first P-1 prompt tokens, feed the
last prompt token as the first decode input, argmax per step, stop at
EOS — concurrent decode with staggered joins/leaves is BIT-IDENTICAL
to each sequence run alone (tests/test_decode.py parity oracle).

Observability: `serve/<model>/decode/{tokens_per_s, slot_occupancy,
prefill_ms, step_ms, queue_wait_ms, latency_ms, ttft_ms}` + counters
(`steps`; `steps_ahead`, the steps enqueued while the one before them
was unfetched; `rows_dropped`, the rows computed for a sequence that
had ended by value or been cancelled; `prefill_tokens`, `prefill_calls`
and `prefill_rows`, the chunk advances, the rows they computed, streaming
or not, and the valid tokens they wrote, whether the advance was a call
or rode a step; `prefill_carried`, those that rode a step),
a `decode` section in /statusz,
per-peer decode rows in /fleetz, and the ServeWatchdog pointed at
decode latency p99 with queue-vs-prefill-vs-step attribution
(observe/doctor.py). `step_ms` is what one iteration costs a token:
from a step's dispatch, or from the fetch before it where that came
later (a step was in flight), to its own fetch's return; `prefill_ms`
times the calls alone (a carried chunk's time is its step's).
"""

from __future__ import annotations

import inspect
import logging
import queue as _queue
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from bigdl_tpu import observe
from bigdl_tpu.serve.batcher import (BATCH_FILL_BOUNDS, LATENCY_MS_BOUNDS,
                                     Closed, Overloaded)
from bigdl_tpu.utils.threads import make_condition, spawn

log = logging.getLogger("bigdl_tpu")

# how often the scheduler, waiting for a request to take a free slot, looks
# whether the step in flight is done (DecodeScheduler._await_taker)
_TAKER_POLL_S = 0.0005

# what a served model provides: its cache pytree, its hidden-state function
# over a slot batch, its final norm and head (DecodeEntry._build composes the
# programs from them)
_PAGED_CONTRACT = ("make_paged_slot_caches", "paged_hidden", "head_logits")
# the optional draft piece (`speculative_tokens=1`): a module that drafts the
# token after next from a step's hidden states (`paged_draft`, with its own
# `draft_logits`), whose pool a prompt fills through `paged_hidden(...,
# follow=)`
_DRAFT_CONTRACT = ("paged_draft", "draft_logits")


class BlockPool:
    """Host-side free-list allocator over the device KV block pool.

    Pure bookkeeping (the device arrays never move): `total` blocks
    split into free-list blocks, LIVE blocks (acquired by running
    requests, or prefix-cache entries with refs > 0), and CACHED blocks
    (prefix-cache entries with refs == 0 — evictable on demand, so they
    count as reservable). `reserve()` promises capacity at admission;
    `acquire_reserved()` turns one promise into a concrete block id,
    evicting an LRU cached entry when the free list runs dry.

    NOT thread-safe — the scheduler serializes every call under its
    condition lock (the utils/threads discipline)."""

    def __init__(self, total: int):
        if total < 1:
            raise ValueError(f"KV pool needs >= 1 block, got {total}")
        self.total = int(total)
        self._free: List[int] = list(range(self.total - 1, -1, -1))
        self.reserved = 0
        self.live = 0
        # wired by PrefixCache when prefix caching is on
        self.cached_count: Callable[[], int] = lambda: 0
        self.evict_one: Callable[[], Optional[int]] = lambda: None

    @property
    def free(self) -> int:
        return len(self._free)

    def available(self) -> int:
        """Blocks reservable right now: free + evictable-cached minus
        outstanding reservations."""
        return self.free + self.cached_count() - self.reserved

    def reserve(self, n: int) -> bool:
        if n > self.available():
            return False
        self.reserved += n
        return True

    def unreserve(self, n: int) -> None:
        self.reserved = max(0, self.reserved - n)

    def acquire_reserved(self) -> int:
        """One reserved block -> concrete block id (free list first,
        then LRU prefix-cache eviction — reserve() guaranteed one of
        the two exists)."""
        if not self._free:
            b = self.evict_one()
            if b is None:
                raise RuntimeError(
                    "KV pool reservation accounting violated: no free "
                    "or evictable block for an admitted request")
            self._free.append(b)
        self.reserved -= 1
        self.live += 1
        return self._free.pop()

    def release(self, block: int) -> None:
        """Return one live private block to the free list."""
        self.live -= 1
        self._free.append(block)


class _PrefixEntry:
    __slots__ = ("key", "block", "refs", "tick")

    def __init__(self, key: bytes, block: int, tick: int):
        self.key = key
        self.block = block
        self.refs = 1
        self.tick = tick


class PrefixCache:
    """Refcounted shared-prefix KV blocks over a :class:`BlockPool`.

    Keys are a CHAINED blake2b hash over whole prompt blocks
    (`h_j = H(h_{j-1} || tokens[j*B:(j+1)*B])`), so holding key j
    implies the entire j-block prefix matches — matching is a simple
    walk until the first miss. Only blocks fully inside the PREFILL
    region (the first P-1 prompt tokens) are ever keyed; matching is
    block-granular, so the divergence block is always private and
    copy-on-write never has to copy.

    Lifecycle: a request STAGES its chain keys at admission; as its
    prefill frontier passes the end of block j the block is COMMITTED — published with refs=1
    (the committer's own reference). Later requests `take()` committed
    runs (incref). Retire decrefs; at refs==0 the entry stays CACHED
    (evictable) up to `cap` unreferenced blocks — beyond it, and
    whenever the pool needs a block, the LRU entry is evicted; a
    memory-watchdog alert sweeps every unreferenced entry.

    Same lock discipline as BlockPool: the scheduler serializes."""

    def __init__(self, pool: BlockPool, cap: int):
        self.pool = pool
        self.cap = int(cap)
        self._entries: Dict[bytes, _PrefixEntry] = {}
        self._ref0 = 0
        self._tick = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.committed = 0
        pool.cached_count = self.cached_count
        pool.evict_one = self._evict_lru

    @staticmethod
    def chain_keys(prompt: np.ndarray, block: int, prefill_target: int,
                   lookahead: int = 0) -> List[bytes]:
        """The chained hash key of every whole prompt block inside the
        prefill region (tokens [0, prefill_target)). `lookahead` 1 keys a
        block with the token after it too, for a model whose rows of a
        block depend on it (a draft module's row of the pair (h_j,
        t_j+1), serve/decode.py `DecodeEntry.drafting`): two prompts that
        share a block and differ in the next token share no entry."""
        import hashlib
        keys: List[bytes] = []
        h = b""
        for j in range(prefill_target // block):
            h = hashlib.blake2b(
                h + np.ascontiguousarray(
                    prompt[j * block:(j + 1) * block]).tobytes(),
                digest_size=16).digest()
            end = (j + 1) * block
            keys.append(h if not lookahead else hashlib.blake2b(
                h + np.ascontiguousarray(
                    prompt[end:end + lookahead]).tobytes(),
                digest_size=16).digest())
        return keys

    def cached_count(self) -> int:
        return self._ref0

    def peek(self, keys: List[bytes]) -> int:
        """Longest committed-prefix run length — no refcount change
        (admission sizes its reservation with this before taking)."""
        m = 0
        for k in keys:
            if k not in self._entries:
                break
            m += 1
        return m

    def take(self, keys: List[bytes], m: int) -> List[int]:
        """Incref the first `m` entries and return their block ids;
        records m hits and len(keys)-m misses."""
        blocks: List[int] = []
        for k in keys[:m]:
            e = self._entries[k]
            if e.refs == 0:          # cached -> live again
                self._ref0 -= 1
                self.pool.live += 1
            e.refs += 1
            self._tick += 1
            e.tick = self._tick
            blocks.append(e.block)
        self.hits += m
        self.misses += len(keys) - m
        return blocks

    def commit(self, key: bytes, block: int) -> bool:
        """Publish a live private block under its chain key (refs=1 —
        the committer keeps holding it). False when the key is already
        present (a concurrent request with the same prefix committed
        first; the caller's copy stays private)."""
        if key in self._entries:
            return False
        self._tick += 1
        self._entries[key] = _PrefixEntry(key, int(block), self._tick)
        self.committed += 1
        return True

    def decref(self, key: bytes) -> None:
        e = self._entries.get(key)
        if e is None:
            return
        e.refs -= 1
        if e.refs == 0:
            self._ref0 += 1
            self.pool.live -= 1
            self._tick += 1
            e.tick = self._tick
            while self._ref0 > self.cap:
                b = self._evict_lru()
                if b is None:
                    break
                self.pool._free.append(b)

    def _evict_lru(self) -> Optional[int]:
        """Drop the least-recently-used UNREFERENCED entry; returns its
        block id (the caller owns it now) or None when nothing is
        evictable."""
        victim = None
        for e in self._entries.values():
            if e.refs == 0 and (victim is None or e.tick < victim.tick):
                victim = e
        if victim is None:
            return None
        del self._entries[victim.key]
        self._ref0 -= 1
        self.evictions += 1
        return victim.block

    def sweep(self) -> int:
        """Evict EVERY unreferenced entry (memory-watchdog pressure) —
        their blocks go back to the pool's free list."""
        n = 0
        while True:
            b = self._evict_lru()
            if b is None:
                return n
            self.pool._free.append(b)
            n += 1


def prefill_buckets(chunk: int) -> Tuple[int, ...]:
    """Power-of-two prompt-chunk ladder: 1, 2, 4, ... up to `chunk` —
    O(log chunk) prefill programs total."""
    if chunk < 1:
        raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
    out: List[int] = []
    b = 1
    while b < chunk:
        out.append(b)
        b *= 2
    out.append(chunk)
    return tuple(sorted(set(out)))


def carried_buckets(buckets: Tuple[int, ...]) -> Tuple[int, ...]:
    """The buckets of the ladder a decode step carries a prompt chunk at:
    those of 64 tokens and more (the full chunk alone where it is shorter);
    a shorter chunk is padded up to the smallest of them and masked by its
    length. Set-up pays for each program (~3 s a program of 48 layers from
    a warm cache), and under 64 tokens beside a step's rows every product
    is bound by reading its weights, so a shorter bucket buys the chunk's
    attention alone: carrying steps of 8 | 16 | 32 | 64 tokens take 12.42 |
    12.43 | 12.49 | 13.18 ms in GPT-2 XL on a v5e (PERF.md section 5)."""
    return tuple(b for b in buckets if b >= min(64, buckets[-1]))


class DecodeEntry:
    """One decode-served model: its paged KV pool (with, for a model
    that has one, its state resident by slot), AOT prefill + decode
    executables (mesh shardings pinned), and the placed params the
    programs close over.

    Built by `ModelEntry` under `decode=True` registration
    (serve/registry.py); the scheduler (`DecodeScheduler`) drives it."""

    def __init__(self, name: str, model, params, *, mesh=None,
                 num_slots: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 paged: Optional[bool] = None,
                 kv_block: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_blocks: Optional[int] = None,
                 sampling: Optional[bool] = None,
                 kv_shard: Optional[bool] = None,
                 speculative_tokens: Optional[int] = None):
        from bigdl_tpu.utils import config
        # `paged` (here, on ModelEntry and on ServeEngine.register) is kept
        # only because benchmark/configs/*-serve.json hold `"paged": true`
        # and the harness passes its whole `register` object as keywords
        if paged is not None and not paged:
            raise ValueError(
                f"paged=False: the dense slot bucket was removed; "
                f"{type(model).__name__} is served from the paged KV pool "
                f"(leave `paged` out)")
        lacks = [m for m in _PAGED_CONTRACT if not hasattr(model, m)]
        if lacks:
            raise TypeError(
                f"decode=True needs a model implementing the paged "
                f"slot-decode contract {_PAGED_CONTRACT}; "
                f"{type(model).__name__} lacks {lacks} (docs/serving.md, "
                f"\"What a served model provides\")")
        self.name = name
        self.model = model
        self.params = params
        self.mesh = mesh
        self.num_slots = int(num_slots if num_slots is not None
                             else config.get("SERVE_DECODE_SLOTS"))
        n_pos = getattr(model, "n_positions", None)
        if max_seq_len is None:
            # the default follows the model: a slot cache longer than
            # the position table could never be used
            max_seq_len = config.get("SERVE_MAX_SEQ_LEN")
            if n_pos is not None:
                max_seq_len = min(max_seq_len, n_pos)
        self.max_seq_len = int(max_seq_len)
        self.prefill_chunk = int(
            prefill_chunk if prefill_chunk is not None
            else config.get("SERVE_PREFILL_CHUNK"))
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got "
                             f"{self.num_slots}")
        if n_pos is not None and self.max_seq_len > n_pos:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} > the model's "
                f"n_positions {n_pos} (slot caches cannot outrun the "
                f"position table)")
        self.prefill_chunk = min(self.prefill_chunk, self.max_seq_len)
        self.buckets = prefill_buckets(self.prefill_chunk)
        # the optional carrying form (`paged_hidden(..., chunk=)`): a decode
        # step then advances a streaming slot by a prompt chunk in the same
        # pass over the weights, at these buckets; () keeps the two-call
        # iteration
        self.carried = (
            carried_buckets(self.buckets)
            if "chunk" in inspect.signature(model.paged_hidden).parameters
            else ())
        self.eos_id = (eos_id if eos_id is not None
                       else getattr(model, "eos_id", None))
        if self.eos_id is None:
            raise ValueError(
                f"decode model {name!r} carries no eos_id — pass "
                f"eos_id= at registration")
        self.vocab_size = int(model.vocab_size)
        # what the model counts on the device (`step_counters(caches)`,
        # running int32 totals): a step sends them back behind its tokens
        self.counter_names = tuple(getattr(model, "counter_names", ()))
        # a model whose attention admits at most so many tokens a query
        self.index_topk = getattr(model, "index_topk", None)
        # a model whose cache holds more than keys and values says which
        # leaves are resident by slot (leading axis num_slots: a recurrent
        # state) and not by block
        self.slot_state = hasattr(model, "slot_resident")
        self.kv_block = int(kv_block if kv_block is not None
                            else config.get("SERVE_KV_BLOCK"))
        if self.kv_block < 1:
            raise ValueError(f"kv_block must be >= 1, got "
                             f"{self.kv_block}")
        self.blocks_per_slot = -(-self.max_seq_len // self.kv_block)
        pool = int(kv_pool_blocks if kv_pool_blocks is not None
                   else config.get("SERVE_KV_POOL_BLOCKS"))
        # 0: every slot at full length
        self.pool_blocks = (pool if pool > 0
                            else self.num_slots * self.blocks_per_slot)
        self.sampling = (bool(config.get("SERVE_SAMPLING"))
                         if sampling is None else bool(sampling))
        # tokens a step drafts a slot (0 or 1): with 1, a step verifies the
        # draft of the step before it beside the slot's last token and
        # advances a slot by one or two (`_build`, `_verify`)
        self.drafting = int(speculative_tokens or 0)
        if self.drafting not in (0, 1):
            raise ValueError(f"speculative_tokens={speculative_tokens}: a "
                             f"step verifies one drafted token or none")
        if self.drafting:
            lacks = [m for m in _DRAFT_CONTRACT if not hasattr(model, m)]
            if lacks:
                raise TypeError(
                    f"speculative_tokens=1 needs a model with the draft "
                    f"piece {_DRAFT_CONTRACT}; {type(model).__name__} lacks "
                    f"{lacks} (docs/serving.md)")
            if self.sampling:
                raise ValueError("speculative_tokens=1 verifies greedy "
                                 "tokens: register without sampling")
        if self.slot_state and prefix_cache:
            raise ValueError(
                f"prefix_cache=True cannot serve {type(model).__name__}: "
                f"a prefix hit skips the prefill of tokens whose recurrent "
                f"state no KV block holds (the prefix cache hashes token "
                f"blocks; the slot-resident state is not snapshotted at "
                f"block boundaries)")
        self.prefix_cache = not self.slot_state and (
            bool(config.get("SERVE_PREFIX_CACHE"))
            if prefix_cache is None else bool(prefix_cache))
        cap = int(prefix_cache_blocks if prefix_cache_blocks is not None
                  else config.get("SERVE_PREFIX_CACHE_BLOCKS"))
        self.prefix_cache_cap = cap if cap > 0 else self.pool_blocks // 2
        self.kv_shard = (bool(config.get("SERVE_KV_SHARD"))
                         if kv_shard is None else bool(kv_shard))
        self._shard_axis = None
        if self.kv_shard:
            if mesh is None:
                raise ValueError("kv_shard=True needs a mesh at "
                                 "registration (parallel.create_mesh)")
            from bigdl_tpu.parallel.mesh import DATA_AXIS
            axis = (DATA_AXIS if DATA_AXIS in mesh.axis_names
                    else mesh.axis_names[0])
            self._shard_axis = axis
            n = int(mesh.shape[axis])
            # round the pool up to axis divisibility — every device
            # holds an equal shard of the block dimension
            self.pool_blocks = -(-self.pool_blocks // n) * n
        # memory plane (observe/memz.py): the KV residency is the decode
        # path's dominant resident — size it in CLOSED FORM from
        # eval_shape (zero allocation) and refuse the registration up
        # front when params + pool exceed the remaining headroom,
        # instead of OOMing on the first decode step. The pool sizes to
        # pool_blocks x kv_block tokens, not slots x max_seq_len.
        import jax
        from bigdl_tpu.observe import memz as _memz
        cache_specs = self._cache_specs = jax.eval_shape(self._raw_caches,
                                                         params)
        what = (f"decode model {name!r} ({self.pool_blocks} KV "
                f"blocks x {self.kv_block} tokens paged pool")
        # True at each leaf resident by slot, None where every leaf is KV
        self._slot_mask = (model.slot_resident(cache_specs)
                           if self.slot_state else None)
        self.kv_cache_bytes = _memz.tree_nbytes(cache_specs)
        self.state_bytes = _memz.tree_nbytes(
            self.split_caches(cache_specs)[1])
        if self.slot_state:
            what += (f" + {self.state_bytes:,} bytes of state resident by "
                     f"slot")
        self.kv_pool_bytes = self.kv_cache_bytes - self.state_bytes
        self.state_kind = "kv+recurrent" if self.slot_state else "kv"
        # parameters that already lie on the device are in the backend's
        # bytes in use, and `_place` makes no second copy of them; under a
        # mesh every leaf is placed anew
        to_place = _memz.tree_nbytes(
            [a for a in jax.tree.leaves(params)
             if mesh is not None or not isinstance(a, jax.Array)])
        _memz.admission_check(
            self.kv_cache_bytes + to_place,
            f"{what} = {self.kv_cache_bytes:,} bytes + {to_place:,} bytes "
            f"of params yet to be placed)")
        self._jit_decode = None
        self._jit_verify = None
        self._jit_carry = None
        self._jit_prefill = None
        self._jit_prefill_rows = None
        self._jit_merge = None
        self._aot_decode = None
        self._aot_carry: Dict[int, object] = {}   # bucket -> carrying step
        self._aot_merge = None
        # bucket -> the one-row program; the num_slots-row program of the
        # full chunk
        self._aot_prefill: Dict[int, object] = {}
        self._aot_prefill_all = None
        self._placed = None          # (params, caches) device-resident
        self._shardings = None
        self._build()

    # ------------------------------------------------------------- build
    def _build(self):
        import jax
        model = self.model
        donate = (jax.default_backend() != "cpu")
        kw_d = {"donate_argnums": (1,)} if donate else {}
        kw_p = dict(kw_d)
        self._rep_sharding = None
        self._pool_sharding = None
        self._cache_sh = None       # the caches' shardings, leaf by leaf
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(self.mesh, P())
            # the non-cache shardings are pinned REPLICATED: decode
            # steps are tiny and latency-bound, so the mesh buys program
            # portability (one registration path for meshed servers),
            # not FLOPs. kv_shard=True additionally shards the pool's
            # BLOCK dimension over the data axis (blocks stay whole on a
            # device) — the pool is the one decode resident worth
            # splitting at real-chip scale.
            self._rep_sharding = rep
            self._cache_sh = jax.tree.map(lambda _: rep, self._cache_specs)
            if self.kv_shard:
                # a leaf's block dimension is the one that grows with the
                # pool, wherever the model's layout puts it; a leaf that
                # has none (resident by slot, a count) is replicated
                grown = jax.eval_shape(
                    lambda p: self._raw_caches(p, self.pool_blocks + 1),
                    self.params)
                self._cache_sh = jax.tree.map(
                    lambda a, b: next(
                        (NamedSharding(self.mesh, P(*[None] * i,
                                                    self._shard_axis))
                         for i in range(len(a.shape))
                         if a.shape[i] != b.shape[i]), rep),
                    self._cache_specs, grown)
                self._pool_sharding = next(
                    sh for sh in jax.tree.leaves(self._cache_sh)
                    if sh is not rep)
            cache_sh = self._cache_sh
            # in_shardings as a per-argument prefix pytree: the cache
            # subtree takes the pool sharding, everything else is
            # replicated. Argument layouts (see the programs below):
            #   decode:  (params, caches, tokens, positions, active,
            #             table[, temps, top_ks, top_ps, seeds])
            #   carry:   decode's, then a one-row prefill's five
            #   prefill: (params, caches, tokens, positions, table,
            #             lengths[, slots])
            n_samp = 4 if self.sampling else 0
            kw_d["in_shardings"] = (rep, cache_sh) + (rep,) * (4 + n_samp)
            kw_d["out_shardings"] = (rep, cache_sh)
            kw_p["in_shardings"] = (rep, cache_sh) + (rep,) * (
                4 + self.drafting)
            kw_p["out_shardings"] = cache_sh
        kw_r = dict(kw_p)
        if "in_shardings" in kw_r:
            kw_r["in_shardings"] = kw_r["in_shardings"][:6] + (
                self._rep_sharding,) * (1 + self.drafting)
        # the programs, composed from what the model owns: the token choice
        # is the server's, greedy or sampled as the registration says
        jnp = jax.numpy
        if self.sampling:
            from bigdl_tpu.nn.sampling import sample_tokens

            def choose(logits, pos, temps, tks, tps, seeds):
                return sample_tokens(logits, temps, tks, tps, seeds, pos)
        else:
            def choose(logits, pos):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # (a closure that named `self` would tie the entry, and the arrays
        # it holds, into a cycle only the collector frees)
        counted = bool(self.counter_names)

        def chosen(p, c, x, pos, *samp):
            nxt = choose(model.head_logits(p, x), pos, *samp)
            if counted:
                nxt = jnp.concatenate(
                    [nxt, model.step_counters(c).astype(jnp.int32)])
            return nxt, c

        def _step(p, c, t, pos, a, bt, *samp):
            x, c = model.paged_hidden(
                p, c, t[:, None], pos[:, None], bt, a.astype(jnp.int32),
                decode=True)
            return chosen(p, c, x, pos, *samp)

        def _carry(p, c, t, pos, a, bt, *rest):
            # the step, and in the same pass, before it where rows are
            # told apart, the prompt chunk of the slots `rest[-1]` (their
            # tokens, positions, block-table rows, lengths): every weight
            # is read once for both
            *samp, ct, cpos, cbt, cln, cslots = rest
            x, c = model.paged_hidden(
                p, c, t[:, None], pos[:, None], bt, a.astype(jnp.int32),
                decode=True, chunk=(ct, cpos, cbt, cln, cslots))
            return chosen(p, c, x, pos, *samp)

        def _prefill(p, c, t, pos, bt, ln, *follow):
            # lengths masks the rounded-up bucket's padded tail (and
            # inactive rows) out of the pool write; no logits. `follow`
            # (drafting): the token after each, for the draft module's rows
            kw = {"follow": follow[0]} if follow else {}
            return model.paged_hidden(p, c, t, pos, bt, ln, decode=False,
                                      **kw)[1]

        mask = self._slot_mask

        def _prefill_rows(p, c, t, pos, bt, ln, slots, *follow):
            # the same chunk over the rows of the slots `slots` (R,) and no
            # others: a pool is found through a row's block table whatever
            # the row count; a leaf resident by slot gives the call its rows
            # and takes them back where they lay, so a slot that does not
            # stream is never read
            if mask is None:
                return _prefill(p, c, t, pos, bt, ln, *follow)
            own = jax.tree.map(
                lambda by_slot, a: a.at[slots].get(
                    mode="promise_in_bounds") if by_slot else a, mask, c)
            return jax.tree.map(
                lambda by_slot, a, new: a.at[slots].set(
                    new, mode="promise_in_bounds", unique_indices=True)
                if by_slot else new, mask, c,
                _prefill(p, own, t, pos, bt, ln, *follow))

        def _verify(p, c, rows, a, bt, *chunk):
            # `rows` (4, S): each slot's last token, its draft, whether it
            # has one, the last token's position. The main model computes
            # both (the draft's row only where there is one), the draft is
            # accepted where the last token's argmax is the draft, and the
            # draft module then drafts from the last pair it knows: (h_n,
            # a) and, accepted, (h_n+1, b). With `chunk` (the carried
            # prompt chunk's five and the tokens that follow its tokens)
            # the same pass advances a streaming slot too.
            t, d, has, pos = rows
            S = t.shape[0]
            ln = a.astype(jnp.int32)
            two = pos[:, None] + jnp.arange(2, dtype=jnp.int32)
            kw = ({"chunk": tuple(chunk[:5]), "follow": chunk[5]} if chunk
                  else {})
            x, c = model.paged_hidden(p, c, jnp.stack([t, d], axis=1), two,
                                      bt, ln * (1 + has), decode=True, **kw)
            best = jnp.argmax(model.head_logits(
                p, x.reshape(2 * S, 1, -1)), axis=-1).astype(
                    jnp.int32).reshape(S, 2)
            acc = ((has > 0) & (best[:, 0] == d)).astype(jnp.int32)
            g, c = model.paged_draft(p, c, x, best, two, bt, ln * (1 + acc))
            draft = jnp.argmax(model.draft_logits(p, jnp.where(
                acc[:, None, None] > 0, g[:, 1:], g[:, :1])),
                axis=-1).astype(jnp.int32)
            out = [best[:, 0], best[:, 1], acc, draft, pos + 1 + acc]
            if counted:
                out.append(model.step_counters(c).astype(jnp.int32))
            return jnp.concatenate(out), c

        self._jit_decode = jax.jit(_step, **kw_d)
        # a drafting registration's step: (params, caches, rows (4, S),
        # active, table); the plain step stays for what compiles it alone
        # (benchmark/rehearse_compile.py)
        kw_v = dict(kw_d)
        if "in_shardings" in kw_v:
            kw_v["in_shardings"] = kw_v["in_shardings"][:5]
        self._jit_verify = (jax.jit(_verify, **kw_v) if self.drafting
                            else None)
        if self.carried:
            kw_c = dict(kw_v if self.drafting else kw_d)
            if "in_shardings" in kw_c:
                kw_c["in_shardings"] += (self._rep_sharding,) * (
                    5 + self.drafting)
            self._jit_carry = jax.jit(_verify if self.drafting else _carry,
                                      **kw_c)
        self._jit_prefill = jax.jit(_prefill, **kw_p)
        self._jit_prefill_rows = jax.jit(_prefill_rows, **kw_r)
        # the next step's input tokens while this step's are still on the
        # device: a row that continues takes its own output, every other
        # row what the host knows (DecodeScheduler._dispatch_step)
        kw_m = ({} if self._rep_sharding is None else
                {"in_shardings": (self._rep_sharding,) * 3,
                 "out_shardings": self._rep_sharding})
        S = self.num_slots

        def _merge_rows(use_prev, prev, host):
            # a drafting step's rows (4, S) from `_verify`'s output: the
            # last token (the draft's argmax where it was accepted), the
            # new draft, one, the last token's position
            a, b, acc, d, at = (prev[i * S:(i + 1) * S] for i in range(5))
            dev = jnp.stack([jnp.where(acc > 0, b, a), d,
                             jnp.ones_like(a), at])
            return jnp.where(use_prev[None], dev, host)
        self._jit_merge = jax.jit(
            _merge_rows if self.drafting else
            lambda use_prev, prev, host: jnp.where(
                use_prev, prev[:S] if counted else prev, host), **kw_m)

    def _place(self, a):
        """`a` where the programs take it; an array already on the device
        stays there."""
        import jax
        if self._rep_sharding is None:
            return jax.numpy.asarray(a)
        if not isinstance(a, jax.Array):
            a = np.asarray(a)
        return jax.device_put(a, self._rep_sharding)

    def placed_params(self):
        if self._placed is None:
            import jax
            self._placed = jax.tree.map(self._place, self.params)
        return self._placed

    def _raw_caches(self, params, pool_blocks: Optional[int] = None):
        """The model's zero cache pytree for this registration: the pools
        of blocks (of whatever shapes the model lays out) with, for a model
        that has one, its state resident by slot."""
        by_slot = {"num_slots": self.num_slots} if self.slot_state else {}
        return self.model.make_paged_slot_caches(
            params, pool_blocks or self.pool_blocks, self.kv_block,
            **by_slot)

    def split_caches(self, caches):
        """(the leaves pooled by block, the leaves resident by slot)."""
        import jax
        leaves = jax.tree.leaves(caches)
        if self._slot_mask is None:
            return leaves, []
        mask = jax.tree.leaves(self._slot_mask)
        return ([a for a, m in zip(leaves, mask) if not m],
                [a for a, m in zip(leaves, mask) if m])

    def make_caches(self):
        """The persistent cache pytree (zeros, placed)."""
        caches = self._raw_caches(self.params)
        if self._cache_sh is not None:
            import jax
            caches = jax.device_put(caches, self._cache_sh)
        return caches

    # --------------------------------------------------------------- AOT
    def precompile(self) -> Dict[str, Dict]:
        """AOT-compile the fused decode step, the prefill programs and the
        token merge before traffic (compilecache.precompile_fixed) — with
        the persistent compile cache warm, a restarted decode server
        compiles ZERO fresh programs (counter-asserted in
        tests/test_decode.py). The prefill programs: one over ONE row (the
        slot that streams a prompt, named by its index) for every bucket,
        and the `num_slots`-row program of the full chunk, for the burst in
        which many slots stream a full chunk at once (`prefill_rows`); and
        for each of `carried`, the step that carries such a row. Cost
        analyses land under `compile/serve/<model>/decode/...`."""
        import jax
        from bigdl_tpu.compilecache import precompile_fixed

        def spec(shape, dtype, sharding=None):
            sh = sharding or self._rep_sharding
            kw = {"sharding": sh} if sh is not None else {}
            return jax.ShapeDtypeStruct(shape, dtype, **kw)

        p_s = jax.tree.map(lambda a: spec(tuple(a.shape), a.dtype),
                           self.params)
        raw = self._cache_specs
        if self._cache_sh is None:
            c_s = jax.tree.map(lambda a: spec(tuple(a.shape), a.dtype), raw)
        else:
            c_s = jax.tree.map(
                lambda a, sh: spec(tuple(a.shape), a.dtype, sharding=sh),
                raw, self._cache_sh)
        S, M = self.num_slots, self.blocks_per_slot
        i32 = np.dtype(np.int32)
        f32 = np.dtype(np.float32)
        vec = spec((S,), i32)
        act = spec((S,), np.dtype(np.bool_))
        table = spec((S, M), i32)
        samp = ((spec((S,), f32), vec, spec((S,), f32), vec)
                if self.sampling else ())
        d_args = ((p_s, c_s, spec((4, S), i32), act, table)
                  if self.drafting else (p_s, c_s, vec, vec, act, table)
                  + samp)
        results: Dict[str, Dict] = {}
        cost, self._aot_decode = precompile_fixed(
            self._jit_verify or self._jit_decode, d_args,
            name=f"serve/{self.name}/decode/step")
        self._assert_pool_sharding(self._aot_decode)
        results["decode_step"] = cost
        row = spec((1,), i32)
        for b in self.buckets:
            chunk = spec((1, b), i32)
            one_row = (chunk, chunk, spec((1, M), i32), row, row) + (
                (chunk,) * self.drafting)
            cost, exe = precompile_fixed(
                self._jit_prefill_rows, (p_s, c_s) + one_row,
                name=f"serve/{self.name}/decode/prefill{b}")
            self._assert_pool_sharding(exe)
            self._aot_prefill[b] = exe
            results[f"prefill{b}"] = cost
            if b in self.carried:
                cost, exe = precompile_fixed(
                    self._jit_carry, d_args + one_row,
                    name=f"serve/{self.name}/decode/carry{b}")
                self._assert_pool_sharding(exe)
                self._aot_carry[b] = exe
                results[f"carry{b}"] = cost
        C = self.prefill_chunk
        chunk = spec((S, C), i32)
        cost, self._aot_prefill_all = precompile_fixed(
            self._jit_prefill, (p_s, c_s, chunk, chunk, table, vec)
            + (chunk,) * self.drafting,
            name=f"serve/{self.name}/decode/prefill{C}x{S}")
        self._assert_pool_sharding(self._aot_prefill_all)
        results[f"prefill{C}x{S}"] = cost
        out = (5 if self.drafting else 1) * S + len(self.counter_names)
        cost, self._aot_merge = precompile_fixed(
            self._jit_merge,
            (act, spec((out,), i32),
             spec((4, S), i32) if self.drafting else vec),
            name=f"serve/{self.name}/decode/merge")
        results["merge"] = cost
        return results

    def prefill_rows(self, C: int, k: int) -> int:
        """Rows of the program that advances `k` slots by a chunk of bucket
        `C`: 1 (a one-row call a slot) or `num_slots` (one call for all,
        which exists for the full chunk alone). The one call from half the
        slots on: on a v5e a one-row chunk of 64 takes 11.47 ms (GPT-2 XL)
        and 12.10 ms (Olmo-Hybrid), the 8-row one 34.09 and 45.52 ms, so
        three calls tie with it or beat it and four lose (PERF.md section
        5). The compile-time cost analyses put the switch a slot too early
        for the second: they count bytes, and see neither what its 8-row
        program computes nor its triangular solves."""
        if C == self.prefill_chunk and k >= 2 and 2 * k >= self.num_slots:
            return self.num_slots
        return 1

    def _assert_pool_sharding(self, exe) -> None:
        """kv_shard=True: assert the compiled executable actually
        carries the block-dim NamedSharding spec on its pool inputs —
        a silently-replicated pool would 1/N the capacity win."""
        if self._pool_sharding is None or exe is None:
            return
        import jax
        have = {getattr(s, "spec", None)
                for s in jax.tree.leaves(exe.input_shardings[0])}
        for sh in jax.tree.leaves(self._cache_sh):
            if sh is not self._rep_sharding and sh.spec not in have:
                raise RuntimeError(
                    f"serve[{self.name}]: kv_shard pool sharding {sh.spec} "
                    f"absent from the AOT executable's input shardings — "
                    f"GSPMD dropped the block-dim partition")

    # ------------------------------------------------------------ device
    def run_prefill(self, caches, tokens: np.ndarray, positions, table,
                    lengths, slots: Optional[np.ndarray] = None,
                    follow: Optional[np.ndarray] = None):
        """One chunk-prefill program call; returns the new caches (the
        input cache buffers are donated on TPU). `slots` (R,) int32 names
        the slots whose rows `tokens`, `positions` (R, C), `table` (R, M)
        and `lengths` (R,) hold; None: row i is slot i, all `num_slots`.
        `follow` (R, C), drafting: the token after each of `tokens`."""
        C = tokens.shape[1]
        by_row = slots is not None
        host = (tokens, positions, table, lengths) + ((slots,) if by_row
                                                      else ()) + (
            (follow,) if self.drafting else ())
        args = (self.placed_params(), caches) + \
            tuple(self._place(a) for a in host)
        if by_row:
            exe = self._aot_prefill.get(C) if len(slots) == 1 else None
        else:
            exe = self._aot_prefill_all if C == self.prefill_chunk else None
        if exe is not None:
            try:
                return exe(*args)
            except (TypeError, ValueError):
                # an argument-spec mismatch, raised before the caches
                # are donated; a device error is not caught: the donated
                # buffers are gone and a second try would hide it
                log.warning("serve[%s]: decode prefill%d AOT executable "
                            "rejected live inputs; falling back to jit",
                            self.name, C)
                if by_row:
                    self._aot_prefill.pop(C, None)
                else:
                    self._aot_prefill_all = None
        return (self._jit_prefill_rows if by_row
                else self._jit_prefill)(*args)

    def run_decode(self, caches, tokens_last: np.ndarray, *rest):
        """One fused decode step, enqueued and not waited for; returns
        (next_tokens device array, new caches). `tokens_last` is a host
        array or, for a step enqueued ahead, `merge_tokens`' device array,
        which goes in untouched. The scheduler fetches next_tokens (the
        iteration's single host sync) only after it has enqueued the step
        that follows. `rest` is the trailing host args (positions, active,
        block_table[, temps, top_ks, top_ps, seeds]) and, where the step
        carries a prompt chunk, a one-row prefill call's five behind them
        (tokens (1, C), positions, the slot's block-table row, its length,
        its index), C one of `carried`. A drafting step takes the rows (4,
        S) of `_verify` for `tokens_last`, then (active, block_table) and
        the chunk's five and its `follow`."""
        args = (self.placed_params(), caches,
                self._place(tokens_last)) + \
            tuple(self._place(a) for a in rest)
        n_step = 2 if self.drafting else 7 if self.sampling else 3
        C = rest[n_step + 1].shape[1] if len(rest) > n_step else None
        exe = self._aot_decode if C is None else self._aot_carry.get(C)
        if exe is not None:
            try:
                return exe(*args)
            except (TypeError, ValueError):   # see run_prefill
                log.warning("serve[%s]: decode-step AOT executable "
                            "rejected live inputs; falling back to jit",
                            self.name)
                if C is None:
                    self._aot_decode = None
                else:
                    self._aot_carry.pop(C)
        return (self._jit_verify or self._jit_decode if C is None
                else self._jit_carry)(*args)

    def step_program(self, carried: bool) -> str:
        """The name a device trace gives the step program `run_decode`
        runs (its `XLA Modules` events read `<name>(<hash>)`): the one that
        carries a prompt chunk or the plain one."""
        fn = self._jit_carry if carried else (self._jit_verify
                                              or self._jit_decode)
        return "jit_" + fn.__name__

    def merge_tokens(self, use_prev: np.ndarray, prev_next, host_tokens):
        """`where(use_prev, prev_next, host_tokens)` over the slots, on the
        device: `prev_next` is the unfetched output of the step in flight
        (drafting: the rows (4, S) it leads to, `_merge_rows`)."""
        merge = self._aot_merge or self._jit_merge
        return merge(*(self._place(a)
                       for a in (use_prev, prev_next, host_tokens)))


class GenReply:
    """Streaming-capable handle for one generate request.

    `result(timeout)` blocks for the full generation (np.int32 array of
    generated tokens, EOS included when emitted); `stream(timeout)`
    yields token ids AS THEY DECODE — tokens are pushed at every
    iteration-level step, so a consumer sees the first token at
    time-to-first-token, not at completion."""

    _SENTINEL = object()

    def __init__(self):
        self._tokens: _queue.Queue = _queue.Queue()
        self._done = threading.Event()
        self._cancelled = threading.Event()
        self._result: Optional[np.ndarray] = None
        self._exc: Optional[BaseException] = None

    # -------------------------------------------------- producer side
    def _push(self, token: int) -> None:
        self._tokens.put(int(token))

    def _finish(self, tokens: List[int]) -> None:
        self._result = np.asarray(tokens, np.int32)
        self._tokens.put(self._SENTINEL)
        self._done.set()

    def _fail(self, exc: BaseException) -> None:
        self._exc = exc
        self._tokens.put(self._SENTINEL)
        self._done.set()

    # -------------------------------------------------- consumer side
    def cancel(self) -> None:
        """Abandon the request: the scheduler frees its decode slot at
        the next iteration instead of generating tokens nobody reads
        (the network front calls this when an SSE client disconnects
        mid-stream — serve/net.py). Safe from any thread; a no-op once
        the request completed."""
        self._cancelled.set()

    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._done.wait(timeout):
            raise TimeoutError("generate request still decoding")
        if self._exc is not None:
            raise self._exc
        return self._result

    def stream(self, timeout: Optional[float] = None):
        """Iterate generated token ids as they arrive; raises the
        request's failure (if any) after the stream drains."""
        while True:
            tok = self._tokens.get(timeout=timeout)
            if tok is self._SENTINEL:
                break
            yield tok
        if self._exc is not None:
            raise self._exc


class _GenRequest:
    __slots__ = ("prompt", "max_new", "eos_id", "reply", "t_submit",
                 "t_admit", "t_first", "fed", "generated", "slot", "order",
                 "temperature", "top_k", "top_p", "seed",
                 "need_blocks", "reserved", "shared", "keys",
                 "committed", "commit_upto", "draft")

    def __init__(self, prompt: np.ndarray, max_new: int, eos_id: int,
                 t_submit: float, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.eos_id = int(eos_id)
        self.reply = GenReply()
        self.t_submit = t_submit
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.fed = 0                       # prompt tokens prefilled so far
        self.generated: List[int] = []
        self.slot: Optional[int] = None
        self.order = 0                     # its turn among the admitted
        # sampling (greedy unless temperature > 0; nn/sampling.py)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        # paged-pool bookkeeping (scheduler-owned, under its lock)
        self.need_blocks = 0      # ceil(total tokens / kv_block)
        self.reserved = 0         # reserved, not yet acquired
        self.shared = 0           # leading block-table entries matched
                                  # from the prefix cache (refcounted)
        self.keys: List[bytes] = []        # chain keys (prefill region)
        self.committed: List[int] = []     # key idxs THIS req committed
        self.commit_upto = 0               # next key idx to consider
        # drafting: the token drafted after the last generated one, as the
        # last fetched step left it (None before the first)
        self.draft: Optional[int] = None

    @property
    def prefill_target(self) -> int:
        # mirror generate(kv_cache=True): prefill P-1 tokens, the last
        # prompt token is the first decode input
        return self.prompt.shape[0] - 1

    def next_input(self) -> Tuple[int, int]:
        """(token, position) the next decode step consumes."""
        n = len(self.generated)
        if n == 0:
            return int(self.prompt[-1]), self.prompt.shape[0] - 1
        return self.generated[-1], self.prompt.shape[0] - 1 + n


class _Step(NamedTuple):
    """A decode step that is enqueued and whose tokens are not fetched yet:
    the device array of next tokens, the requests it computes a row for
    (matched by identity when the tokens arrive: a slot may have changed
    hands meanwhile), when it was dispatched and the longest context
    (tokens a row may attend) among its rows; drafting, whether each row
    verifies a draft."""
    nxt: object
    rows: List[_GenRequest]
    t_dispatch: float
    context: int
    drafted: Tuple[bool, ...] = ()


class DecodeScheduler:
    """One decode model's request queue + iteration-level scheduler.

    Every iteration (`step_once`, the clock-injectable synchronous core
    the thread loop composes — batcher.py's testing discipline):

      1. **admit**: pop queued requests into free slots (any number, any
         step — requests join the running batch mid-flight), having
         waited for one while a slot is free, nobody is queued and the
         step in flight is still running;
      2. **prefill**: where the iteration decodes, the oldest streaming
         slot's next chunk is handed to the step, which carries it, and
         the other streaming slots wait; where nothing decodes, every
         streaming slot advances by one length-bucketed chunk, a one-row
         call each (`_prefill_pass`);
      3. **decode**: enqueue one fused step over all prompt-complete
         slots (a slot that continues from the step in flight takes its
         token on the device), then fetch the step that was in flight:
         EOS/max_new retirements complete their reply and free the slot
         IMMEDIATELY — the next iteration admits into it.

    Admission control: `submit` sheds with the typed `Overloaded` past
    `max_queue` waiting requests (the batcher's door discipline), and
    validates prompt + max_new against the slot cache length up front.
    """

    def __init__(self, entry: DecodeEntry, *,
                 max_queue: int = 256,
                 name: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic,
                 start: bool = True):
        from bigdl_tpu.analysis import sancov
        self.entry = entry
        self.name = name or entry.name
        self.max_queue = int(max_queue)
        self._clock = clock
        self._cv = make_condition(f"serve.decode.cv.{self.name}")
        sancov.register_shared(f"serve.decode.queue.{self.name}",
                               self._cv)
        self._queue: List[_GenRequest] = []
        self._slots: List[Optional[_GenRequest]] = \
            [None] * entry.num_slots
        self._caches = entry.make_caches()
        self._state_handle = None
        from bigdl_tpu.observe import memz as _memz
        # pool bookkeeping: free-list allocator + per-slot block tables
        # (+ the prefix cache when enabled). All mutation happens under
        # self._cv.
        self._pool = BlockPool(entry.pool_blocks)
        self._prefix = (PrefixCache(self._pool, entry.prefix_cache_cap)
                        if entry.prefix_cache else None)
        self._tables = np.full(
            (entry.num_slots, entry.blocks_per_slot), -1, np.int32)
        # buffer ledger (observe/memz.py): the pool under
        # `serve/<model>/kv_pool`, kind="kv_pool" — bytes stay constant
        # across donated steps while the meta carries the LIVE block
        # accounting (headroom = free blocks)
        pooled, by_slot = entry.split_caches(self._caches)
        self._mem_handle = _memz.ledger().register(
            f"serve/{self.name}/kv_pool", pooled, anchor=self,
            kind="kv_pool",
            meta={"blocks": entry.pool_blocks,
                  "block": entry.kv_block,
                  "bytes_per_block":
                      entry.kv_pool_bytes // entry.pool_blocks,
                  "blocks_free": entry.pool_blocks,
                  "slots": entry.num_slots,
                  "max_seq_len": entry.max_seq_len})
        if entry.slot_state:
            # what the model keeps by slot and not by block (a recurrent
            # state): its own owner beside the pool
            self._state_handle = _memz.ledger().register(
                f"serve/{self.name}/slot_state", by_slot, anchor=self,
                kind="slot_state", meta={"slots": entry.num_slots})
        self._closed = False
        self._draining = False
        self._admitted = 0                 # requests given a slot so far
        # the decode step that is enqueued and not fetched yet; written by
        # the thread that runs step_once, cleared by close()
        self._in_flight: Optional[_Step] = None
        # the args of the `serve/decode/dispatch` span that enqueued it,
        # which the `fetch` span of its tokens carries too
        self._in_flight_args: Optional[dict] = None
        self._t_fetched = float("-inf")    # when the last fetch returned
        self._thread: Optional[threading.Thread] = None
        self._stop_check: Optional[Callable[[], bool]] = None
        # --------------------------------------------------- telemetry
        n = self.name
        self._m_tokens = observe.counter(f"serve/{n}/decode/tokens")
        self._m_requests = observe.counter(f"serve/{n}/decode/requests")
        self._m_retired = observe.counter(f"serve/{n}/decode/retired")
        self._m_steps = observe.counter(f"serve/{n}/decode/steps")
        # steps enqueued while the step before them was still unfetched,
        # and rows computed for a sequence that had ended meanwhile
        self._m_steps_ahead = observe.counter(
            f"serve/{n}/decode/steps_ahead")
        self._m_rows_dropped = observe.counter(
            f"serve/{n}/decode/rows_dropped")
        # drafting: the drafts verified (rows of a delivered step that had
        # one) and, of those, the ones accepted
        self._m_draft_rows = observe.counter(f"serve/{n}/decode/draft_rows")
        self._m_draft_accepted = observe.counter(
            f"serve/{n}/decode/draft_accepted")
        self._m_tps = observe.gauge(f"serve/{n}/decode/tokens_per_s")
        self._m_active = observe.gauge(f"serve/{n}/decode/active_slots")
        self._m_queued = observe.gauge(f"serve/{n}/decode/queued")
        self._h_occ = observe.histogram(
            f"serve/{n}/decode/slot_occupancy", BATCH_FILL_BOUNDS)
        self._h_prefill = observe.histogram(
            f"serve/{n}/decode/prefill_ms", LATENCY_MS_BOUNDS)
        self._h_step = observe.histogram(
            f"serve/{n}/decode/step_ms", LATENCY_MS_BOUNDS)
        # the wall time of an iteration, and of it what the host spent
        # working: not waiting on the device (`fetch`) or for a taker
        self._h_iter = observe.histogram(
            f"serve/{n}/decode/iteration_ms", LATENCY_MS_BOUNDS)
        self._h_host = observe.histogram(
            f"serve/{n}/decode/host_ms", LATENCY_MS_BOUNDS)
        self._h_qw = observe.histogram(
            f"serve/{n}/decode/queue_wait_ms", LATENCY_MS_BOUNDS)
        self._h_lat = observe.histogram(
            f"serve/{n}/decode/latency_ms", LATENCY_MS_BOUNDS)
        self._h_ttft = observe.histogram(
            f"serve/{n}/decode/ttft_ms", LATENCY_MS_BOUNDS)
        self._m_shed = observe.counter(f"serve/{n}/shed")
        self._m_cancelled = observe.counter(
            f"serve/{n}/decode/cancelled")
        # paged-pool + prefix-cache planes (gauges track the live
        # accounting; counters mirror the PrefixCache tallies)
        self._m_blocks_free = observe.gauge(
            f"serve/{n}/decode/kv_blocks_free")
        self._m_blocks_live = observe.gauge(
            f"serve/{n}/decode/kv_blocks_live")
        self._m_blocks_cached = observe.gauge(
            f"serve/{n}/decode/kv_blocks_cached")
        self._m_pool_util = observe.gauge(
            f"serve/{n}/decode/kv_pool_util")
        self._m_prefix_hits = observe.counter(
            f"serve/{n}/decode/prefix_hits")
        self._m_prefix_misses = observe.counter(
            f"serve/{n}/decode/prefix_misses")
        self._m_prefix_evictions = observe.counter(
            f"serve/{n}/decode/prefix_evictions")
        self._m_prefix_hit_rate = observe.gauge(
            f"serve/{n}/decode/prefix_hit_rate")
        self._prefix_synced = (0, 0, 0)    # (hits, misses, evictions)
        # what the cache holds, by kind of leaf, and what prefill wrote
        observe.gauge(f"serve/{n}/decode/kv_pool_bytes").set(
            float(entry.kv_pool_bytes))
        observe.gauge(f"serve/{n}/decode/state_bytes").set(
            float(entry.state_bytes))
        self._m_prefill_tokens = observe.counter(
            f"serve/{n}/decode/prefill_tokens")
        # prefill program calls, and the rows they computed, active or not
        self._m_prefill_calls = observe.counter(
            f"serve/{n}/decode/prefill_calls")
        self._m_prefill_rows = observe.counter(
            f"serve/{n}/decode/prefill_rows")
        # of those calls, the chunk advances that rode a decode step and
        # were no program of their own
        self._m_prefill_carried = observe.counter(
            f"serve/{n}/decode/prefill_carried")
        self._m_state_resets = observe.counter(
            f"serve/{n}/decode/state_resets")
        # over every query token a program computed: the tokens it may
        # attend (those before it and itself), and how many of them its
        # attention admits (all, but for a model with a sparse selection)
        self._m_context = observe.counter(
            f"serve/{n}/decode/context_tokens")
        self._m_attended = observe.counter(
            f"serve/{n}/decode/attended_tokens")
        # `context_tokens` of the decode steps' rows alone
        self._m_step_context = observe.counter(
            f"serve/{n}/decode/step_context_tokens")
        # what the model counts on the device, fetched with a step's tokens
        self._m_model = [observe.counter(f"serve/{n}/decode/{c}")
                         for c in entry.counter_names]
        self._model_counts = [0] * len(self._m_model)
        self._win_t0 = self._clock()
        self._win_tokens = 0
        if start:
            self.start()

    # ------------------------------------------------------------ admission
    def submit(self, prompt_ids, max_new_tokens: int,
               eos_id: Optional[int] = None, *,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: int = 0) -> GenReply:
        """Queue one generate request; returns its `GenReply`. Raises
        ValueError (bad prompt / budget over the slot cache length /
        sampling params on a greedy registration), `CapacityError`
        (the request needs more KV blocks than the whole pool —
        it can NEVER be scheduled; the error carries the live
        block-level capacity report and leaves no partial state),
        `Overloaded` (queue at bound), or `Closed` (shut down).

        `temperature > 0` samples (top_k/top_p filtered, per-slot
        stateless rng keyed by `seed` — deterministic per (seed,
        position)); 0 is greedy, the parity-oracle path."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("generate request needs a non-empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if temperature > 0.0 and not self.entry.sampling:
            raise ValueError(
                f"model {self.name!r} was registered without the "
                f"sampling decode step — register(sampling=True) or "
                f"BIGDL_TPU_SERVE_SAMPLING=1 to serve temperature > 0")
        # a drafting step writes its draft's row one position further
        total = prompt.size - 1 + int(max_new_tokens) + self.entry.drafting
        if total > self.entry.max_seq_len:
            raise ValueError(
                f"prompt({prompt.size}) - 1 + max_new({max_new_tokens}) "
                f"{'+ 1 drafted ' if self.entry.drafting else ''}= {total} "
                f"exceeds the slot cache length "
                f"{self.entry.max_seq_len} (BIGDL_TPU_SERVE_MAX_SEQ_LEN"
                f" / register(max_seq_len=...))")
        eos = self.entry.eos_id if eos_id is None else int(eos_id)
        req = _GenRequest(prompt, max_new_tokens, eos, self._clock(),
                          temperature=temperature, top_k=top_k,
                          top_p=top_p, seed=seed)
        req.need_blocks = -(-total // self.entry.kv_block)
        if req.need_blocks > self._pool.total:
            # refuse, don't queue: no retirement can ever free
            # enough blocks. Live block-level capacity report; the
            # submit leaves NO partial state, so a resized retry
            # (or a bigger pool) goes through cleanly.
            from bigdl_tpu.observe.memz import CapacityError
            with self._cv:
                p = self._pool
                cached = p.cached_count()
                report = (f"{p.total} blocks total = {p.live} live "
                          f"+ {cached} cached + {p.free} free "
                          f"({p.reserved} reserved)")
            observe.instant("serve/decode/refuse", cat="serve",
                            args={"model": self.name,
                                  "need_blocks": req.need_blocks})
            raise CapacityError(
                f"decode request needs {req.need_blocks} KV blocks "
                f"({total} tokens @ {self.entry.kv_block}/block) "
                f"but the {self.name!r} pool holds {report} — "
                f"shrink the request or grow "
                f"BIGDL_TPU_SERVE_KV_POOL_BLOCKS / "
                f"register(kv_pool_blocks=...)")
        with self._cv:
            if self._closed or self._draining:
                raise Closed(f"decode scheduler {self.name!r} is shut "
                             f"down")
            if len(self._queue) >= self.max_queue:
                observe.counter("serve/shed").inc()
                self._m_shed.inc()
                observe.instant("serve/shed", cat="serve",
                                args={"model": self.name,
                                      "decode": True})
                raise Overloaded(
                    f"decode queue for {self.name!r} at bound "
                    f"({self.max_queue} requests waiting)")
            self._queue.append(req)
            self._m_requests.inc()
            self._m_queued.set(len(self._queue))
            self._cv.notify()
        return req.reply

    @property
    def active_slots(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    @property
    def queued(self) -> int:
        return len(self._queue)

    # --------------------------------------------------- iteration core
    def _admit(self) -> int:
        """Move queued requests into free slots (holding the lock).
        Admission reserves the request's KV blocks against the LIVE
        pool (matching any committed shared prefix first — matched blocks are refcounted into the slot's table and
        their prefill is skipped); when the head request's blocks don't
        fit, admission stops — FIFO, no overtaking — and retries next
        iteration after retirements return blocks."""
        admitted = 0
        with self._cv:
            free_slots = [s for s, occ in enumerate(self._slots)
                          if occ is None]
            while free_slots and self._queue:
                req = self._queue[0]
                s = free_slots[0]
                if not self._admit_blocks(req, s):
                    break
                self._queue.pop(0)
                free_slots.pop(0)
                req.slot = s
                self._admitted += 1
                req.order = self._admitted
                req.t_admit = self._clock()
                self._h_qw.record(
                    max(0.0, (req.t_admit - req.t_submit) * 1e3))
                self._slots[s] = req
                admitted += 1
            self._m_queued.set(len(self._queue))
        if admitted and self.entry.slot_state:
            # a slot handed to a new request starts from a zero state: the
            # programs see to it themselves, from position 0
            self._m_state_resets.inc(admitted)
        return admitted

    def _admit_blocks(self, req: _GenRequest, s: int) -> bool:
        """Reserve `req`'s KV blocks (lock held). Prefix-cache hits
        shrink the reservation AND the prefill: matched blocks land in
        the slot's table refcounted and `req.fed` jumps past them."""
        B = self.entry.kv_block
        if self._prefix is not None:
            req.keys = PrefixCache.chain_keys(req.prompt, B,
                                              req.prefill_target,
                                              self.entry.drafting)
            m = self._prefix.peek(req.keys)
        else:
            req.keys, m = [], 0
        if not self._pool.reserve(req.need_blocks - m):
            return False
        req.reserved = req.need_blocks - m
        if self._prefix is not None:
            blocks = self._prefix.take(req.keys, m)
            if m:
                self._tables[s, :m] = blocks
                req.shared = m
                req.commit_upto = m
                req.fed = m * B       # shared prefill is already paid
                observe.instant("serve/decode/prefix_hit", cat="serve",
                                args={"model": self.name, "blocks": m})
        return True

    def _ensure_blocks(self, req: _GenRequest, last_pos: int) -> None:
        """Acquire the slot's private blocks through the one covering
        `last_pos` (lock held) — the lazy frontier-crossing acquisition;
        the admission reservation guarantees success. No further than
        the request's own blocks: a drafting step's row past a sequence's
        end writes nowhere (an unacquired block)."""
        row = self._tables[req.slot]
        upto = min(last_pos // self.entry.kv_block + 1, req.need_blocks)
        for j in np.flatnonzero(row[:upto] < 0):
            row[j] = self._pool.acquire_reserved()
            req.reserved -= 1

    def _release_blocks(self, req: _GenRequest) -> None:
        """Return a leaving request's blocks (takes the lock): shared /
        committed entries decref in the prefix cache (refs==0 entries
        stay CACHED for future hits), private blocks go back to the
        free list, unacquired reservations are dropped."""
        if req.slot is None:
            return
        with self._cv:
            row = self._tables[req.slot]
            refd = set(range(req.shared)) | set(req.committed)
            for j in range(row.shape[0]):
                b = int(row[j])
                if b < 0:
                    continue
                if j in refd:
                    self._prefix.decref(req.keys[j])
                else:
                    self._pool.release(b)
            row[:] = -1
            if req.reserved:
                self._pool.unreserve(req.reserved)
                req.reserved = 0
        self._refresh_pool_stats()

    def _refresh_pool_stats(self) -> None:
        """Mirror the live pool/prefix accounting into the gauges,
        counters, and the ledger owner's meta (headroom = free
        blocks)."""
        pool = self._pool
        cached = pool.cached_count()
        self._m_blocks_free.set(float(pool.free))
        self._m_blocks_live.set(float(pool.live))
        self._m_blocks_cached.set(float(cached))
        self._m_pool_util.set(pool.live / pool.total)
        if self._prefix is not None:
            pf = self._prefix
            h0, m0, e0 = self._prefix_synced
            self._m_prefix_hits.inc(pf.hits - h0)
            self._m_prefix_misses.inc(pf.misses - m0)
            self._m_prefix_evictions.inc(pf.evictions - e0)
            self._prefix_synced = (pf.hits, pf.misses, pf.evictions)
            seen = pf.hits + pf.misses
            self._m_prefix_hit_rate.set(
                pf.hits / seen if seen else 0.0)
        self._mem_handle.update_meta(blocks_free=pool.free)

    def _chunk_for(self, req: _GenRequest) -> int:
        """The prefill bucket this request's next chunk uses: smallest
        bucket covering the remaining prompt (capped by the chunk knob),
        shrunk so the padded write never runs past the slot cache."""
        remaining = req.prefill_target - req.fed
        want = min(remaining, self.entry.prefill_chunk)
        room = self.entry.max_seq_len - req.fed
        c = self.entry.buckets[0]
        for b in self.entry.buckets:
            if b <= room:
                c = b
            if b >= want and b <= room:
                return b
        return c

    def _prefill_pass(self, stepping: bool
                      ) -> Tuple[int, Optional[tuple], Optional[dict]]:
        """The iteration's prompt chunks. Where it dispatches a decode step
        (`stepping`) and the model has the carrying form, the step is the
        iteration's one program: it carries the chunk of ONE streaming
        slot, the one admitted first, and every other streaming slot waits
        its turn (a second program would read the weights a second time,
        and every decoding slot's token would wait for it). Else every
        streaming slot advances by one chunk, bucket by bucket: a one-row
        program call for each slot. Either way, where half the slots or
        more stream a full chunk at once, the one `num_slots`-row call
        (`DecodeEntry.prefill_rows`) and a plain step. Returns (the slots
        that stream, the chunk for the step to carry or None, the args of
        the `serve/decode/prefill` span: whether the chunk is carried, the
        bucket of the last chunk and the rows computed, or None)."""
        pending = [r for r in self._slots
                   if r is not None and r.fed < r.prefill_target]
        if not pending:
            return 0, None, None
        by_bucket: Dict[int, List[_GenRequest]] = {}
        for req in pending:
            by_bucket.setdefault(self._chunk_for(req), []).append(req)
        if stepping and self.entry.carried:
            full = self.entry.prefill_chunk
            burst = by_bucket.get(full, [])
            if self.entry.prefill_rows(full, len(burst)) > 1:
                self._prefill_call(full, burst, by_row=False)
                return len(pending), None, {
                    "carried": False, "chunk": full,
                    "rows": self.entry.num_slots}
            first = min(pending, key=lambda r: r.order)
            C = next(b for b in self.entry.carried
                     if b >= self._chunk_for(first))
            return len(pending), self._prefill_call(
                C, [first], by_row=True, carried=True), {
                    "carried": True, "chunk": C, "rows": 1}
        rows = 0
        for C, reqs in sorted(by_bucket.items()):
            if self.entry.prefill_rows(C, len(reqs)) == 1:
                for req in reqs:
                    self._prefill_call(C, [req], by_row=True)
                rows += len(reqs)
            else:
                self._prefill_call(C, reqs, by_row=False)
                rows += self.entry.num_slots
        return len(pending), None, {"carried": False, "chunk": C,
                                    "rows": rows}

    def _count_context(self, contexts: np.ndarray) -> None:
        """`contexts`: of each query token a call computes, the tokens it
        may attend (its position + 1)."""
        self._m_context.inc(int(contexts.sum()))
        topk = self.entry.index_topk
        self._m_attended.inc(int(
            contexts.sum() if topk is None
            else np.minimum(contexts, topk).sum()))

    def _prefill_call(self, C: int, reqs: List[_GenRequest], by_row: bool,
                      carried: bool = False) -> Optional[tuple]:
        """One prefill program call that advances `reqs` by a chunk of
        bucket `C`: over their slots' rows and no others (`by_row`), or
        over all `num_slots` rows, those of the other slots at length 0.
        `carried`: no call is made; the arguments are returned for the
        decode step this iteration dispatches, which carries the chunk, and
        the requests are advanced as by a call (the step is enqueued before
        anything that reads what it writes)."""
        slots = np.asarray([req.slot for req in reqs], np.int32)
        R = len(reqs) if by_row else self.entry.num_slots
        rows = range(R) if by_row else slots
        tokens = np.zeros((R, C), np.int32)
        positions = np.zeros((R, C), np.int32)
        lengths = np.zeros((R,), np.int32)
        # drafting: the token after each, for the draft module's rows
        follow = np.zeros((R, C), np.int32) if self.entry.drafting else None
        for i, req in zip(rows, reqs):
            n = min(req.prefill_target - req.fed, C)
            tokens[i, :n] = req.prompt[req.fed:req.fed + n]
            positions[i] = req.fed + np.arange(C)
            lengths[i] = n
            if follow is not None:
                follow[i, :n] = req.prompt[req.fed + 1:req.fed + n + 1]
        with self._cv:
            for i, req in zip(rows, reqs):
                self._ensure_blocks(req, req.fed + int(lengths[i]) - 1)
            table = self._tables[slots] if by_row else self._tables.copy()
        # of each valid query token, the tokens it may attend
        contexts = (positions + 1)[np.arange(C) < lengths[:, None]]
        if carried:
            self._m_prefill_carried.inc()
        else:
            t0 = self._clock()
            # lengths masks the rounded-up bucket's padded tail (and
            # inactive rows) out of the pool scatter
            self._caches = self.entry.run_prefill(
                self._caches, tokens, positions, table, lengths,
                slots if by_row else None, follow)
            self._h_prefill.record(max(0.0, (self._clock() - t0) * 1e3))
        self._m_prefill_calls.inc()
        self._m_prefill_rows.inc(R)
        self._m_prefill_tokens.inc(int(lengths.sum()))
        self._count_context(contexts)
        for req in reqs:
            req.fed += min(req.prefill_target - req.fed, C)
            if self._prefix is not None:
                self._commit_prefix(req)
        if not carried:
            return None
        return (tokens, positions, table, lengths, slots) + (
            (follow,) if follow is not None else ())

    def _commit_prefix(self, req: _GenRequest) -> None:
        """Publish the whole prompt blocks `req`'s prefill frontier has
        passed (the commit half of the stage/commit discipline): later
        admissions with the same prefix chain take them refcounted. A
        concurrent identical prefix may have committed a key first —
        this request's copy then simply stays private."""
        with self._cv:
            B = self.entry.kv_block
            j = req.commit_upto
            while j < len(req.keys) and (j + 1) * B <= req.fed:
                blk = int(self._tables[req.slot, j])
                if blk >= 0 and self._prefix.commit(req.keys[j], blk):
                    req.committed.append(j)
                j += 1
            req.commit_upto = j

    def _decode_pass(self, chunk: Optional[tuple]) -> Tuple[int, int]:
        """Enqueue the next fused step over the rows `_step_rows` gives (a
        slot whose prompt this iteration's chunk completes among them),
        with the prompt chunk it carries, then fetch and deliver the step
        that was in flight, which the device has been running meanwhile.
        Returns (the rows of the step delivered, or else of the one
        enqueued; the nanoseconds the host waited in the fetch)."""
        prev, prev_args = self._in_flight, self._in_flight_args
        # `program`, `rows` and `context` (the longest of the step
        # enqueued) are known once it is dispatched; the span reads the
        # dict when it closes. The fetch of that step's tokens carries the
        # same dict: a reader pairs the two by it.
        args = {"model": self.name, "state": self.entry.state_kind}
        with observe.span("serve/decode/dispatch", cat="serve", args=args):
            self._in_flight = self._dispatch_step(
                prev, self._step_rows(prev), chunk)
            self._in_flight_args = args
            if self._in_flight is not None:
                args["program"] = self.entry.step_program(chunk is not None)
                if chunk is not None:
                    # the carrying programs are one a bucket
                    args["chunk"] = chunk[0].shape[1]
                args["rows"] = len(self._in_flight.rows)
                args["context"] = self._in_flight.context
        waited = 0
        if prev is not None:
            with observe.span("serve/decode/fetch", cat="serve",
                              args=prev_args) as fetch:
                nxt = self._fetch(prev)
            waited = fetch.dur_ns
        with observe.span("serve/decode/deliver", cat="serve"):
            step = prev or self._in_flight
            rows = len(step.rows) if step is not None else 0
            if prev is not None:
                self._deliver(prev, nxt)
            # free the fetched step's device buffer inside this span, not
            # between spans as the frame unwinds
            del prev, step
            self._refresh_pool_stats()
        return rows, waited

    def _step_rows(self, prev: Optional[_Step]
                   ) -> List[Tuple[_GenRequest, bool]]:
        """The rows of the step to dispatch now: every slot whose prompt is
        complete and whose sequence does not end by count in `prev`, the
        step in flight, each with whether it continues from a row of
        `prev`."""
        rows = []
        for req in self._slots:
            if req is None or req.fed < req.prefill_target:
                continue
            ahead = prev is not None and any(r is req for r in prev.rows)
            if ahead and len(req.generated) + 1 >= req.max_new:
                continue            # `prev` holds its last token
            rows.append((req, ahead))
        return rows

    def _dispatch_step(self, prev: Optional[_Step],
                       step_rows: List[Tuple[_GenRequest, bool]],
                       chunk: Optional[tuple]) -> Optional[_Step]:
        """One fused decode step over `step_rows`. A row of `prev` that
        continues takes its input token from `prev.nxt` on the device;
        everything else of the step the host knows without that token, the
        prompt chunk it carries (`_prefill_pass`) included."""
        if not step_rows:
            return None
        if self.entry.drafting:
            return self._dispatch_verify(prev, step_rows, chunk)
        S = self.entry.num_slots
        tokens = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        use_prev = np.zeros((S,), bool)
        rows = [req for req, _ in step_rows]
        for req, ahead in step_rows:
            tok, pos = req.next_input()
            if ahead:
                pos += 1
                use_prev[req.slot] = True
            else:
                tokens[req.slot] = tok
            positions[req.slot] = pos
            active[req.slot] = True
        with self._cv:
            for req in rows:
                self._ensure_blocks(req, int(positions[req.slot]))
            extra = [self._tables.copy()]
        if self.entry.sampling:
            temps = np.zeros((S,), np.float32)
            tks = np.zeros((S,), np.int32)
            tps = np.ones((S,), np.float32)
            seeds = np.zeros((S,), np.int32)
            for req in rows:
                temps[req.slot] = req.temperature
                tks[req.slot] = req.top_k
                tps[req.slot] = req.top_p
                seeds[req.slot] = req.seed
            extra += [temps, tks, tps, seeds]
        extra += chunk or ()
        t0 = self._clock()
        if use_prev.any():
            tokens = self.entry.merge_tokens(use_prev, prev.nxt, tokens)
        nxt, self._caches = self.entry.run_decode(
            self._caches, tokens, positions, active, *extra)
        if prev is not None:
            self._m_steps_ahead.inc()
        contexts = positions[active] + 1
        self._count_context(contexts)
        self._m_step_context.inc(int(contexts.sum()))
        return _Step(nxt, rows, t0, int(contexts.max()))

    def _dispatch_verify(self, prev: Optional[_Step],
                         step_rows: List[Tuple[_GenRequest, bool]],
                         chunk: Optional[tuple]) -> _Step:
        """`_dispatch_step` of a drafting registration: a row is (last
        token, its draft, whether there is one, the last token's position).
        A row that continues from `prev` takes all four from `prev` on the
        device, since how far `prev` advances it (one or two) is known
        there alone; the host reserves blocks for either. A row the host
        feeds verifies the draft the last fetched step left it, if any."""
        S = self.entry.num_slots
        host = np.zeros((4, S), np.int32)
        active = np.zeros((S,), bool)
        use_prev = np.zeros((S,), bool)
        drafted = []
        for req, ahead in step_rows:
            tok, pos = req.next_input()
            if ahead:
                # `prev` yields one token or two: its last one and that
                # one's draft lie at most three positions on
                use_prev[req.slot] = True
                pos += 3
            else:
                has = req.draft is not None
                host[:, req.slot] = (tok, req.draft if has else 0, has, pos)
                pos += 1
            drafted.append(ahead or req.draft is not None)
            active[req.slot] = True
            with self._cv:
                self._ensure_blocks(req, pos)
        with self._cv:
            table = self._tables.copy()
        t0 = self._clock()
        rows = (self.entry.merge_tokens(use_prev, prev.nxt, host)
                if use_prev.any() else host)
        nxt, self._caches = self.entry.run_decode(
            self._caches, rows, active, table, *(chunk or ()))
        if prev is not None:
            self._m_steps_ahead.inc()
        reqs = [req for req, _ in step_rows]
        # the work of a row is counted when its tokens are known (_deliver)
        return _Step(nxt, reqs, t0, int(max(req.next_input()[1] + 2
                                            for req in reqs)),
                     tuple(drafted))

    @staticmethod
    def _fetch(step: _Step) -> np.ndarray:
        """A step's tokens on the host: the iteration's single host sync,
        and the one place the host waits on the device."""
        from bigdl_tpu.analysis.sancov import sanctioned_sync
        import jax
        with sanctioned_sync("decode next-token fetch"):
            return np.asarray(jax.device_get(step.nxt))

    def _deliver(self, step: _Step, nxt: np.ndarray) -> None:
        """Push a fetched step's tokens to their streams and retire the
        sequences that end. A row whose request left its slot while the
        step was in flight (it ended by value in the step before, or was
        cancelled) is dropped."""
        now = self._clock()
        if self.entry.drafting:
            return self._deliver_verified(step, nxt, now)
        # what the iteration cost a token: from dispatch when nothing was
        # in flight before it, else from the fetch before this one
        self._h_step.record(
            max(0.0, (now - max(step.t_dispatch, self._t_fetched)) * 1e3))
        self._t_fetched = now
        self._count_model(nxt[self.entry.num_slots:])
        live = [r for r in step.rows if self._slots[r.slot] is r]
        self._m_rows_dropped.inc(len(step.rows) - len(live))
        self._h_occ.record(len(step.rows) / self.entry.num_slots)
        self._m_steps.inc()
        self._m_tokens.inc(len(live))
        self._win_tokens += len(live)
        if now - self._win_t0 >= 0.5:
            self._m_tps.set(self._win_tokens / (now - self._win_t0))
            self._win_t0, self._win_tokens = now, 0
        for req in live:
            tok = int(nxt[req.slot])
            req.generated.append(tok)
            req.reply._push(tok)
            if req.t_first is None:
                req.t_first = now
                self._h_ttft.record(
                    max(0.0, (now - req.t_submit) * 1e3))
            if tok == req.eos_id or len(req.generated) >= req.max_new:
                self._retire(req, now)
        self._m_active.set(self.active_slots)

    def _deliver_verified(self, step: _Step, nxt: np.ndarray,
                          now: float) -> None:
        """`_deliver` of a drafting step: a row yields its last token's
        argmax and, where the draft was accepted, the draft's argmax too;
        a sequence may end at either. The context of each token delivered
        is counted here, where its position is known."""
        S = self.entry.num_slots
        a, b, acc, draft, at = (nxt[i * S:(i + 1) * S] for i in range(5))
        self._h_step.record(
            max(0.0, (now - max(step.t_dispatch, self._t_fetched)) * 1e3))
        self._t_fetched = now
        self._count_model(nxt[5 * S:])
        live = [(r, d) for r, d in zip(step.rows, step.drafted)
                if self._slots[r.slot] is r]
        self._m_rows_dropped.inc(len(step.rows) - len(live))
        self._h_occ.record(len(step.rows) / self.entry.num_slots)
        self._m_steps.inc()
        delivered, contexts = 0, []
        for req, drafted in live:
            s = req.slot
            if drafted:
                self._m_draft_rows.inc()
                self._m_draft_accepted.inc(int(acc[s]))
            # the first row's position: the last token's, less the advance
            first = int(at[s]) - 1 - int(acc[s])
            self._m_step_context.inc(first + 1)
            for k, tok in enumerate([a[s], b[s]][:1 + int(acc[s])]):
                tok = int(tok)
                req.generated.append(tok)
                req.reply._push(tok)
                delivered += 1
                contexts.append(first + 1 + k)
                if req.t_first is None:
                    req.t_first = now
                    self._h_ttft.record(
                        max(0.0, (now - req.t_submit) * 1e3))
                if tok == req.eos_id or len(req.generated) >= req.max_new:
                    self._retire(req, now)
                    break
            else:
                req.draft = int(draft[s])
        self._count_context(np.asarray(contexts, np.int64))
        self._m_tokens.inc(delivered)
        self._win_tokens += delivered
        if now - self._win_t0 >= 0.5:
            self._m_tps.set(self._win_tokens / (now - self._win_t0))
            self._win_t0, self._win_tokens = now, 0
        self._m_active.set(self.active_slots)

    def _count_model(self, totals) -> None:
        """The model's running int32 totals of the device, which wrap."""
        for i, m in enumerate(self._m_model):
            total = int(totals[i])
            m.inc((total - self._model_counts[i]) & 0xFFFFFFFF)
            self._model_counts[i] = total

    def _retire(self, req: _GenRequest, now: float) -> None:
        self._slots[req.slot] = None
        self._release_blocks(req)
        self._m_retired.inc()
        self._h_lat.record(max(0.0, (now - req.t_submit) * 1e3))
        observe.instant("serve/decode/retire", cat="serve",
                        args={"model": self.name,
                              "tokens": len(req.generated)})
        req.reply._finish(req.generated)

    def _sweep_cancelled(self) -> int:
        """Free slots (and queue positions) whose client abandoned the
        request (`GenReply.cancel()` — e.g. an SSE consumer hung up
        mid-stream): the slot returns to the pool THIS iteration instead
        of decoding `max_new` tokens nobody reads. The reply completes
        with whatever was generated so a racing `.result()` caller is
        never stranded."""
        freed = 0
        with self._cv:
            keep = []
            for req in self._queue:
                if req.reply.cancelled():
                    self._m_cancelled.inc()
                    req.reply._finish(req.generated)
                    freed += 1
                else:
                    keep.append(req)
            self._queue[:] = keep
            self._m_queued.set(len(self._queue))
        for s, req in enumerate(self._slots):
            if req is not None and req.reply.cancelled():
                self._slots[s] = None
                self._release_blocks(req)
                self._m_cancelled.inc()
                req.reply._finish(req.generated)
                freed += 1
        if freed:
            self._m_active.set(self.active_slots)
            observe.instant("serve/decode/cancel", cat="serve",
                            args={"model": self.name, "freed": freed})
        return freed

    def _await_taker(self) -> None:
        """With a slot free, nobody queued and a step in flight, wait for
        the request that takes the slot, until that step is done. Admitted
        in this iteration its prefill runs right behind the step in flight;
        an iteration later it would run behind the next step too, which
        this iteration is about to enqueue. The wait is off the interpreter
        lock (the front's threads get to run) and costs the device nothing
        while the step runs; if no request came, the next step is enqueued
        late by the host's dispatch time."""
        step = self._in_flight
        if step is None:
            return
        with self._cv:
            while (not self._queue and not self._closed
                   and not self._draining
                   and self.active_slots < self.entry.num_slots
                   and not self._done(step)):
                self._cv.wait(timeout=_TAKER_POLL_S)

    @staticmethod
    def _done(step: _Step) -> bool:
        """Whether the device has finished `step` (never blocks)."""
        is_ready = getattr(step.nxt, "is_ready", None)
        return is_ready is None or is_ready()

    def step_once(self) -> bool:
        """One scheduler iteration: sweep cancels → admit → prefill (as
        calls, or as the chunk the step carries) → decode (enqueue the next
        step, then fetch the one in flight).
        Returns True when any work happened (the thread loop sleeps
        otherwise); tests drive this synchronously with a fake clock.

        The span `serve/decode/iteration` holds it all, and every instant
        of it lies in one of its leaves: `admit` (twice: the sweeps, then
        admission), `await_taker`, `prefill`, `dispatch`, `fetch` and
        `deliver` (docs/observability.md). `iteration_ms` records its wall
        time and `host_ms` that time less the waits in `fetch` and
        `await_taker`."""
        span = observe.span
        with span("serve/decode/iteration", cat="serve") as it:
            with span("serve/decode/admit", cat="serve"):
                worked = self._sweep() > 0
            with span("serve/decode/await_taker", cat="serve") as wait:
                self._await_taker()
            with span("serve/decode/admit", cat="serve"):
                worked = self._admit() > 0 or worked
            with span("serve/decode/prefill", cat="serve") as pre:
                stepping = bool(self._step_rows(self._in_flight))
                streaming, chunk, pre.args = self._prefill_pass(stepping)
            rows, fetched = self._decode_pass(chunk)
        self._h_iter.record(it.dur_ns * 1e-6)
        self._h_host.record((it.dur_ns - wait.dur_ns - fetched) * 1e-6)
        return rows > 0 or streaming > 0 or worked

    def _sweep(self) -> int:
        """The iteration's sweeps: abandoned requests (`_sweep_cancelled`,
        whose count it returns) and, under the memory watchdog, the prefix
        cache's unreferenced blocks."""
        freed = self._sweep_cancelled()
        if self._prefix is not None:
            from bigdl_tpu.observe import memz as _memz
            if _memz.watchdog_active():
                with self._cv:
                    swept = self._prefix.sweep()
                if swept:
                    observe.instant("serve/decode/prefix_sweep",
                                    cat="serve",
                                    args={"model": self.name,
                                          "blocks": swept})
        return freed

    # ----------------------------------------------------------- lifecycle
    def start(self, stop_check: Optional[Callable[[], bool]] = None
              ) -> "DecodeScheduler":
        """Launch the scheduler thread (`stop_check` = the engine's
        SIGTERM drain probe, as in ContinuousBatcher.start)."""
        if self._thread is not None:
            return self
        self._stop_check = stop_check
        self._thread = spawn(self._loop, name=f"serve-decode-{self.name}")
        return self

    def _idle(self) -> bool:
        """Nothing queued, no slot taken and no step in flight (lock
        held): a step in flight still owes its tokens."""
        return (not self._queue and self.active_slots == 0
                and self._in_flight is None)

    def _loop(self) -> None:
        while True:
            with self._cv:
                if self._stop_check is not None and not self._draining \
                        and not self._closed and self._stop_check():
                    log.warning("serve[%s]: stop requested — draining "
                                "%d queued + %d active generates",
                                self.name, len(self._queue),
                                self.active_slots)
                    observe.instant("serve/drain", cat="serve",
                                    args={"model": self.name,
                                          "decode": True})
                    self._draining = True
                if self._idle():
                    if self._closed or self._draining:
                        self._closed = True
                        return
                    self._cv.wait(timeout=0.05)
                    continue
            try:
                self.step_once()
            except Exception as exc:     # noqa: BLE001 — routed to callers
                # a failed iteration must not strand replies forever on
                # a dead scheduler thread; RESOURCE_EXHAUSTED
                # additionally dumps the OOM forensics bundle (ledger +
                # device memory profile — observe/memz.py)
                from bigdl_tpu.observe import memz as _memz
                if _memz.is_oom(exc):
                    from bigdl_tpu.observe import doctor as _doctor
                    _doctor.dump_forensics(
                        "serve-resource-exhausted", exc=exc,
                        extra={"model": self.name, "decode": True,
                               "kv_cache_bytes":
                                   self.entry.kv_cache_bytes})
                log.error("serve[%s]: decode iteration failed (%s: %s) "
                          "— failing %d active + %d queued generates",
                          self.name, type(exc).__name__, exc,
                          self.active_slots, len(self._queue))
                with self._cv:
                    pending = ([r for r in self._slots if r is not None]
                               + list(self._queue))
                for req in pending:      # fail with the REAL error
                    if not req.reply.done():
                        req.reply._fail(exc)
                self.close(drain=False, timeout=0.0)
                return

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Stop admission and wait for every queued + active generate
        to complete, the last step's tokens delivered. Returns False on
        timeout."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while True:
            with self._cv:
                if self._idle():
                    return True
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(0.002)

    def close(self, drain: bool = True,
              timeout: Optional[float] = 30.0) -> None:
        """Shut down; `drain=False` fails every incomplete reply with
        `Closed` — no reply is ever left pending."""
        if drain:
            self.drain(timeout=timeout)
        with self._cv:
            self._draining = True
            self._closed = True
            dropped = list(self._queue)
            self._queue.clear()
            dropped += [r for r in self._slots if r is not None]
            self._slots = [None] * self.entry.num_slots
            self._in_flight = None       # its rows' requests fail below
            self._m_queued.set(0)
            self._m_active.set(0)
            self._cv.notify_all()
        for req in dropped:
            self._release_blocks(req)
            if not req.reply.done():
                req.reply._fail(Closed(
                    f"decode scheduler {self.name!r} closed before "
                    f"completion"))
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5.0)
        self._thread = None
        # the KV pool itself is freed when the scheduler drops its
        # cache reference; release the ledger accounting with it
        self._caches = None
        self._mem_handle.close()
        if self._state_handle is not None:
            self._state_handle.close()

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict:
        """The per-model decode SLO view (engine.stats()[model]
        ['decode'], mirrored into /statusz and /fleetz)."""
        reg = observe.registry()
        n = self.name
        lat = reg.histogram(f"serve/{n}/decode/latency_ms",
                            LATENCY_MS_BOUNDS)
        ttft = reg.histogram(f"serve/{n}/decode/ttft_ms",
                             LATENCY_MS_BOUNDS)
        step = reg.histogram(f"serve/{n}/decode/step_ms",
                             LATENCY_MS_BOUNDS)
        occ = reg.histogram(f"serve/{n}/decode/slot_occupancy",
                            BATCH_FILL_BOUNDS)
        qw = reg.histogram(f"serve/{n}/decode/queue_wait_ms",
                           LATENCY_MS_BOUNDS)
        rate = float(self._m_tps.value or 0.0)
        if not rate and self._win_tokens:
            # short-lived schedulers never close a 0.5 s rate window —
            # report the live partial-window estimate instead of 0
            rate = self._win_tokens / max(self._clock() - self._win_t0,
                                          1e-9)
        pool = self._pool
        cached = pool.cached_count()
        out = {
            "slots": self.entry.num_slots,
            "max_seq_len": self.entry.max_seq_len,
            "active_slots": self.active_slots,
            "queued": self.queued,
            "requests": int(self._m_requests.value),
            "retired": int(self._m_retired.value),
            "tokens": int(self._m_tokens.value),
            "tokens_per_s": round(rate, 2),
            "slot_occupancy_mean": round(occ.sum / occ.count, 4)
            if occ.count else 0.0,
            "ttft_p50_ms": round(ttft.quantile(0.50), 3),
            "ttft_p99_ms": round(ttft.quantile(0.99), 3),
            "step_p50_ms": round(step.quantile(0.50), 3),
            "step_p99_ms": round(step.quantile(0.99), 3),
            "p99_ms": round(lat.quantile(0.99), 3),
            "queue_wait_p99_ms": round(qw.quantile(0.99), 3),
            "cancelled": int(self._m_cancelled.value),
            "steps": int(self._m_steps.value),
            "steps_ahead": int(self._m_steps_ahead.value),
            "rows_dropped": int(self._m_rows_dropped.value),
            "draft_rows": int(self._m_draft_rows.value),
            "draft_accepted": int(self._m_draft_accepted.value),
            "state": self.entry.state_kind,
            "kv_pool_bytes": self.entry.kv_pool_bytes,
            "state_bytes": self.entry.state_bytes,
            "state_resets": int(self._m_state_resets.value),
            "prefill_tokens": int(self._m_prefill_tokens.value),
            "prefill_calls": int(self._m_prefill_calls.value),
            "prefill_rows": int(self._m_prefill_rows.value),
            "prefill_carried": int(self._m_prefill_carried.value),
            "context_tokens": int(self._m_context.value),
            "attended_tokens": int(self._m_attended.value),
            "step_context_tokens": int(self._m_step_context.value),
            **{c: int(m.value) for c, m in zip(self.entry.counter_names,
                                               self._m_model)},
            "kv_block": self.entry.kv_block,
            "kv_blocks_total": pool.total,
            "kv_blocks_free": pool.free,
            "kv_blocks_live": pool.live,
            "kv_blocks_cached": cached,
            "kv_blocks_reserved": pool.reserved,
            "kv_pool_util": round(pool.live / pool.total, 4),
        }
        if self._prefix is not None:
            pf = self._prefix
            seen = pf.hits + pf.misses
            out.update({
                "prefix_hits": pf.hits,
                "prefix_misses": pf.misses,
                "prefix_evictions": pf.evictions,
                "prefix_cached_blocks": cached,
                "prefix_hit_rate": round(pf.hits / seen, 4)
                if seen else 0.0,
            })
        return out


def decode_demo_model(vocab_size: int = 64, n_positions: int = 256,
                      d_model: int = 32, num_heads: int = 4,
                      num_layers: int = 2, eos_id: int = 1, seed: int = 0):
    """Tiny randomly-initialized GPT2LM + params — the default model the
    `python -m bigdl_tpu.serve --decode` CLI stands up when no factory
    is given (smoke tests, demos)."""
    import jax
    from bigdl_tpu.interop.huggingface import GPT2LM
    model = GPT2LM(vocab_size, n_positions, d_model, num_heads,
                   num_layers, eos_id=eos_id)
    params, state = model.init(
        jax.random.PRNGKey(seed))  # tpu-lint: disable=004
    return model, params, state
