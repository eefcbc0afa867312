"""Process identity helpers shared by observability and visualization.

Multihost hygiene needs two facts very early — often before anyone wants
the JAX backend initialized (touching `jax.process_index()` would
initialise it, and with it claim the chip, as a side effect):

  * `process_index()` — reads jax's distributed client state WITHOUT
    initializing a backend: 0 in single-process runs, the real index in
    multi-process ones (tests/multihost_worker*.py call
    jax.distributed.initialize first).
  * `run_id()` — one short id per training process (override with
    BIGDL_TPU_RUN_ID so all hosts of one job share it), stamped into log
    lines, trace metadata, and JSONL run logs so interleaved output from
    `dryrun_multichip` workers stays attributable.
"""

from __future__ import annotations

import os
import time

from bigdl_tpu.utils.threads import make_lock

_run_id = None
_lock = make_lock("utils.runtime")


def process_index() -> int:
    """This process's index in the job (0 for single-process) without
    initializing a JAX backend."""
    try:
        from jax._src import distributed
        pid = distributed.global_state.process_id
        return int(pid) if pid is not None else 0
    except Exception:
        return 0


def process_count() -> int:
    try:
        from jax._src import distributed
        n = distributed.global_state.num_processes
        return int(n) if n is not None else 1
    except Exception:
        return 1


def coordinator_host() -> str:
    """Host of the distributed coordinator (process 0's machine) from
    jax's distributed client state, without initializing a backend;
    loopback when the job is single-process or the state is absent."""
    try:
        from jax._src import distributed
        addr = getattr(distributed.global_state, "coordinator_address",
                       None)
        if addr:
            return str(addr).rsplit(":", 1)[0]
    except Exception:
        pass
    return "127.0.0.1"


def fleet_peer_candidates(base_port: int) -> list:
    """Derived fleet peer addresses — the distributed process table
    mapped onto the statusz port convention (observe/fleet.py): process
    i serves its plane at ``base_port + i`` (observe/statusz.py offsets
    the bind when BIGDL_TPU_FLEET is on), all reached through the
    coordinator host. One process per host sharing a port layout needs
    the explicit BIGDL_TPU_FLEET_PEERS list instead; this derivation
    covers the same-host multi-process shape (dryrun_multichip, the
    multihost_worker tests, a single TPU VM running several planes)."""
    n = process_count()
    base = int(base_port or 0)
    if n <= 1 or base <= 0:
        return []
    host = coordinator_host()
    return [f"{host}:{base + i}" for i in range(n)]


def run_id() -> str:
    """Stable per-process run id (env BIGDL_TPU_RUN_ID wins — set it on
    every host of a multihost job to correlate their logs)."""
    global _run_id
    env = os.environ.get("BIGDL_TPU_RUN_ID")
    if env:
        return env
    with _lock:
        if _run_id is None:
            _run_id = f"r{int(time.time()) & 0xFFFFFF:06x}"
        return _run_id


class _PrefixFilter:
    """Prepends `[pI rID]` to every record logged through the
    `bigdl_tpu` logger — the structured prefix that keeps multihost
    (and multi-trainer) log streams attributable. Implemented as a
    filter mutating the format string so it composes with whatever
    formatter the application installed (models/train.py basicConfig,
    pytest caplog, a user's own handler)."""

    def filter(self, record):
        if not getattr(record, "_bigdl_prefixed", False):
            record._bigdl_prefixed = True
            record.msg = (f"[p{process_index()} {run_id()}] "
                          f"{record.msg}")
        return True


_prefix_installed = False


def install_log_prefix() -> None:
    """Idempotently attach the structured prefix to the bigdl_tpu
    logger."""
    global _prefix_installed
    with _lock:
        if _prefix_installed:
            return
        import logging
        logging.getLogger("bigdl_tpu").addFilter(_PrefixFilter())
        _prefix_installed = True
