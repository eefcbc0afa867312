"""Async checkpointer — hide snapshot cost behind training (CheckFreq).

The v1 writer stalls the train loop for gather + serialization + IO.
Following CheckFreq (Mohan et al., FAST '21), the save splits in two:

  1. **snapshot** (foreground, at the step boundary): ONE jitted identity
     dispatch clones every leaf device-side — async dispatch, so the call
     returns in microseconds — then the host-side piece plan is built
     from the clones. The clones are fresh buffers, so the next train
     step is free to donate/overwrite the live trees immediately.
  2. **persist** (background thread): CRC + npz serialization + IO +
     COMMIT + retention GC run off the training thread
     (resilience/manifest.py). No jax collectives happen here, so the
     thread is multi-host-safe by construction.

Double-buffering: a new save() first dispatches its own device clone
(buffer B) while the previous write (buffer A) may still be draining,
then joins A before queueing B — at most two snapshot buffers ever live.
`wait()` joins the in-flight write and re-raises its failure; the
trainers call it before every dependent read (resume, shutdown) and the
retry loop calls it before trusting `latest_checkpoint`.

The tree dict is open-ended: besides params/model_state/slots the
trainers add an `exchange` tree when the DCN-tier exchange is armed
(parallel/dcn.py) — per-slice gradient accumulators, error-feedback
residual norm, and outer-optimizer state — with `exchange_every` /
`exchange_pending` provenance in the meta, so a kill-and-resume
mid-T-window restores the window exactly. The clone/persist path is
tree-generic (structure-keyed clone fns, per-leaf piece plans), so the
extra tree rides the same discipline with no special casing.
"""

from __future__ import annotations

import logging
import queue
import threading
from typing import Any, Dict, Optional

from bigdl_tpu import observe
from bigdl_tpu.resilience import manifest

log = logging.getLogger("bigdl_tpu")


class AsyncCheckpointer:
    """Format-v2 snapshot writer with optional background persistence.

    async_mode=None / keep_n=None read the BIGDL_TPU_CHECKPOINT_ASYNC /
    BIGDL_TPU_CHECKPOINT_KEEP_N knobs at construction.
    """

    def __init__(self, async_mode: Optional[bool] = None,
                 keep_n: Optional[int] = None):
        from bigdl_tpu.utils import config
        self.async_mode = (config.get("CHECKPOINT_ASYNC")
                           if async_mode is None else async_mode)
        self.keep_n = (config.get("CHECKPOINT_KEEP_N")
                       if keep_n is None else keep_n)
        # ONE persistent writer thread per checkpointer (spawned lazily):
        # per-save thread creation costs milliseconds on a busy host,
        # which is the same order as the whole foreground stall
        self._queue: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._clone_fns: Dict[Any, Any] = {}
        self._last_path: Optional[str] = None

    # ------------------------------------------------------------ plumbing
    def _clone(self, trees):
        """Device-side copy of every leaf in ONE jitted dispatch (cached
        per tree structure). Output buffers are fresh (no donation), and
        sharding propagation keeps each input's layout, so the background
        fetch reads stable buffers while training overwrites the originals."""
        import jax
        import jax.numpy as jnp
        treedef = jax.tree.structure(trees)
        fn = self._clone_fns.get(treedef)
        if fn is None:
            fn = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
            self._clone_fns[treedef] = fn
        return fn(trees)

    def _persist(self, path: str, plan: dict, root: Optional[str]):
        try:
            # runs on the ckpt-writer thread: its own lane in the trace
            with observe.phase("checkpoint/persist", cat="checkpoint"):
                manifest.write_snapshot(path, plan)
                if root is not None and plan["process_index"] == 0:
                    manifest.gc_snapshots(root, self.keep_n)
            observe.counter("checkpoint/saves").inc()
        except BaseException as e:                 # noqa: BLE001 — deferred
            self._error = e
            observe.counter("checkpoint/failures").inc()
            observe.instant("checkpoint/failure", cat="checkpoint",
                            args={"path": path, "error": str(e)[:200]})
            log.error("background checkpoint %s failed: %s", path, e)
        finally:
            # /statusz "checkpoint in-flight" flag (at most one write is
            # ever in flight — save() joins the previous one first)
            observe.gauge("checkpoint/in_flight").set(0)

    def _run_worker(self):
        while True:
            item = self._queue.get()
            try:
                if item is not None:
                    self._persist(*item)
            finally:
                self._queue.task_done()
            if item is None:
                return

    def _enqueue(self, path, plan, root):
        if self._worker is None or not self._worker.is_alive():
            from bigdl_tpu.utils.threads import spawn
            self._worker = spawn(self._run_worker, name="ckpt-writer")
        self._queue.put((path, plan, root))

    # ------------------------------------------------------------------ api
    def save(self, path: str, trees: Dict[str, Any],
             meta: Optional[Dict] = None,
             root: Optional[str] = None, clone: bool = True) -> None:
        """Snapshot `trees` to `path`. Blocking cost is the device-side
        clone dispatch + host piece-plan build; serialization and IO run
        in the background (async mode). `root` enables retention GC of
        sibling snapshots after a successful commit. Raises any error the
        PREVIOUS background write hit — a failed write surfaces at the
        next save/wait rather than vanishing.

        `clone=False` skips the device-side copy and lets the background
        writer read the LIVE buffers directly — only safe when the
        caller's train step does NOT donate them (the shard references
        held by the plan keep the buffers alive; a donating step would
        invalidate them mid-read). Both trainers donate, so they keep
        the default."""
        if self.async_mode:
            # buffer B (async dispatch) while buffer A's write drains
            if clone:
                with observe.phase("checkpoint/clone", cat="checkpoint"):
                    clones = self._clone(trees)
            else:
                clones = trees
            self.wait()                            # join buffer A's write
            with observe.phase("checkpoint/plan", cat="checkpoint"):
                plan = manifest.snapshot_to_host(clones, meta)
            self._last_path = path
            observe.gauge("checkpoint/in_flight").set(1)
            self._enqueue(path, plan, root)
        else:
            self.wait()
            with observe.phase("checkpoint/plan", cat="checkpoint"):
                plan = manifest.snapshot_to_host(trees, meta)
            self._last_path = path
            with observe.phase("checkpoint/persist", cat="checkpoint"):
                manifest.write_snapshot(path, plan)
                if root is not None and plan["process_index"] == 0:
                    manifest.gc_snapshots(root, self.keep_n)
            observe.counter("checkpoint/saves").inc()

    def wait(self) -> None:
        """Block until the in-flight background write (if any) is fully
        committed; re-raise its failure."""
        if self._worker is not None:
            self._queue.join()
        if self._error is not None:
            e, self._error = self._error, None
            raise e

    def drain(self) -> Optional[BaseException]:
        """Join without raising — shutdown/recovery path. Returns the
        swallowed error (already logged) so callers can decide."""
        try:
            self.wait()
            return None
        except BaseException as e:                 # noqa: BLE001 — drained
            return e

    def close(self) -> Optional[BaseException]:
        """Drain, then retire the writer thread for good: the daemon
        flag keeps an abrupt exit from hanging, but a CLEAN shutdown
        joins the worker so no write can race interpreter teardown
        (thread-shutdown audit, docs/concurrency.md). Idempotent."""
        err = self.drain()
        worker, self._worker = self._worker, None
        if worker is not None and worker.is_alive():
            self._queue.put(None)                  # stop sentinel
            worker.join(timeout=10)
        return err
