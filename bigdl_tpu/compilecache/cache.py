"""Persistent XLA compilation cache at a place the caller can choose.

The reference amortizes per-task re-initialization by broadcasting ONE
serialized model to every executor and reusing it for the whole job
(`ModelBroadcast.scala`, cached replicas per core). The TPU-native analog
of that cost is XLA compilation: every process used to recompile its
step programs from scratch. jax's own persistent cache
(`jax_compilation_cache_dir`) removes that cost; this module only decides
WHERE it lives:

  * `JAX_COMPILATION_CACHE_DIR` set — jax reads it at import and the
    cache is already on. `enable()` changes nothing, and no code of this
    repository points jax anywhere else.
  * unset — `enable()` points jax at `<checkout>/.jax_cache`, a fixed
    path (the directory is part of every cache key through the XLA
    side caches jax derives from it, so a path made from a pid, a
    temporary name or the time would never hit).

Processes may share the directory. The installed jax (0.9) writes an
entry with a plain `write_bytes`; a reader that catches a half-written
entry fails to deserialize it, warns (`Error reading persistent
compilation cache entry`) and compiles — a lost hit, never a wrong
program — and two writers of one key write the same bytes. The
per-process staging directories and hard-link publishing this module
once carried guarded against that torn read at the price of a cache
directory named from the process id; they are gone.

Entries are jax's: `<dir>/jit_<name>-<key>-cache` (+ `-atime` when an
eviction limit is set). Only programs that took at least
`jax_persistent_cache_min_compile_time_secs` (jax's default: 1 s) to
compile are written. See docs/compile_cache.md.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, Optional

log = logging.getLogger("bigdl_tpu")

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CACHE_SUFFIX = "-cache"
_ATIME_SUFFIX = "-atime"
# <checkout>/bigdl_tpu/compilecache/cache.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The directory the persistent cache lives in: the environment's
    `JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def _reset_jax_cache() -> None:
    """Drop jax's initialized cache object so a config change takes
    effect (jax pins the cache at first use)."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def enable() -> str:
    """Turn the persistent compile cache on for this process and return
    its directory. Entry points call this once at start-up; idempotent."""
    import jax
    path = cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        # jax read the environment variable at import: where that was
        # set the two already agree, and `path` here is the fixed
        # checkout directory
        _reset_jax_cache()
        jax.config.update("jax_compilation_cache_dir", path)
        log.info("compile cache enabled: %s", path)
    return path


def enabled() -> bool:
    import jax
    return bool(jax.config.jax_compilation_cache_dir) \
        and bool(jax.config.jax_enable_compilation_cache)


def disable() -> None:
    """Detach jax from the persistent cache (tests / explicit
    teardown). Entries stay on disk."""
    import jax
    if jax.config.jax_compilation_cache_dir is not None:
        _reset_jax_cache()
        jax.config.update("jax_compilation_cache_dir", None)


def stats(path: Optional[str] = None) -> Dict:
    """Inventory of a cache directory: entries, bytes, and per-program
    counts (cache keys are `jit_<fn-name>-<hash>`, so the program name
    is recoverable)."""
    path = os.path.abspath(path or cache_dir())
    out: Dict = {"root": path, "entries": 0, "bytes": 0, "programs": {}}
    try:
        names = sorted(os.listdir(path))
    except OSError:
        return out
    for name in names:
        if not name.endswith(_CACHE_SUFFIX):
            continue
        try:
            out["bytes"] += os.path.getsize(os.path.join(path, name))
        except OSError:
            continue
        out["entries"] += 1
        prog = name[: -len(_CACHE_SUFFIX)].rsplit("-", 1)[0]
        out["programs"][prog] = out["programs"].get(prog, 0) + 1
    return out


def clear(path: Optional[str] = None) -> int:
    """Remove every entry, sidecar and lockfile under the directory.
    Returns the number of entries removed."""
    path = os.path.abspath(path or cache_dir())
    try:
        names = os.listdir(path)
    except OSError:
        return 0
    removed = 0
    for name in names:
        if name.endswith((_CACHE_SUFFIX, _ATIME_SUFFIX)) \
                or name == ".lockfile":
            try:
                os.unlink(os.path.join(path, name))
            except OSError:
                continue
            removed += name.endswith(_CACHE_SUFFIX)
    return removed
