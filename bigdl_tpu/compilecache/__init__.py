"""bigdl_tpu.compilecache — compile once, run everywhere.

Compile-latency subsystem (reference analogue: `ModelBroadcast` cached
model replicas + warm `Engine` thread pools — the reference never pays
re-initialization per task; here the equivalent fixed cost is XLA
compilation):

  * **cache**  — jax's persistent XLA compilation cache at
                 JAX_COMPILATION_CACHE_DIR, else the fixed
                 `<checkout>/.jax_cache`; entry points call `enable()`;
  * **warmup** — AOT `jit(...).lower(specs).compile()` plumbing for the
                 trainers' `precompile()` (BIGDL_TPU_PRECOMPILE /
                 --precompile), logging XLA cost analysis (flops, bytes,
                 peak memory) through the observe metrics registry;
  * **CLI**    — `python -m bigdl_tpu.compilecache {stats,clear}`.

See docs/compile_cache.md.
"""

from bigdl_tpu.compilecache.cache import (cache_dir, clear, disable,
                                          enable, enabled, stats)
from bigdl_tpu.compilecache.warmup import (cost_summary, key_sds, log_cost,
                                           precompile_buckets,
                                           precompile_fixed, scalar_sds,
                                           sds_like)

__all__ = [
    "enable", "enabled", "disable", "cache_dir", "stats", "clear",
    "cost_summary", "log_cost", "sds_like", "key_sds", "scalar_sds",
    "precompile_buckets", "precompile_fixed",
]
