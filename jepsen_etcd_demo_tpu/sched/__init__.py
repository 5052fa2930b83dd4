"""sched — the corpus throughput engine (ISSUE 2 tentpole).

Three coordinated layers that make checking MANY histories as fast as
the hardware allows:

  * engine.py        — length-bucketed batch scheduler over the corpus /
                       independent-key lanes (bounded padding waste,
                       bounded compilations per kernel)
  * pipeline.py      — double-buffered chunk pipelining primitives used
                       by the resumable sweeps in ops/wgl2 + ops/wgl3
  * compile_cache.py — the persistent (on-disk, one fixed directory)
                       and in-process (per-bucket-shape LRU) compilation
                       caches, with the hit accounting behind the bench's
                       cache_hit_rate field

See doc/perf.md for the operator-facing story.
"""

from .compile_cache import (compile_cache_dir, enable_persistent_cache,
                            kernel_cache)
from .engine import (assign_step_buckets, check_corpus, corpus_executor,
                     fold_stats, lpt_shard_order, submit_corpus)
from .pipeline import InflightWindow, double_buffer
from .warmup import startup_warmup, warmup_plans

__all__ = [
    "assign_step_buckets",
    "check_corpus",
    "compile_cache_dir",
    "corpus_executor",
    "double_buffer",
    "enable_persistent_cache",
    "fold_stats",
    "InflightWindow",
    "kernel_cache",
    "lpt_shard_order",
    "startup_warmup",
    "submit_corpus",
    "warmup_plans",
]
