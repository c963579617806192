"""Central JAX configuration for the framework.

Import this module before any `jax` use inside opensearch_tpu.  It enables
x64 so int64 doc-value columns (date millis, longs — ref
server/src/main/java/org/opensearch/index/mapper/NumberFieldMapper.java,
DateFieldMapper) keep full precision on device.  XLA emulates s64 on TPU
with int32 pairs; the hot scoring kernels below explicitly use
int32/float32 so the MXU path is unaffected.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: the query engine compiles one program
# per (plan shape, size bucket), and a TPU compile costs seconds — caching
# them on disk makes every process after the first start warm (the same
# role Lucene's per-segment codec state plays for reopen cost).  Where
# JAX_COMPILATION_CACHE_DIR is set jax reads it itself and this module
# names no directory; otherwise the cache lives at a fixed path inside the
# checkout, because a directory that moves between runs never hits.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
# An accelerator caches every program, however quick its compile: a
# threshold would let a borderline program land in the cache on one run
# and not the next.  A run held to the CPU caches only its slow compiles,
# because XLA:CPU logs a multi-KB "machine type doesn't match" error on
# every cache LOAD (its loader counts tuning pseudo-features as missing).
jax.config.update("jax_persistent_cache_min_compile_time_secs",
                  0.5 if jax.config.jax_platforms == "cpu" else 0.0)
