"""Device ops layer: slot table, batch packing, vectorized bucket kernels.

Importing this package enables JAX x64 mode — the protocol's counters and
timestamps are int64 (proto gubernator.proto:140-161, store.go:29-43) and the
leaky-bucket remainder is float64.  TPU executes both via XLA's 32-bit-pair
emulation; the elementwise VPU work here is cheap relative to HBM traffic.

It also places the persistent compile cache (core/config.compile_cache_dir):
where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and nothing is
set here; otherwise the cache goes to one fixed directory inside the
checkout, so a second daemon start finds what the first one compiled.
The minimum-compile-time floor drops to zero either way: the daemon's
warm-up is a dozen small executables, most under JAX's 1s default.
"""
import os

import jax

from gubernator_tpu.core.config import (
    COMPILE_CACHE_ENV,
    compile_cache_dir,
)

jax.config.update("jax_enable_x64", True)

if not os.environ.get(COMPILE_CACHE_ENV):
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
