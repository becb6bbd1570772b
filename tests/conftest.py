"""Test harness configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding
(Mesh/pjit/shard_map) is exercised without TPU hardware; the driver's
dryrun_multichip does the same.  Must run before jax initializes a backend.
"""

import os

# Forced assignment: tests run on the virtual CPU mesh whatever the
# shell exports (the chip is reached only through chip_smoke.py).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# The ONE compile-cache rule (utils.set_default_compile_cache): a set
# JAX_COMPILATION_CACHE_DIR is left alone, else <checkout>/.jax_cache.
# Cache keys include the HLO + backend/compile options, so CPU test
# programs can't collide with TPU entries, and repeat suite runs skip
# recompiles.  The 0.5s floor catches this suite's many ~1s model
# compiles that the 1s default would skip.
from textsummarization_on_flink_tpu.utils import (  # noqa: E402
    set_default_compile_cache,
)

set_default_compile_cache(os.environ)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402

# a pytest plugin may import jax before this file runs, so the env vars
# above can land too late — pass them through jax.config as well
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


# Build the native library at test time (a fresh clone + toolchain must
# run the native-queue and native-chunk-reader tests; without a compiler
# the native-parametrized tests skip via native_available()).
try:
    from textsummarization_on_flink_tpu.native import build as _native_build

    _native_build.build()
except Exception:  # noqa: BLE001 — optional dependency, skip-gated tests
    pass


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test (>=20s: multiprocess runs, dryruns, "
        "full-scale compiles).  Fast iteration: -m 'not slow' (~half the "
        "suite wall clock); the full suite gates round-end.")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection recovery test "
        "(RESILIENCE.md).  Select with -m chaos (scripts/chaos.sh runs "
        "these under TS_FAULTS sweeps); all are seeded and CPU-fast, so "
        "they also run in the default suite.")
