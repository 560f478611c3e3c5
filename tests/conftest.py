"""Test env: virtual 8-device CPU mesh (SURVEY §4 TPU-build implication).

Must set XLA flags before jax initializes a backend.  Note: pytest plugins
(e.g. jaxtyping) import jax BEFORE this conftest runs, so setting the
JAX_PLATFORMS env var here is too late — jax snapshots it at import.  The
``jax_platforms`` config update below pins the CPU regardless of import
order.
"""

import faulthandler
import os

# Suite-crash canary (VERDICT r5 weak #5): a round-5 full-suite run died
# with a bare `Fatal Python error` and no traceback.  faulthandler dumps
# every thread's Python stack on SIGSEGV/SIGFPE/SIGABRT/SIGBUS — next
# time the crash leaves evidence.  (Tier-1 docs also set
# PYTHONFAULTHANDLER=1 so crashes during interpreter startup, before
# this conftest imports, are covered too.)
faulthandler.enable()

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# hermetic: FFModel turns the persistent compilation cache on at a fixed
# path in the checkout, and a suite that asserts on compiles must not be
# served programs a previous run left there.  The variable covers the
# children tests start; the cache tests opt back in for theirs.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running acceptance tests excluded from tier-1 "
        "(-m 'not slow'); run explicitly",
    )
