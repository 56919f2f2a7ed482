"""Shared pytest fixtures.

The suite compiles several hundred distinct XLA programs (every container
x solver x mesh combination is jitted). On the CPU backend that much
accumulated compile state has crashed the compiler mid-suite — a native
segfault in a late module's first `pjit` cache miss that no single module
reproduces in isolation. Dropping the caches at module boundaries keeps
each module's compile session small; the only cost is re-tracing shared
helpers, which is noise next to the solves themselves.
"""

import os

# The sharded and mesh tests want 8 host devices. XLA reads this flag once,
# at the first backend initialisation, so it is appended here — before any
# test module can touch a backend — keeping whatever the caller already set.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=8") if f
    )

import jax  # noqa: E402  (after XLA_FLAGS)
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _fresh_compile_caches_per_module():
    yield
    jax.clear_caches()


# Property-test profiles (DESIGN.md §8.9 testing policy): tier-1 runs the
# cheap derandomized "quick" profile; `scripts/test.sh --tier2` re-runs the
# property/differential suites under "deep" (more examples, fresh seeds).
# Falls back to tests/_minihyp.py when hypothesis isn't installed, so the
# suites execute either way.
try:
    from hypothesis import HealthCheck, settings as _hyp_settings

    _hyp_settings.register_profile(
        "quick", max_examples=10, deadline=None, derandomize=True,
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
    )
    _hyp_settings.register_profile("deep", max_examples=75, deadline=None)
except ImportError:
    from _minihyp import settings as _hyp_settings

    _hyp_settings.register_profile("quick", max_examples=6)
    _hyp_settings.register_profile("deep", max_examples=30)
_hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "quick"))
