"""Root pytest conftest: run the test suite on a simulated multi-device CPU mesh.

This is the TPU-native analog of the reference's ``LocalSparkContext`` fixture
(/root/reference src/test/.../utils/LocalSparkContext.scala:10-21): the reference
validates its distributed code paths on a threaded ``local[2]`` Spark backend;
we validate ours by running the *same* mesh/sharding/collective code paths on an
8-device CPU platform via ``--xla_force_host_platform_device_count``.

Must run before any test module imports jax.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

# Pin tests to the CPU platform whatever the environment's default backend.
jax.config.update("jax_platforms", "cpu")
# Tests compare against float64 NumPy oracles; enable x64 so CPU math is exact
# enough for the golden comparisons (TPU runtime uses f32/bf16 — see config).
jax.config.update("jax_enable_x64", False)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _bounded_xla_state():
    """Clear JAX's in-process caches after every test module.

    The suite has grown to ~400 tests whose accumulated compiled executables
    eventually segfault the XLA CPU compiler deep into a full run (observed
    at test_transformer::test_gqa_trains_and_decodes after ~370 tests; the
    same test passes standalone and in any ~70-test subset, and host RAM is
    ~free — the crash is in-process XLA state, not memory pressure or the
    test). Module-boundary cache clears bound that state; cross-module
    cache hits are rare (shapes differ per module), so the cost is small.
    """
    yield
    jax.clear_caches()
