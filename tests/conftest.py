"""Test fixtures: virtual 8-device CPU mesh + singleton reset.

Mirrors the reference's test strategy (SURVEY.md §4): a CPU multi-device
fake-mesh path for CI (`xla_force_host_platform_device_count`) and
singleton-reset fixtures (the reference's `AccelerateTestCase`,
test_utils/testing.py:667-679).
"""

import os

# Must run before jax is imported (it reads JAX_PLATFORMS then; XLA reads
# XLA_FLAGS when the backend starts). Tests always target the virtual CPU
# mesh (set ACCELERATE_TEST_USE_TPU=1 to run tests/tpu against real chips).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
if not os.environ.get("ACCELERATE_TEST_USE_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    # Persistent XLA compilation cache: tried (2.6x on warm model-file
    # reruns) and REVERTED — cache-hit replays of the ring-attention
    # (shard_map/ppermute) executables SIGABRT the CPU backend, with or
    # without jax_persistent_cache_enable_xla_caches. Opt in explicitly via
    # ACCELERATE_TEST_COMPILE_CACHE for suites that skip the cp/ring tests.
    cache_dir = os.environ.get("ACCELERATE_TEST_COMPILE_CACHE")
    if cache_dir:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir)
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import pytest  # noqa: E402


_test_counter = {"n": 0}


@pytest.fixture(autouse=True)
def reset_accelerate_state():
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    # Periodically drop live compiled executables: the full suite compiles
    # thousands of tiny programs in ONE process, and jaxlib's CPU backend
    # nondeterministically SIGSEGVs inside backend_compile_and_load late in
    # such runs (observed ~test 290+ at varying tests). Bounding the live
    # executables (and their JIT code mappings) is the mitigation; the
    # recompile cost is small because most tests build fresh modules anyway.
    _test_counter["n"] += 1
    if _test_counter["n"] % 40 == 0 and not os.environ.get("ACCELERATE_TEST_USE_TPU"):
        import jax as _jax

        _jax.clear_caches()
