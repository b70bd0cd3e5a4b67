"""TPU-gated kernel tier — runs ONLY against a real chip.

CI runs the whole suite on the virtual CPU mesh, which exercises the Pallas
kernels in *interpreter* mode only. A compiled-lowering regression (Mosaic
tiling, SMEM prefetch, scalar-prefetch offsets) is invisible to that suite.
This tier is the compiled-mode health check, kept separable from the
benchmark so kernel status costs a few minutes of chip time.

Run via `make test_tpu` (sets ACCELERATE_TEST_USE_TPU=1, serial). Without
that variable the tier skips; with it, a chip that cannot be reached is a
FAILURE of every test here, not a skip. The check runs in pytest's own
process: a chip belongs to one process at a time, so a probe subprocess
would take it from the tests it probes for.
"""

import os
from pathlib import Path

import pytest

_TIER_DIR = Path(__file__).parent


def pytest_collection_modifyitems(config, items):
    if os.environ.get("ACCELERATE_TEST_USE_TPU"):
        return
    marker = pytest.mark.skip(
        reason="TPU tier needs ACCELERATE_TEST_USE_TPU=1 (use `make test_tpu`)"
    )
    # This hook receives EVERY collected item in the session, not just this
    # directory's — mark only the TPU tier or `pytest tests/` would skip the
    # whole CPU suite.
    for item in items:
        if _TIER_DIR in Path(str(item.fspath)).parents:
            item.add_marker(marker)


@pytest.fixture(autouse=True)
def _require_tpu_backend():
    from accelerate_tpu.utils import is_tpu_available

    assert is_tpu_available(), (
        "ACCELERATE_TEST_USE_TPU=1 but JAX's default backend is not a TPU"
    )
