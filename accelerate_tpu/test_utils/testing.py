"""Testing helpers (reference: test_utils/testing.py:84-820).

The reference's central trick — multi-process tests are subprocess-launched
copies of the product's own launcher — carries over directly: build an
`accelerate-tpu launch --num_processes=N <script>` command and assert inside
the launched script, which runs under a real multi-process JAX runtime
(SURVEY.md §4). CPU CI gets a pod-shaped mesh via ``--virtual_devices``.
"""

from __future__ import annotations

import os
import subprocess
import sys
import unittest

from ..utils.imports import is_tpu_available

DEFAULT_LAUNCH_PORT = 29876


def skip(reason: str):
    return unittest.skip(reason)


def require_tpu(test_case):
    """Skip unless JAX's default backend is a TPU."""
    return unittest.skipUnless(is_tpu_available(), "test requires TPU")(test_case)


def require_multi_device(test_case):
    import jax

    try:
        n = len(jax.devices())
    except Exception:
        n = 0
    return unittest.skipUnless(n > 1, "test requires multiple devices")(test_case)


def require_multi_process(test_case):
    import jax

    return unittest.skipUnless(jax.process_count() > 1, "test requires multiple processes")(
        test_case
    )


def get_launch_command(num_processes: int = 1, virtual_devices: int = 0, port: int | None = None,
                      **launch_kwargs) -> list[str]:
    """Build the `accelerate-tpu launch` argv prefix (reference:
    test_utils/testing.py:114-133)."""
    cmd = [sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch",
           f"--num_processes={num_processes}"]
    if virtual_devices:
        cmd += [f"--virtual_devices={virtual_devices}", "--cpu"]
    if port is not None:
        cmd += [f"--main_process_port={port}"]
    for k, v in launch_kwargs.items():
        if v is True:
            cmd.append(f"--{k}")
        elif v not in (None, False):
            cmd.append(f"--{k}={v}")
    return cmd


def execute_subprocess(cmd: list[str], env: dict | None = None, timeout: int = 600) -> str:
    """Run a launched test script, raising with its full output on failure
    (reference: testing.py:781-798 `execute_subprocess_async`)."""
    result = subprocess.run(
        cmd,
        env={**os.environ, **(env or {})},
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if result.returncode != 0:
        raise RuntimeError(
            f"Command {' '.join(cmd)} failed with exit code {result.returncode}\n"
            f"--- stdout ---\n{result.stdout}\n--- stderr ---\n{result.stderr}"
        )
    return result.stdout


def assert_trees_equal(a, b, rtol: float = 1e-5, atol: float = 1e-6, path: str = ""):
    """Recursively assert two pytrees of arrays match."""
    import numpy as np

    if isinstance(a, dict):
        assert set(a) == set(b), f"{path}: keys {set(a)} != {set(b)}"
        for k in a:
            assert_trees_equal(a[k], b[k], rtol, atol, f"{path}/{k}")
        return
    if isinstance(a, (list, tuple)):
        assert len(a) == len(b), f"{path}: length {len(a)} != {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            assert_trees_equal(x, y, rtol, atol, f"{path}[{i}]")
        return
    np.testing.assert_allclose(
        np.asarray(a, dtype=np.float64) if hasattr(a, "dtype") else a,
        np.asarray(b, dtype=np.float64) if hasattr(b, "dtype") else b,
        rtol=rtol,
        atol=atol,
        err_msg=path,
    )
