"""``chip_smoke.py`` refuses to run without a chip, and its control flow stays
runnable (``--rehearse``) between chip runs."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # one CPU device, like a bare run
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_no_chip_is_a_failure_not_a_cpu_run(script):
    """Off-chip: nonzero exit, the missing TPU named, and no result row —
    never a CPU number under a device metric's name."""
    r = _run(script, timeout=120)
    assert r.returncode != 0, r.stdout[-2000:]
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr, r.stderr[-2000:]
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")], r.stdout[-2000:]


@pytest.mark.slow
def test_chip_smoke_rehearsal_cpu():
    """The opt-in rehearsal runs every phase at a tiny size; every line says
    it ran on the CPU and the result row is marked as a rehearsal."""
    r = _run("chip_smoke.py", "--rehearse", timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    *lines, last = r.stdout.splitlines()
    assert all(l.startswith("[platform=cpu rehearsal]") for l in lines), r.stdout[-2000:]
    for phase in ("device", "kernels", "trainer", "server"):
        assert any(f"phase {phase}: PASS" in l for l in lines), phase
    assert json.loads(last) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
