"""In-process launchers (reference: launchers.py:43-322 —
``notebook_launcher`` via xmp.spawn/elastic_launch, ``debug_launcher`` via a
2-proc gloo fork).

TPU-native version: fan out ``multiprocessing`` *spawn* workers, each a fresh
interpreter that sets the coordinator env contract BEFORE importing jax, then
calls the user function. On a machine already attached to TPU chips a single
process sees all local chips, so ``num_processes=1`` (the default) just calls
the function — multi-process spawn is for CPU simulation and multi-host-like
testing.
"""

from __future__ import annotations

import multiprocessing
import os
import traceback
from typing import Callable


def _worker(fn, args, index: int, num_processes: int, port: int, use_cpu: bool,
            virtual_devices: int, error_queue):
    try:
        os.environ["ACCELERATE_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        os.environ["ACCELERATE_NUM_PROCESSES"] = str(num_processes)
        os.environ["ACCELERATE_PROCESS_INDEX"] = str(index)
        os.environ["ACCELERATE_LOCAL_PROCESS_INDEX"] = str(index)
        os.environ["FORK_LAUNCHED"] = "1"
        if use_cpu:
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax

            # A forked worker inherits the parent's imported jax, which read
            # the environment before this line: ask through its config too.
            jax.config.update("jax_platforms", "cpu")
        if virtual_devices:
            flags = os.environ.get("XLA_FLAGS", "")
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={virtual_devices}"
            ).strip()
        fn(*args)
    except Exception:
        error_queue.put((index, traceback.format_exc()))
        raise


def notebook_launcher(
    function: Callable,
    args: tuple = (),
    num_processes: int | None = None,
    use_cpu: bool = False,
    virtual_devices: int = 0,
    master_port: int | None = None,
):
    """Launch ``function(*args)`` on ``num_processes`` JAX processes from a
    live notebook/session (reference: launchers.py:43-285).

    Pre-flight check mirrors the reference: if JAX was already initialized with
    devices in this process, spawning sub-processes that grab the same TPU
    chips would deadlock — in that case only num_processes=1 is allowed.
    """
    num_processes = num_processes or 1
    if num_processes <= 1:
        return function(*args)

    # Pre-flight WITHOUT initializing a backend ourselves: if this process
    # already brought one up, forked children would inherit a live PJRT client
    # (undefined behavior) and spawned children could not re-acquire the TPU
    # (reference does the same check against CUDA init, launchers.py:108-148).
    if _jax_backend_initialized():
        raise RuntimeError(
            "A JAX backend is already initialized in this process (something "
            "called jax.devices()/jit earlier). Restart the notebook and call "
            "notebook_launcher before any JAX computation, or use "
            "num_processes=1 — a single JAX process drives all local chips."
        )

    if master_port is None:
        from .utils.other import get_free_port

        master_port = get_free_port()

    # Fork keeps notebook-defined functions callable (they live in an
    # unimportable __main__, so spawn could not unpickle them — the reference
    # forks for the same reason). Safe because no backend is initialized yet.
    method = "fork" if "fork" in multiprocessing.get_all_start_methods() else "spawn"
    ctx = multiprocessing.get_context(method)
    error_queue = ctx.SimpleQueue()
    procs = []
    for index in range(num_processes):
        p = ctx.Process(
            target=_worker,
            args=(function, args, index, num_processes, master_port, use_cpu,
                  virtual_devices, error_queue),
        )
        p.start()
        procs.append(p)
    failed = []
    for index, p in enumerate(procs):
        p.join()
        if p.exitcode != 0:
            failed.append((index, p.exitcode))
    if failed:
        detail = ""
        while not error_queue.empty():
            idx, tb = error_queue.get()
            detail += f"\n--- process {idx} ---\n{tb}"
        raise RuntimeError(f"notebook_launcher processes failed: {failed}{detail}")


def _jax_backend_initialized() -> bool:
    import sys

    if "jax" not in sys.modules:
        return False
    # No public jax call answers this without initialising a backend itself.
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def debug_launcher(function: Callable, args: tuple = (), num_processes: int = 2):
    """2-process CPU launch for tests (reference: launchers.py:287-322)."""
    notebook_launcher(
        function, args, num_processes=num_processes, use_cpu=True, virtual_devices=1
    )
