"""Environment-variable helpers.

Config flows through environment variables, same architectural decision as the
reference (reference: src/accelerate/utils/environment.py and SURVEY.md §1):
the launcher encodes choices as ``ACCELERATE_*`` / ``PARALLELISM_CONFIG_*``
vars, worker processes decode them.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Iterator


def inside_shard_map() -> bool:
    """True while tracing inside ``jax.shard_map`` (some mesh axis is
    Manual): sharding constraints and a nested shard_map do not apply there."""
    from jax.sharding import AxisType, get_abstract_mesh

    return any(t == AxisType.Manual for t in get_abstract_mesh().axis_types)


def str_to_bool(value: str) -> int:
    """Convert a string to a bool int, accepting y/yes/t/true/on/1 (case-insensitive).

    Same contract as the reference's ``str_to_bool``
    (reference: utils/environment.py:60-75).
    """
    value = value.lower()
    if value in ("y", "yes", "t", "true", "on", "1"):
        return 1
    if value in ("n", "no", "f", "false", "off", "0"):
        return 0
    raise ValueError(f"invalid truth value {value}")


def get_int_from_env(env_keys, default: int) -> int:
    """Return the first positive int found among ``env_keys``."""
    for e in env_keys:
        val = int(os.environ.get(e, -1))
        if val >= 0:
            return val
    return default


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key, str(default))
    return bool(str_to_bool(value))


def parse_choice_from_env(key: str, default: str = "no") -> str:
    return os.environ.get(key, str(default))


def are_libraries_initialized(*library_names: str) -> list[str]:
    import sys

    return [lib for lib in library_names if lib in sys.modules.keys()]


@contextlib.contextmanager
def clear_environment() -> Iterator[None]:
    """Temporarily clear ``os.environ``, restoring it afterwards even on error.

    (reference: utils/environment.py:197-230)
    """
    cached = dict(os.environ)
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(cached)


@contextlib.contextmanager
def patch_environment(**kwargs: Any) -> Iterator[None]:
    """Temporarily set env vars (upper-cased keys), restoring previous values.

    (reference: utils/environment.py:233-262)
    """
    existing = {}
    for key, value in kwargs.items():
        key = key.upper()
        if key in os.environ:
            existing[key] = os.environ[key]
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key in kwargs:
            key = key.upper()
            if key in existing:
                os.environ[key] = existing[key]
            else:
                os.environ.pop(key, None)


def purge_accelerate_environment(func):
    """Decorator: run ``func`` with all ACCELERATE_*/PARALLELISM_CONFIG_* vars
    removed, restoring them afterwards (reference: utils/environment.py:417-523)."""
    import functools

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        cached = {
            k: os.environ.pop(k)
            for k in list(os.environ)
            if k.startswith(("ACCELERATE_", "PARALLELISM_CONFIG_", "FSDP_"))
        }
        try:
            return func(*args, **kwargs)
        finally:
            for k in list(os.environ):
                if k.startswith(("ACCELERATE_", "PARALLELISM_CONFIG_", "FSDP_")):
                    del os.environ[k]
            os.environ.update(cached)

    return wrapper


def get_cpu_count() -> int:
    return os.cpu_count() or 1


def set_numa_affinity(local_process_index: int, verbose: bool = False) -> None:
    """Bind this process to the NUMA node of its local index.

    The reference pins GPU processes to NUMA nodes
    (reference: utils/environment.py:263-360). On TPU hosts there is normally
    one process per host so this is a best-effort no-op unless numactl-style
    sysfs info is present.
    """
    try:
        nodes = sorted(
            int(d.replace("node", ""))
            for d in os.listdir("/sys/devices/system/node")
            if d.startswith("node")
        )
    except OSError:
        return
    if not nodes:
        return
    node = nodes[local_process_index % len(nodes)]
    cpus = []
    try:
        with open(f"/sys/devices/system/node/node{node}/cpulist") as f:
            for part in f.read().strip().split(","):
                if "-" in part:
                    lo, hi = part.split("-")
                    cpus.extend(range(int(lo), int(hi) + 1))
                elif part:
                    cpus.append(int(part))
        if cpus and hasattr(os, "sched_setaffinity"):
            os.sched_setaffinity(0, cpus)
    except OSError:
        return
