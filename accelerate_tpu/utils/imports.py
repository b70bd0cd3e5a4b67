"""Feature probes.

The reference ships ~60 ``is_*_available()`` probes (reference:
src/accelerate/utils/imports.py). Here the core stack (jax/flax/optax/orbax)
is a hard dependency; probes cover the optional integrations (trackers,
safetensors, torch-interop, datasets).
"""

import functools
import importlib.metadata
import importlib.util


@functools.lru_cache(maxsize=None)
def _is_package_available(pkg_name: str) -> bool:
    if importlib.util.find_spec(pkg_name) is None:
        return False
    try:
        importlib.metadata.version(pkg_name)
    except importlib.metadata.PackageNotFoundError:
        # Namespace packages (or vendored modules) have no metadata but are
        # importable all the same.
        pass
    return True


def is_jax_available() -> bool:
    return _is_package_available("jax")


def is_flax_available() -> bool:
    return _is_package_available("flax")


def is_optax_available() -> bool:
    return _is_package_available("optax")


def is_orbax_available() -> bool:
    return _is_package_available("orbax")


def is_safetensors_available() -> bool:
    return _is_package_available("safetensors")


def is_torch_available() -> bool:
    return _is_package_available("torch")


def is_transformers_available() -> bool:
    return _is_package_available("transformers")


def is_datasets_available() -> bool:
    return _is_package_available("datasets")


def is_pandas_available() -> bool:
    return _is_package_available("pandas")


def is_rich_available() -> bool:
    return _is_package_available("rich")


def is_psutil_available() -> bool:
    return _is_package_available("psutil")


def is_yaml_available() -> bool:
    return _is_package_available("yaml")


# ---------------------------------------------------------------------------
# Trackers (reference: tracking.py:178-1246 — 9 integrations behind probes)
# ---------------------------------------------------------------------------

def is_tensorboard_available() -> bool:
    return _is_package_available("tensorboardX") or _is_package_available("tensorboard")


def is_wandb_available() -> bool:
    return _is_package_available("wandb")


def is_comet_ml_available() -> bool:
    return _is_package_available("comet_ml")


def is_aim_available() -> bool:
    return _is_package_available("aim")


def is_mlflow_available() -> bool:
    return _is_package_available("mlflow")


def is_clearml_available() -> bool:
    return _is_package_available("clearml")


def is_dvclive_available() -> bool:
    return _is_package_available("dvclive")


def is_swanlab_available() -> bool:
    return _is_package_available("swanlab")


def is_trackio_available() -> bool:
    return _is_package_available("trackio")


# ---------------------------------------------------------------------------
# Hardware probes
# ---------------------------------------------------------------------------

def is_tpu_available() -> bool:
    """True when JAX's default backend is a TPU.

    The one platform test in the package: kernel dispatch (compiled Pallas
    vs interpreter/blockwise), host offload and the TPU test tier all ask
    here. A backend that fails to initialise raises — a broken chip must not
    read as "no TPU" and quietly select a CPU path."""
    import jax

    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def is_multihost() -> bool:
    import jax

    return jax.process_count() > 1
