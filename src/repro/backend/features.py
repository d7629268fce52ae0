"""The Pallas handles and the version report of the installed JAX.

The repository targets one JAX release (jax/jaxlib 0.9.0, libtpu 0.0.34 —
the versions of ``requirements-dev.txt``); nothing here probes for older
spellings.  This module is the ONLY place in the repository that imports
``jax.experimental.pallas.tpu`` (enforced by tests/test_backend.py); the
``pl``/``pltpu`` handles re-exported here are consumed by the sibling
modules and must not leak outside ``repro.backend``.
"""
from __future__ import annotations

from importlib import metadata

import jax
import jaxlib
from jax.experimental import pallas as pl  # noqa: F401  (re-exported)
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (re-exported)

__all__ = ["describe", "pl", "pltpu"]


def _dist_version(name: str):
    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return None


def describe() -> dict:
    """Versions, the first device and the lowering target, for logs."""
    from repro.backend.target import target

    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": _dist_version("libtpu"),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "target": target(),
    }
