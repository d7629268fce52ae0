"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state.  Single pod: (data=16, model=16) = 256 chips.  Multi-pod: 2 pods = 512
chips with a leading "pod" axis (pure-DP replica axis by default; the runtime
can regroup it as a PP axis for deeper jobs).
"""
from __future__ import annotations

import jax

from repro.compat import make_mesh

__all__ = ["make_production_mesh", "make_dev_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) == n:
        return make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for {shape} mesh, have {len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (dryrun.py does this)")
    # more devices than needed (e.g. 512 placeholders, single-pod 256 mesh)
    import numpy as np
    from jax.sharding import Mesh

    arr = np.asarray(devices[:n]).reshape(shape)
    return Mesh(arr, axes)


def make_dev_mesh(n_model: int = None, n_data: int = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    n_model = n_model or (2 if n >= 2 else 1)
    n_data = n_data or max(1, n // n_model)
    return make_mesh((1, n_data, n_model), ("pod", "data", "model"))
