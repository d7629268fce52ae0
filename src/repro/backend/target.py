"""Backend target selection: real TPU lowering vs. emulated (interpret) CPU.

Targets:

  "tpu"       lower Pallas kernels to Mosaic; remote DMAs ride the ICI.
  "emulated"  run every kernel — including the fused communication kernels —
              in the TPU interpreter on a host with no TPU, using XLA's
              forced-host-device pool for the mesh axes.

Resolution order: the ``REPRO_BACKEND`` environment variable ("tpu",
"emulated", or "auto"); "auto" is "tpu" iff ``jax.default_backend() ==
"tpu"``.  A host without a TPU has no Mosaic runtime, so "auto" can only
mean the interpreter there; code that must run on the chip (``chip_smoke.py``)
checks ``target() == "tpu"`` and refuses anything else.
"""
from __future__ import annotations

import os

import jax

from repro.backend.features import pltpu

__all__ = ["target", "is_emulated", "resolve_interpret", "default_interpret"]

_ENV = "REPRO_BACKEND"
_VALID = ("auto", "tpu", "emulated")


def target() -> str:
    """The active lowering target: "tpu" or "emulated"."""
    choice = os.environ.get(_ENV, "auto").strip().lower()
    if choice not in _VALID:
        raise ValueError(
            f"{_ENV}={choice!r}: expected one of {_VALID}"
        )
    if choice != "auto":
        return choice
    return "tpu" if jax.default_backend() == "tpu" else "emulated"


def is_emulated() -> bool:
    return target() == "emulated"


def resolve_interpret(interpret=None):
    """Normalize an ``interpret`` request into what pallas_call accepts here.

    ``None`` means "whatever the target needs": Mosaic on the "tpu" target,
    the TPU interpreter (``pltpu.InterpretParams``, which simulates the
    inter-device DMAs and semaphores) on the "emulated" target.  The
    emulated target has no Mosaic compiler, so an explicit ``False`` there
    interprets too.  ``"hlo"`` asks for Pallas's plain interpreter on the
    emulated target: it lowers the kernel to ordinary HLO with no host
    callbacks, so it also runs under a partially automatic shard_map, where
    the TPU interpreter's callbacks cannot; it has local DMAs, not remote
    ones.
    """
    if interpret == "hlo":
        return is_emulated()
    if interpret is None:
        interpret = is_emulated()
    if isinstance(interpret, bool):
        if not interpret and not is_emulated():
            return False
        return pltpu.InterpretParams()
    return interpret  # already an InterpretParams object


def default_interpret() -> bool:
    """Plain-bool view of the target, for jit-static ``interpret`` args."""
    return is_emulated()
