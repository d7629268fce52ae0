"""Per-chip hardware constants, keyed by ``device_kind`` (one table).

The tuner prunes its tile lattice against the VMEM a kernel may use, the
cost model and the roofline divide by the chip's peaks, and the kernels size
their scoped-VMEM request.  All of them read :data:`CHIPS`.  A TPU whose
``device_kind`` is not in the table is an error, never a default.  The
emulated target (a CPU host running the kernels in the interpreter) models
one named chip, :data:`EMULATED_KIND`, so tiles tuned there stay valid on it.

Sources, TPU v5e ("TPU v5 lite" is its ``device_kind``):

  * peaks — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI per chip (4 links, so
    50 GB/s per link);
  * VMEM — 128 MiB per TensorCore (``jax.experimental.pallas.tpu.
    get_tpu_info()`` in jax 0.9.0); a kernel gets the compiler's default
    *scoped* limit of 16 MiB unless it asks for more through
    ``vmem_limit_bytes`` (JAX Pallas TPU documentation).

``REPRO_VMEM_BYTES`` overrides the scoped budget (tests use it to exercise
the pruning path).
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from repro.backend.target import is_emulated

__all__ = [
    "Chip",
    "CHIPS",
    "EMULATED_KIND",
    "MXU_DIM",
    "LANE_MULTIPLE",
    "device_kind",
    "chip",
    "mxu_dim",
    "vmem_budget_bytes",
    "vmem_array_bytes",
    "vmem_limit_bytes",
    "sublane_multiple",
    "lane_multiple",
]

_ENV_VMEM = "REPRO_VMEM_BYTES"

# the MXU systolic array is 128x128 on v5e; the vector lane width (last-dim
# packing multiple) is likewise 128
MXU_DIM = 128
LANE_MULTIPLE = 128


@dataclasses.dataclass(frozen=True)
class Chip:
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    link_bw: float  # bytes/s per ICI link
    vmem_bytes: int  # physical VMEM per TensorCore
    scoped_vmem_bytes: int  # default scoped-VMEM limit of one kernel


CHIPS = {
    "TPU v5 lite": Chip(
        peak_flops=197e12,
        hbm_bw=819e9,
        link_bw=50e9,
        vmem_bytes=128 * 2**20,
        scoped_vmem_bytes=16 * 2**20,
    ),
}
EMULATED_KIND = "TPU v5 lite"


def device_kind() -> str:
    """The chip the program runs on, or :data:`EMULATED_KIND` on the emulated target."""
    if is_emulated():
        return EMULATED_KIND
    return jax.devices()[0].device_kind


def chip(kind=None) -> Chip:
    """Constants of ``kind`` (default: :func:`device_kind`); unknown kinds raise."""
    kind = kind or device_kind()
    if kind not in CHIPS:
        raise ValueError(
            f"unknown TPU device_kind {kind!r}: add it to repro.backend.hw.CHIPS "
            f"with its source (known: {sorted(CHIPS)})"
        )
    return CHIPS[kind]


def mxu_dim() -> int:
    """Edge length of the MXU systolic array (tiles below it underutilize)."""
    return MXU_DIM


def vmem_budget_bytes() -> int:
    """VMEM one kernel's tile working set may use without asking for more."""
    env = os.environ.get(_ENV_VMEM)
    if env:
        return max(1, int(env))
    return chip().scoped_vmem_bytes


def vmem_array_bytes(shape, dtype) -> int:
    """Bytes of one VMEM array, its last two dims padded to the (sublane, 128) tiling."""
    *lead, rows, cols = (1,) + tuple(int(d) for d in shape)
    sub = sublane_multiple(dtype)
    rows = -(-rows // sub) * sub
    cols = -(-cols // LANE_MULTIPLE) * LANE_MULTIPLE
    n = rows * cols
    for d in lead:
        n *= d
    return n * jnp.dtype(dtype).itemsize


def vmem_limit_bytes(footprint: int):
    """The scoped-VMEM request of a kernel whose buffers take ``footprint`` bytes.

    None (the compiler's default) while it fits the smallest default scoped
    limit in :data:`CHIPS`; else the footprint plus a quarter for Mosaic's
    own scratch, in whole MiB.  The compiler refuses a request beyond the
    chip's VMEM.
    """
    if footprint <= min(c.scoped_vmem_bytes for c in CHIPS.values()):
        return None
    return -(-(footprint * 5 // 4) // 2**20) * 2**20


def sublane_multiple(dtype) -> int:
    """Second-to-last-dim packing multiple for ``dtype`` (8 sublanes x 32b).

    f32 packs 8 rows per tile register, bf16/f16 16, int8/fp8 32 — the
    standard (8 * 4 / itemsize) rule.
    """
    itemsize = jnp.dtype(dtype).itemsize
    return max(8, (8 * 4) // max(1, itemsize))


def lane_multiple() -> int:
    """Last-dim packing multiple (always the 128-wide vector lane)."""
    return LANE_MULTIPLE
