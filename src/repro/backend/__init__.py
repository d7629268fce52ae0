"""Backend: tile-centric primitives -> JAX 0.9 Pallas/Mosaic.

TileLink's design keeps primitives *tile-centric* and pushes every
platform quirk into a backend that lowers them to what the target supports.
This package is that backend for the JAX/Pallas port: the single point where
kernels, tile primitives, and the mesh layer touch the Pallas TPU API.
Nothing outside ``repro.backend`` may import ``jax.experimental.pallas.tpu``
(enforced by tests/test_backend.py).

Supported JAX
-------------
One release: jax/jaxlib 0.9.0 with libtpu 0.0.34 (pinned in
``requirements-dev.txt``), on the CPU for tests and on TPU v5e.  There are no
branches for other releases; a new JAX is adopted by moving the pin and
fixing what breaks.

Targets
-------
``target()`` returns "tpu" (Mosaic lowering, ICI remote DMAs) or "emulated"
(the TPU interpreter, so the full suite runs on a CPU-only host).  Override
with ``REPRO_BACKEND=tpu|emulated|auto``.

Surface
-------
  mesh / manual regions:   make_mesh, shard_map, axis_size
  kernel launch:           pallas_call, compiler_params, prefetch_grid_spec,
                           pl (pallas frontend handle), HBM, SMEM
  allocation:              vmem_scratch, smem_scratch, dma_semaphore,
                           regular_semaphore, barrier_semaphore
  tile data movement:      make_async_copy, make_async_remote_copy (by
                           logical rank), semaphore_signal, semaphore_wait
  target control:          target, is_emulated, resolve_interpret,
                           default_interpret, describe
  hardware table:          chip, device_kind, mxu_dim, vmem_budget_bytes,
                           vmem_array_bytes, vmem_limit_bytes,
                           sublane_multiple, lane_multiple
  compile cache:           enable_compile_cache
"""
from repro.backend.features import describe
from repro.backend.hw import (
    chip,
    device_kind,
    mxu_dim,
    vmem_budget_bytes,
    vmem_array_bytes,
    vmem_limit_bytes,
    sublane_multiple,
    lane_multiple,
)
from repro.backend.target import (
    target,
    is_emulated,
    resolve_interpret,
    default_interpret,
)
from repro.backend.mesh import make_mesh, shard_map, axis_size
from repro.backend.lowering import (
    pl,
    HBM,
    SMEM,
    compiler_params,
    pallas_call,
    prefetch_grid_spec,
    vmem_scratch,
    smem_scratch,
    dma_semaphore,
    regular_semaphore,
    barrier_semaphore,
    make_async_copy,
    make_async_remote_copy,
    semaphore_signal,
    semaphore_wait,
)
from repro.backend.compile_cache import enable_compile_cache

__all__ = [
    "describe",
    "chip",
    "device_kind",
    "mxu_dim",
    "vmem_budget_bytes",
    "vmem_array_bytes",
    "vmem_limit_bytes",
    "sublane_multiple",
    "lane_multiple",
    "target",
    "is_emulated",
    "resolve_interpret",
    "default_interpret",
    "make_mesh",
    "shard_map",
    "axis_size",
    "pl",
    "HBM",
    "SMEM",
    "compiler_params",
    "pallas_call",
    "prefetch_grid_spec",
    "vmem_scratch",
    "smem_scratch",
    "dma_semaphore",
    "regular_semaphore",
    "barrier_semaphore",
    "make_async_copy",
    "make_async_remote_copy",
    "semaphore_signal",
    "semaphore_wait",
    "enable_compile_cache",
]
