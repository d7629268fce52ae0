"""Pallas lowering surface: the only path from kernels to ``pltpu``.

Kernels and tile primitives call these functions instead of touching
``jax.experimental.pallas.tpu``, so the interpret-mode policy (see
``target.py``) and the remote device-id representation live in one place.

Remote device ids: every fused kernel addresses peers by *logical rank along
the single manual mesh axis* it runs under, which Mosaic takes as a
MESH-coordinate device id (a 1-tuple).  The kernels are therefore launched
under shard_map over a one-axis mesh.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.backend import features as _f
from repro.backend.target import resolve_interpret as _resolve_interpret

pl = _f.pl
pltpu = _f.pltpu

__all__ = [
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
]

HBM = pltpu.HBM
SMEM = pltpu.SMEM


# ---- compile parameters ------------------------------------------------------

def compiler_params(*, dimension_semantics=None, **kw):
    """``pltpu.CompilerParams``; unknown fields raise (a misspelled
    ``vmem_limit_bytes`` must not be dropped in silence).

    The fused ring kernels rely on "arbitrary" ``dimension_semantics`` to
    force sequential grid execution (each step waits on the previous step's
    DMA).
    """
    if dimension_semantics is not None:
        kw["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kw)


def pallas_call(kernel, *, name, dimension_semantics=None, interpret=None,
                compiler_params_kw=None, **kw):
    """``pl.pallas_call`` with TPU compiler params and the target's interpret mode.

    ``name``: the kernel's stable name, which its ops carry in the compiled
    program and the profiler's trace.
    ``interpret``: True/False, or None for "whatever the target needs"
    (the emulated target always interprets).
    """
    params = compiler_params(
        dimension_semantics=dimension_semantics, **(compiler_params_kw or {})
    )
    return pl.pallas_call(
        kernel,
        name=name,
        compiler_params=params,
        interpret=_resolve_interpret(interpret),
        **kw,
    )


def prefetch_grid_spec(*, num_scalar_prefetch, grid, in_specs, out_specs,
                       scratch_shapes=()):
    """Scalar-prefetch grid spec (dynamic-mapping kernels)."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )


# ---- scratch / semaphore allocation ------------------------------------------

def vmem_scratch(shape, dtype=jnp.float32):
    """A VMEM scratch allocation for ``scratch_shapes``."""
    return pltpu.VMEM(tuple(shape), dtype)


def smem_scratch(shape, dtype=jnp.int32):
    return pltpu.SMEM(tuple(shape), dtype)


def dma_semaphore(shape=None):
    """A DMA semaphore (optionally an array of them) for ``scratch_shapes``."""
    if shape is None:
        return pltpu.SemaphoreType.DMA
    return pltpu.SemaphoreType.DMA(tuple(shape))


def regular_semaphore(shape=None):
    if shape is None:
        return pltpu.SemaphoreType.REGULAR
    return pltpu.SemaphoreType.REGULAR(tuple(shape))


def barrier_semaphore():
    """The kernel's cross-device barrier semaphore (needs ``collective_id``
    in the compiler params)."""
    return pltpu.get_barrier_semaphore()


# ---- DMA + semaphore primitives ----------------------------------------------

def make_async_copy(src_ref, dst_ref, sem):
    """Local async copy handle (start()/wait())."""
    return pltpu.make_async_copy(src_ref, dst_ref, sem)


def make_async_remote_copy(src_ref, dst_ref, send_sem, recv_sem, rank):
    """Remote async copy handle addressed by logical rank on the manual axis."""
    return pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=(rank,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )


def semaphore_signal(sem, inc: int = 1, *, rank=None):
    """Signal a semaphore, locally or on peer ``rank`` (release semantics)."""
    if rank is None:
        pltpu.semaphore_signal(sem, inc)
        return
    pltpu.semaphore_signal(
        sem, inc, device_id=(rank,), device_id_type=pltpu.DeviceIdType.MESH
    )


def semaphore_wait(sem, count: int = 1):
    """Block until the semaphore holds ``count`` (acquire semantics)."""
    pltpu.semaphore_wait(sem, count)
