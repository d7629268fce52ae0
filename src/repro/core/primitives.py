"""Tile-centric device primitives (paper Table 3) for Pallas TPU kernels.

These are thin, semantically faithful wrappers over Pallas TPU semaphore and
remote-DMA operations, so fused kernels in ``repro.kernels`` read like the
paper's pseudo-code (Figs. 4–6):

  paper primitive            TPU realization
  -------------------------  ----------------------------------------------
  producer_tile_notify       pltpu.semaphore_signal on the consumer's channel
                             semaphore (local or remote rank) — *release*
  consumer_tile_wait         pltpu.semaphore_wait on the channel semaphore —
                             *acquire* (Mosaic DMAs/semaphores order memory)
  peer_tile_notify/wait      same, on a peer-channel semaphore
  tile_push_data             pltpu.make_async_remote_copy (push over ICI)
  tile_pull_data             SPMD-symmetric push (ICI RDMA is push-native; in
                             an SPMD program every rank pushing its shard is
                             dataflow-equivalent to every rank pulling)
  rank_copy_data             host-side: lax.ppermute / XLA async collective
                             (the "copy engine" resource mapping)

Memory consistency (paper §4.2): Mosaic's semaphore_signal has release
semantics w.r.t. prior DMAs/stores issued by the core, and semaphore_wait has
acquire semantics; additionally the kernel builders in ``repro.kernels`` only
emit loads of a tile *after* the wait that guards it, so no pipelining pass can
reorder across the barrier — the strict-dependency rule of the paper.
"""
from __future__ import annotations

from repro import backend

__all__ = [
    "producer_tile_notify",
    "consumer_tile_wait",
    "peer_tile_notify",
    "peer_tile_wait",
    "tile_push_data",
    "make_tile_push",
    "rank_barrier",
]


def producer_tile_notify(sem, *, rank=None, inc: int = 1):
    """Mark a producer tile done; notify its consumer tile's channel semaphore.

    ``rank=None`` notifies the local consumer (p2p, same device);
    ``rank=r`` notifies rank ``r`` (push mode); broadcast = loop over ranks.
    """
    backend.semaphore_signal(sem, inc, rank=rank)


def consumer_tile_wait(sem, *, count: int = 1):
    """Block the consumer until ``count`` producer tiles signalled the channel."""
    backend.semaphore_wait(sem, count)


# peers are the same mechanism on a dedicated peer channel (paper Fig. 4 ring)
peer_tile_notify = producer_tile_notify
peer_tile_wait = consumer_tile_wait


def rank_barrier(my, world: int):
    """Every rank of the axis has entered the kernel once this returns.

    A remote DMA lands in the peer's VMEM scratch and signals its semaphore,
    which exist only while the peer runs this kernel; pushing before the
    peer entered it would corrupt whatever the peer ran before and lose the
    signal.  So kernels that push call this first: each rank signals the
    barrier semaphore of every other rank and waits for ``world - 1``
    signals (the kernel's compiler params need a ``collective_id``).
    """
    sem = backend.barrier_semaphore()
    for d in range(1, world):
        backend.semaphore_signal(sem, 1, rank=(my + d) % world)
    backend.semaphore_wait(sem, world - 1)


def make_tile_push(src_ref, dst_ref, send_sem, recv_sem, rank):
    """Build an async remote copy handle: tile_push_data (start/wait split).

    Returns the handle so callers can overlap: ``h.start()`` issues the DMA on
    the ICI engine; compute proceeds; ``h.wait()`` (or the receiver's
    ``wait_recv``) completes it.
    """
    return backend.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        rank=rank,
    )


def tile_push_data(src_ref, dst_ref, send_sem, recv_sem, rank, *, notify_sem=None):
    """Synchronous-ish push: start the DMA and wait for local send completion.

    If ``notify_sem`` is given, also signals the remote consumer's channel
    (producer_tile_notify in push mode) after the send completes.
    """
    h = make_tile_push(src_ref, dst_ref, send_sem, recv_sem, rank)
    h.start()
    h.wait_send()
    if notify_sem is not None:
        producer_tile_notify(notify_sem, rank=rank)
    return h
