"""Pallas TPU kernels for TileLink's compute hot-spots.

Compute kernels: matmul, flash_attention, grouped_matmul (dynamic-mapping MoE),
ssd (Mamba-2), decode_attention (one query row a head over the live spans of
a stacked KV cache).  Fused compute-communication kernels (remote DMA + semaphores):
ag_gemm_shard, gemm_rs_shard.  Oracles live in ref.py; tests sweep shapes and
dtypes against them.
"""
from repro.kernels.decode_attention import decode_attention
from repro.kernels.ops import (
    matmul,
    flash_attention,
    grouped_matmul,
    ag_gemm_shard,
    gemm_rs_shard,
    ssd_chunked,
    ssd_intra_chunk,
    auto_interpret,
)
from repro.kernels import ref

__all__ = [
    "matmul",
    "flash_attention",
    "decode_attention",
    "grouped_matmul",
    "ag_gemm_shard",
    "gemm_rs_shard",
    "ssd_chunked",
    "ssd_intra_chunk",
    "auto_interpret",
    "ref",
]
