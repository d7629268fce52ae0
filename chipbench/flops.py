"""Operations and bytes of the work a cell asks for, computed from shapes.

``Dims`` is read from a configuration file of ``chipbench/configs``; nothing
here imports the program.  The parameter arithmetic follows the 6ND / 2ND rule
(N counts every matmul weight, the output head included, and not the
embedding lookup), with causal attention's score and value products added.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

__all__ = ["Dims", "dims_of", "forward_flops", "train_flops", "serve_pass",
           "serve_step", "SlotWork"]

F32 = 4


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool = False

    @property
    def layer_matmul_params(self) -> int:
        """Weights one layer multiplies by: q, k, v, o projections and the
        gated MLP's gate, up and down."""
        attn = self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        return attn + 3 * self.d * self.d_ff

    @property
    def layer_params(self) -> int:
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d  # two norm scales

    @property
    def head_params(self) -> int:
        return self.d * self.vocab

    @property
    def matmul_params(self) -> int:
        """N of the 6ND rule: every layer's matmul weights and the head."""
        return self.layers * self.layer_matmul_params + self.head_params

    def attn_flops(self, queries: int, keys_each: float) -> float:
        """Score and value products of ``queries`` rows against ``keys_each``
        keys on average, all layers (2 FLOPs per multiply-add, two products)."""
        return 4.0 * self.layers * self.heads * self.head_dim * queries * keys_each

    @property
    def kv_row_bytes(self) -> int:
        """Bytes of one token's keys and values over all layers, float32."""
        return self.layers * self.kv_heads * self.head_dim * 2 * F32


def dims_of(model: dict) -> Dims:
    """Dims from the ``model`` block of a configuration file (HF key names)."""
    heads = int(model["num_attention_heads"])
    return Dims(
        layers=int(model["num_hidden_layers"]),
        d=int(model["hidden_size"]),
        heads=heads,
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model.get("head_dim") or model["hidden_size"] // heads),
        d_ff=int(model["intermediate_size"]),
        vocab=int(model["vocab_size"]),
        qkv_bias=bool(model.get("qkv_bias", False)),
    )


def forward_flops(dm: Dims, seq: int, batch: int = 1) -> float:
    """One causal forward over ``batch`` sequences of ``seq`` tokens, logits
    at every position."""
    tokens = batch * seq
    return 2.0 * dm.matmul_params * tokens + dm.attn_flops(tokens, (seq + 1) / 2)


def train_flops(dm: Dims, seq: int, batch: int) -> float:
    """Forward and backward (3x the forward); recomputation does not count."""
    return 3.0 * forward_flops(dm, seq, batch)


@dataclasses.dataclass(frozen=True)
class SlotWork:
    """One slot's share of a serving step: keys already cached before the
    step, prompt or pending tokens fed in the step's first pass, and tokens
    the step emitted for it (the first from that pass, each later one from a
    decode pass)."""
    ctx: int
    fed: int
    emitted: int


def serve_pass(dm: Dims, slots: Iterable[Tuple[int, int, bool]]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one forward pass of the serving step.

    ``slots``: (cached keys, tokens fed, samples a token) for every slot that
    feeds tokens.  Weights are read once per pass; each slot reads its cached
    keys and values up to its current length and writes those of its new
    tokens; the head runs only for rows that are sampled.
    """
    flops = 0.0
    kv_rows = 0
    any_slot = False
    for ctx, fed, samples in slots:
        if fed <= 0:
            continue
        any_slot = True
        flops += 2.0 * dm.layers * dm.layer_matmul_params * fed
        flops += dm.attn_flops(fed, ctx + (fed + 1) / 2)
        if samples:
            flops += 2.0 * dm.head_params
        kv_rows += ctx + fed
    if not any_slot:
        return 0.0, 0.0
    weights = F32 * (dm.layers * dm.layer_params + dm.head_params + dm.d)
    return flops, float(weights + kv_rows * dm.kv_row_bytes)


def serve_step(dm: Dims, work: Iterable[SlotWork]):
    """Per-pass (FLOPs, bytes) of one engine step: the mixed prefill/decode
    pass, then one decode pass per further token emitted."""
    work = [w for w in work if w.fed > 0 or w.emitted > 0]
    passes = [serve_pass(dm, [(w.ctx, w.fed, w.emitted > 0) for w in work])]
    longest = max((w.emitted for w in work), default=0)
    for t in range(1, longest):
        passes.append(serve_pass(
            dm, [(w.ctx + w.fed + t - 1, 1, True) for w in work if w.emitted > t]))
    return passes
