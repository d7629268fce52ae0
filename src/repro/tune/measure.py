"""Measured ranker — time candidates through the real compiled frontend.

Each candidate is realized as a ``BlockChannel``, lowered with
``compile_overlap`` (the SAME entry point production code uses — no
tuning-only code path), wrapped in shard_map over the target mesh, and timed
on synthetic operands reconstructed from the shape signature.  The signature
is per-shard (what the ops see inside the manual region), so global operands
scale the sharded dim by the mesh's axis size.

Timing discipline (the Triton-distributed-style pitfalls, PAPERS.md):

  * compilation is split from measurement via the AOT path —
    ``jit(...).lower(*args).compile()`` — so jit compile time can never land
    inside a timed window and ONE compiled executable is reused across every
    warmup and repeat of a candidate;
  * ``warmup >= 1`` is enforced: even a pre-compiled executable's first call
    pays one-time costs (buffer donation bookkeeping, allocator warm-up)
    that are not steady state, so a cold call must never be scored;
  * :func:`time_fn` reports ``(median_us, iqr_us)`` — the interquartile
    range is the noise estimate the early-exit sweep (``tune/sweep.py``)
    reasons with when deciding whether an incumbent can still be beaten;
  * :class:`CaseTimer` synthesizes the operands ONCE per ``(kind, mesh,
    signature)`` and shares them across every candidate of a sweep, so
    candidate scores differ only by the design point, never by the data.

Wall time is only a meaningful perf signal on a real accelerator target —
on the emulated CPU target the analytic model (``tune/cost.py``) should rank
instead (``ranker="auto"`` does this; see ``repro.tune.autotune``).  The
measured path still *runs* everywhere, which is how tests exercise it.
"""
from __future__ import annotations

import time
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.channels import BlockChannel
from repro.core.compiler import compile_overlap
from repro.tune.candidates import TUNABLE_KINDS

__all__ = ["build_case", "time_fn", "CaseTimer"]


def _aot_compile(fn, *args):
    """Ahead-of-time compile ``fn`` for ``args`` when it has an AOT surface.

    Jitted callables go through ``lower(*args).compile()`` so the executable
    exists before the first timed window; plain callables (already-compiled
    executables, host functions in tests) are returned as-is.
    """
    lower = getattr(fn, "lower", None)
    if lower is None:
        return fn
    try:
        return lower(*args).compile()
    except Exception:  # version-moved AOT surface: fall back to the jit cache
        return fn


def time_fn(fn, *args, repeats: int = 3, warmup: int = 1) -> Tuple[float, float]:
    """``(median_us, iqr_us)`` wall time per call, compile time excluded.

    ``fn`` is AOT-compiled first (see :func:`_aot_compile`) and the ONE
    compiled executable serves every warmup and timed call.  ``warmup`` must
    be >= 1 so a cold first call can never be scored; ``iqr_us`` is the
    spread between the upper and lower quartile of the timed repeats (0.0
    for a single repeat) — the pruner's noise estimate.
    """
    if warmup < 1:
        raise ValueError(
            f"time_fn needs warmup >= 1 (a cold call must never be scored), got {warmup}"
        )
    if repeats < 1:
        raise ValueError(f"time_fn needs repeats >= 1, got {repeats}")
    compiled = _aot_compile(fn, *args)
    for _ in range(warmup):
        jax.block_until_ready(compiled(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    n = len(ts)
    median = ts[n // 2]
    iqr = ts[min(n - 1, (3 * n) // 4)] - ts[n // 4]
    return median * 1e6, iqr * 1e6


def build_case(kind: str, mesh, axis: str, sig: Tuple[int, ...]):
    """(builder, args): builder(channel) -> jitted global-operand callable.

    Shapes come from the per-shard signature (see ``candidates.signature``);
    operands are synthesized deterministically so repeated measurements of
    the same signature are comparable.
    """
    world = int(mesh.shape[axis])
    key = jax.random.PRNGKey(0)

    def sm(fn, in_specs, out_specs):
        wrapped = compat.shard_map(fn, mesh, in_specs=in_specs, out_specs=out_specs)
        return jax.jit(wrapped)

    def lead_shape(lead, *rest):
        return ((lead,) if lead > 1 else ()) + rest

    if kind == "ag_matmul":
        lead, m_loc, k, n_loc = sig
        x = jax.random.normal(key, lead_shape(lead, world * m_loc, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n_loc), jnp.float32)
        nlead = len(x.shape) - 2
        xspec = P(*((None,) * nlead), axis, None)
        out_spec = P(*((None,) * (nlead + 2)))

        def build(ch: BlockChannel):
            return sm(compile_overlap(kind, ch), (xspec, P(None, None)), out_spec)

        return build, (x, w)

    if kind == "matmul_rs":
        lead, m_glob, k_loc, n = sig
        x = jax.random.normal(key, lead_shape(lead, m_glob, world * k_loc), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(2), (world * k_loc, n), jnp.float32)
        nlead = len(x.shape) - 2
        xspec = P(*((None,) * nlead), None, axis)
        out_spec = P(*((None,) * nlead), axis, None)

        def build(ch: BlockChannel):
            return sm(compile_overlap(kind, ch), (xspec, P(axis, None)), out_spec)

        return build, (x, w)

    if kind == "ag_attention":
        b, h, hkv, s_loc, d = sig
        q = jax.random.normal(key, (b, h, world * s_loc, d), jnp.float32)
        kv = jax.random.normal(jax.random.PRNGKey(3), (b, hkv, world * s_loc, d), jnp.float32)
        spec = P(None, None, axis, None)

        def build(ch: BlockChannel):
            return sm(compile_overlap(kind, ch, causal=True), (spec, spec, spec), spec)

        return build, (q, kv, kv)

    if kind == "ag_moe":
        from repro.core.moe_overlap import moe_router

        m_loc, d_model, top_k, e_loc, d_exp = sig
        e = e_loc * world
        x = jax.random.normal(key, (world * m_loc, d_model), jnp.float32) * 0.5
        wr = jax.random.normal(jax.random.PRNGKey(4), (d_model, e), jnp.float32)
        wgu = jax.random.normal(jax.random.PRNGKey(5), (e, d_model, 2 * d_exp), jnp.float32) * 0.1
        wdn = jax.random.normal(jax.random.PRNGKey(6), (e, d_exp, d_model), jnp.float32) * 0.1

        def build(ch: BlockChannel):
            g = compile_overlap(kind, ch, capacity_factor=8.0)

            def f_(xs, wgu_, wdn_):
                ids, wts, _ = moe_router(xs, wr, num_experts=e, top_k=max(1, top_k))
                return g(xs, ids, wts, wgu_, wdn_)

            in_specs = (P(axis, None), P(axis, None, None), P(axis, None, None))
            return sm(f_, in_specs, P(axis, None))

        return build, (x, wgu, wdn)

    raise ValueError(f"kind {kind!r} is not measurable; one of {TUNABLE_KINDS}")


class CaseTimer:
    """One ``(kind, mesh, signature)`` measurement context for a whole sweep.

    ``build_case`` runs ONCE — the synthetic operands are shared by every
    candidate, so scores differ only by the design point.  Each candidate
    still compiles its own executable (a different design point is a
    different program) through the AOT split in :func:`time_fn`.
    """

    def __init__(self, kind: str, mesh, axis: str, sig: Tuple[int, ...]):
        self.kind = kind
        self._build, self._args = build_case(kind, mesh, axis, sig)

    def time(self, channel: BlockChannel, *, repeats: int = 3, warmup: int = 1):
        """``(median_us, iqr_us)`` for one realized candidate."""
        return time_fn(self._build(channel), *self._args, repeats=repeats, warmup=warmup)
