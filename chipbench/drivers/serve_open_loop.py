"""Open-loop serving through ``ServeEngine``: requests arrive on the traffic
mix's schedule whether or not earlier ones have finished.

Every request due in the window is timed from its scheduled arrival.  After
the window's last arrival the engine runs on until each of them has finished
(at most ``DRAIN_LIMIT_S`` more); the window closes then.  The check compares
two things with the reference:

* a sample of the finished requests, the one with the most output tokens
  among them: prompt and served tokens through the reference's full causal
  forward, and how far below the reference's best logit each served token's
  logit lies (``served_token_gap``);
* the keys and values of the first layer that the slot pool still holds for
  each finished request whose slot nobody took after it, written by chunked
  prefill and by decode, against the reference's over the same tokens
  (``kv_cache_err``).  They are stored in the program's precision, so this
  is the number a lower precision fails: the served tokens' gaps are as wide
  under the program's own single-pass bf16 products as under bfloat16.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, traffic, weights

DRAIN_LIMIT_S = 60.0


def _spanned(fn, ctx, name):
    def wrapped(*a, **k):
        with ctx.span(name):
            return fn(*a, **k)
    return wrapped


def build(ctx):
    """The engine over the seed's weights, warmed up on the window's shapes."""
    from repro.parallel.context import ParallelContext
    from repro.serving import Request, ServeEngine

    mix, cfg = ctx.mix, ctx.arch
    pc = ParallelContext(mesh=ctx.mesh(), mode="overlap")
    engine = ServeEngine(cfg, pc, ctx.weights(pc), max_len=mix["max_len"],
                         n_slots=mix["slots"])
    engine._fetch = _spanned(engine._fetch, ctx, "serve.fetch")
    # warm-up: a request through a prefill chunk and the decode block, then two
    # seated at once, so that the slot reset has seen the caches of the
    # initial pool, of a step and of another reset
    warm = np.arange(engine.prefill_chunk + 1, dtype=np.int32) % cfg.vocab_size
    for n_warm in (1, 2):
        engine.drain([engine.submit(Request(tokens=warm, max_new_tokens=3))
                      for _ in range(n_warm)])
    jax.block_until_ready(engine.pool.caches)
    return engine


def window(ctx, engine, reqs, seconds, drain_s=DRAIN_LIMIT_S):
    """Send ``reqs`` on their schedule from now on, step the engine until each
    has finished or ``drain_s`` past the window's end; times are seconds
    after the window opened.  Returns the timings, the handles, the request
    seated last in each slot, the most requests left waiting for a slot after
    a step, and the work of every step."""
    from repro.serving import Request

    n = len(reqs)
    at = np.array([r.at for r in reqs])
    admit = np.full(n, np.nan)
    first = np.full(n, np.nan)
    last = np.full(n, np.nan)
    got = np.zeros(n, np.int64)
    handle_of, index_of = {}, {}
    queued, flying = set(), set()
    steps = []  # (seconds, [SlotWork])
    nxt = 0
    sch = engine.scheduler
    states = sch.states
    seats = {}  # slot -> handle of the request seated there last
    slot_queue = 0
    real_admit = sch.admit

    def seating():
        seated = real_admit()
        seats.update((slot, sch.slots[slot]) for slot in seated)
        return seated

    sch.admit = seating
    try:
        t_open = ctx.now()
        while True:
            t = ctx.now()
            rel = t - t_open
            ctx.tick(t)
            with ctx.span("generator"):
                while nxt < n and at[nxt] <= rel:
                    r = reqs[nxt]
                    h = engine.submit(Request(tokens=r.prompt, max_new_tokens=r.out_len,
                                              seed=nxt))
                    handle_of[nxt], index_of[h] = h, nxt
                    queued.add(nxt)
                    flying.add(nxt)
                    nxt += 1
            if nxt == n and not flying:
                break
            if rel > seconds + drain_s:
                break
            if not flying:
                time.sleep(max(0.0, min(at[nxt] - rel, 0.05)))
                continue
            before = {i: (states[handle_of[i]].pos, states[handle_of[i]].cache_len,
                          len(states[handle_of[i]].generated)) for i in flying}
            t_a = ctx.now()
            with ctx.span("serve.step"):
                out = engine.step()
            t_b = ctx.now()
            slot_queue = max(slot_queue, len(sch.queue))
            work = []
            for i in list(flying):
                st = states[handle_of[i]]
                pos, cache_len, n_gen = before[i]
                emitted = len(st.generated) - n_gen
                fed = st.pos - pos if st.pos > pos else (1 if emitted else 0)
                if fed or emitted:
                    work.append(flops.SlotWork(cache_len, fed, emitted))
                if i in queued and (st.slot is not None or st.done or st.pos > 0):
                    admit[i] = t_a - t_open
                    queued.discard(i)
            for h, toks in out.items():
                i = index_of.get(h)
                if i is None:  # a request of an earlier window still draining
                    continue
                if toks:
                    if np.isnan(first[i]):
                        first[i] = t_b - t_open
                    last[i] = t_b - t_open
                    got[i] += len(toks)
                if states[h].done:
                    flying.discard(i)
            steps.append((t_b - t_a, work))
    finally:
        sch.admit = real_admit
    return {"at": at, "admit": admit, "first": first, "last": last, "tokens": got,
            "handles": handle_of, "seats": seats, "slot_queue": slot_queue, "work": steps}


def run(ctx):
    cfg = ctx.arch
    engine = build(ctx)
    reqs = traffic.open_loop(ctx.mix, ctx.seed, ctx.seconds, cfg.vocab_size)
    ctx.open_window()
    w = window(ctx, engine, reqs, ctx.seconds)
    ctx.close_window()
    ctx.read_memory()

    handles = w.pop("handles")
    served = {}
    for i, h in handles.items():
        p = engine.poll(h)
        if p["done"]:
            served[i] = np.asarray(p["tokens"], np.int32)
    n = len(reqs)
    short = sum(1 for i, toks in served.items() if len(toks) != reqs[i].out_len)
    outside = sum(1 for toks in served.values()
                  if len(toks) and (toks.min() < 0 or toks.max() >= cfg.vocab_size))
    held = held_rows(ctx, engine, reqs, served, handles, w.pop("seats"))
    del engine
    gc.collect()

    ctx.check("short_requests", short, 0)
    ctx.check("tokens_outside_vocab", outside, 0)
    _check_logits(ctx, reqs, served)
    _check_cache(ctx, reqs, served, held)
    w["steps"] = [(dt, flops.serve_step(ctx.dims, work)) for dt, work in w.pop("work")]
    return {"attempted": n, "failed": n - len(served), "serve": w}


def sample(ctx, reqs, served):
    """Indices of the requests the check compares: the finished one with the
    most output tokens, and others drawn from the seed."""
    k = int(ctx.mix["check"]["requests"])
    done = sorted(served)
    if not done:
        return []
    longest = max(done, key=lambda i: (len(served[i]), -i))
    rest = [i for i in done if i != longest]
    pick = traffic.rng(ctx.seed, 4).permutation(len(rest))[: k - 1]
    return [longest] + sorted(rest[j] for j in pick)


def _check_logits(ctx, reqs, served):
    idx = sample(ctx, reqs, served)
    if not idx:
        ctx.check("no_request_finished", 1, 0)
        return
    toks, rows, chosen, mask = teacher_forced(ctx, reqs, served, idx)
    reference = ctx.reference
    key = weights.root_key(ctx.seed)
    kw = reference.options(ctx.cell.config)
    logits = reference.forward_rows(key, ctx.dims, toks, rows, **kw)
    ctx.check("served_token_gap", token_gap(reference, logits, chosen, mask),
              ctx.mix["check"]["served_token_gap"])
    if ctx.control:
        low = reference.forward_rows(key, ctx.dims, toks, rows, dtype=jnp.bfloat16, **kw)
        ctx.control_readings["served_token_gap"] = token_gap(
            reference, logits, jnp.argmax(low, axis=-1), mask)
        ctx.control_readings["served_tokens_checked"] = int(mask.sum())


def token_gap(reference, logits, chosen, mask) -> float:
    """The widest gap by which a chosen token's logit lies below the
    reference's best, over the checked positions."""
    gaps = np.where(mask, np.asarray(reference.token_gaps(logits, chosen)), 0.0)
    return float(gaps.max())


def cached_tokens(reqs, served, i):
    """The tokens whose keys and values request ``i`` leaves in its slot:
    the prompt and every served token but the last."""
    return np.concatenate([reqs[i].prompt, served[i][:-1]])


def held_rows(ctx, engine, reqs, served, handles, seats) -> dict:
    """Request index -> (keys, values) [rows, KV, hd] of the first layer that
    the slot pool still holds for each finished request that was the last
    seated in its slot."""
    slot_of = {h: slot for slot, h in seats.items()}
    out = {}
    for i, h in handles.items():
        if i in served and h in slot_of:
            out[i] = ctx.reference.cached_kv(engine.pool.caches, 0, slot_of[h],
                                             len(cached_tokens(reqs, served, i)))
    return out


def _check_cache(ctx, reqs, served, held):
    if not held:
        ctx.check("no_cache_rows_held", 1, 0)
        return
    reference = ctx.reference
    order = sorted(held)
    toks = np.zeros((int(ctx.mix["slots"]), int(ctx.mix["max_len"])), np.int32)  # one shape
    for b, i in enumerate(order):
        seq = cached_tokens(reqs, served, i)
        toks[b, : len(seq)] = seq
    key = weights.root_key(ctx.seed)
    kw = reference.options(ctx.cell.config)
    ref = reference.first_layer_kv(key, ctx.dims, toks, **kw)
    ctx.check("kv_cache_err", kv_err(held, order, ref), ctx.mix["check"]["kv_cache_err"])
    if ctx.control:
        low = [np.asarray(a) for a in
               reference.first_layer_kv(key, ctx.dims, toks, dtype=jnp.bfloat16, **kw)]
        low_held = {i: (low[0][b, : len(held[i][0])], low[1][b, : len(held[i][0])])
                    for b, i in enumerate(order)}
        ctx.control_readings["kv_cache_err"] = kv_err(low_held, order, ref)
        ctx.control_readings["kv_rows_checked"] = sum(len(held[i][0]) for i in order)


def kv_err(held, order, ref) -> float:
    """The largest, over the requests and over keys and values, of the
    difference's norm over the reference's norm, on the request's rows."""
    ref = [np.asarray(a, np.float64) for a in ref]
    worst = 0.0
    for b, i in enumerate(order):
        for got, want in zip(held[i], ref):
            want = want[b, : len(got)]
            err = np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want)
            worst = max(worst, float(err) if np.isfinite(err) else 1e30)
    return worst


def teacher_forced(ctx, reqs, served, idx):
    """Prompt and served tokens of each sampled request, padded to the
    engine's ``max_len`` (and to the check's count of requests with empty
    rows); the positions whose logits chose each served token,
    the served tokens, and the mask of real rows."""
    max_len = int(ctx.mix["max_len"])
    width = max(len(served[i]) for i in idx)
    count = max(len(idx), int(ctx.mix["check"]["requests"]))  # one shape for every run
    toks = np.zeros((count, max_len), np.int32)
    rows = np.zeros((count, width), np.int32)
    chosen = np.zeros((count, width), np.int32)
    mask = np.zeros((count, width), bool)
    for b, i in enumerate(idx):
        p, o = reqs[i].prompt, served[i]
        seq = np.concatenate([p, o[:-1]])
        toks[b, : len(seq)] = seq
        n = len(o)
        rows[b, :n] = np.arange(len(p) - 1, len(p) - 1 + n)
        rows[b, n:] = rows[b, n - 1] if n else 0
        chosen[b, :n] = o
        mask[b, :n] = True
    return toks, rows, chosen, mask
