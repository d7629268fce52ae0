"""Continuous-batching serving engine: a request-level API over one jit'd step.

``submit(Request) -> handle`` queues work; the scheduler seats requests into
a fixed slot pool (`serving.cache.SlotPool`) as slots free up.  ``step()``
advances every admitted sequence one iteration:

  * chunked prefill and decode interleave in the SAME forward — one
    ``lm.decode_step`` call where prefilling slots carry up to
    ``prefill_chunk`` prompt tokens and decoding slots carry their one
    pending token, masked per slot by length + validity;
  * then a ``lax.while_loop`` decode body samples ON DEVICE (greedy /
    temperature / top-k, per-slot knobs) for up to ``decode_block`` tokens,
    writing into a device token buffer — no per-token host round-trip;
  * the host syncs exactly once per step (``jax.device_get`` of the token
    buffer), asserted by ``stats["host_syncs"] == stats["steps"]``.

Each step records host spans in the profiler's trace (``repro.serve.step``
around ``repro.serve.admit``, ``.prep``, ``.dispatch``, ``.fetch`` and
``.post``; ``.post`` carries the step's counts as arguments, and ``.step``
``kv_rows_read`` beside ``kv_rows_full``, the cache rows its decode passes
read against every row), and the jitted step names its device work
(``mixed_pass``, ``decode_pass``, ``sample``).
``stats`` sums the same counts over steps, for a reader without a profiler.

``poll(handle)`` reads a request's progress, ``step()``'s return value is
the streaming surface ({handle: new tokens}), and ``drain()`` runs steps to
completion.  ``generate(prompts, max_new_tokens)`` keeps the legacy
padded-batch convenience surface on top.

Sampling is reproducible per request: each slot's key is
``fold_in(PRNGKey(request.seed), n_sampled)``, so results don't depend on
which other requests share the batch or on step boundaries.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.nn import attention
from repro.serving.cache import SlotPool
from repro.serving.scheduler import Request, Scheduler

__all__ = ["ServeEngine", "Request"]

_TOPK_MAX = 64  # static width of the top-k threshold lattice (clamped to V)


def _span(name):
    """A host span in the profiler's trace (nearly free when none runs)."""
    return jax.profiler.TraceAnnotation(f"repro.serve.{name}")


@jax.named_scope("sample")
def _sample(logits, temp, topk, keys):
    """Per-slot on-device sampling. logits [S, V] f32; temp/topk/keys [S...]."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    kmax = min(logits.shape[-1], _TOPK_MAX)
    vals = jax.lax.top_k(logits, kmax)[0]  # [S, kmax] sorted desc
    kidx = jnp.clip(topk - 1, 0, kmax - 1)
    thresh = jnp.take_along_axis(vals, kidx[:, None], axis=-1)
    masked = jnp.where((topk > 0)[:, None] & (logits < thresh), -jnp.inf, logits)
    scaled = masked / jnp.maximum(temp, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temp > 0, sampled.astype(jnp.int32), greedy)


@dataclasses.dataclass
class ServeEngine:
    """Request-level continuous-batching engine over ``lm.decode_step``."""

    cfg: object
    pc: object
    params: object
    max_len: int = 512
    temperature: float = 0.0  # default for the generate() convenience path
    n_slots: int = 8
    prefill_chunk: int = 16
    decode_block: int = 32
    cache_dtype: object = None

    def __post_init__(self):
        cfg, pc = self.cfg, self.pc
        if self.cache_dtype is None:
            self.cache_dtype = self.params["embed"].dtype
        # ring-buffer (sliding window) layers cap the prefill chunk: a chunk
        # wider than the ring would overwrite rows its own queries still need
        rings = [min(self.max_len, d.window)
                 for d in _all_layer_defs(cfg) if d.window is not None]
        self.prefill_chunk = max(1, min([self.prefill_chunk] + rings))
        self.scheduler = Scheduler(self.n_slots)
        self.pool = SlotPool(cfg, pc, self.n_slots, self.max_len,
                             self.cache_dtype)
        # sums over steps; "passes" counts the decode passes of the loop,
        # "slots_busy" and "queued" are slot-steps and request-steps;
        # "kv_rows_read" the rows of the attention layers' caches that the
        # decode passes read, summed over layers (in spans where attention
        # reads only live rows), "kv_rows_full" what reading every row would be
        self.stats = {"steps": 0, "host_syncs": 0, "step_traces": 0,
                      "admitted": 0, "passes": 0, "prefill_tokens": 0,
                      "decode_tokens": 0, "slots_busy": 0, "queued": 0,
                      "kv_rows_read": 0, "kv_rows_full": 0}
        # attention layers by (cache rows, window, span rows or None)
        prefix, unit, n_units, suffix = lm.layer_plan(cfg)
        self._kv_layers = collections.Counter()
        for d in prefix + unit * n_units + suffix:
            if d.kind != "mamba":
                size = self.max_len if d.window is None else min(self.max_len, d.window)
                self._kv_layers[size, d.window, attention.decode_read_span(
                    cfg, pc.tp, size, d.window, self.cache_dtype)] += 1
        donate = () if jax.default_backend() == "cpu" else (1,)
        self._step_fn = jax.jit(self._build_step(), donate_argnums=donate)
        self.decode_channels = self._warm_decode_channels() if pc.tune else {}

    # ------------------------------------------------------------------ jit'd
    def _build_step(self):
        cfg, pc = self.cfg, self.pc
        dmax = self.decode_block

        def step_fn(params, caches, lens, tokens, valid, active, budget,
                    eos, temp, topk, seeds, n_sampled, n_decode):
            self.stats["step_traces"] += 1
            n = tokens.shape[0]
            # mixed forward: prefill chunks + pending decode tokens together
            with jax.named_scope("mixed_pass"):
                logits, caches = lm.decode_step(params, caches, cfg, pc, tokens,
                                                lens, q_valid=valid)
            lens = lens + valid
            idx = jnp.clip(valid - 1, 0, tokens.shape[1] - 1)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0].astype(jnp.float32)
            keys = jax.vmap(jax.random.PRNGKey)(seeds)
            sub = jax.vmap(jax.random.fold_in)(keys, n_sampled)
            tok0 = _sample(last, temp, topk, sub)
            alive = active & (budget > 0)
            n_sampled = n_sampled + alive.astype(jnp.int32)
            buf = jnp.full((n, dmax), -1, jnp.int32)
            buf = buf.at[:, 0].set(jnp.where(alive, tok0, -1))
            emitted = alive.astype(jnp.int32)
            alive = alive & (tok0 != eos) & (budget > 1)

            def cond(st):
                return (st[0] < n_decode) & jnp.any(st[4])

            @jax.named_scope("decode_pass")
            def body(st):
                t, caches_, lens_, tok, alive_, buf_, em_, ns_ = st
                lg, caches_ = lm.decode_step(
                    params, caches_, cfg, pc, tok[:, None], lens_,
                    q_valid=alive_.astype(jnp.int32))
                lens_ = lens_ + alive_.astype(jnp.int32)
                sub_ = jax.vmap(jax.random.fold_in)(keys, ns_)
                nt = _sample(lg[:, 0].astype(jnp.float32), temp, topk, sub_)
                ns_ = ns_ + alive_.astype(jnp.int32)
                buf_ = buf_.at[:, t].set(jnp.where(alive_, nt, -1),
                                         mode="drop")
                em_ = em_ + alive_.astype(jnp.int32)
                alive_ = alive_ & (nt != eos) & (em_ < budget)
                return (t + 1, caches_, lens_, nt, alive_, buf_, em_, ns_)

            st = (jnp.int32(1), caches, lens, tok0, alive, buf, emitted,
                  n_sampled)
            st = jax.lax.while_loop(cond, body, st)
            # buffer column 0 is the mixed pass's token: t - 1 decode passes ran
            return st[1], st[5], st[6], st[0] - 1

        return step_fn

    # ------------------------------------------------------------------ host
    def submit(self, req: Request) -> int:
        """Queue a request; returns a handle for poll()/drain()."""
        n_prompt = int(np.asarray(req.tokens).reshape(-1).shape[0])
        if n_prompt == 0:
            raise ValueError("empty prompt")
        if n_prompt + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({n_prompt}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds the engine max_len ({self.max_len})")
        return self.scheduler.submit(req)

    def _admit(self) -> None:
        with _span("admit") as span:
            seated = self.scheduler.admit()
            for slot in seated:
                self.pool.reset(slot)
            self.stats["admitted"] += len(seated)
            span.set_metadata(admitted=len(seated))

    def _fetch(self, tree):
        with _span("fetch"):
            self.stats["host_syncs"] += 1
            return jax.device_get(tree)

    def step(self) -> Dict[int, List[int]]:
        """Advance every admitted sequence one iteration.

        Returns {handle: tokens emitted this step} — the streaming surface.
        Exactly one host sync regardless of how many tokens were decoded.
        """
        with _span("step") as step_span:
            self._admit()
            sch = self.scheduler
            if not any(r is not None for r in sch.slots):
                return {}
            with _span("prep"):
                inputs, valid, prefill = self._prepare()
            with _span("dispatch"):
                out = self._step_fn(self.params, self.pool.caches,
                                    *(jnp.asarray(a) for a in inputs))
                self.pool.caches = out[0]
            buf, emitted, passes = self._fetch(out[1:])
            self.stats["steps"] += 1
            with _span("post") as span:
                counts = {"passes": int(passes), "prefill_tokens": prefill,
                          "decode_tokens": int(emitted.sum()),
                          "slots_busy": sum(r is not None for r in sch.slots)}
                kv_rows = self._kv_rows(inputs[0] + valid, emitted, int(passes))
                results = self._post(buf, emitted, valid)
                counts["queued"] = len(sch.queue)
                for k, v in {**counts, **kv_rows}.items():
                    self.stats[k] += v
                span.set_metadata(**counts)
            step_span.set_metadata(**kv_rows)
            return results

    def _prepare(self):
        """The step's host inputs (after params and caches), each slot's fed
        rows, and the prompt tokens fed."""
        sch = self.scheduler
        n, c = self.n_slots, self.prefill_chunk
        tokens = np.zeros((n, c), np.int32)
        valid = np.zeros((n,), np.int32)
        active = np.zeros((n,), bool)
        budget = np.zeros((n,), np.int32)
        eos = np.full((n,), -1, np.int32)
        temp = np.zeros((n,), np.float32)
        topk = np.zeros((n,), np.int32)
        seeds = np.zeros((n,), np.int32)
        nsamp = np.zeros((n,), np.int32)
        lens = np.zeros((n,), np.int32)
        prefill = 0
        for i, st in sch.active():
            req = st.request
            lens[i] = st.cache_len
            budget[i] = st.remaining
            eos[i] = -1 if req.eos_id is None else req.eos_id
            temp[i] = req.temperature
            topk[i] = req.top_k
            seeds[i] = req.seed
            nsamp[i] = len(st.generated)
            if st.pos < len(st.prompt):
                take = min(c, len(st.prompt) - st.pos)
                tokens[i, :take] = st.prompt[st.pos:st.pos + take]
                valid[i] = take
                prefill += take
                st.pos += take
                active[i] = st.pos == len(st.prompt)
            else:
                tokens[i, 0] = st.pending
                valid[i] = 1
                active[i] = True
        n_decode = int(min(self.decode_block,
                           max([0] + [int(budget[i]) for i, _ in sch.active()
                                      if active[i]])))
        inputs = (lens, tokens, valid, active, budget, eos, temp, topk, seeds,
                  nsamp, np.int32(n_decode))
        return inputs, valid, prefill

    def _kv_rows(self, lens, emitted, passes) -> Dict[str, int]:
        """Cache rows the step's decode passes read, summed over attention
        layers: a slot that emitted ``e`` tokens decoded in passes 1 to
        e - 1, in pass p with the ``lens + p - 1`` rows before its own in the
        cache; ``lens`` are the rows after the mixed pass.  A layer that reads
        only live rows reads, of each such slot, the spans that hold its rows
        in the window; any other reads every row of every slot."""
        before = np.concatenate([lens[i] + np.arange(e - 1)
                                 for i, e in enumerate(emitted)] + [np.zeros(0, int)])
        read = full = 0
        for (size, window, t), layers in self._kv_layers.items():
            full += layers * passes * self.n_slots * size
            if t is None:
                read += layers * passes * self.n_slots * size
                continue
            lo = 0 if window is None else np.maximum(before - window + 1, 0)
            read += layers * int(np.maximum(-(-before // t) - lo // t, 0).sum()) * t
        return {"kv_rows_read": read, "kv_rows_full": full}

    def _post(self, buf, emitted, valid) -> Dict[int, List[int]]:
        """Hand the fetched tokens to their requests; release finished slots."""
        sch = self.scheduler
        results: Dict[int, List[int]] = {}
        finished = []
        for i, st in sch.active():
            e = int(emitted[i])
            st.cache_len += int(valid[i]) + max(0, e - 1)
            if e:
                toks = buf[i, :e].tolist()
                st.generated.extend(toks)
                results[st.rid] = toks
                hit_eos = (st.request.eos_id is not None
                           and toks[-1] == st.request.eos_id)
                if hit_eos or st.remaining <= 0:
                    st.done = True
                    finished.append(i)
        for i in finished:
            sch.release(i)
        return results

    def poll(self, handle: int) -> Dict[str, object]:
        """Progress of one request: done flag, tokens so far, queue state."""
        st = self.scheduler.states[handle]
        return {"done": st.done, "tokens": list(st.generated),
                "queued": st.slot is None and not st.done}

    def drain(self, handles=None, max_steps: int = 100_000):
        """Run step() until the given (default: all) requests finish."""
        if handles is None:
            handles = list(self.scheduler.states)
        for _ in range(max_steps):
            if all(self.scheduler.states[h].done for h in handles):
                break
            if not self.scheduler.has_work:
                break
            self.step()
        return {h: np.asarray(self.scheduler.states[h].generated, np.int32)
                for h in handles}

    def generate(self, prompts: np.ndarray, max_new_tokens: int = 32,
                 seed: int = 0) -> np.ndarray:
        """Legacy convenience surface: prompts [B, S0] (already padded, pads
        attend as real tokens exactly like the old fixed-batch engine);
        returns [B, S0 + max_new_tokens] with exactly ``max_new_tokens`` new
        tokens per row."""
        prompts = np.asarray(prompts, np.int32)
        _, s0 = prompts.shape
        if s0 + max_new_tokens > self.max_len:
            raise ValueError("prompt + max_new_tokens exceeds max_len")
        handles = [
            self.submit(Request(tokens=row, max_new_tokens=max_new_tokens,
                                temperature=self.temperature, seed=seed + i))
            for i, row in enumerate(prompts)
        ]
        outs = self.drain(handles)
        gen = np.stack([outs[h] for h in handles])
        return np.concatenate([prompts, gen], axis=1)

    # ------------------------------------------------------- decode tuning
    def _warm_decode_channels(self):
        """Resolve decode-shape joint winners for this engine's TP GEMMs.

        Decode GEMMs (M == n_slots rows, 1 token) live in a different corner
        of the joint space than prefill shapes; ``signature(..., decode=True)``
        keys them separately so the cache holds both winners side by side.
        """
        from repro import tune
        from repro.nn.attention import _lay

        cfg, pc = self.cfg, self.pc
        lay = _lay(cfg, pc.tp)
        hd, d = cfg.hd, cfg.d_model
        s = self.n_slots
        gemms = {
            "qkv": ("ag_matmul",
                    ((s, 1, d), (d, (lay.h_loc + 2 * lay.kv_loc) * hd))),
            "attn_out": ("matmul_rs",
                         ((s, 1, lay.h_loc * hd), (lay.h_loc * hd, d))),
        }
        if cfg.d_ff:
            f_loc = max(1, cfg.d_ff // pc.tp)
            gemms["ffn_gu"] = ("ag_matmul", ((s, 1, d), (d, 2 * f_loc)))
            gemms["ffn_down"] = ("matmul_rs", ((s, 1, f_loc), (f_loc, d)))
        return {
            name: tune.resolve_channel(
                kind, sig=tune.signature(kind, shapes, decode=True),
                mesh=pc.mesh, axis=pc.axis, ranker=pc.tune_ranker,
                space=tune.JOINT_SPACE)
            for name, (kind, shapes) in gemms.items()
        }


def _all_layer_defs(cfg):
    prefix, unit, n_units, suffix = lm.layer_plan(cfg)
    return list(prefix) + (list(unit) if n_units else []) + list(suffix)
