"""Serving engine: continuous batching with a device-resident decode loop.

Two execution paths share one model, one sampler and one RNG discipline:

**Continuous (the serving path, ``generate`` / ``serve``)** -- a host-side
FIFO scheduler (serving/scheduler.py) admits requests into live batch
*slots*; each admission prefilles the request alone (at its exact prompt
length, or right-padded to a bucket length with ``prefill_buckets=`` so a
handful of compiled prefill shapes covers every prompt) and scatters the
resulting caches into its slot (serving/cache.py).  Decode then runs **on
device** as one ``lax.while_loop`` whose carry holds the caches, per-slot
positions, sampled tokens, EOS/length state and the output buffers --
between prefill and completion there is *zero* host<->device token traffic:
the all-done predicate is a ``mapreduce`` over the active flags, and every
per-token decision (sampling, EOS masking, length caps, logprob
accumulation) happens inside the loop body.  Slots free as requests hit EOS
/ ``max_new_tokens``; the scheduler recycles them for waiting arrivals
(open-loop traffic), so the batch is continuously full instead of padded to
the slowest request.

**What the loop body does is a pluggable policy**: a
:class:`~repro.serving.strategies.DecodeStrategy` (``Engine(strategy=...)``)
owns the device state layout, the admission scatter, the loop-body step and
the drain rendering -- greedy/top-k/top-p is the trivial default
(``strategies.Vanilla``), and speculative decoding, beam search and
grammar-constrained sampling ride the same while-loop/scheduler machinery
(serving/strategies/).  The engine keeps the policy-free parts: scheduler,
prefill admission, the loop *condition* (any-active / budget /
stop-on-free), the transfer-guard dispatch seam, and stats.

**Padded (the reference oracle, ``generate_padded``)** -- the original
fixed-batch host loop: a prefill of each left-padded row, one decode
dispatch + host sync per token.  It stays as the differential oracle for the
parity suite (tests/test_serving_parity.py): same requests, same seeds =>
identical token streams.  It is a *vanilla-sampling* oracle and refuses to
run under any other strategy.

Cross-path determinism is anchored in counter-based sampling keys: the key
for request ``r``'s ``j``-th token is ``fold_in(fold_in(base, seed_r), j)``
-- a pure function of (engine seed, request seed, token index), independent
of batch composition, admission order, or which engine runs it.  Batch rows
never mix inside the model (attention/recurrence are row-local), so a
request's stream depends only on its own prompt + seed; that is what makes
continuous-vs-padded parity exact, staggered admission safe, and exact-match
speculative verification bit-identical (strategies/speculative.py).  The
sampler itself lives in serving/sampling.py (re-exported here).
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Flat
from repro.models import lm
from repro.serving import cache as CA
from repro.serving import strategies as ST
from repro.serving.sampling import (  # noqa: F401  (re-exported API)
    chosen_logprobs, request_step_keys, sample_tokens)
from repro.serving.scheduler import Scheduler
from repro.training import train_step as TS


@dataclasses.dataclass
class Request:
    prompt: list          # token ids
    max_new_tokens: int = 16
    eos_id: int = -1      # -1: never stops early
    # Per-request sampling seed; None = the engine assigns the submission
    # index.  The j-th sampled token uses fold_in(fold_in(base, seed), j),
    # so a request's stream is reproducible under any batching/scheduling.
    seed: int | None = None


def _has_global_attn(cfg) -> bool:
    kinds = tuple(cfg.prefix) + tuple(cfg.unit) + tuple(cfg.suffix)
    return any(k not in ("attn_local", "rglru", "mlstm", "slstm")
               for k in kinds)


class Engine:
    def __init__(self, cfg, mesh, params, *, cache_len: int, batch_size: int,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 top_p_candidates: int = 64, seed: int = 0,
                 max_new_cap: int | None = None, poison_on_evict: bool = False,
                 quantize_kv: str | None = None, strategy=None,
                 prefill_buckets=None):
        self.cfg = cfg
        self.mesh = mesh
        self.params = params
        self.cache_len = cache_len
        self.batch_size = batch_size
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.top_p_candidates = top_p_candidates
        self.max_new_cap = max_new_cap or cache_len
        self.poison_on_evict = poison_on_evict
        if quantize_kv == "fp8":              # spelling alias: default format
            quantize_kv = "fp8_e4m3"
        if quantize_kv is not None and quantize_kv not in alg.QUANT_MODES:
            raise ValueError(
                f"quantize_kv={quantize_kv!r} not in {alg.QUANT_MODES}")
        self.quantize_kv = quantize_kv
        self.strategy = ST.resolve_strategy(strategy)
        if cfg.is_encdec and self.strategy.name != "vanilla":
            raise NotImplementedError(
                f"strategy {self.strategy.name!r} requires the continuous "
                "decode loop; enc-dec archs route through the padded "
                "vanilla oracle only")
        self.prefill_buckets = self._resolve_buckets(prefill_buckets)
        self._base_key = jax.random.PRNGKey(seed)
        # The programs are jitted as named functions (never partials), so a
        # profiler trace names them jit_prefill, jit_admit, jit_decode_loop.
        prefill_step = (
            TS.make_prefill_step(cfg, mesh, cache_len) if mesh is not None
            else functools.partial(self._plain_prefill, cache_len=cache_len))

        def prefill(params, batch):
            return prefill_step(params, batch)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(
            TS.make_decode_step(cfg, mesh) if mesh is not None
            else lambda p, c, t, pos: lm.decode_step(p, cfg, c, t, pos))
        self._sample = functools.partial(
            sample_tokens, temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p, top_p_candidates=self.top_p_candidates)
        self.strategy.bind(self)
        self._strategy_params = self.strategy.loop_params(self)
        # The state (caches included) is donated: every caller rebinds it
        # to the result, and at full size a second copy of the caches does
        # not fit beside the weights.
        def admit(state, *args):
            return self._admit_impl(state, *args)

        def loop_program(stop_on_free):
            def decode_loop(params, sparams, state, budget):
                return self._loop_impl(params, sparams, state, budget,
                                       stop_on_free=stop_on_free)
            return jax.jit(decode_loop, donate_argnums=(2,))

        self._admit_fn = jax.jit(admit, donate_argnums=(0,))
        self._loop_fn = {stop_on_free: loop_program(stop_on_free)
                         for stop_on_free in (False, True)}
        self._host_syncs = 0
        self.last_stats: dict = {}
        self.last_scores = np.zeros((0,), np.float32)

    def _resolve_buckets(self, spec):
        """Normalize ``prefill_buckets`` to a sorted tuple (or None).

        ``"pow2"`` generates powers of two up to the cache budget; an
        explicit sequence is validated against it.  Prompts longer than the
        largest bucket fall back to exact-length prefill.
        """
        limit = self.cache_len - self.cfg.num_prefix_embeds
        if spec is None:
            return None
        if spec == "pow2":
            out, b = [], 8
            while b < limit:
                out.append(b)
                b *= 2
            out.append(limit)
            return tuple(out)
        buckets = sorted({int(b) for b in spec})
        if not buckets or buckets[0] < 1 or buckets[-1] > limit:
            raise ValueError(
                f"prefill_buckets={spec!r} must be nonempty ints in "
                f"[1, {limit}] (cache_len minus prefix embeds)")
        return tuple(buckets)

    def _plain_prefill(self, params, batch, *, cache_len):
        kwargs = {}
        if self.cfg.is_encdec:
            kwargs["src_embeds"] = batch["src_embeds"]
        if self.cfg.num_prefix_embeds:
            kwargs["vision_embeds"] = batch["vision_embeds"]
        if "valid_len" in batch:
            kwargs["valid_len"] = batch["valid_len"]
        return lm.prefill(params, self.cfg, batch["tokens"],
                          cache_len=cache_len, **kwargs)

    def _make_batch(self, toks: np.ndarray, valid_len=None) -> dict:
        cfg = self.cfg
        B, plen = toks.shape
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.is_encdec:
            batch["src_embeds"] = jnp.zeros((B, plen, cfg.d_model), jnp.float32)
        if cfg.num_prefix_embeds:
            batch["vision_embeds"] = jnp.zeros(
                (B, cfg.num_prefix_embeds, cfg.d_model), jnp.float32)
        if valid_len is not None:
            batch["valid_len"] = jnp.asarray(valid_len, jnp.int32)
        return batch

    def _pad_prompt(self, prompt):
        """Right-pad a prompt to its bucket length.  Returns (toks (1, L)
        int32, valid_len | None); None = exact-length (no bucketing, or the
        prompt exceeds the largest bucket)."""
        plen = len(prompt)
        if self.prefill_buckets:
            for b in self.prefill_buckets:
                if b >= plen:
                    toks = np.zeros((1, b), np.int32)
                    toks[0, :plen] = prompt
                    return toks, (plen if b > plen else None)
        return np.asarray(prompt, np.int32)[None, :], None

    # -----------------------------------------------------------------------
    # Continuous-batching path
    # -----------------------------------------------------------------------

    def _cache_zeros(self, batch: int):
        """Zeroed decode-cache tree for ``batch`` slots, shaped/dtyped via
        ``eval_shape`` of the prefill so slot scatters are always exact-dtype
        -- mixed-precision caches (f32 recurrent states riding bf16 KV) get
        no silent casts."""
        _, cache_shape = jax.eval_shape(
            self._prefill, self.params,
            self._make_batch(np.zeros((batch, 1), np.int32)))
        if self.quantize_kv is not None:
            # Shape-level transform: the resident tree holds KVQuant
            # (values, scales) nodes for every attention KV leaf.
            cache_shape = jax.eval_shape(
                functools.partial(CA.quantize_kv_tree, mode=self.quantize_kv),
                cache_shape)
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), cache_shape)

    def _base_state(self, *, cache_batch: int | None = None) -> dict:
        """The standard device-resident state: caches + per-slot control
        arrays.  Strategies with richer state extend (or replace) this."""
        B, T = self.batch_size, self.max_new_cap
        return {
            "caches": self._cache_zeros(cache_batch or B),
            "tok": jnp.zeros((B,), jnp.int32),
            "pos": jnp.zeros((B,), jnp.int32),
            "emitted": jnp.zeros((B,), jnp.int32),
            "active": jnp.zeros((B,), bool),
            "out": jnp.zeros((B, T), jnp.int32),
            "logps": jnp.zeros((B, T), jnp.float32),
            "seeds": jnp.zeros((B,), jnp.int32),
            "max_new": jnp.zeros((B,), jnp.int32),
            "eos": jnp.full((B,), -1, jnp.int32),
        }

    def _fresh_state(self) -> dict:
        return self.strategy.init_state(self)

    def _admit_impl(self, state, caches1, logits1, extras, slot, seed,
                    max_new, eos, pos0):
        """Admission, delegated to the strategy -- all on device; the first
        token never visits the host."""
        if self.quantize_kv is not None:
            caches1 = CA.quantize_kv_tree(caches1, mode=self.quantize_kv)
        return self.strategy.admit(
            self, state, caches1, logits1, extras, slot=slot, seed=seed,
            max_new=max_new, eos=eos, pos0=pos0)

    def _loop_impl(self, params, sparams, state, budget, *, stop_on_free):
        """The device-resident decode loop: ONE ``lax.while_loop`` dispatch.

        Runs until every live slot is done (EOS or length cap), or until
        ``budget`` steps have executed (the scheduler bounds a dispatch at
        the next arrival event), or -- with ``stop_on_free`` (waiters are
        queued) -- as soon as any slot frees.  The body is the strategy's
        ``step``; the condition stays policy-free.  Returns (state,
        steps_run).
        """
        active0 = state["active"]

        def cond(carry):
            st, t = carry
            # All-done predicate as a commutative mapreduce over the active
            # flags -- the loop predicate itself runs on the primitive layer.
            any_active = forge.mapreduce(
                lambda a: a, alg.MAX, st["active"].astype(jnp.int32),
                layout=Flat()) > 0
            go = any_active & (t < budget)
            if stop_on_free:
                go &= jnp.all(~active0 | st["active"])
            return go

        def body(carry):
            st, t = carry
            return self.strategy.step(self, params, sparams, st), t + 1

        state, steps = jax.lax.while_loop(
            cond, body, (state, jnp.zeros((), jnp.int32)))
        return state, steps

    def _dispatch_loop(self, state, budget, stop_on_free):
        """One device-loop dispatch (separate method so tests can wrap it in
        a transfer guard: nothing here may sync tokens to host)."""
        return self._loop_fn[stop_on_free](
            self.params, self._strategy_params, state,
            jnp.asarray(budget, jnp.int32))

    def _validate_request(self, r: Request):
        plen = len(r.prompt) + self.cfg.num_prefix_embeds
        if plen > self.cache_len:
            raise ValueError(
                f"prompt ({plen} tokens incl. prefix) exceeds cache_len="
                f"{self.cache_len}")
        if r.max_new_tokens > self.max_new_cap:
            raise ValueError(
                f"max_new_tokens={r.max_new_tokens} exceeds the engine's "
                f"output buffer cap {self.max_new_cap} (raise max_new_cap)")
        if _has_global_attn(self.cfg) and \
                plen + r.max_new_tokens > self.cache_len:
            raise ValueError(
                f"prompt+max_new ({plen}+{r.max_new_tokens}) exceeds "
                f"cache_len={self.cache_len} for a global-attention arch "
                f"(the KV ring would overwrite live context)")

    def serve(self, arrivals) -> list:
        """Run an open-loop arrival trace to completion.

        ``arrivals``: iterable of ``(arrival_step, Request)`` (or bare
        ``Request``s, all arriving at step 0); the step clock is the decode-
        step clock (one step = one loop iteration; a speculative iteration
        may emit several tokens) -- arrivals between device dispatches are
        admitted into whatever slots have freed.  Returns the scheduler's
        completed ``RequestState`` records in submission order (tokens,
        seq_logprob, submit/admit/finish steps).
        """
        if self.cfg.is_encdec:
            raise NotImplementedError(
                "continuous batching for enc-dec archs: cross-attention "
                "caches are source-length-shaped, which breaks uniform slot "
                "scatter -- use generate_padded()")
        pending = []
        for a in arrivals:
            step, req = a if isinstance(a, tuple) else (0, a)
            self._validate_request(req)
            pending.append((int(step), req))
        pending.sort(key=lambda a: a[0])
        pending = list(reversed(pending))   # pop() = earliest

        sched = Scheduler(self.batch_size)
        state = self._fresh_state()
        now = 0
        stats = {"loop_dispatches": 0, "decode_steps": 0, "decode_s": 0.0,
                 "admissions": 0}
        self._host_syncs = 0

        def submit_due():
            while pending and pending[-1][0] <= now:
                step, req = pending.pop()
                sched.submit(req, step=max(step, now))

        submit_due()
        while not (sched.all_done and not pending):
            # -- admission: prefill each new request alone, scatter its cache
            for rec in sched.admit(step=now):
                r = rec.request
                if r.max_new_tokens < 1:
                    sched.complete(rec.slot, step=now)
                    continue
                with jax.profiler.TraceAnnotation("admit",
                                                  request_id=rec.rid):
                    toks, vlen = self._pad_prompt(r.prompt)
                    logits1, caches1 = self._prefill(
                        self.params, self._make_batch(toks, valid_len=vlen))
                    extras = self.strategy.host_prefill(self, toks, vlen)
                    pos0 = len(r.prompt) + self.cfg.num_prefix_embeds
                    state = self._admit_fn(
                        state, caches1, logits1, extras,
                        jnp.asarray(rec.slot, jnp.int32),
                        jnp.asarray(rec.seed, jnp.int32),
                        jnp.asarray(r.max_new_tokens, jnp.int32),
                        jnp.asarray(r.eos_id, jnp.int32),
                        jnp.asarray(pos0, jnp.int32))
                stats["admissions"] += 1

            live = sched.live_slots
            if not live:
                if pending:
                    now = max(now, pending[-1][0])
                    submit_due()
                    continue
                break
            # An admitted request may be done already (EOS/cap on its first
            # token); drain before dispatching an empty loop.
            self._drain_done(sched, state, now)
            if not sched.live_slots:
                submit_due()
                continue

            # -- one device-loop dispatch: run until all-done, bounded by the
            # next arrival event; break out early on a freed slot only when
            # someone is waiting for it.
            budget = int(np.max(self._to_host(
                state["max_new"] - state["emitted"]))) + 1
            if pending:
                budget = max(1, min(budget, pending[-1][0] - now))
            stop_on_free = sched.has_waiting or bool(pending)
            t0 = time.time()
            with jax.profiler.TraceAnnotation("dispatch"):
                state, steps = self._dispatch_loop(state, budget,
                                                   stop_on_free)
                steps = int(self._to_host(steps))  # control-plane sync only
            stats["decode_s"] += time.time() - t0
            stats["loop_dispatches"] += 1
            stats["decode_steps"] += steps
            now += steps
            submit_due()
            state = self._drain_done(sched, state, now)

        recs = [sched.records[rid] for rid in sorted(sched.records)]
        n_tok = sum(len(rec.tokens) for rec in recs)
        stats["decode_tok_per_s"] = n_tok / max(stats["decode_s"], 1e-9)
        stats["seq_logprob"] = [rec.seq_logprob for rec in recs]
        stats["total_tokens"] = n_tok
        stats.update(self.strategy.stats(self, state))
        stats["host_syncs"] = self._host_syncs
        self.last_stats = stats
        self.last_scores = np.asarray(
            [rec.seq_logprob for rec in recs], np.float32)
        return recs

    def _to_host(self, x) -> np.ndarray:
        """Every device-to-host read ``serve`` makes goes through here;
        ``last_stats["host_syncs"]`` counts them."""
        self._host_syncs += 1
        return np.asarray(x)

    def _drain_done(self, sched: Scheduler, state, now):
        """Evict finished slots: pull their ragged outputs (the only token
        sync -- at completion) through the CSR compaction descriptor."""
        with jax.profiler.TraceAnnotation("drain"):
            with jax.profiler.TraceAnnotation("drain.poll"):
                done_slots = [s for s in sched.live_slots
                              if not self._to_host(state["active"][s])]
            if not done_slots:
                return state
            with jax.profiler.TraceAnnotation("drain.collect"):
                return self._collect(sched, state, now, done_slots)

    def _collect(self, sched: Scheduler, state, now, done_slots):
        outs = self.strategy.outputs(self, state)
        seq_lp = outs["seq_logprob"]
        flat, offsets = CA.compact_ragged(outs["out"], outs["emitted"])
        flat = self._to_host(flat)
        offsets = self._to_host(offsets)
        meta = outs.get("meta", {})
        for slot in done_slots:
            rec = sched.complete(slot, step=now)
            rec.tokens = [int(t) for t in flat[offsets[slot]:offsets[slot + 1]]]
            rec.seq_logprob = float(self._to_host(seq_lp[slot]))
            for key, per_slot in meta.items():
                rec.meta[key] = self._to_host(per_slot[slot]).item()
            if self.poison_on_evict:
                state = dict(state)
                state["caches"] = self.strategy.poison(
                    self, state["caches"], jnp.asarray(slot, jnp.int32))
        return state

    def generate(self, requests: list) -> list:
        """Run requests to completion (continuous batching); token lists in
        input order.  More requests than ``batch_size`` simply queue."""
        if self.cfg.is_encdec:
            return self.generate_padded(requests)
        recs = self.serve([(0, r) for r in requests])
        return [rec.tokens for rec in recs]

    # -----------------------------------------------------------------------
    # Padded-batch reference path (the vanilla parity oracle)
    # -----------------------------------------------------------------------

    def generate_padded(self, requests: list) -> list:
        """Fixed-batch reference: pad to ``batch_size``, left-align prompts,
        one decode dispatch + host sync per token.  Kept as the differential
        oracle for *vanilla sampling*; same seeds => bit-identical tokens vs
        the continuous path.  Non-vanilla strategies have their own
        reference decoders (strategies/ref.py) and refuse this path."""
        if self.strategy.name != "vanilla":
            raise NotImplementedError(
                "generate_padded is the vanilla-sampling parity oracle; "
                f"strategy {self.strategy.name!r} has its own reference "
                "decoder in serving/strategies/ref.py")
        cfg = self.cfg
        B = self.batch_size
        n_req = len(requests)
        assert n_req <= B
        seeds = np.arange(B, dtype=np.int32)
        for i, r in enumerate(requests):
            if r.seed is not None:
                seeds[i] = r.seed
        seeds = jnp.asarray(seeds)
        plen = max(len(r.prompt) for r in requests)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(requests):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
        # Each row is prefilled alone, as admission prefills a request: on
        # a TPU, XLA compiles a prefill differently for different numbers
        # of rows, and a row of a batched prefill rounds differently from
        # the same row alone.  The rows are decoded together.
        rows = [self._prefill(self.params, self._make_batch(toks[i:i + 1]))
                for i in range(B)]
        logits = jnp.concatenate([lg for lg, _ in rows])
        caches = CA.stack_slots([c for _, c in rows])
        del rows

        max_new = max(r.max_new_tokens for r in requests)
        outputs = [[] for _ in range(B)]
        done = np.zeros(B, bool)
        tok = self._sample(self._base_key, logits, seeds,
                           jnp.zeros((B,), jnp.int32))
        tok_h = np.asarray(tok).astype(np.int32)
        step_logps = [chosen_logprobs(logits, tok)]  # stays on device
        pos0 = plen + cfg.num_prefix_embeds
        t1 = time.time()
        for i, r in enumerate(requests):
            # First sampled token: subject to the same cap/EOS bookkeeping as
            # every later token (a 0-budget request emits nothing, and EOS as
            # the first token finishes the request).
            if r.max_new_tokens >= 1:
                outputs[i].append(int(tok_h[i]))
            if len(outputs[i]) >= r.max_new_tokens or \
                    (outputs[i] and outputs[i][-1] == r.eos_id):
                done[i] = True
        for t in range(1, max_new):
            if done[:n_req].all():
                break
            logits, caches = self._decode(
                self.params, caches, jnp.asarray(tok_h[:, None]),
                jnp.asarray(pos0 + t - 1, jnp.int32))
            tok = self._sample(self._base_key, logits, seeds,
                               jnp.full((B,), t, jnp.int32))
            tok_h = np.asarray(tok).astype(np.int32)
            step_logps.append(chosen_logprobs(logits, tok))
            for i, r in enumerate(requests):
                if not done[i] and len(outputs[i]) < r.max_new_tokens:
                    outputs[i].append(int(tok_h[i]))
                    if outputs[i][-1] == r.eos_id or \
                            len(outputs[i]) >= r.max_new_tokens:
                        done[i] = True
        decode_s = time.time() - t1
        n_tok = sum(len(o) for o in outputs[:n_req])

        # Sequence scores over the ragged batch: one batched-mapreduce row
        # per request, masked to its realized length -- a single launch over
        # (n_req, steps) with no per-request host loop or flatten, and the
        # identical code path whether n_req is 1 or the full batch.
        lengths = [len(o) for o in outputs[:n_req]]
        lp = jnp.stack(step_logps, axis=1)[:n_req]      # (n_req, steps)
        from repro.serving.sampling import masked_seq_logprobs
        seq_logprob = masked_seq_logprobs(
            lp.astype(jnp.float32), jnp.asarray(lengths, jnp.int32))
        self.last_scores = np.asarray(seq_logprob)

        self.last_stats = {
            "decode_s": decode_s,
            "decode_tok_per_s": n_tok / max(decode_s, 1e-9),
            "seq_logprob": self.last_scores.tolist(),
        }
        return outputs[:n_req]
