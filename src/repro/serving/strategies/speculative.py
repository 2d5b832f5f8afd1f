"""Draft-and-verify speculative decoding with an exact-match acceptance rule.

One loop-body iteration (a *round*) runs a combined draft+verify
``lax.scan`` of ``k + 1`` steps.  At step ``s`` both models decode the same
input token ``x_s`` (``x_0`` = the slot's current token, ``x_{s+1}`` = the
draft's proposal ``d_s``):

* the **target** samples its authoritative token ``t_s`` with the engine's
  *untagged* counter key at token index ``emitted + s`` -- exactly the key
  vanilla decoding would use for that token, which is what makes the
  accepted stream bit-identical to vanilla at the same seeds (the lossless
  acceptance rule: a draft token is accepted iff it *equals* the target's
  sample, so the emitted stream is the target's own sample path, always);
* the **draft** samples its proposal ``d_s`` from the
  :data:`~repro.serving.sampling.DRAFT_STREAM`-tagged key at the same
  index, so draft randomness never collides with (or perturbs) the verify
  stream and is batch-composition- and depth-independent (satellite S2).

Acceptance is resolved *after* the scan as a batched exclusive ``scan`` over
the per-step match flags: token ``t_i`` is valid iff every earlier step
matched (its context was correct) and no earlier valid token was EOS --
``prefix_ok = (exclusive +scan of failures) == 0``.  Each round therefore
emits between 1 (immediate mismatch: the target's own ``t_0`` is always
correct) and ``k + 1`` tokens per active slot.

**Cache rollback** is per-step select-commit: inside the scan, both models'
caches advance only where the acceptance chain is still alive
(:func:`repro.serving.cache.select_slots` over the slot axis), so a slot
whose chain broke at step ``s`` keeps the cache state of its last valid
token -- no post-hoc rewind of ring-buffer writes is needed, and the scheme
is valid for *every* architecture (including O(1) recurrent states, which
cannot be rewound).  The only over-commit happens on EOS/length-cap paths,
which provably end with the slot inactive; a recycled slot is fully
re-scattered at admission, so the stale suffix is unreachable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Batched
from repro.models import lm
from repro.serving import cache as CA
from repro.serving import sampling as SP
from repro.serving.strategies.base import DecodeStrategy, vanilla_admit


class Speculative(DecodeStrategy):
    """Draft-and-verify speculative decoding (``k`` proposals per round).

    ``draft_cfg``/``draft_params`` are a (smaller) model sharing the
    target's vocabulary; its caches ride the same slot machinery in a
    parallel tree.  Output streams are bit-identical to ``Vanilla`` at the
    same seeds -- speculation only changes *how many* target-forward
    launches the stream costs, never its tokens.
    """

    name = "speculative"

    def __init__(self, draft_cfg, draft_params, *, k: int = 4):
        if k < 1:
            raise ValueError(f"speculative k must be >= 1, got {k}")
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.k = k

    def bind(self, eng):
        if self.draft_cfg.vocab_size != eng.cfg.vocab_size:
            raise ValueError(
                f"draft vocab_size {self.draft_cfg.vocab_size} != target "
                f"vocab_size {eng.cfg.vocab_size}: draft proposals must be "
                "target token ids")
        if self.draft_cfg.is_encdec:
            raise ValueError("draft model must be decoder-only")
        if self.draft_cfg.num_prefix_embeds or eng.cfg.num_prefix_embeds:
            raise ValueError(
                "speculative decoding requires num_prefix_embeds == 0 on "
                "both models (position bookkeeping is shared)")

        def dft_prefill(params, batch):
            kwargs = {}
            if "valid_len" in batch:
                kwargs["valid_len"] = batch["valid_len"]
            return lm.prefill(params, self.draft_cfg, batch["tokens"],
                              cache_len=eng.cache_len, **kwargs)

        self._dft_prefill = jax.jit(dft_prefill)
        self._dft_decode = functools.partial(
            lambda cfg, p, c, t, pos: lm.decode_step(p, cfg, c, t, pos),
            self.draft_cfg)

    def loop_params(self, eng):
        return self.draft_params

    def host_prefill(self, eng, toks, valid_len):
        batch = {"tokens": jnp.asarray(toks)}
        if valid_len is not None:
            batch["valid_len"] = jnp.asarray(valid_len, jnp.int32)
        _, dft_caches1 = self._dft_prefill(self.draft_params, batch)
        return dft_caches1

    def stats(self, eng, state) -> dict:
        prop = int(eng._to_host(state["tot_prop"]))
        acc = int(eng._to_host(state["tot_acc"]))
        return {
            "spec_rounds": int(eng._to_host(state["tot_rounds"])),
            "spec_proposed": prop,
            "spec_accepted": acc,
            "spec_acceptance_rate": acc / max(prop, 1),
        }

    def init_state(self, eng) -> dict:
        st = eng._base_state()
        B = eng.batch_size
        _, dft_shape = jax.eval_shape(
            self._dft_prefill, self.draft_params,
            {"tokens": np.zeros((B, 1), np.int32)})
        st["dft_caches"] = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), dft_shape)
        # Per-slot round accounting (reset at admission, drained into the
        # record's meta) + engine-lifetime totals (read once in stats()).
        for key in ("acc", "prop", "rounds"):
            st[key] = jnp.zeros((B,), jnp.int32)
        for key in ("tot_acc", "tot_prop", "tot_rounds"):
            st[key] = jnp.zeros((), jnp.int32)
        return st

    def admit(self, eng, state, caches1, logits1, extras, *, slot, seed,
              max_new, eos, pos0):
        st = vanilla_admit(eng, state, caches1, logits1, slot=slot,
                           seed=seed, max_new=max_new, eos=eos, pos0=pos0)
        st["dft_caches"] = CA.scatter_slot(state["dft_caches"], extras, slot)
        for key in ("acc", "prop", "rounds"):
            st[key] = st[key].at[slot].set(0)
        return st

    def step(self, eng, params, sparams, st):
        B, S, T = eng.batch_size, self.k + 1, eng.max_new_cap
        was_active = st["active"]
        e0 = st["emitted"]
        dkey = SP.stream_key(eng._base_key, SP.DRAFT_STREAM)

        def substep(carry, s):
            tgt_c, dft_c, x, pos, accepting = carry
            logits_t, tgt_c2 = eng._decode(params, tgt_c, x[:, None], pos)
            logits_d, dft_c2 = self._dft_decode(sparams, dft_c, x[:, None],
                                                pos)
            t = eng._sample(eng._base_key, logits_t, st["seeds"], e0 + s)
            lp = SP.chosen_logprobs(logits_t, t)
            d = eng._sample(dkey, logits_d, st["seeds"], e0 + s)
            # Commit the step's cache writes only where the acceptance
            # chain is still alive -- this IS the rollback.
            commit = accepting & was_active
            tgt_c = CA.select_slots(commit, tgt_c2, tgt_c)
            dft_c = CA.select_slots(commit, dft_c2, dft_c)
            return ((tgt_c, dft_c, d, pos + commit, accepting & (t == d)),
                    (t, lp, t == d))

        carry0 = (st["caches"], st["dft_caches"], st["tok"], st["pos"],
                  jnp.ones((B,), bool))
        (tgt_c, dft_c, _, pos2, _), (ts, lps, ms) = jax.lax.scan(
            substep, carry0, jnp.arange(S, dtype=jnp.int32))
        ts, lps, ms = ts.T, lps.T, ms.T                     # (B, S)

        # Validity: t_i is authoritative iff every earlier step matched AND
        # no earlier valid token was EOS -- the batched exclusive scan over
        # acceptance flags.  t_0 is always valid: prefix failures are 0.
        fail = (~(ms & (ts != st["eos"][:, None]))).astype(jnp.int32)
        prefix_ok = forge.scan(alg.ADD, fail, inclusive=False,
                               layout=Batched()) == 0
        idx = jnp.arange(S, dtype=jnp.int32)[None, :]
        rem = (st["max_new"] - e0)[:, None]
        emit = prefix_ok & (idx < rem) & was_active[:, None]
        n_emit = emit.sum(axis=1).astype(jnp.int32)

        # Deterministic ragged append into the (B, T) output buffers: a
        # where-based gather (never a scatter -- duplicate-index scatter
        # conflicts would be nondeterministic at the clipped tail).
        rel = jnp.arange(T, dtype=jnp.int32)[None, :] - e0[:, None]
        take = (rel >= 0) & (rel < n_emit[:, None])
        src = jnp.clip(rel, 0, S - 1)
        out = jnp.where(take, jnp.take_along_axis(ts, src, axis=1),
                        st["out"])
        logps = jnp.where(take, jnp.take_along_axis(lps, src, axis=1),
                          st["logps"])

        emitted = e0 + n_emit
        hit_eos = (emit & (ts == st["eos"][:, None])).any(axis=1)
        hit_cap = emitted >= st["max_new"]
        last = jnp.take_along_axis(
            ts, jnp.clip(n_emit - 1, 0, S - 1)[:, None], axis=1)[:, 0]

        accepted = jnp.where(was_active, n_emit - 1, 0)
        act = was_active.astype(jnp.int32)
        new = dict(st)
        new["caches"] = tgt_c
        new["dft_caches"] = dft_c
        new["tok"] = jnp.where(was_active, last, st["tok"])
        new["pos"] = pos2
        new["emitted"] = emitted
        new["active"] = was_active & ~hit_eos & ~hit_cap
        new["out"] = out
        new["logps"] = logps
        new["acc"] = st["acc"] + accepted
        new["prop"] = st["prop"] + self.k * act
        new["rounds"] = st["rounds"] + act
        new["tot_acc"] = st["tot_acc"] + accepted.sum()
        new["tot_prop"] = st["tot_prop"] + self.k * act.sum()
        new["tot_rounds"] = st["tot_rounds"] + act.sum()
        return new

    def outputs(self, eng, state):
        return {
            "out": state["out"], "emitted": state["emitted"],
            "seq_logprob": SP.masked_seq_logprobs(
                state["logps"], state["emitted"]),
            "meta": {"spec_accepted": state["acc"],
                     "spec_proposed": state["prop"],
                     "spec_rounds": state["rounds"]},
        }
