"""The decoding-strategy interface and the trivial default strategy.

A :class:`DecodeStrategy` owns everything about the serving engine's state
that is *decoding-policy* shaped: what extra device state rides the
``lax.while_loop`` carry, what happens at admission, what one loop-body
iteration does (sampling, EOS, logprob bookkeeping), and how the finished
per-slot state renders into ragged token streams at drain.  The engine
(serving/engine.py) keeps everything policy-free: the scheduler, the
prefill admission path, the loop *condition* (any-active / budget /
stop-on-free), the transfer-guard dispatch seam, and stats.

Every hook that runs on device (``admit``, ``step``, ``outputs``) is traced
inside the engine's jitted programs, so strategies must stay functional and
sync-free -- the transfer-guard test in tests/test_serving.py holds for
every strategy, not just the default.

``Vanilla`` is the engine's historical greedy/top-k/top-p behavior moved
behind the interface verbatim: the parity suite (continuous vs padded,
staggered admission, slot recycling) pins that the refactor is
bit-identical.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.serving import cache as CA
from repro.serving import sampling as SP


class DecodeStrategy:
    """Pluggable decoding policy for the continuous-batching engine.

    Subclasses override the hooks below; ``bind`` is called once from
    ``Engine.__init__`` (validate the config, build any extra jits), the
    device hooks are traced into the engine's admit/loop programs.
    """

    name = "?"

    # -- host-side, once per engine -----------------------------------------

    def bind(self, eng) -> None:
        """Validate the engine config and set up strategy-owned resources."""

    def loop_params(self, eng):
        """Extra parameter pytree threaded into the jitted loop/admit
        programs (e.g. the draft model's params).  Must be a pytree of
        device arrays; () when the strategy needs none."""
        return ()

    def host_prefill(self, eng, toks, valid_len):
        """Host-side extra prefill work at admission (e.g. the draft
        model's prefill).  Returns a pytree of device arrays handed to
        ``admit`` as ``extras``."""
        return ()

    def stats(self, eng, state) -> dict:
        """Strategy-specific entries merged into ``engine.last_stats`` at
        the end of ``serve`` (host sync is fine here: serving is done;
        read through ``eng._to_host`` so that ``host_syncs`` counts it)."""
        return {}

    # -- device-side, traced -------------------------------------------------

    def init_state(self, eng) -> dict:
        """The full device-resident state dict (the while-loop carry).

        Required keys the engine reads: ``active`` (B,) bool, ``emitted``
        and ``max_new`` (B,) int32 (budget bookkeeping), ``caches`` (slot
        eviction poisoning).  Everything else is strategy-owned.
        """
        return eng._base_state()

    def admit(self, eng, state, caches1, logits1, extras, *, slot, seed,
              max_new, eos, pos0) -> dict:
        raise NotImplementedError

    def step(self, eng, params, sparams, st) -> dict:
        """One while-loop body iteration.  Must keep ``active`` honest:
        the engine's loop condition and drain both read it."""
        raise NotImplementedError

    def outputs(self, eng, state) -> dict:
        """Render finished state for drain: ``{"out": (B, T) int32,
        "emitted": (B,) int32, "seq_logprob": (B,) float32}`` plus an
        optional ``"meta"`` dict of per-slot (B,) arrays copied onto each
        completed record's ``meta``."""
        raise NotImplementedError

    def poison(self, eng, caches, slot):
        """Poison a freed slot's cache state (``poison_on_evict``)."""
        return CA.poison_slot(caches, slot)


def vanilla_admit(eng, state, caches1, logits1, *, slot, seed, max_new, eos,
                  pos0):
    """Scatter a prefilled request into ``slot`` + sample its first token
    -- all on device; the token never visits the host.  Shared by every
    strategy that keeps the vanilla one-token-per-slot state layout."""
    T = eng.max_new_cap
    tok1 = eng._sample(eng._base_key, logits1, seed[None],
                       jnp.zeros((1,), jnp.int32))[0]
    lp1 = SP.chosen_logprobs(logits1, tok1[None])[0]
    st = dict(state)
    st["caches"] = CA.scatter_slot(state["caches"], caches1, slot)
    st["tok"] = state["tok"].at[slot].set(tok1)
    st["pos"] = state["pos"].at[slot].set(pos0)
    st["emitted"] = state["emitted"].at[slot].set(1)
    st["active"] = state["active"].at[slot].set(
        (tok1 != eos) & (max_new > 1))
    st["out"] = state["out"].at[slot].set(
        jnp.zeros((T,), jnp.int32).at[0].set(tok1))
    st["logps"] = state["logps"].at[slot].set(
        jnp.zeros((T,), jnp.float32).at[0].set(lp1))
    st["seeds"] = state["seeds"].at[slot].set(seed)
    st["max_new"] = state["max_new"].at[slot].set(max_new)
    st["eos"] = state["eos"].at[slot].set(eos)
    return st


class Vanilla(DecodeStrategy):
    """Greedy / top-k / top-p sampling -- the engine's default policy.

    One target decode + one sampled token per loop iteration, per-slot
    EOS/length-cap masking, logprob accumulation into the (B, T) buffer;
    exactly the pre-strategy engine behavior (the parity suite pins it).
    """

    name = "vanilla"

    def admit(self, eng, state, caches1, logits1, extras, *, slot, seed,
              max_new, eos, pos0):
        return vanilla_admit(eng, state, caches1, logits1, slot=slot,
                             seed=seed, max_new=max_new, eos=eos, pos0=pos0)

    def _adjust_logits(self, eng, st, logits):
        """Hook: transform the step's logits before sampling (identity
        here; constrained sampling masks the disallowed vocabulary)."""
        return logits

    def _post_step(self, eng, st, new, nxt, was_active):
        """Hook: extend the committed state after the vanilla bookkeeping
        (identity here; constrained sampling advances its DFA state)."""
        return new

    def step(self, eng, params, sparams, st):
        bidx = jnp.arange(eng.batch_size)
        was_active = st["active"]
        logits, caches = eng._decode(
            params, st["caches"], st["tok"][:, None], st["pos"])
        logits = self._adjust_logits(eng, st, logits)
        nxt = eng._sample(eng._base_key, logits, st["seeds"], st["emitted"])
        lp = SP.chosen_logprobs(logits, nxt)
        widx = jnp.minimum(st["emitted"], eng.max_new_cap - 1)
        out = st["out"].at[bidx, widx].set(
            jnp.where(was_active, nxt, st["out"][bidx, widx]))
        logps = st["logps"].at[bidx, widx].set(
            jnp.where(was_active, lp, st["logps"][bidx, widx]))
        emitted = st["emitted"] + was_active
        hit_eos = was_active & (nxt == st["eos"])
        hit_cap = emitted >= st["max_new"]
        new = dict(st)
        new["caches"] = caches
        new["tok"] = jnp.where(was_active, nxt, st["tok"])
        new["pos"] = st["pos"] + was_active
        new["emitted"] = emitted
        new["active"] = was_active & ~hit_eos & ~hit_cap
        new["out"] = out
        new["logps"] = logps
        return self._post_step(eng, st, new, nxt, was_active)

    def outputs(self, eng, state):
        return {"out": state["out"], "emitted": state["emitted"],
                "seq_logprob": SP.masked_seq_logprobs(
                    state["logps"], state["emitted"])}
