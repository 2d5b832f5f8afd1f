"""Counter-based sampling shared by the serving engine and every decoding
strategy (serving/strategies/).

The key discipline is the serving stack's determinism anchor: the key for
request ``r``'s ``j``-th token is ``fold_in(fold_in(base, seed_r), j)`` -- a
pure function of (engine seed, request seed, token index), independent of
batch composition, admission order, or which engine runs it.  Decoding
strategies that need *additional* random streams (the draft proposals of
speculative decoding) derive them by folding a per-stream tag into the base
key first (:func:`stream_key`), so the extra stream inherits the same
composition-independence without ever colliding with the verify stream.

``sample_tokens`` routes temperature>0 sampling through the primitive
substrate: ``top_k(layout=Segmented(...))`` over the flat per-request vocab
stream plus a ``scan(layout=Batched())`` nucleus cutoff over the (B, k)
candidate grid; see its docstring for the pinned nucleus semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Batched, Segmented

# Per-stream tags folded into the engine base key (:func:`stream_key`).
# The verify/vanilla stream uses the *untagged* base key -- that identity is
# load-bearing: exact-match speculative verification samples the target's
# authoritative token with the untagged key, which is why its stream is
# bit-identical to vanilla decoding at the same seeds.
DRAFT_STREAM = 0x5D1A_F7  # draft-proposal stream of speculative decoding


def stream_key(base_key, tag: int):
    """Derive a decoding-strategy stream key: ``fold_in(base, tag)``.

    Request/step folding on top of the returned key follows the exact
    counter scheme of :func:`request_step_keys`, so tagged streams are as
    batch-composition- and draft-depth-independent as the vanilla stream.
    """
    return jax.random.fold_in(base_key, jnp.uint32(tag))


def request_step_keys(base_key, seeds, steps):
    """(B,) per-row keys: fold_in(fold_in(base, seed_b), step_b)."""
    def fold(s, t):
        return jax.random.fold_in(jax.random.fold_in(base_key, s), t)

    return jax.vmap(fold)(seeds.astype(jnp.uint32), steps.astype(jnp.uint32))


def chosen_logprobs(logits, tok):
    """log p of each batch row's sampled token under this step's logits."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]


def sample_tokens(base_key, logits, seeds, steps, *, temperature, top_k,
                  top_p, top_p_candidates):
    """Sample one token per batch row.  Returns (B,) int32.

    Greedy when ``temperature <= 0``; otherwise per-row Gumbel-argmax with
    counter-based keys (see module docstring), filtered through the
    segmented top-k / batched nucleus-cutoff primitives when configured.

    **Nucleus semantics**: the top-p cutoff is measured on the softmax
    *renormalized over the k retained candidates* (``top_k``, or
    ``top_p_candidates`` when only top-p is set), not on the full-vocab
    distribution.  Consequences this module pins with conformance tests,
    so alternative logits paths (e.g. quantized decode) cannot silently
    change them: (a) the first (highest) candidate always survives -- its
    exclusive prefix mass is 0 < top_p; (b) when the candidates' full-vocab
    mass is below ``top_p`` the renormalized masses still sum to 1, so the
    cutoff binds at the same prefix as if the tail mass were redistributed
    -- in particular every candidate survives iff the renormalized
    exclusive prefix stays below ``top_p``, regardless of how little
    full-vocab mass the k candidates carry.

    The ops run under the scope ``sampler``, and the candidate selection
    under ``sampler/top_k``, whatever implements it: a profiler trace
    attributes device time to them by name.
    """
    with jax.named_scope("sampler"):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        keys = request_step_keys(base_key, seeds, steps)
        B, V = logits.shape
        if top_k or top_p < 1.0:
            k = min(top_k if top_k else top_p_candidates, V)
            flat = logits.astype(jnp.float32).reshape(-1)
            offsets = jnp.arange(B + 1, dtype=jnp.int32) * V
            with jax.named_scope("top_k"):
                vals, idx = forge.top_k(
                    flat, k, layout=Segmented(offsets=offsets))
            scaled = vals / temperature               # (B, k) descending
            # Keep the shortest prefix whose mass reaches top_p (the first
            # candidate always survives: its exclusive prefix mass is 0).
            # The (B, k) candidate grid is exactly the batched-scan layout:
            # one launch scans every request's row, whatever the batch size.
            probs = jax.nn.softmax(scaled, axis=-1)
            cum = forge.scan(alg.ADD, probs, inclusive=False,
                             layout=Batched())
            filtered = jnp.where(cum < top_p, scaled, -jnp.inf)
            g = jax.vmap(
                lambda kk: jax.random.gumbel(kk, (k,), jnp.float32))(keys)
            choice = jnp.argmax(filtered + g, axis=-1)
            return jnp.take_along_axis(
                idx, choice[:, None], axis=1)[:, 0].astype(jnp.int32)
        g = jax.vmap(
            lambda kk: jax.random.gumbel(kk, (V,), jnp.float32))(keys)
        return jnp.argmax(logits.astype(jnp.float32) / temperature + g,
                          axis=-1).astype(jnp.int32)


def masked_seq_logprobs(logps, emitted):
    """Per-slot sequence scores over the ragged (slots, steps) buffer:
    one masked ``mapreduce(layout=Batched())`` launch, identity at masked
    steps -- identical code path at any live-slot count."""
    T = logps.shape[1]
    mask = (jnp.arange(T, dtype=jnp.int32)[None, :]
            < emitted[:, None]).astype(jnp.int32)
    return forge.mapreduce(
        lambda t: jnp.where(t[1] != 0, t[0], 0.0), alg.ADD,
        (logps, mask), layout=Batched())
