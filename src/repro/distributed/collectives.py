"""Distributed decode attention: flash-decoding over the model axis.

Baseline pathology (EXPERIMENTS.md §Perf): for archs whose KV heads do not
divide the model axis (gemma3 K=4, minitron K=8, dscoder K=8, MQA K=1) the
decode cache was *replicated* across the 16 model-axis chips -- every chip
streamed the whole 32k-deep cache per token (memory term) and the ZeRO-3
parameter gathers piled onto that (collective term).

Fix: shard the cache along the *sequence* axis over ``model`` and give each
chip a partial softmax over its slice; the partials (m, l, o) form the
``SOFTMAX_MERGE`` monoid from the core operator algebra -- the distributed
combine IS ``mapreduce(SOFTMAX_MERGE, layout=Sharded("model"))``.  That
route now compiles to a staged ShardPlan (distributed/primitives.py): the
local reduce is one stage, and the operator's registered
:class:`~repro.core.operators.FoldSpec` (``pmax`` + two ``psum``) is the
collective stage the plan driver issues -- chunked along the partials'
row axis so later chunks' local math overlaps earlier chunks' collectives
(``tests/test_sharded.py`` pins the equivalence to the operator fold).
What used to be a hand-staged two-phase merge here is exactly the shape
the plan driver emits; no hand-rolled collective remains -- the merge
dispatches through the same registry route every other consumer uses.

Per-chip traffic drops from O(L) to O(L/16) cache reads plus O(B*H*hd)
collective bytes -- a ~16x cut of the decode memory term at the cost of a
tiny all-reduce (the §Perf before/after numbers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Sharded

NEG_INF = -1e30


def _partial_softmax(s, v):
    """s: (..., L) masked scores fp32; v: (..., L, hd).  -> (m, l, o)."""
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    # Rows with no valid key on this shard: m == NEG_INF, p must be 0.
    p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("...t,...td->...d", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def merge_partials(m, l, o, axis_name):
    """SOFTMAX_MERGE folded across ``axis_name``, via the @sharded route.

    Dispatches ``mapreduce(SOFTMAX_MERGE, layout=Sharded(axis_name))`` in
    its in-mesh form: each device contributes its one partial (a length-1
    stream along leaf axis 0) and the staged plan issues the operator's
    registered collective fold -- m* = pmax m; w = exp(m - m*);
    l* = psum(w l); o* = psum(w o) -- per batch-row chunk, so the fold for
    one chunk of rows flies while the next chunk reduces.

    Rows masked on **every** shard (batch-padding rows during decode) have
    l* == 0 and an o* that may carry masked garbage (0 * NaN from poisoned
    cache slots); dividing by the 1e-30 clamp would amplify it, so such
    rows return explicit zeros instead.
    """
    m_g, l_g, o_g = forge.mapreduce(
        lambda t: t, alg.SOFTMAX_MERGE,
        jax.tree.map(lambda t: t[None], (m, l, o)),
        layout=Sharded(axis_name))
    out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
    all_masked = m_g <= NEG_INF / 2
    return jnp.where(all_masked[..., None], jnp.zeros_like(out), out)


def _local_ring_update(cache_loc, new_row, slot, axis_name="model"):
    """Owner-shard cache write at traced ``slot`` on the sharded seq axis.

    A jnp-level dynamic_update_slice at a traced position on a sharded axis
    makes GSPMD all-gather the whole cache (the 86 GB/step pathology in the
    §Perf decode iteration); done shard-locally it is free.
    """
    L_loc = cache_loc.shape[1]
    start = jax.lax.axis_index(axis_name) * L_loc
    rel = jnp.clip(slot - start, 0, L_loc - 1)
    owns = (slot >= start) & (slot < start + L_loc)
    updated = jax.lax.dynamic_update_slice_in_dim(
        cache_loc, new_row.astype(cache_loc.dtype), rel, axis=1)
    return jnp.where(owns, updated, cache_loc)


def flash_decode_gqa(mesh, q, k_cache, v_cache, k_new, v_new, slot,
                     key_valid, *, softcap=0.0, batch_sharded=True):
    """Sequence-sharded decode attention with in-shard cache update.

    q: (B, 1, K, G, hd) replicated over model; caches: (B, L, K, hd) with L
    sharded over "model"; k_new/v_new: (B, 1, K, hd); slot: scalar write
    position; key_valid: (L,) bool (already accounting for the new token).
    Returns (out, new_k_cache, new_v_cache).
    """
    hd = q.shape[-1]
    scale = 1.0 / np.sqrt(hd)
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    b = dp if batch_sharded else None

    def local(qb, kb, vb, knb, vnb, slot_, validb):
        kb = _local_ring_update(kb, knb, slot_)
        vb = _local_ring_update(vb, vnb, slot_)
        s = jnp.einsum("bskgd,btkd->bskgt", qb.astype(jnp.float32) * scale,
                       kb.astype(jnp.float32))
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(validb[None, None, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)                      # (B,1,K,G)
        p = jnp.exp(s - m[..., None])
        p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, p)
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bskgt,btkd->bskgd", p, vb.astype(jnp.float32))
        out = merge_partials(m, l, o, "model")
        return out.astype(qb.dtype), kb, vb

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b, None, None, None, None),
                  P(b, "model", None, None),
                  P(b, "model", None, None),
                  P(b, None, None, None),
                  P(b, None, None, None),
                  P(),
                  P("model")),
        out_specs=(P(b, None, None, None, None),
                   P(b, "model", None, None),
                   P(b, "model", None, None)),
        check_vma=False)
    return fn(q, k_cache, v_cache, k_new, v_new, slot, key_valid)


def flash_decode_mla(mesh, q_abs, q_rope, ckv, krope, ckv_new, krope_new,
                     slot, key_valid, *, scale, batch_sharded=True):
    """Sequence-sharded MLA decode in the compressed latent space.

    q_abs: (B,1,H,r) and q_rope: (B,1,H,rd) replicated over model;
    ckv: (B,L,r), krope: (B,L,rd) with L sharded over "model";
    ckv_new/krope_new: (B,1,*) this step's compressed KV; slot: write pos.
    Returns (ctx: (B,1,H,r), new_ckv, new_krope).
    """
    def local(qa, qr, cb, kb, cnb, knb, slot_, validb):
        cb = _local_ring_update(cb, cnb, slot_)
        kb = _local_ring_update(kb, knb, slot_)
        s = (jnp.einsum("bshr,btr->bsht", qa.astype(jnp.float32),
                        cb.astype(jnp.float32)) +
             jnp.einsum("bshr,btr->bsht", qr.astype(jnp.float32),
                        kb.astype(jnp.float32))) * scale
        s = jnp.where(validb[None, None, None, :], s, NEG_INF)
        # cb broadcast over (s=1, H): v -> (B,1,1,t,r); o: (B,1,H,r).
        m, l, o = _partial_softmax(s, cb.astype(jnp.float32)[:, None, None])
        out = merge_partials(m, l, o, "model")
        return out, cb, kb

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    b = dp if batch_sharded else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b, None, None, None), P(b, None, None, None),
                  P(b, "model", None), P(b, "model", None),
                  P(b, None, None), P(b, None, None), P(), P("model")),
        out_specs=(P(b, None, None, None),
                   P(b, "model", None), P(b, "model", None)),
        check_vma=False)
    return fn(q_abs, q_rope, ckv, krope, ckv_new, krope_new, slot, key_valid)
