"""Slot-indexed decode cache: per-request paged/ring state + CSR accounting.

The continuous-batching engine keeps ONE device-resident cache pytree for
the whole batch (the same ``lm.init_caches`` tree the padded engine uses)
and treats its batch axis as an array of *slots*: a request owns a slot from
admission to eviction, and every layer's state for that request -- KV rings
for attention layers, O(1) recurrent states, conv tails -- lives at that
slot index.  This module is the address layer:

* :func:`scatter_slot` writes a freshly prefilled single-request cache into
  one slot of the live tree (handling the ``units`` stacking, whose leading
  axis is the layer axis, not the batch axis);
* :func:`poison_slot` overwrites a freed slot with a sentinel value -- used
  by the stale-state-bleed tests (a recycled slot must behave exactly like a
  fresh engine, so tests poison on eviction and diff the outputs) and
  available as a debugging mode;
* :func:`ring_slot` is the ring-buffer address map shared with
  ``attention.gqa_decode`` (slot = pos mod window), kept here so the
  wraparound tests pin the exact arithmetic the kernels use;
* :class:`SlotLedger` tracks ragged per-slot lengths on the host and renders
  them as the CSR ``offsets`` descriptor of the ``Segmented`` layout -- the
  engine's own docstring promise that ragged per-request state is "a
  descriptor change, not a new code path";
* :func:`compact_ragged` drains ragged per-slot output buffers into one
  flat stream + CSR offsets, with the exclusive +scan of lengths running on
  ``core.primitives.scan`` (the same primitive the MoE dispatch uses for
  its CSR construction).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Flat


def _update(live_leaf, single_leaf, slot, axis):
    return jax.lax.dynamic_update_slice_in_dim(
        live_leaf, single_leaf.astype(live_leaf.dtype), slot, axis=axis)


def scatter_slot(live, single, slot):
    """Write a batch=1 cache tree ``single`` into ``slot`` of ``live``.

    ``live`` is the full-batch decode cache (leaves lead with the slot axis;
    ``units`` leaves lead with the layer axis, slot axis second -- the
    ``lax.scan``-stacked layout of ``lm._stack_cache``).  ``slot`` may be a
    traced scalar, so admission runs inside one jitted program.
    """
    out = dict(live)
    for part in ("prefix", "suffix"):
        out[part] = jax.tree.map(
            lambda lv, sg: _update(lv, sg, slot, 0), live[part], single[part])
    out["units"] = jax.tree.map(
        lambda lv, sg: _update(lv, sg, slot, 1), live["units"], single["units"])
    return out


def stack_slots(singles):
    """Stack batch=1 cache trees into one full-batch tree, slot ``i`` from
    ``singles[i]``; the slot axes are those :func:`scatter_slot` writes."""
    out = dict(singles[0])
    for part, axis in (("prefix", 0), ("suffix", 0), ("units", 1)):
        out[part] = jax.tree.map(
            lambda *s, axis=axis: jnp.concatenate(s, axis),
            *[c[part] for c in singles])
    return out


def select_slots(mask, new, old):
    """Per-slot select between two full-batch cache trees.

    ``mask`` is (B,) bool over the slot axis; leaf ``l`` takes ``new``'s
    slot where ``mask`` holds, ``old``'s otherwise.  This is the cache
    *rollback* primitive of speculative decoding: the post-verify commit
    keeps the advanced cache only on slots whose proposal was accepted,
    broadcast per leaf over the slot axis (axis 0 for prefix/suffix leaves,
    axis 1 under the ``units`` layer stacking).
    """
    def sel(axis):
        def leaf(nw, od):
            shape = [1] * nw.ndim
            shape[axis] = mask.shape[0]
            return jnp.where(mask.reshape(shape), nw, od)
        return leaf

    out = dict(old)
    for part in ("prefix", "suffix"):
        out[part] = jax.tree.map(sel(0), new[part], old[part])
    out["units"] = jax.tree.map(sel(1), new["units"], old["units"])
    return out


def gather_slots(live, rows):
    """Reindex the slot axis: slot ``i`` of the result is slot ``rows[i]``
    of ``live`` (``rows``: (B,) int32; identity rows leave a slot alone).

    This is beam search's beam-reorder move: after the per-round top-k over
    beam x vocab candidates, each surviving beam inherits the cache of the
    beam it extends -- one gather over the slot axis of every leaf.
    """
    out = dict(live)
    for part in ("prefix", "suffix"):
        out[part] = jax.tree.map(
            lambda l: jnp.take(l, rows, axis=0), live[part])
    out["units"] = jax.tree.map(
        lambda l: jnp.take(l, rows, axis=1), live["units"])
    return out


def poison_slot(live, slot, value=float("nan")):
    """Overwrite every leaf of ``slot``'s state with ``value``.

    Freed-slot hygiene check: if any downstream compute ever reads a freed
    slot's state, a NaN poison turns the silent stale-read into a loud one.
    Integer leaves get the truncated value (NaN -> large sentinel via -1).
    """
    def poison(leaf, axis):
        shape = list(leaf.shape)
        shape[axis] = 1
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            fill = jnp.full(shape, value, leaf.dtype)
        else:
            fill = jnp.full(shape, -1, leaf.dtype)
        return jax.lax.dynamic_update_slice_in_dim(leaf, fill, slot, axis=axis)

    out = dict(live)
    for part in ("prefix", "suffix"):
        out[part] = jax.tree.map(lambda l: poison(l, 0), live[part])
    out["units"] = jax.tree.map(lambda l: poison(l, 1), live["units"])
    return out


def ring_slot(pos, window: int):
    """Ring-buffer slot of absolute position ``pos`` in a ``window`` cache.

    This is the address map ``attention.gqa_decode`` (local layers) and the
    engine's position bookkeeping both use; ``pos`` may be scalar or array.
    """
    return pos % window


def slot_position(slot_idx, pos, window: int):
    """Absolute position currently held by ring slot ``slot_idx`` when the
    writer is at ``pos`` (negative: slot not yet written)."""
    return pos - (pos - slot_idx) % window


def quantize_kv_tree(caches, mode: str):
    """Replace every attention KV leaf with a ``KVQuant`` (values, scales)
    node -- the opt-in ``quantize_kv=`` cache form.

    Attention KV leaves are the ``"k"``/``"v"`` dict entries of rank >= 4
    ((slot, pos, kv_head, head_dim), plus a leading layer axis under the
    ``units`` stacking); everything else -- MLA latents, recurrent states,
    conv tails -- stays dense.  Because ``KVQuant`` is a registered pytree
    whose children share the leaf's leading axes, :func:`scatter_slot`,
    :func:`poison_slot` and the ring address math above work on the
    quantized tree unchanged.
    """
    def walk(node):
        if isinstance(node, dict):
            return {
                key: (alg.quantize_kv(val, mode)
                      if key in ("k", "v") and getattr(val, "ndim", 0) >= 4
                      else walk(val))
                for key, val in node.items()}
        if isinstance(node, tuple):
            return tuple(walk(v) for v in node)
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node
    return walk(caches)


class SlotError(IndexError):
    """A slot index outside ``[0, num_slots)`` reached the ledger.

    Raised instead of letting numpy's negative-index wraparound silently
    redirect the update into another live slot's length accounting."""


class SlotLedger:
    """Host-side ragged length accounting for the live slots.

    One integer length per slot (tokens currently resident in the slot's
    cache); rendered on demand as the CSR ``offsets`` descriptor that the
    ``Segmented(offsets=...)`` layout consumes.  The ledger is pure host
    bookkeeping -- it never forces a device sync.
    """

    def __init__(self, num_slots: int, cache_len: int):
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.lengths = np.zeros(num_slots, np.int64)

    def _check_slot(self, slot: int) -> int:
        slot = int(slot)
        if not 0 <= slot < self.num_slots:
            raise SlotError(
                f"slot {slot} outside [0, {self.num_slots}): negative or "
                "out-of-range slots would wrap into another slot's ledger "
                "entry")
        return slot

    def occupy(self, slot: int, length: int):
        slot = self._check_slot(slot)
        if not 0 <= length <= self.cache_len:
            raise ValueError(
                f"slot {slot}: length {length} outside [0, {self.cache_len}]")
        self.lengths[slot] = length

    def advance(self, slot: int, by: int = 1):
        slot = self._check_slot(slot)
        self.lengths[slot] = min(self.lengths[slot] + by, self.cache_len)

    def free(self, slot: int):
        slot = self._check_slot(slot)
        self.lengths[slot] = 0

    def offsets(self) -> jax.Array:
        """CSR offsets (num_slots + 1,) int32 -- the Segmented descriptor."""
        return jnp.asarray(
            np.concatenate([[0], np.cumsum(self.lengths)]), jnp.int32)

    def segment_of(self, slot: int) -> tuple[int, int]:
        """[start, end) of ``slot``'s segment in the flat CSR stream."""
        slot = self._check_slot(slot)
        start = int(self.lengths[:slot].sum())
        return start, start + int(self.lengths[slot])


def compact_ragged(buf, counts):
    """Drain ragged per-slot rows into (flat stream, CSR offsets).

    ``buf``: (B, T) per-slot buffers; ``counts``: (B,) valid prefix lengths.
    Returns ``(flat, offsets)`` with ``flat[offsets[b]:offsets[b+1]] ==
    buf[b, :counts[b]]`` -- the CSR compaction pattern (exclusive +scan of
    counts = segment starts, then a gather), with the scan on the library's
    own primitive.  Host-side drain helper: runs eagerly on small arrays.
    """
    B, T = buf.shape
    # The flat extent must be a host int (it shapes the gather).  When the
    # counts are already concrete -- the ledger hands over host numpy --
    # summing them locally avoids the blocking device->host sync that
    # ``int(incl[-1])`` forces, keeping the drain path on the module's
    # no-sync promise; only genuinely device-resident counts pay the wait.
    host_counts = None if isinstance(counts, jax.Array) else np.asarray(counts)
    counts = jnp.asarray(counts, jnp.int32)
    incl = forge.scan(alg.ADD, counts, layout=Flat())        # (B,) inclusive
    starts = incl - counts                                   # exclusive form
    if host_counts is not None:
        total = int(host_counts.sum()) if B else 0
    else:
        total = int(incl[-1]) if B else 0
    offsets = jnp.concatenate(
        [starts.astype(jnp.int32), jnp.asarray([total], jnp.int32)])
    # Gather: flat[k] = buf[b, k - starts[b]] for k in [starts[b], incl[b]).
    seg = jnp.searchsorted(incl, jnp.arange(total, dtype=jnp.int32),
                           side="right").astype(jnp.int32)
    col = jnp.arange(total, dtype=jnp.int32) - starts[seg]
    flat = buf[seg, col]
    return flat, offsets
