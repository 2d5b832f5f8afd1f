"""Segmented scan / mapreduce kernels for ragged workloads.

Ragged batches (variable-length decode, MoE expert grouping) are flat arrays
partitioned into contiguous segments.  The paper's single-pass scan machinery
extends to them through Blelloch's *segmented lift* (``operators.segmented``):
each element becomes a ``(flag, value)`` pair, a nonzero flag marking a
segment start, and the lifted combine discards everything left of a boundary.
The lift preserves associativity, so the entire grid-carry protocol of
``kernels/scan.py`` carries over unchanged -- the carry itself resets when a
tile containing a boundary flows through it.

Two input conventions are supported at the dispatch layer (kernels/ops.py):

* **flag array** -- ``flags[i] != 0`` marks the first element of a segment
  (position 0 is always implicitly a start);
* **offsets** -- a ``(num_segments + 1,)`` monotone array of segment starts
  with ``offsets[0] == 0`` and ``offsets[-1] == n`` (CSR-style).  Offsets are
  scattered into a flag array before the kernel; empty segments contribute no
  flags and are handled at the gather step of mapreduce.

The kernel here is the flag-array form: a single-pass segmented scan over
flat ``(n,)`` pytree leaves with arbitrary (possibly non-commutative)
operators.  Flags ride along as one extra int32 input; scanned flags are
*not* written back (they are only needed in-register), so the data movement
is ``2n + n_flags`` -- one read and one write per value element, one read per
flag.  Segmented mapreduce = segmented inclusive scan + a gather of each
segment's last element, composed in kernels/ops.py.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels.pallas_compat import pltpu

from repro.core import intrinsics as ki
from repro.core import operators as alg

Pytree = Any


def _tile_likes(treedef, shape, dtypes):
    return jax.tree.unflatten(
        treedef, [jax.ShapeDtypeStruct(shape, d) for d in dtypes])


def _segscan1d_kernel(op, treedef, n, rows, inclusive, n_leaves, *refs):
    """Grid-carry segmented scan over one (rows, LANES) tile per step.

    Refs: [flags] + value inputs + value outputs + [flag carry] + value
    carries.  The carry is an element of the *lifted* type: its flag half
    records whether any boundary has flowed past, which makes the lifted
    combine reset the value half automatically.
    """
    seg = alg.segmented(op)
    f_ref = refs[0]
    x_refs = refs[1:1 + n_leaves]
    o_refs = refs[1 + n_leaves:1 + 2 * n_leaves]
    cf_ref = refs[1 + 2 * n_leaves]
    cv_refs = refs[2 + 2 * n_leaves:]
    g = pl.program_id(0)
    block = rows * ki.LANES

    dtypes = [r.dtype for r in x_refs]
    ident_tile = seg.identity(
        (jax.ShapeDtypeStruct((rows, ki.LANES), jnp.int32),
         _tile_likes(treedef, (rows, ki.LANES), dtypes)))
    ident_carry = seg.identity(
        (jax.ShapeDtypeStruct((1, 1), jnp.int32),
         _tile_likes(treedef, (1, 1), dtypes)))

    @pl.when(g == 0)
    def _init():
        cf_ref[...] = ident_carry[0]
        for cr, ic in zip(cv_refs, jax.tree.leaves(ident_carry[1])):
            cr[...] = ic

    flags = f_ref[...]
    vals = jax.tree.unflatten(treedef, [xr[...] for xr in x_refs])

    # Masked tail: out-of-bounds positions become the lifted identity
    # (flag 0, value identity) so they cannot contaminate the carry.
    ridx = jax.lax.broadcasted_iota(jnp.int32, (rows, ki.LANES), 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (rows, ki.LANES), 1)
    valid = (g * block + ridx * ki.LANES + cidx) < n
    flags = jnp.where(valid, flags, ident_tile[0])
    vals = jax.tree.map(
        lambda l, i: jnp.where(valid, l, i), vals, ident_tile[1])
    x = (flags, vals)

    # Block-local lifted scan, entirely in registers (same three-stage shape
    # as the flat scan: lane scan -> row-total prefix -> broadcast combine).
    lane_scan = ki.tile_scan(seg, x, axis=1)
    row_tot = ki.tile_take_last(lane_scan, axis=1)           # (rows, 1)
    row_pref = ki.tile_scan(seg, row_tot, axis=0)            # inclusive
    ident_col = seg.identity(
        (jax.ShapeDtypeStruct((rows, 1), jnp.int32),
         _tile_likes(treedef, (rows, 1), dtypes)))
    row0 = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) == 0
    row_excl = jax.tree.map(
        lambda p, i: jnp.where(row0, i, jnp.roll(p, 1, axis=0)),
        row_pref, ident_col)
    local = seg(row_excl, lane_scan)                         # broadcast over lanes

    carry = (cf_ref[...],
             jax.tree.unflatten(treedef, [cr[...] for cr in cv_refs]))
    incl = seg(carry, local)                                 # broadcast over tile

    if inclusive:
        out = incl[1]
    else:
        # exclusive[k] = inclusive[k-1] within the segment; the first element
        # of every segment gets the identity instead.  Shift the inclusive
        # values by one element (lane roll + row-boundary fixup + carry at
        # (0, 0)), then overwrite segment starts.
        incl_v = incl[1]
        prev_lane = jax.tree.map(lambda l: jnp.roll(l, 1, axis=1), incl_v)
        row_last = ki.tile_take_last(incl_v, axis=1)
        prev_row_last = jax.tree.map(
            lambda rl, c: jnp.where(row0, c, jnp.roll(rl, 1, axis=0)),
            row_last, carry[1])
        shifted = jax.tree.map(
            lambda pl_, prl: jnp.where(cidx == 0, prl, pl_),
            prev_lane, prev_row_last)
        out = jax.tree.map(
            lambda s, i: jnp.where(flags != 0, i, s),
            shifted, ident_tile[1])

    new_carry = seg(carry, ki.tile_take_last(row_pref, axis=0))
    cf_ref[...] = new_carry[0]
    for cr, nc in zip(cv_refs, jax.tree.leaves(new_carry[1])):
        cr[...] = nc
    for orf, o in zip(o_refs, jax.tree.leaves(out)):
        orf[...] = o


def segmented_scan_1d_pallas(op, xs: Pytree, flags: jax.Array, *,
                             inclusive: bool = True,
                             policy: ki.TuningPolicy | None = None,
                             interpret: bool = False) -> Pytree:
    """Single-pass segmented scan over flat ``(n,)`` pytree leaves.

    ``flags`` is an int ``(n,)`` array; nonzero entries start a new segment
    (element 0 implicitly starts one regardless).  ``op`` is any associative
    AssocOp over pytree elements; non-commutative operators are supported --
    the lifted operator is order-preserving by construction.
    """
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    leaves, treedef = jax.tree.flatten(xs)
    n = leaves[0].shape[0]
    assert all(l.shape == (n,) for l in leaves), "segmented scan: uniform leaves"
    assert flags.shape == (n,), "flags must match the scanned extent"
    flags = flags.astype(jnp.int32)
    sub = max(ki.min_tile(l.dtype)[0] for l in [flags, *leaves])
    views = [ki.lane_rows(l, policy.nitem_scan * sub, sub)
             for l in [flags, *leaves]]
    rows = views[0][1]
    m = views[0][0].shape[0]

    kernel = functools.partial(
        _segscan1d_kernel, op, treedef, n, rows, inclusive, len(leaves))
    spec = pl.BlockSpec((rows, ki.LANES), lambda g: (g, 0))
    out = pl.pallas_call(
        kernel,
        grid=(ki.cdiv(m, rows),),
        in_specs=[spec for _ in views],
        out_specs=[spec for _ in leaves],
        out_shape=[jax.ShapeDtypeStruct((m, ki.LANES), l.dtype)
                   for l in leaves],
        scratch_shapes=([pltpu.VMEM((1, 1), jnp.int32)] +
                        [pltpu.VMEM((1, 1), l.dtype) for l in leaves]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*[v for v, _ in views])
    return jax.tree.unflatten(treedef, [ki.from_lane_rows(o, n) for o in out])


# ---------------------------------------------------------------------------
# Segment bookkeeping shared by the dispatch wrappers (kernels/ops.py).
# ---------------------------------------------------------------------------


def offsets_to_flags(offsets: jax.Array, n: int) -> jax.Array:
    """CSR offsets -> flag array.  Empty segments leave no flag behind."""
    flags = jnp.zeros((n,), jnp.int32)
    if n == 0:
        return flags
    return flags.at[offsets[:-1]].set(1, mode="drop").at[0].set(1)


def flags_to_segment_ids(flags: jax.Array, *, backend: str | None = None,
                         policy=None) -> jax.Array:
    """0-based contiguous segment id per element (element 0 starts seg 0),
    as a +scan of the flags on ``backend``'s own ``scan@flat`` (XLA's cumsum
    at millions of elements takes the TPU compiler seconds)."""
    if flags.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    f = flags.astype(jnp.int32).at[0].set(1)
    return ki.resolve_impl("scan@flat", backend)(alg.ADD, f, policy=policy) - 1


def gather_segment_lasts(op, incl: Pytree, *,
                         offsets: jax.Array | None = None,
                         flags: jax.Array | None = None,
                         num_segments: int | None = None) -> Pytree:
    """Pick each segment's last inclusive-scan element; identity for empties.

    ``incl`` is the segmented *inclusive* scan of the mapped values; its
    element at the last index of segment ``s`` is that segment's reduction.
    """
    leaves = jax.tree.leaves(incl)
    n = leaves[0].shape[0]
    if offsets is not None:
        num_segments = offsets.shape[0] - 1
        last = offsets[1:] - 1
        empty = offsets[1:] == offsets[:-1]
        idx = jnp.clip(last, 0, n - 1)
        picked = jax.tree.map(lambda l: l[idx], incl)
        ident = op.identity(picked)
        return jax.tree.map(
            lambda p, i: jnp.where(empty, i, p), picked, ident)
    assert flags is not None and num_segments is not None, (
        "flag-variant segmented mapreduce needs num_segments")
    seg_ids = flags_to_segment_ids(flags)
    # Deterministic scatter-max finds each segment's last position; segments
    # past the flag count (or never started) keep -1 and take the identity.
    lasts = jnp.full((num_segments,), -1, jnp.int32).at[seg_ids].max(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    idx = jnp.clip(lasts, 0, n - 1)
    picked = jax.tree.map(lambda l: l[idx], incl)
    ident = op.identity(picked)
    return jax.tree.map(
        lambda p, i: jnp.where(lasts < 0, i, p), picked, ident)
