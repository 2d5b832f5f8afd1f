"""Single-pass scan kernels: the TPU adaptation of decoupled look-back.

Paper (§V-B): Merrill–Garland single-pass scan reads global memory exactly
once and writes exactly once per element (2n movement); inter-block prefix
propagation uses release/acquire status flags because CUDA thread blocks have
no execution-order guarantee.

TPU adaptation (DESIGN.md §2): Pallas grid steps on a TPU core execute
*sequentially*, so the look-back protocol collapses to an exact running carry
held in VMEM scratch -- the same 2n data movement, zero spinning, zero flag
traffic.  The block-local phase is unchanged in spirit: each grid step loads
``Nitem`` aligned tiles (vectorized HBM->VMEM transfer), scans them entirely
in registers via log-step shifted combines, applies the carry, and stores
exactly once.

Two layouts are provided:

* :func:`scan_1d_pallas` -- flat scan over ``(n,)`` pytree leaves with
  arbitrary associative (possibly non-commutative) operators.  Element order
  within a (R, 128) tile is row-major, so the in-tile scan is
  lane-scan -> sublane prefix of row totals -> broadcast combine.
* :func:`scan_channel_pallas` -- batched scan along the middle axis of
  ``(B, T, C)`` leaves (the layout of diagonal linear recurrences such as
  RG-LRU and mLSTM inter-chunk states).  Channels ride the 128 lanes, time
  rides sublanes: the scan needs *no cross-lane communication at all* -- the
  TPU-native answer to the paper's warp-shuffle scan.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels.pallas_compat import pltpu

from repro.core import intrinsics as ki

Pytree = Any


def _tile_likes(tree_shape, shape, leaves_dtypes):
    return jax.tree.unflatten(
        tree_shape, [jax.ShapeDtypeStruct(shape, d) for d in leaves_dtypes])


def _mask_tree(mask, x, ident):
    return jax.tree.map(lambda l, i: jnp.where(mask, l, i), x, ident)


# ---------------------------------------------------------------------------
# 1-D scan
# ---------------------------------------------------------------------------


def block_scan_rowmajor(op, treedef, dtypes, x, carry, *, rows, inclusive):
    """Scan one masked ``(rows, LANES)`` tile in row-major element order.

    ``carry`` is the running ``(1, 1)``-shaped pytree carried across the
    sequential grid.  Returns ``(out, new_carry)``.  Entirely in registers:

      1. scan along lanes within each row (row-major element order),
      2. prefix the per-row totals down the sublanes,
      3. broadcast-combine row prefixes back onto the lane scans.

    Shared by the flat 1-D kernel here and the grid-batched kernel
    (kernels/batched.py), which runs this exact body once per
    (row, block) grid step with a per-row carry reset.
    """
    cidx = jax.lax.broadcasted_iota(jnp.int32, (rows, ki.LANES), 1)
    lane_scan = ki.tile_scan(op, x, axis=1)
    row_tot = ki.tile_take_last(lane_scan, axis=1)           # (rows, 1)
    row_pref = ki.tile_scan(op, row_tot, axis=0)             # inclusive
    ident_col = op.identity(_tile_likes(treedef, (rows, 1), dtypes))
    row0 = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) == 0
    row_excl = jax.tree.map(
        lambda p, i: jnp.where(row0, i, jnp.roll(p, 1, axis=0)),
        row_pref, ident_col)
    local = op(row_excl, lane_scan)                          # broadcast over lanes

    incl = op(carry, local)                                  # broadcast over tile

    if inclusive:
        out = incl
    else:
        # exclusive[k] = inclusive[k-1]; the element entering each row 0 is
        # the previous row's last, and tile element (0, 0) gets the carry.
        prev_lane = jax.tree.map(lambda l: jnp.roll(l, 1, axis=1), incl)
        row_last = ki.tile_take_last(incl, axis=1)
        prev_row_last = jax.tree.map(
            lambda rl, c: jnp.where(row0, c, jnp.roll(rl, 1, axis=0)),
            row_last, carry)
        out = jax.tree.map(
            lambda pl_, prl: jnp.where(cidx == 0, prl, pl_),
            prev_lane, prev_row_last)

    new_carry = op(carry, ki.tile_take_last(row_pref, axis=0))
    return out, new_carry


def _scan1d_kernel(op, treedef, n, rows, inclusive, n_leaves, *refs):
    x_refs = refs[:n_leaves]
    o_refs = refs[n_leaves:2 * n_leaves]
    carry_refs = refs[2 * n_leaves:]
    g = pl.program_id(0)
    block = rows * ki.LANES

    dtypes = [r.dtype for r in x_refs]
    ident_tile = op.identity(_tile_likes(treedef, (rows, ki.LANES), dtypes))
    ident_carry = op.identity(
        _tile_likes(treedef, (1, 1), [r.dtype for r in carry_refs]))

    @pl.when(g == 0)
    def _init():
        for cr, ic in zip(carry_refs, jax.tree.leaves(ident_carry)):
            cr[...] = ic

    x = jax.tree.unflatten(treedef, [xr[...] for xr in x_refs])

    # Masked tail (vload_pattern analogue): OOB lanes read garbage; replace
    # with the operator identity so they cannot contaminate the carry.
    ridx = jax.lax.broadcasted_iota(jnp.int32, (rows, ki.LANES), 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (rows, ki.LANES), 1)
    gidx = g * block + ridx * ki.LANES + cidx
    valid = gidx < n
    x = _mask_tree(valid, x, ident_tile)

    carry = jax.tree.unflatten(treedef, [cr[...] for cr in carry_refs])
    out, new_carry = block_scan_rowmajor(
        op, treedef, dtypes, x, carry, rows=rows, inclusive=inclusive)
    for cr, nc in zip(carry_refs, jax.tree.leaves(new_carry)):
        cr[...] = nc
    for orf, o in zip(o_refs, jax.tree.leaves(out)):
        orf[...] = o


def scan_1d_pallas(op, xs: Pytree, *, inclusive: bool = True,
                   policy: ki.TuningPolicy | None = None,
                   interpret: bool = False) -> Pytree:
    """Single-pass scan over flat ``(n,)`` pytree leaves."""
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    leaves, treedef = jax.tree.flatten(xs)
    n = leaves[0].shape[0]
    assert all(l.shape == (n,) for l in leaves), "1d scan: uniform leaf shapes"
    sub = max(ki.min_tile(l.dtype)[0] for l in leaves)
    views = [ki.lane_rows(l, policy.nitem_scan * sub, sub) for l in leaves]
    rows = views[0][1]
    m = views[0][0].shape[0]

    kernel = functools.partial(
        _scan1d_kernel, op, treedef, n, rows, inclusive, len(leaves))
    spec = pl.BlockSpec((rows, ki.LANES), lambda g: (g, 0))
    out = pl.pallas_call(
        kernel,
        grid=(ki.cdiv(m, rows),),
        in_specs=[spec for _ in leaves],
        out_specs=[spec for _ in leaves],
        out_shape=[jax.ShapeDtypeStruct((m, ki.LANES), l.dtype)
                   for l in leaves],
        scratch_shapes=[pltpu.VMEM((1, 1), l.dtype) for l in leaves],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*[v for v, _ in views])
    return jax.tree.unflatten(treedef, [ki.from_lane_rows(o, n) for o in out])


# ---------------------------------------------------------------------------
# Channelwise scan along the middle axis of (B, T, C) -- recurrence layout
# ---------------------------------------------------------------------------


def _chan_kernel(op, treedef, t_extent, t_rows, inclusive, reverse, n_leaves,
                 *refs):
    x_refs = refs[:n_leaves]
    o_refs = refs[n_leaves:2 * n_leaves]
    carry_refs = refs[2 * n_leaves:]
    tb = pl.program_id(2)
    nt = pl.num_programs(2)

    carry_like = _tile_likes(treedef, (1, ki.LANES), [r.dtype for r in carry_refs])
    ident_carry = op.identity(carry_like)

    @pl.when(tb == 0)
    def _init():
        for cr, ic in zip(carry_refs, jax.tree.leaves(ident_carry)):
            cr[...] = ic

    x = jax.tree.unflatten(
        treedef, [xr[...].reshape(t_rows, ki.LANES) for xr in x_refs])

    tile_like = _tile_likes(treedef, (t_rows, ki.LANES), [r.dtype for r in x_refs])
    ident_tile = op.identity(tile_like)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (t_rows, ki.LANES), 0)
    if reverse:
        # Grid walks T blocks back-to-front; flip in-tile so the combine
        # direction matches, then flip back on store.  After the flip, row r
        # corresponds to global time t_start + (t_rows - 1 - r).
        x = jax.tree.map(lambda l: jnp.flip(l, axis=0), x)
        t_start = (nt - 1 - tb) * t_rows
        valid = jnp.flip((t_start + ridx) < t_extent, axis=0)
    else:
        t_start = tb * t_rows
        valid = (t_start + ridx) < t_extent
    x = _mask_tree(valid, x, ident_tile)

    local = ki.tile_scan(op, x, axis=0)          # per-lane scan down sublanes
    carry = jax.tree.unflatten(treedef, [cr[...] for cr in carry_refs])
    incl = op(carry, local)

    if inclusive:
        out = incl
    else:
        out = jax.tree.map(
            lambda l, c: jnp.where(ridx == 0, c, jnp.roll(l, 1, axis=0)),
            incl, carry)

    new_carry = op(carry, ki.tile_take_last(local, axis=0))
    for cr, nc in zip(carry_refs, jax.tree.leaves(new_carry)):
        cr[...] = nc
    if reverse:
        out = jax.tree.map(lambda l: jnp.flip(l, axis=0), out)
    for orf, o in zip(o_refs, jax.tree.leaves(out)):
        orf[...] = o.reshape(1, t_rows, ki.LANES)


def scan_channel_pallas(op, xs: Pytree, *, inclusive: bool = True,
                        reverse: bool = False,
                        policy: ki.TuningPolicy | None = None,
                        interpret: bool = False) -> Pytree:
    """Scan along axis 1 of ``(B, T, C)`` leaves, independent per (b, c).

    Channels ride the lanes: no cross-lane combine is ever emitted.  This is
    the layout used by the RG-LRU / mLSTM linear recurrences.
    """
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    leaves, treedef = jax.tree.flatten(xs)
    B, T, C = leaves[0].shape
    assert all(l.shape == (B, T, C) for l in leaves)
    sub = max(ki.min_tile(l.dtype)[0] for l in leaves)
    t_rows = min(policy.nitem_scan * sub, max(sub, 1 << (max(T - 1, 1)).bit_length()))
    c_blocks = ki.cdiv(C, ki.LANES)
    t_blocks = ki.cdiv(T, t_rows)

    if reverse:
        def idx_map(b, c, t, _nt=t_blocks):
            return (b, _nt - 1 - t, c)
    else:
        def idx_map(b, c, t):
            return (b, t, c)

    kernel = functools.partial(
        _chan_kernel, op, treedef, T, t_rows, inclusive, reverse, len(leaves))
    out = pl.pallas_call(
        kernel,
        grid=(B, c_blocks, t_blocks),
        in_specs=[pl.BlockSpec((1, t_rows, ki.LANES), idx_map) for _ in leaves],
        out_specs=[pl.BlockSpec((1, t_rows, ki.LANES), idx_map) for _ in leaves],
        out_shape=[jax.ShapeDtypeStruct((B, T, C), l.dtype) for l in leaves],
        scratch_shapes=[pltpu.VMEM((1, ki.LANES), l.dtype) for l in leaves],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*leaves)
    return jax.tree.unflatten(treedef, out)
