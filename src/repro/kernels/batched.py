"""Grid-batched primitive kernels: one launch for a whole batch of rows.

Serving and recurrent-model decode present *uniform* batches -- B independent
problems of identical extent (per-request candidate lists, per-request score
streams, per-head recurrences).  Dispatching the flat primitives per row pays
one kernel launch and one tuning lookup per request; ``vmap`` over the 1-D
kernels is not an option either (Pallas calls do not batch).  The portability
studies this repo tracks (Godoy et al., arXiv:2303.06195; Besard et al.,
arXiv:1604.03410) both find abstraction overhead concentrating exactly there:
dispatch/launch amplification on small per-item problems.

The batched family answers with a third grid-layout column next to the flat
and segmented ones: the batch rides a leading **parallel** grid dimension,
the per-row work keeps the flat kernels' sequential protocol on the *inner*
grid axis, and the per-row state (scan carry / mapreduce accumulator /
matvec output-block accumulator) resets at inner step 0 -- which, because the
inner axis is minor, is exactly the start of every new row.  One launch, one
tuning decision, B independent problems.

The kernel *bodies* are shared with the flat family -- see
``scan.block_scan_rowmajor``, ``mapreduce._mapreduce_kernel`` (``grid_axis``)
and ``matvec._matvec_kernel`` / ``matvec._vecmat_kernel`` (``batched``) --
so a correctness fix or a tiling improvement lands in both layouts at once.

Zero-extent edges (B == 0, n == 0) are handled by the dispatch wrappers in
kernels/ops.py; the kernels here require every grid dimension >= 1.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels.pallas_compat import pltpu

from repro.core import intrinsics as ki
from repro.kernels import mapreduce as mapreduce_k
from repro.kernels import matvec as matvec_k
from repro.kernels import scan as scan_k

Pytree = Any


# ---------------------------------------------------------------------------
# Batched scan: (B, n) leaves, scan along axis 1, independent per row.
# ---------------------------------------------------------------------------


def _batched_scan_kernel(op, treedef, n, rows, inclusive, n_leaves, *refs):
    x_refs = refs[:n_leaves]
    o_refs = refs[n_leaves:2 * n_leaves]
    carry_refs = refs[2 * n_leaves:]
    g = pl.program_id(1)            # within-row block (sequential, minor)
    block = rows * ki.LANES

    dtypes = [r.dtype for r in x_refs]
    ident_tile = op.identity(
        scan_k._tile_likes(treedef, (rows, ki.LANES), dtypes))
    ident_carry = op.identity(scan_k._tile_likes(treedef, (1, 1), dtypes))

    # Every row's first block resets the carry: rows are independent scans.
    @pl.when(g == 0)
    def _init():
        for cr, ic in zip(carry_refs, jax.tree.leaves(ident_carry)):
            cr[...] = ic

    x = jax.tree.unflatten(treedef, [xr[...] for xr in x_refs])
    ridx = jax.lax.broadcasted_iota(jnp.int32, (rows, ki.LANES), 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (rows, ki.LANES), 1)
    valid = (g * block + ridx * ki.LANES + cidx) < n
    x = scan_k._mask_tree(valid, x, ident_tile)

    carry = jax.tree.unflatten(treedef, [cr[...] for cr in carry_refs])
    out, new_carry = scan_k.block_scan_rowmajor(
        op, treedef, dtypes, x, carry, rows=rows, inclusive=inclusive)
    for cr, nc in zip(carry_refs, jax.tree.leaves(new_carry)):
        cr[...] = nc
    for orf, o in zip(o_refs, jax.tree.leaves(out)):
        orf[...] = o


def batched_scan_pallas(op, xs: Pytree, *, inclusive: bool = True,
                        policy: ki.TuningPolicy | None = None,
                        interpret: bool = False) -> Pytree:
    """Per-row prefix scan over ``(B, n)`` pytree leaves, single launch."""
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    leaves, treedef = jax.tree.flatten(xs)
    B, n = leaves[0].shape
    assert all(l.shape == (B, n) for l in leaves), "batched scan: uniform leaves"
    sub = max(ki.min_tile(l.dtype)[0] for l in leaves)
    views = [ki.lane_rows(l, policy.nitem_scan * sub, sub) for l in leaves]
    rows = views[0][1]
    m = views[0][0].shape[1]

    kernel = functools.partial(
        _batched_scan_kernel, op, treedef, n, rows, inclusive, len(leaves))
    spec = pl.BlockSpec((None, rows, ki.LANES), lambda b, g: (b, g, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, ki.cdiv(m, rows)),
        in_specs=[spec for _ in leaves],
        out_specs=[spec for _ in leaves],
        out_shape=[jax.ShapeDtypeStruct((B, m, ki.LANES), l.dtype)
                   for l in leaves],
        scratch_shapes=[pltpu.VMEM((1, 1), l.dtype) for l in leaves],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*[v for v, _ in views])
    return jax.tree.unflatten(treedef, [ki.from_lane_rows(o, n) for o in out])


# ---------------------------------------------------------------------------
# Batched mapreduce: (B, n) leaves -> per-row scalars (B,).
# ---------------------------------------------------------------------------


def batched_mapreduce_pallas(f, op, xs: Pytree, *,
                             policy: ki.TuningPolicy | None = None,
                             interpret: bool = False) -> Pytree:
    """Per-row op-reduce of ``f(x)`` over ``(B, n)`` leaves, single launch.

    Commutative ``op`` only (same accumulate-tile argument as the flat
    kernel); non-commutative ops are routed through the batched scan by the
    dispatcher (kernels/ops.py).
    """
    assert op.commutative, \
        "batched_mapreduce kernel requires a commutative operator"
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    in_leaves, in_treedef = jax.tree.flatten(xs)
    B, n = in_leaves[0].shape
    assert all(l.shape == (B, n) for l in in_leaves)

    out_shape_tree = jax.eval_shape(
        f, jax.tree.unflatten(
            in_treedef,
            [jax.ShapeDtypeStruct((1, ki.LANES), l.dtype) for l in in_leaves]))
    out_leaves, out_treedef = jax.tree.flatten(out_shape_tree)

    sub = max(ki.min_tile(l.dtype)[0] for l in in_leaves)
    views = [ki.lane_rows(l, policy.nitem_reduce * sub, sub)
             for l in in_leaves]
    rows = views[0][1]
    m = views[0][0].shape[1]

    kernel = functools.partial(
        mapreduce_k._mapreduce_kernel, f, op, in_treedef, out_treedef, n,
        rows, len(in_leaves), len(out_leaves), 1)
    out = pl.pallas_call(
        kernel,
        grid=(B, ki.cdiv(m, rows)),
        in_specs=[pl.BlockSpec((None, rows, ki.LANES), lambda b, g: (b, g, 0))
                  for _ in in_leaves],
        out_specs=[pl.BlockSpec((None, 1, 1), lambda b, g: (b, 0, 0))
                   for _ in out_leaves],
        out_shape=[jax.ShapeDtypeStruct((B, 1, 1), l.dtype)
                   for l in out_leaves],
        scratch_shapes=[pltpu.VMEM((rows, ki.LANES), l.dtype)
                        for l in out_leaves],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*[v for v, _ in views])
    return jax.tree.unflatten(out_treedef, [o[:, 0, 0] for o in out])


# ---------------------------------------------------------------------------
# Batched matvec / vecmat: (B, n, p) matrices against per-row vectors.
# ---------------------------------------------------------------------------


def batched_matvec_pallas(f, op, A: jax.Array, x: jax.Array, *,
                          block_rows: int, block_cols: int,
                          interpret: bool = False) -> Pytree:
    """y[b, j] = op_i f(x[b, i], A[b, i, j]).  A: (B, n, p), x: (B, n)."""
    B, n, p = A.shape
    rn, cp = block_rows, block_cols
    out_leaves, out_treedef = matvec_k._out_struct(
        f, jax.ShapeDtypeStruct((1, 1), x.dtype),
        jax.ShapeDtypeStruct((1, 1), A.dtype))

    grid = (B, ki.cdiv(p, cp), ki.cdiv(n, rn))
    kernel = functools.partial(
        matvec_k._matvec_kernel, f, op, out_treedef, n, rn,
        len(out_leaves), True)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rn, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, rn, cp), lambda b, j, i: (b, i, j)),
        ],
        out_specs=[pl.BlockSpec((1, 1, cp), lambda b, j, i: (b, 0, j))
                   for _ in out_leaves],
        out_shape=[jax.ShapeDtypeStruct((B, 1, p), l.dtype)
                   for l in out_leaves],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.reshape(B, n, 1), A)
    return jax.tree.unflatten(out_treedef, [o.reshape(B, p) for o in out])


def batched_vecmat_pallas(f, op, A: jax.Array, x: jax.Array, *,
                          block_rows: int, block_cols: int,
                          interpret: bool = False) -> Pytree:
    """z[b, i] = op_j f(A[b, i, j], x[b, j]).  A: (B, n, p), x: (B, p)."""
    B, n, p = A.shape
    ri, cj = block_rows, block_cols
    out_leaves, out_treedef = matvec_k._out_struct(
        f, jax.ShapeDtypeStruct((1, 1), A.dtype),
        jax.ShapeDtypeStruct((1, 1), x.dtype))

    grid = (B, ki.cdiv(n, ri), ki.cdiv(p, cj))
    kernel = functools.partial(
        matvec_k._vecmat_kernel, f, op, out_treedef, p, cj,
        len(out_leaves), True)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, cj), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, ri, cj), lambda b, i, j: (b, i, j)),
        ],
        out_specs=[pl.BlockSpec((1, ri, 1), lambda b, i, j: (b, i, 0))
                   for _ in out_leaves],
        out_shape=[jax.ShapeDtypeStruct((B, n, 1), l.dtype)
                   for l in out_leaves],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.reshape(B, 1, p), A)
    return jax.tree.unflatten(out_treedef, [o.reshape(B, n) for o in out])


def batched_matvec_quantized_pallas(f, op, q, x: jax.Array, *,
                                    block_rows: int, block_cols: int,
                                    interpret: bool = False) -> Pytree:
    """Batched matvec over a ``Quantized`` (B, n, p) matrix operand: the
    scale tiles ride the same (batch, stripe) grid as the value tiles, and
    the shared quantized kernel body dequantizes per tile (f32 accumulate).
    ``block_rows`` must be a multiple of ``q.block``."""
    B, n, p = q.shape
    rn, cp = block_rows, block_cols
    rpb = matvec_k._check_quant_blocks(rn, q)
    out_leaves, out_treedef = matvec_k._out_struct(
        f, jax.ShapeDtypeStruct((1, 1), x.dtype),
        jax.ShapeDtypeStruct((1, 1), jnp.float32))

    grid = (B, ki.cdiv(p, cp), ki.cdiv(n, rn))
    kernel = functools.partial(
        matvec_k._matvec_q_kernel, f, op, out_treedef, n, rn, q.block,
        q.mode, True)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rn, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, rn, cp), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, rpb, cp), lambda b, j, i: (b, i, j)),
        ],
        out_specs=[pl.BlockSpec((1, 1, cp), lambda b, j, i: (b, 0, j))
                   for _ in out_leaves],
        out_shape=[jax.ShapeDtypeStruct((B, 1, p), l.dtype)
                   for l in out_leaves],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.reshape(B, n, 1), q.values, q.scales)
    return jax.tree.unflatten(out_treedef, [o.reshape(B, p) for o in out])


def batched_vecmat_quantized_pallas(f, op, q, x: jax.Array, *,
                                    block_rows: int, block_cols: int,
                                    interpret: bool = False) -> Pytree:
    """Batched vecmat over a ``Quantized`` (B, n, p) matrix operand; scale
    blocks tile the row axis exactly as in the flat quantized vecmat."""
    B, n, p = q.shape
    ri, cj = block_rows, block_cols
    rpb = matvec_k._check_quant_blocks(ri, q)
    out_leaves, out_treedef = matvec_k._out_struct(
        f, jax.ShapeDtypeStruct((1, 1), jnp.float32),
        jax.ShapeDtypeStruct((1, 1), x.dtype))

    grid = (B, ki.cdiv(n, ri), ki.cdiv(p, cj))
    kernel = functools.partial(
        matvec_k._vecmat_q_kernel, f, op, out_treedef, p, cj, ri, q.block,
        q.mode, True)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, cj), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, ri, cj), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, rpb, cj), lambda b, i, j: (b, i, j)),
        ],
        out_specs=[pl.BlockSpec((1, ri, 1), lambda b, i, j: (b, i, 0))
                   for _ in out_leaves],
        out_shape=[jax.ShapeDtypeStruct((B, n, 1), l.dtype)
                   for l in out_leaves],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.reshape(B, 1, p), q.values, q.scales)
    return jax.tree.unflatten(out_treedef, [o.reshape(B, n) for o in out])
