"""Single-pass mapreduce kernel (paper §V-A), TPU adaptation.

Paper: fixed grid of blocks, each thread strides the input accumulating in
registers; hierarchical register -> warp-shuffle -> shared-memory reduction;
single launch via release/acquire completion flags instead of a second
kernel.

TPU adaptation: the sequential Pallas grid *is* the strided loop -- one VMEM
accumulator tile persists across grid steps (register accumulation analogue),
each step folds ``Nitem`` aligned tiles into it elementwise, and the final
step collapses the accumulator with log-step in-register combines
(shuffle-tree analogue) and writes the scalar: one kernel launch, exactly n
element reads, O(1) writes.  ``f`` may change element type (e.g. the paper's
UnitFloat8 -> Float32 promotion), so the accumulator carries the *mapped*
dtype.
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


def _mapreduce_kernel(f, op, in_treedef, out_treedef, n, rows, n_in, n_out,
                      grid_axis, *refs):
    """Strided accumulate into a persistent VMEM tile, collapse on last step.

    ``grid_axis`` names the sequential (reduction) grid dimension: 0 for the
    flat 1-D kernel, 1 for the grid-batched kernel (kernels/batched.py) whose
    leading grid dimension rides the batch in parallel.  The accumulator
    resets at step 0 of the sequential axis, which for the batched layout is
    exactly the start of each new row.
    """
    x_refs = refs[:n_in]
    o_refs = refs[n_in:n_in + n_out]
    acc_refs = refs[n_in + n_out:]
    g = pl.program_id(grid_axis)
    ng = pl.num_programs(grid_axis)
    block = rows * ki.LANES

    acc_like = jax.tree.unflatten(
        out_treedef,
        [jax.ShapeDtypeStruct((rows, ki.LANES), r.dtype) for r in acc_refs])
    ident_acc = op.identity(acc_like)

    @pl.when(g == 0)
    def _init():
        for ar, ia in zip(acc_refs, jax.tree.leaves(ident_acc)):
            ar[...] = ia

    x = jax.tree.unflatten(in_treedef, [xr[...] for xr in x_refs])
    vals = f(x)

    ridx = jax.lax.broadcasted_iota(jnp.int32, (rows, ki.LANES), 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (rows, ki.LANES), 1)
    valid = (g * block + ridx * ki.LANES + cidx) < n
    vals = jax.tree.map(lambda v, i: jnp.where(valid, v, i), vals, ident_acc)

    acc = jax.tree.unflatten(out_treedef, [ar[...] for ar in acc_refs])
    acc = op(acc, vals)
    for ar, a in zip(acc_refs, jax.tree.leaves(acc)):
        ar[...] = a

    @pl.when(g == ng - 1)
    def _finalize():
        r = ki.tile_reduce(op, acc, axis=0)
        r = ki.tile_reduce(op, r, axis=1)
        for orf, l in zip(o_refs, jax.tree.leaves(r)):
            orf[...] = l


def mapreduce_1d_pallas(f, op, xs: Pytree, *,
                        policy: ki.TuningPolicy | None = None,
                        interpret: bool = False) -> Pytree:
    """op-reduce of ``f(x)`` over flat ``(n,)`` pytree leaves -> scalar pytree.

    ``op`` must be commutative (paper §II-C requires commutativity for
    mapreduce; scan relaxes it).
    """
    assert op.commutative, "mapreduce requires a commutative operator (use scan)"
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    in_leaves, in_treedef = jax.tree.flatten(xs)
    n = in_leaves[0].shape[0]
    assert all(l.shape == (n,) for l in in_leaves)

    # Trace f on abstract tiles to learn the mapped (output) structure.
    out_shape_tree = jax.eval_shape(
        f, jax.tree.unflatten(
            in_treedef,
            [jax.ShapeDtypeStruct((1, ki.LANES), l.dtype) for l in in_leaves]))
    out_leaves, out_treedef = jax.tree.flatten(out_shape_tree)

    sub = max(ki.min_tile(l.dtype)[0] for l in in_leaves)
    views = [ki.lane_rows(l, policy.nitem_reduce * sub, sub)
             for l in in_leaves]
    rows = views[0][1]
    m = views[0][0].shape[0]

    kernel = functools.partial(
        _mapreduce_kernel, f, op, in_treedef, out_treedef, n, rows,
        len(in_leaves), len(out_leaves), 0)
    out = pl.pallas_call(
        kernel,
        grid=(ki.cdiv(m, rows),),
        in_specs=[pl.BlockSpec((rows, ki.LANES), lambda g: (g, 0))
                  for _ in in_leaves],
        out_specs=[pl.BlockSpec((1, 1), lambda g: (0, 0)) for _ in out_leaves],
        out_shape=[jax.ShapeDtypeStruct((1, 1), l.dtype) for l in out_leaves],
        scratch_shapes=[pltpu.VMEM((rows, ki.LANES), l.dtype)
                        for l in out_leaves],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*[v for v, _ in views])
    return jax.tree.unflatten(out_treedef, [o[0, 0] for o in out])
