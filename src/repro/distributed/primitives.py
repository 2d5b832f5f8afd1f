"""Device-spanning primitive lowerings: the staged ``@sharded`` routes.

The paper's thesis is that a thin layer of backend-agnostic intrinsics
(shuffles, ordered access, vectorized loads) is enough to build
vendor-competitive primitives portably.  The multi-device analogue of a warp
shuffle is a mesh collective, and this module is the analogue of
``kernels/*.py`` one level up -- with the same staging discipline the
kernels apply to DMA: every ``primitive@sharded`` route compiles to a
:class:`ShardPlan` of three stages,

    **local**      per-shard compute via the existing local route
                   ``-> (part, aux)``
    **collective** the cross-device fold of ``part``, built from the
                   operator algebra's :class:`~repro.core.operators.FoldSpec`
                   descriptor (``core.operators.collective_fold_spec``)
    **epilogue**   combine the folded result with the local ``aux``

and one driver, :func:`run_plan`, executes it.  Plans whose work is
elementwise over a non-stream axis declare ``chunk_axes``, and the driver
splits the operands into ``num_chunks`` slabs: with ``overlap=True`` the
collective for chunk *i* is dispatched as soon as chunk *i*'s local stage is
emitted -- before chunk *i+1*'s local stage -- so an async runtime (XLA with
``--xla_gpu_enable_async_collectives`` / the latency-hiding scheduler) can
run communication under the next chunk's compute.  ``overlap=False`` emits
every local stage, then every collective: the old blocking-barrier issue
order.  Both orders execute the *identical* per-chunk arithmetic, so they
are bit-identical by construction -- ``overlap`` is a scheduling knob, never
a numerics knob.  The chunk count is a tuned policy field
(``TuningPolicy.overlap_chunks``, raced on the topology-keyed ladder in
``core/tuning.py``).

The routes:

* ``scan@sharded``      -- local scan per shard, then an exclusive
  cross-device scan of the per-shard carries (gathered totals folded in
  axis order, so non-commutative operators stay valid).  Unchunkable: the
  stream axis is the scan axis.
* ``mapreduce@sharded`` -- local reduce along leaf axis 0, then the
  operator's collective fold.  Chunked along leaf axis 1 when every mapped
  leaf has one (the combine is elementwise over non-stream axes -- the same
  contract the tile kernels rely on when they slice elements into tiles).
* ``matvec@sharded`` / ``vecmat@sharded`` -- contraction-axis tensor
  parallelism: the contraction dimension (matvec rows / vecmat columns) is
  sharded, each device computes a strip partial with the local route, and
  the operator's collective fold (ADD -> psum for the decode GEMV) combines
  strip partials.  A ``< shards`` contraction remainder rides replicated
  and is folded in last by the epilogue, so uneven extents never pad the
  operand (no identity element of ``f`` exists in general).  Chunked along
  the *output* axis.
* ``linear_recurrence@sharded`` -- sequence (T) sharding for long-context
  prefill: local AFFINE scan per shard, exclusive cross-device carry of the
  per-shard (A, B) totals via the scan machinery, epilogue applies the
  incoming state.  Chunked along the channel axis.
* ``top_k@sharded``     -- per-shard top-k candidates, then a k-way partial
  merge of the gathered (value, global-index) candidates; tie-stability by
  global index is preserved because shards gather in axis order.
* ``sort_pairs@sharded`` -- shard-local sort, then a splitter exchange in
  portable form: gathered sorted runs are merged by cross-run rank
  (``searchsorted`` per run with the left/right side chosen by run order,
  the collision-free merge-path tie-break), and each shard keeps its slice
  of the global order.  The portable merge step gathers the full stream per
  device, so per-device memory on that step is O(n).

Two calling forms, selected by the layout descriptor
(``core.layout.Sharded``):

* ``mesh=`` given -- the global form: arguments are global arrays; the
  route wraps :func:`run_plan` in ``shard_map`` over the named axis,
  padding uneven leading extents with the operator's identity
  (scan/mapreduce), an order sentinel (sort family) or the affine identity
  (linear recurrence) and slicing the result back to size.
* ``mesh=None`` -- the in-mesh form: the caller is already inside a
  ``shard_map`` over the axis and passes its local shard; the plan runs
  directly.  This is how ``distributed/collectives.py`` dispatches the
  flash-decoding merge.

Registered for every backend in ``kernels/ops.py``; ``backend`` names the
backend the *local* stages dispatch to (the same spelling every primitive
uses), so ``pallas-interpret`` exercises the real kernel bodies and
``pallas-gpu`` runs the GPU lowerings under the collective composition.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import intrinsics as ki
from repro.core import operators as alg
from repro.core import tuning

Pytree = object


def _shard_map(local, mesh, in_specs, out_specs):
    """``jax.shard_map`` over ``mesh``, compiled as one program: called
    outside ``jit``, a bare shard_map runs its body op by op and compiles
    every op for the mesh."""
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _axis_extent(mesh, axis_name: str) -> int:
    return int(mesh.shape[axis_name])


def _lead(xs) -> int:
    return jax.tree.leaves(xs)[0].shape[0]


def _pad_with(xs: Pytree, pad: int, fill: Pytree) -> Pytree:
    """Append ``pad`` copies of the (1,)-leading ``fill`` element."""
    return jax.tree.map(
        lambda l, f: jnp.concatenate(
            [l, jnp.broadcast_to(f, (pad,) + l.shape[1:])], axis=0),
        xs, fill)


def _order_sentinel(dtype, key_bits, extreme: str):
    """A key that sorts past every real key under the pinned total order.

    ``max``: canonical NaN for floats (NaN ranks above +inf), the integer
    max (capped to ``key_bits`` for unsigned small-range keys); ``min``:
    -inf / the integer min / 0.  Ties against real extremes resolve
    real-first because padding is appended after the stream.
    """
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.nan if extreme == "max" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    if extreme == "min":
        return jnp.asarray(info.min, dtype)
    if key_bits is not None and jnp.issubdtype(dtype, jnp.unsignedinteger):
        return jnp.asarray((1 << int(key_bits)) - 1, dtype)
    return jnp.asarray(info.max, dtype)


# ---------------------------------------------------------------------------
# The staged plan and its driver.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """One ``@sharded`` route, staged: local -> collective -> epilogue.

    ``local(*operands) -> (part, aux)`` runs the per-shard compute;
    ``collective(part) -> folded`` is the cross-device stage (built once
    from the operator's :class:`~repro.core.operators.FoldSpec` or the
    exclusive-carry machinery); ``epilogue(folded, aux) -> out`` combines.

    ``chunk_axes`` (None: unchunkable) gives, per operand, the axis along
    which the driver may slice that operand into independent slabs -- a
    ``None`` entry marks an operand that is replicated across chunks.  It
    must be an axis over which the plan's arithmetic is elementwise, so
    chunked and unchunked execution agree; chunk outputs are concatenated
    along ``out_axis``.  ``fold`` carries the FoldSpec (when the collective
    stage is an operator fold) for introspection -- e.g. the analytic byte
    models price the collective stage off ``fold.collectives``.
    """

    name: str
    local: Callable
    collective: Callable
    epilogue: Callable
    fold: alg.FoldSpec | None = None
    chunk_axes: tuple | None = None
    out_axis: int = 0


def dispatch_collective(plan: ShardPlan, part: Pytree) -> Pytree:
    """The collective issue point of :func:`run_plan`.

    Every cross-device stage of every plan funnels through this one
    module-level function, so its position in the emission order *is* the
    overlap schedule -- and tests/CI can monkeypatch it to count chunked
    collective dispatches (the overlap smoke).
    """
    return plan.collective(part)


def _chunk_slices(extent: int, num_chunks: int) -> list[tuple[int, int]]:
    """(start, size) per chunk: even split, remainder spread over the first
    chunks, empty chunks dropped (extent < num_chunks)."""
    num_chunks = max(1, int(num_chunks))
    base, rem = divmod(int(extent), num_chunks)
    out, start = [], 0
    for i in range(num_chunks):
        size = base + (1 if i < rem else 0)
        if size:
            out.append((start, size))
        start += size
    return out


def _chunk_take(operand, axis, start, size):
    if axis is None or operand is None:
        return operand
    return jax.tree.map(
        lambda l: jax.lax.slice_in_dim(l, start, start + size, axis=axis),
        operand)


def run_plan(plan: ShardPlan, operands: tuple, *, num_chunks: int = 1,
             overlap: bool = True) -> Pytree:
    """Execute a :class:`ShardPlan` over its operands.

    With ``num_chunks > 1`` on a chunkable plan, the operands are sliced
    along ``plan.chunk_axes`` and the stages run per chunk.  ``overlap``
    selects the collective *issue order* only -- local(0), collective(0),
    local(1), collective(1), ... (True: chunk i's fold is in flight while
    chunk i+1 computes) versus all locals then all collectives (False: the
    blocking-barrier shape).  Both orders run the same per-chunk arithmetic
    on the same slices, so the results are bit-identical.
    """
    axes = plan.chunk_axes
    slices = None
    if axes is not None and num_chunks > 1:
        extent = None
        for operand, axis in zip(operands, axes):
            if axis is not None and operand is not None:
                extent = jax.tree.leaves(operand)[0].shape[axis]
                break
        if extent:
            slices = _chunk_slices(extent, num_chunks)
    if slices is None or len(slices) <= 1:
        part, aux = plan.local(*operands)
        return plan.epilogue(dispatch_collective(plan, part), aux)
    chunks = [tuple(_chunk_take(o, ax, start, size)
                    for o, ax in zip(operands, axes))
              for start, size in slices]
    if overlap:
        staged = []
        for ops_c in chunks:
            part, aux = plan.local(*ops_c)
            staged.append((dispatch_collective(plan, part), aux))
    else:
        parts = [plan.local(*ops_c) for ops_c in chunks]
        staged = [(dispatch_collective(plan, part), aux)
                  for part, aux in parts]
    outs = [plan.epilogue(folded, aux) for folded, aux in staged]
    return jax.tree.map(
        lambda *ls: jnp.concatenate(ls, axis=plan.out_axis), *outs)


# ---------------------------------------------------------------------------
# scan@sharded
# ---------------------------------------------------------------------------


def _exclusive_carry(op: alg.AssocOp, total: Pytree, axis_name: str) -> Pytree:
    """Exclusive cross-device scan of per-shard totals, in axis order.

    ``all_gather`` stacks the totals in axis-index order; the fold below
    combines exactly the shards *before* this one (a masked ordered fold
    over the static axis extent), so the carry is correct for
    non-commutative operators -- the distributed twin of the grid-carry
    protocol in kernels/scan.py.  Works for totals of any leaf shape (the
    gather stacks a new leading axis).
    """
    g = jax.tree.map(lambda l: jax.lax.all_gather(l, axis_name, axis=0),
                     total)
    extent = jax.tree.leaves(g)[0].shape[0]
    rank = jax.lax.axis_index(axis_name)
    carry = op.identity(total)
    for i in range(extent):
        step = op(carry, jax.tree.map(lambda l: l[i], g))
        carry = jax.tree.map(
            lambda s, c: jnp.where(i < rank, s, c), step, carry)
    return carry


def _scan_plan(op, *, axis_name, inclusive, backend, policy) -> ShardPlan:
    def local(xs_loc):
        incl = ki.dispatch("scan", None, backend, (op, xs_loc),
                           {"axis": 0, "inclusive": True, "reverse": False,
                            "policy": policy})
        total = jax.tree.map(lambda l: l[-1:], incl)
        return total, incl

    def epilogue(carry, incl):
        out = op(carry, incl)
        if not inclusive:
            # Shift right within the shard; slot 0 is exactly the carry (the
            # exclusive prefix of this shard's first element).
            out = jax.tree.map(
                lambda o, c: jnp.concatenate([c, o[:-1]], axis=0), out, carry)
        return out

    return ShardPlan(
        name="scan@sharded", local=local,
        collective=lambda total: _exclusive_carry(op, total, axis_name),
        epilogue=epilogue)


@ki.sub_backend_alias
def sharded_scan(op, xs, *, axis_name, mesh, inclusive=True, overlap=True,
                 backend="xla", policy=None):
    plan = _scan_plan(op, axis_name=axis_name, inclusive=inclusive,
                      backend=backend, policy=policy)
    if mesh is None:
        return run_plan(plan, (xs,), overlap=overlap)
    shards = _axis_extent(mesh, axis_name)
    n = _lead(xs)
    n_pad = -(-n // shards) * shards
    if n_pad != n:
        ident = op.identity(jax.tree.map(lambda l: l[:1], xs))
        xs = _pad_with(xs, n_pad - n, ident)

    def local(xs_loc):
        return run_plan(plan, (xs_loc,), overlap=overlap)

    out = _shard_map(local, mesh, (P(axis_name),), P(axis_name))(xs)
    if n_pad != n:
        out = jax.tree.map(lambda l: l[:n], out)
    return out


# ---------------------------------------------------------------------------
# mapreduce@sharded
# ---------------------------------------------------------------------------


def _fold_axis0(op, vals):
    """Balanced order-preserving fold of leaf axis 0 (any leaf ranks).

    Pairs adjacent elements each round -- a re-association, never a
    reordering, so it is exact for every associative operator including
    mixed-rank pytree elements (e.g. SOFTMAX_MERGE's (m, l, o)) that the
    uniform-shape tile combines cannot carry.
    """
    n = _lead(vals)
    while n > 1:
        even = n - (n % 2)
        lo = jax.tree.map(lambda l: l[0:even:2], vals)
        hi = jax.tree.map(lambda l: l[1:even:2], vals)
        merged = op(lo, hi)
        if n % 2:
            merged = jax.tree.map(
                lambda m, l: jnp.concatenate([m, l[even:]], axis=0),
                merged, vals)
        vals, n = merged, (n + 1) // 2
    return jax.tree.map(lambda l: l[0], vals)


def _reduce_local(op, vals_loc, *, backend, policy):
    """Reduce leaf axis 0 of the local shard, elementwise over the rest."""
    if all(l.ndim == 1 for l in jax.tree.leaves(vals_loc)):
        return ki.dispatch("mapreduce", None, backend,
                           (lambda v: v, op, vals_loc),
                           {"axis": None, "policy": policy})
    return _fold_axis0(op, vals_loc)


def _elementwise_chunk_axes(vals) -> tuple | None:
    """Chunk mapped values along leaf axis 1 when every leaf has one.

    Axis 0 is the reduced stream; the combine is elementwise over the rest
    (the contract the tile kernels already rely on when slicing elements
    into tiles), so slabbing axis 1 is exact.  Rank-1 leaves, or leaves
    whose axis-1 extents disagree, leave the plan unchunkable.
    """
    leaves = jax.tree.leaves(vals)
    if not leaves or any(l.ndim < 2 for l in leaves):
        return None
    if len({int(l.shape[1]) for l in leaves}) != 1:
        return None
    return (1,)


def _mapreduce_plan(op, *, axis_name, backend, policy,
                    chunk_axes) -> ShardPlan:
    spec = alg.collective_fold_spec(op)

    def local(vals_loc):
        return _reduce_local(op, vals_loc, backend=backend,
                             policy=policy), None

    return ShardPlan(
        name="mapreduce@sharded", local=local,
        collective=spec.build(axis_name),
        epilogue=lambda folded, aux: folded,
        fold=spec, chunk_axes=chunk_axes, out_axis=0)


@ki.sub_backend_alias
def sharded_mapreduce(f, op, xs, *, axis_name, mesh, overlap=True,
                      backend="xla", policy=None):
    num_chunks = tuning.resolve_overlap_chunks(policy, backend)
    if mesh is None:
        vals = f(xs)
        plan = _mapreduce_plan(op, axis_name=axis_name, backend=backend,
                               policy=policy,
                               chunk_axes=_elementwise_chunk_axes(vals))
        return run_plan(plan, (vals,), num_chunks=num_chunks,
                        overlap=overlap)
    shards = _axis_extent(mesh, axis_name)
    n = _lead(xs)
    if n == 0:
        one = jax.eval_shape(f, jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((1,) + l.shape[1:], l.dtype), xs))
        return op.identity(jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), one))
    vals = f(xs)
    n_pad = -(-n // shards) * shards
    if n_pad != n:
        ident = op.identity(jax.tree.map(lambda l: l[:1], vals))
        vals = _pad_with(vals, n_pad - n, ident)

    def local(vals_loc):
        plan = _mapreduce_plan(op, axis_name=axis_name, backend=backend,
                               policy=policy,
                               chunk_axes=_elementwise_chunk_axes(vals_loc))
        return run_plan(plan, (vals_loc,), num_chunks=num_chunks,
                        overlap=overlap)

    return _shard_map(local, mesh, (P(axis_name),), P())(vals)


# ---------------------------------------------------------------------------
# matvec@sharded / vecmat@sharded: contraction-axis tensor parallelism.
# ---------------------------------------------------------------------------


def _mv_plan(primitive, f, op, *, axis_name, backend, policy,
             remainder) -> ShardPlan:
    """Strip-partial plan shared by matvec (rows sharded, chunk output
    columns) and vecmat (columns sharded, chunk output rows).

    The contraction axis is sharded in contiguous blocks in axis order and
    the fold composes shards in axis order (the gather fallback for
    non-commutative operators), with the replicated remainder folded in
    last -- so the reduction order matches the flat route's element order
    exactly.
    """
    spec = alg.collective_fold_spec(op)
    out_chunk_axis = 1 if primitive == "matvec" else 0

    def local(A_loc, x_loc, *rem):
        part = ki.dispatch(primitive, None, backend, (f, op, A_loc, x_loc),
                           {"policy": policy})
        rem_part = None
        if remainder:
            A_rem, x_rem = rem
            rem_part = ki.dispatch(primitive, None, backend,
                                   (f, op, A_rem, x_rem), {"policy": policy})
        return part, rem_part

    def epilogue(folded, rem_part):
        if rem_part is None:
            return folded
        # Remainder rows/columns sit at the end of the contraction stream.
        return op(folded, rem_part)

    chunk_axes = ((out_chunk_axis, None, out_chunk_axis, None) if remainder
                  else (out_chunk_axis, None))
    return ShardPlan(
        name=f"{primitive}@sharded", local=local,
        collective=spec.build(axis_name), epilogue=epilogue,
        fold=spec, chunk_axes=chunk_axes, out_axis=0)


def _sharded_mv(primitive, f, op, A, x, *, axis_name, mesh, overlap,
                backend, policy):
    num_chunks = tuning.resolve_overlap_chunks(policy, backend)
    if mesh is None:
        plan = _mv_plan(primitive, f, op, axis_name=axis_name,
                        backend=backend, policy=policy, remainder=False)
        return run_plan(plan, (A, x), num_chunks=num_chunks, overlap=overlap)
    shards = _axis_extent(mesh, axis_name)
    contract_axis = 0 if primitive == "matvec" else 1
    n = A.shape[contract_axis]
    n_even = (n // shards) * shards
    if n_even == 0:
        # Fewer contraction elements than devices: nothing to distribute --
        # the flat route on the replicated operands is the whole problem.
        return ki.dispatch(primitive, None, backend, (f, op, A, x),
                           {"policy": policy})
    remainder = n_even != n
    plan = _mv_plan(primitive, f, op, axis_name=axis_name, backend=backend,
                    policy=policy, remainder=remainder)

    def local(*ops_loc):
        return run_plan(plan, ops_loc, num_chunks=num_chunks,
                        overlap=overlap)

    if primitive == "matvec":
        spec_even, spec_rep = P(axis_name, None), P(None, None)
        A_even, A_rem = A[:n_even], A[n_even:]
    else:
        spec_even, spec_rep = P(None, axis_name), P(None, None)
        A_even, A_rem = A[:, :n_even], A[:, n_even:]
    if remainder:
        args = (A_even, x[:n_even], A_rem, x[n_even:])
        in_specs = (spec_even, P(axis_name), spec_rep, P(None))
    else:
        args = (A_even, x)
        in_specs = (spec_even, P(axis_name))
    return _shard_map(local, mesh, in_specs, P())(*args)


@ki.sub_backend_alias
def sharded_matvec(f, op, A, x, *, axis_name, mesh, overlap=True,
                   backend="xla", policy=None):
    """y[j] = op_i f(x[i], A[i, j]) with rows i sharded over ``axis_name``."""
    return _sharded_mv("matvec", f, op, A, x, axis_name=axis_name, mesh=mesh,
                       overlap=overlap, backend=backend, policy=policy)


@ki.sub_backend_alias
def sharded_vecmat(f, op, A, x, *, axis_name, mesh, overlap=True,
                   backend="xla", policy=None):
    """z[i] = op_j f(A[i, j], x[j]) with columns j sharded over
    ``axis_name``."""
    return _sharded_mv("vecmat", f, op, A, x, axis_name=axis_name, mesh=mesh,
                       overlap=overlap, backend=backend, policy=policy)


# ---------------------------------------------------------------------------
# linear_recurrence@sharded: sequence (T) sharding with a cross-device carry.
# ---------------------------------------------------------------------------


def _linrec_plan(has_h0, *, axis_name, backend, policy) -> ShardPlan:
    def local(a_loc, b_loc, h0_c=None):
        incl_A, incl_B = ki.dispatch(
            "scan", None, backend, (alg.AFFINE, (a_loc, b_loc)),
            {"axis": 1, "inclusive": True, "reverse": False,
             "policy": policy})
        totals = (incl_A[:, -1], incl_B[:, -1])        # (B, C) each
        return totals, (incl_A, incl_B, h0_c)

    def collective(totals):
        # The affine maps of the shards before this one, composed in axis
        # order (AFFINE is non-commutative): E(h) = cA * h + cB.
        return _exclusive_carry(alg.AFFINE, totals, axis_name)

    def epilogue(carry, aux):
        cA, cB = carry
        incl_A, incl_B, h0_c = aux
        if h0_c is None:
            h = incl_A * cB[:, None, :] + incl_B
            # The first shard's carry is the identity (cB == 0); take its
            # incl_B directly so h0=None stays bit-identical to the flat
            # route, which never multiplies the absent state.
            return jnp.where(jax.lax.axis_index(axis_name) == 0, incl_B, h)
        h_in = cA * h0_c + cB
        return incl_A * h_in[:, None, :] + incl_B

    return ShardPlan(
        name="linear_recurrence@sharded", local=local, collective=collective,
        epilogue=epilogue, chunk_axes=(2, 2, 1) if has_h0 else (2, 2),
        out_axis=2)


@ki.sub_backend_alias
def sharded_linear_recurrence(a, b, *, h0=None, axis_name, mesh,
                              overlap=True, backend="xla", policy=None):
    """h_t = a_t * h_{t-1} + b_t over (B, T, C) with T sharded over
    ``axis_name``; ``h0`` (B, C) is replicated."""
    num_chunks = tuning.resolve_overlap_chunks(policy, backend)
    plan = _linrec_plan(h0 is not None, axis_name=axis_name, backend=backend,
                        policy=policy)
    operands = (a, b) if h0 is None else (a, b, h0)
    if mesh is None:
        return run_plan(plan, operands, num_chunks=num_chunks,
                        overlap=overlap)
    T = a.shape[1]
    if T == 0:
        return b
    shards = _axis_extent(mesh, axis_name)
    if shards == 1:
        # Degenerate axis: the flat route, bitwise.
        return ki.dispatch("linear_recurrence", None, backend, (a, b),
                           {"h0": h0, "reverse": False, "policy": policy})
    t_pad = -(-T // shards) * shards
    if t_pad != T:
        # The affine identity (a=1, b=0) propagates the running state
        # unchanged through padded steps, so the [:T] slice is exact.
        pad = t_pad - T
        a = jnp.concatenate(
            [a, jnp.ones((a.shape[0], pad, a.shape[2]), a.dtype)], axis=1)
        b = jnp.concatenate(
            [b, jnp.zeros((b.shape[0], pad, b.shape[2]), b.dtype)], axis=1)

    def local(*ops_loc):
        return run_plan(plan, ops_loc, num_chunks=num_chunks,
                        overlap=overlap)

    in_specs = (P(None, axis_name, None), P(None, axis_name, None))
    args = (a, b)
    if h0 is not None:
        in_specs += (P(None, None),)
        args += (h0,)
    h = _shard_map(local, mesh, in_specs, P(None, axis_name, None))(*args)
    if t_pad != T:
        h = h[:, :T]
    return h


# ---------------------------------------------------------------------------
# top_k@sharded
# ---------------------------------------------------------------------------


def _top_k_plan(k, *, axis_name, largest, key_bits, backend,
                policy) -> ShardPlan:
    def local(keys_loc):
        n_loc = keys_loc.shape[0]
        kk = min(k, n_loc)
        v, i = ki.dispatch("top_k", None, backend, (keys_loc, kk),
                           {"largest": largest, "key_bits": key_bits,
                            "policy": policy})
        gi = i + (jax.lax.axis_index(axis_name) * n_loc).astype(i.dtype)
        return (v, gi), n_loc

    def collective(part):
        return jax.tree.map(
            lambda l: jax.lax.all_gather(l, axis_name, axis=0), part)

    def epilogue(gathered, n_loc):
        gv, ggi = gathered                              # (S, kk), axis order
        shards = gv.shape[0]
        if k > shards * n_loc:
            raise ValueError(
                f"top_k@sharded: need 0 <= k <= n, got k={k}, "
                f"n={shards * n_loc}")
        # k-way partial merge: per-shard candidates are extreme-first and
        # tie-stable by local index; gathering in axis order makes the
        # stable merge sort tie-stable by *global* index -- identical to the
        # flat oracle's order.
        mv, mi = ki.dispatch("sort_pairs", None, backend,
                             (gv.reshape(-1), ggi.reshape(-1)),
                             {"descending": largest, "key_bits": key_bits,
                              "policy": policy})
        return mv[:k], mi[:k]

    return ShardPlan(name="top_k@sharded", local=local,
                     collective=collective, epilogue=epilogue)


@ki.sub_backend_alias
def sharded_top_k(keys, k, *, axis_name, mesh, largest=True, key_bits=None,
                  overlap=True, backend="xla", policy=None):
    if k == 0:
        return keys[:0], jnp.zeros((0,), jnp.int32)
    plan = _top_k_plan(k, axis_name=axis_name, largest=largest,
                       key_bits=key_bits, backend=backend, policy=policy)
    if mesh is None:
        return run_plan(plan, (keys,), overlap=overlap)
    n = keys.shape[0]
    if not 0 <= k <= n:
        raise ValueError(f"top_k@sharded: need 0 <= k <= n, got k={k}, n={n}")
    shards = _axis_extent(mesh, axis_name)
    n_pad = -(-n // shards) * shards
    if n_pad != n:
        # Pad with the key that *loses* every comparison, so padding can
        # only surface once all real candidates are taken (k <= n forbids
        # that); real extremes win ties because padding is appended last.
        sent = _order_sentinel(keys.dtype, key_bits,
                               "min" if largest else "max")
        keys = _pad_with(keys, n_pad - n, sent[None])

    def local(keys_loc):
        return run_plan(plan, (keys_loc,), overlap=overlap)

    return _shard_map(local, mesh, (P(axis_name),), (P(), P()))(keys)


# ---------------------------------------------------------------------------
# sort_pairs@sharded
# ---------------------------------------------------------------------------


def _sort_pairs_plan(*, axis_name, descending, key_bits, backend,
                     policy) -> ShardPlan:
    def local(keys_loc, values_loc):
        ks, vs = ki.dispatch("sort_pairs", None, backend,
                             (keys_loc, values_loc),
                             {"descending": descending, "key_bits": key_bits,
                              "policy": policy})
        return (ks, vs), None

    def collective(part):
        ks, vs = part
        gk = jax.lax.all_gather(ks, axis_name, axis=0)     # (S, n_loc)
        gv = jax.tree.map(
            lambda l: jax.lax.all_gather(l, axis_name, axis=0), vs)
        return gk, gv

    def epilogue(gathered, aux):
        # Splitter exchange, portable form.  Ranks are computed on the
        # pinned radix bit order (descending = complemented bits); the side
        # choice per run pair is the collision-free merge-path tie-break:
        # equal keys in an earlier run precede equal keys in a later run,
        # and local order breaks ties within a run -- i.e. global stability.
        # One gather of the sorted key runs (+ payload) crosses the wire;
        # the rank bits are a pure local function of the gathered keys,
        # recomputed rather than re-gathered.
        gk, gv = gathered
        gb = alg.key_to_radix_bits(gk)
        if descending:
            gb = ~gb
        shards, n_loc = gb.shape
        rank_self = jax.lax.axis_index(axis_name)

        bits_all = gb.reshape(-1)
        run_id = jnp.repeat(jnp.arange(shards, dtype=jnp.int32), n_loc)
        rank_all = jnp.tile(jnp.arange(n_loc, dtype=jnp.int32), shards)
        for t in range(shards):
            right = jnp.searchsorted(gb[t], bits_all, side="right")
            left = jnp.searchsorted(gb[t], bits_all, side="left")
            cnt = jnp.where(run_id > t, right, left).astype(jnp.int32)
            rank_all = rank_all + jnp.where(run_id == t, 0, cnt)

        # My output slice of the merged order: global positions
        # [rank_self * n_loc, (rank_self + 1) * n_loc).
        pos = rank_all - rank_self * n_loc
        pos = jnp.where((pos >= 0) & (pos < n_loc), pos, n_loc)  # OOB -> drop
        out_k = jnp.zeros((n_loc,), gk.dtype).at[pos].set(
            gk.reshape(-1), mode="drop")
        out_v = jax.tree.map(
            lambda l: jnp.zeros((n_loc,) + l.shape[2:], l.dtype).at[pos].set(
                l.reshape((-1,) + l.shape[2:]), mode="drop"),
            gv)
        return out_k, out_v

    return ShardPlan(name="sort_pairs@sharded", local=local,
                     collective=collective, epilogue=epilogue)


@ki.sub_backend_alias
def sharded_sort_pairs(keys, values, *, axis_name, mesh, descending=False,
                       key_bits=None, overlap=True, backend="xla",
                       policy=None):
    plan = _sort_pairs_plan(axis_name=axis_name, descending=descending,
                            key_bits=key_bits, backend=backend, policy=policy)
    if mesh is None:
        return run_plan(plan, (keys, values), overlap=overlap)
    n = keys.shape[0]
    if n == 0:
        return keys, values
    shards = _axis_extent(mesh, axis_name)
    n_pad = -(-n // shards) * shards
    if n_pad != n:
        # Padding sorts past every real key (ties resolve real-first), so
        # the [:n] slice of the merged stream is exactly the real sort.
        sent = _order_sentinel(keys.dtype, key_bits,
                               "min" if descending else "max")
        keys = _pad_with(keys, n_pad - n, sent[None])
        values = jax.tree.map(
            lambda l: jnp.concatenate(
                [l, jnp.zeros((n_pad - n,) + l.shape[1:], l.dtype)], axis=0),
            values)

    def local(keys_loc, values_loc):
        return run_plan(plan, (keys_loc, values_loc), overlap=overlap)

    out_k, out_v = _shard_map(
        local, mesh, (P(axis_name), P(axis_name)),
        (P(axis_name), P(axis_name)))(keys, values)
    if n_pad != n:
        out_k = out_k[:n]
        out_v = jax.tree.map(lambda l: l[:n], out_v)
    return out_k, out_v
