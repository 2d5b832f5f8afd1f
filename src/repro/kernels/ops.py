"""jit-ready wrappers + backend registration for every kernel.

This module is the "package extension" of the two-layer design: it registers
each (primitive, layout) route's implementations with the Layer-1 registry
(``core.intrinsics``) under four backends:

* ``pallas-tpu``       -- the Pallas kernels, compiled by Mosaic (TARGET);
* ``pallas-gpu``       -- the GPU kernel bodies (kernels/gpu.py):
                          decoupled-lookback scan, two-phase (partials)
                          mapreduce and semiring matvec/vecmat -- compiled
                          by Triton/Mosaic-GPU on a GPU platform,
                          interpreted elsewhere (the kernels auto-detect).
                          The scan routes dispatch to xla on real hardware
                          until the acquire-spin lookback lands (the
                          single-probe form is exact only on in-order
                          grids; see _gpu_lookback_unavailable);
* ``pallas-interpret`` -- the TPU kernel bodies executed in Python on CPU
                          (correctness validation of the TPU path);
* ``xla``              -- portable pure-XLA fallbacks (used by the CPU
                          dry-run; also the baseline the benchmarks compare
                          bytes-moved against).

Registration is table-driven: ``IMPLS`` below maps every route key
(``"scan@batched"``) to its per-backend implementations, and the module
asserts at import time that the table covers exactly the routes declared in
the ``PrimitiveDef`` registry -- adding a route without implementations (or
an implementation without a registry row) is an import error, not a latent
dispatch failure.  Validation, zero-extent guards and non-commutative
rerouting live in the registry's dispatch pipeline, so the wrappers here
only ever see well-formed, non-empty problems through the public API.

The algorithmic layer (``core.primitives``) never names a backend.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import intrinsics as ki
from repro.core import operators as alg
from repro.distributed import primitives as dist_k
from repro.kernels import batched as batched_k
from repro.kernels import copy as copy_k
from repro.kernels import gpu as gpu_k
from repro.kernels import mapreduce as mapreduce_k
from repro.kernels import matvec as matvec_k
from repro.kernels import ref
from repro.kernels import scan as scan_k
from repro.kernels import segmented as seg_k
from repro.kernels import sort as sort_k

Pytree = Any


# ---------------------------------------------------------------------------
# copy
# ---------------------------------------------------------------------------


def _copy_xla(x, *, nitem=None, policy=None):
    return jnp.copy(x)


# ---------------------------------------------------------------------------
# scan@flat
# ---------------------------------------------------------------------------


def _scan_pallas(op, xs, *, axis=0, inclusive=True, reverse=False,
                 interpret=False, policy=None):
    leaves = jax.tree.leaves(xs)
    ndim = leaves[0].ndim
    if ndim == 1:
        if reverse:
            xs = jax.tree.map(lambda l: jnp.flip(l, 0), xs)
        out = scan_k.scan_1d_pallas(op, xs, inclusive=inclusive,
                                    policy=policy, interpret=interpret)
        if reverse:
            out = jax.tree.map(lambda l: jnp.flip(l, 0), out)
        return out
    if ndim == 3 and axis == 1:
        return scan_k.scan_channel_pallas(
            op, xs, inclusive=inclusive, reverse=reverse, policy=policy,
            interpret=interpret)
    # Other layouts: normalize to (B, T, C) via moveaxis (metadata-only when
    # already contiguous along the scan axis).
    if ndim == 2:
        xs3 = jax.tree.map(lambda l: jnp.moveaxis(l, axis, 1)[:, :, None], xs)
        out = scan_k.scan_channel_pallas(
            op, xs3, inclusive=inclusive, reverse=reverse, policy=policy,
            interpret=interpret)
        return jax.tree.map(lambda l: jnp.moveaxis(l[:, :, 0], 1, axis), out)
    # >=3D general axis: flatten around the scan axis.
    def to3(l):
        l = jnp.moveaxis(l, axis, 1)
        lead = l.shape[0]
        t = l.shape[1]
        rest = int(np_prod(l.shape[2:])) if l.ndim > 2 else 1
        return l.reshape(lead, t, rest), l.shape

    shapes = [to3(l)[1] for l in leaves]
    xs3 = jax.tree.map(lambda l: to3(l)[0], xs)
    out = scan_k.scan_channel_pallas(
        op, xs3, inclusive=inclusive, reverse=reverse, policy=policy,
        interpret=interpret)
    outs = [l.reshape(s) for l, s in zip(jax.tree.leaves(out), shapes)]
    outs = [jnp.moveaxis(l, 1, axis) for l in outs]
    return jax.tree.unflatten(jax.tree.structure(xs), outs)


def np_prod(t):
    r = 1
    for v in t:
        r *= int(v)
    return r


def _scan_xla(op, xs, *, axis=0, inclusive=True, reverse=False, policy=None):
    return ref.ref_scan(op, xs, axis=axis, inclusive=inclusive, reverse=reverse)


# ---------------------------------------------------------------------------
# pallas-gpu wrappers: shape normalization onto the flat/batched lookback
# kernels (kernels/gpu.py).  The GPU kernels scan along the minor axis, so
# every other layout is moveaxis-normalized to (lead, n).
# ---------------------------------------------------------------------------


def _gpu_lookback_unavailable(interpret):
    """True when the lookback scan would have to *compile* for real GPU
    hardware, where the single-probe form races (kernels/gpu.py): the
    registered scan routes take the portable xla path instead, so the racy
    lowering is unreachable by default."""
    return (not gpu_k._auto_interpret(interpret)
            and not gpu_k.HARDWARE_LOOKBACK_READY)


def _scan_gpu(op, xs, *, axis=0, inclusive=True, reverse=False,
              interpret=None, policy=None):
    if _gpu_lookback_unavailable(interpret):
        return _scan_xla(op, xs, axis=axis, inclusive=inclusive,
                         reverse=reverse, policy=policy)
    leaves = jax.tree.leaves(xs)
    ndim = leaves[0].ndim
    if reverse:
        xs = jax.tree.map(lambda l: jnp.flip(l, axis), xs)
    if ndim == 1:
        out = gpu_k.scan_flat_gpu(op, xs, inclusive=inclusive,
                                  policy=policy, interpret=interpret)
    else:
        def to2(l):
            l2 = jnp.moveaxis(l, axis, -1)
            return l2.reshape(-1, l2.shape[-1]), l2.shape

        shapes = [to2(l)[1] for l in leaves]
        xs2 = jax.tree.map(lambda l: to2(l)[0], xs)
        out2 = gpu_k.scan_batched_gpu(op, xs2, inclusive=inclusive,
                                      policy=policy, interpret=interpret)
        outs = [jnp.moveaxis(l.reshape(s), -1, axis)
                for l, s in zip(jax.tree.leaves(out2), shapes)]
        out = jax.tree.unflatten(jax.tree.structure(xs), outs)
    if reverse:
        out = jax.tree.map(lambda l: jnp.flip(l, axis), out)
    return out


def _batched_scan_gpu(op, xs, *, inclusive=True, reverse=False,
                      interpret=None, policy=None):
    if _gpu_lookback_unavailable(interpret):
        return _batched_scan_xla(op, xs, inclusive=inclusive,
                                 reverse=reverse, policy=policy)
    if reverse:
        xs = jax.tree.map(lambda l: jnp.flip(l, 1), xs)
    out = gpu_k.scan_batched_gpu(op, xs, inclusive=inclusive,
                                 policy=policy, interpret=interpret)
    if reverse:
        out = jax.tree.map(lambda l: jnp.flip(l, 1), out)
    return out


def _mapreduce_gpu(f, op, xs, *, axis=None, interpret=None, policy=None):
    leaves = jax.tree.leaves(xs)
    ndim = leaves[0].ndim
    if axis is None:
        flat = jax.tree.map(lambda l: l.reshape(-1), xs)
        return gpu_k.mapreduce_flat_gpu(f, op, flat, policy=policy,
                                        interpret=interpret)
    if ndim == 2:
        # Rows of the batched reducer are whichever axis survives: reducing
        # axis 0 transposes so columns become independent rows.
        if axis == 0:
            xs = jax.tree.map(lambda l: l.T, xs)
        return gpu_k.mapreduce_batched_gpu(f, op, xs, policy=policy,
                                           interpret=interpret)
    raise NotImplementedError("mapreduce: gpu path supports axis=None or 2D")


def _linrec_gpu(a, b, h0=None, *, reverse=False, interpret=None, policy=None):
    if _gpu_lookback_unavailable(interpret):
        return _linrec_xla(a, b, h0, reverse=reverse, policy=policy)
    A, B = _scan_gpu(alg.AFFINE, (a, b), axis=1, inclusive=True,
                     reverse=reverse, interpret=interpret, policy=policy)
    if h0 is None:
        return B
    return A * h0[:, None, :] + B


# ---------------------------------------------------------------------------
# scan@segmented / mapreduce@segmented (ragged workloads)
# ---------------------------------------------------------------------------


def _segment_flags(xs, flags, offsets):
    """Normalize either segment descriptor to a flag array (the dispatch
    layer has already validated that exactly one is present)."""
    n = jax.tree.leaves(xs)[0].shape[0]
    if offsets is not None:
        return seg_k.offsets_to_flags(offsets, n)
    return flags.astype(jnp.int32)


def _segmented_scan_pallas(op, xs, *, flags=None, offsets=None, inclusive=True,
                           interpret=False, policy=None):
    f = _segment_flags(xs, flags, offsets)
    return seg_k.segmented_scan_1d_pallas(
        op, xs, f, inclusive=inclusive, policy=policy, interpret=interpret)


def _segmented_scan_xla(op, xs, *, flags=None, offsets=None, inclusive=True,
                        policy=None):
    """Portable path: associative_scan of the lifted (flag, value) operator."""
    f = _segment_flags(xs, flags, offsets)
    seg = alg.segmented(op)
    _, incl = jax.lax.associative_scan(seg.combine, (f, xs), axis=0)
    if inclusive:
        return incl
    ident = op.identity(jax.tree.map(lambda l: l[:1], xs))
    shifted = jax.tree.map(
        lambda l, i: jnp.concatenate([i, l[:-1]], axis=0), incl, ident)
    ident_full = op.identity(incl)
    return jax.tree.map(
        lambda s, i: jnp.where(f != 0, i, s), shifted, ident_full)


def _segmented_mapreduce_pallas(f, op, xs, *, flags=None, offsets=None,
                                num_segments=None, interpret=False,
                                policy=None):
    fl = _segment_flags(xs, flags, offsets)
    vals = f(xs)
    incl = seg_k.segmented_scan_1d_pallas(
        op, vals, fl, inclusive=True, policy=policy, interpret=interpret)
    return seg_k.gather_segment_lasts(
        op, incl, offsets=offsets, flags=None if offsets is not None else fl,
        num_segments=num_segments)


def _segmented_mapreduce_xla(f, op, xs, *, flags=None, offsets=None,
                             num_segments=None, policy=None):
    fl = _segment_flags(xs, flags, offsets)
    vals = f(xs)
    # Fast path: the standard algebra over plain arrays maps onto XLA's
    # native segment reductions.
    direct = {"add": jax.ops.segment_sum, "mul": jax.ops.segment_prod,
              "max": jax.ops.segment_max, "min": jax.ops.segment_min}
    ns = num_segments if offsets is None else offsets.shape[0] - 1
    if op.name in direct and isinstance(vals, jax.Array) and ns is not None:
        seg_ids = (seg_k.flags_to_segment_ids(fl, backend="xla")
                   if offsets is None else
                   jnp.searchsorted(offsets[1:], jnp.arange(vals.shape[0]),
                                    side="right"))
        return direct[op.name](vals, seg_ids, num_segments=ns)
    seg = alg.segmented(op)
    _, incl = jax.lax.associative_scan(seg.combine, (fl, vals), axis=0)
    return seg_k.gather_segment_lasts(
        op, incl, offsets=offsets, flags=None if offsets is not None else fl,
        num_segments=num_segments)


# ---------------------------------------------------------------------------
# mapreduce@flat
# ---------------------------------------------------------------------------


def _mapreduce_pallas(f, op, xs, *, axis=None, interpret=False, policy=None):
    leaves = jax.tree.leaves(xs)
    ndim = leaves[0].ndim
    if axis is None:
        flat = jax.tree.map(lambda l: l.reshape(-1), xs)
        return mapreduce_k.mapreduce_1d_pallas(
            f, op, flat, policy=policy, interpret=interpret)
    if ndim == 2 and isinstance(xs, jax.Array):
        policy_ = policy or ki.resolve_tuning("interpret" if interpret else None)
        sub = ki.min_tile(xs.dtype)[0]
        n, p = xs.shape
        if axis == 0:
            # Reduce over rows -> one value per column: the matvec path
            # (paper §V-A dispatches 2-D mapreduce to the matvec kernels).
            dummy = jnp.zeros((n, 1), xs.dtype)
            return matvec_k.matvec_pallas(
                lambda _x, a: f(a), op, xs, dummy[:, 0],
                block_rows=policy_.matvec_rows * sub,
                block_cols=policy_.matvec_cols * ki.LANES,
                interpret=interpret)
        dummy = jnp.zeros((p,), xs.dtype)
        return matvec_k.vecmat_pallas(
            lambda a, _x: f(a), op, xs, dummy,
            block_rows=policy_.vecmat_rows * sub,
            block_cols=policy_.vecmat_cols * ki.LANES,
            interpret=interpret)
    raise NotImplementedError("mapreduce: pallas path supports axis=None or 2D")


def _mapreduce_xla(f, op, xs, *, axis=None, policy=None):
    # Fast paths for the standard algebra (XLA reductions); generic fallback
    # via associative_scan otherwise.
    direct = {"add": jnp.sum, "mul": jnp.prod, "max": jnp.max, "min": jnp.min}
    vals = f(xs)
    if op.name in direct and isinstance(vals, jax.Array):
        return direct[op.name](vals, axis=axis)
    if op.name == "logsumexp" and isinstance(vals, jax.Array):
        return jax.scipy.special.logsumexp(vals, axis=axis)
    return ref.ref_mapreduce(f, op, xs, axis=axis)


# ---------------------------------------------------------------------------
# matvec@flat / vecmat@flat (semiring generalized forms)
# ---------------------------------------------------------------------------


def _pick_blocks_matvec(policy, A, n, p):
    sub = ki.min_tile(A.dtype)[0]
    rn = policy.matvec_rows * sub
    cp = policy.matvec_cols * ki.LANES
    if p <= ki.LANES:                      # tall-narrow: stride more rows
        cp = ki.LANES
        rn = rn * 4
    elif n <= 8 * sub:                     # wide-short: widen columns
        cp = cp * 4
    rn = min(rn, ki.round_up(n, sub))
    cp = min(cp, ki.round_up(p, ki.LANES))
    return rn, cp


def _pick_blocks_vecmat(policy, A, n, p):
    sub = ki.min_tile(A.dtype)[0]
    ri = policy.vecmat_rows * sub
    cj = policy.vecmat_cols * ki.LANES
    if n <= 8:                              # short: widen columns
        cj = cj * 4
    elif p <= ki.LANES:                     # narrow: more rows
        ri = ri * 4
    ri = min(ri, ki.round_up(n, sub))
    cj = min(cj, ki.round_up(p, ki.LANES))
    return ri, cj


def _quant_row_block(bn: int, q) -> int:
    """Round a picked row-block extent up to whole ``q.block`` scale rows so
    every value tile owns complete scale rows (kernels/matvec.py enforces
    the invariant)."""
    return ki.round_up(bn, q.block)


def _matvec_pallas(f, op, A, x, *, interpret=False, policy=None):
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    n, p = A.shape
    if isinstance(A, alg.Quantized):
        rn, cp = _pick_blocks_matvec(policy, A, n, p)
        rn = _quant_row_block(rn, A)
        return matvec_k.matvec_quantized_pallas(
            f, op, A, x, block_rows=rn, block_cols=cp, interpret=interpret)
    if p <= 64 and n >= 4 * ki.LANES and getattr(op, "commutative", False):
        # Tall-narrow: lane-packed kernel (EXPERIMENTS.md §Kernel gap fix) --
        # g = 128//p row groups share the lanes instead of padding p to 128.
        # Commutative-only: groups interleave rows (i -> group i mod g).
        return matvec_k.matvec_packed_pallas(
            f, op, A, x, block_rows=policy.matvec_rows * ki.min_tile(A.dtype)[0],
            interpret=interpret)
    rn, cp = _pick_blocks_matvec(policy, A, n, p)
    return matvec_k.matvec_pallas(f, op, A, x, block_rows=rn, block_cols=cp,
                                  interpret=interpret)


def _vecmat_pallas(f, op, A, x, *, interpret=False, policy=None):
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    n, p = A.shape
    ri, cj = _pick_blocks_vecmat(policy, A, n, p)
    if isinstance(A, alg.Quantized):
        ri = _quant_row_block(ri, A)
        return matvec_k.vecmat_quantized_pallas(
            f, op, A, x, block_rows=ri, block_cols=cj, interpret=interpret)
    return matvec_k.vecmat_pallas(f, op, A, x, block_rows=ri, block_cols=cj,
                                  interpret=interpret)


def _matvec_xla(f, op, A, x, *, policy=None):
    if isinstance(A, alg.Quantized):
        A = A.dequantize()      # reference lowering: dequantize, then dense
    if op.name == "add" and _is_arithmetic(f, x, A):
        # Standard semiring -> MXU-friendly contraction.
        return jnp.einsum("n,np->p", x, A)
    return ref.ref_matvec(f, op, A, x)


def _vecmat_xla(f, op, A, x, *, policy=None):
    if isinstance(A, alg.Quantized):
        A = A.dequantize()
    if op.name == "add" and _is_arithmetic(f, x, A):
        return jnp.einsum("np,p->n", A, x)
    return ref.ref_vecmat(f, op, A, x)


def _is_arithmetic(f, x, A):
    """Detect f == multiply by probing on tiny concrete values."""
    try:
        a = f(jnp.asarray(3.0, x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32),
              jnp.asarray(5.0, A.dtype if jnp.issubdtype(A.dtype, jnp.floating) else jnp.float32))
        return isinstance(a, jax.Array) and a.shape == () and float(a) == 15.0
    except Exception:
        return False


# ---------------------------------------------------------------------------
# linear recurrence  h_t = a_t * h_{t-1} + b_t  on (B, T, C)
#
# The (B, T, C) channelwise scan IS the grid-batched layout (batch and
# channel blocks ride parallel grid dimensions), so the same implementations
# serve the flat and batched routes; the batched route is the one consumers
# (serving, recurrent models) call and the one the tuner keys with a batch
# bucket.
# ---------------------------------------------------------------------------


def _linrec_pallas(a, b, h0=None, *, reverse=False, interpret=False,
                   policy=None):
    A, B = scan_k.scan_channel_pallas(
        alg.AFFINE, (a, b), inclusive=True, reverse=reverse, policy=policy,
        interpret=interpret)
    if h0 is None:
        return B
    return A * h0[:, None, :] + B


def _linrec_xla(a, b, h0=None, *, reverse=False, policy=None):
    return ref.ref_linear_recurrence(a, b, h0=h0, axis=1, reverse=reverse)


# ---------------------------------------------------------------------------
# Batched family: one launch per uniform batch of independent rows
# (kernels/batched.py).  Zero-extent edges (B == 0, n == 0, p == 0) and the
# non-commutative mapreduce reroute are resolved by the registry's dispatch
# pipeline, so these wrappers only see grids of extent >= 1 and commutative
# reductions.
# ---------------------------------------------------------------------------


def _batched_scan_pallas(op, xs, *, inclusive=True, reverse=False,
                         interpret=False, policy=None):
    if reverse:
        xs = jax.tree.map(lambda l: jnp.flip(l, 1), xs)
    out = batched_k.batched_scan_pallas(op, xs, inclusive=inclusive,
                                        policy=policy, interpret=interpret)
    if reverse:
        out = jax.tree.map(lambda l: jnp.flip(l, 1), out)
    return out


def _batched_scan_xla(op, xs, *, inclusive=True, reverse=False, policy=None):
    return ref.ref_scan(op, xs, axis=1, inclusive=inclusive, reverse=reverse)


def _batched_mapreduce_pallas(f, op, xs, *, interpret=False, policy=None):
    return batched_k.batched_mapreduce_pallas(
        f, op, xs, policy=policy, interpret=interpret)


def _batched_mapreduce_xla(f, op, xs, *, policy=None):
    direct = {"add": jnp.sum, "mul": jnp.prod, "max": jnp.max, "min": jnp.min}
    vals = f(xs)
    if op.name in direct and isinstance(vals, jax.Array):
        return direct[op.name](vals, axis=1)
    if op.name == "logsumexp" and isinstance(vals, jax.Array):
        return jax.scipy.special.logsumexp(vals, axis=1)
    scanned = jax.lax.associative_scan(op.combine, vals, axis=1)
    return jax.tree.map(lambda l: l[:, -1], scanned)


def _batched_matvec_pallas(f, op, A, x, *, interpret=False, policy=None):
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    rn, cp = _pick_blocks_matvec(policy, A, A.shape[1], A.shape[2])
    if isinstance(A, alg.Quantized):
        rn = _quant_row_block(rn, A)
        return batched_k.batched_matvec_quantized_pallas(
            f, op, A, x, block_rows=rn, block_cols=cp, interpret=interpret)
    return batched_k.batched_matvec_pallas(
        f, op, A, x, block_rows=rn, block_cols=cp, interpret=interpret)


def _batched_vecmat_pallas(f, op, A, x, *, interpret=False, policy=None):
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    ri, cj = _pick_blocks_vecmat(policy, A, A.shape[1], A.shape[2])
    if isinstance(A, alg.Quantized):
        ri = _quant_row_block(ri, A)
        return batched_k.batched_vecmat_quantized_pallas(
            f, op, A, x, block_rows=ri, block_cols=cj, interpret=interpret)
    return batched_k.batched_vecmat_pallas(
        f, op, A, x, block_rows=ri, block_cols=cj, interpret=interpret)


def _batched_matvec_xla(f, op, A, x, *, policy=None):
    if isinstance(A, alg.Quantized):
        A = A.dequantize()
    if op.name == "add" and _is_arithmetic(f, x, A):
        return jnp.einsum("bn,bnp->bp", x, A)
    vals = f(x[:, :, None], A)
    scanned = jax.lax.associative_scan(op.combine, vals, axis=1)
    return jax.tree.map(lambda l: l[:, -1], scanned)


def _batched_vecmat_xla(f, op, A, x, *, policy=None):
    if isinstance(A, alg.Quantized):
        A = A.dequantize()
    if op.name == "add" and _is_arithmetic(f, x, A):
        return jnp.einsum("bnp,bp->bn", A, x)
    vals = f(A, x[:, None, :])
    scanned = jax.lax.associative_scan(op.combine, vals, axis=2)
    return jax.tree.map(lambda l: l[:, :, -1], scanned)


# ---------------------------------------------------------------------------
# The registration table.  ``_pallas_pair`` expands one kernel body into the
# compiled and interpreted backends; the radix-sort family is one shared
# composition (kernels/sort.py) whose scan/mapreduce steps dispatch to the
# named sub-backend, so ``pallas-interpret`` runs the real kernel bodies and
# ``xla`` stays a pure portable fallback -- no backend-specific sort code.
# ---------------------------------------------------------------------------


def _pallas_pair(fn):
    return {"pallas-tpu": functools.partial(fn, interpret=False),
            "pallas-interpret": functools.partial(fn, interpret=True)}


def _per_backend(fn):
    # Compositions (radix sorts, sharded folds) take the backend their
    # scan/mapreduce building blocks dispatch to -- the same ``backend``
    # spelling as everywhere else, so each registered row just pins it.
    return {b: functools.partial(fn, backend=b)
            for b in ("pallas-tpu", "pallas-gpu", "pallas-interpret", "xla")}


IMPLS: dict[str, dict[str, Any]] = {
    "copy@flat": {**_pallas_pair(copy_k.copy_pallas), "xla": _copy_xla,
                  "pallas-gpu": gpu_k.copy_gpu},
    "scan@flat": {**_pallas_pair(_scan_pallas), "xla": _scan_xla,
                  "pallas-gpu": _scan_gpu},
    "scan@batched": {**_pallas_pair(_batched_scan_pallas),
                     "xla": _batched_scan_xla,
                     "pallas-gpu": _batched_scan_gpu},
    # scan@segmented / mapreduce@segmented have no native pallas-gpu rows
    # (yet): dispatch falls back to xla, and supports() reports it.
    "scan@segmented": {**_pallas_pair(_segmented_scan_pallas),
                       "xla": _segmented_scan_xla},
    "mapreduce@flat": {**_pallas_pair(_mapreduce_pallas),
                       "xla": _mapreduce_xla,
                       "pallas-gpu": _mapreduce_gpu},
    "mapreduce@batched": {**_pallas_pair(_batched_mapreduce_pallas),
                          "xla": _batched_mapreduce_xla,
                          "pallas-gpu": gpu_k.mapreduce_batched_gpu},
    "mapreduce@segmented": {**_pallas_pair(_segmented_mapreduce_pallas),
                            "xla": _segmented_mapreduce_xla},
    "matvec@flat": {**_pallas_pair(_matvec_pallas), "xla": _matvec_xla,
                    "pallas-gpu": gpu_k.matvec_gpu},
    "matvec@batched": {**_pallas_pair(_batched_matvec_pallas),
                       "xla": _batched_matvec_xla,
                       "pallas-gpu": gpu_k.batched_matvec_gpu},
    "vecmat@flat": {**_pallas_pair(_vecmat_pallas), "xla": _vecmat_xla,
                    "pallas-gpu": gpu_k.vecmat_gpu},
    "vecmat@batched": {**_pallas_pair(_batched_vecmat_pallas),
                       "xla": _batched_vecmat_xla,
                       "pallas-gpu": gpu_k.batched_vecmat_gpu},
    "linear_recurrence@flat": {**_pallas_pair(_linrec_pallas),
                               "xla": _linrec_xla,
                               "pallas-gpu": _linrec_gpu},
    "linear_recurrence@batched": {**_pallas_pair(_linrec_pallas),
                                  "xla": _linrec_xla,
                                  "pallas-gpu": _linrec_gpu},
    "sort@flat": _per_backend(sort_k.sort_radix),
    "sort@segmented": _per_backend(sort_k.segmented_sort_radix),
    "sort_pairs@flat": _per_backend(sort_k.sort_pairs_radix),
    "sort_pairs@segmented": _per_backend(sort_k.segmented_sort_pairs_radix),
    "argsort@flat": _per_backend(sort_k.argsort_radix),
    "argsort@segmented": _per_backend(sort_k.segmented_argsort_radix),
    "top_k@flat": _per_backend(sort_k.top_k_radix),
    "top_k@segmented": _per_backend(sort_k.segmented_top_k_radix),
    # Device-spanning routes (distributed/primitives.py): staged ShardPlans
    # -- the local route, the operator's collective fold, an epilogue --
    # executed by one chunked, overlap-capable driver.  ``backend`` names
    # the backend the shard-local stages dispatch to, so pallas-interpret
    # runs the real kernel bodies (and pallas-gpu the GPU lowerings) under
    # the collective composition.
    "scan@sharded": _per_backend(dist_k.sharded_scan),
    "mapreduce@sharded": _per_backend(dist_k.sharded_mapreduce),
    "matvec@sharded": _per_backend(dist_k.sharded_matvec),
    "vecmat@sharded": _per_backend(dist_k.sharded_vecmat),
    "linear_recurrence@sharded": _per_backend(dist_k.sharded_linear_recurrence),
    "sort_pairs@sharded": _per_backend(dist_k.sharded_sort_pairs),
    "top_k@sharded": _per_backend(dist_k.sharded_top_k),
}

# The registration table and the declarative PrimitiveDef registry must
# enumerate exactly the same routes, and every route must keep a portable
# fallback.  Raised (not assert) so the check survives python -O.
if set(IMPLS) != ki.route_keys():
    raise RuntimeError(
        "kernels/ops.py IMPLS out of sync with the PrimitiveDef registry: "
        f"missing={sorted(ki.route_keys() - set(IMPLS))} "
        f"extra={sorted(set(IMPLS) - ki.route_keys())}")
for _key, _impls in IMPLS.items():
    if "xla" not in _impls:
        raise RuntimeError(f"{_key}: every route needs an xla fallback")
    for _backend, _fn in _impls.items():
        ki.register_impl(_key, _backend)(_fn)
