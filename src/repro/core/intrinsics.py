"""Layer 1 -- portable kernel intrinsics (the KernelIntrinsics.jl analogue).

KernelIntrinsics.jl isolates the three capabilities vendor-competitive
primitives need -- warp shuffles over arbitrary types, ordered memory access,
and vectorized loads -- behind backend-dispatched abstractions.  On TPU the
same *purposes* are served by different mechanisms (see DESIGN.md §2); this
module provides them:

* **In-tile combines** (:func:`tile_scan`, :func:`tile_reduce`): the shuffle
  analogue.  Log-step shifted combines over pytrees of tile values -- JAX
  tracing specializes the structural recursion at compile time like Julia's
  ``@generated``.  The *shift primitive* is flavor-dispatched
  (:class:`IntrinsicsFlavor`): the TPU flavor emits roll+select combines
  that lower to in-register VPU ops over ``(sublane, 128)`` tiles; the GPU
  flavor emits identity-padded shifts -- the ``shfl_up`` formulation, where
  lanes below the shift distance receive the operator identity so no
  post-combine select is needed.
* **Ordered visibility** (:func:`memory_fence`): the release/acquire
  analogue behind the decoupled-lookback scan.  The TPU flavor is the
  identity (grid steps execute sequentially per core, so prior tiles'
  aggregates are visible by construction); the GPU flavor pins ordering
  with an optimization barrier so the publish of a block's aggregate
  cannot be reordered past the status flag derived from it (a hardware
  Mosaic-GPU lowering strengthens this to a device-scope fence).
* **Alignment / vectorization helpers** (:func:`min_tile`,
  :func:`vec_width`, :func:`pattern_decompose`): the ``vload`` /
  ``vload_pattern`` analogue.  Block shapes are chosen so every transfer
  is wide and aligned -- ``vec_width`` is the float4-style vectorized
  load/store width hint the GPU block arithmetic uses; ragged tails become
  *statically generated* masked patterns, never dynamic shapes.
* **Tuning-policy dispatch** (:class:`TuningPolicy`): the paper's
  ``A40 <: Ampere <: AbstractArch`` hierarchy, as a chip-family registry
  resolved at trace time.
* **Backend dispatch** (:func:`register_impl` / :func:`resolve_impl`) and
  the **backend selection API** (:func:`use_backend`,
  :func:`available_backends`, :func:`supports`): the package-extension
  mechanism.  Algorithms in ``core/primitives.py`` never name a backend;
  implementations register themselves per backend and the dispatcher picks
  ``pallas-tpu`` on TPU, ``pallas-gpu`` on GPU, ``xla`` elsewhere --
  overridable per call (``backend=``) or per scope
  (``with use_backend("pallas-gpu"): ...``, thread-safe).
* **The primitive registry** (:class:`PrimitiveDef` / :class:`RouteDef` /
  :func:`dispatch`): the declarative table behind the layout-polymorphic
  Layer-2 API.  One row per (primitive, layout) names the registered
  implementation key (``"scan@batched"``), the validation rules (segment
  descriptor exclusivity, leaf-rank checks, commutativity requirements),
  the zero-extent behavior, and the tuning-key recipe -- so the guards,
  reroutes and cache keys are single data-driven implementations instead
  of per-family copies.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layout as lay

Pytree = Any

LANES = 128  # TPU vector lane count (minor-most tile dimension)
WARP = 32    # GPU subgroup width (warp/wavefront32; the shuffle scope)

_SUBLANE_BY_ITEMSIZE = {8: 4, 4: 8, 2: 16, 1: 32}


def min_tile(dtype) -> tuple[int, int]:
    """Minimum (sublane, lane) tile for ``dtype`` on current-gen TPUs."""
    itemsize = jnp.dtype(dtype).itemsize
    return (_SUBLANE_BY_ITEMSIZE.get(itemsize, 8), LANES)


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def lane_rows(x: jax.Array, rows: int, sub: int) -> tuple[jax.Array, int]:
    """View the last axis of ``x`` as ``(m, LANES)`` rows, the layout Mosaic
    tiles natively, for kernels whose blocks walk a flat extent.

    Returns ``(view, block_rows)``: ``block_rows`` is ``rows`` (a multiple
    of the sublane tile ``sub``), or -- for a view shorter than one block --
    the whole view, padded to whole ``(sub, LANES)`` tiles, since a block
    may only be smaller than the array in multiples of the tile.  Only a
    ragged tail is padded; kernels mask it by the true extent, and
    :func:`from_lane_rows` drops it again.
    """
    n = x.shape[-1]
    m = cdiv(n, LANES)
    if m < rows:
        rows = m = round_up(m, sub)
    if m * LANES != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, m * LANES - n)])
    return x.reshape(x.shape[:-1] + (m, LANES)), rows


def from_lane_rows(y: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`lane_rows`: ``(..., m, LANES)`` -> ``(..., n)``."""
    y = y.reshape(y.shape[:-2] + (-1,))
    return y if y.shape[-1] == n else y[..., :n]


# --------------------------------------------------------------------------
# vload_pattern analogue: static decomposition of a ragged extent.
# --------------------------------------------------------------------------


def pattern_decompose(n: int, block: int) -> tuple[int, int]:
    """Split extent ``n`` into (full_blocks, tail).

    The paper's ``vload_pattern`` emits an optimal aligned load sequence for a
    statically known misalignment; our blocks are always aligned (JAX arrays
    start aligned and block starts are multiples of the block shape), so the
    pattern reduces to (main body, masked tail).  The tail mask is generated
    at trace time from static shape arithmetic in the kernels.
    """
    return n // block, n % block


def tile_mask(tile_shape: Sequence[int], axis: int, start: Any, valid_until: Any):
    """Boolean mask marking in-bounds elements along ``axis`` of a tile.

    ``start`` is the global offset of the tile along ``axis`` (may be traced),
    ``valid_until`` the global extent.  Used for masked tail tiles.
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, tuple(tile_shape), axis)
    return (idx + start) < valid_until


# --------------------------------------------------------------------------
# Shuffle analogue: in-tile ordered scans and reductions over pytrees.
#
# The log-step structure is shared; the *shift primitive* underneath it is
# flavor-dispatched (IntrinsicsFlavor below), because TPUs and GPUs reach
# "combine with the value s slots back" through different hardware:
#
# * "tpu": roll the tile and select -- lowers to in-register VPU permutes
#   over (sublane, 128) tiles; no operator identity is needed.
# * "gpu": identity-padded shift -- the warp/subgroup ``shfl_up``
#   formulation, where slots below the shift distance receive the operator
#   identity so the combine is unconditional (no post-select), exactly the
#   shuffle-scan inner loop of the paper's KernelIntrinsics layer.
#
# Both produce bit-identical scans for any associative op (identity is
# two-sided), so every flavor validates against the same oracle.
# --------------------------------------------------------------------------


def _shift_along(x, s: int, axis: int):
    """Shift ``x`` by ``s`` along ``axis`` (towards higher indices)."""
    return jnp.roll(x, s, axis=axis)


def _tpu_shift_combine(op, x: Pytree, s: int, axis: int, idx) -> Pytree:
    """Roll + select: out[i] = i >= s ? op(x[i-s], x[i]) : x[i]."""
    shifted = jax.tree.map(lambda l: _shift_along(l, s, axis), x)
    combined = op(shifted, x)
    keep = idx >= s
    return jax.tree.map(lambda c, o: jnp.where(keep, c, o), combined, x)


def _slice_head(l, s: int, axis: int):
    sl = [slice(None)] * l.ndim
    sl[axis] = slice(0, l.shape[axis] - s)
    return l[tuple(sl)]


def _gpu_shift_combine(op, x: Pytree, s: int, axis: int, idx) -> Pytree:
    """shfl_up analogue: slots < s receive the operator identity, so the
    combine needs no keep-mask select afterwards."""
    def pad_shape(l):
        shape = list(l.shape)
        shape[axis] = s
        return jax.ShapeDtypeStruct(tuple(shape), l.dtype)

    ident = op.identity(jax.tree.map(pad_shape, x))
    shifted = jax.tree.map(
        lambda il, l: jnp.concatenate([il, _slice_head(l, s, axis)],
                                      axis=axis), ident, x)
    return op(shifted, x)


def _fence_noop(values: Pytree) -> Pytree:
    return values


def _fence_barrier(values: Pytree) -> Pytree:
    return jax.lax.optimization_barrier(values)


@dataclasses.dataclass(frozen=True)
class IntrinsicsFlavor:
    """One Layer-1 lowering strategy (per backend family).

    ``shift_combine`` is the primitive under :func:`tile_scan` /
    :func:`tile_reduce`; ``fence`` implements :func:`memory_fence`;
    ``vec_bytes`` is the default vectorized load/store transaction width
    :func:`vec_width` derives element counts from.
    """

    name: str
    shift_combine: Callable
    fence: Callable
    vec_bytes: int


_FLAVORS: dict[str, IntrinsicsFlavor] = {}
_BACKEND_FLAVOR: dict[str, str] = {}


def register_flavor(flavor: IntrinsicsFlavor, backends: Sequence[str] = ()):
    """Register a Layer-1 flavor and map backend names onto it."""
    _FLAVORS[flavor.name] = flavor
    for b in backends:
        _BACKEND_FLAVOR[b] = flavor.name


register_flavor(
    IntrinsicsFlavor("tpu", _tpu_shift_combine, _fence_noop,
                     vec_bytes=4 * LANES),
    backends=("pallas-tpu", "pallas-interpret", "xla"))
register_flavor(
    IntrinsicsFlavor("gpu", _gpu_shift_combine, _fence_barrier,
                     vec_bytes=16),
    backends=("pallas-gpu",))


def get_flavor(name_or_backend: str) -> IntrinsicsFlavor:
    """Resolve a flavor by its own name or by a backend name."""
    name = _BACKEND_FLAVOR.get(name_or_backend, name_or_backend)
    flavor = _FLAVORS.get(name)
    if flavor is None:
        raise ValueError(
            f"unknown intrinsics flavor {name_or_backend!r} "
            f"(flavors: {sorted(_FLAVORS)}; "
            f"backends: {sorted(_BACKEND_FLAVOR)})")
    return flavor


def memory_fence(values: Pytree, *, flavor: str = "tpu") -> Pytree:
    """Ordered-visibility edge: the returned values are guaranteed to be
    materialized before anything computed *from them* afterwards.

    Kernels thread a (publish, flag) pair through the fence so the status
    flag a successor observes cannot be reordered before the aggregate it
    guards -- the release/acquire protocol of decoupled lookback.  The TPU
    flavor is the identity (per-core sequential grids order memory by
    construction); the GPU flavor lowers to an optimization barrier today
    and is the seam where a hardware Mosaic-GPU lowering emits a
    device-scope fence.
    """
    return get_flavor(flavor).fence(values)


def vec_width(dtype, *, flavor: str = "gpu") -> int:
    """Elements per vectorized load/store transaction for ``dtype`` --
    the float4-style width hint (16-byte transactions on GPUs, a full
    lane-row on TPUs)."""
    return max(1, get_flavor(flavor).vec_bytes // jnp.dtype(dtype).itemsize)


def tile_scan(op, x: Pytree, axis: int, *, flavor: str = "tpu") -> Pytree:
    """In-order inclusive scan of a tile along ``axis`` (Hillis–Steele).

    log2(extent) shifted combines; order-preserving, so correct for
    non-commutative ``op`` (quaternions, affine maps, 2x2 matrices).
    The shift primitive is flavor-dispatched (see module docstring): the
    TPU form needs no identity (roll + select), the GPU form is the
    identity-padded ``shfl_up`` combine.
    """
    shift_combine = get_flavor(flavor).shift_combine
    leaves = jax.tree.leaves(x)
    extent = leaves[0].shape[axis]
    shape = leaves[0].shape
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    s = 1
    while s < extent:
        x = shift_combine(op, x, s, axis, idx)
        s *= 2
    return x


def tile_take_last(x: Pytree, axis: int) -> Pytree:
    """Slice the last element along ``axis`` (keepdims)."""
    def take(l):
        sl = [slice(None)] * l.ndim
        sl[axis] = slice(l.shape[axis] - 1, l.shape[axis])
        return l[tuple(sl)]

    return jax.tree.map(take, x)


def _split_along(x: Pytree, axis: int, k: int) -> tuple[Pytree, Pytree]:
    """Split pytree ``x`` into ([0:k], [k:2k]) slices along ``axis``."""
    treedef = jax.tree.structure(x)
    pairs = []
    for l in jax.tree.leaves(x):
        sl_lo = [slice(None)] * l.ndim
        sl_hi = [slice(None)] * l.ndim
        sl_lo[axis] = slice(0, k)
        sl_hi[axis] = slice(k, 2 * k)
        pairs.append((l[tuple(sl_lo)], l[tuple(sl_hi)]))
    lo = jax.tree.unflatten(treedef, [p[0] for p in pairs])
    hi = jax.tree.unflatten(treedef, [p[1] for p in pairs])
    return lo, hi


def tile_reduce(op, x: Pytree, axis: int, *, flavor: str = "tpu") -> Pytree:
    """Reduce a tile along ``axis``, keepdims.

    Commutative ops with power-of-two extents use a balanced halving fold
    (fewest combines, flavor-independent); otherwise an order-preserving
    flavored scan + take-last.  The commutativity dispatch is itself a
    tuning decision exposed by the operator algebra (DESIGN.md §3).
    """
    extent = jax.tree.leaves(x)[0].shape[axis]
    pow2 = extent > 0 and (extent & (extent - 1)) == 0
    if not getattr(op, "commutative", False) or not pow2:
        return tile_take_last(tile_scan(op, x, axis, flavor=flavor), axis)
    k = extent
    while k > 1:
        k //= 2
        lo, hi = _split_along(x, axis, k)
        x = op(lo, hi)
    return x


# --------------------------------------------------------------------------
# Tuning-policy dispatch hierarchy (A40 <: Ampere <: AbstractArch analogue).
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TuningPolicy:
    """Per-chip static kernel parameters, resolved at trace time."""

    name: str = "generic"
    # Items-per-grid-step multiplier (the paper's Nitem): how many minimum
    # tiles one grid step processes.  Scan uses a larger Nitem to amortize
    # carry propagation, exactly like the paper's 16-items-per-thread scan.
    nitem_copy: int = 8
    nitem_scan: int = 16
    nitem_reduce: int = 8
    # matvec / vecmat block parameters (rows, cols are in units of min tile).
    matvec_rows: int = 16
    matvec_cols: int = 2
    vecmat_rows: int = 8
    vecmat_cols: int = 8
    # Wide/tall shape cutover (aspect ratio heuristic, paper §V-C).
    tall_threshold: float = 64.0
    vmem_budget_bytes: int = 64 * 1024 * 1024
    # Radix-sort digit width in bits (2^bits buckets per pass).  Wider digits
    # mean fewer passes but a larger per-pass rank scan; the sweet spot is
    # shape- and chip-dependent, so it sits on the tuning ladder.
    sort_digit_bits: int = 8
    # GPU (pallas-gpu) block arithmetic: a block covers
    # gpu_threads x nitem_* x vec_width(dtype) elements -- threads per
    # block times the paper's items-per-thread times the vectorized
    # transaction width, so the existing nitem_* ladders race meaningful
    # GPU knobs with no new tuning keys.  gpu_vec_bytes is the vectorized
    # load/store transaction width (float4-style 128-bit accesses).
    gpu_threads: int = 128
    gpu_vec_bytes: int = 16
    # @sharded staged-plan driver (distributed/primitives.py): how many
    # slabs a chunkable plan splits into, so each slab's collective can be
    # issued while the next slab's local stage computes.  1 disables
    # chunking; the knob is raced on the topology-keyed tuning ladder (a
    # winner on one mesh shape is never replayed on another).
    overlap_chunks: int = 4


_TUNING_REGISTRY: dict[str, TuningPolicy] = {}
_TUNING_PARENTS: dict[str, str] = {}


def register_tuning(name: str, policy: TuningPolicy, parent: str = "generic"):
    _TUNING_REGISTRY[name] = policy
    _TUNING_PARENTS[name] = parent


register_tuning("generic", TuningPolicy())
# TPU v5e: 16 GiB HBM @ 819 GB/s, 197 bf16 TFLOP/s, ~128 MiB VMEM/core.
register_tuning(
    "tpu_v5e",
    TuningPolicy(name="tpu_v5e", nitem_scan=16, nitem_reduce=8, nitem_copy=8,
                 vmem_budget_bytes=96 * 1024 * 1024),
)
# v5p: larger HBM/bandwidth; deeper pipelining pays off.
register_tuning(
    "tpu_v5p",
    TuningPolicy(name="tpu_v5p", nitem_scan=32, nitem_reduce=16, nitem_copy=16,
                 vmem_budget_bytes=96 * 1024 * 1024),
    parent="tpu_v5e",
)
# Interpret mode: tiny tiles keep the Python loop fast while exercising the
# same code paths (masking, carries, patterns).
register_tuning(
    "interpret",
    TuningPolicy(name="interpret", nitem_copy=2, nitem_scan=2, nitem_reduce=2,
                 matvec_rows=2, matvec_cols=1, vecmat_rows=2, vecmat_cols=1,
                 sort_digit_bits=4, overlap_chunks=2),
)
# GPU family (the paper's A40 <: Ampere chain, across vendors): blocks are
# gpu_threads x nitem x vec elements.  Datacenter parts get more threads
# per block; the MI300 wavefront64 part doubles the subgroup multiple.
register_tuning("gpu_generic", TuningPolicy(name="gpu_generic"))
register_tuning(
    "gpu_a100",
    TuningPolicy(name="gpu_a100", nitem_scan=16, nitem_reduce=8,
                 gpu_threads=256),
    parent="gpu_generic")
register_tuning(
    "gpu_h100",
    TuningPolicy(name="gpu_h100", nitem_scan=32, nitem_reduce=16,
                 gpu_threads=256),
    parent="gpu_a100")
register_tuning(
    "gpu_mi300",
    TuningPolicy(name="gpu_mi300", nitem_scan=16, nitem_reduce=8,
                 gpu_threads=256),
    parent="gpu_generic")
# GPU kernel bodies under the Pallas interpreter (CI's gpu-interpret job):
# small blocks keep the Python grid loop fast, same code paths as hardware.
register_tuning(
    "gpu_interpret",
    TuningPolicy(name="gpu_interpret", nitem_scan=2, nitem_reduce=2,
                 nitem_copy=2, matvec_rows=2, matvec_cols=1, vecmat_rows=2,
                 vecmat_cols=1, sort_digit_bits=4, gpu_threads=32,
                 overlap_chunks=2),
    parent="gpu_generic")


def resolve_tuning(name: str | None = None) -> TuningPolicy:
    if name is None:
        name = detect_chip()
    while name not in _TUNING_REGISTRY:
        name = _TUNING_PARENTS.get(name, "generic")
    return _TUNING_REGISTRY[name]


_GPU_PLATFORMS = ("gpu", "cuda", "rocm")


def detect_chip() -> str:
    """Tuning-policy name of device 0.  A TPU this table does not know is
    named after its ``device_kind`` (e.g. ``tpu_v6_lite``), which
    :func:`resolve_tuning` maps to the generic policy -- never to another
    chip's policy."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        kind = dev.device_kind.lower()
        if "v5 lite" in kind or "v5e" in kind:
            return "tpu_v5e"
        if "v5p" in kind or kind == "tpu v5":
            return "tpu_v5p"
        return "tpu_" + "_".join(kind.removeprefix("tpu").split())
    if dev.platform in _GPU_PLATFORMS:
        kind = getattr(dev, "device_kind", "").lower()
        for tag, name in (("h100", "gpu_h100"), ("h200", "gpu_h100"),
                          ("a100", "gpu_a100"), ("mi300", "gpu_mi300"),
                          ("mi250", "gpu_mi300")):
            if tag in kind:
                return name
        return "gpu_generic"
    return "generic"


def default_policy_name(backend: str | None) -> str | None:
    """Tuning-policy name a backend's kernels should resolve when no policy
    is passed (None means: detect the chip).  Shared by the kernel wrappers
    and the autotuner hook so both start from the same base policy."""
    if backend == "pallas-interpret":
        return "interpret"
    if backend == "pallas-gpu":
        # On a real GPU the chip detector picks the family; everywhere else
        # the kernel bodies run under the interpreter and want tiny blocks.
        return None if jax.default_backend() in _GPU_PLATFORMS \
            else "gpu_interpret"
    return None


# --------------------------------------------------------------------------
# Backend dispatch registry (package-extension analogue) and the public
# backend-selection API: a thread-safe scoped override (use_backend) plus
# registry-driven capability queries (available_backends / supports).
# --------------------------------------------------------------------------

_IMPL_REGISTRY: dict[tuple[str, str], Callable] = {}
_FORCED_BACKEND: str | None = None           # legacy force_backend() shim
_FORCE_BACKEND_WARNED = False
# Optional autotuner hook (installed by core.tuning to avoid a layering
# cycle): called as hook(primitive, backend, impl) and may return a wrapped
# impl that injects a benchmarked TuningPolicy, or None to pass through.
_TUNER_HOOK: Callable[[str, str, Callable], Callable | None] | None = None


class _BackendScope(threading.local):
    """Per-thread stack of use_backend() overrides (innermost wins)."""

    def __init__(self):
        self.stack: list[str] = []


_BACKEND_SCOPE = _BackendScope()


def set_tuner_hook(hook: Callable | None):
    """Install (or clear) the autotune wrapper consulted by resolve_impl."""
    global _TUNER_HOOK
    _TUNER_HOOK = hook


def register_impl(primitive: str, backend: str):
    def deco(fn):
        _IMPL_REGISTRY[(primitive, backend)] = fn
        return fn

    return deco


def registered_backends(key: str) -> list[str]:
    """Backends with an implementation registered for ``key`` (sorted)."""
    return sorted(b for (p, b) in _IMPL_REGISTRY if p == key)


def _known_backends() -> set[str]:
    # Registration happens when kernels/ops.py imports; pull it in lazily so
    # the query API works from a bare `import repro` without making Layer 1
    # depend on the kernels package at import time.
    if not _IMPL_REGISTRY:
        from repro.kernels import ops as _ops  # noqa: F401
    return {b for (_, b) in _IMPL_REGISTRY}


def available_backends() -> tuple[str, ...]:
    """All backend names with at least one registered implementation."""
    return tuple(sorted(_known_backends()))


def supports(route: str, backend: str) -> bool:
    """Whether ``route`` (e.g. ``"scan@batched"``) has a native ``backend``
    implementation.  False means dispatch would use the xla fallback;
    unknown route or backend *names* raise ValueError, mirroring what
    dispatch itself (and :func:`use_backend`) would do with them."""
    if route not in route_keys():
        raise ValueError(
            f"unknown route {route!r} (routes: {sorted(route_keys())})")
    if backend not in _known_backends():
        raise ValueError(
            f"unknown backend {backend!r} "
            f"(available: {', '.join(available_backends())})")
    return (route, backend) in _IMPL_REGISTRY


@contextlib.contextmanager
def use_backend(backend: str):
    """Scoped backend override: ``with use_backend("pallas-gpu"): ...``.

    Thread-safe (each thread keeps its own stack; innermost scope wins) and
    validated against the registry up front, so a typo fails at the `with`
    statement rather than as a silent xla fallback deep in a trace.  An
    explicit ``backend=`` argument on a primitive call still takes
    precedence over the scope.
    """
    if backend not in _known_backends():
        raise ValueError(
            f"unknown backend {backend!r} "
            f"(available: {', '.join(available_backends())})")
    _BACKEND_SCOPE.stack.append(backend)
    try:
        yield backend
    finally:
        _BACKEND_SCOPE.stack.pop()


def force_backend(backend: str | None):
    """Deprecated: process-global backend pin.  Use :func:`use_backend`.

    Kept as a warn-once shim with unchanged behavior (a global default that
    scoped overrides and explicit ``backend=`` arguments still beat).
    """
    global _FORCED_BACKEND, _FORCE_BACKEND_WARNED
    if not _FORCE_BACKEND_WARNED:
        warnings.warn(
            "force_backend() is deprecated; use the scoped "
            "repro.use_backend(...) context manager instead",
            DeprecationWarning, stacklevel=2)
        _FORCE_BACKEND_WARNED = True
    _FORCED_BACKEND = backend


_SUB_BACKEND_WARNED = False


def sub_backend_alias(fn):
    """Deprecated-alias shim: the composition entry points (radix sorts,
    sharded folds) used to spell their backend parameter ``sub_backend=``.
    The alias still works -- warn once per process, like
    :func:`force_backend` -- and forwards to ``backend=``; passing both
    spellings is an error."""

    @functools.wraps(fn)
    def wrapper(*args, sub_backend=None, **kwargs):
        global _SUB_BACKEND_WARNED
        if sub_backend is not None:
            if "backend" in kwargs:
                raise TypeError(
                    f"{fn.__name__}: got both backend= and its deprecated "
                    "alias sub_backend=; pass backend= only")
            if not _SUB_BACKEND_WARNED:
                warnings.warn(
                    "the sub_backend= keyword is deprecated; compositions "
                    "now take the same backend= spelling as every other "
                    "primitive",
                    DeprecationWarning, stacklevel=2)
                _SUB_BACKEND_WARNED = True
            kwargs["backend"] = sub_backend
        return fn(*args, **kwargs)

    return wrapper


def current_backend() -> str:
    """The backend dispatch uses when no explicit ``backend=`` is passed:
    innermost use_backend() scope, else the (deprecated) forced global,
    else the platform default."""
    if _BACKEND_SCOPE.stack:
        return _BACKEND_SCOPE.stack[-1]
    if _FORCED_BACKEND is not None:
        return _FORCED_BACKEND
    platform = jax.default_backend()
    if platform == "tpu":
        return "pallas-tpu"
    if platform in _GPU_PLATFORMS:
        return "pallas-gpu"
    return "xla"


def resolve_impl(primitive: str, backend: str | None = None) -> Callable:
    backend = backend or current_backend()
    key = (primitive, backend)
    # Registers the kernels first: a lookup before kernels/ops.py is
    # imported would otherwise take the xla fallback below.
    known = _known_backends()
    impl = _IMPL_REGISTRY.get(key)
    if impl is None:
        if backend not in known:
            # Unknown backend *names* are user errors and fail loudly,
            # uniformly naming the route; known backends without a native
            # implementation for this route fall back below.
            raise ValueError(
                f"{primitive}: unknown backend {backend!r} "
                f"(available: {', '.join(available_backends())})")
        # Fall back to the portable XLA implementation -- the algorithmic
        # layer is always available even on backends with no Pallas lowering.
        impl = _IMPL_REGISTRY.get((primitive, "xla"))
    if impl is None:
        raise NotImplementedError(f"no implementation registered for {primitive}")
    if _TUNER_HOOK is not None:
        wrapped = _TUNER_HOOK(primitive, backend, impl)
        if wrapped is not None:
            return wrapped
    return impl


# --------------------------------------------------------------------------
# The declarative primitive registry: one table drives dispatch, validation,
# zero-extent guards, non-commutative rerouting, tuning keys and the
# generated docs/conformance enumerations.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TuneRecipe:
    """How to build a tuning cache key + which policy knobs to race.

    ``dims`` selects the generic key extractor in ``core.tuning``:

    * ``"flat"``   -- ``n`` = total element count over the data's leaves;
    * ``"row"``    -- ``(B, n)`` leaves: per-row extent + batch bucket;
    * ``"trail2"`` -- ``(B, d1, d2)`` leading leaf: the two trailing dims
      bucket *separately* (``"8192x128"``) because block selection branches
      on the aspect ratio, plus the batch bucket.
    """

    ladder: tuple  # TuningPolicy field-override dicts to race
    # Argument indices default to the enclosing RouteDef's data_arg/op_arg
    # (resolved in core.tuning) -- override only when the key should read a
    # different operand than dispatch validates.
    data_arg: int | None = None
    op_arg: int | None = None      # positional index of the AssocOp, or
    op_label: str | None = None    # a fixed label when the op is implicit
    dims: str = "flat"


@dataclasses.dataclass(frozen=True)
class RouteDef:
    """One (primitive, layout) row of the registry.

    ``args`` indices refer to the positional call convention of the public
    entry point (and of the registered implementations, which share it).
    """

    primitive: str
    layout: str
    data_arg: int = 0
    op_arg: int | None = None
    # ((arg index, required leaf rank), ...) -- checked on every leaf.
    arg_ranks: tuple = ()
    # ((kwarg name, required value), ...) -- kwargs the layout pins; they are
    # validated then stripped before the implementation call.
    fixed_kwargs: tuple = ()
    commutative_only: bool = False
    # Registered key to reroute non-commutative ops through (mapreduce ->
    # order-preserving scan of the mapped values, take-last).
    noncomm_route: str | None = None
    # Name of a shared zero-extent guard in _ZERO_GUARDS (None: the
    # implementation/composition handles zero extents itself).
    zero_extent: str | None = None
    needs_descriptor: bool = False    # Segmented: exactly one of flags/offsets
    needs_num_segments: bool = False  # Segmented flag variant: static extent
    # Sharded: validate the mesh/axis pair and inject them as kwargs
    # (axis_name=, mesh=) before the implementation call.
    needs_mesh: bool = False
    tuning: TuneRecipe | None = None
    notes: str = ""                   # surfaced in the generated docs table

    @property
    def key(self) -> str:
        return f"{self.primitive}@{self.layout}"


@dataclasses.dataclass(frozen=True)
class PrimitiveDef:
    """A public primitive and its layout routes."""

    name: str
    routes: dict  # layout kind -> RouteDef
    doc: str = ""


PRIMITIVE_DEFS: dict[str, PrimitiveDef] = {}


def define_primitive(name: str, *routes: RouteDef, doc: str = ""):
    PRIMITIVE_DEFS[name] = PrimitiveDef(
        name=name, routes={r.layout: r for r in routes}, doc=doc)


def iter_routes():
    """Every RouteDef in the registry, in definition order."""
    for pdef in PRIMITIVE_DEFS.values():
        yield from pdef.routes.values()


def route_keys() -> set[str]:
    return {r.key for r in iter_routes()}


def get_route(primitive: str, kind: str) -> RouteDef:
    pdef = PRIMITIVE_DEFS.get(primitive)
    if pdef is None:
        raise NotImplementedError(f"unknown primitive {primitive!r}")
    route = pdef.routes.get(kind)
    if route is None:
        raise ValueError(
            f"{primitive}: unsupported layout {kind!r} "
            f"(supported: {sorted(pdef.routes)})")
    return route


# -- shared zero-extent guards (single implementations, wired by name) ------


def _zg_passthrough(route, args, kwargs):
    """Any zero extent in the data: the input already is the output."""
    data = args[route.data_arg]
    lead = jax.tree.leaves(data)[0]
    if any(d == 0 for d in lead.shape):
        return True, data
    return False, None


def _zg_batched_reduce_identity(route, args, kwargs):
    """(B, 0) rows / B == 0: reducing zero elements yields identity rows."""
    f, op, xs = args[0], args[1], args[2]
    B, n = jax.tree.leaves(xs)[0].shape
    if B and n:
        return False, None
    one = jax.eval_shape(
        f, jax.tree.map(lambda l: jax.ShapeDtypeStruct((1, 1), l.dtype), xs))
    return True, op.identity(jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((B,), l.dtype), one))


def _zg_segmented_reduce_identity(route, args, kwargs):
    """Zero-length stream: every declared segment reduces to identity."""
    f, op, xs = args[0], args[1], args[2]
    if jax.tree.leaves(xs)[0].shape[0] != 0:
        return False, None
    offsets = kwargs.get("offsets")
    ns = (kwargs.get("num_segments") if offsets is None
          else offsets.shape[0] - 1)
    vals = jax.eval_shape(f, xs)
    return True, op.identity(jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((ns,) + l.shape[1:], l.dtype), vals))


def _zg_batched_mv_identity(route, args, kwargs):
    """(B, n, p) with any zero extent: identity rows of the output extent."""
    f, op, A, x = args[0], args[1], args[2], args[3]
    B, n, p = A.shape
    if B and n and p:
        return False, None
    if route.primitive == "matvec":       # y[b, j]: extent p, f(x, a)
        out_extent, arg_dtypes = p, (x.dtype, A.dtype)
    else:                                 # z[b, i]: extent n, f(a, x)
        out_extent, arg_dtypes = n, (A.dtype, x.dtype)
    one = jax.eval_shape(
        f, jax.ShapeDtypeStruct((1, 1), arg_dtypes[0]),
        jax.ShapeDtypeStruct((1, 1), arg_dtypes[1]))
    return True, op.identity(jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((B, out_extent), l.dtype), one))


_ZERO_GUARDS = {
    "passthrough": _zg_passthrough,
    "batched_reduce_identity": _zg_batched_reduce_identity,
    "segmented_reduce_identity": _zg_segmented_reduce_identity,
    "batched_mv_identity": _zg_batched_mv_identity,
}


# -- the dispatch pipeline --------------------------------------------------


def _validate(route: RouteDef, layout, args, kwargs):
    where = route.key
    for name, required in route.fixed_kwargs:
        if name in kwargs:
            got = kwargs.pop(name)
            if got is not required and got != required:
                raise ValueError(
                    f"{where}: {name}= is pinned by the "
                    f"{layout.describe()} layout -- leave it at its "
                    f"default ({required!r}); got {got!r}"
                    + (f". {route.notes}" if route.notes else ""))
    if route.needs_descriptor:
        lay.validate_descriptor(layout.flags, layout.offsets, where=where)
        if (route.needs_num_segments and layout.offsets is None
                and layout.num_segments is None):
            raise ValueError(
                f"{where}: the flags descriptor needs Segmented("
                f"num_segments=...) -- the output extent is static")
    if route.needs_mesh:
        if not isinstance(layout.axis, str) or not layout.axis:
            raise ValueError(
                f"{where}: Sharded(axis=...) must name a mesh axis, got "
                f"{layout.axis!r}")
        if layout.mesh is not None:
            names = tuple(getattr(layout.mesh, "axis_names", ()))
            if layout.axis not in names:
                raise ValueError(
                    f"{where}: axis {layout.axis!r} is not an axis of the "
                    f"mesh (axes: {names})")
    for idx, rank in route.arg_ranks:
        for leaf in jax.tree.leaves(args[idx]):
            if leaf.ndim != rank:
                raise ValueError(
                    f"{where}: argument {idx} expects rank-{rank} leaves "
                    f"for the {layout.describe()} layout, got shape "
                    f"{tuple(leaf.shape)}")
    if route.op_arg is not None and route.commutative_only:
        op = args[route.op_arg]
        if not getattr(op, "commutative", False):
            raise ValueError(
                f"{where}: requires a commutative operator, got "
                f"{getattr(op, 'name', op)!r} (non-commutative ops take "
                f"the order-preserving scan routes)")


def dispatch(primitive: str, layout, backend: str | None,
             args: tuple, kwargs: dict):
    """Resolve and call one (primitive, layout, backend) route.

    The pipeline -- validation, layout-descriptor injection, zero-extent
    guard, non-commutative reroute, tuner-wrapped implementation -- is
    driven entirely by the RouteDef row, so it is written once for every
    primitive family.
    """
    layout = lay.as_layout(layout)
    route = get_route(primitive, layout.kind)
    kwargs = dict(kwargs)
    _validate(route, layout, args, kwargs)
    if route.needs_descriptor:
        kwargs["flags"] = layout.flags
        kwargs["offsets"] = layout.offsets
        if route.needs_num_segments:
            kwargs["num_segments"] = layout.num_segments
    if route.needs_mesh:
        kwargs["axis_name"] = layout.axis
        kwargs["mesh"] = layout.mesh
        kwargs["overlap"] = layout.overlap
    if route.zero_extent is not None:
        handled, result = _ZERO_GUARDS[route.zero_extent](route, args, kwargs)
        if handled:
            return result
    if route.noncomm_route is not None and not getattr(
            args[route.op_arg], "commutative", False):
        # Order-preserving reroute: scan the mapped values with the same
        # layout, take each problem's last element.  (Registered scans are
        # order-preserving, so the batched family relaxes mapreduce's
        # commutativity contract for free.)
        f, op, xs = args[0], args[1], args[2]
        incl = resolve_impl(route.noncomm_route, backend)(
            op, f(xs), inclusive=True)
        return jax.tree.map(lambda l: l[:, -1], incl)
    return resolve_impl(route.key, backend)(*args, **kwargs)


# -- the table itself -------------------------------------------------------

_NITEM_SCAN = tuple({"nitem_scan": v} for v in (4, 8, 16, 32))
_NITEM_REDUCE = tuple({"nitem_reduce": v} for v in (4, 8, 16))
_NITEM_COPY = tuple({"nitem_copy": v} for v in (4, 8, 16))
# Radix sort races digit width x block policy: wider digits mean fewer
# scatter passes but a larger per-pass rank scan, and the rank scan's own
# block size (nitem_scan) interacts with the digit count.
_SORT_LADDER = tuple({"sort_digit_bits": d, "nitem_scan": m}
                     for d in (2, 4, 8) for m in (8, 16))
_MATVEC_ROWS = tuple({"matvec_rows": v} for v in (4, 8, 16))
_VECMAT_ROWS = tuple({"vecmat_rows": v} for v in (4, 8, 16))
# Chunk count raced by the @sharded staged-plan driver: more chunks expose
# more communication/compute overlap but shrink each local launch.  Sharded
# tuning keys carry the mesh topology, so a winner on one axis extent is
# never replayed on another.
_OVERLAP_CHUNKS = tuple({"overlap_chunks": v} for v in (1, 2, 4, 8))

_SORT_TUNE = TuneRecipe(_SORT_LADDER, op_label="keys")

define_primitive(
    "copy",
    RouteDef("copy", "flat", zero_extent="passthrough",
             tuning=TuneRecipe(_NITEM_COPY, op_label="copy")),
    doc="bandwidth-ceiling tiled copy")

define_primitive(
    "scan",
    RouteDef("scan", "flat", data_arg=1, op_arg=0, zero_extent="passthrough",
             tuning=TuneRecipe(_NITEM_SCAN)),
    RouteDef("scan", "batched", data_arg=1, op_arg=0, arg_ranks=((1, 2),),
             fixed_kwargs=(("axis", 0),), zero_extent="passthrough",
             tuning=TuneRecipe(_NITEM_SCAN, dims="row"),
             notes="per-row scan along axis 1 of (B, n) leaves"),
    RouteDef("scan", "segmented", data_arg=1, op_arg=0, arg_ranks=((1, 1),),
             fixed_kwargs=(("axis", 0), ("reverse", False)),
             needs_descriptor=True, zero_extent="passthrough",
             tuning=TuneRecipe(_NITEM_SCAN),
             notes="restarts at every segment boundary"),
    RouteDef("scan", "sharded", data_arg=1, op_arg=0, arg_ranks=((1, 1),),
             fixed_kwargs=(("axis", 0), ("reverse", False)),
             needs_mesh=True, zero_extent="passthrough",
             tuning=TuneRecipe(_NITEM_SCAN),
             notes="local scan per shard + exclusive cross-device scan of "
                   "per-shard carries; order-preserving, so non-commutative "
                   "ops are valid"),
    doc="prefix scan with any associative operator")

define_primitive(
    "mapreduce",
    RouteDef("mapreduce", "flat", data_arg=2, op_arg=1,
             commutative_only=True,
             tuning=TuneRecipe(_NITEM_REDUCE)),
    RouteDef("mapreduce", "batched", data_arg=2, op_arg=1,
             arg_ranks=((2, 2),), fixed_kwargs=(("axis", None),),
             noncomm_route="scan@batched",
             zero_extent="batched_reduce_identity",
             # Non-commutative ops never reach this tuner: dispatch reroutes
             # them to scan@batched, whose own ladder races nitem_scan.
             tuning=TuneRecipe(_NITEM_REDUCE, dims="row"),
             notes="non-commutative ops reroute via scan@batched"),
    RouteDef("mapreduce", "segmented", data_arg=2, op_arg=1,
             arg_ranks=((2, 1),), fixed_kwargs=(("axis", None),),
             needs_descriptor=True, needs_num_segments=True,
             zero_extent="segmented_reduce_identity",
             tuning=TuneRecipe(_NITEM_SCAN),
             notes="one output element per segment; empties yield identity; "
                   "order-preserving (segmented scan + gather), so "
                   "non-commutative ops are valid"),
    RouteDef("mapreduce", "sharded", data_arg=2, op_arg=1,
             commutative_only=True, fixed_kwargs=(("axis", None),),
             needs_mesh=True,
             tuning=TuneRecipe(_NITEM_REDUCE + _OVERLAP_CHUNKS),
             notes="local reduce along leaf axis 0 + the operator's "
                   "collective fold (psum/pmax/pmin rewrite when the monoid "
                   "allows, all_gather fold otherwise); the cross-device "
                   "fold requires commutativity; rank>=2 mapped leaves are "
                   "chunked along axis 1 for collective/compute overlap"),
    doc="op-reduction of f(x)")

define_primitive(
    "matvec",
    RouteDef("matvec", "flat", data_arg=2, op_arg=1,
             arg_ranks=((2, 2), (3, 1))),
    RouteDef("matvec", "batched", data_arg=2, op_arg=1,
             arg_ranks=((2, 3), (3, 2)), zero_extent="batched_mv_identity",
             tuning=TuneRecipe(_MATVEC_ROWS, dims="trail2")),
    RouteDef("matvec", "sharded", data_arg=2, op_arg=1,
             arg_ranks=((2, 2), (3, 1)), needs_mesh=True,
             tuning=TuneRecipe(_OVERLAP_CHUNKS, dims="row"),
             notes="contraction-axis (row) tensor parallelism: local strip "
                   "matvec per shard + the operator's collective fold over "
                   "strip partials (ADD -> psum for the decode GEMV); a "
                   "< shards row remainder rides replicated and folds in "
                   "last, so reduction order matches the flat route"),
    doc="y[j] = op_i f(x[i], A[i, j]) (generalized semiring matvec)")

define_primitive(
    "vecmat",
    RouteDef("vecmat", "flat", data_arg=2, op_arg=1,
             arg_ranks=((2, 2), (3, 1))),
    RouteDef("vecmat", "batched", data_arg=2, op_arg=1,
             arg_ranks=((2, 3), (3, 2)), zero_extent="batched_mv_identity",
             tuning=TuneRecipe(_VECMAT_ROWS, dims="trail2")),
    RouteDef("vecmat", "sharded", data_arg=2, op_arg=1,
             arg_ranks=((2, 2), (3, 1)), needs_mesh=True,
             tuning=TuneRecipe(_OVERLAP_CHUNKS, dims="row"),
             notes="contraction-axis (column) tensor parallelism, the "
                   "row-wise mirror of matvec@sharded: column strips are "
                   "sharded, strip partials fold across the axis, and the "
                   "< shards column remainder rides replicated"),
    doc="z[i] = op_j f(A[i, j], x[j]) (generalized semiring vecmat)")

define_primitive(
    "linear_recurrence",
    RouteDef("linear_recurrence", "flat", arg_ranks=((0, 3), (1, 3))),
    RouteDef("linear_recurrence", "batched", arg_ranks=((0, 3), (1, 3)),
             tuning=TuneRecipe(_NITEM_SCAN, op_label="affine",
                               dims="trail2"),
             notes="the decode hot path; tuner keys carry a batch bucket"),
    RouteDef("linear_recurrence", "sharded", arg_ranks=((0, 3), (1, 3)),
             fixed_kwargs=(("reverse", False),), needs_mesh=True,
             tuning=TuneRecipe(_OVERLAP_CHUNKS, op_label="affine",
                               dims="trail2"),
             notes="sequence (T) sharding for long-context prefill: local "
                   "affine scan per shard + an exclusive cross-device carry "
                   "of per-shard (A, B) totals; h0 rides replicated; uneven "
                   "T pads with the affine identity (a=1, b=0)"),
    doc="h_t = a_t * h_{t-1} + b_t along axis 1 of (B, T, C)")

_SHARDED_SORT_NOTES = {
    "sort_pairs": "shard-local sort, then a splitter exchange in portable "
                  "form (gathered sorted runs merged by cross-run rank); "
                  "each shard keeps its slice of the global order",
    "top_k": "per-shard top-k candidates + a k-way partial merge; result "
             "replicated across the axis",
}

for _sort_prim, _sort_notes in (
        ("sort", "stable LSD radix; zero extents short-circuit in the "
                 "shared composition (kernels/sort.py)"),
        ("sort_pairs", "payload pytree rides the same permutation"),
        ("argsort", "segmented variant returns within-segment offsets"),
        ("top_k", "extreme-first; segmented fills short segments with "
                  "identity and index -1")):
    _sort_routes = [
        RouteDef(_sort_prim, "flat", arg_ranks=((0, 1),),
                 tuning=_SORT_TUNE),
        RouteDef(_sort_prim, "segmented", arg_ranks=((0, 1),),
                 needs_descriptor=True,
                 needs_num_segments=(_sort_prim == "top_k"),
                 tuning=_SORT_TUNE, notes=_sort_notes),
    ]
    if _sort_prim in _SHARDED_SORT_NOTES:
        _sort_routes.append(
            RouteDef(_sort_prim, "sharded", arg_ranks=((0, 1),),
                     needs_mesh=True, tuning=_SORT_TUNE,
                     notes=_SHARDED_SORT_NOTES[_sort_prim]))
    define_primitive(_sort_prim, *_sort_routes,
                     doc=f"radix-sort family: {_sort_prim}")
