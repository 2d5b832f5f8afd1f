"""Associative operators and semirings over arbitrary pytree element types.

This is the algebra layer of the paper's "arbitrary types and operators"
contribution.  KernelForge.jl supports any Julia Bitstype through recursive
``@generated`` decomposition into 32-bit shuffles; the JAX-native analogue is
a *pytree of arrays*: an element type is any pytree whose leaves are JAX
arrays, and an operator is any function combining two such pytrees leafwise /
structurally.  JAX tracing unrolls the structural recursion at compile time
exactly like Julia's generated functions -- zero runtime dispatch.

Every operator used by the kernels is an :class:`AssocOp`:

* ``combine(a, b)`` must be **associative** and **vectorized** (it is applied
  to whole tiles, combining along the scanned/reduced dimension while staying
  elementwise over the remaining tile dimensions).
* ``identity(like)`` materializes the identity element matching the
  shape/dtype of ``like`` (used for tile padding masks and carry init).
* ``commutative`` selects between the balanced-fold reduction tree (fast) and
  the order-preserving scan-fold (required for e.g. quaternion products or
  matrix-affine composition) inside the kernels.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


def _leaf_shape_dtype(x):
    return jnp.shape(x), jnp.result_type(x)


def full_like_spec(like, value):
    """``jnp.full`` matching a concrete array *or* a ShapeDtypeStruct leaf."""
    shape, dtype = _leaf_shape_dtype(like)
    return jnp.full(shape, value, dtype=dtype)


def _min_value(dtype):
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    if jnp.issubdtype(dtype, jnp.bool_):
        return False
    return jnp.iinfo(dtype).min


def _max_value(dtype):
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf
    if jnp.issubdtype(dtype, jnp.bool_):
        return True
    return jnp.iinfo(dtype).max


@dataclasses.dataclass(frozen=True)
class AssocOp:
    """An associative binary operator over pytree elements."""

    name: str
    combine: Callable[[Pytree, Pytree], Pytree]
    identity: Callable[[Pytree], Pytree]  # (pytree of shape/dtype likes) -> pytree
    commutative: bool = False

    def __call__(self, a: Pytree, b: Pytree) -> Pytree:
        return self.combine(a, b)

    def __repr__(self):  # keep kernel cache keys short
        return f"AssocOp({self.name})"


def _elementwise_identity(fill_fn):
    def identity(like):
        return jax.tree.map(lambda l: full_like_spec(l, fill_fn(_leaf_shape_dtype(l)[1])), like)

    return identity


# --------------------------------------------------------------------------
# Standard scalar/elementwise operators
# --------------------------------------------------------------------------

ADD = AssocOp(
    name="add",
    combine=lambda a, b: jax.tree.map(jnp.add, a, b),
    identity=_elementwise_identity(lambda dt: 0),
    commutative=True,
)

MUL = AssocOp(
    name="mul",
    combine=lambda a, b: jax.tree.map(jnp.multiply, a, b),
    identity=_elementwise_identity(lambda dt: 1),
    commutative=True,
)

MAX = AssocOp(
    name="max",
    combine=lambda a, b: jax.tree.map(jnp.maximum, a, b),
    identity=_elementwise_identity(_min_value),
    commutative=True,
)

MIN = AssocOp(
    name="min",
    combine=lambda a, b: jax.tree.map(jnp.minimum, a, b),
    identity=_elementwise_identity(_max_value),
    commutative=True,
)


def _logaddexp(a, b):
    return jax.tree.map(jnp.logaddexp, a, b)


LOGSUMEXP = AssocOp(
    name="logsumexp",
    combine=_logaddexp,
    identity=_elementwise_identity(lambda dt: -jnp.inf),
    commutative=True,
)

# Tropical semiring reducers (the paper's shortest-path use case).
TROPICAL_MIN = MIN   # (min, +) semiring: reduce with min, map with +
TROPICAL_MAX = MAX   # (max, +) semiring


# --------------------------------------------------------------------------
# Affine composition: the operator behind diagonal linear recurrences
#   h_t = a_t * h_{t-1} + b_t.
# Elements are pairs (a, b) representing x -> a*x + b; composition is applied
# left-to-right: (g1 . g2)(x) = g2(g1(x)).  NON-commutative.
# --------------------------------------------------------------------------


def _affine_combine(p, q):
    (a1, b1), (a2, b2) = p, q
    return (
        jax.tree.map(jnp.multiply, a2, a1),
        jax.tree.map(lambda a2_, b1_, b2_: a2_ * b1_ + b2_, a2, b1, b2),
    )


def _affine_identity(like):
    a_like, b_like = like
    return (
        jax.tree.map(lambda l: full_like_spec(l, 1), a_like),
        jax.tree.map(lambda l: full_like_spec(l, 0), b_like),
    )


AFFINE = AssocOp(
    name="affine",
    combine=_affine_combine,
    identity=_affine_identity,
    commutative=False,
)


# Max-plus affine: elements (a, b) represent m -> max(m + a, b).  This is the
# AFFINE operator over the (max, +) semiring -- the recurrence behind xLSTM's
# exponential-gating stabilizer m_t = max(log f_t + m_{t-1}, log i_t).
# NON-commutative; exercised by the xlstm-1.3b architecture via core.scan.


def _maxplus_affine_combine(p, q):
    (a1, b1), (a2, b2) = p, q
    return (
        jax.tree.map(jnp.add, a1, a2),
        jax.tree.map(lambda b1_, a2_, b2_: jnp.maximum(b1_ + a2_, b2_), b1, a2, b2),
    )


def _maxplus_affine_identity(like):
    a_like, b_like = like
    return (
        jax.tree.map(lambda l: full_like_spec(l, 0), a_like),
        jax.tree.map(lambda l: full_like_spec(l, _min_value(_leaf_shape_dtype(l)[1])), b_like),
    )


MAXPLUS_AFFINE = AssocOp(
    name="maxplus_affine",
    combine=_maxplus_affine_combine,
    identity=_maxplus_affine_identity,
    commutative=False,
)


# --------------------------------------------------------------------------
# Softmax-merge: combining partial attention results (m, l, o) where
#   m = running max of logits, l = sum of exp(logit - m), o = weighted values.
# Associative and commutative; the operator behind distributed flash-decoding.
# --------------------------------------------------------------------------


def _softmax_merge(p, q):
    (m1, l1, o1), (m2, l2, o2) = p, q
    m = jnp.maximum(m1, m2)
    # Guard exp(-inf - -inf): where both sides are empty keep weights at 0.
    w1 = jnp.where(jnp.isneginf(m1), 0.0, jnp.exp(m1 - m)).astype(l1.dtype)
    w2 = jnp.where(jnp.isneginf(m2), 0.0, jnp.exp(m2 - m)).astype(l2.dtype)
    l = l1 * w1 + l2 * w2
    o = o1 * w1[..., None] + o2 * w2[..., None] if o1.ndim == l1.ndim + 1 else o1 * w1 + o2 * w2
    return (m, l, o)


def _softmax_identity(like):
    m_like, l_like, o_like = like
    return (
        jax.tree.map(lambda l: full_like_spec(l, -jnp.inf), m_like),
        jax.tree.map(lambda l: full_like_spec(l, 0), l_like),
        jax.tree.map(lambda l: full_like_spec(l, 0), o_like),
    )


SOFTMAX_MERGE = AssocOp(
    name="softmax_merge",
    combine=_softmax_merge,
    identity=_softmax_identity,
    commutative=True,
)


# --------------------------------------------------------------------------
# Quaternion multiplication: the paper's canonical non-commutative composite
# type (a 4-field struct).  Elements are tuples (w, x, y, z) of arrays.
# --------------------------------------------------------------------------


def _quat_mul(p, q):
    w1, x1, y1, z1 = p
    w2, x2, y2, z2 = q
    return (
        w1 * w2 - x1 * x2 - y1 * y2 - z1 * z2,
        w1 * x2 + x1 * w2 + y1 * z2 - z1 * y2,
        w1 * y2 - x1 * z2 + y1 * w2 + z1 * x2,
        w1 * z2 + x1 * y2 - y1 * x2 + z1 * w2,
    )


def _quat_identity(like):
    w, x, y, z = like
    return (
        full_like_spec(w, 1),
        full_like_spec(x, 0),
        full_like_spec(y, 0),
        full_like_spec(z, 0),
    )


QUATERNION_MUL = AssocOp(
    name="quaternion_mul",
    combine=_quat_mul,
    identity=_quat_identity,
    commutative=False,
)


# --------------------------------------------------------------------------
# 2x2 matrix product under flattened (m00, m01, m10, m11) representation --
# exercises a non-commutative struct type distinct from quaternions.
# --------------------------------------------------------------------------


def _mat2_mul(p, q):
    a00, a01, a10, a11 = p
    b00, b01, b10, b11 = q
    # Row-vector convention (state @ M): compose left-to-right as p then q.
    return (
        a00 * b00 + a01 * b10,
        a00 * b01 + a01 * b11,
        a10 * b00 + a11 * b10,
        a10 * b01 + a11 * b11,
    )


def _mat2_identity(like):
    m00, m01, m10, m11 = like
    return (
        full_like_spec(m00, 1),
        full_like_spec(m01, 0),
        full_like_spec(m10, 0),
        full_like_spec(m11, 1),
    )


MAT2_MUL = AssocOp(
    name="mat2_mul",
    combine=_mat2_mul,
    identity=_mat2_identity,
    commutative=False,
)


# --------------------------------------------------------------------------
# Segmented lift: turn any AssocOp into an operator over (flag, value) pairs
# that resets at segment boundaries (Blelloch's segmented-scan construction).
# Elements are ``(flag, value)`` where a nonzero flag marks the first element
# of a segment.  The lift preserves associativity; it is never commutative
# (segment boundaries are positional), so kernels always take the
# order-preserving scan path.
# --------------------------------------------------------------------------


def segmented(op: AssocOp) -> AssocOp:
    """Lift ``op`` to the segment-resetting operator over (flag, value).

    combine((f1, v1), (f2, v2)) = (f1 | f2, v2 if f2 else op(v1, v2)):
    once the right operand starts a new segment, everything to its left is
    discarded.  Identity is (0, identity_of_op).
    """

    def combine(p, q):
        f1, v1 = p
        f2, v2 = q
        started = f2 != 0
        merged = op(v1, v2)
        v = jax.tree.map(lambda m, r: jnp.where(started, r, m), merged, v2)
        return (jnp.maximum(f1, f2), v)

    def identity(like):
        f_like, v_like = like
        return (
            jax.tree.map(lambda l: full_like_spec(l, 0), f_like),
            op.identity(v_like),
        )

    return AssocOp(
        name=f"segmented[{op.name}]",
        combine=combine,
        identity=identity,
        commutative=False,
    )


# --------------------------------------------------------------------------
# Collective folds: the multi-device analogue of the in-tile shuffle combine.
# A mesh axis is the device-level lane dimension, and folding an AssocOp
# across it is the same algebraic object as tile_reduce -- so, exactly as the
# kernels rewrite tile combines into VPU shifts, the distributed layer
# rewrites operator folds into the native collectives (psum/pmax/pmin) when
# the monoid structure allows, and falls back to an order-preserving
# all_gather + local fold otherwise.
#
# The registry *returns a descriptor* (:class:`FoldSpec`) rather than
# eagerly executing a fold: ``distributed/primitives.py`` stages every
# ``@sharded`` route as a ShardPlan (local stage -> collective stage ->
# epilogue), and the plan driver decides *when* each collective is issued
# (chunked, overlapped with the next chunk's local compute).  The
# ``collectives`` tuple names the collective ops the built fold emits, so
# the structural byte models in benchmarks/analytic.py can price the
# cross-device stage without running it.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FoldSpec:
    """Descriptor for one operator's cross-device fold.

    ``build(axis_name)`` returns the function mapping one *local* element (a
    pytree) to the fold of all devices' elements along that mesh axis --
    algebraically ``functools.reduce(op, shards-in-axis-order)``.
    ``collectives`` names the collectives the built fold emits, in issue
    order (``"psum"``/``"pmax"``/``"pmin"``/``"all_gather"``).
    """

    op_name: str
    collectives: tuple[str, ...]
    build: Callable[[str], Callable]

    @property
    def native(self) -> bool:
        """True when the fold is a native-collective rewrite (no gather)."""
        return "all_gather" not in self.collectives


_COLLECTIVE_FOLDS: dict[str, FoldSpec] = {}


def register_collective_fold(op_name: str, *, collectives: tuple[str, ...]):
    """Register a collective-form rewrite for the operator named ``op_name``.

    The decorated builder takes the mesh ``axis_name`` and returns a function
    mapping one *local* element (a pytree) to the fold of all devices'
    elements along that axis.  Rewrites must be algebraically equivalent to
    ``functools.reduce(op, shards-in-axis-order)``.  ``collectives`` declares
    the collective ops the built fold emits (metadata for the staged plan
    layer and the analytic byte models).
    """

    def deco(builder):
        _COLLECTIVE_FOLDS[op_name] = FoldSpec(
            op_name=op_name, collectives=tuple(collectives), build=builder)
        return builder

    return deco


def has_collective_rewrite(op: AssocOp) -> bool:
    """True when ``op`` folds via native collectives (no all_gather)."""
    return op.name in _COLLECTIVE_FOLDS


def _gather_fold(op: AssocOp, axis_name: str) -> Callable:
    """Portable fallback: gather every shard's element, fold in axis order.

    ``all_gather`` stacks shards along a new leading axis in axis-index
    order, so the Python fold (static extent: the mesh axis size) preserves
    device order -- non-commutative operators are safe here, exactly like
    the order-preserving scan path inside the kernels.
    """

    def fold(x):
        g = jax.tree.map(
            lambda l: jax.lax.all_gather(l, axis_name, axis=0), x)
        extent = jax.tree.leaves(g)[0].shape[0]
        out = jax.tree.map(lambda l: l[0], g)
        for i in range(1, extent):
            out = op(out, jax.tree.map(lambda l: l[i], g))
        return out

    return fold


def collective_fold_spec(op: AssocOp) -> FoldSpec:
    """The :class:`FoldSpec` describing ``op``'s cross-device fold.

    Returns the registered native-collective rewrite when the operator's
    monoid structure allows, otherwise the portable ``all_gather`` + ordered
    local fold -- always algebraically the same reduction, so callers never
    branch on the operator.  This is the descriptor form the staged
    ``@sharded`` plans consume: the caller decides when to ``build`` and
    issue the fold, not this registry.
    """
    spec = _COLLECTIVE_FOLDS.get(op.name)
    if spec is not None:
        return spec
    return FoldSpec(op_name=op.name, collectives=("all_gather",),
                    build=functools.partial(_gather_fold, op))


def collective_fold(op: AssocOp, axis_name: str) -> Callable:
    """Fold ``op`` across mesh axis ``axis_name``: local element -> total.

    Eager convenience form of :func:`collective_fold_spec` (build the fold
    for one axis immediately); kept for callers that do not stage.
    """
    return collective_fold_spec(op).build(axis_name)


@register_collective_fold("add", collectives=("psum",))
def _add_collective(axis_name):
    return lambda x: jax.tree.map(
        lambda l: jax.lax.psum(l, axis_name), x)


@register_collective_fold("max", collectives=("pmax",))
def _max_collective(axis_name):
    return lambda x: jax.tree.map(
        lambda l: jax.lax.pmax(l, axis_name), x)


@register_collective_fold("min", collectives=("pmin",))
def _min_collective(axis_name):
    return lambda x: jax.tree.map(
        lambda l: jax.lax.pmin(l, axis_name), x)


@register_collective_fold("logsumexp", collectives=("pmax", "psum"))
def _logsumexp_collective(axis_name):
    """log(psum(exp(x - pmax x))) + pmax x, guarded for all--inf shards."""

    def fold(x):
        def one(l):
            m = jax.lax.pmax(l, axis_name)
            w = jnp.where(jnp.isneginf(l), 0.0, jnp.exp(l - m)).astype(l.dtype)
            s = jax.lax.psum(w, axis_name)
            return jnp.where(jnp.isneginf(m), m, m + jnp.log(s))

        return jax.tree.map(one, x)

    return fold


@register_collective_fold("softmax_merge", collectives=("pmax", "psum", "psum"))
def _softmax_merge_collective(axis_name):
    """The distributed flash-decoding merge: m* = pmax m; w = exp(m - m*);
    l* = psum(w l); o* = psum(w o) -- SOFTMAX_MERGE's fold in collective
    form (``tests/test_sharded.py`` pins the equivalence to the operator
    fold).  The ``isneginf`` guard matches the operator's combine; finite
    mask sentinels (e.g. -1e30 with a finite m*) underflow ``exp`` to the
    same exact zero.
    """

    def fold(part):
        m, l, o = part
        m_g = jax.lax.pmax(m, axis_name)
        w = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_g)).astype(l.dtype)
        wo = w[..., None] if o.ndim == l.ndim + 1 else w
        # A zero-weight shard contributes exactly zero even when its o is
        # poisoned (NaN/inf from masked garbage rows): 0 * NaN is NaN, so the
        # product alone would leak the garbage into the psum.
        o_w = jnp.where(wo > 0, o * wo, jnp.zeros_like(o))
        l_g = jax.lax.psum(l * w, axis_name)
        o_g = jax.lax.psum(o_w, axis_name)
        return (m_g, l_g, o_g)

    return fold


# --------------------------------------------------------------------------
# Radix-sortable key transforms: order-preserving bijections from every
# supported key dtype onto unsigned integers of the same width, so the LSD
# radix sort (kernels/sort.py) only ever manipulates unsigned bit patterns.
#
# The induced total order is pinned down exactly:
#
# * unsigned ints -- numeric order (identity transform);
# * signed ints   -- numeric order (flip the sign bit);
# * floats        -- IEEE numeric order with two canonicalizations applied
#   *before* the transform: ``-0.0`` maps to ``+0.0`` (so the two zeros
#   compare equal, matching ``np.sort``), and every NaN maps to the
#   all-ones-mantissa positive NaN (so **all NaNs compare equal and sort
#   after +inf**, again matching ``np.sort``'s NaN-last order).  The float
#   transform is the classic sign-magnitude fix-up: negative values are
#   bitwise complemented, non-negative values get the sign bit set.
# --------------------------------------------------------------------------

_RADIX_UINT_FOR_WIDTH = {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32}


def radix_key_bits(dtype) -> int:
    """Total significant bits in the sortable-transformed key."""
    dtype = jnp.dtype(dtype)
    if dtype not in {jnp.dtype(d) for d in
                     (jnp.uint8, jnp.uint16, jnp.uint32, jnp.int8, jnp.int16,
                      jnp.int32, jnp.float32, jnp.bfloat16, jnp.float16)}:
        raise TypeError(f"radix sort: unsupported key dtype {dtype}")
    return dtype.itemsize * 8


def key_to_radix_bits(keys: jax.Array) -> jax.Array:
    """Map keys onto same-width unsigned bits; ``a < b`` iff ``bits(a) < bits(b)``
    under the pinned total order documented above."""
    dtype = jnp.dtype(keys.dtype)
    width = radix_key_bits(dtype)
    udt = _RADIX_UINT_FOR_WIDTH[width]
    if jnp.issubdtype(dtype, jnp.unsignedinteger):
        return keys
    if jnp.issubdtype(dtype, jnp.signedinteger):
        sign = jnp.asarray(1 << (width - 1), udt)
        return jax.lax.bitcast_convert_type(keys, udt) ^ sign
    # Floats: canonicalize -0.0 and NaN, then sign-magnitude fix-up.
    keys = jnp.where(keys == 0, jnp.zeros_like(keys), keys)
    bits = jax.lax.bitcast_convert_type(keys, udt)
    nan_bits = jnp.asarray((1 << (width - 1)) - 1, udt)   # +NaN, max mantissa
    bits = jnp.where(jnp.isnan(keys), nan_bits, bits)
    sign = jnp.asarray(1 << (width - 1), udt)
    return jnp.where((bits & sign) != 0, ~bits, bits | sign)


def radix_bits_to_key(bits: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`key_to_radix_bits` (up to the documented float
    canonicalizations: ``-0.0`` comes back as ``+0.0`` and NaNs as the
    canonical quiet NaN)."""
    dtype = jnp.dtype(dtype)
    width = radix_key_bits(dtype)
    udt = _RADIX_UINT_FOR_WIDTH[width]
    if jnp.issubdtype(dtype, jnp.unsignedinteger):
        return bits.astype(dtype)
    sign = jnp.asarray(1 << (width - 1), udt)
    if jnp.issubdtype(dtype, jnp.signedinteger):
        return jax.lax.bitcast_convert_type(bits ^ sign, dtype)
    raw = jnp.where((bits & sign) != 0, bits ^ sign, ~bits)
    return jax.lax.bitcast_convert_type(raw, dtype)


# --------------------------------------------------------------------------
# Semirings: (map f, reduce op) pairs for generalized matvec / mapreduce.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Semiring:
    """Generalized (f, op): y = op_i f(x_i, a_i).

    ``f`` is applied elementwise to (vector element, matrix element) pairs and
    ``op`` reduces.  ``f`` may change the element type (e.g. UnitFloat8 ->
    Float32 promotion in the paper's mapreduce benchmark).
    """

    name: str
    f: Callable[[Any, Any], Pytree]
    op: AssocOp


ARITHMETIC = Semiring("arithmetic", f=lambda x, a: x * a, op=ADD)
TROPICAL_MIN_PLUS = Semiring("tropical_min_plus", f=lambda x, a: x + a, op=MIN)
TROPICAL_MAX_PLUS = Semiring("tropical_max_plus", f=lambda x, a: x + a, op=MAX)
LOG_SEMIRING = Semiring("log", f=lambda x, a: x + a, op=LOGSUMEXP)


# --------------------------------------------------------------------------
# UnitFloat8: the paper's custom 8-bit type -- values in [-1, 1] encoded as
# 256 evenly spaced uint8 levels, promoted to f32 before accumulation.
# --------------------------------------------------------------------------


def unitfloat8_encode(x: jax.Array) -> jax.Array:
    x = jnp.clip(x, -1.0, 1.0)
    return jnp.round((x + 1.0) * (255.0 / 2.0)).astype(jnp.uint8)


def unitfloat8_decode(u: jax.Array) -> jax.Array:
    # Widen through int32: Mosaic has no direct uint8 -> float32 cast.
    return u.astype(jnp.int32).astype(jnp.float32) * (2.0 / 255.0) - 1.0


# --------------------------------------------------------------------------
# Quantized: blockwise-scaled (values, scales) matrices -- the "arbitrary
# types" stress test on the decode GEMV hot path.  A matrix is stored as a
# small-dtype values array plus one f32 scale per ``block`` rows per column
# (blocks tile the leading/reduction axis), so HBM traffic drops ~2-4x vs
# bf16 while the matvec/vecmat kernels dequantize per tile and accumulate
# in f32.  The pytree has exactly two leaves (values, scales) of the SAME
# rank as the plain matrix they replace, so the registry's rank validation
# and tree surgery (scatter/poison/jit) all work unchanged.
# --------------------------------------------------------------------------

# mode -> (exponent bits, mantissa bits, exponent bias, max finite value).
# e4m3 follows the "fn" convention (no inf, 448 max); e5m2 keeps 57344 as
# its largest finite.  Both are *emulated*: values are stored as uint8 bit
# patterns and decoded with integer ops + exp2, so the routes work on any
# backend/jax pin regardless of native float8 support.
FP8_FORMATS = {"fp8_e4m3": (4, 3, 7, 448.0), "fp8_e5m2": (5, 2, 15, 57344.0)}
QUANT_MODES = ("int8",) + tuple(FP8_FORMATS)


def fp8_decode(u: jax.Array, mode: str) -> jax.Array:
    """uint8 bit patterns -> f32 (sign/exponent/mantissa field decode).

    Pure integer ops + ``exp2``, so it is safe to call *inside* a Pallas
    kernel body (the dequant-in-kernel path) as well as on the host.
    """
    _, man, bias, _ = FP8_FORMATS[mode]
    b = u.astype(jnp.int32)
    sign = jnp.where(b >= 128, -1.0, 1.0).astype(jnp.float32)
    exp = (b >> man) & ((1 << (7 - man)) - 1)
    frac = (b & ((1 << man) - 1)).astype(jnp.float32) * (1.0 / (1 << man))
    # 2**(exp-bias) built as f32 bits: exact, unlike exp2 (which some
    # backends lower through exp(x*ln2) and round).
    pow2 = jax.lax.bitcast_convert_type(
        ((exp - bias + 127) << 23).astype(jnp.int32), jnp.float32)
    normal = pow2 * (1.0 + frac)
    subnormal = (2.0 ** (1 - bias)) * frac
    return sign * jnp.where(exp > 0, normal, subnormal)


def fp8_encode(x: jax.Array, mode: str) -> jax.Array:
    """f32 -> uint8 bit patterns, round-to-nearest onto the fp8 grid,
    saturating at the format's max finite value (no inf/nan encodings)."""
    _, man, bias, fmax = FP8_FORMATS[mode]
    sign = jnp.where(x < 0, jnp.uint8(0x80), jnp.uint8(0))
    a = jnp.minimum(jnp.abs(x.astype(jnp.float32)), fmax)
    mant, e = jnp.frexp(a)                 # a == mant * 2**e, mant in [.5, 1)
    E = e - 1 + bias                       # tentative biased exponent
    # Normal path: field = round((1.f - 1) * 2^man), carrying into E.
    nf = jnp.round((mant * 2.0 - 1.0) * (1 << man)).astype(jnp.int32)
    E = jnp.where(nf >= (1 << man), E + 1, E)
    nf = jnp.where(nf >= (1 << man), 0, nf)
    # Subnormal path (E <= 0): field = round(a / 2^(1-bias) * 2^man); a
    # field of 2^man is exactly the smallest normal.
    sf = jnp.round(a * (2.0 ** (bias - 1 + man))).astype(jnp.int32)
    sub = sf < (1 << man)
    bits = jnp.where(
        E <= 0,
        jnp.where(sub, sf, (1 << man)),              # 1<<man == E=1, field 0
        (jnp.minimum(E, (1 << (7 - man)) - 1) << man) | nf)
    # Saturate anything that rounded past fmax back to the max finite code.
    maxcode = _fp8_max_code(mode)
    bits = jnp.where(a >= fmax, maxcode, jnp.minimum(bits, maxcode))
    bits = jnp.where(a == 0.0, 0, bits)
    return (bits.astype(jnp.uint8) | sign).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _fp8_max_code(mode: str) -> int:
    """Bit pattern of the largest finite value (exponent all-usable-ones,
    mantissa at the format's top finite field).  Pure host float math --
    every grid value is exactly representable in double -- so it stays
    concrete even when ``fp8_encode`` is first reached inside a trace
    (jit / eval_shape)."""
    _, man, bias, fmax = FP8_FORMATS[mode]
    for code in range(127, -1, -1):                  # positive half suffices
        exp = code >> man
        frac = (code & ((1 << man) - 1)) / (1 << man)
        v = ((2.0 ** (exp - bias)) * (1.0 + frac) if exp > 0
             else (2.0 ** (1 - bias)) * frac)
        if v == fmax:
            return code
    raise AssertionError(f"fmax {fmax} not on the {mode} grid")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Quantized:
    """Blockwise-quantized matrix operand: ``values`` is int8 (mode
    ``"int8"``) or uint8 fp8 bit patterns, ``scales`` holds one f32 per
    ``block`` rows per column -- shape ``(ceil(n/block), p)`` for an
    ``(n, p)`` matrix, ``(B, ceil(n/block), p)`` batched, i.e. the same
    rank as ``values`` so registry rank validation passes untouched.

    ``dequantize()`` is the reference semantics every kernel must match:
    ``decode(values) * scales`` with scales repeated ``block``-wise along
    the row axis.  ``error_bound()`` is the per-element dequantization
    error bound the conformance oracles integrate (kernels/ref.py).
    """

    values: jax.Array
    scales: jax.Array
    block: int = 64
    mode: str = "int8"

    def tree_flatten(self):
        return (self.values, self.scales), (self.block, self.mode)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self):
        return self.values.shape

    @property
    def dtype(self):
        # The *compute* dtype: kernels dequantize to f32 before applying f,
        # so shape/dtype probes (zero-extent guards, einsum fast paths) see
        # the matrix this object stands in for.
        return jnp.dtype(jnp.float32)

    @property
    def qtag(self) -> str:
        """Tuning-key dtype tag: distinct from the plain dtypes so cached
        block choices never leak between quantized and dense routes."""
        return f"{self.mode}q{self.block}"

    def _expanded_scales(self) -> jax.Array:
        s = self.scales
        nb, p = s.shape[-2], s.shape[-1]
        lead = s.shape[:-2]
        e = jnp.broadcast_to(s[..., :, None, :], lead + (nb, self.block, p))
        return e.reshape(lead + (nb * self.block, p))[
            ..., : self.values.shape[-2], :]

    def decoded(self) -> jax.Array:
        """values -> f32 on the quantization grid (scales NOT applied)."""
        if self.mode == "int8":
            return self.values.astype(jnp.float32)
        return fp8_decode(self.values, self.mode)

    def dequantize(self) -> jax.Array:
        return self.decoded() * self._expanded_scales()

    def error_bound(self) -> jax.Array:
        """Per-element bound on |original - dequantize()| for a matrix
        produced by :func:`quantize`: half a quantization step.  int8 steps
        are uniform (scale); fp8 steps are relative for normals plus the
        subnormal absolute step, both scaled by the block scale."""
        s = self._expanded_scales()
        if self.mode == "int8":
            return 0.5 * s
        _, man, bias, _ = FP8_FORMATS[self.mode]
        rel = jnp.abs(self.decoded()) * (2.0 ** -man)
        sub_step = 2.0 ** (1 - bias - man)
        return (0.5 * rel + 0.5 * sub_step) * s


def quantize(A: jax.Array, *, mode: str = "int8", block: int = 64) -> Quantized:
    """Blockwise-quantize ``A`` along its row (reduction) axis.

    Each ``(block, 1)`` column strip gets scale ``absmax / QMAX`` so the
    scaled values fill the representable range; encode is round-to-nearest
    (int8) or round-onto-the-fp8-grid, giving the half-step error bound
    :meth:`Quantized.error_bound` advertises.  Works on ``(n, p)`` and
    batched ``(B, n, p)`` operands.
    """
    if mode not in QUANT_MODES:
        raise ValueError(f"mode {mode!r} not in {QUANT_MODES}")
    if block < 1:
        raise ValueError(f"block must be positive, got {block}")
    A = jnp.asarray(A, jnp.float32)
    n, p = A.shape[-2], A.shape[-1]
    lead = A.shape[:-2]
    nb = -(-n // block) if n else 0
    pad = nb * block - n
    Ap = jnp.pad(A, [(0, 0)] * len(lead) + [(0, pad), (0, 0)])
    blocks = Ap.reshape(lead + (nb, block, p))
    absmax = jnp.max(jnp.abs(blocks), axis=-2)            # (..., nb, p)
    qmax = 127.0 if mode == "int8" else FP8_FORMATS[mode][3]
    scales = jnp.maximum(absmax, jnp.finfo(jnp.float32).tiny) / qmax
    scaled = Ap / jnp.repeat(scales, block, axis=-2)
    scaled = scaled[..., :n, :]
    if mode == "int8":
        values = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    else:
        values = fp8_encode(scaled, mode)
    return Quantized(values, scales, block=block, mode=mode)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVQuant:
    """Per-vector quantized KV-cache leaf (serving's ``quantize_kv=`` mode).

    ``values`` holds int8 (mode ``"int8"``) or uint8 fp8 bit patterns with
    the cached vector on the last axis; ``scales`` holds one f32 per vector
    (same shape with a trailing 1).  Unlike :class:`Quantized` -- whose
    scales tile the reduction axis of a matrix in ``block``-row strips --
    this is the cache-resident form: one scale per (token, head) vector, so
    slot scatter / ring updates address values and scales with the *same*
    index arithmetic as the unquantized leaf.  ``mode`` is static aux data,
    so it survives jit/eval_shape and the decode read can branch on it.
    """

    values: jax.Array
    scales: jax.Array
    mode: str = "int8"

    def tree_flatten(self):
        return (self.values, self.scales), (self.mode,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self):
        return self.values.shape

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        dec = (self.values.astype(jnp.float32) if self.mode == "int8"
               else fp8_decode(self.values, self.mode))
        return (dec * self.scales).astype(dtype)


def quantize_kv(x: jax.Array, mode: str = "int8") -> KVQuant:
    """Quantize cache vectors along the last axis, one scale per vector."""
    if mode not in QUANT_MODES:
        raise ValueError(f"mode {mode!r} not in {QUANT_MODES}")
    a = jnp.asarray(x, jnp.float32)
    absmax = jnp.max(jnp.abs(a), axis=-1, keepdims=True)
    qmax = 127.0 if mode == "int8" else FP8_FORMATS[mode][3]
    scales = jnp.maximum(absmax, jnp.finfo(jnp.float32).tiny) / qmax
    scaled = a / scales
    if mode == "int8":
        values = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    else:
        values = fp8_encode(scaled, mode)
    return KVQuant(values, scales, mode=mode)


STD_OPS = {
    op.name: op
    for op in [ADD, MUL, MAX, MIN, LOGSUMEXP, AFFINE, MAXPLUS_AFFINE,
               SOFTMAX_MERGE, QUATERNION_MUL, MAT2_MUL]
}

STD_SEMIRINGS = {
    s.name: s for s in [ARITHMETIC, TROPICAL_MIN_PLUS, TROPICAL_MAX_PLUS, LOG_SEMIRING]
}
