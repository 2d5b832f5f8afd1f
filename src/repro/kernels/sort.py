"""LSD radix sort / argsort / top-k built *only* on the portable primitives.

CUB's flagship derived primitive is radix sort, and the paper's thesis is
that vendor-competitive primitives compose from portable scan/mapreduce
machinery.  This module is that composition made explicit: every pass of the
least-significant-digit radix sort is

1. **bit-extract map** -- the current digit of every key
   (``operators.key_to_radix_bits`` first maps any supported key dtype onto
   order-preserving unsigned bits, so passes only ever see unsigned ints);
2. **per-digit histogram** via ``mapreduce`` over the one-hot digit matrix;
3. **digit base offsets** via an exclusive ``scan`` of the histogram;
4. **within-bucket stable rank** via an exclusive ``scan`` down the one-hot
   matrix with the ``2^digit_bits`` buckets riding the 128 lanes (the
   ``(1, n, R)`` channel layout -- no cross-lane combine);
5. **scatter** of keys (and any payload pytree) to
   ``base[digit] + rank``.

No step hardcodes a backend: every scan/mapreduce goes through the Layer-1
dispatch registry keyed by the ``backend=`` parameter, so the same code runs
on ``pallas-tpu``, ``pallas-gpu``, ``pallas-interpret`` and ``xla`` -- the
scatter/gather glue between passes is dispatch-layer XLA, exactly like the
segmented primitives' descriptor bookkeeping.

The segmented variants reuse the PR 1 descriptors (flag array / CSR
offsets): a segmented sort is two chained stable radix phases -- key digits
first, then segment-id digits -- which is sort-by-``(segment, key)`` without
ever packing the pair into one word (so u32 keys plus any segment count
compose).  Because segments are contiguous and the sort is stable, the
output layout (segment boundaries) is identical to the input layout.

Top-k selects rather than sorts: one-bit counting passes (a segmented
``mapreduce`` each, most significant bit first) find every segment's k-th
key, one ``scan`` places the survivors, and only the ``S * k`` survivors
go through the radix sort.

Total order (pinned in ``operators.key_to_radix_bits``): unsigned/signed
ints numerically; floats numerically with ``-0.0 == +0.0`` and **all NaNs
equal, sorting after +inf** (NaN-last ascending, NaN-first for
``descending``/``largest`` -- the ``np.sort`` convention).  Ties preserve
input order (LSD radix is stable).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import intrinsics as ki
from repro.core import operators as alg
from repro.core.layout import Segmented
from repro.kernels import segmented as seg_k

Pytree = Any


def _resolve_policy(policy, backend):
    if policy is not None:
        return policy
    return ki.resolve_tuning(ki.default_policy_name(backend))


def _full_mask(kb: int, dtype) -> jax.Array:
    return jnp.asarray((1 << kb) - 1, dtype)


def _key_bits_for(keys, key_bits):
    """Validate/resolve the significant-bit hint (unsigned keys only)."""
    width = alg.radix_key_bits(keys.dtype)
    if key_bits is None:
        return width
    if not jnp.issubdtype(keys.dtype, jnp.unsignedinteger):
        raise ValueError(
            "key_bits= is only meaningful for unsigned integer keys (signed "
            "and float transforms touch the high bits)")
    if not 0 < key_bits <= width:
        raise ValueError(f"key_bits must be in (0, {width}], got {key_bits}")
    return key_bits


# ---------------------------------------------------------------------------
# The radix pass: histogram (mapreduce) + offsets (scan) + rank (scan) +
# scatter, all through the backend registry.
# ---------------------------------------------------------------------------


# Bytes of the int32 (elements x buckets) one-hot slab a radix pass holds at
# once.  The stream is ranked one slab at a time, so a pass's temporaries
# stay bounded whatever n is: a whole-stream one-hot of 8 x 256000 sampler
# logits at 256 buckets would take 2 GiB, and its rank scan 2 GiB more.
_RANK_SLAB_BYTES = 64 << 20


def _digit_ranks(digit, n_buckets, backend, policy):
    """Per-element stable rank within its bucket, and the bucket histogram.

    The stream is cut into slabs; inside a slab the rank is an exclusive
    +scan along the element axis with the buckets on the lanes ((1, s, R)
    channel layout) and the slab histogram is a mapreduce over the same
    one-hot; the histogram of the slabs before it, carried across the
    slabs, turns slab ranks into stream ranks.
    """
    n = digit.shape[0]
    scan = ki.resolve_impl("scan@flat", backend)
    mapreduce = ki.resolve_impl("mapreduce@flat", backend)
    # Buckets ride the lanes, padded to whole 128-lane rows in memory.
    slab = min(n, max(1, _RANK_SLAB_BYTES
                      // (4 * ki.round_up(n_buckets, ki.LANES))))
    n_slabs = ki.cdiv(n, slab)
    buckets = jnp.arange(n_buckets, dtype=jnp.int32)

    def one_slab(before, d):
        onehot = (d[:, None] == buckets[None, :]).astype(jnp.int32)
        rank = scan(alg.ADD, onehot[None], axis=1, inclusive=False,
                    policy=policy)[0]
        dc = jnp.clip(d, 0, n_buckets - 1)
        rank = jnp.take_along_axis(rank, dc[:, None], axis=1)[:, 0]
        hist = mapreduce(lambda v: v, alg.ADD, onehot, axis=0, policy=policy)
        return before + hist, rank + before[dc]

    zeros = jnp.zeros((n_buckets,), jnp.int32)
    if n_slabs == 1:
        # No loop for one slab: called outside jit, a lax.scan would be
        # compiled anew on every call.
        hist, rank = one_slab(zeros, digit)
        return rank, hist
    # Padding digits match no bucket: they add nothing to any count.
    padded = jnp.pad(digit, (0, n_slabs * slab - n), constant_values=-1)
    hist, rank = jax.lax.scan(one_slab, zeros, padded.reshape(n_slabs, slab))
    return rank.reshape(-1)[:n], hist


def _radix_pass(bits, payloads, shift, digit_bits, backend, policy):
    n_buckets = 1 << digit_bits
    scan = ki.resolve_impl("scan@flat", backend)

    digit = jnp.right_shift(bits, jnp.asarray(shift, bits.dtype))
    digit = (digit & _full_mask(digit_bits, bits.dtype)).astype(jnp.int32)
    rank, hist = _digit_ranks(digit, n_buckets, backend, policy)
    # Exclusive scan of the histogram = each bucket's base offset.
    base = scan(alg.ADD, hist, inclusive=False, policy=policy)

    dest = base[digit] + rank
    out_bits = jnp.zeros_like(bits).at[dest].set(bits, unique_indices=True)
    out_payloads = tuple(
        jnp.zeros_like(p).at[dest].set(p, unique_indices=True)
        for p in payloads)
    return out_bits, out_payloads


def _radix_passes(bits, payloads, key_bits, digit_bits, backend, policy):
    shift = 0
    while shift < key_bits:
        d = min(digit_bits, key_bits - shift)
        bits, payloads = _radix_pass(bits, payloads, shift, d, backend, policy)
        shift += d
    return bits, payloads


def radix_pass_count(key_bits: int, digit_bits: int) -> int:
    """Number of scatter passes an LSD sort of ``key_bits``-bit keys makes."""
    return ki.cdiv(key_bits, digit_bits)


def _to_bits(keys, kb, descending):
    bits = alg.key_to_radix_bits(keys)
    if descending:
        # Complement reverses the unsigned order; mask back to the
        # significant bits so high bits stay outside the sorted digits.
        bits = jnp.invert(bits) & _full_mask(kb, bits.dtype)
    return bits


def _from_bits(bits, dtype, kb, descending):
    if descending:
        bits = jnp.invert(bits) & _full_mask(kb, bits.dtype)
    return alg.radix_bits_to_key(bits, dtype)


# ---------------------------------------------------------------------------
# Flat sorts.
# ---------------------------------------------------------------------------


@ki.sub_backend_alias
def sort_radix(keys, *, descending=False, key_bits=None, backend="xla",
               policy=None):
    """Stable LSD radix sort of a flat key array (keys only: 2n/pass)."""
    policy = _resolve_policy(policy, backend)
    kb = _key_bits_for(keys, key_bits)
    if keys.shape[0] == 0:
        return keys
    bits = _to_bits(keys, kb, descending)
    bits, _ = _radix_passes(bits, (), kb, policy.sort_digit_bits,
                            backend, policy)
    return _from_bits(bits, keys.dtype, kb, descending)


@ki.sub_backend_alias
def sort_pairs_radix(keys, values, *, descending=False, key_bits=None,
                     backend="xla", policy=None):
    """Stable key sort carrying an arbitrary pytree payload along."""
    policy = _resolve_policy(policy, backend)
    kb = _key_bits_for(keys, key_bits)
    leaves, treedef = jax.tree.flatten(values)
    n = keys.shape[0]
    if any(l.shape[0] != n for l in leaves):
        raise ValueError(
            "sort_pairs: every payload leaf needs leading extent "
            f"{n}, got {[l.shape for l in leaves]}")
    if n == 0:
        return keys, values
    bits = _to_bits(keys, kb, descending)
    bits, leaves = _radix_passes(bits, tuple(leaves), kb,
                                 policy.sort_digit_bits, backend, policy)
    return (_from_bits(bits, keys.dtype, kb, descending),
            jax.tree.unflatten(treedef, list(leaves)))


@ki.sub_backend_alias
def argsort_radix(keys, *, descending=False, key_bits=None,
                  backend="xla", policy=None):
    """Stable sorting permutation (int32), via an index payload."""
    n = keys.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    _, perm = sort_pairs_radix(keys, iota, descending=descending,
                               key_bits=key_bits, backend=backend,
                               policy=policy)
    return perm


# ---------------------------------------------------------------------------
# Segmented variants (PR 1 descriptors: flag array / CSR offsets).  The
# descriptor-exclusivity and num_segments checks live in the registry's
# dispatch pipeline (core/intrinsics.py), which is the only caller of these
# registered compositions.
# ---------------------------------------------------------------------------


def _segment_ids_and_starts(n, flags, offsets, backend, policy):
    """(seg_ids, start_per_elem, seg_bits): contiguous-run bookkeeping.

    ``seg_ids`` are monotone run ids (offsets-declared empty segments do not
    shift them -- only the relative order matters for the sort phase);
    ``start_per_elem[i]`` is the flat index where element i's run begins,
    computed as a running MAX scan of flagged positions -- primitive reuse,
    not a parallel codepath.
    """
    scan = ki.resolve_impl("scan@flat", backend)
    if offsets is not None:
        f = seg_k.offsets_to_flags(offsets, n)
        s_bound = int(offsets.shape[0]) - 1
    else:
        f = flags.astype(jnp.int32)
        s_bound = n  # static bound: at most one segment per element
    seg_ids = seg_k.flags_to_segment_ids(f, backend=backend, policy=policy)
    iota = jnp.arange(n, dtype=jnp.int32)
    flagged = jnp.where((f != 0) | (iota == 0), iota, -1)
    starts = scan(alg.MAX, flagged, policy=policy)
    seg_bits = max(int(s_bound - 1).bit_length(), 0) if s_bound > 1 else 0
    return seg_ids, starts, seg_bits


def _segmented_sort_core(keys, payload_leaves, *, flags, offsets, descending,
                         key_bits, backend, policy, carry_starts=False):
    """Two stable phases: key digits, then segment-id digits.

    With ``carry_starts`` each element's run-start index rides along as one
    extra int32 payload (argsort / top_k need it to localize indices).
    """
    policy = _resolve_policy(policy, backend)
    kb = _key_bits_for(keys, key_bits)
    n = keys.shape[0]
    if n == 0:
        return keys, tuple(payload_leaves), jnp.zeros((0,), jnp.int32)
    seg_ids, starts, seg_bits = _segment_ids_and_starts(
        n, flags, offsets, backend, policy)
    bits = _to_bits(keys, kb, descending)
    extra = (starts,) if carry_starts else ()
    carried = (seg_ids.astype(jnp.uint32),) + extra + tuple(payload_leaves)
    bits, carried = _radix_passes(bits, carried, kb, policy.sort_digit_bits,
                                  backend, policy)
    payload = (bits,) + tuple(carried[1:])
    if seg_bits > 0:
        _, payload = _radix_passes(
            carried[0], payload, seg_bits, policy.sort_digit_bits,
            backend, policy)
    if carry_starts:
        bits, starts, leaves = payload[0], payload[1], tuple(payload[2:])
    else:
        bits, leaves, starts = payload[0], tuple(payload[1:]), None
    return _from_bits(bits, keys.dtype, kb, descending), leaves, starts


@ki.sub_backend_alias
def segmented_sort_radix(keys, *, flags=None, offsets=None, descending=False,
                         key_bits=None, backend="xla", policy=None):
    """Independent stable sort of every contiguous segment (layout kept)."""
    out, _, _ = _segmented_sort_core(
        keys, (), flags=flags, offsets=offsets, descending=descending,
        key_bits=key_bits, backend=backend, policy=policy)
    return out


@ki.sub_backend_alias
def segmented_sort_pairs_radix(keys, values, *, flags=None, offsets=None,
                               descending=False, key_bits=None,
                               backend="xla", policy=None):
    leaves, treedef = jax.tree.flatten(values)
    n = keys.shape[0]
    if any(l.shape[0] != n for l in leaves):
        raise ValueError(
            "segmented_sort_pairs: every payload leaf needs leading extent "
            f"{n}, got {[l.shape for l in leaves]}")
    out, out_leaves, _ = _segmented_sort_core(
        keys, tuple(leaves), flags=flags, offsets=offsets,
        descending=descending, key_bits=key_bits, backend=backend,
        policy=policy)
    return out, jax.tree.unflatten(treedef, list(out_leaves))


@ki.sub_backend_alias
def segmented_argsort_radix(keys, *, flags=None, offsets=None,
                            descending=False, key_bits=None,
                            backend="xla", policy=None):
    """Within-segment sorting permutation: out[i] is the *offset inside its
    segment* of the element placed at flat position i."""
    n = keys.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    _, (perm,), starts = _segmented_sort_core(
        keys, (iota,), flags=flags, offsets=offsets, descending=descending,
        key_bits=key_bits, backend=backend, policy=policy,
        carry_starts=True)
    # The sorted stream keeps the input's segment layout, and each element's
    # run start rode along through both phases -- so within-segment position
    # is just the carried global index minus the carried run start.
    return perm - starts


# ---------------------------------------------------------------------------
# Top-k by radix select.  A top-k needs each segment's k-th key, not the
# order of the elements behind it: one-bit counting passes, most significant
# bit first, narrow that key; what beats it, plus its first ties in index
# order, survives; only the survivors are sorted.
# ---------------------------------------------------------------------------


def _ordered_i32(bits):
    """int32 with the order of the unsigned radix ``bits``: Mosaic compares
    int32, not uint32, so 32-bit words flip their top bit."""
    if bits.dtype.itemsize < 4:
        return bits.astype(jnp.int32)
    top = jnp.asarray(1 << 31, bits.dtype)
    return jax.lax.bitcast_convert_type(bits ^ top, jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "k", "num_segments", "largest", "kb", "backend", "policy"))
def _select_top_k(keys, flags, offsets, *, k, num_segments, largest, kb,
                  backend, policy):
    """``(S, k)`` values and within-segment indices, extreme-first, ties in
    index order; slots past a segment's length hold the identity and -1.

    One program per shape, so an eager call compiles once.  Elements past
    the ``num_segments``-th flagged run belong to no segment.
    """
    n = keys.shape[0]
    S = num_segments
    scan = ki.resolve_impl("scan@flat", backend)
    if offsets is not None:
        layout = Segmented(offsets=offsets)
        offs = offsets.astype(jnp.int32)
        # An element's segment is the number of segment ends at or before it.
        ends = jnp.zeros((n,), jnp.int32).at[offs[1:]].add(1, mode="drop")
        seg = scan(alg.ADD, ends, policy=policy)
    else:
        layout = Segmented(flags=flags, num_segments=S)
        seg = seg_k.flags_to_segment_ids(flags, backend=backend,
                                         policy=policy)
        counts = jnp.zeros((S,), jnp.int32).at[seg].add(1, mode="drop")
        offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                scan(alg.ADD, counts, policy=policy)])
    seg = jnp.minimum(seg, S - 1)
    starts = offs[:-1]
    want = jnp.minimum(offs[1:] - starts, k)
    # The wanted elements are the ``want`` smallest bits of each segment.
    bits = _to_bits(keys, kb, largest)
    order = _ordered_i32(bits & _full_mask(kb, bits.dtype))

    def count_below(thr):
        """Per segment, the elements ordered below its threshold ``thr``."""
        return ki.dispatch(
            "mapreduce", layout, backend,
            (lambda t: (t[0] < thr[t[1]]).astype(jnp.int32), alg.ADD,
             (order, seg)), {"policy": policy})

    def narrow(i, carry):
        # ``t`` is a prefix of the want-th smallest bits of its segment and
        # ``below`` counts the segment's elements under ``t``.
        t, below = carry
        shift = (kb - 1 - i).astype(bits.dtype)
        cand = t | jnp.left_shift(jnp.asarray(1, bits.dtype), shift)
        count = count_below(_ordered_i32(cand))
        up = count < want
        return jnp.where(up, cand, t), jnp.where(up, count, below)

    t, below = jax.lax.fori_loop(
        0, kb, narrow,
        (jnp.zeros((S,), bits.dtype), jnp.zeros((S,), jnp.int32)))

    # Survivors: every element below the segment's k-th bits, then the
    # first ``want - below`` of its ties.  Counts over the whole stream rise
    # by one at each element of a kind, so the j-th element of a kind in
    # segment s sits where its count first reaches (its count before s) +
    # j + 1: a binary search per (s, j) slot, never a pass over n.
    thr = _ordered_i32(t)[seg]
    lt, eq = ki.dispatch(
        "scan", None, backend,
        (alg.ADD, ((order < thr).astype(jnp.int32),
                   (order == thr).astype(jnp.int32))), {"policy": policy})
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    prior = lambda c: jnp.where(  # noqa: E731
        starts > 0, c[jnp.maximum(starts - 1, 0)], 0)[:, None]
    pos = jnp.where(
        j < below[:, None], jnp.searchsorted(lt, prior(lt) + j + 1),
        jnp.searchsorted(eq, prior(eq) + j - below[:, None] + 1))
    valid = j < want[:, None]
    pos = jnp.clip(pos, 0, n - 1)
    # Below-threshold survivors fill a row before its ties, each kind in
    # index order, so the stable sort of the (S, k) grid by (segment, bits)
    # orders ties by index.  Empty slots take the largest bits: last.
    cand = jnp.where(valid, bits[pos], _full_mask(kb, bits.dtype))
    idx = jnp.where(valid, pos - starts[:, None], -1)
    rows = jnp.repeat(jnp.arange(S, dtype=jnp.uint32), k)
    cand, (rows, idx) = _radix_passes(
        cand.reshape(-1), (rows, idx.reshape(-1)), kb,
        policy.sort_digit_bits, backend, policy)
    seg_bits = (S - 1).bit_length()
    if seg_bits:
        _, (cand, idx) = _radix_passes(rows, (cand, idx), seg_bits,
                                       policy.sort_digit_bits, backend,
                                       policy)
    fill = jnp.asarray((alg._min_value if largest else alg._max_value)(
        keys.dtype), keys.dtype)
    vals = _from_bits(cand.reshape(S, k), keys.dtype, kb, largest)
    return jnp.where(valid, vals, fill), idx.reshape(S, k)


@ki.sub_backend_alias
def top_k_radix(keys, k, *, largest=True, key_bits=None, backend="xla",
                policy=None):
    """(values, indices) of the k extreme elements, sorted, ties stable:
    the one-segment radix select."""
    n = keys.shape[0]
    if not 0 <= k <= n:
        raise ValueError(f"top_k: need 0 <= k <= n, got k={k}, n={n}")
    policy = _resolve_policy(policy, backend)
    kb = _key_bits_for(keys, key_bits)
    if k == 0:
        return keys[:0], jnp.zeros((0,), jnp.int32)
    vals, idx = _select_top_k(
        keys, None, jnp.asarray([0, n], jnp.int32), k=k, num_segments=1,
        largest=largest, kb=kb, backend=backend, policy=policy)
    return vals[0], idx[0]


@ki.sub_backend_alias
def segmented_top_k_radix(keys, k, *, flags=None, offsets=None,
                          num_segments=None, largest=True, key_bits=None,
                          backend="xla", policy=None):
    """Per-segment (values, indices): ``(S, k)`` each, extreme-first.

    ``indices`` are within-segment offsets into the original layout; slots
    past a segment's length are filled with the reduction identity
    (``-inf``/dtype-min for ``largest``, ``+inf``/dtype-max otherwise) and
    index ``-1``.  With ``flags``, a static ``num_segments`` is required
    (trailing never-started segments come back entirely filled).
    """
    policy = _resolve_policy(policy, backend)
    if k < 0:
        raise ValueError(f"top_k: k must be >= 0, got {k}")
    n = keys.shape[0]
    if offsets is not None:
        num_segments = int(offsets.shape[0]) - 1
    fill = alg.full_like_spec(
        jax.ShapeDtypeStruct((num_segments, k), keys.dtype),
        alg._min_value(keys.dtype) if largest else alg._max_value(keys.dtype))
    if n == 0 or k == 0 or num_segments == 0:
        return fill, jnp.full((num_segments, k), -1, jnp.int32)
    return _select_top_k(
        keys, flags, offsets, k=k, num_segments=num_segments,
        largest=largest, kb=_key_bits_for(keys, key_bits), backend=backend,
        policy=policy)
