"""Structural HBM-traffic model for every KernelForge-TPU kernel.

Derived from the same grid/BlockSpec arithmetic the kernels use -- each input
block is transferred HBM->VMEM exactly once per grid step that maps it, and
each output block VMEM->HBM exactly once (sequential-grid revisiting keeps
the block resident).  This is the structural 2n-movement argument of the
paper's scan (§V-B) made checkable: the numbers below are what the lowered
kernel *must* move, including ragged-tail padding.

For the XLA-fallback baselines, bytes come from compiled ``cost_analysis()``
instead -- the honest CPU-only stand-in for the paper's measured vendor
baselines.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import intrinsics as ki


def _pad(n, b):
    return ki.cdiv(n, b) * b


def scan_bytes(n: int, dtypes, policy=None) -> int:
    """1-D scan: exactly one read + one write per (padded) element."""
    policy = policy or ki.resolve_tuning()
    sub = max(ki.min_tile(d)[0] for d in dtypes)
    block = policy.nitem_scan * sub * ki.LANES
    np_ = _pad(n, block)
    per_elem = sum(jnp.dtype(d).itemsize for d in dtypes)
    return 2 * np_ * per_elem


def mapreduce_bytes(n: int, in_dtypes, out_dtypes, policy=None) -> int:
    """Reduce: one read per element + O(1) output."""
    policy = policy or ki.resolve_tuning()
    sub = max(ki.min_tile(d)[0] for d in in_dtypes)
    block = policy.nitem_reduce * sub * ki.LANES
    np_ = _pad(n, block)
    return np_ * sum(jnp.dtype(d).itemsize for d in in_dtypes) + \
        sum(jnp.dtype(d).itemsize for d in out_dtypes)


def matvec_bytes(n: int, p: int, dtype, out_dtype=None, policy=None) -> int:
    """y[j] = op_i f(x[i], A[i,j]): A once, x re-read per column stripe."""
    from repro.kernels.ops import _pick_blocks_matvec
    policy = policy or ki.resolve_tuning()
    sz = jnp.dtype(dtype).itemsize
    osz = jnp.dtype(out_dtype or dtype).itemsize
    if p <= 64 and n >= 4 * ki.LANES:
        # Lane-packed tall-narrow path: g row groups share the 128 lanes.
        g = max(ki.LANES // p, 1)
        ng = _pad(n, g) // g
        rn = policy.matvec_rows * ki.min_tile(dtype)[0]
        return (_pad(ng, rn) * g * p + _pad(ng, rn) * g) * sz + ki.LANES * osz
    rn, cp = _pick_blocks_matvec(policy, jnp.zeros((1, 1), dtype), n, p)
    a_bytes = _pad(n, rn) * _pad(p, cp) * sz
    x_bytes = ki.cdiv(p, cp) * _pad(n, rn) * sz       # x per column stripe
    y_bytes = _pad(p, cp) * osz
    return a_bytes + x_bytes + y_bytes


def vecmat_bytes(n: int, p: int, dtype, out_dtype=None, policy=None) -> int:
    """z[i] = op_j f(A[i,j], x[j]): A once, x re-read per row stripe."""
    from repro.kernels.ops import _pick_blocks_vecmat
    policy = policy or ki.resolve_tuning()
    sz = jnp.dtype(dtype).itemsize
    osz = jnp.dtype(out_dtype or dtype).itemsize
    ri, cj = _pick_blocks_vecmat(policy, jnp.zeros((1, 1), dtype), n, p)
    a_bytes = _pad(n, ri) * _pad(p, cj) * sz
    x_bytes = ki.cdiv(n, ri) * _pad(p, cj) * sz
    z_bytes = _pad(n, ri) * osz
    return a_bytes + x_bytes + z_bytes


def quantized_matvec_bytes(n: int, p: int, block: int = 64,
                           policy=None) -> int:
    """Quantized matvec: 1-byte A values + one f32 scale per ``block`` rows
    per column, f32 x/y.  Block picks mirror the dense f32 route with the
    row extent rounded up to whole scale blocks (kernels/ops.py), so the
    model tracks exactly what the quantized kernel streams."""
    from repro.kernels.ops import _pick_blocks_matvec
    policy = policy or ki.resolve_tuning()
    rn, cp = _pick_blocks_matvec(policy, jnp.zeros((1, 1), jnp.float32), n, p)
    rn = ki.round_up(rn, block)
    v_bytes = _pad(n, rn) * _pad(p, cp) * 1
    s_bytes = (_pad(n, rn) // block) * _pad(p, cp) * 4
    x_bytes = ki.cdiv(p, cp) * _pad(n, rn) * 4
    y_bytes = _pad(p, cp) * 4
    return v_bytes + s_bytes + x_bytes + y_bytes


def quantized_vecmat_bytes(n: int, p: int, block: int = 64,
                           policy=None) -> int:
    """Quantized vecmat: same (values + scales) streaming model with the
    vecmat stripe shape; scale blocks still tile the row axis."""
    from repro.kernels.ops import _pick_blocks_vecmat
    policy = policy or ki.resolve_tuning()
    ri, cj = _pick_blocks_vecmat(policy, jnp.zeros((1, 1), jnp.float32), n, p)
    ri = ki.round_up(ri, block)
    v_bytes = _pad(n, ri) * _pad(p, cj) * 1
    s_bytes = (_pad(n, ri) // block) * _pad(p, cj) * 4
    x_bytes = ki.cdiv(n, ri) * _pad(p, cj) * 4
    z_bytes = _pad(n, ri) * 4
    return v_bytes + s_bytes + x_bytes + z_bytes


def gpu_quantized_matvec_bytes(n: int, p: int, block: int = 64,
                               policy=None) -> int:
    """GPU two-phase quantized matvec: values + scales in, f32 partials
    round-tripped once, y out (kernels/gpu.py rounds the row strip up to
    whole scale blocks via lcm)."""
    import math
    policy = policy or ki.resolve_tuning("gpu_generic")
    rows = math.lcm(policy.matvec_rows * ki.WARP, block)
    cols = max(policy.matvec_cols * ki.vec_width(jnp.float32, flavor="gpu"),
               1)
    v_bytes = _pad(n, rows) * _pad(p, cols) * 1
    s_bytes = (_pad(n, rows) // block) * _pad(p, cols) * 4
    x_bytes = ki.cdiv(p, cols) * _pad(n, rows) * 4
    part_bytes = 2 * ki.cdiv(n, rows) * _pad(p, cols) * 4
    y_bytes = _pad(p, cols) * 4
    return v_bytes + s_bytes + x_bytes + part_bytes + y_bytes


def segmented_scan_bytes(n: int, dtypes, policy=None) -> int:
    """Segmented scan: 2n value movement + one int32 flag read per element
    (scanned flags stay in-register and are never written back)."""
    policy = policy or ki.resolve_tuning()
    sub = max(ki.min_tile(d)[0] for d in dtypes)
    block = policy.nitem_scan * sub * ki.LANES
    np_ = _pad(n, block)
    per_elem = sum(jnp.dtype(d).itemsize for d in dtypes)
    return 2 * np_ * per_elem + np_ * 4


def batched_scan_bytes(batch: int, n: int, dtypes, policy=None) -> int:
    """Batched scan: one read + one write per (padded) element of every row,
    in a single launch -- the 2*B*n element-movement bound.  Padding is per
    row (each row tiles independently on the inner grid axis)."""
    policy = policy or ki.resolve_tuning()
    sub = max(ki.min_tile(d)[0] for d in dtypes)
    block = policy.nitem_scan * sub * ki.LANES
    np_ = _pad(n, block)
    per_elem = sum(jnp.dtype(d).itemsize for d in dtypes)
    return 2 * batch * np_ * per_elem


def batched_mapreduce_bytes(batch: int, n: int, in_dtypes, out_dtypes,
                            policy=None) -> int:
    """Batched reduce: one read per element of every row + one output
    element per row."""
    policy = policy or ki.resolve_tuning()
    sub = max(ki.min_tile(d)[0] for d in in_dtypes)
    block = policy.nitem_reduce * sub * ki.LANES
    np_ = _pad(n, block)
    return batch * (np_ * sum(jnp.dtype(d).itemsize for d in in_dtypes) +
                    sum(jnp.dtype(d).itemsize for d in out_dtypes))


def batched_matvec_bytes(batch: int, n: int, p: int, dtype, out_dtype=None,
                         policy=None) -> int:
    """B independent matvecs in one launch: per-row traffic times B (the
    batch grid dimension maps disjoint blocks, so no amplification)."""
    from repro.kernels.ops import _pick_blocks_matvec
    policy = policy or ki.resolve_tuning()
    sz = jnp.dtype(dtype).itemsize
    osz = jnp.dtype(out_dtype or dtype).itemsize
    rn, cp = _pick_blocks_matvec(policy, jnp.zeros((1, 1), dtype), n, p)
    a_bytes = _pad(n, rn) * _pad(p, cp) * sz
    x_bytes = ki.cdiv(p, cp) * _pad(n, rn) * sz
    y_bytes = _pad(p, cp) * osz
    return batch * (a_bytes + x_bytes + y_bytes)


def channel_scan_bytes(batch: int, t: int, c: int, n_leaves_in: int,
                       n_leaves_out: int, dtype, policy=None) -> int:
    """(B, T, C) channelwise scan (the batched linear-recurrence layout):
    one read per input leaf element, one write per output leaf element,
    padded to the (t_rows, LANES) tile grid."""
    policy = policy or ki.resolve_tuning()
    sub = ki.min_tile(dtype)[0]
    t_rows = min(policy.nitem_scan * sub,
                 max(sub, 1 << (max(t - 1, 1)).bit_length()))
    tp = _pad(t, t_rows)
    cp_ = _pad(c, ki.LANES)
    sz = jnp.dtype(dtype).itemsize
    return batch * tp * cp_ * sz * (n_leaves_in + n_leaves_out)


# ---------------------------------------------------------------------------
# pallas-gpu routes: block = gpu_threads * nitem * vec_width (float4-style
# transactions), and the decoupled-lookback scan adds only the O(n/block)
# cross-block mailbox on top of the 2n element movement -- the single-pass
# argument of the paper's GPU scan made checkable.
# ---------------------------------------------------------------------------


def _gpu_block(policy, nitem: int, dtypes) -> int:
    vw = min(ki.vec_width(d, flavor="gpu") for d in dtypes)
    return policy.gpu_threads * nitem * vw


def gpu_scan_bytes(n: int, dtypes, policy) -> int:
    """Single-pass lookback scan: one read + one write per (padded) element,
    plus the per-block (partial, status) mailbox -- 2n + O(n/block), NOT the
    3n of scan-then-propagate or the multi-launch reduce-then-scan."""
    block = _gpu_block(policy, policy.nitem_scan, dtypes)
    np_ = _pad(n, block)
    nb = np_ // block
    per_elem = sum(jnp.dtype(d).itemsize for d in dtypes)
    # Mailbox: each block writes its inclusive partial + an int32 status
    # flag and reads its predecessor's.
    return 2 * np_ * per_elem + 2 * nb * (per_elem + 4)


def gpu_batched_scan_bytes(batch: int, n: int, dtypes, policy) -> int:
    """Per-row lookback rides the inner grid axis: B x the flat traffic."""
    return batch * gpu_scan_bytes(n, dtypes, policy)


def gpu_mapreduce_bytes(n: int, in_dtypes, out_dtypes, policy) -> int:
    """Block partials written once, folded once: n reads + 2*(n/block)."""
    block = _gpu_block(policy, policy.nitem_reduce, in_dtypes)
    np_ = _pad(n, block)
    nb = np_ // block
    out_elem = sum(jnp.dtype(d).itemsize for d in out_dtypes)
    return (np_ * sum(jnp.dtype(d).itemsize for d in in_dtypes)
            + 2 * nb * out_elem + out_elem)


def gpu_batched_mapreduce_bytes(batch: int, n: int, in_dtypes, out_dtypes,
                                policy) -> int:
    return batch * gpu_mapreduce_bytes(n, in_dtypes, out_dtypes, policy)


def gpu_matvec_bytes(n: int, p: int, dtype, out_dtype=None,
                     policy=None) -> int:
    """A once, x re-read per column stripe; two-phase partials form: each
    row strip writes its own (nbi, p) partial row (no output revisiting,
    so the kernel is exact on parallel grids), the strips fold outside the
    kernel (read back once), y written once."""
    policy = policy or ki.resolve_tuning("gpu_generic")
    sz = jnp.dtype(dtype).itemsize
    osz = jnp.dtype(out_dtype or dtype).itemsize
    rows = policy.matvec_rows * ki.WARP
    cols = max(policy.matvec_cols * ki.vec_width(dtype, flavor="gpu"), 1)
    a_bytes = _pad(n, rows) * _pad(p, cols) * sz
    x_bytes = ki.cdiv(p, cols) * _pad(n, rows) * sz
    part_bytes = 2 * ki.cdiv(n, rows) * _pad(p, cols) * osz
    y_bytes = _pad(p, cols) * osz
    return a_bytes + x_bytes + part_bytes + y_bytes


def gpu_vecmat_bytes(n: int, p: int, dtype, out_dtype=None,
                     policy=None) -> int:
    policy = policy or ki.resolve_tuning("gpu_generic")
    sz = jnp.dtype(dtype).itemsize
    osz = jnp.dtype(out_dtype or dtype).itemsize
    rows = policy.vecmat_rows * ki.WARP
    cols = max(policy.vecmat_cols * ki.vec_width(dtype, flavor="gpu"), 1)
    a_bytes = _pad(n, rows) * _pad(p, cols) * sz
    x_bytes = ki.cdiv(n, rows) * _pad(p, cols) * sz
    part_bytes = 2 * ki.cdiv(p, cols) * _pad(n, rows) * osz
    z_bytes = _pad(n, rows) * osz
    return a_bytes + x_bytes + part_bytes + z_bytes


def gpu_copy_bytes(n: int, dtype, nitem: int, policy) -> int:
    block = policy.gpu_threads * nitem * ki.vec_width(dtype, flavor="gpu")
    return 2 * _pad(n, block) * jnp.dtype(dtype).itemsize


# ---------------------------------------------------------------------------
# @sharded routes: per-DEVICE traffic of the staged plans
# (distributed/primitives.py).  Each model is local-stage bytes at the
# ceil(n/S) shard extent plus the collective stage priced off the operator's
# FoldSpec descriptor -- an all-reduce-shaped collective (psum/pmax/pmin)
# moves ~2x its payload through each device (ring send+recv), an all_gather
# lands S copies.  The strong-scaling claim these encode: local traffic is
# ~1/S of the flat route while the collective term is independent of n
# (except the sort family's documented O(n) portable gather).
# ---------------------------------------------------------------------------


def fold_bytes(collectives, payload: int, shards: int) -> int:
    """Per-device bytes of a FoldSpec's collective stage.

    ``collectives`` is the descriptor tuple from
    ``core.operators.collective_fold_spec(op).collectives`` -- the byte
    model prices exactly the collectives the staged plan will issue.
    """
    total = 0
    for c in collectives:
        total += shards * payload if c == "all_gather" else 2 * payload
    return total


def sharded_scan_bytes(n: int, dtypes, shards: int, policy=None) -> int:
    """scan@sharded per device: the local scan at ceil(n/S), the carry
    epilogue's re-read + write of the local prefix (op(carry, incl)), and
    the all-gathered per-shard totals (S elements -- O(S), not O(n))."""
    n_loc = ki.cdiv(n, shards)
    per_elem = sum(jnp.dtype(d).itemsize for d in dtypes)
    local = scan_bytes(n_loc, dtypes, policy)
    epilogue = 2 * n_loc * per_elem
    collective = fold_bytes(("all_gather",), per_elem, shards)
    return local + epilogue + collective


def sharded_mapreduce_bytes(n: int, in_dtypes, out_dtypes, shards: int,
                            collectives=("psum",), policy=None) -> int:
    """mapreduce@sharded per device: local reduce at ceil(n/S) + the
    operator's fold over the O(1) output -- pass the FoldSpec's
    ``collectives`` tuple for non-native operators (logsumexp is
    ("pmax", "psum"), the gather fallback is ("all_gather",))."""
    n_loc = ki.cdiv(n, shards)
    out_payload = sum(jnp.dtype(d).itemsize for d in out_dtypes)
    return (mapreduce_bytes(n_loc, in_dtypes, out_dtypes, policy)
            + fold_bytes(collectives, out_payload, shards))


def sharded_matvec_bytes(n: int, p: int, dtype, shards: int, out_dtype=None,
                         policy=None) -> int:
    """matvec@sharded per device: the local strip matvec over n//S rows,
    the replicated ``n % S`` remainder rows (folded in by the epilogue),
    and the ADD fold's psum of the (p,)-sized strip partial."""
    sz_out = jnp.dtype(out_dtype or dtype).itemsize
    n_loc = n // shards
    rem = n - n_loc * shards
    b = matvec_bytes(n_loc, p, dtype, out_dtype, policy) if n_loc else 0
    if rem:
        b += matvec_bytes(rem, p, dtype, out_dtype, policy)
    return b + fold_bytes(("psum",), p * sz_out, shards)


def sharded_vecmat_bytes(n: int, p: int, dtype, shards: int, out_dtype=None,
                         policy=None) -> int:
    """vecmat@sharded per device: the column-strip mirror -- p//S columns
    local, ``p % S`` replicated, psum of the (n,)-sized partial."""
    sz_out = jnp.dtype(out_dtype or dtype).itemsize
    p_loc = p // shards
    rem = p - p_loc * shards
    b = vecmat_bytes(n, p_loc, dtype, out_dtype, policy) if p_loc else 0
    if rem:
        b += vecmat_bytes(n, rem, dtype, out_dtype, policy)
    return b + fold_bytes(("psum",), n * sz_out, shards)


def sharded_channel_scan_bytes(batch: int, t: int, c: int, shards: int,
                               dtype, policy=None) -> int:
    """linear_recurrence@sharded per device: the local (B, ceil(T/S), C)
    affine scan (2 leaves in, 2 out), the gathered per-shard (A, B) totals
    (2 x (B, C) x S -- sequence-length independent), and the epilogue's
    re-read of both inclusive planes + the h write."""
    t_loc = ki.cdiv(t, shards)
    sz = jnp.dtype(dtype).itemsize
    local = channel_scan_bytes(batch, t_loc, c, 2, 2, dtype, policy)
    epilogue = 3 * batch * t_loc * c * sz
    collective = fold_bytes(("all_gather",), 2 * batch * c * sz, shards)
    return local + epilogue + collective


def sort_pass_count(key_bits: int, digit_bits: int, num_segments: int = 1) -> int:
    """LSD scatter passes: key digits, then segment-id digits (if any)."""
    passes = ki.cdiv(key_bits, digit_bits)
    if num_segments > 1:
        passes += ki.cdiv(max((num_segments - 1).bit_length(), 1), digit_bits)
    return passes


def sort_bytes(n: int, dtype, policy=None, *, key_bits: int | None = None,
               payload_itemsize: int = 0, num_segments: int = 1) -> int:
    """Structural *key-level* movement of an LSD radix pass, the fused-kernel
    bound the design targets (and the CI budget enforces):

    * keys read for the digit extract / rank scan (1n),
    * keys re-read and written by the rank-and-scatter (2n),
    * any payload read + scattered alongside (2n x payload bytes),
    * the 2^digit_bits histogram + its offsets (O(R), not O(n)).

    Honesty note: this is what a pass *must* move -- the <= passes x 3n
    budget made checkable.  A fused TPU kernel keeps the one-hot/rank tiles
    in VMEM; the current portable composition instead materializes an
    ``(n, 2^digit_bits)`` rank intermediate through XLA per pass, so its
    realized traffic exceeds this bound by up to the digit fan-out (the
    tuning ladder's ``sort_digit_bits`` races exactly that trade-off, and
    shrinking the gap is the motivation for a future fused sort pass).
    Fewer significant ``key_bits`` (small-range keys like expert ids)
    proportionally cut the pass count in both models.  The top-k routes no
    longer sort the stream (:func:`top_k_bytes`).
    """
    policy = policy or ki.resolve_tuning()
    sz = jnp.dtype(dtype).itemsize
    kb = key_bits if key_bits is not None else 8 * sz
    passes = sort_pass_count(kb, policy.sort_digit_bits, num_segments)
    sub = ki.min_tile(dtype)[0]
    block = policy.nitem_scan * sub * ki.LANES
    np_ = _pad(n, block)
    per_pass = (3 * np_ * sz + 2 * np_ * payload_itemsize +
                2 * (1 << policy.sort_digit_bits) * 4)
    return passes * per_pass


def top_k_bytes(n: int, k: int, dtype, policy=None, *,
                num_segments: int = 1) -> int:
    """top-k by radix select: what selection must move.

    * one read of the n keys per threshold pass, one pass per key bit (each
      pass counts, per segment, the keys below a candidate threshold);
    * one scan of the int32 survivor mask;
    * the index-carrying sort of the S*k survivors, and the (S, k) output.

    Honesty note: the portable composition counts through
    ``mapreduce@segmented``, a segmented scan plus a gather, so each pass
    also reads the segment ids, writes and re-reads the 0/1 mask and writes
    the scan's n counts: about seven times this per-pass bound (one key
    read).  A fused count kernel would keep all of that in VMEM.  No
    ``(n, 2^digit_bits)`` array is made: the sort runs over S*k survivors.
    """
    sz = jnp.dtype(dtype).itemsize
    cand = num_segments * k
    return (8 * sz * n * sz + scan_bytes(n, [jnp.int32], policy) +
            sort_bytes(cand, dtype, policy, payload_itemsize=4,
                       num_segments=num_segments) +
            cand * (sz + 4))


def sharded_top_k_bytes(n: int, k: int, dtype, shards: int,
                        policy=None) -> int:
    """top_k@sharded per device: local top-k over ceil(n/S), the gathered
    S x k (value, global index) candidates, and the k-way partial merge (an
    index-carrying sort of the S*k candidate pool -- O(S*k), not O(n))."""
    sz = jnp.dtype(dtype).itemsize
    n_loc = ki.cdiv(n, shards)
    cand = shards * min(k, n_loc)
    return (top_k_bytes(n_loc, min(k, n_loc), dtype, policy)
            + fold_bytes(("all_gather",), min(k, n_loc) * (sz + 4), shards)
            + sort_bytes(cand, dtype, policy, payload_itemsize=4))


def sharded_sort_pairs_bytes(n: int, dtype, shards: int, *,
                             payload_itemsize: int = 0, policy=None) -> int:
    """sort_pairs@sharded per device: the local sort of ceil(n/S), then the
    portable splitter exchange -- the gathered full stream (keys + payload,
    the documented O(n)-per-device step of the portable merge), S rank
    passes over the gathered keys, and the scattered local output slice."""
    sz = jnp.dtype(dtype).itemsize
    n_loc = ki.cdiv(n, shards)
    n_all = shards * n_loc
    local = sort_bytes(n_loc, dtype, policy, payload_itemsize=payload_itemsize)
    gather = fold_bytes(("all_gather",), n_loc * (sz + payload_itemsize),
                        shards)
    rank = shards * n_all * sz                       # searchsorted per run
    scatter = n_all * (sz + payload_itemsize)        # read-back + local write
    return local + gather + rank + scatter


def copy_bytes(n: int, dtype, nitem: int, policy=None) -> int:
    sub = ki.min_tile(dtype)[0]
    block = nitem * sub * ki.LANES
    return 2 * _pad(n, block) * jnp.dtype(dtype).itemsize


def xla_baseline_cost(fn, *args) -> dict:
    """Compile ``fn`` on the host backend and read its cost analysis."""
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}
