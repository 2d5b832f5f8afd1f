"""Radix sort / argsort / top-k family vs the numpy/Python-loop oracles.

Covers every supported key dtype (unsigned, signed, f32/bf16 with negatives,
±0.0, ±inf and NaNs -- the pinned NaN-last total order), pytree payloads,
stability under heavy duplication, descending order, the key_bits fast path,
both segment descriptors, and zero-length inputs, on both the xla and
pallas-interpret backends.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_trees_close
from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Segmented
from repro.kernels import ref

BACKENDS = ["xla", "pallas-interpret"]

# Every dtype on xla (cheap); the interpret kernel bodies are exercised on a
# spread of widths/transforms (unsigned, signed, float, bfloat) at sizes
# keeping the pass count x grid-step budget test-suite friendly.
DTYPES_XLA = ["uint8", "uint16", "uint32", "int8", "int32",
              "float32", "bfloat16"]
DTYPES_INTERPRET = ["uint8", "int16", "float32", "bfloat16"]


def _keys(dtype, n, seed=0, specials=True):
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        return jnp.asarray(
            rng.integers(info.min, int(info.max) + 1, n), dt)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32), dt)
    if specials and n >= 16:
        x = (x.at[1].set(jnp.nan).at[5].set(-jnp.nan)
              .at[7].set(jnp.inf).at[9].set(-jnp.inf)
              .at[11].set(0.0).at[13].set(-0.0))
    return x


def _equal_with_nans(got, want, err=""):
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(want, np.float64),
        rtol=0, atol=0, equal_nan=True, err_msg=err)


@pytest.mark.parametrize("backend,dtype",
                         [("xla", d) for d in DTYPES_XLA] +
                         [("pallas-interpret", d) for d in DTYPES_INTERPRET])
def test_sort_matches_oracle(backend, dtype):
    n = 300 if backend == "xla" or jnp.dtype(dtype).itemsize < 4 else 150
    k = _keys(dtype, n)
    got = forge.sort(k, backend=backend)
    assert got.dtype == k.dtype
    _equal_with_nans(got, ref.ref_sort(k), err=f"{dtype}/{backend}")


@pytest.mark.parametrize("backend,dtype",
                         [("xla", d) for d in DTYPES_XLA] +
                         [("pallas-interpret", d) for d in DTYPES_INTERPRET])
def test_argsort_stable_and_exact(backend, dtype):
    """Heavy duplication: the permutation itself must match the stable
    oracle exactly (not just produce equal keys)."""
    rng = np.random.default_rng(3)
    n = 257 if backend == "xla" else 130
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.integer):
        k = jnp.asarray(rng.integers(0, 7, n), dt)   # ~37 ties per value
    else:
        k = jnp.asarray(rng.integers(0, 7, n).astype(np.float32), dt)
        k = k.at[2].set(jnp.nan).at[40].set(jnp.nan).at[17].set(-0.0)
    got = forge.argsort(k, backend=backend)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.ref_argsort(k)),
                                  err_msg=f"{dtype}/{backend}")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("descending", [False, True])
def test_sort_pairs_pytree_payload(backend, descending):
    """Arbitrary pytree payload, incl. a 2-D leaf, rides the permutation."""
    rng = np.random.default_rng(4)
    n = 300 if backend == "xla" else 260
    k = jnp.asarray(rng.integers(0, 50, n), jnp.uint16)
    payload = {"idx": jnp.arange(n, dtype=jnp.int32),
               "w": (jnp.asarray(rng.normal(size=n), jnp.float32),
                     jnp.asarray(rng.normal(size=(n, 3)), jnp.float32))}
    ks, vs = forge.sort_pairs(k, payload, descending=descending,
                              backend=backend)
    rk, rv = ref.ref_sort_pairs(k, payload, descending=descending)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(rk))
    assert_trees_close(vs, rv, rtol=0, atol=0,
                       err=f"{backend}/desc={descending}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_top_k_ties_stable(backend):
    rng = np.random.default_rng(5)
    n = 200
    k = jnp.asarray(rng.integers(0, 9, n).astype(np.float32))
    for largest in (True, False):
        v, i = forge.top_k(k, 17, largest=largest, backend=backend)
        rv, ri = ref.ref_top_k(k, 17, largest=largest)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


def test_top_k_nan_ranks_above_inf():
    k = jnp.asarray([1.0, jnp.inf, jnp.nan, -jnp.inf, 2.0], jnp.float32)
    v, i = forge.top_k(k, 2, backend="xla")
    assert np.isnan(np.asarray(v)[0]) and int(i[0]) == 2
    assert np.isinf(np.asarray(v)[1]) and int(i[1]) == 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_key_bits_small_range(backend):
    """key_bits caps the pass count; result identical to the full sort."""
    rng = np.random.default_rng(6)
    k = jnp.asarray(rng.integers(0, 13, 300), jnp.uint32)   # fits in 4 bits
    got = forge.argsort(k, key_bits=4, backend=backend)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.ref_argsort(k)))


def test_key_bits_validation():
    with pytest.raises(ValueError):
        forge.sort(jnp.zeros((4,), jnp.float32), key_bits=8, backend="xla")
    with pytest.raises(ValueError):
        forge.sort(jnp.zeros((4,), jnp.int32), key_bits=8, backend="xla")
    with pytest.raises(ValueError):
        forge.sort(jnp.zeros((4,), jnp.uint8), key_bits=0, backend="xla")
    with pytest.raises(ValueError):
        forge.sort(jnp.zeros((4,), jnp.uint8), key_bits=9, backend="xla")
    with pytest.raises(TypeError):
        forge.sort(jnp.zeros((4,), jnp.complex64), backend="xla")


@pytest.mark.parametrize("backend", BACKENDS)
def test_zero_length_inputs(backend):
    empty = jnp.zeros((0,), jnp.float32)
    assert forge.sort(empty, backend=backend).shape == (0,)
    assert forge.argsort(empty, backend=backend).shape == (0,)
    ks, vs = forge.sort_pairs(empty, jnp.zeros((0,), jnp.int32),
                              backend=backend)
    assert ks.shape == (0,) and vs.shape == (0,)
    v, i = forge.top_k(empty, 0, backend=backend)
    assert v.shape == (0,) and i.shape == (0,)
    with pytest.raises(ValueError):
        forge.top_k(empty, 1, backend=backend)


def test_radix_bit_transform_roundtrip():
    """key_to_radix_bits is order-preserving and (canonicalization aside)
    invertible for every supported dtype."""
    rng = np.random.default_rng(7)
    for dtype in DTYPES_XLA:
        k = _keys(dtype, 64, seed=8)
        bits = alg.key_to_radix_bits(k)
        assert jnp.issubdtype(bits.dtype, jnp.unsignedinteger)
        assert bits.dtype.itemsize == jnp.dtype(dtype).itemsize
        # order preservation against the oracle order
        order = np.asarray(ref.ref_argsort(k))
        b = np.asarray(bits)[order].astype(np.uint64)
        assert (np.diff(b) >= 0).all(), dtype
        back = alg.radix_bits_to_key(bits, k.dtype)
        _equal_with_nans(back, jnp.where(k == 0, jnp.zeros_like(k), k)
                         if jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
                         else k, err=dtype)


# ---------------------------------------------------------------------------
# Segmented variants.
# ---------------------------------------------------------------------------

OFFSETS = [0, 7, 7, 40, 41, 170, 300]


def _flags_from_offsets(offsets, n):
    f = np.zeros(n, np.int32)
    f[[o for o in offsets[:-1] if o < n]] = 1
    f[0] = 1
    return jnp.asarray(f)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("variant", ["offsets", "flags"])
def test_segmented_sort_and_argsort(backend, variant):
    rng = np.random.default_rng(9)
    n = OFFSETS[-1]
    k = jnp.asarray(rng.integers(0, 2**16, n), jnp.uint16)
    kw = ({"offsets": jnp.asarray(OFFSETS, jnp.int32)}
          if variant == "offsets"
          else {"flags": _flags_from_offsets(OFFSETS, n)})
    got = forge.sort(k, backend=backend, layout=Segmented(**kw))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.ref_segmented_sort(k, offsets=OFFSETS)),
        err_msg=f"{backend}/{variant}")
    ga = forge.argsort(k, backend=backend, layout=Segmented(**kw))
    np.testing.assert_array_equal(
        np.asarray(ga),
        np.asarray(ref.ref_segmented_argsort(k, offsets=OFFSETS)),
        err_msg=f"{backend}/{variant}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_segmented_sort_pairs_floats_with_specials(backend):
    n = OFFSETS[-1] if backend == "xla" else 170
    offsets = OFFSETS if backend == "xla" else [0, 7, 7, 40, 41, 170]
    k = _keys("float32", n, seed=10)
    vals = jnp.arange(n, dtype=jnp.int32)
    ks, vs = forge.sort_pairs(
        k, vals, layout=Segmented(offsets=jnp.asarray(offsets, jnp.int32)),
        backend=backend)
    rk, rv = ref.ref_segmented_sort_pairs(k, vals, offsets=offsets)
    _equal_with_nans(ks, rk, err=backend)
    np.testing.assert_array_equal(np.asarray(vs), np.asarray(rv))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("variant", ["offsets", "flags"])
def test_segmented_top_k_ragged(backend, variant):
    """k exceeds some segment lengths; empty + never-started segments fill."""
    rng = np.random.default_rng(11)
    n = OFFSETS[-1]
    k = jnp.asarray(rng.normal(size=n), jnp.float32)
    if variant == "offsets":
        kw = {"offsets": jnp.asarray(OFFSETS, jnp.int32)}
        ns = len(OFFSETS) - 1
        rv, ri = ref.ref_segmented_top_k(k, 9, offsets=OFFSETS)
    else:
        # Flags cannot express empty segments: segments are the flagged
        # runs, numbered in flag order (the segmented_mapreduce convention),
        # plus never-started trailing ones up to num_segments.
        kw = {"flags": _flags_from_offsets(OFFSETS, n), "num_segments": 8}
        ns = 8
        rv, ri = ref.ref_segmented_top_k(
            k, 9, flags=np.asarray(kw["flags"]), num_segments=8)
    v, i = forge.top_k(k, 9, backend=backend, layout=Segmented(**kw))
    assert v.shape == (ns, 9) and i.shape == (ns, 9)
    _equal_with_nans(v, rv, err=f"{backend}/{variant}")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    # declared-but-elementless segments come back entirely filled: the empty
    # offsets segment, or the never-started trailing flag segments
    empty_row = 1 if variant == "offsets" else ns - 1
    assert np.isneginf(np.asarray(v)[empty_row]).all()
    assert (np.asarray(i)[empty_row] == -1).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_segmented_sort_multiblock(backend):
    """Segments crossing kernel grid-step boundaries, incl. one segment
    spanning every block of the rank scan."""
    rng = np.random.default_rng(12)
    n = 2600
    k = jnp.asarray(rng.integers(0, 256, n), jnp.uint8)
    offsets = jnp.asarray([0, 1, 2047, 2050, 2600], jnp.int32)
    got = forge.sort(k, layout=Segmented(offsets=offsets), backend=backend)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.ref_segmented_sort(k, offsets=np.asarray(offsets))))
    # one segment spanning everything == the flat sort
    got = forge.sort(k, layout=Segmented(offsets=jnp.asarray([0, n], jnp.int32)),
                     backend=backend)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(forge.sort(k, backend=backend)))


@pytest.mark.parametrize("backend", BACKENDS)
def test_segmented_zero_length(backend):
    empty = jnp.zeros((0,), jnp.float32)
    got = forge.sort(empty, layout=Segmented(offsets=jnp.asarray([0, 0, 0])),
                     backend=backend)
    assert got.shape == (0,)
    v, i = forge.top_k(empty, 3, layout=Segmented(offsets=jnp.asarray([0, 0, 0])),
                       backend=backend)
    assert v.shape == (2, 3) and np.isneginf(np.asarray(v)).all()
    assert (np.asarray(i) == -1).all()


def test_segmented_descriptor_validation():
    k = jnp.arange(8, dtype=jnp.float32)
    with pytest.raises(ValueError):
        forge.sort(k, layout=Segmented(), backend="xla")
    with pytest.raises(ValueError):
        forge.top_k(k, 2, layout=Segmented(flags=jnp.ones(8, jnp.int32)),
                    backend="xla")   # flags need num_segments


# ---------------------------------------------------------------------------
# Consumer-shaped compositions.
# ---------------------------------------------------------------------------


def test_moe_dispatch_shape_sort_matches_argsort():
    """The moe_sharded dispatch pattern: stable expert-id sort_pairs must
    reproduce the XLA argsort-based stream it replaced."""
    rng = np.random.default_rng(13)
    E, n = 16, 512
    flat_e = jnp.asarray(rng.integers(0, E, n), jnp.int32)
    flat_t = jnp.arange(n, dtype=jnp.int32)
    flat_g = jnp.asarray(rng.normal(size=n), jnp.float32)
    se, (st, sg) = forge.sort_pairs(
        flat_e.astype(jnp.uint32), (flat_t, flat_g),
        key_bits=(E - 1).bit_length(), backend="xla")
    order = np.argsort(np.asarray(flat_e), kind="stable")
    np.testing.assert_array_equal(np.asarray(se), np.asarray(flat_e)[order])
    np.testing.assert_array_equal(np.asarray(st), np.asarray(flat_t)[order])
    np.testing.assert_array_equal(np.asarray(sg), np.asarray(flat_g)[order])


def test_sort_under_jit():
    """The composition is jit-traceable (static shapes throughout)."""
    k = jnp.asarray(np.random.default_rng(14).normal(size=256), jnp.float32)

    @jax.jit
    def f(keys):
        return forge.top_k(keys, 8, backend="xla")

    v, i = f(k)
    rv, ri = ref.ref_top_k(k, 8)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


# ---------------------------------------------------------------------------
# top-k by radix select: the contract of the sort-based composition it
# replaced, case by case, under jit.
# ---------------------------------------------------------------------------

SPECIALS = [np.nan, -np.nan, np.inf, -np.inf, 0.0, -0.0]


def _select_keys(kind, n, seed):
    rng = np.random.default_rng(seed)
    if kind == "ties":        # few values: many keys equal the k-th one
        return jnp.asarray(rng.integers(0, 7, n).astype(np.float32))
    if kind == "specials":    # every special, many times, amid ties
        x = rng.integers(-2, 3, n).astype(np.float32)
        x[rng.integers(0, n, n // 3)] = rng.choice(SPECIALS, n // 3)
        return jnp.asarray(x)
    if kind == "equal":
        return jnp.full((n,), 1.5, jnp.float32)
    if kind == "bf16":
        return jnp.asarray(rng.integers(-3, 4, n).astype(np.float32)
                           ).astype(jnp.bfloat16).at[4].set(jnp.nan)
    if kind == "int32":
        return jnp.asarray(rng.choice(
            [np.iinfo(np.int32).min, -5, 0, 3, np.iinfo(np.int32).max], n),
            jnp.int32)
    if kind == "uint32":      # 10 significant bits
        return jnp.asarray(rng.integers(0, 12, n) * 80, jnp.uint32)
    raise ValueError(kind)


# name -> (keys, k, descriptor, key_bits, largest); descriptor None is flat,
# a list is CSR offsets, ("flags", offsets, num_segments) is the flag form.
SELECT_CASES = {
    "flat-ties-k17": ("ties", 17, None, None, True),
    "flat-ties-smallest-k17": ("ties", 17, None, None, False),
    "flat-specials-k40": ("specials", 40, None, None, True),
    "flat-k1": ("ties", 1, None, None, True),
    "flat-k-len": ("specials", 300, None, None, False),
    "flat-int32-k33": ("int32", 33, None, None, True),
    "flat-uint32-key-bits": ("uint32", 50, None, 10, False),
    "seg-ties-k9": ("ties", 9, OFFSETS, None, True),
    "seg-specials-smallest-k9": ("specials", 9, OFFSETS, None, False),
    "seg-equal-k5": ("equal", 5, OFFSETS, None, True),
    "seg-k0": ("ties", 0, OFFSETS, None, True),
    "seg-k1": ("specials", 1, OFFSETS, None, True),
    "seg-k-over-len": ("ties", 400, OFFSETS, None, True),
    "seg-empty-ends": ("specials", 6, [0, 0, 50, 50, 300, 300], None, True),
    "seg-bf16": ("bf16", 12, OFFSETS, None, True),
    "seg-uint32-key-bits": ("uint32", 20, OFFSETS, 10, True),
    "flags-never-started": ("ties", 9, ("flags", OFFSETS, 8), None, True),
    "flags-fewer-segments": ("int32", 9, ("flags", OFFSETS, 3), None, False),
}


@pytest.mark.parametrize("case", sorted(SELECT_CASES))
@pytest.mark.parametrize("backend", BACKENDS)
def test_top_k_select_matches_oracle(backend, case):
    kind, k, desc, key_bits, largest = SELECT_CASES[case]
    n = 300
    keys = _select_keys(kind, n, seed=len(case))
    kw = {"largest": largest, "key_bits": key_bits, "backend": backend}
    if desc is None:
        got = jax.jit(lambda x: forge.top_k(x, k, **kw))(keys)
        want = ref.ref_top_k(keys, k, largest=largest)
    elif isinstance(desc, list):
        got = jax.jit(lambda x, o: forge.top_k(
            x, k, layout=Segmented(offsets=o), **kw))(
                keys, jnp.asarray(desc, jnp.int32))
        want = ref.ref_segmented_top_k(keys, k, offsets=desc,
                                       largest=largest)
    else:
        _, offs, ns = desc
        flags = _flags_from_offsets(offs, n)
        got = jax.jit(lambda x, f: forge.top_k(
            x, k, layout=Segmented(flags=f, num_segments=ns), **kw))(
                keys, flags)
        want = ref.ref_segmented_top_k(keys, k, flags=np.asarray(flags),
                                       num_segments=ns, largest=largest)
    assert got[0].dtype == keys.dtype and got[1].dtype == jnp.int32
    assert got[0].shape == want[0].shape == got[1].shape
    _equal_with_nans(got[0], want[0], err=case)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]),
                                  err_msg=case)


@pytest.mark.parametrize("rows", [8, 1])
@pytest.mark.parametrize("backend", BACKENDS)
def test_top_k_select_sampler_shape(backend, rows):
    """The sampler's call: rows x 32256 f32 logits, 64 candidates, ties
    planted across the 64th candidate of every row."""
    V, k = 32256, 64
    rng = np.random.default_rng(rows)
    x = rng.normal(size=(rows, V)).astype(np.float32)
    for r in range(rows):
        ids = rng.permutation(V)
        x[r, ids[:50]] = 6.0 + rng.uniform(0, 1, 50)
        x[r, ids[50:90]] = 6.0
    flat = jnp.asarray(x.reshape(-1))
    offsets = jnp.arange(rows + 1, dtype=jnp.int32) * V
    v, i = jax.jit(lambda f, o: forge.top_k(
        f, k, layout=Segmented(offsets=o), backend=backend))(flat, offsets)
    rv, ri = ref.ref_segmented_top_k(flat, k, offsets=np.asarray(offsets))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


def test_top_k_select_holds_no_per_bucket_array():
    """The TPU route's program at the sampler's 8 x 32256, k = 64 holds no
    array of 16 n elements or more: selection counts, it never spreads the
    stream over digit buckets (the sort's one-hot was 256 n)."""
    from repro.core import intrinsics as ki
    n = 8 * 32256
    offsets = jnp.arange(9, dtype=jnp.int32) * 32256
    jaxpr = jax.make_jaxpr(lambda f: ki.resolve_impl(
        "top_k@segmented", "pallas-tpu")(
            f, 64, offsets=offsets, flags=None, num_segments=None,
            policy=ki.resolve_tuning("tpu_v5e")))(
                jax.ShapeDtypeStruct((n,), jnp.float32))

    def sizes(jx):
        for eqn in jx.eqns:
            for var in eqn.outvars:
                yield int(np.prod(getattr(var.aval, "shape", ())))
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (list, tuple))
                            else [param]):
                    inner = getattr(sub, "jaxpr", sub)
                    if isinstance(inner, jax.extend.core.Jaxpr):
                        yield from sizes(inner)

    largest = max(sizes(jaxpr.jaxpr))
    assert n <= largest < 16 * n, largest
