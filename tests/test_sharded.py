"""Device-spanning (@sharded) routes: conformance vs the flat oracles.

Two tiers:

* Single-process tests (1-device mesh / plain numpy): the collective-fold
  registry, the SOFTMAX_MERGE operator-fold equivalence (folded in here
  from test_flash_decode.py -- the collective form now lives behind
  ``mapreduce@sharded``), and the degenerate 1-device mesh.
* Two 8-virtual-device legs (``XLA_FLAGS=--xla_force_host_platform_
  device_count=8`` subprocesses, like the other distributed tests):

  - **primitives**: sharded vs flat-oracle parity for every @sharded
    route -- uneven shard remainders, a degenerate 1-extent axis of a
    multi-axis mesh, non-commutative operators on the order-preserving
    scan, rejection of non-commutative ops on the commutativity-requiring
    mapreduce fold, and topology-keyed tuning-cache entries.  Sort-family
    sweeps use small-range keys (``key_bits=4``: one radix pass) to keep
    the 8-device SPMD compiles cheap, plus one full float32 case for the
    pinned NaN/-0.0 special ordering.
  - **consumers**: merge_partials == the SOFTMAX_MERGE operator fold
    through the real 8-device collective (the equivalence assertion moved
    from test_flash_decode.py); the flash-decoding all-masked-row
    regression; the MoE expert-parallel capacity regression at
    ``E_loc != E``.

  CI runs both from a cold job-local ``REPRO_TUNING_CACHE`` (the
  ``test-distributed`` job).
"""
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Sharded
from repro.kernels import ref
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# Single-process tier.
# ---------------------------------------------------------------------------


def test_merge_is_softmax_merge_fold(rng):
    """The pmax/psum collective merge == folding SOFTMAX_MERGE over shards.

    (Folded in from test_flash_decode.py: this equivalence is what lets
    merge_partials dispatch through mapreduce(SOFTMAX_MERGE,
    layout=Sharded(...)) -- the registered collective fold must be the same
    reduction as the operator fold.)
    """
    ks = jax.random.split(rng, 3)
    S = 8  # shards
    m = jax.random.normal(ks[0], (S, 4), jnp.float32)
    l = jax.random.uniform(ks[1], (S, 4), jnp.float32, 0.1, 2.0)
    o = jax.random.normal(ks[2], (S, 4, 16), jnp.float32)
    # operator fold
    parts = [(m[i], l[i], o[i]) for i in range(S)]
    fm, fl, fo = functools.reduce(alg.SOFTMAX_MERGE, parts)
    want = fo / fl[..., None]
    # collective-form merge (pmax/psum along shard axis)
    mg = jnp.max(m, 0)
    w = jnp.exp(m - mg)
    lg = jnp.sum(l * w, 0)
    og = jnp.sum(o * w[..., None], 0)
    got = og / lg[..., None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_collective_fold_registry():
    """Known monoids rewrite to native collectives; the rest gather-fold."""
    for name in ("add", "max", "min", "logsumexp", "softmax_merge"):
        assert alg.has_collective_rewrite(alg.STD_OPS[name]), name
    for name in ("mul", "affine", "quaternion_mul", "mat2_mul"):
        assert not alg.has_collective_rewrite(alg.STD_OPS[name]), name


def test_foldspec_descriptors():
    """FoldSpec is a *descriptor*, not an eager collective: the registry
    returns the collective tuple the staged plans issue (and the analytic
    byte models price), and ``native`` marks the rewrite forms."""
    pins = {
        "add": ("psum",),
        "max": ("pmax",),
        "min": ("pmin",),
        "logsumexp": ("pmax", "psum"),
        "softmax_merge": ("pmax", "psum", "psum"),
    }
    for name, collectives in pins.items():
        spec = alg.collective_fold_spec(alg.STD_OPS[name])
        assert spec.collectives == collectives, name
        assert spec.native, name
        assert callable(spec.build("shard"))
    mul = alg.collective_fold_spec(alg.STD_OPS["mul"])
    assert mul.collectives == ("all_gather",) and not mul.native


def test_one_device_mesh_new_routes():
    """matvec/vecmat/linear_recurrence@sharded on a 1-extent axis == the
    flat oracles (no strip split, identity fold)."""
    mesh = _mesh1()
    lo = Sharded("shard", mesh=mesh)
    nprng = np.random.default_rng(7)
    A = jnp.asarray(nprng.normal(size=(23, 11)), jnp.float32)
    xv = jnp.asarray(nprng.normal(size=(23,)), jnp.float32)
    got = forge.matvec(lambda x, a: x * a, alg.ADD, A, xv, layout=lo,
                       backend="xla")
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.ref_matvec(lambda x, a: x * a, alg.ADD, A, xv)),
        rtol=1e-5, atol=1e-5)
    xp = jnp.asarray(nprng.normal(size=(11,)), jnp.float32)
    got = forge.vecmat(lambda a, v: a * v, alg.ADD, A, xp, layout=lo,
                       backend="xla")
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.ref_vecmat(lambda a, v: a * v, alg.ADD, A, xp)),
        rtol=1e-5, atol=1e-5)
    a = jnp.asarray(nprng.uniform(0.5, 1.0, (2, 13, 5)), jnp.float32)
    b = jnp.asarray(nprng.normal(size=(2, 13, 5)), jnp.float32)
    h0 = jnp.asarray(nprng.normal(size=(2, 5)), jnp.float32)
    got = forge.linear_recurrence(a, b, h0, layout=lo, backend="xla")
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.ref_batched_linear_recurrence(a, b, h0)),
        rtol=1e-4, atol=1e-4)


def test_overlap_bit_identity_single_process():
    """overlap toggles only the collective issue order -- chunked plans must
    be *bitwise* identical either way (it is a scheduling knob, never a
    numerics knob)."""
    mesh = _mesh1()
    nprng = np.random.default_rng(11)
    cases = []
    A = jnp.asarray(nprng.normal(size=(64, 37)), jnp.float32)
    xv = jnp.asarray(nprng.normal(size=(64,)), jnp.float32)
    cases.append(lambda lo: forge.matvec(lambda x, a: x * a, alg.ADD, A, xv,
                                         layout=lo, backend="xla"))
    x2 = jnp.asarray(nprng.normal(size=(23, 9)), jnp.float32)
    cases.append(lambda lo: forge.mapreduce(lambda v: v, alg.ADD, x2,
                                            layout=lo, backend="xla"))
    for run in cases:
        ov = run(Sharded("shard", mesh=mesh, overlap=True))
        bl = run(Sharded("shard", mesh=mesh, overlap=False))
        for g, w in zip(jax.tree.leaves(ov), jax.tree.leaves(bl)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_overlap_smoke_chunked_collectives(monkeypatch):
    """The plan driver must emit one collective dispatch per chunk -- the
    overlap schedule exists iff the chunked plans funnel >1 dispatch
    through ``dispatch_collective`` (the CI overlap smoke)."""
    from repro.distributed import primitives as dist

    calls = []
    real = dist.dispatch_collective

    def spy(plan, part):
        calls.append(plan.name)
        return real(plan, part)

    monkeypatch.setattr(dist, "dispatch_collective", spy)
    mesh = _mesh1()
    nprng = np.random.default_rng(13)
    A = jnp.asarray(nprng.normal(size=(64, 40)), jnp.float32)
    xv = jnp.asarray(nprng.normal(size=(64,)), jnp.float32)
    forge.matvec(lambda x, a: x * a, alg.ADD, A, xv,
                 layout=Sharded("shard", mesh=mesh), backend="xla")
    assert calls.count("matvec@sharded") > 1, calls
    calls.clear()
    x2 = jnp.asarray(nprng.normal(size=(23, 16)), jnp.float32)
    forge.mapreduce(lambda v: v, alg.ADD, x2,
                    layout=Sharded("shard", mesh=mesh), backend="xla")
    assert calls.count("mapreduce@sharded") > 1, calls
    # Unchunkable plans still funnel their single collective through the
    # same seam (the spy sees exactly one dispatch).
    calls.clear()
    xs = jnp.asarray(nprng.normal(size=(31,)), jnp.float32)
    forge.scan(alg.ADD, xs, layout=Sharded("shard", mesh=mesh), backend="xla")
    assert calls.count("scan@sharded") == 1, calls


def _mesh1():
    return make_mesh((1,), ("shard",))


def test_one_device_mesh_degenerate():
    """Sharded routes on a 1-extent mesh axis == the flat oracles exactly
    (the collective fold degenerates to the identity composition)."""
    mesh = _mesh1()
    lo = Sharded("shard", mesh=mesh)
    nprng = np.random.default_rng(3)
    x = jnp.asarray(nprng.normal(size=(37,)), jnp.float32)

    got = forge.scan(alg.ADD, x, layout=lo, backend="xla")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.ref_scan(alg.ADD, x)),
                               rtol=1e-5, atol=1e-5)
    got = forge.mapreduce(lambda v: v, alg.ADD, x, layout=lo, backend="xla")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.sum(x)), rtol=1e-5, atol=1e-4)
    k = jnp.asarray(nprng.integers(0, 9, size=(37,)), jnp.uint32)
    gv, gi = forge.top_k(k, 5, key_bits=4, layout=lo, backend="xla")
    wv, wi = forge.top_k(k, 5, key_bits=4, backend="xla")
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    gk, gvals = forge.sort_pairs(k, x, key_bits=4, layout=lo, backend="xla")
    wk, wvals = forge.sort_pairs(k, x, key_bits=4, backend="xla")
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(gvals), np.asarray(wvals))


def test_in_mesh_form_inside_shard_map():
    """Sharded(axis) with mesh=None composes inside an existing shard_map."""
    from jax.sharding import PartitionSpec as P

    mesh = _mesh1()
    x = jnp.arange(12, dtype=jnp.float32)

    def local(xl):
        s = forge.scan(alg.ADD, xl, layout=Sharded("shard"), backend="xla")
        t = forge.mapreduce(lambda v: v, alg.ADD, xl,
                            layout=Sharded("shard"), backend="xla")
        return s, t

    s, t = jax.shard_map(local, mesh=mesh, in_specs=(P("shard"),),
                         out_specs=(P("shard"), P()), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(s), np.cumsum(np.arange(12.0)),
                               rtol=1e-6)
    np.testing.assert_allclose(float(t), 66.0, rtol=1e-6)


def test_sharded_scan_exclusive_and_uneven_padding():
    """Uneven remainders pad with the operator identity; exclusive scans
    carry the cross-shard prefix into slot 0 of every shard."""
    mesh = _mesh1()
    x = jnp.asarray(np.random.default_rng(5).normal(size=(11,)), jnp.float32)
    got = forge.scan(alg.ADD, x, inclusive=False,
                     layout=Sharded("shard", mesh=mesh), backend="xla")
    want = ref.ref_scan(alg.ADD, x, inclusive=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The 8-virtual-device legs (subprocess, like the other distributed tests).
# ---------------------------------------------------------------------------

_SCRIPT_PRELUDE = r"""
import os
# Append, don't clobber: CI's test-distributed jax-latest leg hands down
# async-collective / latency-hiding-scheduler flags that must reach the
# 8-device subprocesses.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import sys
sys.path.insert(0, sys.argv[1])
import functools
import jax, jax.numpy as jnp, numpy as np
from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Sharded
from repro.kernels import ref
from repro.launch.mesh import make_mesh

def close(a, b, tol=1e-5, err=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=tol, atol=tol, err_msg=err)

def exact(a, b, err=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=err)

nprng = np.random.default_rng(17)
mesh8 = make_mesh((8,), ("shard",))
mesh1 = make_mesh((1, 8), ("one", "shard"))  # degenerate axis alongside
lo8 = Sharded("shard", mesh=mesh8)
lo1 = Sharded("one", mesh=mesh1)
"""

PRIMITIVES_SCRIPT = _SCRIPT_PRELUDE + r"""
# -- scan@sharded: even / uneven / length-1 / exclusive / non-commutative --
for n, inc in ((64, True), (61, True), (61, False), (1, True)):
    x = jnp.asarray(nprng.normal(size=(n,)), jnp.float32)
    got = forge.scan(alg.ADD, x, inclusive=inc, layout=lo8, backend="xla")
    close(got, ref.ref_scan(alg.ADD, x, inclusive=inc), 1e-4,
          f"scan n={n} inc={inc}")
q = tuple(jnp.asarray(nprng.uniform(0.7, 1.3, (27,)), jnp.float32)
          for _ in range(4))
got = forge.scan(alg.MAT2_MUL, q, layout=lo8, backend="xla")
close(got, ref.ref_scan(alg.MAT2_MUL, q), 1e-3, "scan mat2_mul")
# degenerate 1-extent axis of a 2-axis mesh
x = jnp.asarray(nprng.normal(size=(61,)), jnp.float32)
got = forge.scan(alg.ADD, x, layout=lo1, backend="xla")
close(got, ref.ref_scan(alg.ADD, x), 1e-4, "scan degenerate axis")
print("scan@sharded OK", flush=True)

# -- mapreduce@sharded: rewrites (add/max/logsumexp), gather fallback (mul),
#    elementwise trailing dims, zero extent, non-commutative rejection ------
for op_name in ("add", "max", "logsumexp", "mul"):
    op = alg.STD_OPS[op_name]
    x = jnp.asarray(nprng.uniform(0.5, 1.5, (53,)), jnp.float32)
    got = forge.mapreduce(lambda v: v, op, x, layout=lo8, backend="xla")
    close(got, ref.ref_mapreduce(lambda v: v, op, x), 1e-4,
          f"mapreduce {op_name}")
x = jnp.asarray(nprng.uniform(0.5, 1.5, (53,)), jnp.float32)
got = forge.mapreduce(lambda v: v, alg.ADD, x, layout=lo1, backend="xla")
close(got, ref.ref_mapreduce(lambda v: v, alg.ADD, x), 1e-4,
      "mapreduce degenerate axis")
# trailing-dims elementwise reduction (rank-2 leaves)
x2 = jnp.asarray(nprng.normal(size=(23, 5)), jnp.float32)
got = forge.mapreduce(lambda v: v, alg.ADD, x2, layout=lo8, backend="xla")
close(got, jnp.sum(x2, axis=0), 1e-4, "mapreduce rank2")
# zero-extent stream reduces to identity
z = forge.mapreduce(lambda v: v, alg.ADD, jnp.zeros((0,), jnp.float32),
                    layout=lo8, backend="xla")
assert float(z) == 0.0
try:
    forge.mapreduce(lambda v: v, alg.MAT2_MUL,
                    tuple(jnp.ones((16,), jnp.float32) for _ in range(4)),
                    layout=lo8, backend="xla")
    raise SystemExit("mapreduce@sharded accepted a non-commutative op")
except ValueError as e:
    assert "mapreduce@sharded" in str(e) and "commutative" in str(e), e
print("mapreduce@sharded OK", flush=True)

# -- top_k@sharded: dup-heavy small-range keys (one radix pass), both
#    directions, k > n_loc (forces the partial merge), k == n, uneven; one
#    float32 case pins the NaN/-inf/tie specials ---------------------------
ku = jnp.asarray(nprng.integers(0, 13, size=(61,)), jnp.uint32)
for k, largest in ((1, True), (13, True), (13, False), (61, True)):
    got = forge.top_k(ku, k, largest=largest, key_bits=4, layout=lo8,
                      backend="xla")
    want = forge.top_k(ku, k, largest=largest, key_bits=4, backend="xla")
    exact(got, want, f"top_k u32 k={k} largest={largest}")
got = forge.top_k(ku, 5, key_bits=4, layout=lo1, backend="xla")
exact(got, forge.top_k(ku, 5, key_bits=4, backend="xla"),
      "top_k degenerate axis")
xf = jnp.asarray(nprng.normal(size=(61,)), jnp.float32)
xf = xf.at[3].set(jnp.nan).at[9].set(-jnp.inf).at[11].set(xf[30])
got = forge.top_k(xf, 13, layout=lo8, backend="xla")
exact(got, forge.top_k(xf, 13, backend="xla"), "top_k f32 specials")
print("top_k@sharded OK", flush=True)

# -- sort_pairs@sharded: uneven, descending, key_bits, pytree payload; one
#    float32 case pins the NaN/-0.0 canonicalization -----------------------
def payload(n):
    return (jnp.arange(n, dtype=jnp.int32),
            jnp.asarray(nprng.normal(size=(n, 3)), jnp.float32))
for n, desc in ((64, False), (61, False), (61, True), (9, True)):
    kk = jnp.asarray(nprng.integers(0, 13, size=(n,)), jnp.uint32)
    vv = payload(n)
    got = forge.sort_pairs(kk, vv, descending=desc, key_bits=4,
                           layout=lo8, backend="xla")
    want = forge.sort_pairs(kk, vv, descending=desc, key_bits=4,
                            backend="xla")
    exact(got, want, f"sort_pairs u32 n={n} desc={desc}")
kk = jnp.asarray(nprng.integers(0, 13, size=(43,)), jnp.uint32)
got = forge.sort_pairs(kk, jnp.arange(43, dtype=jnp.int32), key_bits=4,
                       layout=lo1, backend="xla")
exact(got, forge.sort_pairs(kk, jnp.arange(43, dtype=jnp.int32), key_bits=4,
                            backend="xla"), "sort_pairs degenerate axis")
kf = jnp.asarray(nprng.normal(size=(21,)), jnp.float32)
kf = kf.at[1].set(jnp.nan).at[2].set(-0.0).at[5].set(kf[7])
got = forge.sort_pairs(kf, jnp.arange(21, dtype=jnp.int32), layout=lo8,
                       backend="xla")
exact(got, forge.sort_pairs(kf, jnp.arange(21, dtype=jnp.int32),
                            backend="xla"), "sort_pairs f32 specials")
print("sort_pairs@sharded OK", flush=True)

# -- tuning-cache keys carry mesh topology + device count ------------------
import tempfile
from repro.core import tuning
cache = os.environ.get("REPRO_TUNING_CACHE") or os.path.join(
    tempfile.mkdtemp(), "tuning.json")
tuner = tuning.enable(cache, bench_repeats=1)
xs = jnp.asarray(nprng.normal(size=(64,)), jnp.float32)
forge.scan(alg.ADD, xs, layout=lo8, backend="pallas-interpret")
keys = [k for k in tuner._cache if k.startswith("scan@sharded|")]
assert keys, f"no scan@sharded tuning entry: {list(tuner._cache)}"
assert "|mesh=shard=8:8|" in keys[0], keys[0]
assert "/d8" in keys[0], keys[0]
# A different topology is a different key (no second benchmark race needed
# to prove the schema: the keyer is deterministic in the mesh).
k1 = tuner.make_key("scan@sharded", "xla", "add", "float32", 64, None,
                    tuning._mesh_topology({"axis_name": "one",
                                           "mesh": mesh1}))
assert "|mesh=one=1:1x8|" in k1 and k1 not in tuner._cache, k1
tuning.disable()
print("topology-keyed tuning OK", flush=True)

print("SHARDED_PRIMITIVES_OK")
"""

CONSUMERS_SCRIPT = _SCRIPT_PRELUDE + r"""
# -- merge_partials dispatches through mapreduce@sharded and still equals
#    the SOFTMAX_MERGE operator fold (the test_flash_decode.py equivalence
#    assertion, now exercised through the real 8-device collective) --------
from jax.sharding import PartitionSpec as P
from repro.distributed import collectives as coll

ks = jax.random.split(jax.random.PRNGKey(42), 3)
S = 8
m = jax.random.normal(ks[0], (S, 4), jnp.float32)
l = jax.random.uniform(ks[1], (S, 4), jnp.float32, 0.1, 2.0)
o = jax.random.normal(ks[2], (S, 4, 16), jnp.float32)
parts = [(m[i], l[i], o[i]) for i in range(S)]
fm, fl, fo = functools.reduce(alg.SOFTMAX_MERGE, parts)
want = fo / fl[..., None]
merged = jax.shard_map(
    lambda mm, ll, oo: coll.merge_partials(mm[0], ll[0], oo[0], "shard"),
    mesh=mesh8, in_specs=(P("shard"), P("shard"), P("shard")),
    out_specs=P(), check_vma=False)(m, l, o)
np.testing.assert_allclose(np.asarray(merged), np.asarray(want),
                           rtol=1e-5, atol=1e-5)
print("merge_partials == SOFTMAX_MERGE fold OK", flush=True)

# -- all-masked-row regression: an all-padding request through
#    flash_decode_gqa must yield exact zeros, even with poisoned (NaN)
#    cache slots -- not 0/1e-30 garbage ------------------------------------
mesh = make_mesh((2, 4), ("data", "model"))
B, L, K, G, hd = 2, 32, 2, 2, 8
q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, K, G, hd), jnp.float32)
k_cache = jnp.full((B, L, K, hd), jnp.nan, jnp.float32)   # uninitialized
v_cache = jnp.full((B, L, K, hd), jnp.nan, jnp.float32)
k_new = jax.random.normal(jax.random.PRNGKey(1), (B, 1, K, hd), jnp.float32)
v_new = jax.random.normal(jax.random.PRNGKey(2), (B, 1, K, hd), jnp.float32)
key_valid = jnp.zeros((L,), bool)                          # all padding
out, _, _ = coll.flash_decode_gqa(
    mesh, q, k_cache, v_cache, k_new, v_new,
    jnp.asarray(0, jnp.int32), key_valid)
assert not np.any(np.isnan(np.asarray(out))), "all-masked rows emitted NaN"
np.testing.assert_array_equal(np.asarray(out), np.zeros_like(np.asarray(out)))
# ...and rows with valid keys stay unaffected by the guard.
key_valid = jnp.zeros((L,), bool).at[0].set(True)
k_cache0 = jnp.zeros((B, L, K, hd), jnp.float32)
v_cache0 = jnp.zeros((B, L, K, hd), jnp.float32)
out2, _, _ = coll.flash_decode_gqa(
    mesh, q, k_cache0, v_cache0, k_new, v_new,
    jnp.asarray(0, jnp.int32), key_valid)
assert np.all(np.isfinite(np.asarray(out2)))
assert np.any(np.asarray(out2) != 0.0)
print("flash_decode all-masked regression OK", flush=True)

# -- MoE expert-parallel capacity at E_loc != E: capacity_factor=1.0 with
#    exactly-even routing must drop nothing (per-expert capacity divides by
#    global E; the buffer allocates C per *local* expert) ------------------
import dataclasses
from repro.configs import base as C
from repro.models import moe as M
from repro.distributed.moe_sharded import moe_forward_sharded

cfg = C.get_config("moonshot-v1-16b-a3b", smoke=True)
cfg = dataclasses.replace(cfg, dtype="float32", capacity_factor=1.0,
                          n_experts=8, moe_top_k=1)
E = cfg.n_experts
params = M.init_moe(jax.random.PRNGKey(0), cfg)
D = cfg.d_model
# Deterministic even routing: token t is a one-hot of (t mod E) and the
# router is a scaled identity block, so expert e receives exactly T/E
# tokens on every data shard -- per-expert load == ceil(T_loc*k/E) exactly,
# i.e. capacity has zero slack and any under-allocation drops tokens.
params["router"] = jnp.zeros((D, E), jnp.float32).at[
    jnp.arange(E), jnp.arange(E)].set(10.0)
if "router_bias" in params:
    params["router_bias"] = jnp.zeros_like(params["router_bias"])
Bm, Sm = 4, 32
tok = jnp.arange(Bm * Sm) % E
x = jax.nn.one_hot(tok, D, dtype=jnp.float32).reshape(Bm, Sm, D)
ref_out, _ = M.moe_forward(params, cfg, x)
with mesh:   # (2, 4): E_loc = 2 != E = 8
    got, _ = jax.jit(lambda p, xx: moe_forward_sharded(p, cfg, xx, mesh))(
        params, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref_out),
                           rtol=2e-3, atol=2e-3,
                           err_msg="tokens dropped at E_loc != E")
print("moe capacity E_loc != E OK", flush=True)

print("SHARDED_CONSUMERS_OK")
"""


NEWROUTES_SCRIPT = _SCRIPT_PRELUDE + r"""
# -- matvec@sharded / vecmat@sharded: contraction-axis tensor parallelism
#    vs the dense single-device oracles -- even split, uneven remainder
#    (replicated rows folded last), fewer contraction elements than devices
#    (the direct flat path) ------------------------------------------------
def f_mv(x, a):
    return x * a
def f_vm(a, v):
    return a * v
for n, p in ((64, 16), (67, 16), (16, 67), (5, 12)):
    A = jnp.asarray(nprng.normal(size=(n, p)), jnp.float32)
    x = jnp.asarray(nprng.normal(size=(n,)), jnp.float32)
    got = forge.matvec(f_mv, alg.ADD, A, x, layout=lo8, backend="xla")
    close(got, ref.ref_matvec(f_mv, alg.ADD, A, x), 1e-4, f"matvec {n}x{p}")
    xp = jnp.asarray(nprng.normal(size=(p,)), jnp.float32)
    got = forge.vecmat(f_vm, alg.ADD, A, xp, layout=lo8, backend="xla")
    close(got, ref.ref_vecmat(f_vm, alg.ADD, A, xp), 1e-4, f"vecmat {n}x{p}")
# non-ADD fold (MIN -> pmin) through the semiring bundle
W = jnp.asarray(nprng.uniform(0.0, 1.0, (61, 9)), jnp.float32)
d = jnp.asarray(nprng.uniform(0.0, 1.0, (61,)), jnp.float32)
got = forge.semiring_matvec(alg.TROPICAL_MIN_PLUS, W, d, layout=lo8,
                            backend="xla")
close(got, ref.ref_matvec(alg.TROPICAL_MIN_PLUS.f, alg.MIN, W, d), 1e-4,
      "tropical matvec")
# degenerate 1-extent axis of a 2-axis mesh
got = forge.matvec(f_mv, alg.ADD, W, d, layout=lo1, backend="xla")
close(got, ref.ref_matvec(f_mv, alg.ADD, W, d), 1e-4, "matvec degenerate")
# overlap=False is bit-identical (issue order, not numerics)
lo8_block = Sharded("shard", mesh=mesh8, overlap=False)
A = jnp.asarray(nprng.normal(size=(67, 33)), jnp.float32)
x = jnp.asarray(nprng.normal(size=(67,)), jnp.float32)
exact(forge.matvec(f_mv, alg.ADD, A, x, layout=lo8, backend="xla"),
      forge.matvec(f_mv, alg.ADD, A, x, layout=lo8_block, backend="xla"),
      "matvec overlap bit-identity")
print("matvec/vecmat@sharded OK", flush=True)

# -- linear_recurrence@sharded: cross-device affine carry vs the numpy
#    float64 time-loop oracle -- uneven T (affine-identity padding),
#    T < devices, T == 1, with and without h0 ------------------------------
for T in (64, 61, 5, 1):
    a = jnp.asarray(nprng.uniform(0.5, 1.0, (2, T, 6)), jnp.float32)
    b = jnp.asarray(nprng.normal(size=(2, T, 6)), jnp.float32)
    h0 = jnp.asarray(nprng.normal(size=(2, 6)), jnp.float32)
    got = forge.linear_recurrence(a, b, layout=lo8, backend="xla")
    close(got, ref.ref_batched_linear_recurrence(a, b), 1e-4,
          f"linrec T={T}")
    got = forge.linear_recurrence(a, b, h0, layout=lo8, backend="xla")
    close(got, ref.ref_batched_linear_recurrence(a, b, h0), 1e-4,
          f"linrec h0 T={T}")
# degenerate 1-extent axis == the flat route bitwise
a = jnp.asarray(nprng.uniform(0.5, 1.0, (2, 19, 4)), jnp.float32)
b = jnp.asarray(nprng.normal(size=(2, 19, 4)), jnp.float32)
exact(forge.linear_recurrence(a, b, layout=lo1, backend="xla"),
      forge.linear_recurrence(a, b, backend="xla"), "linrec degenerate")
# overlap=False bit-identity (channel-axis chunks, h0 chunked alongside)
h0 = jnp.asarray(nprng.normal(size=(2, 4)), jnp.float32)
exact(forge.linear_recurrence(a, b, h0, layout=lo8, backend="xla"),
      forge.linear_recurrence(a, b, h0, layout=lo8_block, backend="xla"),
      "linrec overlap bit-identity")
print("linear_recurrence@sharded OK", flush=True)

# -- consumers: the sharded decode GEMV equals the dense unembed; the
#    sequence-sharded RG-LRU prefill equals the single-device path ---------
from repro.models import lm
from repro.models import layers as L
from repro.models import recurrent as R
params = {"embedding": jnp.asarray(nprng.normal(size=(50, 19)), jnp.float32)}
h = jnp.asarray(nprng.normal(size=(3, 1, 19)), jnp.float32)
close(lm.unembed_sharded(params, h, 5.0, mesh8, "shard"),
      L.unembed(params, h, 5.0), 1e-4, "sharded unembed")

class Cfg:
    d_model = 16; rnn_width = 16; conv_width = 4; n_heads = 4
p = R.init_rglru_block(jax.random.PRNGKey(0), Cfg)
x = jnp.asarray(nprng.normal(size=(2, 21, 16)), jnp.float32)
y0, c0 = R.rglru_forward(p, Cfg, x, return_cache=True)
y1, c1 = R.rglru_forward(p, Cfg, x, return_cache=True,
                         seq_shard=(mesh8, "shard"))
close(y0, y1, 1e-4, "rglru seq_shard")
close(c0["h"], c1["h"], 1e-4, "rglru seq_shard cache")
print("sharded consumers OK", flush=True)

print("SHARDED_NEWROUTES_OK")
"""


def _run_leg(tmp_path, name, script, token):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    path = tmp_path / f"{name}.py"
    path.write_text(script)
    env = dict(os.environ)
    env.pop("REPRO_AUTOTUNE", None)   # the script enables tuning explicitly
    env.setdefault("REPRO_TUNING_CACHE", str(tmp_path / "tuning.json"))
    out = subprocess.run([sys.executable, str(path), src],
                         capture_output=True, text=True, timeout=560,
                         env=env)
    assert token in out.stdout, out.stdout + out.stderr[-3000:]


@pytest.mark.slow
def test_sharded_primitives_8_devices(tmp_path):
    _run_leg(tmp_path, "sharded_primitives", PRIMITIVES_SCRIPT,
             "SHARDED_PRIMITIVES_OK")


@pytest.mark.slow
def test_sharded_consumers_8_devices(tmp_path):
    _run_leg(tmp_path, "sharded_consumers", CONSUMERS_SCRIPT,
             "SHARDED_CONSUMERS_OK")


@pytest.mark.slow
def test_sharded_new_routes_8_devices(tmp_path):
    _run_leg(tmp_path, "sharded_new_routes", NEWROUTES_SCRIPT,
             "SHARDED_NEWROUTES_OK")
