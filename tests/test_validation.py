"""Uniform validation errors from the PrimitiveDef dispatch layer.

Every malformed call must fail with a ``ValueError`` whose message names the
primitive and the layout (``"scan@segmented: ..."``), raised *before* any
kernel work -- the rules live declaratively on the RouteDef rows
(``core/intrinsics.py``), so one test per rule covers every family that
declares it.
"""
import jax.numpy as jnp
import pytest

from repro.core import intrinsics as ki
from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Batched, Flat, Segmented, Sharded
from repro.launch.mesh import make_mesh

X = jnp.arange(8, dtype=jnp.float32)
FLAGS = jnp.ones((8,), jnp.int32)
OFFS = jnp.asarray([0, 3, 8], jnp.int32)


def _raises(match):
    return pytest.raises(ValueError, match=match)


# ---------------------------------------------------------------------------
# Segment descriptors: exactly one of flags/offsets, flag-variant reductions
# need a static num_segments.
# ---------------------------------------------------------------------------


def test_segmented_neither_descriptor():
    with _raises(r"scan@segmented: pass exactly one of flags= or offsets="):
        forge.scan(alg.ADD, X, layout=Segmented(), backend="xla")


def test_segmented_both_descriptors():
    with _raises(r"scan@segmented: pass exactly one"):
        forge.scan(alg.ADD, X, backend="xla",
                   layout=Segmented(flags=FLAGS, offsets=OFFS))


@pytest.mark.parametrize("call", [
    lambda lo: forge.mapreduce(lambda v: v, alg.ADD, X, layout=lo,
                               backend="xla"),
    lambda lo: forge.sort(X, layout=lo, backend="xla"),
    lambda lo: forge.top_k(X, 2, layout=lo, backend="xla"),
])
def test_descriptor_exclusivity_is_uniform_across_families(call):
    """The same rule fires with the same message shape for every segmented
    route -- it is one validator on the table, not per-family copies."""
    with _raises(r"@segmented: pass exactly one"):
        call(Segmented())
    with _raises(r"@segmented: pass exactly one"):
        call(Segmented(flags=FLAGS, offsets=OFFS))


def test_flag_variant_reduction_needs_num_segments():
    with _raises(r"mapreduce@segmented: .*num_segments"):
        forge.mapreduce(lambda v: v, alg.ADD, X,
                        layout=Segmented(flags=FLAGS), backend="xla")
    with _raises(r"top_k@segmented: .*num_segments"):
        forge.top_k(X, 2, layout=Segmented(flags=FLAGS), backend="xla")
    # The offsets variant carries its own extent: no num_segments needed.
    forge.mapreduce(lambda v: v, alg.ADD, X,
                    layout=Segmented(offsets=OFFS), backend="xla")


# ---------------------------------------------------------------------------
# Rank / shape checks per layout.
# ---------------------------------------------------------------------------


def test_batched_scan_rejects_non_rank2_leaves():
    with _raises(r"scan@batched: .*rank-2 leaves.*got shape \(8,\)"):
        forge.scan(alg.ADD, X, layout=Batched(), backend="xla")


def test_batched_mapreduce_rejects_non_rank2_leaves():
    with _raises(r"mapreduce@batched: .*rank-2"):
        forge.mapreduce(lambda v: v, alg.ADD, jnp.zeros((2, 3, 4)),
                        layout=Batched(), backend="xla")


def test_batched_matvec_rejects_flat_operands():
    A2, x1 = jnp.zeros((4, 5)), jnp.zeros((4,))
    with _raises(r"matvec@batched: .*rank-3"):
        forge.matvec(lambda x, a: x * a, alg.ADD, A2, x1,
                     layout=Batched(), backend="xla")
    with _raises(r"vecmat@batched: .*rank-3"):
        forge.vecmat(lambda a, x: a * x, alg.ADD, A2, x1,
                     layout=Batched(), backend="xla")


def test_flat_matvec_rejects_batched_operands():
    A3, x2 = jnp.zeros((2, 4, 5)), jnp.zeros((2, 4))
    with _raises(r"matvec@flat: .*rank-2"):
        forge.matvec(lambda x, a: x * a, alg.ADD, A3, x2, backend="xla")


def test_segmented_scan_rejects_rank2_leaves():
    with _raises(r"scan@segmented: .*rank-1"):
        forge.scan(alg.ADD, jnp.zeros((2, 4)),
                   layout=Segmented(offsets=OFFS), backend="xla")


def test_linear_recurrence_rank_check():
    with _raises(r"linear_recurrence@batched: .*rank-3"):
        forge.linear_recurrence(jnp.zeros((4, 4)), jnp.zeros((4, 4)),
                                layout=Batched(), backend="xla")


# ---------------------------------------------------------------------------
# Commutativity requirements.
# ---------------------------------------------------------------------------


def test_flat_mapreduce_rejects_non_commutative_op():
    q = tuple(jnp.ones((8,)) for _ in range(4))
    with _raises(r"mapreduce@flat: requires a commutative operator, got "
                 r"'quaternion_mul'"):
        forge.mapreduce(lambda v: v, alg.QUATERNION_MUL, q, backend="xla")


def test_segmented_mapreduce_accepts_non_commutative_op():
    """The segmented route is order-preserving by construction (segmented
    scan + gather-lasts), so -- unlike the flat route -- non-commutative
    operators are valid, per its table row."""
    a = jnp.linspace(0.5, 1.0, 8)
    out = forge.mapreduce(lambda v: v, alg.AFFINE, (a, a),
                          layout=Segmented(offsets=OFFS), backend="xla")
    assert all(l.shape == (2,) for l in out)


def test_batched_mapreduce_accepts_non_commutative_op():
    """The batched route reroutes through the order-preserving scan instead
    of raising -- the relaxation is declared on its table row."""
    q = tuple(jnp.ones((2, 8)) * c for c in (1.0, 0.1, 0.0, 0.0))
    out = forge.mapreduce(lambda v: v, alg.QUATERNION_MUL, q,
                          layout=Batched(), backend="xla")
    assert all(l.shape == (2,) for l in out)


# ---------------------------------------------------------------------------
# Unsupported (primitive, layout) pairs and layout-pinned kwargs.
# ---------------------------------------------------------------------------


def test_unsupported_layout_names_primitive_and_options():
    with _raises(r"sort: unsupported layout 'batched' .*flat.*segmented"):
        forge.sort(X, layout=Batched(), backend="xla")
    with _raises(r"copy: unsupported layout 'segmented'"):
        forge.copy(X, layout=Segmented(offsets=OFFS), backend="xla")


def test_layout_pinned_kwargs_rejected():
    with _raises(r"scan@batched: axis= is pinned"):
        forge.scan(alg.ADD, jnp.zeros((2, 4)), axis=1, layout=Batched(),
                   backend="xla")
    with _raises(r"scan@segmented: reverse= is pinned"):
        forge.scan(alg.ADD, X, reverse=True,
                   layout=Segmented(offsets=OFFS), backend="xla")


def test_layout_must_be_a_descriptor():
    with pytest.raises(TypeError, match="layout= must be a Layout"):
        forge.scan(alg.ADD, X, layout="batched", backend="xla")


# ---------------------------------------------------------------------------
# Sharded layout: mesh-aware validation.
# ---------------------------------------------------------------------------


def test_sharded_axis_must_name_a_mesh_axis():
    import jax
    mesh = make_mesh((1,), ("model",))
    with _raises(r"scan@sharded: axis 'nope' is not an axis of the mesh "
                 r"\(axes: \('model',\)\)"):
        forge.scan(alg.ADD, X, layout=Sharded("nope", mesh=mesh),
                   backend="xla")
    with _raises(r"mapreduce@sharded: axis 'nope'"):
        forge.mapreduce(lambda v: v, alg.ADD, X,
                        layout=Sharded("nope", mesh=mesh), backend="xla")


def test_sharded_axis_must_be_a_name():
    with _raises(r"scan@sharded: Sharded\(axis=...\) must name a mesh axis"):
        forge.scan(alg.ADD, X, layout=Sharded(axis=""), backend="xla")


def test_sharded_mapreduce_rejects_non_commutative_op():
    """The cross-device fold of mapreduce@sharded requires commutativity
    (declared on its table row), unlike the order-preserving scan route."""
    q = tuple(jnp.ones((8,)) for _ in range(4))
    with _raises(r"mapreduce@sharded: requires a commutative operator, got "
                 r"'quaternion_mul'"):
        forge.mapreduce(lambda v: v, alg.QUATERNION_MUL, q,
                        layout=Sharded("model"), backend="xla")


def test_sharded_scan_pinned_kwargs():
    with _raises(r"scan@sharded: reverse= is pinned"):
        forge.scan(alg.ADD, X, reverse=True, layout=Sharded("model"),
                   backend="xla")


def test_sharded_unsupported_primitives_name_their_routes():
    with _raises(r"argsort: unsupported layout 'sharded'"):
        forge.argsort(X, layout=Sharded("model"), backend="xla")
    with _raises(r"copy: unsupported layout 'sharded'"):
        forge.copy(X, layout=Sharded("model"), backend="xla")


def test_registry_routes_all_have_impls_and_validation_fields():
    """Registry sanity: every declared route resolves an implementation on
    the portable backend, segmented routes all declare the descriptor
    requirement, and sharded routes all declare the mesh requirement (the
    rules the uniform errors above come from)."""
    for route in ki.iter_routes():
        assert ki.resolve_impl(route.key, "xla") is not None
        if route.layout == "segmented":
            assert route.needs_descriptor
        if route.layout == "sharded":
            assert route.needs_mesh
    assert ki.get_route("scan", Flat().kind).key == "scan@flat"


# ---------------------------------------------------------------------------
# Backend selection: unknown names fail loudly, uniformly naming the route.
# ---------------------------------------------------------------------------


def test_unknown_backend_string_names_the_route():
    with _raises(r"scan@flat: unknown backend 'pallas-rocm' \(available: "):
        forge.scan(alg.ADD, X, backend="pallas-rocm")
    with _raises(r"sort@flat: unknown backend 'cub'"):
        forge.sort(jnp.arange(8, dtype=jnp.uint32), backend="cub")
    with _raises(r"mapreduce@batched: unknown backend 'tirton'"):
        forge.mapreduce(lambda v: v, alg.ADD, jnp.ones((2, 8)),
                        layout=Batched(), backend="tirton")


def test_use_backend_rejects_unknown_names_up_front():
    """A typo fails at the `with` statement, not as a silent xla fallback."""
    with _raises(r"unknown backend 'metal' \(available: "):
        with ki.use_backend("metal"):
            pass  # pragma: no cover - never entered


def test_known_backend_without_route_falls_back_not_raises():
    """Known backends missing a native route fall back to the portable
    implementation -- only unknown *names* are errors."""
    got = forge.scan(
        alg.ADD, X,
        layout=Segmented(flags=jnp.zeros(8, jnp.int32).at[0].set(1)),
        backend="pallas-gpu")
    assert got.shape == X.shape
