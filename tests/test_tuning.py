"""Autotuned dispatch: benchmark-once semantics and on-disk cache round-trip."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core import tuning
from repro.core.layout import Batched, Segmented
from repro.launch.mesh import make_mesh


@pytest.fixture
def tuner(tmp_path):
    t = tuning.enable(str(tmp_path / "tuning.json"))
    yield t
    tuning.disable()


def test_first_call_benchmarks_second_call_hits(tuner):
    x = jnp.arange(4096, dtype=jnp.float32)
    y = forge.scan(alg.ADD, x, backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(y), np.cumsum(np.arange(4096)),
                               rtol=1e-5)
    assert tuner.stats["benchmarks"] == 1
    assert tuner.stats["bench_calls"] == len(
        tuning.TUNABLE["scan@flat"].candidates)

    # Identical key (same primitive/op/dtype/shape-bucket): no re-benchmark.
    y2 = forge.scan(alg.ADD, x * 2, backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(y2),
                               np.cumsum(2.0 * np.arange(4096)), rtol=1e-5)
    assert tuner.stats["benchmarks"] == 1
    assert tuner.stats["hits"] >= 1


def test_cache_round_trips_across_tuner_instances(tuner, tmp_path):
    x = jnp.arange(4096, dtype=jnp.float32)
    forge.scan(alg.ADD, x, backend="pallas-interpret")
    path = tuner.cache_path
    entry = json.load(open(path))
    assert len(entry) == 1
    (key, val), = entry.items()
    assert key.startswith("scan@flat|op=add|dtype=float32|n=4096|")
    assert "overrides" in val

    # A fresh tuner reading the same file performs no re-benchmarking.
    fresh = tuning.enable(path)
    forge.scan(alg.ADD, x + 3, backend="pallas-interpret")
    assert fresh.stats["benchmarks"] == 0
    assert fresh.stats["hits"] == 1


def test_distinct_keys_tune_separately(tuner):
    x = jnp.arange(4096, dtype=jnp.float32)
    forge.scan(alg.ADD, x, backend="pallas-interpret")
    forge.scan(alg.MAX, x, backend="pallas-interpret")      # different op
    forge.scan(alg.ADD, x.astype(jnp.bfloat16),             # different dtype
               backend="pallas-interpret")
    assert tuner.stats["benchmarks"] == 3


def test_segmented_scan_is_tuned_and_correct(tuner):
    x = jnp.arange(3000, dtype=jnp.float32)
    offs = jnp.asarray([0, 100, 2500, 3000], jnp.int32)
    got = forge.scan(alg.ADD, x, layout=Segmented(offsets=offs),
                     backend="pallas-interpret")
    assert tuner.stats["benchmarks"] == 1
    want = np.concatenate([np.cumsum(np.asarray(x)[s:e])
                           for s, e in zip([0, 100, 2500], [100, 2500, 3000])])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)


def test_explicit_policy_bypasses_tuner(tuner):
    from repro.core import intrinsics as ki
    x = jnp.arange(1024, dtype=jnp.float32)
    impl = ki.resolve_impl("scan@flat", "pallas-interpret")
    impl(alg.ADD, x, policy=ki.resolve_tuning("interpret"))
    assert tuner.stats["benchmarks"] == 0


def test_xla_backend_not_tuned(tuner):
    x = jnp.arange(1024, dtype=jnp.float32)
    forge.scan(alg.ADD, x, backend="xla")
    assert tuner.stats["benchmarks"] == 0


def test_shape_bucket_shares_entries(tuner):
    a = jnp.arange(3000, dtype=jnp.float32)   # bucket 4096
    b = jnp.arange(4000, dtype=jnp.float32)   # bucket 4096
    forge.scan(alg.ADD, a, backend="pallas-interpret")
    forge.scan(alg.ADD, b, backend="pallas-interpret")
    assert tuner.stats["benchmarks"] == 1


def test_corrupt_cache_re_tunes_instead_of_raising(tmp_path):
    """A truncated/corrupt JSON cache (interrupted concurrent writer) must
    never raise: the tuner starts empty, re-benchmarks, and the next save
    rewrites a valid file."""
    path = tmp_path / "tuning.json"
    path.write_text('{"scan@flat|op=add|dtype=float32|n=4096"')   # truncated
    t = tuning.enable(str(path))
    try:
        x = jnp.arange(4096, dtype=jnp.float32)
        y = forge.scan(alg.ADD, x, backend="pallas-interpret")
        np.testing.assert_allclose(np.asarray(y),
                                   np.cumsum(np.arange(4096)), rtol=1e-5)
        assert t.stats["benchmarks"] == 1        # re-tuned, no crash
        data = json.load(open(path))             # save rewrote valid JSON
        assert len(data) == 1
    finally:
        tuning.disable()


def test_concurrent_writers_merge_not_clobber(tmp_path):
    """Two tuners sharing one cache path (parallel test shards /
    self-hosted runners): the second save must merge with what's on disk,
    not overwrite it with its own stale view."""
    path = str(tmp_path / "tuning.json")
    a = tuning.Autotuner(path)
    b = tuning.Autotuner(path)                   # loads the same empty file
    a._cache["key_a"] = {"overrides": {"nitem_scan": 8}, "seconds": 1.0}
    a._save()
    b._cache["key_b"] = {"overrides": {"nitem_scan": 16}, "seconds": 2.0}
    b._save()                                    # must not drop key_a
    data = json.load(open(path))
    assert set(data) == {"key_a", "key_b"}
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


def test_batched_keys_carry_batch_bucket(tuner):
    """Batched-family cache keys bucket the batch separately from the
    per-row extent, and one race covers the whole batch -- not one per row."""
    x = jnp.ones((4, 4096), jnp.float32)
    forge.scan(alg.ADD, x, layout=Batched(), backend="pallas-interpret")
    assert tuner.stats["benchmarks"] == 1          # one race for all 4 rows
    key = [k for k in tuner._cache if k.startswith("scan@batched|")]
    assert key and "|n=4096|batch=4|" in key[0]
    # Same rows, different batch bucket: tunes separately (small batches
    # and large batches want different block policies).
    forge.scan(alg.ADD, jnp.ones((32, 4096), jnp.float32),
               layout=Batched(), backend="pallas-interpret")
    assert tuner.stats["benchmarks"] == 2
    # Same batch bucket again: pure cache hit.
    forge.scan(alg.ADD, x * 3, layout=Batched(), backend="pallas-interpret")
    assert tuner.stats["benchmarks"] == 2
    assert tuner.stats["hits"] >= 1


def test_sharded_keys_carry_mesh_topology(tuner):
    """@sharded cache keys pin the mesh topology (axis name + extent, mesh
    shape) and every key's platform part carries the device count -- a
    1-device winner is never replayed on an N-device mesh."""
    import jax
    from repro.core.layout import Sharded
    mesh = make_mesh((1,), ("shard",))
    x = jnp.arange(512, dtype=jnp.float32)
    got = forge.scan(alg.ADD, x, layout=Sharded("shard", mesh=mesh),
                     backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(got), np.cumsum(np.arange(512)),
                               rtol=1e-5)
    keys = [k for k in tuner._cache if k.startswith("scan@sharded|")]
    assert keys, list(tuner._cache)
    assert "|mesh=shard=1:1|" in keys[0], keys[0]
    assert "/d1" in keys[0], keys[0]   # device count in the platform part
    # The flat route's key carries the device count too (no mesh part).
    forge.scan(alg.ADD, x, backend="pallas-interpret")
    flat = [k for k in tuner._cache if k.startswith("scan@flat|")]
    assert flat and "/d1" in flat[0] and "|mesh=" not in flat[0]


def test_sort_ladder_races_digit_width(tuner):
    """The sort family is tuned over digit width x block policy and stays
    correct under every candidate."""
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.integers(0, 256, 256), jnp.uint8)
    got = forge.sort(k, backend="pallas-interpret")
    assert tuner.stats["benchmarks"] >= 1
    key = [c for c in tuner._cache if c.startswith("sort@flat|")]
    assert key and "overrides" in tuner._cache[key[0]]
    assert set(tuner._cache[key[0]]["overrides"]) <= {"sort_digit_bits",
                                                      "nitem_scan"}
    np.testing.assert_array_equal(np.asarray(got),
                                  np.sort(np.asarray(k), kind="stable"))


@pytest.mark.parametrize("kind,chip,policy", [
    ("TPU v5 lite", "tpu_v5e", "tpu_v5e"),
    ("TPU v5", "tpu_v5p", "tpu_v5p"),
    ("TPU v6 lite", "tpu_v6_lite", "generic"),
    ("TPU v4", "tpu_v4", "generic"),
])
def test_detect_chip_names_the_tpu_it_sees(monkeypatch, kind, chip, policy):
    """An unknown TPU is named after its device_kind and gets the generic
    policy -- never another chip's."""
    import types

    import jax
    from repro.core import intrinsics as ki
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    assert ki.detect_chip() == chip
    assert ki.resolve_tuning().name == policy


def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes
    to the fixed .jax_cache at the root of the checkout."""
    import pathlib

    import jax
    from repro.launch import compile_cache as CC
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert CC.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = pathlib.Path(__file__).resolve().parents[1]
        assert CC.enable_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
