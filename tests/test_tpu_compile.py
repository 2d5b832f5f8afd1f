"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode runs the kernel bodies but never meets Mosaic, which refuses
blocks that do not tile, casts it has no lowering for and programs that do
not fit.  Each case compiles one ``pallas-tpu`` route at the shape
``chip_smoke.py`` and the serving engine use it at, for one chip of a
``v5e:2x2`` topology that is described, not attached, and checks that the
Mosaic kernel is in the program (``tpu_custom_call``): the registry's xla
fallback would compile too, and would hide a missing kernel.  The serving
engine's prefill and decode-loop programs are compiled for the full
minitron-4b from ``jax.eval_shape`` weights and must fit the chip's HBM.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU compiler library, and each
pytest-xdist worker imports every test file.  On CPU ``detect_chip`` says
``generic``, so the kernel cases pass the v5e policy explicitly and the
engine cases make ``detect_chip`` answer ``tpu_v5e``.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import intrinsics as ki
from repro.core import operators as alg
from repro.models import lm
from repro.serving.engine import Engine

B, V, K = 8, 256000, 50          # serving batch, minitron-4b vocab, top_k


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip lands in the persistent cache but can
    # never be read back without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _impl(route):
    return ki.resolve_impl(route, "pallas-tpu")


def _top_k(pol):
    offsets = jnp.arange(B + 1, dtype=jnp.int32) * V
    return lambda flat: _impl("top_k@segmented")(
        flat, K, offsets=offsets, flags=None, num_segments=None, policy=pol)


# name -> (policy -> fn, [(shape, dtype)] of the arguments)
CASES = {
    "scan@flat f32 1e7": (
        lambda pol: lambda x: _impl("scan@flat")(alg.ADD, x, policy=pol),
        [((10**7,), jnp.float32)]),
    "mapreduce@flat f32 1e7": (
        lambda pol: lambda x: _impl("mapreduce@flat")(
            lambda v: v, alg.ADD, x, policy=pol),
        [((10**7,), jnp.float32)]),
    "mapreduce@flat unitfloat8 1e7": (
        lambda pol: lambda x: _impl("mapreduce@flat")(
            alg.unitfloat8_decode, alg.ADD, x, policy=pol),
        [((10**7,), jnp.uint8)]),
    "matvec@flat bf16 4096x4096": (
        lambda pol: lambda A, x: _impl("matvec@flat")(
            lambda x_i, a: x_i * a, alg.ADD, A, x, policy=pol),
        [((4096, 4096), jnp.bfloat16), ((4096,), jnp.bfloat16)]),
    "scan@batched f32 8x64": (
        lambda pol: lambda x: _impl("scan@batched")(
            alg.ADD, x, inclusive=False, policy=pol),
        [((8, 64), jnp.float32)]),
    # The decode loop's condition: MAX over the B int32 active flags.
    "mapreduce@flat i32 8 loop condition": (
        lambda pol: lambda a: _impl("mapreduce@flat")(
            lambda v: v, alg.MAX, a, policy=pol),
        [((B,), jnp.int32)]),
    # The sampler's nucleus cutoff over the (B, top_k) candidates.
    "scan@batched f32 8x50 nucleus": (
        lambda pol: lambda p: _impl("scan@batched")(
            alg.ADD, p, inclusive=False, policy=pol),
        [((B, K), jnp.float32)]),
    # The drain's CSR offsets over per-slot token counts.
    "scan@flat i32 8 drain": (
        lambda pol: lambda c: _impl("scan@flat")(alg.ADD, c, policy=pol),
        [((B,), jnp.int32)]),
    # Per-slot sequence scores over the (B, max_new) logprob buffer.
    "mapreduce@batched f32+i32 8x2048 seq logprob": (
        lambda pol: lambda lp, m: _impl("mapreduce@batched")(
            lambda t: jnp.where(t[1] != 0, t[0], 0.0), alg.ADD, (lp, m),
            policy=pol),
        [((B, 2048), jnp.float32), ((B, 2048), jnp.int32)]),
    # The sampler's segmented radix top-k over B x V logits.
    "top_k@segmented f32 8x256000": (_top_k, [((B * V,), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    build, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(build(ki.resolve_tuning("tpu_v5e"))).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if name.startswith("top_k"):
        # The radix passes rank the stream in bounded slabs; a whole-stream
        # one-hot rank here took 6 GiB of the chip's 16.
        assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


# ---------------------------------------------------------------------------
# The serving engine's programs for the full minitron-4b (32 layers, bf16
# weights, 8 slots x 2048 tokens), as chip_smoke.py runs them: each must fit
# the v5e's 16 GB of HBM.
# ---------------------------------------------------------------------------

HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def full_engine(one_chip):
    cfg = get_config("minitron-4b")

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: lm.init_params(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0)))
    engines = {
        "greedy": Engine(cfg, None, params, cache_len=2048, batch_size=8),
        "sampled": Engine(cfg, None, params, cache_len=2048, batch_size=8,
                          temperature=0.8, top_k=K, top_p=0.9)}
    return engines, params, on_chip


def _live_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("rows", [1, 8])
def test_engine_prefill_fits_v5e(full_engine, monkeypatch, rows):
    """Admission prefills one request (1 row); a batched prefill takes the
    whole batch (8 rows).  Both must accumulate the logit projection in
    f32: a bf16 result of that dot rounds the top of 256000 logits into
    ties, which the two row counts then break differently."""
    monkeypatch.setattr(ki, "detect_chip", lambda: "tpu_v5e")
    engines, params, on_chip = full_engine
    eng = engines["greedy"]
    batch = on_chip(jax.eval_shape(
        lambda: eng._make_batch(np.zeros((rows, 1024), np.int32))))
    with ki.use_backend("pallas-tpu"):
        lowered = eng._prefill.lower(params, batch)
    text = lowered.as_text()
    assert f"{rows}x1x{V}xbf16" not in text
    assert f"{rows}x1x{V}xf32" in text
    assert _live_bytes(lowered.compile()) < HBM_BYTES


@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_engine_decode_loop_fits_v5e(full_engine, monkeypatch, mode):
    """The device-resident decode loop holds weights, the donated slot
    state and the step's temporaries; with sampling the radix top-k and the
    loop condition are Mosaic kernels inside it."""
    monkeypatch.setattr(ki, "detect_chip", lambda: "tpu_v5e")
    engines, params, on_chip = full_engine
    eng = engines[mode]
    state = on_chip(jax.eval_shape(eng._fresh_state))
    budget = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    with ki.use_backend("pallas-tpu"):
        compiled = eng._loop_fn[True].lower(
            params, on_chip(eng._strategy_params), state, budget).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _live_bytes(compiled) < HBM_BYTES
