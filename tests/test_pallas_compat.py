"""kernels/pallas_compat.py must import cleanly and expose the Pallas
lowerings the kernels use, and ``gpu_compiler_params`` must build the
Triton params of the pallas-gpu kernels.
"""
import jax

from repro.kernels import pallas_compat as pc


def test_reexports_exist():
    assert pc.pl is not None
    assert pc.pltpu is not None
    for name in pc.__all__:
        assert hasattr(pc, name), name


def test_gpu_compiler_params_builder():
    params = pc.gpu_compiler_params(num_warps=4, num_stages=2)
    assert isinstance(params, pc.pltriton.CompilerParams)
    assert (params.num_warps, params.num_stages) == (4, 2)


def test_gpu_compiler_params_defaults():
    params = pc.gpu_compiler_params()
    assert isinstance(params, pc.pltriton.CompilerParams)
    assert (params.num_warps, params.num_stages) == (None, None)


def test_interpret_call_ignores_gpu_params():
    """An interpret-mode pallas_call must work with compiler_params absent
    (the shape gpu.py uses on CPU)."""
    import jax.numpy as jnp

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    x = jnp.arange(8, dtype=jnp.float32)
    out = pc.pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x)
    assert (out == x * 2).all()
