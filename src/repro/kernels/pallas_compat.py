"""One import point for the Pallas lowerings the kernels use.

Kernels import ``pl`` / ``pltpu`` / ``pltriton`` from here.
:func:`gpu_compiler_params` builds the Triton params of the pallas-gpu
kernels (interpret-mode calls never consult them).
"""
from __future__ import annotations

from jax.experimental import pallas as pl  # noqa: F401  (re-export)
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas import triton as pltriton


def gpu_compiler_params(num_warps: int | None = None,
                        num_stages: int | None = None):
    """Triton compiler params for ``pl.pallas_call``."""
    return pltriton.CompilerParams(num_warps=num_warps, num_stages=num_stages)


__all__ = ["pl", "pltpu", "pltriton", "gpu_compiler_params"]
