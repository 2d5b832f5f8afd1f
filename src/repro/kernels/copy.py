"""Vectorized copy kernel (paper Fig. 1): the practical bandwidth ceiling.

Each grid step moves ``Nitem`` aligned (sublane, 128) tiles HBM->VMEM->HBM.
The ``nitem`` parameter is the paper's items-per-thread: larger blocks
amortize grid overhead until VMEM pressure wins -- the benchmark sweeps it
exactly like Fig. 1 sweeps 1/4/8 items.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels.pallas_compat import pltpu

from repro.core import intrinsics as ki


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def copy_pallas(x: jax.Array, *, nitem: int | None = None,
                policy: ki.TuningPolicy | None = None,
                interpret: bool = False) -> jax.Array:
    policy = policy or ki.resolve_tuning("interpret" if interpret else None)
    nitem = nitem or policy.nitem_copy
    n = x.shape[0]
    sub = ki.min_tile(x.dtype)[0]
    view, rows = ki.lane_rows(x, nitem * sub, sub)
    m = view.shape[0]
    spec = pl.BlockSpec((rows, ki.LANES), lambda g: (g, 0))
    out = pl.pallas_call(
        _copy_kernel,
        grid=(ki.cdiv(m, rows),),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, ki.LANES), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(view)
    return ki.from_lane_rows(out, n)
