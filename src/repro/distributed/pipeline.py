"""GPipe-style pipeline parallelism over the "pod" axis (optional feature).

On the multi-pod mesh the ``pod`` axis crosses the slower inter-pod links;
pipelining layers across pods trades the per-layer FSDP/TP collectives on
that axis for point-to-point microbatch handoffs (one ``ppermute`` of a
microbatch activation per stage step) -- the standard reason 1000+-node
deployments pipeline across the DCN boundary.

This module provides the schedule as a composable harness: a stage function
+ per-stage params stacked on a leading axis, lowered via ``shard_map`` over
``pod``.  Bubble fraction is (n_stages - 1) / (n_micro + n_stages - 1).
``tests/test_pipeline.py`` checks exact parity with sequential execution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def gpipe_forward(mesh, stage_fn, stage_params, x, *, n_micro,
                  axis_name="pod"):
    """Run ``n_stages`` stage_fn's over the ``axis_name`` mesh axis.

    stage_params: pytree whose leaves have leading dim n_stages (stage i's
    slice lives on pod i).  x: (B, ...) with B divisible by n_micro.
    Returns stage_{n-1}(...stage_0(x)) with GPipe microbatching.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = sizes[axis_name]
    B = x.shape[0]
    assert B % n_micro == 0
    mb = B // n_micro
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(params_local, xb):
        sid = jax.lax.axis_index(axis_name)
        params_local = jax.tree.map(lambda l: l[0], params_local)
        mbs = xb.reshape((n_micro, mb) + xb.shape[1:])
        recv = jnp.zeros((mb,) + xb.shape[1:], xb.dtype)
        outs = jnp.zeros_like(mbs)
        for t in range(n_micro + n_stages - 1):
            # Stage 0 injects microbatch t; others consume the handoff.
            feed_idx = min(max(t, 0), n_micro - 1)
            inject = (sid == 0) & (t < n_micro)
            inp = jnp.where(inject, mbs[feed_idx], recv)
            out = stage_fn(params_local, inp)
            # Last stage retires microbatch t - (n_stages - 1).
            ret = t - (n_stages - 1)
            if 0 <= ret < n_micro:
                retire = (sid == n_stages - 1)
                outs = outs.at[ret].set(jnp.where(retire, out, outs[ret]))
            recv = jax.lax.ppermute(out, axis_name, fwd_perm)
        # Result lives on the last stage; broadcast it to every pod so the
        # output is replicated along the axis (psum of one-hot contribution).
        mask = (jax.lax.axis_index(axis_name) == n_stages - 1)
        outs = jax.lax.psum(jnp.where(mask, outs, 0), axis_name)
        return outs.reshape(xb.shape)

    other_axes = [a for a in mesh.axis_names if a != axis_name]
    pspec = P(*([axis_name] + [None] * 0))

    def leaf_spec(l):
        return P(*([axis_name] + [None] * (l.ndim - 1)))

    in_specs = (jax.tree.map(leaf_spec, stage_params),
                P(*([None] * x.ndim)))
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=P(*([None] * x.ndim)), check_vma=False)
    return fn(stage_params, x)
