"""shard_map MoE: dispatch without collectives (the dsv3 §Perf fix).

Baseline pathology: the GSPMD lowering of the sort-based capacity dispatch
(models/moe.py) all-gathers token buffers across the mesh -- the argsort and
scatter are *global* over tokens, so XLA materializes gathered operands:
deepseek-v3 train_4k showed a 3963s collective term vs 48s of compute.

Key observation: the residual stream is already **replicated across the
model axis** within each data-parallel row (activations are P(dp, None,
None)).  So every model rank can compute routing locally and simply *take*
the tokens destined for its own expert slice -- the dispatch "all-to-all"
costs zero bytes.  Only the combine needs communication: one psum of the
[T_local, D] output per MoE layer (what a dense TP layer pays anyway), which
also carries the shared-expert partial sums for free.

Per-layer collectives:  before: O(T*D) gathers of dispatch buffers;
after: 1 all-reduce of T_local x D (+ the FSDP weight gathers both pay).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import operators as alg
from repro.core import primitives as forge
from repro.core.layout import Segmented, Sharded
from repro.models import layers as L


def _gather_axis(w, axis, dp_axes):
    """All-gather a weight's FSDP-sharded dim inside shard_map (ZeRO-3)."""
    for a in dp_axes:
        w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
    return w


def moe_forward_sharded(params, cfg, x, mesh):
    """Drop-in replacement for moe_forward under a ("data","model") mesh.

    x: (B, S, D) with batch sharded over dp and replicated over model.
    Experts are sharded over "model" (EP); expert weights' D axis is
    FSDP-sharded over dp (gathered per layer, as GSPMD FSDP would).
    """
    dtype = x.dtype
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes.get("model", 1)
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp_total = int(np.prod([sizes[a] for a in dp_axes])) if dp_axes else 1
    E_loc = E // m
    T_loc = (B // dp_total) * S if B % dp_total == 0 else B * S
    # Per-expert capacity.  The denominator is the *global* expert count on
    # purpose: C bounds tokens **per expert id** (the `pos < C` cap below
    # counts within one expert's run of the sorted stream), and the dispatch
    # buffer allocates C slots for each of the E_loc local experts -- so
    # under expert parallelism (E_loc < E) every local expert still holds up
    # to its full even-share x capacity_factor.  Dividing by E_loc instead
    # would inflate capacity m-fold, not fix a drop.  tests/test_sharded.py
    # pins the E_loc != E no-drop parity at capacity_factor=1.0 with
    # exactly-even routing.
    C = int(np.ceil(T_loc * k * cfg.capacity_factor / E))
    C = max(8, ((C + 7) // 8) * 8)
    gated = "w_gate" in params
    shared = params.get("shared", {})
    has_shared = "w_in" in shared
    shared_gated = "w_gate" in shared

    def local(xb, router, router_bias, w_in, w_out, *rest):
        rest = list(rest)
        w_gate = rest.pop(0) if gated else None
        shared_in = rest.pop(0) if has_shared else None
        shared_gate = rest.pop(0) if shared_gated else None
        shared_out = rest.pop(0) if has_shared else None
        xf = xb.reshape(-1, D)                         # (T_loc, D)
        router = _gather_axis(router, 0, dp_axes)      # (D, E)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
        if cfg.router_type == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            sel = scores + router_bias[None, :]
            _, idx = jax.lax.top_k(sel, k)
            gates = jnp.take_along_axis(scores, idx, axis=1)
            gates = gates / jnp.maximum(jnp.sum(gates, 1, keepdims=True), 1e-9)
            probs = scores / jnp.maximum(jnp.sum(scores, 1, keepdims=True), 1e-9)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            gates, idx = jax.lax.top_k(probs, k)
            gates = gates / jnp.maximum(jnp.sum(gates, 1, keepdims=True), 1e-9)

        # ---- router statistics: global across the data axes, through the
        # mapreduce@sharded route (in-mesh form).  The route is a staged
        # ShardPlan whose collective stage is the ADD FoldSpec's psum --
        # the same psum this replaced, but the expert-count reduction now
        # rides the same registry route (and overlap-capable plan driver)
        # as every other consumer; global counts / mean-probs make lb_loss
        # the whole-batch statistic rather than a mean of per-shard
        # products.
        def dp_mean(v):
            for a in dp_axes:
                v = forge.mapreduce(lambda t: t, alg.ADD, v[None],
                                    layout=Sharded(a)) / sizes[a]
            return v

        counts = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        frac = dp_mean(counts / idx.size)
        lb_loss = E * jnp.sum(frac * dp_mean(jnp.mean(probs, axis=0)))
        router_z = dp_mean(jnp.mean(jnp.square(
            jax.scipy.special.logsumexp(logits, axis=-1))))

        # ---- local dispatch (identical math on every model rank) ----
        flat_e = idx.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T_loc), k)
        flat_g = gates.reshape(-1)
        # Expert-sorted stream via the stable radix sort_pairs primitive
        # (the portable replacement for the global XLA argsort): expert ids
        # span only ceil(log2(E)) bits, so key_bits= caps the sort at 1-2
        # digit passes instead of a full 32-bit comparison sort.
        se, (st, sg) = forge.sort_pairs(
            flat_e.astype(jnp.uint32), (flat_t, flat_g),
            key_bits=max(1, (E - 1).bit_length()))
        se = se.astype(jnp.int32)
        # Within-expert slot index = exclusive segmented +scan of ones over
        # the expert-sorted stream (segment = run of equal expert id).  This
        # is the ragged expert grouping done natively -- no E-sized
        # counts/starts scatter, no padded intermediate.
        run_flags = jnp.concatenate(
            [jnp.ones((1,), jnp.int32), (se[1:] != se[:-1]).astype(jnp.int32)])
        pos = forge.scan(
            alg.ADD, jnp.ones_like(se, jnp.int32), inclusive=False,
            layout=Segmented(flags=run_flags))
        keep = pos < C

        # ---- take only MY experts (zero-collective "all-to-all") ----
        my_e0 = jax.lax.axis_index("model") * E_loc
        mine = keep & (se >= my_e0) & (se < my_e0 + E_loc)
        se_rel = jnp.where(mine, se - my_e0, E_loc)    # OOB -> dropped
        xbuf = jnp.zeros((E_loc, C, D), dtype)
        xbuf = xbuf.at[se_rel, pos].set(
            xf[st] * mine[:, None].astype(dtype), mode="drop")

        w_in_g = _gather_axis(w_in, 1, dp_axes)        # (E_loc, D, F)
        h = jnp.einsum("ecd,edf->ecf", xbuf, w_in_g.astype(dtype))
        if gated:
            w_gate_g = _gather_axis(w_gate, 1, dp_axes)
            g = jnp.einsum("ecd,edf->ecf", xbuf, w_gate_g.astype(dtype))
            h = (jax.nn.silu(g) if cfg.activation == "swiglu"
                 else jax.nn.gelu(g)) * h
        else:
            h = jax.nn.gelu(h)
        w_out_g = _gather_axis(w_out, 2, dp_axes)      # (E_loc, F, D)
        y = jnp.einsum("ecf,efd->ecd", h, w_out_g.astype(dtype))

        gathered = y[se_rel.clip(0, E_loc - 1), pos.clip(0, C - 1)]
        contrib = gathered * (sg * mine).astype(dtype)[:, None]
        out = jnp.zeros((T_loc, D), dtype).at[st].add(contrib)

        # ---- shared expert: F sharded over model -> fold into same psum ----
        if shared_in is not None:
            s_in = _gather_axis(shared_in, 0, dp_axes)     # (D, F_loc)
            hs = jnp.einsum("td,df->tf", xf, s_in.astype(dtype))
            if shared_gate is not None:
                s_g = _gather_axis(shared_gate, 0, dp_axes)
                gs = jnp.einsum("td,df->tf", xf, s_g.astype(dtype))
                hs = (jax.nn.silu(gs) if cfg.activation == "swiglu"
                      else jax.nn.gelu(gs)) * hs
            else:
                hs = jax.nn.gelu(hs)
            s_out = _gather_axis(shared_out, 1, dp_axes)   # (F_loc, D)
            out = out + jnp.einsum("tf,fd->td", hs, s_out.astype(dtype))

        out = jax.lax.psum(out, "model")
        return (out.reshape(-1, S, D), lb_loss, router_z)

    dp = dp_axes if (B % dp_total == 0 and dp_total > 1) else None
    args = [x, params["router"], params["router_bias"],
            params["w_in"], params["w_out"]]
    specs = [P(dp, None, None),
             P(dp_axes, None),                    # router (D, E)
             P(),                                 # router bias
             P("model", dp_axes, None),           # w_in (E, D, F)
             P("model", None, dp_axes)]           # w_out (E, F, D)
    if gated:
        args.append(params["w_gate"])
        specs.append(P("model", dp_axes, None))
    if has_shared:
        args.append(shared["w_in"])
        specs.append(P(dp_axes, "model"))
        if shared_gated:
            args.append(shared["w_gate"])
            specs.append(P(dp_axes, "model"))
        args.append(shared["w_out"])
        specs.append(P("model", dp_axes))
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(specs),
        out_specs=(P(dp, None, None), P(), P()),
        check_vma=False)
    out, lb, rz = fn(*args)
    return out, {"lb_loss": lb, "router_z": rz}
