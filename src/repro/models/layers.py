"""Shared model layers: norms, embeddings, rotary, MLPs, sharding hooks.

Everything is functional: ``init_*`` builds param pytrees (plain dicts),
``apply`` functions are pure.  Sharding is expressed through *logical axis*
annotations resolved against a rules table installed by the distributed
layer (``repro.distributed.sharding``); with no rules installed the
annotations are no-ops, so the same model code runs in CPU tests, the
dry-run, and on real meshes.
"""
from __future__ import annotations

import contextlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Logical-axis sharding context
# ---------------------------------------------------------------------------

_RULES: dict | None = None


@contextlib.contextmanager
def sharding_rules(rules: dict | None):
    global _RULES
    prev = _RULES
    _RULES = rules
    try:
        yield
    finally:
        _RULES = prev


def current_rules() -> dict | None:
    return _RULES


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (no-op w/o rules)."""
    if _RULES is None:
        return x
    spec = jax.sharding.PartitionSpec(
        *[_RULES.get(name) if name is not None else None for name in logical])
    return jax.lax.with_sharding_constraint(x, spec)


def logical_spec(*logical):
    if _RULES is None:
        return jax.sharding.PartitionSpec()
    return jax.sharding.PartitionSpec(
        *[_RULES.get(name) if name is not None else None for name in logical])


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis=0, dtype=jnp.float32, scale=1.0):
    fan_in = shape[in_axis] if isinstance(in_axis, int) else int(
        np.prod([shape[a] for a in in_axis]))
    std = scale / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d, dtype=jnp.float32):
    return {"scale": jnp.zeros((d,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # Gemma-style (1 + scale) parameterization, zero-init.
    return (x * (1.0 + params["scale"].astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply RoPE.  x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(
        -jnp.log(theta) * (2 * jnp.arange(half, dtype=jnp.float32) / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (gated + plain variants)
# ---------------------------------------------------------------------------


def init_mlp(key, d, ff, activation, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    gated = activation in ("swiglu", "geglu")
    p = {
        "w_in": dense_init(k1, (d, ff), 0, dtype),
        "w_out": dense_init(k3, (ff, d), 0, dtype),
    }
    if gated:
        p["w_gate"] = dense_init(k2, (d, ff), 0, dtype)
    return p


def _act(name, x):
    if name in ("swiglu",):
        return jax.nn.silu(x)
    if name in ("geglu", "gelu"):
        return jax.nn.gelu(x)
    if name == "relu":
        return jax.nn.relu(x)
    if name == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(name)


def mlp(params, x, activation, dtype=None):
    """x: (B, S, D) -> (B, S, D); inner dim sharded on 'ffn'."""
    dtype = dtype or x.dtype
    h = jnp.einsum("bsd,df->bsf", x, params["w_in"].astype(dtype))
    if "w_gate" in params:
        g = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(dtype))
        h = _act(activation, g) * h
    else:
        h = _act(activation, h)
    # Inside the MLP the ffn axis carries "model"; under SP the residual
    # stream's seq shards are all-gathered on entry and reduce-scattered on
    # exit (Megatron sequence parallelism) -- hence seq is None here.
    h = shard(h, "batch", None, "ffn")
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"].astype(dtype))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def init_embed(key, vocab, d, tie, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    p = {"embedding": embed_init(k1, (vocab, d), dtype)}
    if not tie:
        p["unembed"] = dense_init(k2, (d, vocab), 0, dtype)
    return p


def embed(params, tokens, scale=False, dtype=jnp.bfloat16):
    x = jnp.take(params["embedding"], tokens, axis=0).astype(dtype)
    if scale:
        x = x * jnp.asarray(np.sqrt(x.shape[-1]), dtype)
    return x


def unembed(params, x, softcap=0.0):
    table = params.get("unembed")
    if table is None:
        table = params["embedding"].T
    # Accumulate and keep the logits in f32: rounded to bf16, the top of a
    # large vocabulary ties, and XLA's matrix-vector form of this dot (one
    # row) and its matrix form (several rows) round differently, which
    # flips greedy tokens between batch shapes.
    logits = jnp.einsum("bsd,dv->bsv", x, table.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    return shard(logits, "batch", "seq_sp", "vocab")


# ---------------------------------------------------------------------------
# Losses (via the KernelForge mapreduce algebra where natural)
# ---------------------------------------------------------------------------


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          z_loss: float = 0.0):
    """Mean token cross-entropy; labels < 0 are masked out."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    loss = lse - gold
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
