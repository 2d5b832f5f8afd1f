"""Plain reference of a dense decoder with grouped-query attention.

Pre-norm residual blocks: RMSNorm with a (1 + scale) gain, attention with
rotary embeddings (the two halves of each head rotated), grouped-query
heads (query head h reads key/value head h // (H / K)), causal softmax at
1/sqrt(head_dim), then an MLP (``relu2``: relu(x W_in)^2 W_out, or
``swiglu``: silu(x W_gate) * (x W_in) W_out); a final RMSNorm and an
untied unembedding.  Everything is float32 with every matrix product at
``Precision.HIGHEST``; layers run one after another under ``lax.scan`` and
attention in blocks of queries, so a long sequence fits beside the served
weights.

``init_weights`` makes the benchmark's weights from a key in one jitted
call, in the pytree layout the program's ``Engine`` takes (a scanned stack
of identical blocks).  The reference reads them; it never reads anything
the program made.

``quant="int8"`` or ``quant="fp8"`` is the control: every linear layer
(q, k, v, o, the MLP and the unembedding) computed from int8 (symmetric)
or float8 e4m3 weights with one scale per output channel, and activations
in the same type with one scale per row, accumulated in f32 -- the
precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def init_weights(key, m: dict):
    """Random weights from ``key`` for model fields ``m`` (a dict of the
    configuration file's ``model``), in its ``dtype``.  Matrices are
    N(0, 1/fan_in), the embedding N(0, 0.02^2), norm gains N(0, 0.1^2)
    around the 1 of the (1 + scale) form."""
    dtype = jnp.dtype(m["dtype"])
    d, H, K, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ff, V, n = m["d_ff"], m["vocab_size"], m["n_units"]
    gated = m["activation"] == "swiglu"
    shapes = {
        "embedding": ((V, d), 0.02), "unembed": ((d, V), d ** -0.5),
        "final": ((d,), 0.1), "norm1": ((n, d), 0.1), "norm2": ((n, d), 0.1),
        "wq": ((n, d, H, hd), d ** -0.5), "wk": ((n, d, K, hd), d ** -0.5),
        "wv": ((n, d, K, hd), d ** -0.5), "wo": ((n, H, hd, d), (H * hd) ** -0.5),
        "w_in": ((n, d, ff), d ** -0.5), "w_out": ((n, ff, d), ff ** -0.5),
    }
    if gated:
        shapes["w_gate"] = ((n, d, ff), d ** -0.5)

    def make(key):
        keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
        w = {name: (jax.random.normal(keys[name], shp, dtype)
                    * jnp.asarray(std, dtype))
             for name, (shp, std) in shapes.items()}
        mlp = {"w_in": w["w_in"], "w_out": w["w_out"]}
        if gated:
            mlp["w_gate"] = w["w_gate"]
        block = {"norm1": {"scale": w["norm1"]},
                 "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                 "norm2": {"scale": w["norm2"]}, "mlp": mlp}
        return {"embed": {"embedding": w["embedding"],
                          "unembed": w["unembed"]},
                "decoder": {"prefix": (), "units": (block,), "suffix": ()},
                "final_norm": {"scale": w["final"]}}

    return jax.jit(make)(key)


def _quant(x, axis, kind):
    """Fake quantization to int8 (symmetric) or float8 e4m3, with one
    scale per slice along every axis but ``axis`` (the contraction
    axis)."""
    top = 127.0 if kind == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if kind == "int8":
        return jnp.round(x / s).clip(-127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, x, w, quant, w_axes):
    """f32 product of activations ``x`` (rows first, contraction last) and
    weights ``w`` (contraction axes ``w_axes``)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x = _quant(x, tuple(range(1, x.ndim)), quant)
        w = _quant(w, w_axes, quant)
    return jnp.einsum(spec, x, w, precision=HI)


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """x: (S, heads, hd); the first and second halves of each head are the
    two coordinates of each rotated pair."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-2.0 * np.arange(half) / hd)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v):
    """Causal attention over one sequence.  q: (S, K, G, hd); k, v:
    (S, K, hd).  Queries in blocks of ``Q_BLOCK``."""
    S, K, G, hd = q.shape
    nb = S // Q_BLOCK
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HI) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(nb))        # (nb, Q_BLOCK, K, G, hd)
    return out.reshape(S, K * G, hd)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _hidden(params, tokens, *, m, quant):
    """Final-normed hidden states (S, d) of one padded sequence."""
    m = dict(m)
    eps, theta = m["norm_eps"], m["rope_theta"]
    K, G = m["n_kv_heads"], m["n_heads"] // m["n_kv_heads"]
    h = params["embed"]["embedding"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])

    def layer(h, w):
        x = _rmsnorm(h, w["norm1"]["scale"], eps)
        a = w["attn"]
        q = _rope(_mm("sd,dhk->shk", x, a["wq"], quant, 0), pos, theta)
        k = _rope(_mm("sd,dhk->shk", x, a["wk"], quant, 0), pos, theta)
        v = _mm("sd,dhk->shk", x, a["wv"], quant, 0)
        o = _attention(q.reshape(q.shape[0], K, G, -1), k, v)
        h = h + _mm("shk,hkd->sd", o, a["wo"], quant, (0, 1))
        x = _rmsnorm(h, w["norm2"]["scale"], eps)
        f = w["mlp"]
        up = _mm("sd,df->sf", x, f["w_in"], quant, 0)
        if m["activation"] == "swiglu":
            up = jax.nn.silu(_mm("sd,df->sf", x, f["w_gate"], quant, 0)) * up
        elif m["activation"] == "relu2":
            up = jnp.square(jax.nn.relu(up))
        else:
            raise ValueError(m["activation"])
        return h + _mm("sf,fd->sd", up, f["w_out"], quant, 0), None

    (block,) = params["decoder"]["units"]
    h, _ = jax.lax.scan(layer, h, block)
    return _rmsnorm(h, params["final_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def _logits(params, h, rows, *, quant):
    return _mm("sd,dv->sv", h[rows], params["embed"]["unembed"], quant, 0)


def score(params, m: dict, tokens: np.ndarray, rows: np.ndarray,
          quant=None, pad_to: int = Q_BLOCK):
    """Logits (>= len(rows), vocab) f32 on the device; row i is position
    ``rows[i]`` of ``tokens``, and rows past ``len(rows)`` are padding.

    The sequence is right-padded to a multiple of ``pad_to`` (causality
    keeps the pads out of every real position) and the rows to a multiple
    of 128, so few shapes compile."""
    S = len(tokens)
    Sp = -(-S // pad_to) * pad_to
    toks = np.zeros(Sp, np.int32)
    toks[:S] = tokens
    R = -(-len(rows) // 128) * 128
    rows_p = np.zeros(R, np.int32)
    rows_p[:len(rows)] = rows
    key = tuple(sorted((k, v) for k, v in m.items()
                       if isinstance(v, (int, float, str))))
    h = _hidden(params, jnp.asarray(toks), m=key, quant=quant)
    return _logits(params, h, jnp.asarray(rows_p), quant=quant)
