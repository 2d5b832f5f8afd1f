"""The comparison that decides ``correct``.

After the window closes, a sample of the requests it finished -- the one
with the most output tokens, then others drawn from the seed until the
sample holds ``check_tokens`` served tokens -- is run through the plain
reference: one pass over each prompt followed by its served tokens, with
f32 logits at every position that produced a served token.  From those:

* ``gap`` -- the widest gap by which a served token's reference logit lies
  below the reference's best at that position (greedy cells);
* ``logprob_err`` -- per request, the gap between the sequence log-prob
  the engine returned and the reference's log-prob of the same tokens,
  over the square root of the token count; the largest in the sample;
* ``outside_topk`` -- served tokens that the reference ranks at 2k or
  lower, k being the sampler's candidate count (sampled cells).  A sampler
  draws only from its k best; rounding cannot carry a token from rank 2k
  into them (the logits there lie tenths apart), so this is exact;
* ``nucleus_excess`` -- how far outside the reference's nucleus a served
  token lies (sampled cells with top-p).  The sampler keeps a candidate
  while the mass above it, at the mix's temperature and renormalized over
  its k candidates, is below ``top_p``: so the mass from the token down,
  ``tail``, exceeds ``1 - top_p``.  Per token ``log(1 - top_p) - log(tail)``
  under the reference's logits, in nats, and the largest over the sample
  (0 when every token is inside).  Rounding moves it by hundredths; a
  sampler that ignores the temperature, the top-p cut or the candidates'
  order serves tokens whole nats outside;
* ``bad_requests`` -- requests of the window that returned another number
  of tokens than they asked for, or a token id outside the vocabulary.

The control is the reference put in the program's place at the precision
step below the configuration's (``quant="int8"`` or ``quant="fp8"`` of the
reference module, as the cell's file names it): at each position of the same
sequences, ``gap`` reads the reference logit of the token the control
puts first, ``nucleus_excess`` the reference's excess at the last token
the control keeps in its nucleus, and ``logprob_err`` the control's
log-prob of the served tokens against the reference's.  Which control
(``int8`` or ``fp8``) is the cell's ``control``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sample(recs: list, rng: np.random.Generator, target_tokens: int) -> list:
    """The request with the most tokens, then others in a seeded order,
    until ``target_tokens`` served tokens are in the sample."""
    if not recs:
        return []
    first = max(range(len(recs)), key=lambda i: len(recs[i]["tokens"]))
    out, total = [first], len(recs[first]["tokens"])
    for i in rng.permutation(len(recs)):
        if total >= target_tokens:
            break
        if i != first and recs[i]["tokens"]:
            out.append(int(i))
            total += len(recs[i]["tokens"])
    return [recs[i] for i in out]


def _excess(ref, at, nucleus):
    """Per row: ``log(1 - top_p) - log(tail)`` of the logit ``at`` under
    the reference's best ``k`` logits at temperature ``t`` (``nucleus`` is
    ``(k, t, top_p)``; all zeros without one).  ``tail`` is the mass from
    ``at`` down, renormalized over the k; a logit below all k counts its
    own mass."""
    if nucleus is None:
        return jnp.zeros(ref.shape[0])
    k, t, top_p = nucleus
    top = jax.lax.top_k(ref, k)[0] / t                  # (R, k) descending
    a = at[:, None] / t
    lse = jax.nn.logsumexp(top, axis=1)
    tail = jax.nn.logsumexp(jnp.where(top <= a, top, -jnp.inf), axis=1)
    tail = jnp.where(a[:, 0] < top[:, -1], jnp.logaddexp(tail, a[:, 0]),
                     tail)
    return jnp.maximum(np.log1p(-top_p) - (tail - lse), 0.0)


@functools.partial(jax.jit, static_argnames=("nucleus",))
def _reduce(ref, tok, n, far, nucleus):
    """Per sequence: the widest gap below the best reference logit, the
    reference log-prob of the served tokens, how many of them the
    reference ranks at ``far`` or lower, and the widest nucleus excess.
    Rows past ``n`` are padding."""
    valid = jnp.arange(ref.shape[0]) < n
    at = jnp.take_along_axis(ref, tok[:, None], axis=1)[:, 0]
    gap = jnp.where(valid, jnp.max(ref, axis=1) - at, -jnp.inf)
    lp = jnp.where(valid, at - jax.nn.logsumexp(ref, axis=1), 0.0)
    rank = jnp.sum(ref > at[:, None], axis=1)
    exc = jnp.where(valid, _excess(ref, at, nucleus), 0.0)
    return (jnp.max(gap), jnp.sum(lp), jnp.sum(valid & (rank >= far)),
            jnp.max(exc))


@functools.partial(jax.jit, static_argnames=("nucleus",))
def _control(ref, ctl, tok, n, nucleus):
    """The control's readings: the reference's gap at the token the
    control puts first, the reference's nucleus excess at the last token
    the control keeps in its nucleus, and the control's log-prob of the
    served tokens."""
    valid = jnp.arange(ref.shape[0]) < n
    first = jnp.argmax(ctl, axis=1)
    gap = jnp.max(ref, axis=1) - jnp.take_along_axis(
        ref, first[:, None], axis=1)[:, 0]
    lp = (jnp.take_along_axis(ctl, tok[:, None], axis=1)[:, 0]
          - jax.nn.logsumexp(ctl, axis=1))
    exc = jnp.zeros(ref.shape[0])
    if nucleus is not None:
        k, t, top_p = nucleus
        vals, idx = jax.lax.top_k(ctl, k)
        p = jax.nn.softmax(vals / t, axis=1)
        above = jnp.cumsum(p, axis=1) - p               # mass above each
        last = jnp.sum(above < top_p, axis=1) - 1       # last kept
        kept = jnp.take_along_axis(idx, last[:, None], axis=1)[:, 0]
        at = jnp.take_along_axis(ref, kept[:, None], axis=1)[:, 0]
        exc = _excess(ref, at, nucleus)
    return (jnp.max(jnp.where(valid, gap, -jnp.inf)),
            jnp.sum(jnp.where(valid, lp, 0.0)),
            jnp.max(jnp.where(valid, exc, 0.0)))


def request_readings(ref_mod, params, model: dict, rec: dict, *,
                     candidates: int | None, nucleus, control) -> dict:
    """Reference readings of one served request (and the control's)."""
    prompt = np.asarray(rec["prompt"], np.int32)
    toks = np.asarray(rec["tokens"], np.int32)
    n, plen = len(toks), len(prompt)
    seq = np.concatenate([prompt, toks[:-1]])
    rows = np.arange(plen - 1, plen - 1 + n)
    ref = ref_mod.score(params, model, seq, rows)
    tok = np.zeros(ref.shape[0], np.int32)
    tok[:n] = toks
    tok = jnp.asarray(tok)
    far = 2 * candidates if candidates else model["vocab_size"] + 1
    gap, lp, outside, exc = _reduce(ref, tok, n, far, nucleus)
    out = {"n": n, "gap": float(gap), "ref_logprob": float(lp),
           "outside_topk": int(outside), "nucleus_excess": float(exc),
           "logprob_err": abs(rec["seq_logprob"] - float(lp)) / n ** 0.5}
    if control:
        ctl = ref_mod.score(params, model, seq, rows, quant=control)
        cgap, clp, cexc = _control(ref, ctl, tok, n, nucleus)
        out["control_gap"] = float(cgap)
        out["control_nucleus_excess"] = float(cexc)
        out["control_logprob_err"] = abs(float(clp) - float(lp)) / n ** 0.5
        del ctl
    del ref
    return out


def numbers(window_recs: list, readings: list, *, vocab: int,
            control: bool = False) -> dict:
    """The cell's compared numbers from the sample's readings.  With
    ``control`` the control's readings take the program's place."""
    pre = "control_" if control else ""
    out = {
        "gap": max(r[pre + "gap"] for r in readings),
        "logprob_err": max(r[pre + "logprob_err"] for r in readings),
        "nucleus_excess": max(r[pre + "nucleus_excess"] for r in readings),
        "outside_topk": (0 if control else
                         sum(r["outside_topk"] for r in readings)),
    }
    bad = 0
    for rec in window_recs:
        t = rec["tokens"]
        if len(t) != rec["max_new"] or any(not 0 <= x < vocab for x in t):
            bad += 1
    out["bad_requests"] = bad
    return out
