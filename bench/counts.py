"""What the work *needs*, counted from shapes: the yardstick of the
roofline and utilization metrics.  The counts do not depend on how the
program implements the work, so a later implementation is judged on the
same numbers.

Model FLOPs count 2 per multiply-add of every matrix product a token needs:
the q/k/v/o projections and the MLP, the logit projection only where
logits are needed (the last prompt position of a prefill, every decode
step), and attention over the positions actually held (a prompt's causal
triangle; for a decode step, every position up to its own).  Padding and
slots that sit idle are not counted.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    d, H, K, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    mlp = (3 if m["activation"] in ("swiglu", "geglu") else 2) * d * ff
    return d * H * hd + 2 * d * K * hd + H * hd * d + mlp


def _attn_flops_per_pair(m: dict) -> int:
    """Scores (q.k) and the weighted sum of values: 2 x 2 x H x hd."""
    return 4 * m["n_heads"] * m["head_dim"]


def _logit_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def prefill_flops(m: dict, plen: int) -> int:
    """A prompt of ``plen`` tokens, with the logits of its last position."""
    pairs = plen * (plen + 1) // 2
    return (m["n_layers"] * (2 * plen * layer_matmul_params(m)
                             + pairs * _attn_flops_per_pair(m))
            + _logit_flops(m))


def decode_flops(m: dict, pos: int) -> int:
    """One decode step whose input token sits at position ``pos`` (it
    attends to positions 0..pos)."""
    return (m["n_layers"] * (2 * layer_matmul_params(m)
                             + (pos + 1) * _attn_flops_per_pair(m))
            + _logit_flops(m))


def request_flops(m: dict, plen: int, n_out: int) -> int:
    """A served request: its prefill yields the first token, and each of
    the other ``n_out - 1`` tokens takes one decode step at positions
    plen .. plen + n_out - 2."""
    steps = max(n_out - 1, 0)
    # sum of (pos + 1) over pos = plen .. plen + steps - 1
    held = steps * (2 * plen + steps + 1) // 2
    return (prefill_flops(m, plen)
            + steps * (m["n_layers"] * 2 * layer_matmul_params(m)
                       + _logit_flops(m))
            + m["n_layers"] * held * _attn_flops_per_pair(m))


def topk_bytes(rows: int, vocab: int, k: int) -> int:
    """A top-k over ``rows`` x ``vocab`` f32 logits needs one read of them
    and one write of k f32 values and k int32 indices per row."""
    return rows * vocab * 4 + rows * k * (4 + 4)
