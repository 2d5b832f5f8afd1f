"""Whole step: model FLOPs the window's requests needed (prompt tokens
prefilled, tokens decoded; see ``counts.py``) over the traced window x
the chip's bf16 peak x chips."""
import counts

UNIT, SOURCE, LAYER, MOVES = "%", "device_trace", "whole step", \
    "tokens_per_s"


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    flops = sum(counts.request_flops(run.model, len(r["prompt"]),
                                     len(r["tokens"])) for r in run.requests)
    return 100.0 * flops / (t.window_s * run.peaks["bf16_flops_per_s"]
                            * run.chips)
