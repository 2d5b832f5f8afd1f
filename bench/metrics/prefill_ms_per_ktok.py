"""Prefill: device time of the prefill program's executions in the trace,
per 1000 prompt tokens admitted (valid tokens, so bucket padding counts
as cost)."""
UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "prefill", \
    "latency_p95_s"


def read(run):
    t = run.trace
    tokens = sum(len(r["prompt"]) for r in run.requests)
    if t is None or not tokens:
        return None
    s = t.program_s("prefill")
    return 1e3 * s / (tokens / 1e3) if s > 0 else None
