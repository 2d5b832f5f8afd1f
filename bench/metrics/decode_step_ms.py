"""Decode loop: device time of the decode-loop programs' executions in
the trace, over the decode steps they ran."""
UNIT, SOURCE, LAYER, MOVES = "ms", "device_trace", "decode loop", \
    "tokens_per_s"


def read(run):
    t = run.trace
    steps = run.counters.get("decode_steps", 0)
    if t is None or not steps:
        return None
    s = t.program_s("loop")
    return 1e3 * s / steps if s > 0 else None
