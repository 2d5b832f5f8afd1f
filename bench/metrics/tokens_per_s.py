"""Output tokens of every request completed in the window, over the
window's elapsed wall time (host clock)."""
UNIT, SOURCE = "tokens/s", "host_clock"


def read(run):
    return sum(len(r["tokens"]) for r in run.requests) / run.window_s
