"""Engine / scheduler: decode steps per device-loop dispatch (each
dispatch ends in one host sync).  From ``Engine.last_stats``."""
UNIT, SOURCE, LAYER, MOVES = "steps", "program_counter", \
    "engine/scheduler", "tokens_per_s"


def read(run):
    c = run.counters
    if not c.get("loop_dispatches"):
        return None
    return c["decode_steps"] / c["loop_dispatches"]
