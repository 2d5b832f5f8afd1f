"""Engine / scheduler: share of the decode loop's slot-steps that produced
a token -- tokens from decode steps (all tokens less the first of each
request, which its prefill yields) over decode steps x slots.  From
``Engine.last_stats``, summed over the window's chunks."""
UNIT, SOURCE, LAYER, MOVES = "%", "program_counter", "engine/scheduler", \
    "tokens_per_s"


def read(run):
    c = run.counters
    if not c.get("decode_steps"):
        return None
    decoded = c["total_tokens"] - c["admissions"]
    return 100.0 * decoded / (c["decode_steps"] * run.batch_size)
