"""Plain references, one module per architecture.  Each imports nothing of
the program and gives ``init_weights`` (the benchmark's own weights, in the
layout the program takes) and ``score`` (f32 logits rows of given
positions, optionally in the int8 control precision)."""
