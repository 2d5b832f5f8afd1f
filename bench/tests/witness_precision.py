"""A second witness for a cell's compared numbers: the program at a cut
depth in float32 (every matrix product at HIGHEST; the KV cache stays
bfloat16, as the engine keeps it) or in bfloat16, each against the plain
f32 reference on the same seeds.  Where the float32 program agrees with
the reference far closer than the bfloat16 one, the bfloat16 readings
are rounding and not a fault of the program's logic.

    PYTHONPATH=src:bench python bench/tests/witness_precision.py \\
        <layers> <float32|bfloat16> <bench/run.py arguments, --calibrate N>
"""
import sys

import jax

import harness


def main(layers: int, dtype: str, argv) -> int:
    load = harness.load_json

    def cut(path):
        d = load(path)
        if path.parent.name == "configs":
            for m in (d["model"], d["rehearse"]["model"]):
                m.update(n_layers=layers, n_units=layers, dtype=dtype)
        return d

    harness.load_json = cut
    if dtype == "float32":
        jax.config.update("jax_default_matmul_precision", "highest")
    return harness.main(argv)


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]), sys.argv[2], sys.argv[3:]))
