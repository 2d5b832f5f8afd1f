"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the engine the harness builds (the CPU rehearsal
at the configuration's small preset; the chip check is skipped), once per
cell: a decode step that leaves the cache state unchanged, half of the
batch's logits left out (the other half's copied in their place), a
token altered where the sampler produces it, and, in sampled cells, a
sampler that ignores the temperature (draws at 1).  One chip, so no
exchange between chips to leave out.

``PYTHONPATH=src:bench python bench/tests/test_faults.py <fault> <bench/run.py
arguments>`` runs the harness with the fault planted (on the chip, with
``--calibrate N``, it reads the fault's numbers at the cell's size)."""
import functools
import json
import sys

import jax.numpy as jnp
import pytest

import harness
from test_rehearse import CELLS, SPEC

FAULTS = ("state_unchanged", "half_batch", "token_altered",
          "temperature_ignored")


def sampled(workload):
    cell = next(w for w in SPEC["workloads"] if w["name"] == workload)
    mix = harness.load_json(harness.BENCH / "traffic"
                            / f"{cell['traffic']}.json")
    return mix["sampling"]["temperature"] > 0


def plant(monkeypatch, fault):
    real = harness.build_engine

    def build(*a, **k):
        eng = real(*a, **k)
        decode, sample = eng._decode, eng._sample
        if fault == "temperature_ignored":
            eng._sample = functools.partial(sample.func,
                                            **dict(sample.keywords,
                                                   temperature=1.0))
        elif fault == "state_unchanged":
            eng._decode = lambda p, c, t, pos: (decode(p, c, t, pos)[0], c)
        elif fault == "half_batch":
            def half(p, c, t, pos):
                logits, c2 = decode(p, c, t, pos)
                h = logits.shape[0] // 2
                return jnp.concatenate([logits[:h], logits[:h]]), c2
            eng._decode = half
        else:
            def altered(key, logits, seeds, steps):
                tok = sample(key, logits, seeds, steps)
                return jnp.where(steps == 2, (tok + 1) % logits.shape[-1],
                                 tok)
            eng._sample = altered
        return eng

    monkeypatch.setattr(harness, "build_engine", build)


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CELLS for f in FAULTS
    if f != "temperature_ignored" or sampled(w)])
def test_fault_is_not_correct(monkeypatch, capsys, workload, fault):
    plant(monkeypatch, fault)
    rc = harness.main(["--workload", workload, "--seed", "3000000021",
                       "--seconds", "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]


if __name__ == "__main__":
    with pytest.MonkeyPatch.context() as mp:
        plant(mp, sys.argv[1])
        sys.exit(harness.main(sys.argv[2:]))
