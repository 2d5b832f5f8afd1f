"""The control -- the plain reference in int8, put in the program's place
-- comes out not correct, and the program correct, on three seeds of each
cell (the configuration's small preset on the CPU; the chip readings that
set the limits are in PERF.md)."""
import json

import pytest

import harness
from test_rehearse import CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_program_passes(capsys, workload):
    rc = harness.main(["--workload", workload, "--seed", "3000000031",
                       "--seconds", "1", "--calibrate", "3", "--rehearse"])
    assert rc == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert len(lines) == 3
    limits = harness.load_json(
        harness.BENCH / "cells" / f"{workload}.json")["limits"]
    for line in lines:
        prog, ctl = line["program"], line["control"]
        assert all(prog[k] <= limits[k] for k in limits), line
        assert any(ctl[k] > limits[k] for k in limits), line
