"""The trace reduction, on hand-made intervals and on a small trace
recorded on the chip (``record_trace.py``: minitron-4b, two greedy
requests of 4 and 6 tokens served inside the window span)."""
import os

import pytest

import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "minitron_greedy.xplane.pb")


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 15), (20, 30), (25, 26), (40, 41)]
    assert tr.union_length(iv) == 15 + 10 + 1
    assert tr.gaps(iv, 0, 50) == [(15, 20), (30, 40), (41, 50)]
    assert tr.gaps(iv, 2, 28) == [(15, 20)]
    assert tr.union_length([]) == 0


def test_program_kinds():
    loop = "%while.40 = (pred[8]{0:T(512)}, bf16[32,8,2048,8,128]) while(...)"
    prefill = "%while.40 = (s32[]{:T(128)}, bf16[1,1024,3072]) while(...)"
    assert tr.program_kind("jit__unknown(1)", ["%fusion.1 = f32[8]", loop]) \
        == "loop"
    assert tr.program_kind("jit__unknown(2)", [prefill]) == "prefill"
    assert tr.program_kind("jit__admit_impl(3)", [prefill]) == "admit"
    assert tr.program_kind("jit_add(4)", ["%add.1 = f32[8]"]) == "other"
    assert tr.op_label("%fusion.141 = bf16[1024,9216] fusion(...)") == \
        "fusion"


@pytest.fixture(scope="module")
def summary():
    if not os.path.exists(TRACE):
        pytest.fail(f"recorded trace missing: {TRACE}")
    return tr.reduce_file(TRACE)


def test_recorded_trace(summary):
    """What the reduction must give on the recorded trace: a 0.41 s
    window, 0.204 s of it busy; two 1024-token prefills of 39.3 ms each,
    two admissions, one decode-loop dispatch of 5 steps (24.1 ms a step);
    the longest idle gaps while the host drains."""
    s = summary
    assert s.window_s == pytest.approx(0.411890484, abs=1e-9)
    assert s.busy_s == pytest.approx(0.204083033, abs=1e-9)
    assert s.program_s("prefill") == pytest.approx(0.07861756, abs=1e-9)
    assert s.program_s("admit") == pytest.approx(0.001674597, abs=1e-9)
    assert s.program_s("loop") == pytest.approx(0.120489297, abs=1e-9)
    assert s.program_s("other") == pytest.approx(0.003343521, abs=1e-9)
    assert s.breakdown["device_ops"][0][0] == "fusion"
    assert s.breakdown["device_ops"][0][1] == pytest.approx(0.105731855,
                                                            abs=1e-9)
    assert s.idle_gaps[0] == ("drain_done", pytest.approx(0.085177088,
                                                          abs=1e-9))
    assert len(s.idle_gaps) == 178
    assert all(n in tr.SPANS + ("host",) for n, _ in s.idle_gaps)
