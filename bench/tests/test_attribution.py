"""The attribution of a traced window to the program's names: on hand-made
intervals, on the recorded trace of ``test_trace.py`` (the harness's spans
only, programs named ``jit__unknown``), and on a small trace recorded on
the chip with the program's own names (``record_scoped_trace.py``:
deepseek-coder-33b-8l, two top-p requests of 4 and 6 tokens)."""
import os

import pytest

import attribution as at
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
GREEDY = os.path.join(DATA, "minitron_greedy.xplane.pb")
SCOPED = os.path.join(DATA, "deepseek_top_p.xplane.pb")


def test_intervals_by_hand():
    assert at.merge([(5, 15), (0, 10), (20, 30), (25, 26), (40, 40)]) == \
        [(0, 15), (20, 30)]
    assert at.intersect([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == \
        [(5, 10), (20, 25), (28, 30)]
    assert at.intersect([(0, 10)], []) == []


def test_innermost_span_by_hand():
    spans = [(10, 40, "drain"), (12, 20, "drain.poll"),
             (25, 39, "drain.collect"), (50, 60, "dispatch")]
    pieces = at.innermost(spans, 0, 70)
    assert pieces == [(0, 10, "outside"), (10, 12, "drain"),
                      (12, 20, "drain.poll"), (20, 25, "drain"),
                      (25, 39, "drain.collect"), (39, 40, "drain"),
                      (40, 50, "outside"), (50, 60, "dispatch"),
                      (60, 70, "outside")]
    # spans are clipped to the window
    assert at.innermost(spans, 15, 30) == [
        (15, 20, "drain.poll"), (20, 25, "drain"), (25, 30, "drain.collect")]
    idle = [(5, 15), (35, 55)]
    assert at.by_label(idle, pieces) == {
        "outside": 5 + 10, "drain": 2 + 1, "drain.poll": 3,
        "drain.collect": 4, "dispatch": 5}
    assert at.label_at(pieces, 30) == "drain.collect"
    assert at.label_at(pieces, 70) == "outside"


def test_scope_paths():
    assert at.scope_path("jit(decode_loop)/while/body/sampler/top_k/"
                         "top_k.segmented/scan.flat/pallas_call") == \
        "sampler/top_k/top_k.segmented/scan.flat"
    assert at.scope_path("jit(decode_loop)/while/body/closed_call/"
                         "bsd,dhk->bshk/dot_general") == ""
    # the operation itself is not a scope, even when named like one
    assert at.scope_path("jit(f)/top_k") == ""
    assert at.program_name("jit_decode_loop(123)") == "jit_decode_loop"


@pytest.fixture(scope="module")
def greedy():
    return at.attribute_file(GREEDY, spans=tr.SPANS)


def test_recorded_trace_modules_and_compiles(greedy):
    """Every op of the window finds its module's HLO; the device idles
    through the two 47 ms backend compiles (and their lowering) that the
    harness's ``drain_done`` span holds, and nowhere else compiles."""
    with open(GREEDY, "rb") as f:
        protos = at.hlo_protos(f.read())
    assert len(protos) == 34
    assert all(name.endswith(")") for name in protos)
    a = greedy
    assert a.unmapped_s == 0.0
    assert a.window_s == pytest.approx(0.411890484, abs=1e-9)
    # the unscoped programs: everything is "(no scope)"
    assert set(a.scopes["jit__unknown"]) == {""}
    assert a.scopes["jit__unknown"][""] == pytest.approx(0.199039, abs=1e-6)
    assert a.compile_idle_s == pytest.approx(0.155445617, abs=1e-9)
    assert 2 * 0.0465 < a.compile_idle_s <= 0.155446804   # union of events
    idle, cidle, n = a.idle_by_span["drain_done"]
    assert cidle == a.compile_idle_s and n == 2
    assert a.idle_by_span["outside"][1:] == [0.0, 0]
    assert a.idle_s == pytest.approx(
        sum(v[0] for v in a.idle_by_span.values()), abs=1e-12)


@pytest.fixture(scope="module")
def scoped():
    if not os.path.exists(SCOPED):
        pytest.fail(f"recorded trace missing: {SCOPED}")
    return at.attribute_file(SCOPED), tr.reduce_file(SCOPED)


def test_scoped_trace(scoped):
    """The program's names: jit_prefill, jit_admit and jit_decode_loop;
    the sampler and its top_k take device time in the loop and at
    admission; the loop's scopes add up to its program time."""
    a, s = scoped
    assert {"jit_prefill", "jit_admit", "jit_decode_loop"} <= set(a.scopes)
    loop = a.scopes["jit_decode_loop"]
    assert a.scope_s("jit_decode_loop", "sampler") > 0
    assert a.scope_s("jit_decode_loop", "sampler/top_k") > 0
    assert a.scope_s("jit_decode_loop", "sampler/top_k") == pytest.approx(
        0.324755441, abs=1e-9)
    assert a.scope_s("jit_admit", "sampler/top_k") > 0
    assert "sampler/top_k/top_k.segmented" in loop
    assert a.routes["top_k.segmented"] > a.routes["scan.batched"] > 0
    assert a.unmapped_s == 0.0
    # the module renaming leaves trace_reduce's program kinds as they were
    assert sum(loop.values()) == pytest.approx(s.program_s("loop"),
                                               rel=1e-3)
    assert sum(a.scopes["jit_prefill"].values()) == pytest.approx(
        s.program_s("prefill"), rel=1e-3)


def test_scoped_trace_idle_by_span(scoped):
    """Idle time goes to the program's spans; both compiles of the window
    fall in ``drain.collect``."""
    a, _ = scoped
    assert set(a.idle_by_span) <= set(at.PROGRAM_SPANS) | {at.OUTSIDE}
    assert a.idle_by_span["drain.collect"][2] == 2
    assert a.compile_idle_s == a.idle_by_span["drain.collect"][1] > 0
    assert a.idle_s == pytest.approx(0.198614604, abs=1e-9)


def test_command_line(capsys):
    assert at.main([SCOPED]) == 0
    out = capsys.readouterr().out
    assert "jit_decode_loop" in out and "sampler/top_k/top_k.segmented" in out
    assert "drain.collect" in out
    assert at.main([]) == 2
