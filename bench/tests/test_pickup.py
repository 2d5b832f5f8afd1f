"""A configuration, a traffic mix, a cell and a per-layer metric are taken
up by adding files and entries alone, in a copy of the benchmark: no file
that is already there is edited."""
import hashlib
import json
import os
import shutil

from test_rehearse import BENCH, ROOT, rehearse

NEW_METRIC = '''"""Engine / scheduler: admissions per second of the window."""
UNIT, SOURCE, LAYER, MOVES = "1/s", "program_counter", \\
    "engine/scheduler", "tokens_per_s"


def read(run):
    return run.counters["admissions"] / run.window_s
'''


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_taken_up(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    before = digest(tmp_path / "bench")
    b = tmp_path / "bench"

    # A configuration: minitron-4b's file under another name.
    conf = json.loads((b / "configs" / "minitron-4b.json").read_text())
    conf["model"]["name"] = "minitron-copy"
    (b / "configs" / "minitron-copy.json").write_text(json.dumps(conf))
    # A traffic mix: short greedy chat.
    mix = json.loads((b / "traffic" / "chat-sampled.json").read_text())
    mix.update(sampling={"temperature": 0.0, "top_k": 0, "top_p": 1.0,
                         "top_p_candidates": 64},
               output={"dist": "uniform", "min": 16, "max": 64})
    (b / "traffic" / "chat-greedy.json").write_text(json.dumps(mix))
    # The cell's limits and control, and a metric reader.
    (b / "cells" / "minitron-copy.chat-greedy.json").write_text(json.dumps(
        {"control": "int8", "limits": {"gap": 1.0, "bad_requests": 0}}))
    (b / "metrics" / "admissions_per_s.py").write_text(NEW_METRIC)
    # Entries in BENCHMARK.json.
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="minitron-copy",
                                file="bench/configs/minitron-copy.json"))
    spec["workloads"].append({"name": "minitron-copy.chat-greedy",
                              "config": "minitron-copy",
                              "traffic": "chat-greedy", "chips": 1,
                              "why": "pick-up test"})
    spec["per_layer"].append({"name": "admissions_per_s", "unit": "1/s",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "engine/scheduler",
                              "moves": "tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    after = digest(b)
    assert all(after[k] == v for k, v in before.items())
    line, _ = rehearse("minitron-copy.chat-greedy", trace=1, cwd=tmp_path)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["admissions_per_s"]["value"] > 0
    assert set(line["checks"]) == {"gap", "bad_requests"}
