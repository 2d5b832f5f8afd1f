"""Reduce a profiler trace to the numbers the per-layer metrics read.

The harness wraps the measured window in a host span named ``WINDOW`` and,
in the traced run, the engine's ``_prefill``, ``_admit_fn``,
``_dispatch_loop`` and ``_drain_done`` in spans of those names (without
the underscore).  From the trace's device planes (one per chip):

* busy time -- the union of the intervals in which an operation ran on the
  chip, inside the window, averaged over the chips;
* per program -- device time of the engine's programs (the "XLA Modules"
  line).  The engine jits ``functools.partial`` objects, which the trace
  names ``jit__unknown(<hash>)``, so a module is known by its first
  top-level ``while``: the decode loop's carries the slots' ``pred``
  flags first, the prefill's layer scan an ``s32`` counter and one row of
  activations ``bf16[1,``; admission is ``jit__admit_impl``;
* per operation -- device time of each operation (the "XLA Ops" line) by
  its HLO name without the number (``fusion``, ``copy``, ...); the
  containers (``while``, ``conditional``, ``call``) count for busy time,
  not as operations;
* idle gaps -- the longest intervals with no operation on the chip, each
  named by the host span the engine was in at the gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW = "bench_window"
SPANS = ("prefill", "admit_fn", "dispatch_loop", "drain_done")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CONTAINERS = ("while", "conditional", "call")


def program_kind(module: str, ops: list) -> str:
    """Which engine program a module is: ``loop``, ``prefill``, ``admit``
    or ``other``, from its name and the ops that ran inside it."""
    if module.startswith("jit__admit_impl"):
        return "admit"
    for name in ops:
        if name.startswith("%while"):
            sig = name.split("= (", 1)[-1]
            if sig.startswith("pred["):
                return "loop"
            if sig.startswith("s32[]") and "bf16[1," in sig:
                return "prefill"
            return "other"
    return "other"


def union_length(intervals) -> int:
    """Total length covered by a set of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The idle (start, end) intervals of [lo, hi] not covered."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


class Summary:
    """What the trace said about one window."""

    def __init__(self, window_s, busy_s, programs, ops, idle_gaps):
        self.window_s = window_s
        self.busy_s = busy_s
        self.programs = programs      # program kind -> device seconds
        self.ops = ops                # op name -> device seconds
        self.idle_gaps = idle_gaps    # [(host span, seconds)], longest first
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        self.breakdown = {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": [list(g) for g in idle_gaps[:10]]}

    def program_s(self, kind: str) -> float:
        return self.programs.get(kind, 0.0)


def op_label(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.141 = bf16[...] ...``
    becomes ``fusion``, ``%while.40 = ...`` becomes ``while``."""
    head = name.split(" =", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return []


def reduce(pd) -> Summary:
    """Reduce a ``jax.profiler.ProfileData``."""
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name == WINDOW or e.name in SPANS]
        elif plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices.append(plane)
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if not win or not devices:
        raise ValueError("trace holds no window span or no TPU plane")
    lo, hi = win[0]
    spans = sorted((s, e, n) for n, s, e in host if n in SPANS)
    busy, programs, ops = 0, collections.Counter(), collections.Counter()
    idle = []
    for plane in devices:
        iv = []
        op_events = sorted(_events(plane, OPS_LINE), key=lambda ev: ev[1])
        starts = [s for _, s, _ in op_events]
        for name, s, e in op_events:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                iv.append((s, e))
                label = op_label(name)
                if label not in CONTAINERS:
                    ops[label] += (e - s) * 1e-9
        kinds = {}
        for name, s, e in _events(plane, MODULES_LINE):
            if name not in kinds:
                i = bisect.bisect_left(starts, s)
                inside = []
                while i < len(op_events) and op_events[i][1] < e:
                    inside.append(op_events[i][0])
                    i += 1
                kinds[name] = program_kind(name, inside)
            s, e = max(s, lo), min(e, hi)
            if e > s:
                programs[kinds[name]] += (e - s) * 1e-9
        busy += union_length(iv)
        for gs, ge in gaps(iv, lo, hi):
            mid = (gs + ge) / 2
            where = [n for s, e, n in spans if s <= mid < e]
            idle.append((where[-1] if where else "host", (ge - gs) * 1e-9))
    n = len(devices)
    idle.sort(key=lambda g: -g[1])
    return Summary((hi - lo) * 1e-9, busy * 1e-9 / n,
                   {k: v / n for k, v in programs.items()},
                   {k: v / n for k, v in ops.items()}, idle)


def reduce_file(path) -> Summary:
    import jax
    return reduce(jax.profiler.ProfileData.from_file(str(path)))


def reduce_dir(tdir) -> Summary:
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace file under {tdir}: {files}")
    return reduce_file(files[0])
