"""Attribute a traced window to the program's own names.

Two readings of the trace directory that ``trace_reduce`` reduces:

* device time per scope -- each operation on a device's "XLA Ops" line
  belongs to the module ("XLA Modules" line) whose execution interval holds
  it, and takes its ``op_name`` from that module's HLO proto.  The trace
  keeps one HLO proto per module on its ``/host:metadata`` plane, which
  ``jax.profiler.ProfileData`` does not expose; it is decoded here from the
  protobuf wire format.  The named scopes in an ``op_name`` -- the
  program's (``sampler``, ``top_k``) and a primitive's route
  (``<primitive>.<layout>``, such as ``top_k.segmented``) -- make its scope
  path, and device time is summed per program and scope path.  Containers
  (``while``, ``conditional``, ``call``) hold other operations and are not
  counted.
* idle time by host activity -- the device's idle intervals inside the
  window (as ``trace_reduce`` finds them), intersected with (a) the union
  of JAX's compile events (``COMPILE_EVENTS``: tracing, lowering, compiling)
  and (b) the program's host spans, each idle instant going to the
  innermost span over it.

Run on a trace file or directory, it prints both as tables::

    python bench/attribution.py <trace.xplane.pb | trace directory>

``bench/harness.py`` does not call it yet: its traced run removes the
trace directory before the metric readers run, so no metric reads these
numbers until ``measure()`` calls ``attribute_dir``.
``bench/tests/record_scoped_trace.py`` records a small trace to read.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import sys

import trace_reduce as tr

PROGRAM_SCOPES = ("sampler", "top_k")
ROUTE = re.compile(r"^[a-z_]+\.(flat|batched|segmented|sharded)$")
PROGRAM_SPANS = ("admit", "dispatch", "drain", "drain.poll", "drain.collect")
COMPILE_EVENTS = ("trace_to_jaxpr_dynamic", "lower_sharding_computation",
                  "backend_compile_and_load")
COMPILE = "backend_compile_and_load"     # one per program compiled
CONTAINER_OPCODES = ("while", "conditional", "call")
METADATA_PLANE = "/host:metadata"
OUTSIDE = "outside"


# ---------------------------------------------------------------------------
# Protobuf wire format: just the fields read here.
# ---------------------------------------------------------------------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map entries: key
# = 1, value = 2), stat_metadata = 5 (likewise); XEventMetadata: name = 2,
# stats = 5; XStatMetadata: name = 2; XStat: metadata_id = 1, bytes_value
# = 6.  HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto.instructions = 2; HloInstructionProto: name = 1,
# opcode = 2, metadata = 7; OpMetadata.op_name = 2.


def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field, raw bytes for fixed ones."""
    buf = memoryview(buf)
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        wire = tag & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + n], pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield tag >> 3, value


def _first(buf, number, default=None):
    for n, v in fields(buf):
        if n == number:
            return v
    return default


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def hlo_protos(xspace: bytes) -> dict:
    """Module name (``jit_f(<fingerprint>)``) -> serialized HloProto, from
    the trace's metadata plane."""
    out = {}
    for n, plane in fields(xspace):
        if n != 1 or _text(_first(plane, 2, b"")) != METADATA_PLANE:
            continue
        stat_names, events = {}, []
        for m, v in fields(plane):
            if m == 5:
                entry = dict(fields(v))
                stat_names[entry.get(1, 0)] = _text(
                    _first(entry.get(2, b""), 2, b""))
            elif m == 4:
                events.append(_first(v, 2, b""))
        for ev in events:
            name, proto = None, None
            for m, v in fields(ev):
                if m == 2:
                    name = _text(v)
                elif m == 5:
                    stat = dict(fields(v))
                    if stat_names.get(stat.get(1)) == "Hlo Proto":
                        proto = stat.get(6)
            if name and proto is not None:
                out[name] = proto
    return out


def hlo_ops(proto) -> dict:
    """Instruction name -> (opcode, op_name) of every computation of an
    HloProto."""
    out = {}
    for comp in (v for n, v in fields(_first(proto, 1, b"")) if n == 3):
        for n, inst in fields(comp):
            if n != 2:
                continue
            name = opcode = op_name = ""
            for m, v in fields(inst):
                if m == 1:
                    name = _text(v)
                elif m == 2:
                    opcode = _text(v)
                elif m == 7:
                    op_name = _text(_first(v, 2, b""))
            out[name] = (opcode, op_name)
    return out


# ---------------------------------------------------------------------------
# Intervals.
# ---------------------------------------------------------------------------


def merge(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def intersect(a, b) -> list:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def innermost(spans, lo, hi) -> list:
    """Cut [lo, hi] into contiguous ``(start, end, label)`` pieces, each
    labelled by the innermost of the nested ``(start, end, name)`` spans
    over it, or ``OUTSIDE``."""
    pieces, stack, t = [], [], lo

    def advance(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end
        if x > t:
            pieces.append((t, x, stack[-1][1] if stack else OUTSIDE))
            t = x

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            advance(s)
            stack.append((e, name))
    advance(hi)
    return pieces


def by_label(intervals, pieces) -> collections.Counter:
    """Length of sorted, disjoint intervals inside each piece's label."""
    out, i = collections.Counter(), 0
    for s, e in intervals:
        while i < len(pieces) and pieces[i][1] <= s:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < e:
            ps, pe, label = pieces[j]
            out[label] += min(e, pe) - max(s, ps)
            j += 1
    return out


def label_at(pieces, t) -> str:
    starts = [p[0] for p in pieces]
    i = bisect.bisect_right(starts, t) - 1
    return pieces[i][2] if 0 <= i and t < pieces[i][1] else OUTSIDE


# ---------------------------------------------------------------------------
# The attribution.
# ---------------------------------------------------------------------------


def scope_path(op_name: str) -> str:
    """The named scopes of an ``op_name``, outermost first: its program
    scopes and routes, without its last part (the operation itself)."""
    parts = op_name.split("/")[:-1]
    return "/".join(p for p in parts if p in PROGRAM_SCOPES or ROUTE.match(p))


def program_name(module: str) -> str:
    """``jit_decode_loop(123)`` -> ``jit_decode_loop``."""
    return module.split("(", 1)[0]


class Attribution:
    """What the program's names say about one traced window (seconds,
    averaged over the chips)."""

    def __init__(self, window_s, idle_s, compile_idle_s, scopes, routes,
                 idle_by_span, unmapped_s):
        self.window_s = window_s
        self.idle_s = idle_s
        self.compile_idle_s = compile_idle_s
        self.scopes = scopes              # program -> scope path -> s
        self.routes = routes              # innermost route -> s
        self.idle_by_span = idle_by_span  # span -> [idle s, compile-idle
        #                                   s, compiles started]
        self.unmapped_s = unmapped_s      # ops whose module had no HLO

    def scope_s(self, program: str, scope: str) -> float:
        """Device time of ``program``'s ops under the scope path
        ``scope`` (a prefix of their path, whole parts)."""
        return sum(s for path, s in self.scopes.get(program, {}).items()
                   if path == scope or path.startswith(scope + "/"))

    def tables(self) -> str:
        lines = ["attribution: device ms by program and scope path"]
        for prog in sorted(self.scopes):
            for path, s in sorted(self.scopes[prog].items(),
                                  key=lambda kv: -kv[1]):
                lines.append(f"  {prog:<24} {path or '(no scope)':<56} "
                             f"{1e3 * s:12.3f}")
        lines.append("attribution: device ms by innermost route")
        for route, s in sorted(self.routes.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {route:<32} {1e3 * s:12.3f}")
        lines.append(f"attribution: idle s / compile-idle s / compiles by "
                     f"innermost span (window {self.window_s!r} s, idle "
                     f"{self.idle_s!r} s, compile-idle "
                     f"{self.compile_idle_s!r} s)")
        for span, (idle, cidle, n) in sorted(self.idle_by_span.items(),
                                             key=lambda kv: -kv[1][0]):
            lines.append(f"  {span:<16} {idle:12.6f} {cidle:12.6f} {n:6d}")
        return "\n".join(lines)


def attribute(xspace: bytes, spans=PROGRAM_SPANS) -> Attribution:
    """Attribute one serialized XSpace holding ``trace_reduce.WINDOW``."""
    import jax
    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    host, devices = [], []
    wanted = set(spans) | set(COMPILE_EVENTS) | {tr.WINDOW}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name in wanted]
        elif plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices.append(plane)
    win = [(s, e) for n, s, e in host if n == tr.WINDOW]
    if not win or not devices:
        raise ValueError("trace holds no window span or no TPU plane")
    lo, hi = win[0]
    pieces = innermost([(s, e, n) for n, s, e in host if n in spans], lo, hi)
    compiling = merge((max(s, lo), min(e, hi)) for n, s, e in host
                      if n in COMPILE_EVENTS and e > lo and s < hi)
    protos, ops_of = hlo_protos(xspace), {}
    scopes = collections.defaultdict(collections.Counter)
    routes, span_idle = collections.Counter(), collections.Counter()
    span_cidle = collections.Counter()
    idle = cidle = unmapped = 0
    for plane in devices:
        mods = sorted((s, e, n) for n, s, e in tr._events(plane,
                                                          tr.MODULES_LINE))
        starts = [m[0] for m in mods]
        busy = []
        for name, s, e in tr._events(plane, tr.OPS_LINE):
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            busy.append((s, e))
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2] if i >= 0 and s < mods[i][1] else None
            if module not in ops_of:
                ops_of[module] = (hlo_ops(protos[module])
                                  if module in protos else None)
            table = ops_of[module]
            if table is None:
                unmapped += e - s
                continue
            opcode, op_name = table.get(
                name.split(" =", 1)[0].lstrip("%"), ("", ""))
            if opcode in CONTAINER_OPCODES:
                continue
            path = scope_path(op_name)
            scopes[program_name(module)][path] += e - s
            route = [p for p in path.split("/") if ROUTE.match(p)]
            if route:
                routes[route[-1]] += e - s
        gaps = tr.gaps(busy, lo, hi)
        idle += sum(ge - gs for gs, ge in gaps)
        over = intersect(gaps, compiling)
        cidle += sum(ge - gs for gs, ge in over)
        span_idle.update(by_label(gaps, pieces))
        span_cidle.update(by_label(over, pieces))
    compiles = collections.Counter(label_at(pieces, s) for n, s, _ in host
                                   if n == COMPILE and lo <= s < hi)
    k = 1e-9 / len(devices)
    table = {label: [span_idle[label] * k, span_cidle[label] * k,
                     compiles[label]]
             for label in set(span_idle) | set(compiles)}
    return Attribution(
        (hi - lo) * 1e-9, idle * k, cidle * k,
        {p: {path: v * k for path, v in c.items()} for p, c in scopes.items()},
        {r: v * k for r, v in routes.items()}, table, unmapped * k)


def attribute_file(path, spans=PROGRAM_SPANS) -> Attribution:
    with open(path, "rb") as f:
        return attribute(f.read(), spans)


def attribute_dir(tdir, spans=PROGRAM_SPANS) -> Attribution:
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace file under {tdir}: {files}")
    return attribute_file(files[0], spans)


def main(argv) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[0]
    a = attribute_dir(path) if os.path.isdir(path) else attribute_file(path)
    print(a.tables())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
