"""From a profiler trace to the per-layer metrics that read the PROGRAM's own
names: kernel names, model scopes and the trainer's host spans.

``trace_reduce.py`` (PR 23) reduces a trace to busy time, kernel share and
the ten largest operations, under whatever names the compiler made up.  This
file reads what the program itself wrote into the trace:

- **kernel names**: every ``pl.pallas_call`` of ``ops/pallas_kernels.py``
  passes ``name=``; the TPU's ``XLA Ops`` line names the Mosaic call by it
  (``%gru_seq_bwd.1 = (...) custom-call(...)``; read on the chip, PR 25).
- **scope paths**: ``jax.named_scope`` (``encoder``, ``decoder``,
  ``readout_ce``, ``optimizer_apply``, a DSL layer's name) ends up in the
  ``op_name`` of every HLO operation, forward or ``transpose(jvp(...))``.  The
  TPU trace carries it as the stat ``tf_op`` of the operation's event
  METADATA (``jit(step)/jit(main)/transpose(jvp(encoder))/.../mul:``), not in
  the event's name (the HLO line without its metadata) and not among the
  event's own stats, which is all ``jax.profiler.ProfileData`` shows.  So
  ``op_scopes`` decodes that one table from the file's protobuf wire format
  (``XSpace.planes[].event_metadata[].stats[]``), and everything else stays
  with ``ProfileData``.  A fusion carries the ``op_name`` of its root.
- **host spans**: ``SGDTrainer`` records ``paddle_tpu.trainer.iteration``
  (one per batch), its phases and, inside ``step``, ``step.dispatch`` and
  one ``step.sync`` per blocking fetch, as ``TraceAnnotation``s on the
  thread that drives the loop: the same clock as the device's line, so a
  device-idle gap can be given to the innermost span open at that instant.

Where a reader finds the raw trace: ``facts`` carries the reduced summary,
not the path.  ``run.py`` writes the trace of a ``--trace 1`` run under
``<checkout>/.bench_out/trace/<cell>/`` seconds before the readers run, so
``trace_of(facts)`` takes the newest ``*.xplane.pb`` under
``<checkout>/.bench_out/trace/`` (a test passes ``facts["xplane"]``), parses
it once and keeps the result in ``facts`` for the other readers of the run.
A trace without the names (the parent commit's) gives every reader ``None``.

    python benchmark/trace_scopes.py <file.xplane.pb> [steps] [scope ...]

prints, per step, the kernels by name, device time by the scopes given, the
largest operations of each, and the idle time by owner.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

TRACE_ROOT = os.path.join(ROOT, ".bench_out", "trace")
WINDOW_SPAN = "bench.window"
TRAINER_PREFIX = "paddle_tpu.trainer."
SCOPE_STAT = "tf_op"


# -- the one table ProfileData does not show -----------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _map_entry(buf):
    key, value = None, b""
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def op_scopes(path: str) -> dict:
    """``{device plane: {operation's event name: its scope path}}`` from the
    ``tf_op`` stat of the planes' event metadata (xplane.proto: XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .str_value =
    5, .ref_value = 7; XStatMetadata.name = 2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, value in _fields(plane):
            if pnum == 2:
                name = bytes(value).decode()
            elif pnum == 4:
                events.append(_map_entry(value)[1])
            elif pnum == 5:
                key, meta = _map_entry(value)
                stat_names[key] = next(
                    (bytes(v).decode("utf-8", "replace")
                     for n, v in _fields(meta) if n == 2), "")
        if not name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        scopes = {}
        for meta in events:
            op, scope = "", None
            for mnum, value in _fields(meta):
                if mnum == 2:
                    op = bytes(value).decode("utf-8", "replace")
                elif mnum == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        scope = bytes(stat[5]).decode("utf-8", "replace")
                    elif 7 in stat:
                        scope = stat_names.get(stat[7], "")
            if scope:
                scopes[op] = scope
        out[name] = scopes
    return out


def scope_names(path: str) -> set:
    """The names in a scope path, without the primitive that ends it:
    ``jit(step)/transpose(jvp(encoder))/mul:`` holds ``jit``, ``step``,
    ``transpose``, ``jvp`` and ``encoder`` (a layer may be called ``mul``)."""
    return {part for part in re.split(r"[/()]+", path.rpartition("/")[0])
            if part}


# -- one parse per run ----------------------------------------------------

def newest_trace(root: str | None = None):
    found = glob.glob(os.path.join(root or TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def parse(path: str) -> dict:
    """What the readers share: per device operation its own time inside the
    window and its scope path; the device's idle gaps; the trainer thread's
    spans.  Times in nanoseconds, averaged over the devices."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, window, threads = [], None, []
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    events = trace_reduce._events(line)
                    if events:
                        devices.append((plane.name, events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        s = float(ev.start_ns)
                        window = (s, s + float(ev.duration_ns))
                    elif ev.name.startswith(TRAINER_PREFIX):
                        s = float(ev.start_ns)
                        spans.append((s, s + float(ev.duration_ns),
                                      ev.name[len(TRAINER_PREFIX):]))
                if spans:
                    threads.append(sorted(spans,
                                          key=lambda e: (e[0], -e[1])))
    if not devices:
        return {"devices": 0}
    if window is None:   # no harness around the trace: the device's extent
        window = (min(ev[0][0] for _, ev in devices),
                  max(max(e[1] for e in ev) for _, ev in devices))
    w0, w1 = window
    scopes = op_scopes(path)
    own, busy, gaps = defaultdict(float), 0.0, []
    for plane_name, events in devices:
        clipped = [(max(s, w0), min(e, w1), n)
                   for s, e, n in events if e > w0 and s < w1]
        merged = trace_reduce.union_intervals(
            [(s, e) for s, e, _ in clipped])
        busy += sum(e - s for s, e in merged)
        table = scopes.get(plane_name, {})
        for name, ns in trace_reduce.self_times(clipped).items():
            own[(name, table.get(name, ""))] += ns
        edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    # the thread that drives the loop is the one with its ``iteration``s
    trainer = max(threads, default=[], key=lambda spans: sum(
        1 for e in spans if e[2] == "iteration"))
    return {
        "devices": n, "window": window, "busy_ns": busy / n,
        "ops": [(name, scope, ns / n) for (name, scope), ns in own.items()],
        "gaps": sorted(gaps),
        "spans": [e for e in trainer if e[1] > w0 and e[0] < w1],
    }


def trace_of(facts: dict):
    """The parsed trace of this run (kept in ``facts``), or ``None``."""
    if "_trace_scopes" not in facts:
        path = facts.get("xplane") or newest_trace()
        facts["_trace_scopes"] = parse(path) if path else None
    parsed = facts["_trace_scopes"]
    return parsed if parsed and parsed["devices"] else None


# -- the reductions the readers call --------------------------------------

def kernel_ns(parsed: dict, kernels) -> float | None:
    """Own time of the Mosaic calls whose instruction name holds one of
    ``kernels``; ``None`` where the trace has no such call."""
    hits = [ns for name, _, ns in parsed["ops"]
            if trace_reduce.is_kernel(name)
            and any(k in name.split(" = ")[0] for k in kernels)]
    return sum(hits) if hits else None


def scope_ns(parsed: dict, scopes) -> float | None:
    """Own time of every operation, kernels included, whose scope path
    holds one of ``scopes``; ``None`` where none does."""
    want = set(scopes)
    hits = [ns for _, scope, ns in parsed["ops"]
            if scope and want & scope_names(scope)]
    return sum(hits) if hits else None


def innermost(spans):
    """``[(start, end, name)]``: for every instant covered by a span of the
    thread, the innermost one open (spans sorted by (start, -end))."""
    out, stack = [], []   # stack of (end, name); ``at`` = covered up to here

    def emit(t0, t1, name):
        if t1 > t0:
            out.append((t0, t1, name))

    at = None
    for s, e, name in spans:
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(at, end, top)
            at = end
        if stack:
            emit(at, s, stack[-1][1])
        at = s
        stack.append((e, name))
    while stack:
        end, top = stack.pop()
        emit(at, end, top)
        at = end
    return out


def idle_by_owner(parsed: dict) -> dict | None:
    """``{span name or "none": idle ns}``: every device-idle instant of the
    window given to the innermost trainer span open then.  Sums to the
    window less busy time.  ``None`` where the trainer recorded no span."""
    if not parsed["spans"]:
        return None
    segments = innermost(parsed["spans"])
    owner, i = defaultdict(float), 0
    for g0, g1 in parsed["gaps"]:
        while i and segments[i - 1][1] > g0:   # gaps of several devices
            i -= 1
        covered = 0.0
        while i < len(segments) and segments[i][0] < g1:
            s, e, name = segments[i]
            c = min(e, g1) - max(s, g0)
            if c > 0:
                owner[name] += c
                covered += c
            if e > g1:
                break
            i += 1
        owner["none"] += (g1 - g0) - covered
    return {k: v / parsed["devices"] for k, v in owner.items()}


def iterations(parsed: dict) -> int:
    """Iterations of the loop in the window that ran a step (the last
    ``iteration`` of a pass only finds the reader empty)."""
    return sum(1 for _, _, name in parsed["spans"]
               if name == "step.dispatch")


def report(path: str, steps: int | None = None, scopes=()) -> dict:
    """The builder's view of one trace, per step: the kernels by name, own
    device time by scope (an operation goes to the first of ``scopes`` its
    path holds, else to ``unscoped``) with the largest operations of each,
    and the idle time by owner."""
    parsed = parse(path)
    if not parsed["devices"]:
        return {"devices": 0}
    steps = steps or iterations(parsed) or 1

    def ms(ns):
        return ns / steps / 1e6

    kernels = defaultdict(float)
    groups = defaultdict(lambda: defaultdict(float))
    for name, scope, ns in parsed["ops"]:
        op = name.split(" = ")[0]
        if trace_reduce.is_kernel(name):
            kernels[op.lstrip("%").split(".")[0]] += ns
        held = scope_names(scope) if scope else ()
        groups[next((s for s in scopes if s in held), "unscoped")][op] += ns
    idle = idle_by_owner(parsed)
    w0, w1 = parsed["window"]
    return {
        "steps": steps, "window_ms": (w1 - w0) / 1e6,
        "busy_ms": ms(parsed["busy_ns"]),
        "idle_ms": ms((w1 - w0) - parsed["busy_ns"]),
        "kernel_ms": {k: ms(v) for k, v in sorted(
            kernels.items(), key=lambda kv: -kv[1])},
        "scope_ms": {g: ms(sum(ops.values())) for g, ops in groups.items()},
        "largest": {g: {op: ms(v) for op, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:6]}
            for g, ops in groups.items()},
        "idle_ms_by_owner": idle and {k: ms(v) for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "syncs": sum(1 for _, _, n in parsed["spans"]
                     if n == "step.sync") / steps,
    }


if __name__ == "__main__":
    args = sys.argv[1:]
    n = int(args[1]) if len(args) > 1 and args[1].isdigit() else None
    print(json.dumps(report(args[0], n, args[2 if n else 1:]), indent=1))
