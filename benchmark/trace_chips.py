"""A trace of several chips, chip by chip.  ``trace_reduce`` and
``trace_scopes`` average every time over the device planes, which is what a
step costs; the metrics of the layer ``parallel`` need the planes apart: how
long each chip was busy, and what each spent in collective operations.

A collective is an operation of the ``XLA Ops`` line whose HLO opcode is
``all-reduce``, ``reduce-scatter``, ``all-gather``, ``collective-permute`` or
``all-to-all``, or one of the ``-start`` / ``-done`` halves the compiler
splits an asynchronous one into (on the TPU also the fusions it names
``async-collective-start`` / ``-done``, which wrap a collective inside).

    python benchmark/trace_chips.py <file.xplane.pb> [steps]
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce, trace_scopes  # noqa: E402

KINDS = ("all-reduce", "reduce-scatter", "all-gather", "collective-permute",
         "all-to-all", "async-collective")
_KIND = "|".join(KINDS)
#: the opcode of the HLO line, ``... all-reduce-start(...``, and where the
#: trace names an operation by its instruction only, ``%all-reduce-start.3``
_OPCODE = re.compile(rf"[ )]({_KIND})(-start|-done)?\(")
_INSTRUCTION = re.compile(rf"^%?({_KIND})(-start|-done)?(\.\d+)*$")


def collective(name: str):
    """``(kind, half)`` of a collective's event, ``half`` one of ``""``,
    ``"-start"``, ``"-done"``; ``None`` for any other operation."""
    head, eq, line = name.partition(" = ")
    m = ((_OPCODE.search(line) if eq else None)
         or _INSTRUCTION.match(head.strip()))
    return (m.group(1), m.group(2) or "") if m else None


def parse(path: str) -> dict:
    """Per chip, inside the window (the span ``bench.window``, else the
    devices' extent): ``busy_ns`` (union of the operations' intervals),
    ``collective_ns`` (own time of the collectives, ``by_kind`` the same by
    opcode) and ``exposed_ns``; see ``exposed_ns_of``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, window = [], None
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    events = trace_reduce._events(line)
                    if events:
                        planes.append((plane.name, events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_scopes.WINDOW_SPAN:
                        s = float(ev.start_ns)
                        window = (s, s + float(ev.duration_ns))
    if not planes:
        return {"chips": []}
    if window is None:
        window = (min(ev[0][0] for _, ev in planes),
                  max(max(e[1] for e in ev) for _, ev in planes))
    chips = [dict(chip_times(events, window), plane=name)
             for name, events in sorted(planes)]
    return {"window": window, "chips": chips}


def chip_times(events, window) -> dict:
    """One chip's ``busy_ns``, ``collective_ns``, ``by_kind`` and
    ``exposed_ns`` from the events ``(start, end, name)`` of its ``XLA Ops``
    line (sorted by start, a nesting event before what it holds), clipped
    to ``window``."""
    w0, w1 = window
    clipped = [(max(s, w0), min(e, w1), n)
               for s, e, n in events if e > w0 and s < w1]
    merged = trace_reduce.union_intervals([(s, e) for s, e, _ in clipped])
    by_kind = {}
    for op, ns in trace_reduce.self_times(clipped).items():
        kind = "".join(collective(op) or ())
        if kind:
            by_kind[kind] = by_kind.get(kind, 0.0) + ns
    return {"busy_ns": sum(e - s for s, e in merged),
            "collective_ns": sum(by_kind.values()), "by_kind": by_kind,
            "exposed_ns": exposed_ns_of(by_kind)}


def exposed_ns_of(by_kind: dict) -> float:
    """Of one chip's collective time (own time by opcode), the part in
    which the chip runs nothing else.  The ``XLA Ops`` line is the chip's
    one stream of operations, so while a synchronous collective or a
    ``-done`` half (the wait for what its ``-start`` set going) has the
    line to itself, nothing else runs: their own time is exposed.  The own
    time of a ``-start`` half is the cost of issuing it and is not: the
    chip goes on with other operations while the collective is in flight,
    and that in-flight time, hidden behind them, is on no metric."""
    return sum(ns for kind, ns in by_kind.items()
               if not kind.endswith("-start"))


def chips_of(facts: dict):
    """The per-chip parse of this run's trace (kept in ``facts``), or
    ``None`` where there is no trace or no device plane in it."""
    if "_trace_chips" not in facts:
        path = facts.get("xplane") or trace_scopes.newest_trace()
        facts["_trace_chips"] = parse(path) if path else None
    parsed = facts["_trace_chips"]
    return parsed["chips"] if parsed and parsed["chips"] else None


if __name__ == "__main__":
    out = parse(sys.argv[1])
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    for chip in out["chips"]:
        for key in ("busy_ns", "collective_ns", "exposed_ns"):
            chip[key[:-3] + "_ms_per_step"] = chip.pop(key) / steps / 1e6
        chip["by_kind"] = {k: ns / steps / 1e6
                           for k, ns in chip["by_kind"].items()}
    print(json.dumps(out, indent=1))
