"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics and ``breakdown`` read.  Nothing but ``jax.profiler.ProfileData``.

A device plane is one whose name starts with ``/device:TPU:``.  Its line
``XLA Ops`` holds one event per executed operation (a ``while`` holds its
body's operations nested inside it, so busy time is the UNION of the
intervals, and an operation's own time is its duration less its children's).
Host planes hold one line per thread; the benchmark's own
``jax.profiler.TraceAnnotation`` spans (names starting ``bench.``) give idle
gaps an owner.

    python benchmark/trace_reduce.py <file.xplane.pb>     prints the summary
    python benchmark/trace_reduce.py <file.xplane.pb> --dump   and the layout
"""

from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
#: an operation is a Mosaic (Pallas) kernel when its HLO line says custom
#: call; XLA's own fusions never do
KERNEL_MARKS = ("custom-call(", "tpu_custom_call")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    """[(start_ns, end_ns, name)] of one line, by start."""
    out = []
    for ev in line.events:
        start = float(ev.start_ns)
        out.append((start, start + float(ev.duration_ns), ev.name))
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def union_intervals(intervals):
    """Sorted, merged [(start, end)]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def self_times(events):
    """name -> own nanoseconds (duration less nested children), from events
    of one line sorted by (start, -end)."""
    out = defaultdict(float)
    stack = []   # [end, name, duration, children's nanoseconds]
    for s, e, name in events:
        while stack and stack[-1][0] <= s:
            _, n, dur, kids = stack.pop()
            out[n] += dur - kids
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    for _, n, dur, kids in stack:
        out[n] += dur - kids
    return out


def is_kernel(name: str) -> bool:
    """The ``XLA Ops`` line names an operation by its whole HLO line, so a
    Mosaic kernel shows as ``... custom-call(...), custom_call_target=
    "tpu_custom_call"``."""
    text = name.lower()
    return any(mark in text for mark in KERNEL_MARKS)


def reduce_trace(path: str, window=None, window_span=None) -> dict:
    """The summary of one trace.  ``window``: (start_ns, end_ns) on the
    trace's clock to clip to; or ``window_span``, the name of the host span
    that is the window (idle at its two ends then counts); default, from the
    first to the last device operation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append((plane.name, _events(line)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[2].startswith(SPAN_PREFIX)]
    devices = [(n, ev) for n, ev in devices if ev]
    if window is None and window_span is not None:
        edges = [(s, e) for s, e, n in spans if n == window_span]
        if not edges:
            raise ValueError(f"no span {window_span!r} in {path}")
        window = (min(s for s, _ in edges), max(e for _, e in edges))
    spans = [e for e in spans if e[2] != window_span]
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "kernel_s": 0.0,
                "device_ops": [], "kernel_ops": [], "idle_gaps": []}
    if window is None:
        window = (min(ev[0][0] for _, ev in devices),
                  max(max(e[1] for e in ev) for _, ev in devices))
    w0, w1 = window
    busy, kernel_ns, own_total = [], 0.0, defaultdict(float)
    gaps = []
    for _, events in devices:
        clipped = [(max(s, w0), min(e, w1), n)
                   for s, e, n in events if e > w0 and s < w1]
        merged = union_intervals([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        for name, ns in self_times(clipped).items():
            own_total[name] += ns
            if is_kernel(name):
                kernel_ns += ns
        edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    busy_ns = sum(busy) / n_dev
    owner = defaultdict(float)
    for g0, g1 in gaps:
        best, cover = "unattributed", 0.0
        for s, e, name in spans:
            c = min(e, g1) - max(s, g0)
            if c > cover:
                best, cover = name, c
        owner[best] += (g1 - g0)
    short = defaultdict(float)   # the trace names an op by its whole HLO line
    for name, ns in own_total.items():
        short[name.split(" = ")[0][:120]] += ns
    top = sorted(short.items(), key=lambda kv: -kv[1])[:10]
    kernels = sorted(((n.split(" = ")[0][:120], ns)
                      for n, ns in own_total.items() if is_kernel(n)),
                     key=lambda kv: -kv[1])[:10]
    idle = sorted(owner.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": n_dev,
        "t0_ns": w0,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "kernel_s": kernel_ns / n_dev / 1e9,
        "device_ops": [[n, ns / n_dev / 1e9] for n, ns in top],
        "kernel_ops": [[n, ns / n_dev / 1e9] for n, ns in kernels],
        "idle_gaps": [[n, ns / n_dev / 1e9] for n, ns in idle],
    }


def dump_layout(path: str, limit: int = 12) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = _events(line)
            print("  LINE", repr(line.name), len(events), "events")
            for s, e, name in events[:limit]:
                print("     ", name[:100], round((e - s) / 1e3, 2), "us")


if __name__ == "__main__":
    if "--dump" in sys.argv:
        dump_layout(sys.argv[1])
    print(json.dumps(reduce_trace(sys.argv[1])))
