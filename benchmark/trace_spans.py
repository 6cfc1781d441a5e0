"""The parts of ``idle_ms_per_step.loop``: what ``trace_scopes.parse`` does
not keep of the host's spans, read in a pass of its own.

``trace_scopes.parse`` keeps a span's name and one thread.  Since PR 38 the
loop names every line of its turn-around between two steps
(docs/observability.md "Names on the device trace"): ``step.rng``,
``step.post``, ``step.counters``, ``poll``, ``extras``, ``close`` beside
``step.sync`` with its ``reason``, and the ``batch-prefetch`` thread records
``paddle_tpu.data.prefetch.prepare`` / ``.h2d`` / ``.put``.  This file reads
the ``reason`` and the second thread, and takes the window, the device's idle
gaps and the count of devices from ``trace_scopes.trace_of(facts)``, so both
readers cut the same idle time: the six ``PARTS`` sum to what
``idle_ms_per_step.loop`` reads on the same trace.

A trace without the new names (the parent commit's, which has ``step.sync``
and no ``step.rng``) gives every reader ``None``.

    python benchmark/trace_spans.py <file.xplane.pb> [steps]

prints the parts, ``sync_head`` and ``prefetch_overlap`` in ms a step, for a
cell whose metrics do not list them.
"""

from __future__ import annotations

import json
import os
import sys
from bisect import bisect_right

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce, trace_scopes  # noqa: E402

PREFETCH_PREFIX = "paddle_tpu.data.prefetch."
SYNC = "step.sync"
#: the span whose presence says the trace has this vocabulary
MARKER = "step.rng"
#: what ``idle_ms_per_step.loop`` leaves out (its ``owners``): not parts
NOT_LOOP = ("step.dispatch", "callback", "data_wait", "prepare", "h2d")
#: part -> the owners it sums; ``unnamed`` is every other owner of the
#: loop's idle time (``iteration``, ``step``, no span, and a span that a
#: later PR adds without a part), so the six always sum to ``.loop``
PARTS = {
    "sync_guard": (SYNC + ":guard", SYNC + ":amp"),
    "sync_loss": (SYNC + ":loss",),
    "rng": ("step.rng",),
    "step_host": ("step.post", "step.counters"),
    "bookkeeping": ("poll", "extras", "close"),
    "unnamed": (),
}


def host_spans(path: str) -> dict:
    """``{"loop": [(start, end, owner)], "prefetch": [(start, end, name)]}``
    in ns: the ``paddle_tpu.trainer.*`` spans of the thread that drives the
    loop (chosen as ``trace_scopes.parse`` chooses it: the one with the most
    ``iteration``s), a ``step.sync`` as ``step.sync:<reason>``; and the
    ``paddle_tpu.data.prefetch.*`` spans of every other thread."""
    from jax.profiler import ProfileData

    threads, prefetch = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                name = ev.name
                if name.startswith(trace_scopes.TRAINER_PREFIX):
                    name = name[len(trace_scopes.TRAINER_PREFIX):]
                    if name == SYNC:
                        reason = dict(ev.stats).get("reason")
                        name = f"{SYNC}:{reason}" if reason else SYNC
                    bucket = spans
                elif name.startswith(PREFETCH_PREFIX):
                    name = name[len(PREFETCH_PREFIX):]
                    bucket = prefetch
                else:
                    continue
                s = float(ev.start_ns)
                bucket.append((s, s + float(ev.duration_ns), name))
            if spans:
                threads.append(sorted(spans, key=lambda e: (e[0], -e[1])))
    loop = max(threads, default=[], key=lambda spans: sum(
        1 for e in spans if e[2] == "iteration"))
    return {"loop": loop, "prefetch": sorted(prefetch)}


def spans_of(facts: dict):
    """``(parsed, spans)`` of this run: ``trace_scopes``' parse and this
    file's pass over the same file, both threads' spans cut to the window
    as ``parse`` cuts the loop's; ``None`` where there is no trace or it
    lacks the new names."""
    parsed = trace_scopes.trace_of(facts)
    if parsed is None:
        return None
    if "_trace_spans" not in facts:
        path = facts.get("xplane") or trace_scopes.newest_trace()
        facts["_trace_spans"] = host_spans(path) if path else None
    spans = facts["_trace_spans"]
    if not spans or not any(e[2] == MARKER for e in spans["loop"]):
        return None
    w0, w1 = parsed["window"]
    return parsed, {thread: [e for e in held if e[1] > w0 and e[0] < w1]
                    for thread, held in spans.items()}


def idle_owners(parsed: dict, spans: dict):
    """``trace_scopes.idle_by_owner`` with a fetch under its reason:
    ``{owner or "none": idle ns}``, the same gaps given to the same spans;
    ``None`` where the window holds none of the loop's spans."""
    return trace_scopes.idle_by_owner(dict(parsed, spans=spans["loop"]))


def idle_parts(owners: dict) -> dict:
    """``{part: idle ns}`` of the six ``PARTS`` from ``idle_owners``: every
    device-idle instant that ``idle_ms_per_step.loop`` counts, given to the
    part that holds the innermost span open on the loop's thread then."""
    part_of = {owner: part for part, held in PARTS.items() for owner in held}
    out = dict.fromkeys(PARTS, 0.0)
    for owner, ns in owners.items():
        if owner not in NOT_LOOP:
            out[part_of.get(owner, "unnamed")] += ns
    return out


def sync_head_ns(parsed: dict, spans: dict) -> float:
    """Idle at the HEAD of a ``step.sync``: where the device is idle at the
    instant the fetch begins and an operation starts on it before the fetch
    returns, the time from the one to the other (the device had not begun
    the step: the launch is what the host waits for).  Idle after the
    device's last operation, up to the span's end, is the tail (the flag is
    on its way) and is not counted; nor is a span in which nothing starts."""
    gaps = parsed["gaps"]
    starts = [g[0] for g in gaps]
    reach = max((g1 - g0 for g0, g1 in gaps), default=0.0)
    head = 0.0
    for s, e, name in spans["loop"]:
        if not name.startswith(SYNC):
            continue
        s = max(s, parsed["window"][0])
        i = bisect_right(starts, s) - 1
        # gaps of several devices interleave: look back as far as one reaches
        while i >= 0 and gaps[i][0] >= s - reach:
            g0, g1 = gaps[i]
            if g0 <= s < g1 < e:
                head += g1 - s
            i -= 1
    return head / parsed["devices"]


def prefetch_overlap_ns(parsed: dict, spans: dict):
    """Idle, under any owner, while the prefetch thread was inside
    ``prepare`` or ``h2d``; ``None`` where no such span is in the window."""
    busy = trace_reduce.union_intervals(
        [(s, e) for s, e, name in spans["prefetch"]
         if name in ("prepare", "h2d")])
    if not busy:
        return None
    ends = [e for _, e in busy]
    overlap = 0.0
    for g0, g1 in parsed["gaps"]:
        i = bisect_right(ends, g0)
        while i < len(busy) and busy[i][0] < g1:
            overlap += min(busy[i][1], g1) - max(busy[i][0], g0)
            i += 1
    return overlap / parsed["devices"]


def report(path: str, steps: int | None = None) -> dict:
    """The builder's view of one trace, in ms a step (``steps``: the
    window's, else the iterations that ran a step)."""
    facts = {"xplane": path}
    found = spans_of(facts)
    if found is None:
        return {"parts_ms": None}
    parsed, spans = found
    steps = steps or trace_scopes.iterations(parsed) or 1

    def ms(ns):
        return None if ns is None else ns / steps / 1e6

    owners = idle_owners(parsed, spans) or {}
    parts = {k: ms(v) for k, v in idle_parts(owners).items()}
    thread = {}     # the second thread's own time, idle device or not
    for s, e, name in spans["prefetch"]:
        thread[name] = thread.get(name, 0.0) + e - s
    longest = {}    # a span that is long once is not a cost a step
    for s, e, name in spans["loop"]:
        longest[name] = max(longest.get(name, 0.0), e - s)
    return {"steps": steps, "parts_ms": parts,
            "loop_ms": sum(parts.values()),
            "sync_head_ms": ms(sync_head_ns(parsed, spans)),
            "prefetch_overlap_ms": ms(prefetch_overlap_ns(parsed, spans)),
            "idle_ms_by_owner": {k: ms(v) for k, v in sorted(
                owners.items(), key=lambda kv: -kv[1])},
            "prefetch_thread_ms": {k: ms(v) for k, v in thread.items()},
            "longest_span_ms": {k: v / 1e6 for k, v in longest.items()}}


if __name__ == "__main__":
    args = sys.argv[1:]
    print(json.dumps(report(args[0], int(args[1]) if len(args) > 1
                            else None), indent=1))
