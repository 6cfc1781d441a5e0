"""Milliseconds of a step in which the device was idle, by the part of the
host's turn-around that was open on the loop's thread (``part``: one of
``trace_spans.PARTS``, which sum to ``idle_ms_per_step.loop`` of the same
trace), or the two overlays that are not part of that sum: ``sync_head``
and ``prefetch_overlap``.  ``None`` on a trace without the names of PR 38
(``step.rng`` and the rest), as the parent commit's is."""

from benchmark import trace_spans


def read(facts, part):
    found = trace_spans.spans_of(facts)
    if found is None or not facts.get("steps"):
        return None
    parsed, spans = found
    if part == "sync_head":
        ns = trace_spans.sync_head_ns(parsed, spans)
    elif part == "prefetch_overlap":
        ns = trace_spans.prefetch_overlap_ns(parsed, spans)
    else:
        owners = trace_spans.idle_owners(parsed, spans)
        ns = None if owners is None else trace_spans.idle_parts(owners)[part]
    return None if ns is None else ns / facts["steps"] / 1e6
