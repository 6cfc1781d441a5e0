"""Seconds of one phase of the program's own set-up record, or of one part of
a phase: the ``/paddle_tpu/setup/<phase>[/<part>]`` duration events the
program publishes through ``jax.monitoring`` when its first training
iteration has completed (before the window), which ``Context.on_duration``
keeps with JAX's own in ``facts["setup_durations"]``.  A program that
publishes none (the parent of the PR that added the record) gives ``None``."""


def read(facts, events):
    seen = facts.get("setup_durations") or {}
    if not any(e in seen for e in events):
        return None
    return sum(seen.get(e, 0.0) for e in events)
