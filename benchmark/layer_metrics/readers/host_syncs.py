"""Blocking fetches from the device a step pays: the trainer's
``paddle_tpu.trainer.step.sync`` spans in the window over its iterations
that ran a step.  ``None`` on a trace without the trainer's spans."""

from benchmark import trace_scopes


def read(facts):
    parsed = trace_scopes.trace_of(facts)
    if parsed is None:
        return None
    ran = trace_scopes.iterations(parsed)
    if not ran:
        return None
    return sum(1 for _, _, name in parsed["spans"]
               if name == "step.sync") / ran
