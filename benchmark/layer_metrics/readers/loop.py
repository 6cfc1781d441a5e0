"""A looped stack's work counts and what is read against them (a stack whose
layers run ``total_ut_steps`` times over one set of weights, an exit after
every pass).

``kind="expected_exit"``: the pass after which a token leaves, on average,
by the exit distribution the model's own gate gives: ``sum_t t x (mass of
exit t) / sum_t (mass of exit t)`` from the registry's
``loop_exit_mass{step}`` (each exit's probability summed over the real tokens
of every step so far, fed from the step's extra outputs): between 1 and the
number of passes; the passes an early-exit server would run.

``kind="loop_attn_core" | "exit_heads"``: the share of its roofline that a
part of the step reaches: the least time the chip could take for the work,
max(operations / peak FLOP/s, bytes / peak bytes/s) from ``peaks.json``,
over the own device time of the scopes given.  The work functions read the
configuration and the traffic, never the implementation, and are UNDER-counts
of what runs (an exit's forward product is made again when its block is
recomputed; whole blocks of the triangle are visited and masked inside): a
share over 100% would mean work counted that was not done.

``None`` where the trace has no such scope, the registry no such counter, or
the configuration no ``total_ut_steps`` (the parent commit's run)."""

import functools
import os

from benchmark import manifest, trace_scopes


@functools.lru_cache(maxsize=None)
def _roofline():
    return manifest.load_module(os.path.join(
        manifest.BENCH, "layer_metrics", "readers", "roofline.py"),
        "bench_reader_roofline")


def loop_attention_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the attention cores of EVERY pass:
    ``roofline.py``'s count of the causal triangle over the layers built (2
    products forward and 5 backward, 2 x head_dim an element at or under the
    diagonal, per query head; q, k, v, the output and their gradients moved
    once a pass in bf16), times the passes: a layer that runs
    ``total_ut_steps`` times does its work as often."""
    ops, nbytes = _roofline().attention_work(cfg, traffic)
    return float(cfg["total_ut_steps"] * ops), float(
        cfg["total_ut_steps"] * nbytes)


def exit_heads_work(cfg: dict, traffic: dict):
    """(operations, bytes) a step of the exits' head and cross-entropy: an
    exit a pass, each 3 products of 2 x tokens x hidden x vocabulary (the
    logits, the states' gradient, the head's gradient; the forward product
    that a recomputed exit makes a second time is not counted); the head
    read once forward and once backward in bf16 and its gradient written
    once in float32, the logits written and read once in bf16, the states in
    and their gradient out."""
    N = traffic["batch"] * traffic["seq_len"]
    D, V, R = cfg["hidden_size"], cfg["vocab_size"], cfg["total_ut_steps"]
    ops = R * 3 * 2 * N * D * V
    nbytes = R * (D * V * (2 + 2 + 4) + N * V * (2 + 2) + N * D * (2 + 2 + 4))
    return float(ops), float(nbytes)


WORK = {"loop_attn_core": loop_attention_work, "exit_heads": exit_heads_work}


def expected_exit_step():
    """From the registry (this process's)."""
    try:
        from paddle_tpu.obs import get_registry
    except ImportError:
        return None
    series = get_registry().snapshot().get("loop_exit_mass", {}).get(
        "series", [])
    mass = {int(s["labels"]["step"]): s["value"] for s in series}
    total = sum(mass.values())
    if not total:
        return None
    return sum(step * m for step, m in mass.items()) / total


def read(facts, kind, scopes=()):
    cfg, traffic = facts.get("config"), facts.get("traffic")
    if not cfg or not traffic or "total_ut_steps" not in cfg:
        return None
    if kind == "expected_exit":
        return expected_exit_step()
    if kind not in WORK:
        raise ValueError(f"no work function for {kind!r}")
    parsed, steps = trace_scopes.trace_of(facts), facts.get("steps")
    if parsed is None or not steps:
        return None
    ns = trace_scopes.scope_ns(parsed, scopes)
    if not ns:
        return None
    ops, nbytes = WORK[kind](cfg, traffic)
    peaks = facts["peaks"]
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / steps / 1e9)
