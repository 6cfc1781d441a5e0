"""ReLU-gated experts' work counts and what is read against them (a model
whose experts are ``W_2 (relu(W_1 x) * W_3 x)``; the configuration says so
under ``assumed`` / ``experts`` and names the width ``moe_ffn_hidden_size``).

``kind="gate_zero_share"``: of the hidden units of the assignments the
experts held computed, the share the ReLU gate made exactly zero, in
percent, from the program's counters: the registry's ``moe_gate_zero_units``
over ``moe_assignments x moe_ffn_hidden_size``, both summed over the layers
and every step so far.  What a later kernel could skip: a zero hidden unit
needs no ``W_3`` column, no ``W_2`` row and nothing backward.

``kind="moe_reglu"``: the share of its roofline that the experts' grouped
products reach (scope ``moe_experts`` inside ``moe<i>``: ``moe_gmm`` /
``moe_tgmm`` and the gate between them): the least time the chip could take
for the scope's work, max(operations / peak FLOP/s, bytes / peak bytes/s)
from ``peaks.json``, over the scope's own device time in the trace.  The
work function reads the configuration, the traffic and the counter of
assignments, never the implementation, and is an UNDER-count of what runs
(rows padded to whole tiles, the forward products a recomputed layer makes a
second time and the hidden units a ReLU zeroed, which the kernels multiply
all the same, are left as they are: the first two not counted, the last
counted whole): a share over 100% would mean work counted that was not done.

``None`` where the trace has no such scope, the run or the registry no such
counter, or the configuration no ``moe_ffn_hidden_size`` (the parent
commit's run, another configuration's)."""

from benchmark import trace_scopes


def moe_reglu_work(cfg: dict, expert_load: dict, steps: int):
    """(operations, bytes) a step of the gated experts' grouped products: per
    assignment computed 3 products forward (``W_1``, ``W_3``, ``W_2``) and 6
    backward (da, dW2, dW1, dW3 and the two halves of dx), each 2 x D x F;
    the three matrices of every expert held read once forward and once
    backward and their gradients written once, float32; bf16 rows in and out
    of each product."""
    D, F = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    ops = nbytes = 0.0
    for counts in expert_load.values():
        rows = sum(counts) / steps
        ops += rows * 9 * 2 * D * F
        nbytes += 3 * 3 * len(counts) * D * F * 4
        nbytes += rows * 2 * 9 * (D + F)
    return ops, nbytes


def _counter_sum(snapshot: dict, name: str) -> float:
    return sum(s["value"] for s in snapshot.get(name, {}).get("series", []))


def gate_zero_share_pct(cfg: dict):
    """From the registry (this process's): zeroed over computed hidden
    units."""
    try:
        from paddle_tpu.obs import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    zeros = _counter_sum(snap, "moe_gate_zero_units")
    rows = _counter_sum(snap, "moe_assignments")
    if not rows or "moe_gate_zero_units" not in snap:
        return None
    return 100.0 * zeros / (rows * cfg["moe_ffn_hidden_size"])


def read(facts, kind, scopes=()):
    cfg = facts.get("config")
    if not cfg or "moe_ffn_hidden_size" not in cfg:
        return None
    if kind == "gate_zero_share":
        return gate_zero_share_pct(cfg)
    if kind != "moe_reglu":
        raise ValueError(f"no work function for {kind!r}")
    parsed, steps = trace_scopes.trace_of(facts), facts.get("steps")
    if parsed is None or not steps or not facts.get("expert_load"):
        return None
    ns = trace_scopes.scope_ns(parsed, scopes)
    if not ns:
        return None
    ops, nbytes = moe_reglu_work(cfg, facts["expert_load"], steps)
    peaks = facts["peaks"]
    least_s = max(ops / peaks["bf16_flops_per_s"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / steps / 1e9)
