"""Seconds JAX spent tracing, lowering, compiling and loading programs from
its cache during set-up (``jax.monitoring`` duration events)."""

EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


def read(facts):
    seen = facts.get("setup_durations") or {}
    if not any(e in seen for e in EVENTS):
        return None
    return sum(seen.get(e, 0.0) for e in EVENTS)
