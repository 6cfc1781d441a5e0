"""One run of one cell:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set up (weights from the seed on the device, compile or cache load, warm-up
of this cell's shapes), measure for ``--seconds``, print the numbers compared
for ``correct`` beside their limits, and as the LAST line one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``).  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces a few seconds and reports its
per-layer metrics.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import contextlib        # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: a traced run measures this long at most: traces are large
TRACE_SECONDS = 3.0
#: the span the window is wrapped in when a run is traced
WINDOW_SPAN = "bench.window"
#: what a run writes besides JAX's compile cache (listed in .gitignore)
OUT_DIR = os.path.join(ROOT, ".bench_out")
#: inside the window either is a failure: a program compiled, or loaded anew
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class Window:
    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        ctx = self.ctx
        if ctx.trace:
            import jax

            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(ctx.trace_dir)
            # the host window on the trace's clock: the reduction clips to it
            self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self.span.__enter__()
        ctx.in_window = True
        self.t0 = time.perf_counter()
        ctx.setup_s = self.t0 - T_START - sum(ctx.untimed_s.values())
        self.deadline = self.t0 + ctx.seconds
        return self

    def expired(self) -> bool:
        return time.perf_counter() >= self.deadline

    def __exit__(self, *exc):
        ctx = self.ctx
        ctx.window_s = time.perf_counter() - self.t0
        ctx.sample_memory()
        ctx.in_window = False
        if ctx.trace:
            import jax

            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False


class Context:
    """What a runner gets: the cell, the seed, the clock and the tracer."""

    def __init__(self, cell, reference, seed, seconds, trace):
        self.cell, self.reference, self.seed = cell, reference, seed
        self.trace = bool(trace)
        self.seconds = min(seconds, TRACE_SECONDS) if trace else seconds
        self.trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        self.untimed_s = {}
        self.marks = {}
        self.in_window = False
        self.setup_s = self.window_s = None
        self.memory_peak_bytes = 0
        self.durations = {}        # monitoring event -> seconds in set-up
        self.compiles_in_window = 0
        self.cache_hits = self.cache_requests = 0

    # -- JAX's monitoring events -------------------------------------
    def on_duration(self, event, seconds, **_):
        if self.in_window and event in COMPILE_EVENTS:
            self.compiles_in_window += 1
        if not self.in_window and self.setup_s is None:
            self.durations[event] = self.durations.get(event, 0.0) + seconds

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1

    # -- for runners ---------------------------------------------------
    @contextlib.contextmanager
    def untimed(self, name):
        """Work that is not set-up: the plain reference's own time."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.untimed_s[name] = (self.untimed_s.get(name, 0.0)
                                    + time.perf_counter() - t0)

    def window(self) -> Window:
        return Window(self)

    def span(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def mark(self, name):
        """A milestone of set-up, in seconds since the process started."""
        self.marks[name] = time.perf_counter() - T_START

    def note(self, **fields):
        print(json.dumps(fields), flush=True)

    def memory_now(self) -> int:
        """Bytes the fullest chip holds at this instant: live arrays
        (``bytes_in_use``) plus what the loaded executables reserve for
        their temporaries (``bytes_reserved``; this runtime counts the two
        apart, and a reservation stands for as long as its program is
        loaded)."""
        import jax

        def held(d):
            s = d.memory_stats() or {}
            return int(s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0))

        return max(held(d)
                   for d in jax.local_devices()[:self.cell["chips"]])

    def sample_memory(self) -> None:
        """Called by the runner inside the window where the chip holds most
        (steps dispatched and not yet fetched): ``memory_peak_bytes`` is the
        largest of these instants, so it is the timed program's own and not
        the plain reference's or the warm-up's."""
        if self.in_window:
            self.memory_peak_bytes = max(self.memory_peak_bytes,
                                         self.memory_now())


def require_tpu(chips: int) -> dict:
    """The devices as JAX reports them, or exit: no fallback to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"benchmark: no TPU found (platform {devs[0].platform!r}); "
                 f"nothing was run")
    if len(devs) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chips, JAX reports "
                 f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def prepare_program(config: dict) -> None:
    """The program's own init (places JAX's persistent compile cache in
    ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set)
    and the precision policy the configuration states."""
    try:
        from paddle_tpu.utils.devices import init
        from paddle_tpu.utils.flags import FLAGS
    except ImportError as e:
        sys.exit(f"benchmark: the program is not in this checkout ({e})")
    import jax

    init([])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    FLAGS.dtype = config["param_dtype"]
    FLAGS.compute_dtype = config["compute_dtype"]
    FLAGS.amp = bool(config["amp"])


def measure(cell, reference, runner, seed, seconds, trace, device,
            marks=None) -> dict:
    """Drive one run of a cell on the attached devices; the result line."""
    import jax

    from benchmark import correct, manifest

    ctx = Context(cell, reference, seed, seconds, trace)
    ctx.marks.update(marks or {})
    jax.monitoring.register_event_duration_secs_listener(ctx.on_duration)
    jax.monitoring.register_event_listener(ctx.on_event)
    ctx.mark("device_ready")
    result = runner.run(ctx)
    numbers = dict(result["numbers"])
    numbers["compiles_in_window"] = ctx.compiles_in_window
    ctx.note(setup_s=ctx.setup_s, window_s=ctx.window_s,
             untimed_s=ctx.untimed_s, setup_marks_s=ctx.marks,
             compile_cache={"requests": ctx.cache_requests,
                            "hits": ctx.cache_hits},
             setup_compile_events_s=ctx.durations,
             **{k: v for k, v in result.items()
                if k not in ("numbers", "metrics", "facts")})
    ok = correct.judge(numbers, cell["limits"])
    dev = dict(device, memory_peak_bytes=ctx.memory_peak_bytes)
    line = {"correct": bool(ok), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "device": dev}
    if not trace:
        values = dict(result["metrics"], setup_s=ctx.setup_s)
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]}
        return line
    from benchmark import trace_reduce

    summary = trace_reduce.reduce_trace(
        trace_reduce.find_xplane(ctx.trace_dir), window_span=WINDOW_SPAN)
    ctx.note(trace={k: summary[k] for k in ("devices", "window_s", "busy_s",
                                            "kernel_s", "kernel_ops")})
    facts = dict(result.get("facts", {}), trace=summary,
                 window_s=ctx.window_s, setup_durations=ctx.durations,
                 peaks=manifest.peaks(device["kind"]), chips=cell["chips"],
                 steps=result.get("steps"), tokens=result.get("tokens"),
                 flops_per_step=result.get("flops_per_step"))
    metrics = {}
    for m in cell["per_layer"]:
        read, args = manifest.layer_metric_reader(m["name"])
        value = read(facts, **args)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line["metrics"] = metrics
    dev["busy_s"], dev["window_s"] = summary["busy_s"], summary["window_s"]
    line["breakdown"] = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)

    from benchmark import manifest

    cell = manifest.cell(ns.workload)
    prepare_program(cell["config"])
    t_imported = time.perf_counter() - T_START
    device = require_tpu(cell["chips"])
    runner = manifest.runner(cell["traffic"]["runner"])
    reference = manifest.reference(cell["config"])
    line = measure(cell, reference, runner, ns.seed, ns.seconds, ns.trace,
                   device, marks={"program_imported": t_imported})
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
