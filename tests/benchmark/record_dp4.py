"""Records ``tests/benchmark/dp4.xplane.pb``, the small four-plane trace the
reader tests of ``test_train_step_dp.py`` run on: the cell
``seq2seq-train-dp4`` at a toy size (widths of 128, 8 rows a chip), a few
steps of the program's data-parallel step inside ``run.measure``'s own
traced window.  Only a machine with four TPU chips can record it:

    chiprun --chips 4 -- python tests/benchmark/record_dp4.py    (on the chips)
    python tests/benchmark/record_dp4.py --slim chiprun_out/dp4.raw.xplane.pb

The first writes the trace as the profiler left it to ``chiprun_out/``
(Python tracer and HLO protos off) with the result line beside it; the
second, which needs no chip, keeps the TPU's planes and the host lines that
hold ``bench.`` or ``paddle_tpu.`` spans (``record_spans.slim``) and writes
``tests/benchmark/dp4.xplane.pb.gz`` (four planes of a partitioned step hold
four times the event metadata: 4.7 MB, 1.2 MB compressed; a test unpacks it)
with ``dp4.json``: the steps of the window and what the readers read on the
whole trace.
"""

from __future__ import annotations

import copy
import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "seq2seq-train-dp4"
TOY = (dict(src_vocab=2048, trg_vocab=2048, emb_dim=128, enc_dim=128,
            dec_dim=128, att_dim=128),
       dict(batch_per_chip=8, src_len=12, trg_len=8,
            reference_rows_per_block=16, warmup_steps=2, ring=4))
OUT = os.path.join(ROOT, "chiprun_out")


def record() -> None:
    import jax

    from benchmark import manifest, run, trace_reduce

    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TOY[0])
    cell["traffic"].update(TOY[1])
    cell["limits"] = {}          # a toy's gradients have no limits read
    run.prepare_program(cell["config"])
    device = run.require_tpu(cell["chips"])
    start = jax.profiler.start_trace

    def start_small(log_dir, **kw):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        return start(log_dir, profiler_options=options, **kw)

    jax.profiler.start_trace = start_small
    try:
        line = run.measure(cell, manifest.reference(cell["config"]),
                           manifest.runner(cell["traffic"]["runner"]),
                           seed=2025, seconds=0.02, trace=1, device=device)
    finally:
        jax.profiler.start_trace = start
    os.makedirs(OUT, exist_ok=True)
    found = trace_reduce.find_xplane(
        os.path.join(run.OUT_DIR, "trace", CELL))
    shutil.copy(found, os.path.join(OUT, "dp4.raw.xplane.pb"))
    with open(os.path.join(OUT, "dp4.raw.json"), "w") as f:
        json.dump(line, f)
    print(json.dumps(line))


def slim(raw_path: str) -> None:
    """``record_spans.slim`` itself, pointed at a scratch directory (it
    writes beside its own file), then compressed."""
    import tempfile

    from benchmark import manifest

    spans = manifest.load_module(os.path.join(HERE, "record_spans.py"),
                                 "bench_record_spans")
    with tempfile.TemporaryDirectory() as scratch:
        spans.HERE = scratch
        spans.slim(raw_path)
        with open(os.path.join(scratch, "spans.xplane.pb"), "rb") as f:
            slimmed = f.read()
        shutil.copy(os.path.join(scratch, "spans.json"),
                    os.path.join(HERE, "dp4.json"))
    dest = os.path.join(HERE, "dp4.xplane.pb.gz")
    with gzip.GzipFile(dest, "wb", mtime=0) as f:
        f.write(slimmed)
    print(dest, os.path.getsize(dest), "bytes")


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--slim":
        slim(sys.argv[2])
    else:
        record()
