"""Records ``tests/benchmark/spans.xplane.pb``, the small trace the reader
tests of ``test_trace_scopes.py`` run on: the LSTM cell at a toy size (two
peephole LSTM layers of 128 over batch 8, 16 steps of time, so the four
``lstm_seq_*`` kernels pass their gates), a few batches through
``SGDTrainer.train`` inside ``run.measure``'s own traced window.  Only a
machine with a TPU can record it:

    chiprun -- python tests/benchmark/record_spans.py       (on the chip)
    python tests/benchmark/record_spans.py --slim chiprun_out/spans.raw.xplane.pb

The first writes the trace as the profiler left it to ``chiprun_out/``
(Python tracer and HLO protos off, or it would not fit) with the result line
beside it; the second, which needs no chip, keeps what the readers read (the
TPU's planes and the host threads that hold ``bench.`` or ``paddle_tpu.``
spans) and writes ``tests/benchmark/spans.xplane.pb`` with ``spans.json``,
the steps of the window.
"""

from __future__ import annotations

import copy
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "lstm-trainer-b256-t640"
TOY = (dict(vocab=211, emb_dim=16, hid_dim=128),
       dict(batch=8, seq_len=16, lengths={"lo": 8, "hi": 16},
            reference_rows_per_block=4, ring=4))
KEEP_SPANS = (b"bench.", b"paddle_tpu.")
OUT = os.path.join(ROOT, "chiprun_out")


def record() -> None:
    import jax

    from benchmark import manifest, run, trace_reduce

    cell = copy.deepcopy(manifest.cell(CELL))
    cell["config"].update(TOY[0])
    cell["traffic"].update(TOY[1])
    cell["limits"] = {}          # a toy's gradients have no limits read
    run.prepare_program(cell["config"])
    device = run.require_tpu(1)
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
    shutil.copy(found, os.path.join(OUT, "spans.raw.xplane.pb"))
    with open(os.path.join(OUT, "spans.raw.json"), "w") as f:
        json.dump(line, f)
    print(json.dumps(line))


def _tagged(buf):
    """(field number, the field's whole bytes, its payload) of a message."""
    from benchmark.trace_scopes import _varint

    i, n = 0, len(buf)
    while i < n:
        at = i
        key, i = _varint(buf, i)
        kind = key & 7
        payload = None
        if kind == 0:
            _, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            payload, i = buf[i:i + size], i + size
        else:
            i += 8 if kind == 1 else 4
        yield key >> 3, bytes(buf[at:i]), payload


def _varint_bytes(value: int) -> bytes:
    out = bytearray()
    while True:
        out.append((value & 0x7F) | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def slim(raw_path: str) -> None:
    """Keep the TPU's planes whole; of the host's planes keep the lines
    whose events' metadata names include a ``bench.`` or ``paddle_tpu.``
    span; drop every other plane."""
    with open(raw_path, "rb") as f:
        space = memoryview(f.read())
    out = bytearray()
    for num, whole, plane in _tagged(space):
        if num != 1:
            continue
        fields = list(_tagged(plane))
        name = next(bytes(p) for n, _, p in fields if n == 2).decode()
        if name.startswith("/device:TPU:"):
            out += whole
            continue
        if not name.startswith("/host:"):
            continue
        wanted = set()          # ids of the event metadata to keep
        for n, _, entry in fields:
            if n != 4:
                continue
            parts = {k: p for k, _, p in _tagged(entry)}
            meta = list(_tagged(parts[2]))
            label = next((bytes(p) for k, _, p in meta if k == 2), b"")
            if label.startswith(KEEP_SPANS):
                wanted.add(bytes(next(w for k, w, _ in meta if k == 1))[1:])
        if not wanted:
            continue
        body = bytearray()
        for n, whole_field, payload in fields:
            if n == 3:          # a line: keep it if it holds a wanted event
                ids = {bytes(w)[1:] for k, _, ev in _tagged(payload) if k == 4
                       for j, w, _ in _tagged(ev) if j == 1}
                if not ids & wanted:
                    continue
            body += whole_field
        out += b"\x0a" + _varint_bytes(len(body)) + body
    dest = os.path.join(HERE, "spans.xplane.pb")
    with open(dest, "wb") as f:
        f.write(out)
    with open(raw_path[:-len(".xplane.pb")] + ".json") as f:
        line = json.load(f)   # the result line record() left beside it
    with open(os.path.join(HERE, "spans.json"), "w") as f:
        json.dump({"steps": line["attempted"], "device": line["device"],
                   "metrics": line["metrics"]}, f, indent=1)
        f.write("\n")
    print(dest, len(out), "bytes;", line["attempted"], "steps")


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--slim":
        slim(sys.argv[2])
    else:
        record()
