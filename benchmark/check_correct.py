"""Reads, in one process, the numbers ``correct`` compares over many seeds:
for the program as its configuration states, for the control (the plain
reference in the program's place with fp8 operands, the precision below the
bf16 operands the configurations state), and for the program under
``FLAGS.amp`` (bf16 activations and backward too; read for the record, it is
no further from the float32 reference than the default policy).  The limits
beside each cell's traffic file are set from the first two readings; the
benchmark's own runs never run the control.

    python benchmark/check_correct.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--amp-seeds 1,2]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def read(cell, reference, runner, seeds, amp=False, control=False) -> list:
    from paddle_tpu.utils.flags import FLAGS

    out = []
    FLAGS.amp = amp
    try:
        for seed in seeds:
            numbers = runner.correct_numbers(cell, reference, seed,
                                             control=control)
            out.append(numbers)
            print(json.dumps({"seed": seed, "amp": amp, "fp8_reference":
                              control, **numbers}), flush=True)
    finally:
        FLAGS.amp = bool(cell["config"]["amp"])
    return out


def suggest_limits(sound_largest: dict, control_smallest: dict) -> dict:
    """The rule the limits files were written by: EVERY number compared is
    held.  A number sits at three times the sound runs' largest, the room a
    dozen seeds leave for the hundreds a later check draws (one reading over
    a limit refuses a PR).  Where the control separates (its smallest is
    three times the sound runs' largest or more) but by less than 4.5 times,
    the limit comes down to the control's smallest over 1.5, so that the
    control keeps its room too.  A number the control does not separate is
    held all the same, against the fault it is there to catch: a wrong
    gradient in that leaf, a loss that is not the batch's, a step that
    returns its state unchanged."""
    limits = {}
    for k, big in sound_largest.items():
        small = (control_smallest or {}).get(k)
        limit = 3 * big
        if small is not None and small >= 3 * big:
            limit = min(limit, small / 1.5)
        limits[k] = float(f"{limit:.2g}")
    return limits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--amp-seeds", default="")
    ns = ap.parse_args(argv)

    from benchmark import manifest
    from benchmark.run import prepare_program, require_tpu

    cell = manifest.cell(ns.workload)
    prepare_program(cell["config"])
    require_tpu(cell["chips"])
    runner = manifest.runner(cell["traffic"]["runner"])
    reference = manifest.reference(cell["config"])
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    sound = read(cell, reference, runner, ints(ns.seeds))
    control = read(cell, reference, runner, ints(ns.control_seeds),
                   control=True)
    amp = read(cell, reference, runner, ints(ns.amp_seeds), amp=True)
    keys = [k for k in sound[0] if all(k in r for r in sound + control)]
    most = lambda rs: ({k: max(r[k] for r in rs) for k in keys}   # noqa: E731
                       if rs else None)
    least = lambda rs: ({k: min(r[k] for r in rs) for k in keys}  # noqa: E731
                        if rs else None)
    limits = suggest_limits(most(sound), least(control))
    print(json.dumps({"workload": ns.workload, "sound_smallest": least(sound),
                      "sound_largest": most(sound),
                      "control_smallest": least(control),
                      "amp_largest": most(amp),
                      "suggested_limits": limits,
                      "control_fails": sorted(
                          k for k in keys if control
                          and least(control)[k] > limits[k])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
