"""Reads, in one process, the numbers ``correct`` compares when a fault is
planted in the plain reference put in the program's place: the upper ends of
a training cell's limits beside the lower-precision control's
(``check_correct.py``).  The runner names the faults it can plant
(``FAULT_ROWS`` of ``runners/train_step_dp.py``: ``half_batch``, the mean
over half of the rows; ``no_exchange``, over one chip's rows) and reads them
through ``correct_numbers(cell, ref, seed, control=<fault>)``.  The
reference runs on one chip, so this needs one chip, whatever the cell asks.

    python benchmark/check_faults.py --workload <cell> --seeds 1,2,3 \
        [--faults half_batch,no_exchange] [--control-seeds 4,5,6]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--control-seeds", default="")
    ns = ap.parse_args(argv)

    from benchmark import manifest
    from benchmark.run import prepare_program, require_tpu

    cell = manifest.cell(ns.workload)
    prepare_program(cell["config"])
    require_tpu(1)
    runner = manifest.runner(cell["traffic"]["runner"])
    reference = manifest.reference(cell["config"])
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    faults = [f for f in ns.faults.split(",") if f] or sorted(
        runner.FAULT_ROWS)
    plan = [(f, s) for f in faults for s in ints(ns.seeds)]
    plan += [(True, s) for s in ints(ns.control_seeds)]
    smallest = {}
    for fault, seed in plan:
        numbers = runner.correct_numbers(cell, reference, seed,
                                         control=fault)
        name = "fp8_reference" if fault is True else fault
        print(json.dumps({"seed": seed, "fault": name, **numbers}),
              flush=True)
        least = smallest.setdefault(name, dict(numbers))
        for k, v in numbers.items():
            least[k] = min(least[k], v)
    print(json.dumps({"workload": ns.workload, "smallest": smallest}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
