"""The control for ``correct``: the reference at a narrower precision put
in the program's place.

    python3 bench/control.py --workload <name> --seeds 1 2 3

For each seed it makes the cell's layer operands as a run does, computes
the reference's rows at the configuration's precision (fixed-8) and at the
next precision below it (fixed-4), and compares the fixed-4 rows with the
fixed-8 ones exactly as ``check.compare`` compares a run's sweep rows. The
control has to come out not correct; the numbers it reads are the upper
readings ``PERF.md`` sets the limits from. The benchmark's runs never run
it. It needs no accelerator.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

import cell  # noqa: E402
import check  # noqa: E402
from operands import cell_operands  # noqa: E402
from reference import reference_rows  # noqa: E402

CONTROL_BITS = 4


def control_numbers(host_layers, config, traffic):
    """``check.compare`` of the fixed-4 reference against the fixed-8 one."""
    ref = reference_rows(host_layers, config, traffic)
    ctl = reference_rows(host_layers, config, traffic, bits=CONTROL_BITS)
    numbers, _due, _wrong = check.compare([ctl], ref)
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _entry, config, traffic = cell.workload(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        host = cell_operands(config, seed)
        numbers = control_numbers(host, config, traffic)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": check.passed(numbers),
                          "seconds": time.perf_counter() - t0,
                          "check": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
