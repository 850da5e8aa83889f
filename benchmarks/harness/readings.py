#!/usr/bin/env python3
"""The readings that a cell's limits are set from, at the cell's own size
and in one process: the program over a list of seeds, and the control (the
reference at int8 levels put in the program's place) and the planted faults
over the first few: the cell's kind (``harness/<kind>.py``: ``readings``)
drives the program, its configuration's family the reference and the
control. Not part of a benchmark run; the limits in the cells' files were
set from what this prints (PERF.md section 2).

    python3 benchmarks/harness/readings.py --workload <cell> \\
        --seeds 101,102,... --controls 3 [--seconds 8] [--rehearse 1]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(**fields):
    print("reading " + json.dumps(fields), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks.harness import cells, common

    cell = cells.load_cell(args.workload)
    if args.rehearse:
        cell = cells.rehearsed(cell)
    common.prepare_process()
    try:
        say(device=common.device_info(cell["chips"], bool(args.rehearse)))
    except common.NoChip as exc:
        print(f"readings: {exc}", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",") if s]
    kind = cells.kind_module(f"workloads/{args.workload}.json", cell["kind"])
    for entry in kind.readings(cell, seeds, args.controls, args.seconds):
        say(**entry)
    return 0


if __name__ == "__main__":
    sys.exit(main())
