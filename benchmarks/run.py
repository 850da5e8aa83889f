#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run: it finds ``workloads/<cell>.json`` and from it the
configuration, the traffic mix and the per-layer metrics, sets the system up
through its normal entry points (counted as ``setup_s``), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of its standard
output. Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. ``--rehearse 1`` runs the cell's tiny
rehearsal sizes on whatever JAX finds, prints its line on standard error
only and exits 3: it proves control flow, never a number.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    # the program logs on standard output; the result has to be its last
    # line, so everything else goes to standard error
    result_out, sys.stdout = sys.stdout, sys.stderr

    from benchmarks.harness import cells, common

    cell = cells.load_cell(args.workload)
    layer_metrics = cells.load_layer_metrics(args.workload)
    if args.rehearse:
        cell = cells.rehearsed(cell)
    try:
        cache_dir = common.prepare_process()
        device = common.device_info(cell["chips"], bool(args.rehearse))
    except common.NoChip as exc:
        print(f"benchmarks/run.py: {exc}: refusing to run", file=sys.stderr)
        return 2
    common.stamp(PROCESS_START,
                 f"cell={cell['name']} kind={cell['kind']} seed={args.seed} "
                 f"seconds={args.seconds} trace={args.trace} device={device} "
                 f"compile_cache={cache_dir}")

    kind = cells.kind_module(f"workloads/{args.workload}.json", cell["kind"])
    line = kind.run(cell, layer_metrics, args, device, PROCESS_START)
    if args.rehearse:
        print(line, file=sys.stderr)
        print("benchmarks/run.py: rehearsal finished; this is not a chip "
              "run and prints no result", file=sys.stderr)
        return 3
    print(line, file=result_out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
