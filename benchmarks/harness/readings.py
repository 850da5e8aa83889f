#!/usr/bin/env python3
"""The readings that a cell's limits are set from, at the cell's own size
and in one process: the program over a list of seeds, and the control (the
reference at int8 levels put in the program's place) and the planted faults
over the first few. Not part of a benchmark run; the limits in the cells'
files were set from what this prints (PERF.md section 2).

    python3 benchmarks/harness/readings.py --workload <cell> \\
        --seeds 101,102,... --controls 3 [--seconds 8] [--rehearse 1]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(**fields):
    print("reading " + json.dumps(fields), flush=True)


def serve_readings(cell, seeds, controls, seconds):
    from benchmarks.harness import reference, serve

    serving = serve.ServeCell(cell)
    serving.build()
    serving.warm_buckets()
    fields = serving.fields
    windows = []
    for seed in seeds:
        result = serving.window(seed, seconds)
        _metrics, good, malformed = serve.end_to_end(
            result, int(cell["geometry"]["max_new_tokens"]))
        windows.append((seed, good, len(result["failed"]) + len(malformed)
                        + result["hung"]))
    serving.close()
    weights = reference.make_weights(fields, 0, eager=True)
    count = int(cell["check"]["sample_requests"])
    pad_to = serve.pad_length(cell)
    for i, (seed, good, failed) in enumerate(windows):
        sample = serve.sample_finished(good, seed, count)
        started = time.perf_counter()
        program = serve.served_gap(fields, weights, sample, pad_to)
        took = time.perf_counter() - started
        entry = {"seed": seed, "finished": len(good), "failed": failed,
                 "program_gap": program["value"], "tokens": program["tokens"],
                 "where": program["where"], "reference_s": took}
        if i < controls:
            control = serve.served_gap(fields, weights, sample, pad_to,
                                       quant="int8")
            entry["control_int8_gap"] = control["value"]
            # a fault of the timed path: one served token altered where it
            # is produced (the id next to it)
            broken = [dict(r) for r in sample]
            broken[-1]["tokens"] = list(broken[-1]["tokens"])
            broken[-1]["tokens"][-1] = (broken[-1]["tokens"][-1] + 1) \
                % fields["vocab_size"]
            entry["altered_token_gap"] = serve.served_gap(
                fields, weights, broken, pad_to)["value"]
        say(**entry)


def train_readings(cell, seeds, controls, seconds):
    from benchmarks.harness import cells, common, train

    fields = cells.llama_fields(cell["config_data"])
    compiles = common.CompileCounter()
    for i, seed in enumerate(seeds):
        clock = train.drive(cell, seed, seconds, compiles, None,
                            time.perf_counter())
        if clock.error:
            say(seed=seed, error=clock.error)
            continue
        program = train.program_readings(clock)
        del clock
        gc.collect()
        started = time.perf_counter()
        ref = train.reference_readings(fields, cell["traffic_data"], seed)
        took = time.perf_counter() - started
        entry = {"seed": seed, "reference_s": took,
                 "program_losses": program["losses"],
                 "reference_losses": ref["losses"]}
        for name, (value, where) in train.compare(program, ref).items():
            entry[name] = value
            entry[name + ".where"] = where
        if i < controls:
            for label, kw in (("control_int8", {"quant": "int8"}),
                              ("half_batch", {"drop_half_batch": True})):
                other = train.reference_readings(
                    fields, cell["traffic_data"], seed, **kw)
                for name, (value, _w) in train.compare(other, ref).items():
                    entry[f"{label}.{name}"] = value
        say(**entry)
        gc.collect()


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
    {"serve": serve_readings, "train": train_readings}[cell["kind"]](
        cell, seeds, args.controls, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
