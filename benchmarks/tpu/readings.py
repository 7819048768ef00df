#!/usr/bin/env python3
"""The readings a cell's correctness limits are set from, on the chip.

    python3 benchmarks/tpu/readings.py --workload <cell> --seeds 1 2 3 ... \
        [--controls 3] [--out chiprun_out/readings.jsonl]

In one process, for every seed: the cell's set-up, a window of one call
at the cell's own load, and the numbers its run would compare (the
program's reading).  For the first ``--controls`` seeds also the control:
the plain reference computed one precision below the configuration's, in
the program's place, read by the same numbers.  A limit lies above every
program reading and below every control reading.  One JSON line per seed
and side; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent

import harness  # noqa: E402
import run as bench_run  # noqa: E402


def readings(cell_name: str, seeds, controls: int, devices=None,
             here: Path = HERE, root: Path = harness.ROOT):
    cell = harness.find_cell(cell_name, here=here, root=root)
    if devices is None:
        devices = harness.require_devices(cell.entry["chips"])
        bench_run.start_program(root)
    for n, seed in enumerate(seeds):
        ctx = harness.Context(cell=cell, seed=seed, devices=devices,
                              peaks=harness.peaks(devices[0].device_kind,
                                                  here))
        t = time.perf_counter()
        traffic = cell.traffic.Traffic(ctx)
        traffic.setup()
        traffic.window(0.0)                   # exactly one call
        traffic.release()
        sides = [("program", traffic.checks)]
        if n < controls:
            sides.append(("control", traffic.control_checks))
        for side, fn in sides:
            checks = fn()
            yield {"workload": cell_name, "seed": seed, "side": side,
                   "failed": traffic.failed,
                   "checks": {c.name: c.value for c in checks},
                   "seconds": time.perf_counter() - t}
        del traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    out = open(args.out, "a") if args.out else None
    for rec in readings(args.workload, args.seeds, args.controls):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.NoDevice as e:
        print(e, file=sys.stderr)
        sys.exit(3)
