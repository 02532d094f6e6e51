#!/usr/bin/env python3
"""The readings the limits of `correct` are set from, at a cell's own size:

    python benchmark/control.py --workload <name> --seeds 11,12,13

For every seed it prints one JSON line with what the sound program reads
on the numbers `correct` compares, and what the control reads: the plain
reference computed in the precision below the one the configuration
states.  No benchmark run calls this; PERF.md quotes its output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE in sys.path:
    sys.path.remove(HERE)
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def main(argv=None, devices_for=harness.require_devices):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, mix = harness.load_cell(bench, args.workload)
    devices = devices_for(cell["chips"])
    harness.use_compile_cache()
    runner = harness.load_module("runners", config["runner"])
    lowprec = harness.load_module("reference", "lowprec.py")
    seeds = [int(s) for s in args.seeds.split(",")]
    for row in runner.control(config, mix, devices, seeds, lowprec,
                              args.seconds):
        print("CONTROL " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
