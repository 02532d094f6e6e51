#!/usr/bin/env python3
"""One run of one cell:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

prints the cell's numbers and, as the last line of standard output, one
JSON object: correct, attempted, failed, metrics, device (and breakdown
when traced).  Without the chips the cell asks for it exits non-zero and
prints no result.  BENCH_RUN in the environment is not read.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # process start, as near as Python gives it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE in sys.path:
    sys.path.remove(HERE)
sys.path.insert(0, ROOT)

from benchmark import harness, metrics  # noqa: E402


def metric_cells(bench, metric):
    """The cells a metric is reported in: its own ``workloads``, else
    every cell."""
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def main(argv=None, devices_for=harness.require_devices):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell, config, mix = harness.load_cell(bench, args.workload)
    devices = devices_for(cell["chips"])
    harness.use_compile_cache()
    checks = harness.Checks()
    runner = harness.load_module("runners", config["runner"])
    out = runner.run({
        "cell": cell, "config": config, "mix": mix, "seed": args.seed,
        "seconds": args.seconds, "trace": bool(args.trace),
        "devices": devices, "checks": checks, "t_start": _T_START})

    device = harness.device_report(devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    values = dict(out["end_to_end"], setup_s=out["setup_s"])
    line = {"correct": None, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {}, "device": device}
    if args.trace:
        reduced = metrics.reduce_trace(out, devices)
        device["busy_s"], device["window_s"] = (reduced["busy_s"],
                                                reduced["window_s"])
        line["breakdown"] = reduced["breakdown"]
        for m in bench["per_layer"]:
            if cell["name"] not in metric_cells(bench, m):
                continue
            v = metrics.read(m["name"], out["numbers"], reduced,
                                   devices)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        checks.floor("device_busy_s", reduced["busy_s"], 1e-9)
    else:
        for m in bench["end_to_end"]:
            if (cell["name"] in metric_cells(bench, m)
                    and m["name"] in values):
                line["metrics"][m["name"]] = {"value": values[m["name"]],
                                              "unit": m["unit"]}
    for name, v in sorted(values.items()):
        print(f"VALUE {name} = {v}", flush=True)
    checks.print()
    line["correct"] = checks.correct
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
