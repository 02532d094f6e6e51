"""From a traced run to its per-layer metrics.

``reduce_trace`` turns the profiler's files into plain event lists, the
device's busy time and the breakdown; ``read`` finds a metric's data file
(``layer_metrics/<name>.json``) and hands it to the reader it names
(``readers/<reader>.py``).  A reader that finds nothing to read returns
None and the metric is left out of the line.
"""

from __future__ import annotations

from benchmark import harness, trace_reduce


def reduce_trace(out, devices):
    """Busy seconds (averaged over the chips used), the traced window (first
    operation's start to the last one's end on the first chip), the event
    lists of the first chip and the breakdown."""
    if not out.get("trace"):
        raise SystemExit("benchmark: --trace 1 but the runner took no trace")
    planes = trace_reduce.load(out["trace"]["dir"])
    used = sorted(planes)[:len(devices)]
    if not used or not planes[used[0]]["ops"]:
        raise SystemExit("benchmark: the trace holds no device operation")
    first = planes[used[0]]
    t0, t1 = trace_reduce.span(first["ops"])
    busy = [trace_reduce.busy_seconds(planes[d]["ops"]) for d in used]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (t1 - t0) / 1e9,
        "first": {"ops": first["ops"], "modules": first["modules"],
                  "busy_s": busy[0]},
        "breakdown": {
            "device_ops": trace_reduce.top_operations(first["ops"], 10),
            "idle_gaps": trace_reduce.idle_gaps(first["ops"],
                                                first["modules"], 5),
        },
    }


def read(name, numbers, reduced, devices):
    spec = harness.load_json("layer_metrics", name + ".json")
    reader = harness.load_module("readers", spec["reader"] + ".py")
    peaks = harness.peaks_for(devices[0].device_kind) \
        if devices[0].platform == "tpu" else {}
    return reader.read(spec, numbers, reduced, peaks)
