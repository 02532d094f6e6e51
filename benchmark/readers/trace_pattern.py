"""Time of the first chip's operations whose name matches ``pattern``
(optionally only inside executions of programs matching ``module``).

``ms_per``: 1000 x seconds / numbers[per].
``roofline``: 100 x (numbers[work] / peak) / seconds: the least time the
chip could take for the work over the time the operations took.  With
``calls_per_work`` the work is that of one unit (a decode step) made of
numbers[calls_per_work] matching calls, and the time is taken per unit."""

from benchmark import trace_reduce


def read(spec, numbers, reduced, peaks):
    ops = reduced["first"]["ops"]
    if spec.get("module"):
        ops = trace_reduce.inside_modules(ops, reduced["first"]["modules"],
                                          spec["module"])
    seconds, count = trace_reduce.pattern_seconds(ops, spec["pattern"])
    if not count:
        return None
    if spec["mode"] == "ms_per":
        per = numbers.get(spec["per"])
        return 1000.0 * seconds / per if per else None
    if spec["mode"] == "roofline":
        work, peak = numbers.get(spec["work"]), peaks.get(spec["peak"])
        if not work or not peak:
            return None
        if spec.get("calls_per_work"):
            calls = numbers.get(spec["calls_per_work"])
            if not calls:
                return None
            seconds = seconds * calls / count
        return 100.0 * (work / peak) / seconds
    raise ValueError(f"trace_pattern: unknown mode {spec['mode']!r}")
