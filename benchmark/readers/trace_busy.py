"""Shares of the first chip's traced time.

``idle_share``: 100 x (1 - busy / window).
``work_share``: 100 x (numbers[work] x numbers[count] / peak) / busy: the
share of the device's busy time that the required work needs at peak."""


def read(spec, numbers, reduced, peaks):
    busy, window = reduced["first"]["busy_s"], reduced["window_s"]
    if spec["mode"] == "idle_share":
        return 100.0 * (1.0 - busy / window) if window else None
    if spec["mode"] == "work_share":
        work, count = numbers.get(spec["work"]), numbers.get(spec["count"])
        peak = peaks.get(spec["peak"])
        if not work or not count or not peak or not busy:
            return None
        return 100.0 * (work * count / peak) / busy
    raise ValueError(f"trace_busy: unknown mode {spec['mode']!r}")
