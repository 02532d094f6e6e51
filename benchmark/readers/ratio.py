"""value = scale x numbers[num] / numbers[den]: counters of the program and
host-clock sums of the runner, over the measured window."""


def read(spec, numbers, reduced, peaks):
    num, den = numbers.get(spec["num"]), numbers.get(spec["den"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den
