"""value = scale x (sum of every numbers[key] whose key matches ``num``, a
regular expression) / numbers[den], over the measured window: a family of
the program's counters summed over some of its labels.

None where no key matches (a program without the family) or ``den`` is
missing or 0; 0.0 where the matching series all stand at 0."""

import re


def read(spec, numbers, reduced, peaks):
    rx = re.compile(spec["num"])
    hits = [v for k, v in numbers.items() if rx.search(k)]
    den = numbers.get(spec["den"])
    if not hits or not den:
        return None
    return spec.get("scale", 1.0) * sum(hits) / den
