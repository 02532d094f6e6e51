"""Reduction of a profiler trace to the few numbers the benchmark reports.

Three numbers, not a profiler: the busy time of a device (union of the
intervals in which an operation ran), the summed time of the operations
whose name matches a pattern (optionally only inside program executions
whose name matches another), and the operations with most time.  All of it
works on plain tuples ``(name, start_ns, duration_ns)`` so the tests feed it
hand-made lists; ``load()`` is the only part that knows the ``.xplane.pb``
layout (read with ``jax.profiler.ProfileData``, nothing else).

A TPU plane is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO operation (children of a ``while`` or a fusion nest
inside their parent), its line ``XLA Modules`` one event per executed
program (PR 24, read by hand from a v5e trace).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir):
    """{device ordinal: {"ops": [...], "modules": [...]}} with events as
    (name, start_ns, duration_ns), sorted by start."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = sorted(
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events)
        devices[int(m.group(1))] = {"ops": lines.get(OPS_LINE, []),
                                    "modules": lines.get(MODULES_LINE, [])}
    return devices


def describe(trace_dir, top=12):
    """Planes, lines and their most frequent event names: what a person
    looks at before writing a name pattern."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            names = {}
            n = 0
            for ev in line.events:
                n += 1
                agg = names.setdefault(ev.name, [0, 0])
                agg[0] += 1
                agg[1] += int(ev.duration_ns)
            best = sorted(names.items(), key=lambda kv: -kv[1][1])[:top]
            out.append({"plane": plane.name, "line": line.name, "events": n,
                        "top": [[k, c, d / 1e9] for k, (c, d) in best]})
    return out


def busy_seconds(events):
    """Seconds covered by the union of the events' intervals."""
    total, end = 0, None
    start = None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, s + d
        else:
            end = max(end, s + d)
    if end is not None:
        total += end - start
    return total / 1e9


def span(events):
    """(first start, last end) of the events, in ns."""
    return (min(s for _, s, _ in events), max(s + d for _, s, d in events))


def inside_modules(ops, modules, module_pattern):
    """The operations that ran inside an execution of a program whose name
    matches ``module_pattern`` (by time containment of the start)."""
    rx = re.compile(module_pattern)
    windows = sorted((s, s + d) for name, s, d in modules if rx.search(name))
    out, i = [], 0
    for ev in sorted(ops, key=lambda e: e[1]):
        while i < len(windows) and windows[i][1] <= ev[1]:
            i += 1
        if i < len(windows) and windows[i][0] <= ev[1] < windows[i][1]:
            out.append(ev)
    return out


def pattern_seconds(events, pattern):
    """(summed seconds, count) of the events whose name matches."""
    rx = re.compile(pattern)
    hits = [d for name, _, d in events if rx.search(name)]
    return sum(hits) / 1e9, len(hits)


def self_times(events):
    """{name: seconds} with each event's time less the time of the events
    nested inside it, so a ``while`` does not count its body twice."""
    out = {}
    stack = []  # [name, end, self_ns]

    def pop():
        name, _, self_ns = stack.pop()
        out[name] = out.get(name, 0) + max(self_ns, 0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            pop()
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    while stack:
        pop()
    return {k: v / 1e9 for k, v in out.items()}


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%?[\w.\-]+ = (\([^()]*\)|\S+) ([\w\-]+)\(")


def op_label(name):
    """A short label that groups the same operation of different layers:
    the trace names an operation by its whole HLO line, ``%fusion.7 =
    bf16[128,3072]{1,0:T(8,128)} fusion(...)``; the label is its opcode and
    result type, ``fusion bf16[128,3072]``.  Other names pass unchanged."""
    flat = name
    while True:
        cut = _LAYOUT.sub("", flat)
        if cut == flat:
            break
        flat = cut
    m = _HLO.match(flat)
    if not m:
        return name[:96]
    return f"{m.group(2)} {m.group(1)}"[:96]


def top_operations(events, n=10):
    """[[label, seconds]] of the n labels with most self time."""
    times = {}
    for name, v in self_times(events).items():
        label = op_label(name)
        times[label] = times.get(label, 0.0) + v
    return [[k, v] for k, v in
            sorted(times.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops, modules, n=5):
    """The ``n`` longest intervals in which no operation ran, each named by
    the programs that ran before and after it (the only thing the trace
    knows about what the host was doing: the program has no host spans)."""
    if not ops:
        return []
    merged = []
    for _, s, d in sorted(ops, key=lambda e: e[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    mods = sorted((s, s + d, name) for name, s, d in modules)

    def module_at(t):
        best = "?"
        for s, e, name in mods:
            if s <= t:
                best = name
            else:
                break
        return best

    gaps = []
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    out = []
    for length, e0, s1 in gaps[:n]:
        out.append([f"{_short(module_at(e0 - 1))}->{_short(module_at(s1))}",
                    length / 1e9])
    return out


def _short(name):
    return re.sub(r"\(.*$", "", name)[:48]
