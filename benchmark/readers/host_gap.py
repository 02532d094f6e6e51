"""Share of the device's idle time that falls under a class of the
program's host spans.

The harness traces with the host tracer off, so the program's spans are
not in the trace: this reader takes them from the process
(``paddle_tpu.observability.profiling.spans()``, on ``perf_counter_ns``)
and lays them on the device trace's clock.

Clock.  The xplane's ``start_ns`` counts from the start of the profile
(read on a v5e, PR 25: the first program of a trace starts at ~60 ms), and
the trace's ``Task Environment`` plane gives that instant as
``profile_start_time`` in UNIX ns (0.08 ms after ``time.time_ns()`` read
before ``start_trace``).  With ``profiling.span_clock()`` that brings a
span within a few ms of the trace: enough to find which host run belongs
to which traced execution, not to split a gap.  The fine offset comes
from the pairs the data file names (``"pairs": {program pattern: run
span}``): every such run ends in a blocking fetch, so the k-th
execution's end on the device and the end of the k-th run's
``fetch_wait`` are one instant but for the transfer.  The offset is the
median over the pairs.  Fewer than ``MIN_PAIRS``, a residual spread (IQR)
over ``MAX_SPREAD_NS``, a first pair further apart than ``COARSE_NS``, or
a program that starts on the device before its ``dispatch`` span opened
by more than the spread: None, and the metric is left out.  A wrong clock
gives no number.

Attribution.  The idle intervals are the complement of the union of the
device's operations inside the traced window; each is split over the
innermost span alive at each instant.  ``"spans"`` (a regular expression
on span names) selects a class and the value is 100 x idle time under it
/ all idle time; ``"mode": "unattributed"`` is what no span covers.
"""

from __future__ import annotations

import bisect
import os
import re
import statistics

from benchmark import harness, trace_reduce

MIN_PAIRS = 10
MAX_SPREAD_NS = 1_000_000
COARSE_NS = 20_000_000

# fields of a span tuple (profiling.spans())
NAME, _LANE, T0, T1, ID, PARENT = range(6)


def program_spans():
    """(spans, (wall_ns, perf_ns)) of this process, or None where the
    program records none."""
    try:
        from paddle_tpu.observability import profiling
        return profiling.spans(), profiling.span_clock()
    except (ImportError, AttributeError):
        return None


def profile_start_ns(reduced):
    """UNIX ns at which the trace's clock reads 0, or None.  The reduction
    does not carry it, so it is read from the trace file the harness
    wrote; a test hands it over as ``reduced["profile_start_ns"]``."""
    if "profile_start_ns" in reduced:
        return reduced["profile_start_ns"]
    from jax.profiler import ProfileData

    try:
        data = ProfileData.from_file(trace_reduce.find_xplane(
            os.path.join(harness.ROOT, ".bench_trace")))
    except FileNotFoundError:
        return None
    for plane in data.planes:
        if plane.name == "Task Environment":
            for name, value in plane.stats:
                if name == "profile_start_time":
                    return int(value)
    return None


def idle_intervals(ops):
    """[(start, end)] in which no operation ran, between the first
    operation's start and the last one's end."""
    out, end = [], None
    for _, s, d in sorted(ops, key=lambda e: e[1]):
        if end is not None and s > end:
            out.append((end, s))
        end = s + d if end is None else max(end, s + d)
    return out


def align(spans, clock, start_ns, modules, pairs):
    """The offset that moves a span's ``perf_counter_ns`` onto the
    trace's clock, with how it was found, or None where the pairs do not
    agree.  ``clock`` is the spans' (wall, perf_counter) pair,
    ``start_ns`` the wall time of the trace's zero; ``pairs`` maps a
    program-name pattern to the name of the run span that executes it."""
    coarse = clock[0] - clock[1] - start_ns
    children = {}
    for sp in spans:
        children.setdefault(sp[PARENT], []).append(sp)
    residuals, starts = [], []
    for pattern, run_name in sorted(pairs.items()):
        rx = re.compile(pattern)
        execs = sorted((s, s + d) for name, s, d in modules
                       if rx.search(name))
        runs = []
        for sp in spans:
            if sp[NAME] != run_name:
                continue
            kids = {k[NAME]: k for k in children.get(sp[ID], ())}
            fetched = kids.get("fetch_wait", sp)[T1]
            runs.append((fetched, kids.get("dispatch", sp)[T0]))
        if not execs or not runs:
            continue
        ends = [f + coarse for f, _ in runs]
        j = bisect.bisect_left(ends, execs[0][1])
        j = min((k for k in (j - 1, j) if 0 <= k < len(ends)),
                key=lambda k: abs(ends[k] - execs[0][1]))
        if abs(ends[j] - execs[0][1]) > COARSE_NS:
            return None
        for (dev_start, dev_end), (fetched, dispatched) in zip(execs,
                                                               runs[j:]):
            residuals.append(dev_end - fetched)
            starts.append((dev_start, dispatched))
    if len(residuals) < MIN_PAIRS:
        return None
    # whole nanoseconds: a float cannot hold a time since the epoch
    offset = sorted(residuals)[len(residuals) // 2]
    q1, _, q3 = statistics.quantiles([r - offset for r in residuals], n=4)
    spread = q3 - q1
    early = max(dispatched + offset - dev_start
                for dev_start, dispatched in starts)
    out = {"offset_ns": offset, "pairs": len(residuals),
           "spread_ns": spread, "coarse_off_ns": offset - coarse,
           "earliest_start_ns": -early}
    if spread > MAX_SPREAD_NS or early > spread:
        return dict(out, offset_ns=None)
    return out


def innermost(spans):
    """Non-overlapping [(start, end, name)], sorted: over every stretch
    some span covers, the name of the innermost one alive (the one that
    started last)."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    by_start = sorted(spans)
    out, alive, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][0] <= a:
            alive.append(by_start[i])
            i += 1
        alive = [sp for sp in alive if sp[1] > a]
        if alive:
            name = max(alive, key=lambda sp: (sp[0], -sp[1]))[2]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def attribute(idle, segments):
    """({name: ns of idle time under it, None: under no span},
    [(gap ns, [names under it])] of the five longest gaps)."""
    totals, gaps, i = {}, [], 0
    for g0, g1 in idle:
        while i < len(segments) and segments[i][1] <= g0:
            i += 1
        under, t, k = [], g0, i
        while k < len(segments) and segments[k][0] < g1:
            s, e, name = segments[k]
            if s > t:
                totals[None] = totals.get(None, 0) + s - t
            lo, hi = max(s, g0), min(e, g1)
            totals[name] = totals.get(name, 0) + hi - lo
            if name not in under:
                under.append(name)
            t, k = hi, k + 1
        if t < g1:
            totals[None] = totals.get(None, 0) + g1 - t
        gaps.append((g1 - g0, under))
    gaps.sort(key=lambda g: -g[0])
    return totals, gaps[:5]


def reduce(reduced, pairs):
    """Alignment and attribution of one traced run, done once and kept on
    ``reduced`` for the metrics that share it."""
    if "host_gap" in reduced:
        return reduced["host_gap"]
    reduced["host_gap"] = None
    got = program_spans()
    ops = reduced["first"]["ops"]
    if got is None or not got[0] or not ops:
        return None
    spans, clock = got
    start_ns = profile_start_ns(reduced)
    if start_ns is None:
        print("INFO host_gap: the trace does not say when it started",
              flush=True)
        return None
    found = align(spans, clock, start_ns, reduced["first"]["modules"], pairs)
    if found is None:
        print("INFO host_gap: no alignment (too few pairs, or the first "
              "pair lies further apart than the coarse clock allows)",
              flush=True)
        return None
    print(f"INFO host_gap: clock alignment over {found['pairs']} pairs: "
          f"residual spread (IQR) {found['spread_ns'] / 1e6:.3f} ms, "
          f"the device's end lies {found['coarse_off_ns'] / 1e6:.3f} ms "
          f"from the fetch's on the wall clock (trace zero at "
          f"{start_ns} UNIX ns), earliest device start "
          f"{found['earliest_start_ns'] / 1e6:.3f} ms after its dispatch "
          f"span opened", flush=True)
    if found["offset_ns"] is None:
        print("INFO host_gap: alignment refused", flush=True)
        return None
    off = found["offset_ns"]
    t0, t1 = trace_reduce.span(ops)
    moved = [(sp[T0] + off, sp[T1] + off, sp[NAME]) for sp in spans
             if sp[T1] + off > t0 and sp[T0] + off < t1]
    segments = innermost(moved)
    totals, longest = attribute(idle_intervals(ops), segments)
    for length, under in longest:
        print(f"INFO host_gap: idle {length / 1e6:.3f} ms under "
              f"{under or ['no span']}", flush=True)
    own = {}
    for a, b, name in segments:
        own[name] = own.get(name, 0) + max(min(b, t1) - max(a, t0), 0)
    print("INFO host_gap: span: self ms in the traced window / idle ms "
          "under it: " + ", ".join(
              f"{name}: {own.get(name, 0) / 1e6:.2f} / {idle / 1e6:.2f}"
              for name, idle in sorted(totals.items(), key=lambda kv: -kv[1])
          ), flush=True)
    reduced["host_gap"] = totals
    return totals


def read(spec, numbers, reduced, peaks):
    totals = reduce(reduced, spec["pairs"])
    if not totals:
        return None
    idle = sum(totals.values())
    if spec.get("mode") == "unattributed":
        hit = totals.get(None, 0)
    else:
        rx = re.compile(spec["spans"])
        hit = sum(v for name, v in totals.items()
                  if name is not None and rx.search(name))
    return 100.0 * hit / idle if idle else None
