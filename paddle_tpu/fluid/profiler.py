"""Profiler (reference python/paddle/fluid/profiler.py:225 `profiler` context,
platform/profiler.cc RecordEvent spans, device_tracer.cc CUPTI capture).

TPU-native redesign: the hot loop is one compiled XLA program, so per-op host
spans don't exist at run time.  What matters on TPU and what this module
records per program run:
  - compile events (trace+lower+XLA compile per signature — the TPU analog of
    kernel-launch overhead)
  - device execution time per compiled program
  - host-side `RecordEvent` spans for user code
Device-level detail (per-fusion timing, HBM traffic) comes from the xplane
trace: `profiler(...)` wraps `jax.profiler.start_trace/stop_trace`, viewable
in TensorBoard/XProf — the CUPTI→chrome-trace analog.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "RecordEvent", "record_event", "is_profiler_enabled",
           "get_events", "export_chrome_trace"]

_STATE = {
    "enabled": False,
    "trace_dir": None,
    "events": [],  # (kind, name, start_s, dur_s[, args])
    "t0": None,    # profiling session epoch (perf_counter)
    "wall_t0": None,  # wall-clock time of the epoch (cross-process merge)
}


def is_profiler_enabled():
    return _STATE["enabled"]


def _record(kind, name, seconds, start=None, args=None):
    """Record one span.  `args` (optional dict) lands in the chrome-trace
    event's args — how the pserver tags its `rpc_serve:` spans with the
    requesting client's span id for merged-trace attribution."""
    if _STATE["enabled"]:
        if start is None:
            start = time.perf_counter() - seconds
        if args:
            _STATE["events"].append((kind, name, start, seconds,
                                     dict(args)))
        else:
            _STATE["events"].append((kind, name, start, seconds))


def wall_to_session(wall_s):
    """Map a wall-clock timestamp onto the profiling session's
    perf_counter timeline (for spans whose start comes from another
    clock, e.g. the native span journal).  Identity-degrades to "now"
    when no session epoch exists."""
    t0, wall_t0 = _STATE["t0"], _STATE["wall_t0"]
    if t0 is None or wall_t0 is None:
        return time.perf_counter()
    return t0 + (wall_s - wall_t0)


class RecordEvent:
    """Host-side RAII span (reference platform/profiler.h RecordEvent)."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        _record("host", self.name, time.perf_counter() - self._t0,
                start=self._t0)
        return False


@contextlib.contextmanager
def record_event(name):
    with RecordEvent(name):
        yield


class timed_run:
    """Shared executor-run instrumentation: times the wrapped run, blocks on
    the arrays passed to ``done()`` (so async dispatch isn't mistaken for
    execution), and books a signature's first run as "compile+run" (jit
    compiles lazily).  Used by the single-device, shard_map-dp, and GSPMD
    hybrid execution paths — one implementation, no drift.

    with timed_run(label, state) as t:   # state: mutable dict, "ran" key
        out = jitted(...)
        t.done(out)
    """

    def __init__(self, label, state):
        self.enabled = is_profiler_enabled()
        self.label = label
        self.state = state
        self._arrays = ()

    def __enter__(self):
        if self.enabled:
            self._t0 = time.perf_counter()
        return self

    def done(self, *arrays):
        self._arrays = arrays

    def __exit__(self, et, ev, tb):
        if self.enabled and et is None:
            import jax

            jax.block_until_ready(self._arrays)
            kind = "run" if self.state.get("ran") else "compile+run"
            _record(kind, self.label, time.perf_counter() - self._t0,
                    start=self._t0)
        if et is None:
            self.state["ran"] = True
        return False


def start_profiler(state="All", tracer_option=None, trace_dir=None):
    if _STATE["enabled"]:
        return
    _STATE["enabled"] = True
    _STATE["events"] = []
    _STATE["t0"] = time.perf_counter()
    _STATE["wall_t0"] = time.time()
    _STATE["trace_dir"] = trace_dir
    if trace_dir is not None:
        import jax

        jax.profiler.start_trace(trace_dir)
    # the one span path: every observability.profiling span (executor
    # phases, the decode scheduler's turn) lands in this session's
    # events, and with a trace_dir also in the xplane as a
    # TraceAnnotation above the device ops
    from paddle_tpu.observability import profiling as _profiling

    _profiling.set_span_export(True, trace_dir is not None)


def stop_profiler(sorted_key=None, profile_path=None):
    if not _STATE["enabled"]:
        return
    _STATE["enabled"] = False
    from paddle_tpu.observability import profiling as _profiling

    _profiling.set_span_export(False, False)
    if _STATE["trace_dir"] is not None:
        import jax

        jax.profiler.stop_trace()
        _STATE["trace_dir"] = None
    table = _summary(sorted_key)
    if profile_path:
        with open(profile_path, "w") as f:
            f.write(table)
    else:
        print(table)


def reset_profiler():
    _STATE["events"] = []


def get_events():
    """Recorded (kind, name, start_s, dur_s) events of the last/current
    profiling session, with start relative to the session epoch (clamped to
    0 for spans entered before start_profiler).  export_chrome_trace
    writes them for chrome://tracing.  Spans recorded with
    args keep the 4-tuple shape here (back-compat); the args surface only
    in export_chrome_trace."""
    t0 = _STATE["t0"] or 0.0
    return [(e[0], e[1], max(e[2] - t0, 0.0), e[3])
            for e in _STATE["events"]]


def _get_events_with_args():
    t0 = _STATE["t0"] or 0.0
    return [(e[0], e[1], max(e[2] - t0, 0.0), e[3],
             e[4] if len(e) > 4 else None)
            for e in _STATE["events"]]


def export_chrome_trace(path):
    """Write the recorded spans as a chrome://tracing JSON file (the
    reference's tools/timeline.py converts its profiler proto the same
    way).

    The process's REAL pid tags every event and each event kind gets its
    own tid (host=1; run/compile/rpc/... assigned in order of first
    appearance), with ``ph:"M"`` process_name/thread_name metadata
    carrying the role/rank identity — so per-rank traces merged by
    tools/merge_traces.py stay attributable.  A top-level ``ptMeta``
    object records the session's wall-clock epoch for cross-process time
    alignment."""
    import json
    import os

    from paddle_tpu.observability import tracing as _tracing

    ident = _tracing.process_identity()
    pid = os.getpid()
    tids = {"host": 1}  # host spans stay on tid 1 (historic layout)
    events = []
    for kind, name, start, dur, extra in _get_events_with_args():
        tid = tids.setdefault(kind, len(tids) + 1)
        args = {"kind": kind}
        if extra:
            args.update(extra)
        events.append({
            "name": name, "cat": kind, "ph": "X",
            "ts": start * 1e6, "dur": dur * 1e6,
            "pid": pid, "tid": tid,
            "args": args,
        })
    meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": f"{ident['role']}{ident['rank']} "
                              f"(pid {pid})"}},
            {"name": "process_labels", "ph": "M", "pid": pid, "tid": 0,
             "args": {"labels": f"trace_id={ident['trace_id']}"}}]
    for kind, tid in sorted(tids.items(), key=lambda kv: kv[1]):
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": kind}})
    with open(path, "w") as f:
        json.dump({"traceEvents": meta + events,
                   "displayTimeUnit": "ms",
                   "ptMeta": {**ident,
                              "wall_t0": _STATE["wall_t0"] or 0.0}}, f)
    return path


def _summary(sorted_key=None):
    rows = {}
    for kind, name, _start, sec in (e[:4] for e in _STATE["events"]):
        key = (kind, name)
        tot, cnt, mx = rows.get(key, (0.0, 0, 0.0))
        rows[key] = (tot + sec, cnt + 1, max(mx, sec))
    items = [(k[0], k[1], v[0], v[1], v[0] / v[1], v[2]) for k, v in rows.items()]
    if sorted_key in (None, "total", "default"):
        items.sort(key=lambda r: -r[2])
    elif sorted_key == "calls":
        items.sort(key=lambda r: -r[3])
    elif sorted_key == "ave":
        items.sort(key=lambda r: -r[4])
    elif sorted_key == "max":
        items.sort(key=lambda r: -r[5])
    lines = ["-------------------------     Profiling Report     -------------------------",
             f"{'Event':<46} {'Kind':<8} {'Calls':>6} {'Total(s)':>10} {'Avg(s)':>10} {'Max(s)':>10}"]
    for kind, name, tot, cnt, ave, mx in items:
        lines.append(f"{name[:46]:<46} {kind:<8} {cnt:>6} {tot:>10.5f} {ave:>10.5f} {mx:>10.5f}")
    return "\n".join(lines)


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None, trace_dir=None):
    """fluid.profiler.profiler context (reference profiler.py:225).

    state/"GPU" kept for signature parity; on TPU pass trace_dir to also
    capture an xplane trace for XProf/TensorBoard.
    """
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key=sorted_key, profile_path=profile_path)


@contextlib.contextmanager
def cuda_profiler(*a, **kw):  # signature parity (reference profiler.py:39)
    yield
