"""Step-time attribution: phase-decomposed step timing, MFU/roofline
accounting per compiled signature, and a bounded flight recorder
(docs/OBSERVABILITY.md "Step-time attribution").

Before this module, ``pt_step_seconds`` was one opaque histogram: a slow
step could be host feed staging, Python dispatch, device compute,
collective wait, or fetch sync, and nothing could say which.  This is
the ONE audited timing implementation for the stack
(tools/lint_observability.py flags raw ``time.time()``/``perf_counter``
pairs anywhere else):

- phase timing   every execution lane (single-device Executor, run_steps
                 chain, transpiler DP, hybrid, GSPMD, serving) wraps its
                 dispatch in `step_phases(lane, label)` and brackets the
                 four canonical phases — ``feed_prep`` (scope staging +
                 device_put), ``dispatch`` (the jitted call; trace on a
                 signature's first run), ``device_wait``
                 (`block_until_ready` delta = device execution the host
                 had to wait out), ``fetch_sync`` (scope write-back +
                 host ops).  Phases are ALWAYS recorded and never block:
                 FLAGS_profile_phases keeps only what changes timing,
                 the per-step `block_until_ready` of ``device_wait``
                 (without it that phase reads ~0 and the wait shows
                 where the lane really fetches).  Exported as
                 ``pt_step_phase_seconds{phase,lane}`` histograms and,
                 while a fluid.profiler session is open, chrome-trace
                 spans (kind ``phase``) on the PT_TRACE timeline.

- spans          `span(name, lane)` is the one span primitive: the
                 recorder's phases, the executor's ``lookup`` /
                 ``compile`` / ``fetch_wait`` and the decode scheduler's
                 turn are all `_PhaseSpan`s.  Each records name, lane,
                 start and end (`time.perf_counter_ns`), its own id, the
                 id of the span open around it on this thread (0 for a
                 root), the turn or step number of its tree and an
                 optional note, into a bounded ring (`spans()`,
                 `SPAN_RING` entries) whose `span_clock()` pair lets a
                 reader move it onto another clock
                 (benchmark/readers/host_gap.py lays it on the device
                 trace's).

- in-flight      what the program KNOWS of the device without a trace:
  ledger         `enqueued(program)` numbers every program a lane hands
                 to the device (the ``dispatch`` span's note,
                 ``jit_<name>#<ordinal>``), `done(ordinal)` says a host
                 wait returned on that program's outputs (the waiting
                 span's note, ``done#<ordinal>``: the executor's
                 ``fetch_wait``, the decode scheduler's
                 ``prefill.await``).  From the wait that proved the
                 newest program finished until the next `enqueued` the
                 chip stands empty, and those seconds are booked on
                 ``pt_device_starved_seconds_total{under}`` under the
                 innermost span open meanwhile (``any`` for all of
                 them).

- runtime hooks  what the Python runtime and jax do INSIDE a span, and
                 report through hooks of their own
                 (`install_runtime_hooks`, on like the spans are): XLA's
                 trace, lower, backend-compile and cache-read stages
                 (`jax.monitoring`) enter the same ring as finished
                 children of the span open on the reporting thread
                 (`child_span`: ``xla.<stage>``), with
                 ``pt_xla_stage_seconds_total{stage,under}`` beside
                 them; the interpreter's collections (`gc.callbacks`)
                 are counted only, in
                 ``pt_host_gc_seconds_total{generation}``.

- MFU/roofline   `note_cost` (fed by `_JitExecutable.cost_analysis`) and
                 `note_collectives` (fed by compiled-HLO inspection)
                 join the measured device seconds with a per-platform
                 peak table (`device_peaks`, FLAGS_device_peak_*
                 overrides) into ``pt_mfu{signature}`` and
                 ``pt_roofline_bound{signature,bound}`` gauges: the
                 compute/memory/comm time lower bounds
                 (flops/peak_flops, bytes/peak_bw, comm_bytes/peak_ici)
                 name which wall the signature sits against — the
                 Tensor Processing Primitives (arXiv:2104.05755)
                 roofline framing as a scraped verdict.

- HLO inventory  `hlo_inventory` / `hlo_collective_bytes` /
                 `hlo_collective_counts`: the per-category accounting of
                 an optimized HLO module's cross-device collectives
                 (promoted here from parallel/gspmd/executor.py — the
                 gspmd lane re-exports them).

- flight record  a bounded ring (FLAGS_flight_recorder_steps) of the
                 last N steps' phase breakdowns + queue depths + health
                 events.  `dump_flight_record()` writes a JSONL
                 postmortem; automatic dumps fire on a slow-step
                 z-score over the per-lane rolling EMA
                 (FLAGS_profile_slow_step_zscore; the slow step's record
                 names under ``beneath`` every compile stage that ended
                 inside it) and on health-sentinel
                 bad steps (`note_health_event`, wired from
                 health/sentinel.py) — a wedged or anomalous run leaves
                 evidence instead of one opaque histogram.

- /profilez      a JSON status page on every MetricsServer: per-signature
                 MFU + roofline verdict, per-lane phase p50/p95, the
                 feed-bound verdict (prefetcher stall vs step time), and
                 flight-recorder state.  `attribution_digest()` is the
                 same payload compacted to one JSON-able dict.

Import cost is stdlib-only (the observability-package contract); jax,
fluid.flags and fluid.profiler are imported lazily inside functions.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import re
import sys
import threading
import time
import warnings

from . import metrics as _metrics
from . import tracing as _tracing

__all__ = [
    "step_phases", "NullRecorder", "span", "child_span", "spans",
    "span_clock", "enqueued", "done", "install_runtime_hooks",
    "RING_MIN_NS",
    "set_span_export", "SPAN_RING", "note_step", "note_cost",
    "note_collectives",
    "note_health_event", "device_peaks", "roofline",
    "hlo_inventory", "hlo_collective_bytes", "hlo_collective_counts",
    "flight_recorder", "dump_flight_record", "profilez_payload",
    "attribution_digest", "signature_stats", "reset",
    "PHASES",
]

# the canonical phase decomposition of one executed step, in order
PHASES = ("feed_prep", "dispatch", "device_wait", "fetch_sync")

# phase durations span ~100 us (feed staging) to multi-second compiles:
# extend the default latency buckets downward so sub-ms phases resolve
_PHASE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                  0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

_EMA_BETA = 0.9
_EPS = 1e-12


# ---------------------------------------------------------------------------
# metric accessors (lazy idempotent registration — the registry contract)
# ---------------------------------------------------------------------------


def _m_phase():
    return _metrics.histogram(
        "pt_step_phase_seconds",
        "Wall time of one step decomposed into named phases: feed_prep "
        "(scope staging + device transfer), dispatch (the jitted call), "
        "device_wait (block_until_ready delta = device execution the "
        "host waited out), fetch_sync (scope write-back + host ops)",
        labels=("phase", "lane"), buckets=_PHASE_BUCKETS)


def _m_mfu():
    return _metrics.gauge(
        "pt_mfu",
        "Model FLOPs utilization of the most recent steps per compiled "
        "signature: cost-model flops / (device seconds x platform peak "
        "flops, FLAGS_device_peak_flops override)",
        labels=("signature",))


def _m_roofline():
    return _metrics.gauge(
        "pt_roofline_bound",
        "Roofline verdict per compiled signature: 1 on the bound "
        "(compute|memory|comm) whose peak-rate time lower bound "
        "dominates, 0 elsewhere", labels=("signature", "bound"))


def _m_flight_dumps():
    return _metrics.counter(
        "pt_flight_dumps_total",
        "Flight-recorder JSONL postmortems written, by trigger reason "
        "(slow_step / health / explicit)", labels=("reason",))


# ---------------------------------------------------------------------------
# flags (read lazily and tolerantly — this module must import without fluid)
# ---------------------------------------------------------------------------


def _flag(name, default):
    try:
        from paddle_tpu.fluid import flags as _flags

        return _flags.flag(name)
    except Exception:
        return default


def _phases_enabled():
    return bool(_flag("profile_phases", False))


# ---------------------------------------------------------------------------
# phase recorder
# ---------------------------------------------------------------------------

_tls = threading.local()

# The span ring.  131072 entries: one scheduler turn of the decode lane is
# ~24 spans (9 of the scheduler, 7 of the executor under each of its two
# runs), and since PR 34 the benchmark's fastest serve cell makes ~70 turns
# a second (PERF.md §5, cell 6): ~1700 spans a second, of which the ring
# holds 78 s.  The benchmark's reader looks back from the end of a run over
# the second half of its 40-s window (benchmark/readers/host_gap.py): the
# 32768 entries of PR 25, sized when a turn took 130 ms, held 19.5 s of
# that cell and lost the traced interval.  A training lane's ~60 spans a
# second fit for half an hour.  An entry is the tuple
# (name, lane, start_ns, end_ns, id, parent_id, number, note).
SPAN_RING = 131072
_ring = collections.deque(maxlen=SPAN_RING)
_span_ids = itertools.count(1)
# one reading of both clocks, so a reader can move the ring's
# perf_counter_ns stamps onto the wall clock (or, through it, another)
_clock_pair = (time.time_ns(), time.perf_counter_ns())
# [chrome-trace export on, jax TraceAnnotation on] — set by
# fluid.profiler.start_profiler / stop_profiler (set_span_export)
_export = [False, False]


def spans():
    """The recorded spans, oldest first (a ring of the last ``SPAN_RING``
    = 131072: 78 s of the decode lane's fastest cell, ~1700 spans a
    second): tuples ``(name, lane,
    start_ns, end_ns, id, parent_id, number, note)`` on the
    `time.perf_counter_ns` clock.  ``parent_id`` is 0 for a root; ``number`` is the turn or step
    number of the span's tree (a child inherits its parent's)."""
    return list(_ring)


def span_clock():
    """``(time.time_ns(), time.perf_counter_ns())`` read together once:
    wall_ns = start_ns + (pair[0] - pair[1])."""
    return _clock_pair


def set_span_export(chrome, annotate):
    """fluid.profiler's session switch: ``chrome`` records every span as
    a chrome-trace event of kind ``phase``; ``annotate`` (a session with
    a ``trace_dir``, host tracer on) also makes every span a
    `jax.profiler.TraceAnnotation`, so XProf shows the turn above the
    device ops.  Outside a session neither costs anything."""
    _export[0], _export[1] = bool(chrome), bool(annotate)


_phase_fam = [None, -1]  # the histogram family, the registry epoch it is of


def _phase_child(name, lane):
    """The histogram series of one (phase, lane), without the kwargs
    `labels()` lookup on the hot path (the family's own child table is
    the cache, so `clear()` and a registry reset stay honoured)."""
    reg = _metrics.REGISTRY
    if _phase_fam[1] != reg._epoch or _phase_fam[0] is None:
        _phase_fam[0], _phase_fam[1] = _m_phase(), reg._epoch
    fam = _phase_fam[0]
    return (fam._children.get((name, lane))
            or fam.labels(phase=name, lane=lane))


class _PhaseSpan:
    """One host span.  Never blocks, always recorded."""

    __slots__ = ("_rec", "name", "lane", "id", "parent", "number", "note",
                 "t0", "t1", "_ann")

    def __init__(self, name, lane, number=None, rec=None):
        self._rec = rec
        self.name = name
        self.lane = lane
        self.number = number
        self.note = None

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.id = next(_span_ids)
        if stack:
            top = stack[-1]
            self.parent, self.number = top.id, top.number
        else:
            self.parent = 0
        stack.append(self)
        self._ann = None
        if _export[1]:
            import jax

            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = t0 = time.perf_counter_ns()
        empty = _ledger.empty
        if empty is not None and empty.stack is stack:
            # the stretch up to here was the parent's own time
            empty.split(stack[-2].name if len(stack) > 1 else "none", t0)
        return self

    def __exit__(self, et, ev, tb):
        self.t1 = t1 = time.perf_counter_ns()
        stack = _tls.stack
        empty = _ledger.empty
        if empty is not None and empty.stack is stack:
            empty.split(self.name, t1)
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        if stack:  # empty only after a reset() under an open span
            stack.pop()
        _ring.append((self.name, self.lane, self.t0, t1, self.id,
                      self.parent, self.number, self.note))
        if self._rec is not None:
            # the recorder books its phases once a step, summed by name
            self._rec._spans.append(self)
        else:
            _phase_child(self.name, self.lane).observe((t1 - self.t0) / 1e9)
        if _export[0]:
            _chrome_record(self.name, self.lane, self.t0, t1)
        return False

    @property
    def seconds(self):
        return (self.t1 - self.t0) / 1e9


def _chrome_record(name, lane, t0_ns, t1_ns):
    # only reached inside a fluid.profiler session (set_span_export)
    from paddle_tpu.fluid import profiler as _prof

    _prof._record("phase", f"{lane}:{name}", (t1_ns - t0_ns) / 1e9,
                  start=t0_ns / 1e9)


def span(name, lane, number=None):
    """A host span outside a `step_phases` recorder (the executor's
    ``lookup`` / ``compile`` / ``fetch_wait``, the decode scheduler's
    turn).  ``number`` is the turn or step number of a ROOT span's tree;
    a span opened inside another takes its parent's."""
    return _PhaseSpan(name, lane, number)


def child_span(name, lane, t0_ns, t1_ns, note=None):
    """Append a FINISHED span (both ends on `time.perf_counter_ns`) to
    the ring, as a child of the span open on this thread: its id as
    ``parent`` and its ``number`` (0 and None where none is open).  For
    work that reports itself through a hook when it is over (a compile
    stage).  Same tuple, same ring and same
    ``pt_step_phase_seconds{phase=name,lane}`` as `span`.  Returns the
    new span's id."""
    stack = getattr(_tls, "stack", None)
    if stack:
        parent, number = stack[-1].id, stack[-1].number
    else:
        parent, number = 0, None
    sid = next(_span_ids)
    _ring.append((name, lane, t0_ns, t1_ns, sid, parent, number, note))
    _phase_child(name, lane).observe((t1_ns - t0_ns) / 1e9)
    if _export[0]:
        _chrome_record(name, lane, t0_ns, t1_ns)
    return sid


# ---------------------------------------------------------------------------
# the in-flight ledger: what the program knows of the device without a trace
# ---------------------------------------------------------------------------


class _Empty:
    """One stretch in which the chip is known to stand empty.
    ``mark_ns``: the `time.perf_counter_ns` up to which it is split into
    ``under``, starting at the instant the wait returned.  ``under``:
    {span name: ns} of the stretch so far, added to the counter when it
    ends.  ``stack``: the span stack of the thread whose wait began it,
    the one thread whose spans split it, so `split` takes no lock."""

    __slots__ = ("mark_ns", "under", "stack")

    def __init__(self, mark_ns, stack):
        self.mark_ns, self.under, self.stack = mark_ns, {}, stack

    def split(self, under, now_ns):
        """A span of ``stack``'s thread opened or closed at ``now_ns``:
        the stretch since the last mark was spent under ``under``.  (The
        mark moves before the dict grows: an `enqueued` on ANOTHER
        thread, which copies the dict and then reads the mark, can so
        lose this piece and never count it twice.)"""
        mark, self.mark_ns = self.mark_ns, now_ns
        parts = self.under
        parts[under] = parts.get(under, 0) + now_ns - mark


class _Ledger:
    """``enqueued`` / ``done``: the ordinal of the newest program handed to
    the device and of the newest one a host wait proved finished.
    ``empty``: while the two are equal, the `_Empty` stretch that began
    when that became true; None otherwise."""

    __slots__ = ("enqueued", "done", "empty")

    def __init__(self):
        self.enqueued = self.done = 0
        self.empty = None


_ledger = _Ledger()
_ledger_lock = threading.Lock()


def enqueued(program):
    """A lane just handed ``program`` (the ``jit_*`` name a device trace
    shows) to the device: returns its ordinal, a process-wide count from
    1 of the programs every executor lane dispatches.  Called right
    after the jitted call returns, inside the ``dispatch`` span, whose
    note the lanes `single` and `chain` set to ``<program>#<ordinal>``.
    Where the ledger was empty, the known-empty stretch ends here: its
    rest goes under the innermost span open on the calling thread, and
    the whole of it onto ``pt_device_starved_seconds_total``.

    The ordinal is taken AFTER the jitted call, under no lock that spans
    both, so ordinal order is dispatch order only where ONE thread (or
    one caller's lock, as the decode engine's) dispatches to ONE device
    (or one mesh) in the process.  Two dispatching threads, or executors
    on different devices, can number their programs out of order:
    `done` then calls the chip empty while an earlier-numbered program
    runs, and the counter reads too HIGH."""
    led = _ledger
    with _ledger_lock:
        led.enqueued = k = led.enqueued + 1
        empty, led.empty = led.empty, None
    if empty is not None:
        parts = dict(empty.under)  # before the mark: see `_Empty.split`
        stack = getattr(_tls, "stack", None)
        name = stack[-1].name if stack else "none"
        # a negative rest: two threads' clock readings out of order
        parts[name] = parts.get(name, 0) + max(
            time.perf_counter_ns() - empty.mark_ns, 0)
        series = _starved_series(parts)
        for name, ns in parts.items():
            series[name].inc(ns / 1e9)
        series["any"].inc(sum(parts.values()) / 1e9)
    return k


def done(ordinal):
    """A host wait just returned on the outputs of program ``ordinal``.
    ONE CHIP RUNS ITS PROGRAMS IN DISPATCH ORDER, so every program up to
    that ordinal has finished too: the whole record rests on that
    assumption (a lane over several chips dispatches one program to all
    of them, and it holds for each), and on ordinal order BEING dispatch
    order, which holds for one dispatching thread or lock and one device
    or mesh a process (see `enqueued`).  Where it is the newest enqueued,
    the chip stands empty from this instant until the next `enqueued`:
    ``pt_device_starved_seconds_total`` counts from here.  A wait on an
    older program than one already proved done changes nothing.  Returns
    the note of the span that waited, ``done#<ordinal>``."""
    led = _ledger
    with _ledger_lock:
        if ordinal > led.done:
            led.done = ordinal
            if ordinal == led.enqueued:
                stack = getattr(_tls, "stack", None)
                if stack is None:
                    stack = _tls.stack = []
                led.empty = _Empty(time.perf_counter_ns(), stack)
    return f"done#{ordinal}"


def _starved_series(names):
    """{under: series} of ``pt_device_starved_seconds_total`` holding at
    least ``names`` and ``any``."""
    runtime = _runtime_series()
    series = runtime["starved"]
    for name in names:
        if name not in series:
            series[name] = runtime["starved_family"].labels(under=name)
    return series


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class StepPhaseRecorder:
    """Times one executed step.  `phase()` brackets always record the
    named sub-phases as spans (ring + ``pt_step_phase_seconds``) and
    never block.  FLAGS_profile_phases (``detailed``) keeps only what
    changes timing: `wait()` then blocks on the dispatched arrays so the
    device_wait phase measures real device time; with it off `wait()` is
    a no-op, preserving async dispatch pipelining, and the step total
    (and the signature label) is what `note_step` gets as wall time."""

    __slots__ = ("lane", "label", "detailed", "number", "blocked",
                 "_spans", "_t0")

    def __init__(self, lane, label, detailed, number=None):
        self.lane = lane
        self.label = label
        self.detailed = detailed
        self.number = number
        # True once the recorder's interval is known to hold the device's
        # completion (wait() blocked, or the lane fetched inside it):
        # only then may note_step read device time off it
        self.blocked = False
        self._spans = []

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def phase(self, name):
        return _PhaseSpan(name, self.lane, self.number, self)

    def wait(self, arrays):
        """Block until the dispatched device work completes — called
        inside the ``device_wait`` phase bracket.  A no-op with
        FLAGS_profile_phases off: the per-step sync would serialize the
        donated-buffer dispatch pipeline the fetch-free training loop
        relies on."""
        if not self.detailed:
            return
        try:
            import jax

            jax.block_until_ready(arrays)
        except Exception:  # non-jax values (host-op outputs)
            pass
        self.blocked = True

    def __exit__(self, et, ev, tb):
        if et is not None:
            return False
        total = time.perf_counter() - self._t0
        phases, starts = {}, {}
        for sp in self._spans:
            phases[sp.name] = phases.get(sp.name, 0.0) + sp.seconds
            starts.setdefault(sp.name, sp.t0 / 1e9 - self._t0)
        for name, dur in phases.items():
            _phase_child(name, self.lane).observe(dur)
        # hand the breakdown to note_step (same thread, the lane books
        # its pt_step_seconds sample immediately after run() returns)
        _tls.pending = (self.lane, self.label, phases or None, total,
                        self.blocked, self._t0, starts)
        return False


class NullRecorder:
    """Recorder-shaped no-op: nothing timed, nothing deposited.  For
    dispatches that must stay OUT of the attribution surface entirely —
    the serving lane's warmup batches (their duration is compile time,
    which would poison the serve-lane phase histograms and EMA exactly
    the way it is already kept out of the latency SLO histogram)."""

    detailed = False
    blocked = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def phase(self, name):
        return _NULL_SPAN

    def wait(self, arrays):
        pass


def step_phases(lane, label, enabled=True, number=None):
    """The one entry point every execution lane wraps its dispatch in.
    ``enabled=False`` returns the NullRecorder (warmup/precompile
    dispatches that must not enter the attribution stats).  ``number``
    is the step number its phases carry when no span is open around
    them."""
    if not enabled:
        return NullRecorder()
    return StepPhaseRecorder(lane, label, _phases_enabled(), number)


def _pop_pending(lane):
    pending = getattr(_tls, "pending", None)
    if pending is not None and pending[0] == lane:
        _tls.pending = None
        return pending
    return None


# ---------------------------------------------------------------------------
# runtime hooks: collections counted, XLA's stages beneath the open span
# ---------------------------------------------------------------------------

# A trace nested in another that lasted less than this is counted and kept
# OUT of the ring: one step's trace holds thousands of nested `jnp` traces,
# which would shorten the ring's horizon for nothing a reader can place.
# A constant, not a knob.
RING_MIN_NS = 500_000

_GC_GENERATIONS = ("0", "1", "2", "any")
_XLA_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_XLA_STAGES = {
    _XLA_TRACE: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
_XLA_UNDER = ("compile", "dispatch", "other", "none")
# the spans of the executor's own lanes; a scheduler's appear as they book
_STARVED_UNDER = ("any", "none", "lookup", "compile", "feed_prep",
                  "dispatch", "device_wait", "fetch_sync", "fetch_wait")

_xla_listening = [False]  # jax keeps no public list of its listeners
_hooks_lock = threading.Lock()
_gc_t0 = [0]
_runtime = [None, -1]  # the bound series, the registry epoch they are of


def _runtime_series():
    """The hooks' and the ledger's counter series, every one created at 0
    (a window in which nothing was collected, compiled or starved reads
    0, not nothing) and bound again after a registry reset."""
    reg = _metrics.REGISTRY
    if _runtime[1] == reg._epoch and _runtime[0] is not None:
        return _runtime[0]
    gc_s = _metrics.counter(
        "pt_host_gc_seconds_total",
        "Seconds this process spent inside the interpreter's cyclic "
        "garbage collections (gc.callbacks start to stop; every thread "
        "stands still meanwhile), by the generation collected and "
        "generation=any for all of them", labels=("generation",))
    xla_s = _metrics.counter(
        "pt_xla_stage_seconds_total",
        "Seconds inside jax's compile stages (jax.monitoring): trace "
        "(self time: a trace nested in another is taken out of it), "
        "lower, backend_compile, and cache_read, which is a PART of "
        "backend_compile (a persistent-cache hit's read) and no addend; "
        "under = compile or dispatch where a span of that name was open "
        "on the thread (compile first), other under another span, none "
        "under none", labels=("stage", "under"))
    starved_s = _metrics.counter(
        "pt_device_starved_seconds_total",
        "Seconds the device is KNOWN to have stood empty: from the host "
        "wait that proved the newest enqueued program finished "
        "(profiling.done) until the next program was enqueued "
        "(profiling.enqueued), by the innermost span open meanwhile on "
        "the thread that waited (none where no span was) and under=any "
        "for all of it.  Idle time while work is believed in flight is "
        "not in it", labels=("under",))
    series = {
        "gc": {g: gc_s.labels(generation=g) for g in _GC_GENERATIONS},
        "xla": {(st, u): xla_s.labels(stage=st, under=u)
                for st in _XLA_STAGES.values() for u in _XLA_UNDER},
        "starved": {u: starved_s.labels(under=u) for u in _STARVED_UNDER},
        "starved_family": starved_s,
    }
    _runtime[0], _runtime[1] = series, reg._epoch
    return series


def _on_gc(phase, info):
    """`gc.callbacks` entry.  Runs on the thread whose allocation set the
    collection off, wherever that thread stood: it bumps series that
    exist and never registers one (a registry reset leaves the
    collections unbooked until the next `install_runtime_hooks` or
    compile stage binds the series again)."""
    if phase == "start":
        _gc_t0[0] = time.perf_counter_ns()
        return
    t1, t0 = time.perf_counter_ns(), _gc_t0[0]
    series = _runtime[0]
    if not t0 or _runtime[1] != _metrics.REGISTRY._epoch:
        return  # installed between a start and its stop, or orphaned
    _gc_t0[0] = 0
    series["gc"][str(info["generation"])].inc((t1 - t0) / 1e9)
    series["gc"]["any"].inc((t1 - t0) / 1e9)


def _on_xla_enter(event, _value, **_kw):
    """jax.monitoring scalar listener: jax records a stage's start so.
    Traces nest (every jitted callee traced inside a trace reports its
    own), so each open one keeps the seconds of those inside it."""
    if event == _XLA_TRACE:
        opened = getattr(_tls, "xla_open", None)
        if opened is None:
            opened = _tls.xla_open = []
        opened.append(0.0)


def _on_xla_stage(event, duration, fun_name=None, **_kw):
    """jax.monitoring duration listener: a stage just ended on this
    thread."""
    stage = _XLA_STAGES.get(event)
    if stage is None:
        return
    t1 = time.perf_counter_ns()
    own, nested = duration, False
    if stage == "trace":
        opened = getattr(_tls, "xla_open", None)
        inside = opened.pop() if opened else 0.0
        own = max(duration - inside, 0.0)
        if opened:
            opened[-1] += duration
            nested = True
    names = [sp.name for sp in getattr(_tls, "stack", None) or ()]
    under = ("compile" if "compile" in names
             else "dispatch" if "dispatch" in names
             else "other" if names else "none")
    _runtime_series()["xla"][stage, under].inc(own)
    if not nested or duration * 1e9 >= RING_MIN_NS:
        child_span("xla." + stage, "host", t1 - int(duration * 1e9), t1,
                   note=fun_name)


def install_runtime_hooks():
    """Book what the runtime does beneath the program's spans: one
    callback on `gc.callbacks`, and two `jax.monitoring` listeners where
    jax is imported (this module never imports it first).  Idempotent;
    called where `fluid.executor` is imported, which every lane does
    before it builds, compiles or runs anything.  No flag: the hooks are
    on like the spans are, and `reset()` leaves them on.

    Collections: ``pt_host_gc_seconds_total{generation}`` for 0, 1, 2
    and ``any``, from a collection's ``start`` to its ``stop``.  They are
    counted and not put in the ring: a ``gc.gen<N>`` child would be the
    innermost span wherever it fell, and the benchmark's three idle
    shares (`benchmark/readers/host_gap.py`) are tested to sum to 100
    without it (PERF.md, open questions).

    Compile stages: ``xla.trace`` / ``xla.lower`` / ``xla.backend_compile``
    / ``xla.cache_read`` spans on lane ``host`` (end = the callback's
    clock reading, start = end - jax's duration, note = jax's
    ``fun_name``; a trace nested in another enters the ring from
    `RING_MIN_NS` up) and ``pt_xla_stage_seconds_total{stage,under}``.
    A cache read lies inside its backend compile and is counted in both.
    ``under`` is ``compile`` where a `compile` span is open on the thread
    (a signature's first run: its `dispatch` phase lies inside), else
    ``dispatch`` where a `dispatch` is (the compile nobody asked for: a
    jitted call that retraces), else ``other`` under any other span (a
    `jnp` call on a scheduler's path), else ``none`` (the caller's own
    jax)."""
    with _hooks_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        jx = sys.modules.get("jax")
        if not _xla_listening[0] and jx is not None:
            jx.monitoring.register_scalar_listener(_on_xla_enter)
            jx.monitoring.register_event_duration_secs_listener(
                _on_xla_stage)
            _xla_listening[0] = True
    _runtime_series()


def _beneath(t0_ns, t1_ns):
    """``[{"name", "ms"[, "note"]}]`` of every ``xla.*`` span that ended
    inside the interval, on any thread, oldest first.  The ring is in
    order of the spans' ends, so it is read from its tail."""
    out = []
    for name, _lane, s0, s1, _id, _parent, _number, note in reversed(spans()):
        if s1 < t0_ns - 1_000_000_000:  # threads append a little out of order
            break
        if t0_ns <= s1 <= t1_ns and name.startswith("xla."):
            ent = {"name": name, "ms": round((s1 - s0) / 1e6, 3)}
            if note is not None:
                ent["note"] = note
            out.append(ent)
    return out[::-1]


# ---------------------------------------------------------------------------
# per-signature stats + MFU/roofline
# ---------------------------------------------------------------------------

_lock = threading.RLock()
_signatures: dict = {}  # label -> stats dict
_lane_ema: dict = {}    # lane -> [ema, emvar, samples]


def _sig(label):
    s = _signatures.get(label)
    if s is None:
        s = _signatures[label] = {
            "label": label, "lane": None, "steps": 0,
            "total_s": 0.0, "ema_step_s": None,
            "device_s_sum": 0.0, "device_steps": 0,
            "flops": None, "bytes_accessed": None,
            "transcendentals": None, "collective_bytes": None,
            "collective_counts": None,
        }
    return s


_TPU_PEAKS = (
    # device_kind substring -> (bf16 flops/s, HBM bytes/s, ICI bytes/s)
    # public per-chip specs, approximate where vendors publish ranges;
    # first match wins so "v5e"/"lite" must precede the bare "v5" (v5p)
    ("v6", (918e12, 1640e9, 448e9)),
    ("v5p", (459e12, 2765e9, 600e9)),
    ("v5e", (197e12, 819e9, 200e9)),
    ("lite", (197e12, 819e9, 200e9)),
    ("v5", (459e12, 2765e9, 600e9)),
    ("v4", (275e12, 1228e9, 300e9)),
    ("v3", (123e12, 900e9, 87e9)),
    ("v2", (45e12, 700e9, 62e9)),
)

# order-of-magnitude placeholders for the CPU container (documented in
# docs/OBSERVABILITY.md): MFU against a CPU "peak" is a smoke-test
# number, not a claim — override via FLAGS_device_peak_* for anything
# that matters
_CPU_PEAKS = (1e11, 2.5e10, 1e9)


def device_peaks():
    """(platform, peak_flops/s, peak_hbm_bytes/s, peak_ici_bytes/s) for
    the process's device 0.  FLAGS_device_peak_flops /
    FLAGS_device_peak_bandwidth / FLAGS_device_peak_ici_bandwidth
    (nonzero) override the table entry-wise.  A TPU whose kind is not in
    the table is an error — it never borrows another device's numbers.
    Reads jax only when it is ALREADY imported — a /profilez scrape must
    never initialize a TPU runtime."""
    platform, peaks = "cpu", _CPU_PEAKS
    jx = sys.modules.get("jax")
    if jx is not None:
        dev = jx.devices()[0]
        platform = dev.platform
        if platform == "tpu":
            kind = dev.device_kind.lower()
            peaks = next((p for pat, p in _TPU_PEAKS if pat in kind), None)
            if peaks is None:
                raise ValueError(
                    f"no peaks recorded for TPU kind {dev.device_kind!r} "
                    f"— add it to _TPU_PEAKS with its source")
    flops = float(_flag("device_peak_flops", 0) or 0) or peaks[0]
    bw = float(_flag("device_peak_bandwidth", 0) or 0) or peaks[1]
    ici = float(_flag("device_peak_ici_bandwidth", 0) or 0) or peaks[2]
    return platform, flops, bw, ici


def roofline(flops, bytes_accessed, collective_bytes, peaks=None):
    """The roofline verdict for one step: time lower bounds at peak
    compute/memory/comm rates and which dominates.  `peaks` defaults to
    `device_peaks()`; any missing numerator contributes 0 (an
    unmeasured axis can never be named the bound)."""
    if peaks is None:
        _, pf, pbw, pici = device_peaks()
    else:
        pf, pbw, pici = peaks
    t = {
        "compute": (flops or 0.0) / max(pf, _EPS),
        "memory": (bytes_accessed or 0.0) / max(pbw, _EPS),
        "comm": (collective_bytes or 0.0) / max(pici, _EPS),
    }
    bound = max(t, key=t.get)
    return {"bound": bound if t[bound] > 0 else None,
            "t_compute_s": t["compute"], "t_memory_s": t["memory"],
            "t_comm_s": t["comm"]}


def _update_mfu(s):
    """Refresh the pt_mfu / pt_roofline_bound gauges for one signature
    (called under _lock whenever timing or cost changes)."""
    if not s["device_steps"] or not s["flops"]:
        return
    device_s = s["device_s_sum"] / s["device_steps"]
    if device_s <= 0:
        return
    _, pf, pbw, pici = device_peaks()
    mfu = s["flops"] / device_s / pf
    s["mfu"] = mfu
    _m_mfu().labels(signature=s["label"]).set(mfu)
    rl = roofline(s["flops"], s["bytes_accessed"],
                  s["collective_bytes"], peaks=(pf, pbw, pici))
    s["roofline"] = rl
    fam = _m_roofline()
    for bound in ("compute", "memory", "comm"):
        fam.labels(signature=s["label"], bound=bound).set(
            1.0 if rl["bound"] == bound else 0.0)


def note_cost(label, cost, collective_bytes=None):
    """Record a signature's XLA cost-model numbers (fed by
    `_JitExecutable.cost_analysis`).  `cost` is the cost_analysis dict
    ({"flops": ..., "bytes accessed": ...})."""
    get = cost.get if hasattr(cost, "get") else (lambda *_: None)
    with _lock:
        s = _sig(label)
        for key, field in (("flops", "flops"),
                           ("bytes accessed", "bytes_accessed"),
                           ("transcendentals", "transcendentals")):
            v = get(key)
            if v is not None:
                s[field] = float(v)
        if collective_bytes is not None:
            s["collective_bytes"] = float(collective_bytes)
        _update_mfu(s)


def note_collectives(label, hlo_bytes, counts=None):
    """Record a signature's compiled-HLO collective inventory (fed by
    the GSPMD executor's HLO capture)."""
    with _lock:
        s = _sig(label)
        s["collective_bytes"] = float(hlo_bytes)
        if counts is not None:
            s["collective_counts"] = dict(counts)
        _update_mfu(s)


def signature_stats():
    """Snapshot of the per-signature attribution table (tests + the
    /profilez render)."""
    with _lock:
        return {k: dict(v) for k, v in _signatures.items()}


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bounded ring of the last N steps' attribution records plus health
    events.  Dumps a JSONL postmortem on demand or automatically (slow
    step, health bad step); auto-dumps are rate-limited to once per half
    ring so an anomaly storm cannot write unbounded files."""

    def __init__(self, keep=None):
        self._lock = threading.Lock()
        # an explicit keep pins the size; the flag-sized default tracks
        # FLAGS_flight_recorder_steps live (a set_flags mid-run resizes
        # on the next record)
        self._keep_from_flags = keep is None
        self.keep = int(keep if keep is not None
                        else _flag("flight_recorder_steps", 256))
        self._ring = collections.deque(maxlen=max(1, self.keep))
        self._seq = 0
        self._since_dump = 0
        self._attempts = 0  # filename counter; advances on failures too
        self.dumps = 0      # successful writes only
        self.last_dump_path = None
        self.last_dump_reason = None

    def _resize_from_flags(self):
        if not self._keep_from_flags:
            return
        keep = int(_flag("flight_recorder_steps", self.keep))
        if keep != self.keep and keep >= 1:
            self.keep = keep
            self._ring = collections.deque(self._ring, maxlen=keep)

    def record(self, rec):
        with self._lock:
            self._resize_from_flags()
            self._seq += 1
            self._since_dump += 1
            rec = dict(rec, seq=self._seq, ts=round(time.time(), 6))
            self._ring.append(rec)

    def snapshot(self):
        with self._lock:
            return list(self._ring)

    def maybe_auto_dump(self, reason, detail=None):
        """Auto-trigger path: dump unless one already fired within the
        last keep//2 records (the postmortem window would mostly repeat
        itself)."""
        with self._lock:
            if self._since_dump < max(1, self.keep // 2) and self.dumps:
                return None
        return self.dump(reason=reason, detail=detail)

    def _resolve_dir(self):
        d = _flag("flight_recorder_dir", "")
        if d:
            return d
        d = os.environ.get("PT_EVENT_LOG_DIR") or _flag("event_log_dir",
                                                        "")
        # final fallback is the system tempdir, NOT the cwd: auto-dumps
        # fire from library code (a health bad step mid-test-suite), and
        # postmortems must never litter a caller's working tree
        import tempfile

        return d or tempfile.gettempdir()

    def dump(self, path=None, reason="explicit", detail=None):
        """Write the ring as a JSONL postmortem: one meta header line,
        then one line per record (oldest first).  Returns the path, or
        None when writing failed (losing a postmortem must never kill
        the run).  The dumps counter and the auto-dump rate-limit window
        commit only AFTER a successful write — a full disk must neither
        suppress the next trigger's attempt nor report phantom dumps on
        /profilez."""
        with self._lock:
            records = list(self._ring)
            # attempt counter (always advances): filename uniqueness
            # even across failed writes
            self._attempts += 1
            n_dump = self._attempts
        try:
            if path is None:
                d = self._resolve_dir()
                os.makedirs(d, exist_ok=True)
                path = os.path.join(
                    d, f"flight_{os.getpid()}_{n_dump:03d}.jsonl")
            meta = {"flight_record": 1, "reason": reason,
                    "ts": round(time.time(), 6), "keep": self.keep,
                    "records": len(records),
                    **_tracing.process_identity()}
            if detail:
                meta["detail"] = detail
            with open(path, "w", encoding="utf-8") as fh:
                fh.write(json.dumps(meta, default=str) + "\n")
                for rec in records:
                    fh.write(json.dumps(rec, default=str) + "\n")
        except OSError as e:
            warnings.warn(f"flight-recorder dump failed: {e}")
            return None
        with self._lock:
            self.dumps += 1
            self._since_dump = 0
            self.last_dump_path = path
            self.last_dump_reason = reason
        _m_flight_dumps().labels(reason=reason).inc()
        try:
            from . import events as _events

            if _events.enabled():
                _events.emit("flight_record_dump", reason=reason,
                             path=path, records=len(records))
        except Exception:
            pass
        return path

    def status(self):
        with self._lock:
            return {"keep": self.keep, "size": len(self._ring),
                    "steps_seen": self._seq, "dumps": self.dumps,
                    "last_dump_path": self.last_dump_path,
                    "last_dump_reason": self.last_dump_reason}


_flight = FlightRecorder()


def flight_recorder():
    return _flight


def dump_flight_record(path=None, reason="explicit"):
    """Explicitly write the flight-record postmortem (ops entry point)."""
    return _flight.dump(path=path, reason=reason)


def read_flight_record(path):
    """Parse one flight-record JSONL file -> (meta, records)."""
    with open(path, encoding="utf-8") as fh:
        lines = [json.loads(ln) for ln in fh if ln.strip()]
    if not lines:
        return {}, []
    return lines[0], lines[1:]


def _queue_depth_sample():
    """Best-effort prefetch queue depth at this step (None when the
    prefetcher never registered)."""
    fam = _metrics.REGISTRY.get("pt_prefetch_queue_depth")
    if fam is None:
        return None
    try:
        samples = fam._snapshot()["samples"]
        if not samples:
            return None
        return float(next(iter(samples.values())))
    except Exception:
        return None


# ---------------------------------------------------------------------------
# the step sink (fed by fluid.executor._record_step from every lane)
# ---------------------------------------------------------------------------


def note_step(lane, seconds=None, first_run=False):
    """Book one executed step into the attribution layer: per-signature
    stats (+ MFU refresh), the slow-step detector, and the flight
    recorder.  Consumes the phase breakdown the lane's
    `step_phases(...)` recorder deposited on this thread (if any);
    ``seconds=None`` uses the recorder's own step total."""
    pending = _pop_pending(lane)
    label, phases, blocked, t0, starts = lane, None, True, None, None
    if pending is not None:
        _plane, label, phases, total, blocked, t0, starts = pending
        if seconds is None:
            seconds = total
    if seconds is None:
        return
    ensure_profilez_page()
    slow = None
    with _lock:
        s = _sig(label)
        s["lane"] = lane
        s["steps"] += 1
        s["total_s"] += seconds
        if not first_run:
            # a signature's first run includes the lazy XLA compile —
            # folding it into the EMA/MFU would poison both
            prev = s["ema_step_s"]
            s["ema_step_s"] = (seconds if prev is None else
                               prev + (1.0 - _EMA_BETA) * (seconds - prev))
            if blocked:
                # device time needs an interval that held the device's
                # completion: a caller's own measurement (no recorder),
                # a blocked device_wait, or a lane that fetched inside
                # its recorder.  An async step's wall time is only its
                # enqueue (41.5 ms of a 128 ms BERT step, PERF.md), and
                # pt_mfu read off it over-states three-fold: leave the
                # gauges unset instead
                device_s = seconds
                if phases:
                    # dispatch + device_wait: from handing the step to
                    # jax to the computation's completion
                    device_s = (phases.get("dispatch", 0.0)
                                + phases.get("device_wait", 0.0)) or seconds
                s["device_s_sum"] += device_s
                s["device_steps"] += 1
                _update_mfu(s)
            # slow-step z-score over the per-lane rolling EMA (the PR-10
            # EMA machinery applied to wall time)
            zthresh = float(_flag("profile_slow_step_zscore", 8.0) or 0)
            ema = _lane_ema.setdefault(lane, [None, 0.0, 0])
            if ema[0] is None:
                ema[0] = seconds
            else:
                dev = seconds - ema[0]
                z = abs(dev) / ((ema[1] + _EPS) ** 0.5)
                if (zthresh > 0 and ema[2] >= 8 and dev > 0
                        and z > zthresh):
                    slow = {"z": round(z, 2), "ema_s": round(ema[0], 6)}
                ema[0] += (1.0 - _EMA_BETA) * dev
                ema[1] = _EMA_BETA * (ema[1]
                                      + (1.0 - _EMA_BETA) * dev * dev)
            ema[2] += 1
    rec = {"kind": "step", "lane": lane, "label": label,
           "seconds": round(seconds, 6), "first_run": bool(first_run)}
    if phases:
        rec["phases"] = {k: round(v, 6) for k, v in phases.items()}
        # where each phase began, seconds after the step's own start
        # (perf_counter ``t0``): a postmortem can lay the step out
        rec["t0"] = round(t0, 6)
        rec["phase_starts"] = {k: round(v, 6) for k, v in starts.items()}
    qd = _queue_depth_sample()
    if qd is not None:
        rec["prefetch_queue_depth"] = qd
    if slow is not None:
        rec["slow_step"] = slow
        # what the runtime did beneath it: the compile stages that ended
        # inside the step, whatever thread ran them
        end = time.perf_counter_ns()
        start = end - int(seconds * 1e9)
        if t0 is not None:
            start = min(start, int(t0 * 1e9))
        rec["beneath"] = _beneath(start, end)
    _flight.record(rec)
    if slow is not None:
        _flight.maybe_auto_dump(
            "slow_step", detail={"lane": lane, "seconds": seconds, **slow})


def note_health_event(kind, action, lane, step=None, replay=False):
    """Health-sentinel hook (health/sentinel.py books its bad-step
    metric through here too): the event lands in the flight ring and
    triggers the postmortem dump — a poisoned run leaves evidence."""
    _flight.record({"kind": "health", "event": "bad_step",
                    "detect": kind, "action": action, "lane": lane,
                    "step": step, "replay": bool(replay)})
    _flight.maybe_auto_dump(
        "health", detail={"detect": kind, "action": action, "lane": lane})


# ---------------------------------------------------------------------------
# HLO inventory (promoted from parallel/gspmd/executor.py)
# ---------------------------------------------------------------------------

_HLO_ITEMSIZE = {"s8": 1, "u8": 1, "pred": 1, "bf16": 2, "f16": 2,
                 "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4,
                 "f64": 8, "s64": 8, "u64": 8}

_COLLECTIVE_KINDS = ("all-to-all", "all-gather", "collective-permute",
                     "all-reduce", "reduce-scatter")

_COLLECTIVE_RE = re.compile(
    r"=\s+(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(" + "|".join(_COLLECTIVE_KINDS) + r")(-start)?\(")


def _shape_bytes(tok):
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", tok)
    if m is None:
        return 0
    dt, dims = m.groups()
    size = 1
    for d in dims.split(","):
        if d:
            size *= int(d)
    return size * _HLO_ITEMSIZE.get(dt, 4)


def hlo_inventory(hlo):
    """Per-category inventory of an optimized per-device SPMD HLO
    module's cross-device collectives: ``{kind: {"count": n, "bytes":
    b}}`` plus a ``total`` entry.  Async ``-start`` forms (TPU's
    start/done pairs) report a tuple that ALIASES the operand beside the
    result, so their tuple bytes are halved — else on-chip numbers would
    double-count against the sync-form CPU ones and every A/B that gates
    on them would be incomparable."""
    out = {}
    total_bytes = total_count = 0
    for m in _COLLECTIVE_RE.finditer(hlo):
        nbytes = sum(_shape_bytes(t)
                     for t in re.findall(r"[a-z0-9]+\[[0-9,]*\]",
                                         m.group(1)))
        if m.group(3):  # "-start": (operand alias, result) tuple
            nbytes //= 2
        kind = m.group(2)
        ent = out.setdefault(kind, {"count": 0, "bytes": 0})
        ent["count"] += 1
        ent["bytes"] += nbytes
        total_bytes += nbytes
        total_count += 1
    out["total"] = {"count": total_count, "bytes": total_bytes}
    return out


def hlo_collective_bytes(hlo):
    """Total output bytes of every cross-device collective instruction —
    the wire payload the executable moves per step (the accounting the
    ring wire-bytes cross-check and ``pt_gspmd_resharding_bytes`` use)."""
    return hlo_inventory(hlo)["total"]["bytes"]


def hlo_collective_counts(hlo):
    """{collective kind: instruction count} over an optimized HLO module."""
    inv = hlo_inventory(hlo)
    return {k: v["count"] for k, v in inv.items() if k != "total"}


# ---------------------------------------------------------------------------
# /profilez + the attribution digest
# ---------------------------------------------------------------------------


def _phase_quantiles():
    """{lane: {phase: {p50, p95, count}}} from the registry histogram."""
    fam = _metrics.REGISTRY.get("pt_step_phase_seconds")
    if fam is None:
        return {}
    out = {}
    snap = fam._snapshot()
    for key, h in snap["samples"].items():
        labels = dict(zip(snap["label_names"], key))
        lane = labels.get("lane", "?")
        phase = labels.get("phase", "?")
        out.setdefault(lane, {})[phase] = {
            "p50": _rq(_metrics.hist_quantile(h, 0.50)),
            "p95": _rq(_metrics.hist_quantile(h, 0.95)),
            "sum": round(h["sum"], 6),
            "count": h["count"],
        }
    return out


def _rq(v):
    return None if v is None else round(float(v), 6)


def _sig4(v):
    """4 significant figures at any magnitude — a tiny model's 1e-8 MFU
    must not round to 0 the way a fixed-decimal round would."""
    return None if v is None else float(f"{float(v):.4g}")


def _family_sum(name):
    fam = _metrics.REGISTRY.get(name)
    if fam is None:
        return 0.0
    total = 0.0
    snap = fam._snapshot()
    for sample in snap["samples"].values():
        total += sample["sum"] if isinstance(sample, dict) else sample
    return total


def feed_verdict():
    """The ROADMAP "feed is never the bottleneck" claim as a number:
    consumer stall seconds (pt_prefetch_stall_seconds_total — blocked on
    an empty queue AFTER the pipeline filled) over executed step seconds
    (pt_step_seconds sum).  feed_bound names stall fractions above 10%
    — the feed is eating step time, not hiding behind it."""
    stall = _family_sum("pt_prefetch_stall_seconds_total")
    steps = _family_sum("pt_step_seconds")
    frac = stall / steps if steps > 0 else 0.0
    return {"stall_seconds_total": round(stall, 6),
            "step_seconds_total": round(steps, 6),
            "stall_fraction": round(frac, 6),
            "feed_bound": bool(steps > 0 and frac > 0.10)}


def _signature_payload(s):
    out = {"lane": s["lane"], "steps": s["steps"],
           "avg_step_s": _rq(s["total_s"] / s["steps"])
           if s["steps"] else None,
           "ema_step_s": _rq(s["ema_step_s"])}
    if s["device_steps"]:
        out["device_s_avg"] = _rq(s["device_s_sum"] / s["device_steps"])
    for k in ("flops", "bytes_accessed", "transcendentals",
              "collective_bytes"):
        if s.get(k) is not None:
            out[k] = s[k]
    if s.get("collective_counts"):
        out["collective_counts"] = s["collective_counts"]
    if s.get("mfu") is not None:
        out["mfu"] = _sig4(s["mfu"])
    if s.get("roofline"):
        rl = s["roofline"]
        out["roofline"] = {"bound": rl["bound"],
                           "t_compute_s": _sig4(rl["t_compute_s"]),
                           "t_memory_s": _sig4(rl["t_memory_s"]),
                           "t_comm_s": _sig4(rl["t_comm_s"])}
    return out


def profilez_payload():
    """The /profilez body: the whole attribution surface as JSON."""
    platform, pf, pbw, pici = device_peaks()
    return {
        "device": {"platform": platform, "peak_flops": pf,
                   "peak_hbm_bytes_per_s": pbw,
                   "peak_ici_bytes_per_s": pici,
                   "phases_enabled": _phases_enabled()},
        "signatures": {label: _signature_payload(s)
                       for label, s in signature_stats().items()},
        "phase_seconds": _phase_quantiles(),
        "feed": feed_verdict(),
        "flight_recorder": _flight.status(),
    }


def attribution_digest():
    """The compact attribution record: phase quantiles, per-signature
    MFU + roofline verdict, and the feed-bound fraction — WHERE the step
    time went, as one JSON-able dict."""
    sigs = {}
    for label, s in signature_stats().items():
        ent = {"lane": s["lane"], "steps": s["steps"]}
        if s.get("mfu") is not None:
            ent["mfu"] = _sig4(s["mfu"])
        if s.get("roofline"):
            ent["roofline_bound"] = s["roofline"]["bound"]
        if s["device_steps"]:
            ent["device_s_avg"] = _rq(s["device_s_sum"]
                                      / s["device_steps"])
        sigs[label] = ent
    return {"phase_seconds": _phase_quantiles(),
            "signatures": sigs,
            "feed": feed_verdict(),
            "flight_recorder": _flight.status()}


_page_registered = False
_page_lock = threading.Lock()


def ensure_profilez_page():
    """Register /profilez on the process exposition servers (idempotent;
    called from the step sink so any process that runs steps serves the
    page)."""
    global _page_registered
    if _page_registered:
        return
    with _page_lock:
        if _page_registered:
            return
        try:
            from . import exposition as _expo

            _expo.register_page("/profilez", profilez_payload)
            _page_registered = True
        except ValueError:
            # a foreign renderer owns the path — leave it; never fatal
            _page_registered = True


def reset():
    """Drop all attribution state (tests).  The runtime hooks stay
    installed; the ledger keeps its ordinals (a program enqueued before
    may be waited for after) and forgets that the chip stood empty."""
    global _flight
    with _ledger_lock:
        _ledger.empty = None
    with _lock:
        _signatures.clear()
        _lane_ema.clear()
    _flight = FlightRecorder()
    _tls.pending = None
    _tls.stack = []
    _ring.clear()
