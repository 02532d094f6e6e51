"""Unified telemetry: metrics registry semantics, Prometheus exposition
golden format, histogram edge cases, thread safety, the /metricsz HTTP
surface, JSONL events, trace identity, chrome-trace merging, and the
DataParallelRunner acceptance snapshot."""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import events as obs_events
from paddle_tpu.observability import exposition, metrics, tracing
from paddle_tpu.observability.exposition import ExpositionParseError

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "tools"))


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = metrics.MetricsRegistry()
    c = reg.counter("c_total", "help", labels=("k",))
    c.labels(k="a").inc()
    c.labels(k="a").inc(2.5)
    c.labels(k="b").inc()
    with pytest.raises(ValueError):
        c.labels(k="a").inc(-1)  # counters are monotonic
    assert c.labels(k="a").value == 3.5

    g = reg.gauge("g", "help")
    g.set(7)
    g.inc()
    g.dec(0.5)
    assert g.value == 7.5
    with pytest.raises(TypeError):
        reg.counter("c2_total").set(1)  # counters have no set()

    h = reg.histogram("h_seconds", "help", buckets=(1.0, 2.0))
    h.observe(0.5)
    h.observe(1.5)
    h.observe(99)
    data = h._default_child().hist_data()
    assert data["count"] == 3 and data["sum"] == 101.0
    assert data["buckets"] == [(1.0, 1), (2.0, 2), (float("inf"), 3)]


def test_register_idempotent_and_conflicts():
    reg = metrics.MetricsRegistry()
    a = reg.counter("x_total", "h", labels=("l",))
    b = reg.counter("x_total", "h", labels=("l",))
    assert a is b  # lazy call-site registration converges
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # type conflict
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("other",))  # label-schema conflict
    with pytest.raises(ValueError):
        a.labels(wrong="v")  # label names validated


def test_histogram_bucket_boundaries():
    """le semantics: a value exactly ON a bucket boundary lands in that
    bucket; negatives land in the first; inf in +Inf only."""
    reg = metrics.MetricsRegistry()
    h = reg.histogram("hb", "h", buckets=(0.0, 1.0, 10.0))
    for v in (-5.0, 0.0, 1.0, 1.0000001, 10.0, float("inf")):
        h.observe(v)
    data = h._default_child().hist_data()
    buckets = dict((le, c) for le, c in data["buckets"])
    assert buckets[0.0] == 2       # -5.0 and 0.0
    assert buckets[1.0] == 3       # + 1.0 (exactly on the boundary)
    assert buckets[10.0] == 5      # + 1.0000001 and 10.0
    assert buckets[float("inf")] == 6  # + inf itself
    assert data["count"] == 6


def test_hist_quantile():
    """PromQL histogram_quantile semantics over hist_data(): linear
    interpolation inside the winning bucket, lower bound 0 for the first,
    the +Inf bucket clamped to the largest finite le, None on empty."""
    reg = metrics.MetricsRegistry()
    h = reg.histogram("hq_seconds", "h", buckets=(0.1, 1.0, 10.0))
    # empty histogram: no estimate
    assert metrics.hist_quantile(h._default_child().hist_data(), 0.5) is None
    for v in (0.05, 0.05, 0.5, 0.5, 0.5, 0.5, 5.0, 5.0, 5.0, 100.0):
        h.observe(v)
    data = h._default_child().hist_data()
    # p50: rank 5 of 10 -> bucket (0.1, 1.0] with cum 2..6: 0.1 + 0.9*3/4
    assert metrics.hist_quantile(data, 0.5) == pytest.approx(0.775)
    # p90: rank 9 -> bucket (1.0, 10.0] cum 6..9: 1.0 + 9.0 * 3/3
    assert metrics.hist_quantile(data, 0.9) == pytest.approx(10.0)
    # max (q=1): rank 10 lands in +Inf -> clamp to the last finite le
    assert metrics.hist_quantile(data, 1.0) == pytest.approx(10.0)
    # q=0: the distribution's lower edge
    assert metrics.hist_quantile(data, 0.0) == pytest.approx(0.0)
    with pytest.raises(ValueError):
        metrics.hist_quantile(data, 1.5)
    # exported on the package root (callers reach it as
    # obs.hist_quantile)
    assert obs.hist_quantile is metrics.hist_quantile


def test_registry_thread_safety_smoke():
    reg = metrics.MetricsRegistry()
    c = reg.counter("t_total", labels=("w",))
    h = reg.histogram("t_seconds")
    n_threads, n_iter = 8, 500

    def work(i):
        for _ in range(n_iter):
            c.labels(w=str(i % 2)).inc()
            h.observe(0.001)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert all(not t.is_alive() for t in ts)
    total = sum(v for v in reg.snapshot()["t_total"]["samples"].values())
    assert total == n_threads * n_iter
    assert h._default_child().hist_data()["count"] == n_threads * n_iter


# ---------------------------------------------------------------------------
# exposition golden format
# ---------------------------------------------------------------------------


def _golden_registry():
    reg = metrics.MetricsRegistry()
    c = reg.counter("pt_rpc_total", "RPC attempts", labels=("cmd", "status"))
    c.labels(cmd="send_grad", status="ok").inc(4)
    c.labels(cmd='we"ird\\cmd\nx', status="ok").inc()
    g = reg.gauge("pt_depth", "queue depth")
    g.set(3)
    h = reg.histogram("pt_lat_seconds", "latency", labels=("cmd",),
                      buckets=(0.1, 1.0))
    h.labels(cmd="get_param").observe(0.05)
    h.labels(cmd="get_param").observe(5.0)
    return reg


def test_exposition_text_golden_roundtrip():
    reg = _golden_registry()
    text = exposition.render_text(reg.snapshot())
    lines = text.splitlines()
    # line-by-line syntax: HELP precedes TYPE precedes samples
    assert "# HELP pt_rpc_total RPC attempts" in lines
    assert "# TYPE pt_rpc_total counter" in lines
    assert 'pt_rpc_total{cmd="send_grad",status="ok"} 4' in lines
    # histogram expansion with cumulative buckets
    assert 'pt_lat_seconds_bucket{cmd="get_param",le="0.1"} 1' in lines
    assert 'pt_lat_seconds_bucket{cmd="get_param",le="1"} 1' in lines
    assert 'pt_lat_seconds_bucket{cmd="get_param",le="+Inf"} 2' in lines
    assert 'pt_lat_seconds_count{cmd="get_param"} 2' in lines
    # label escaping: backslash, quote, newline
    esc = [ln for ln in lines if "ird" in ln and not ln.startswith("#")]
    assert esc and r'\"' in esc[0] and r'\\' in esc[0] and r'\n' in esc[0]
    # strict parser round-trip (the golden contract)
    parsed = exposition.parse_text(text)
    assert parsed["pt_rpc_total"]["type"] == "counter"
    assert parsed["pt_lat_seconds"]["type"] == "histogram"
    labels = [l for l, v in parsed["pt_rpc_total"]["samples"]]
    assert {"cmd": 'we"ird\\cmd\nx', "status": "ok"} in labels
    # histogram samples attributed to the base family with sample kinds
    kinds = {l.get("__sample__") for l, v in
             parsed["pt_lat_seconds"]["samples"]}
    assert kinds == {"bucket", "sum", "count"}
    # count/sum values survive
    count = [v for l, v in parsed["pt_lat_seconds"]["samples"]
             if l.get("__sample__") == "count"]
    assert count == [2.0]


def test_exposition_parser_rejects_malformed():
    for bad in ('pt_x{l="v} 1',            # unterminated label
                'pt_x{l=v} 1',             # unquoted value
                'pt_x{l="v"}',             # missing value
                'pt_x{l="v"} notanumber',  # bad value
                'pt_x{abc} 1',             # label body without '='
                '# TYPE pt_x florp',       # bad type
                '1bad_name 2'):            # bad metric name
        with pytest.raises(ExpositionParseError):
            exposition.parse_text(bad)


def test_exposition_json_renders():
    reg = _golden_registry()
    data = json.loads(exposition.render_json(reg.snapshot()))
    assert data["pt_depth"]["samples"][0]["value"] == 3
    hist = data["pt_lat_seconds"]["samples"][0]
    assert hist["count"] == 2 and hist["buckets"][-1][0] == "+Inf"


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


def test_metrics_server_endpoints():
    reg = _golden_registry()
    srv = exposition.MetricsServer(port=0, registry=reg)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(base + "/metricsz", timeout=10).read()
        parsed = exposition.parse_text(body.decode())
        assert "pt_rpc_total" in parsed
        health = urllib.request.urlopen(base + "/healthz", timeout=10)
        assert health.read() == b"ok\n"
        status = json.loads(urllib.request.urlopen(
            base + "/statusz", timeout=10).read())
        assert status["pid"] == os.getpid()
        assert "trace_id" in status and "flags" in status
        jdump = json.loads(urllib.request.urlopen(
            base + "/metricsz.json", timeout=10).read())
        assert "pt_depth" in jdump
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)
    finally:
        srv.stop()


def test_metrics_port_flag_starts_server(monkeypatch):
    """FLAGS_metrics_port: executor construction exposes the process."""
    from net_util import free_port

    from paddle_tpu import fluid
    from paddle_tpu.fluid import flags

    port = free_port()
    old = flags.get_flags("FLAGS_metrics_port")
    flags.set_flags({"FLAGS_metrics_port": port})
    try:
        fluid.Executor(fluid.CPUPlace())
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metricsz", timeout=10).read()
        exposition.parse_text(body.decode())  # must parse
    finally:
        flags.set_flags(old)
        exposition.stop_server()


def test_metrics_port_bind_failure_warns_once():
    """A taken port latches disabled: one warning, no re-bind attempt per
    Executor construction."""
    import socket
    import warnings as w

    from paddle_tpu import fluid
    from paddle_tpu.fluid import flags

    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    port = blocker.getsockname()[1]
    old = flags.get_flags("FLAGS_metrics_port")
    flags.set_flags({"FLAGS_metrics_port": port})
    try:
        with w.catch_warnings(record=True) as rec:
            w.simplefilter("always")
            fluid.Executor(fluid.CPUPlace())
            fluid.Executor(fluid.CPUPlace())  # must not warn again
        warns = [r for r in rec if "cannot bind" in str(r.message)]
        assert len(warns) == 1, [str(r.message) for r in rec]
        assert exposition.active_server() is None
    finally:
        blocker.close()
        flags.set_flags(old)
        exposition.stop_server()  # clears the latched port


# ---------------------------------------------------------------------------
# events + tracing
# ---------------------------------------------------------------------------


def test_event_log_schema(tmp_path):
    log = obs_events.configure(str(tmp_path / "ev.jsonl"))
    try:
        obs_events.emit("step", step=3, seconds=0.01)
        obs_events.emit("round_end", round=1)
        recs = obs_events.read_events(str(tmp_path / "ev.jsonl"))
        assert [r["event"] for r in recs] == ["step", "round_end"]
        for r in recs:
            for field in ("ts", "mono", "run_id", "trace_id", "pid",
                          "role", "rank"):
                assert field in r, field
            assert r["pid"] == os.getpid()
        assert recs[0]["step"] == 3
        assert recs[0]["mono"] <= recs[1]["mono"]  # ordered
    finally:
        obs_events.configure()  # no env/flag -> disabled


def test_event_log_dir_env(tmp_path, monkeypatch):
    monkeypatch.setenv("PT_EVENT_LOG_DIR", str(tmp_path))
    obs_events.configure()  # re-probe
    try:
        assert obs_events.enabled()
        obs_events.emit("hello")
        files = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
        assert len(files) == 1 and files[0].startswith("events_")
    finally:
        monkeypatch.delenv("PT_EVENT_LOG_DIR")
        obs_events.configure()  # back to disabled
        assert not obs_events.enabled()


def test_event_log_uncreatable_dir_disables_not_raises(monkeypatch):
    """An uncreatable event-log dir must warn-and-disable — telemetry
    never kills training (emit is called from the executor hot path)."""
    import warnings as w

    monkeypatch.setenv("PT_EVENT_LOG_DIR", "/proc/nonexistent/dir")
    with w.catch_warnings(record=True) as rec:
        w.simplefilter("always")
        obs_events.configure()
        obs_events.emit("step")  # must be a no-op, not a crash
    assert not obs_events.enabled()
    assert any("event log disabled" in str(r.message) for r in rec)
    monkeypatch.delenv("PT_EVENT_LOG_DIR")
    obs_events.configure()


def test_trace_identity(monkeypatch):
    monkeypatch.setenv("PT_TRACE_ID", "deadbeef")
    assert tracing.job_trace_id() == "deadbeef"
    ident = tracing.process_identity()
    assert ident["trace_id"] == "deadbeef" and ident["pid"] == os.getpid()
    s1, s2 = tracing.new_span_id(), tracing.new_span_id()
    assert s1 != s2 and s1.startswith(f"{os.getpid():x}-")
    monkeypatch.setenv("PT_TRACE_ROLE", "pserver")
    assert tracing.process_role() == "pserver"
    # pservers have no PADDLE_TRAINER_ID: PT_TRACE_RANK wins
    monkeypatch.setenv("PT_TRACE_RANK", "3")
    assert tracing.process_rank() == 3
    assert tracing.process_identity()["rank"] == 3


# ---------------------------------------------------------------------------
# resilience back-compat view (shared registry underneath)
# ---------------------------------------------------------------------------


def test_resilience_stats_served_from_registry():
    from paddle_tpu.distributed import resilience

    resilience.reset_resilience_stats()
    stats = resilience.resilience_stats()
    # exact pre-registry shape: every known key present and zero
    assert set(resilience._KNOWN) <= set(stats)
    assert all(v == 0 for v in stats.values())
    resilience.record("rpc_retries")
    resilience.record("rpc_retries", 2)
    resilience.record("custom_event")
    stats = resilience.resilience_stats()
    assert stats["rpc_retries"] == 3 and isinstance(stats["rpc_retries"], int)
    assert stats["custom_event"] == 1
    # and the same numbers are visible on the shared registry surface
    snap = obs.snapshot()["pt_resilience_events_total"]["samples"]
    assert snap[("rpc_retries",)] == 3
    resilience.reset_resilience_stats()
    assert resilience.resilience_stats()["rpc_retries"] == 0


# ---------------------------------------------------------------------------
# chrome-trace merge
# ---------------------------------------------------------------------------


def _fake_trace(path, pid, wall_t0, name):
    data = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
         "args": {"name": name}},
        {"name": f"{name}:span", "cat": "host", "ph": "X", "ts": 10.0,
         "dur": 5.0, "pid": pid, "tid": 1, "args": {}},
    ], "displayTimeUnit": "ms",
        "ptMeta": {"pid": pid, "role": name, "rank": 0,
                   "trace_id": "t", "wall_t0": wall_t0}}
    with open(path, "w") as fh:
        json.dump(data, fh)


def test_merge_traces_aligns_and_keeps_pids(tmp_path):
    import merge_traces

    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    _fake_trace(a, pid=111, wall_t0=100.0, name="trainer0")
    _fake_trace(b, pid=222, wall_t0=100.5, name="pserver0")
    merged = merge_traces.merge([a, b])
    spans = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in spans} == {111, 222}
    # the later process's spans shifted by the wall-clock delta (0.5 s)
    ts = {e["pid"]: e["ts"] for e in spans}
    assert ts[111] == 10.0 and abs(ts[222] - (10.0 + 0.5e6)) < 1.0
    # metadata preserved per process
    names = [e["args"]["name"] for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert set(names) == {"trainer0", "pserver0"}


def test_merge_traces_remaps_pid_collision(tmp_path):
    import merge_traces

    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    _fake_trace(a, pid=7, wall_t0=1.0, name="t0")
    _fake_trace(b, pid=7, wall_t0=1.0, name="t1")  # recycled pid
    merged = merge_traces.merge([a, b])
    spans = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert len({e["pid"] for e in spans}) == 2  # both lanes survive


def test_merge_traces_remerge_terminates(tmp_path):
    """Re-merging a previously merged trace (pids congruent mod 1000 in
    one file) must terminate and keep every lane distinct — the synthetic
    pid allocator is monotone, never a fixed point."""
    import merge_traces

    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    # file b collides with a on BOTH pid 5 and its mod-1000 twin 1005
    for path, name in ((a, "x"), (b, "y")):
        data = {"traceEvents": [
            {"name": f"{name}{pid}", "cat": "host", "ph": "X", "ts": 1.0,
             "dur": 1.0, "pid": pid, "tid": 1, "args": {}}
            for pid in (5, 1005)],
            "ptMeta": {"wall_t0": 1.0, "role": name, "rank": 0,
                       "pid": 5, "trace_id": "t"}}
        json.dump(data, open(path, "w"))
    merged = merge_traces.merge([a, b])
    spans = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert len({e["pid"] for e in spans}) == 4  # 4 distinct lanes


def test_merge_traces_cli(tmp_path):
    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    _fake_trace(a, pid=1, wall_t0=1.0, name="x")
    _fake_trace(b, pid=2, wall_t0=1.0, name="y")
    out = str(tmp_path / "merged.json")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "..", "tools",
                                      "merge_traces.py"),
         "-o", out, "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    data = json.load(open(out))
    assert sum(1 for e in data["traceEvents"] if e["ph"] == "X") == 2
    assert "request trace(s)" in r.stdout


def test_merge_traces_builds_request_trace_index(tmp_path):
    """Serving spans (reqtrace lands them with args.trace/span ids) are
    indexed into ptRequestTraces: one request's spans across every
    merged pid, ordered by re-based start time — a hedged request's
    attempts line up across the replicas that ran them."""
    import merge_traces

    def span(name, ts, pid, args):
        return {"name": name, "cat": "serve", "ph": "X", "ts": ts,
                "dur": 2.0, "pid": pid, "tid": 1, "args": args}

    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    # replica A: the request root + winning attempt; replica B (wall
    # clock 1 s later): the cancelled hedge attempt + an untraced span
    json.dump({"traceEvents": [
        span("span:generate", 10.0, 1,
             {"kind": "request", "trace": "tr1", "span": "s-root"}),
        span("span:dispatch:fast", 12.0, 1,
             {"kind": "attempt", "trace": "tr1", "span": "s-win",
              "parent": "s-root", "links": ["s-batch"]}),
    ], "ptMeta": {"wall_t0": 100.0, "pid": 1, "role": "r0", "rank": 0,
                  "trace_id": "t"}}, open(a, "w"))
    json.dump({"traceEvents": [
        span("span:dispatch:slow", 3.0, 2,
             {"kind": "attempt", "trace": "tr1", "span": "s-lose",
              "parent": "s-root"}),
        span("run", 1.0, 2, {"kind": "run"}),  # no trace id: not indexed
    ], "ptMeta": {"wall_t0": 101.0, "pid": 2, "role": "r1", "rank": 0,
                  "trace_id": "t"}}, open(b, "w"))

    merged = merge_traces.merge([a, b])
    idx = merged["ptRequestTraces"]
    assert set(idx) == {"tr1"}
    recs = idx["tr1"]
    assert [r["span"] for r in recs] == ["s-root", "s-win", "s-lose"]
    assert {r["pid"] for r in recs} == {1, 2}  # spans across both lanes
    assert recs[1]["parent"] == "s-root"
    assert recs[1]["links"] == ["s-batch"]
    assert recs[1]["kind"] == "attempt"
    # ts is the MERGED (re-based) time: replica B's span sits 1 s after
    # replica A's epoch, so fan-in ordering is cross-process-correct
    assert abs(recs[2]["ts"] - (3.0 + 1e6)) < 1.0


# ---------------------------------------------------------------------------
# acceptance: 5-step DataParallelRunner snapshot
# ---------------------------------------------------------------------------


def _sum_samples(snap, name, **labels):
    fam = snap.get(name)
    if not fam:
        return 0.0
    total = 0.0
    for key, v in fam["samples"].items():
        kv = dict(zip(fam["label_names"], key))
        if all(kv.get(k) == str(val) for k, val in labels.items()):
            total += v["count"] if isinstance(v, dict) else v
    return total


def test_data_parallel_run_populates_snapshot():
    """Acceptance: a 5-step DataParallelRunner run leaves non-zero
    step-time histogram counts, compile-cache counters, and
    collective-bytes counters in observability.snapshot(), and the text
    exposition of that snapshot round-trips through the parser."""
    from paddle_tpu import fluid
    from paddle_tpu.fluid.executor import Scope, scope_guard

    base = obs.snapshot()
    steps0 = _sum_samples(base, "pt_step_seconds", path="dp")
    miss0 = _sum_samples(base, "pt_compile_cache_total", path="dp",
                         result="miss")
    hit0 = _sum_samples(base, "pt_compile_cache_total", path="dp",
                        result="hit")
    bytes0 = _sum_samples(base, "pt_collective_payload_bytes_total")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="obs_x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="obs_y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(0)
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        for _ in range(5):
            xb = rng.rand(16, 4).astype("float32")
            exe.run(prog, feed={"obs_x": xb,
                                "obs_y": xb.sum(1, keepdims=True)},
                    fetch_list=[loss.name])

    snap = obs.snapshot()
    assert _sum_samples(snap, "pt_step_seconds", path="dp") - steps0 == 5
    assert _sum_samples(snap, "pt_compile_cache_total", path="dp",
                        result="miss") - miss0 == 1
    assert _sum_samples(snap, "pt_compile_cache_total", path="dp",
                        result="hit") - hit0 == 4
    assert _sum_samples(snap, "pt_collective_payload_bytes_total") > bytes0
    assert _sum_samples(snap, "pt_examples_total", path="dp") >= 5 * 16
    # the whole live registry renders and round-trips strictly
    parsed = exposition.parse_text(exposition.render_text(snap))
    assert "pt_step_seconds" in parsed
    assert "pt_collective_payload_bytes_total" in parsed


def test_executor_cost_analysis_publishes_gauges():
    from paddle_tpu import fluid
    from paddle_tpu.fluid.executor import Scope, scope_guard

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("obs_ca_x", [4, 3], False, dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 2))
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"obs_ca_x": np.ones((4, 3), "float32")}
        exe.run(main, feed=feed, fetch_list=[loss.name])
        ca = exe.cost_analysis(main, feed, fetch_list=[loss.name])
    assert "cost" in ca
    fam = obs.snapshot().get("pt_xla_flops")
    assert fam and fam["samples"], "cost_analysis must publish gauges"


def test_prefetch_reports_queue_metrics():
    from paddle_tpu.fluid.prefetch import DatasetPrefetcher

    pre = DatasetPrefetcher(iter(range(8)), depth=2)
    assert list(pre) == list(range(8))
    snap = obs.snapshot()
    assert _sum_samples(snap, "pt_prefetch_batches_total") >= 8
    assert "pt_prefetch_queue_depth" in snap
