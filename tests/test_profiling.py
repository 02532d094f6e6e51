"""Step-time attribution (observability/profiling.py, ISSUE 11):
phase-decomposed step timing, MFU/roofline accounting, the flight
recorder, /profilez, and the feed-bound verdict."""

import cpu_mesh  # noqa: F401  (must precede any jax import)

import json
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability import profiling
from paddle_tpu.distributed import fault_injection
from paddle_tpu.fluid.executor import Scope, global_scope, scope_guard


@pytest.fixture
def attribution(tmp_path):
    """Fresh attribution state + phase flag armed; everything restored
    after (other tests share the module-global recorder/registry)."""
    names = ["FLAGS_profile_phases", "FLAGS_flight_recorder_steps",
             "FLAGS_flight_recorder_dir",
             "FLAGS_profile_slow_step_zscore",
             "FLAGS_device_peak_flops", "FLAGS_device_peak_bandwidth",
             "FLAGS_device_peak_ici_bandwidth"]
    prior = fluid.get_flags(names)
    fluid.set_flags({"FLAGS_profile_phases": True,
                     "FLAGS_flight_recorder_dir": str(tmp_path)})
    profiling.reset()
    yield tmp_path
    fluid.set_flags(prior)
    profiling.reset()


def _build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _feed(batch=8, seed=0):
    rng = np.random.RandomState(seed)
    xb = rng.uniform(-1, 1, (batch, 4)).astype("float32")
    return {"x": xb, "y": xb @ rng.uniform(-1, 1, (4, 1)).astype(
        "float32")}


# ---------------------------------------------------------------------------
# phase recorder units
# ---------------------------------------------------------------------------


def test_recorder_deposits_phases_and_total(attribution):
    with profiling.step_phases("single", "sig-a") as ph:
        with ph.phase("feed_prep"):
            time.sleep(0.01)
        with ph.phase("dispatch"):
            time.sleep(0.005)
    profiling.note_step("single", first_run=False)
    sigs = profiling.signature_stats()
    assert "sig-a" in sigs
    s = sigs["sig-a"]
    assert s["lane"] == "single" and s["steps"] == 1
    assert s["ema_step_s"] >= 0.015
    # the histogram booked both phases under the lane
    snap = obs.REGISTRY.snapshot()["pt_step_phase_seconds"]
    keys = set(snap["samples"])
    assert ("feed_prep", "single") in keys
    assert ("dispatch", "single") in keys


def test_recorder_flag_off_records_phases_and_never_blocks(
        attribution, monkeypatch):
    """The flag keeps only the per-step block: with it off the phases
    are still recorded (histogram, ring, flight record) and `wait()`
    syncs nothing."""
    import jax

    blocks = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocks.append(x) or x)
    fluid.set_flags({"FLAGS_profile_phases": False})
    fam = obs.REGISTRY.get("pt_step_phase_seconds")
    if fam is not None:
        fam.clear()
    with profiling.step_phases("dp", "sig-b") as ph:
        with ph.phase("dispatch"):
            pass
        with ph.phase("device_wait"):
            ph.wait(jax.numpy.zeros(2))  # must be a no-op, not a sync
    assert not blocks and ph.blocked is False
    profiling.note_step("dp", first_run=False)
    s = profiling.signature_stats()["sig-b"]
    assert s["steps"] == 1 and s["lane"] == "dp"
    # an unblocked step's wall time is its enqueue: no device time, so
    # pt_mfu cannot be refreshed from it
    assert s["device_steps"] == 0
    keys = set(obs.REGISTRY.get("pt_step_phase_seconds")
               ._snapshot()["samples"])
    assert {("dispatch", "dp"), ("device_wait", "dp")} <= keys
    # (lane ``host`` holds what the runtime did beneath them: the
    # `zeros` above compiles)
    assert [sp[0] for sp in profiling.spans()
            if sp[1] == "dp"] == ["dispatch", "device_wait"]
    rec = profiling.flight_recorder().snapshot()[-1]
    assert rec["label"] == "sig-b"
    assert set(rec["phases"]) == set(rec["phase_starts"]) == {
        "dispatch", "device_wait"}
    # flag on: the same bracket blocks, and the step counts as measured
    fluid.set_flags({"FLAGS_profile_phases": True})
    with profiling.step_phases("dp", "sig-b") as ph:
        with ph.phase("device_wait"):
            ph.wait(jax.numpy.zeros(2))
    assert len(blocks) == 1 and ph.blocked is True
    profiling.note_step("dp", first_run=False)
    assert profiling.signature_stats()["sig-b"]["device_steps"] == 1


def test_note_step_first_run_excluded_from_ema(attribution):
    profiling.note_step("single", 100.0, first_run=True)
    profiling.note_step("single", 0.01, first_run=False)
    s = profiling.signature_stats()["single"]
    assert s["steps"] == 2
    assert s["ema_step_s"] == pytest.approx(0.01)


# ---------------------------------------------------------------------------
# MFU / roofline
# ---------------------------------------------------------------------------


def test_roofline_verdicts():
    peaks = (100.0, 10.0, 1.0)  # flops/s, bytes/s, ici bytes/s
    assert profiling.roofline(1000, 1, 0, peaks)["bound"] == "compute"
    assert profiling.roofline(1, 1000, 0, peaks)["bound"] == "memory"
    assert profiling.roofline(1, 1, 1000, peaks)["bound"] == "comm"
    # nothing measured -> no verdict
    assert profiling.roofline(0, 0, 0, peaks)["bound"] is None
    # missing axes contribute zero, never win
    assert profiling.roofline(10, None, None, peaks)["bound"] == "compute"


def test_device_peaks_flag_overrides(attribution):
    fluid.set_flags({"FLAGS_device_peak_flops": 123.0,
                     "FLAGS_device_peak_bandwidth": 45.0,
                     "FLAGS_device_peak_ici_bandwidth": 6.0})
    _plat, pf, pbw, pici = profiling.device_peaks()
    assert (pf, pbw, pici) == (123.0, 45.0, 6.0)


def test_note_cost_sets_mfu_and_roofline_gauges(attribution):
    fluid.set_flags({"FLAGS_device_peak_flops": 1e6,
                     "FLAGS_device_peak_bandwidth": 1e3,
                     "FLAGS_device_peak_ici_bandwidth": 1e3})
    profiling.note_step("single", 1.0, first_run=True)   # compile
    profiling.note_step("single", 0.5, first_run=False)  # measured
    profiling.note_cost("single", {"flops": 1e5,
                                   "bytes accessed": 10.0})
    s = profiling.signature_stats()["single"]
    # mfu = 1e5 flops / (0.5 s * 1e6 flops/s) = 0.2
    assert s["mfu"] == pytest.approx(0.2)
    assert s["roofline"]["bound"] == "compute"
    snap = obs.REGISTRY.snapshot()
    assert snap["pt_mfu"]["samples"][("single",)] == pytest.approx(0.2)
    rl = snap["pt_roofline_bound"]["samples"]
    assert rl[("single", "compute")] == 1.0
    assert rl[("single", "memory")] == 0.0


def test_note_collectives_feeds_comm_axis(attribution):
    fluid.set_flags({"FLAGS_device_peak_flops": 1e12,
                     "FLAGS_device_peak_bandwidth": 1e12,
                     "FLAGS_device_peak_ici_bandwidth": 1.0})
    profiling.note_step("gspmd", 0.5, first_run=False)
    profiling.note_cost("gspmd", {"flops": 1.0, "bytes accessed": 1.0})
    profiling.note_collectives("gspmd", 1000.0,
                               counts={"all-reduce": 2})
    s = profiling.signature_stats()["gspmd"]
    assert s["roofline"]["bound"] == "comm"
    assert s["collective_counts"] == {"all-reduce": 2}


# ---------------------------------------------------------------------------
# HLO inventory (the promoted gspmd parser)
# ---------------------------------------------------------------------------

_HLO = """
  %ar = f32[256,4]{1,0} all-reduce(f32[256,4] %p0), replica_groups={}
  %ag = s8[1024]{0} all-gather(s8[512] %q), dimensions={0}
  %cp = (f32[128]{0}, f32[128]{0}) collective-permute-start(f32[128] %x)
  %dot = f32[64,64]{1,0} dot(f32[64,64] %a, f32[64,64] %b)
"""


def test_hlo_inventory_categories_and_bytes():
    inv = profiling.hlo_inventory(_HLO)
    assert inv["all-reduce"] == {"count": 1, "bytes": 256 * 4 * 4}
    assert inv["all-gather"] == {"count": 1, "bytes": 1024}
    # -start tuple aliases its operand: bytes halved
    assert inv["collective-permute"] == {"count": 1, "bytes": 128 * 4}
    assert inv["total"]["count"] == 3
    assert "dot" not in inv


def test_hlo_reexports_agree_with_inventory():
    from paddle_tpu.parallel.gspmd import (hlo_collective_bytes,
                                           hlo_collective_counts)

    inv = profiling.hlo_inventory(_HLO)
    assert hlo_collective_bytes(_HLO) == inv["total"]["bytes"]
    assert hlo_collective_counts(_HLO) == {
        "all-reduce": 1, "all-gather": 1, "collective-permute": 1}


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_is_bounded(attribution):
    fr = profiling.FlightRecorder(keep=4)
    for i in range(10):
        fr.record({"kind": "step", "i": i})
    snap = fr.snapshot()
    assert len(snap) == 4
    assert [r["i"] for r in snap] == [6, 7, 8, 9]
    assert snap[-1]["seq"] == 10


def test_flight_dump_writes_valid_jsonl(attribution, tmp_path):
    for i in range(5):
        profiling.note_step("single", 0.001, first_run=False)
    path = profiling.dump_flight_record(
        path=str(tmp_path / "fr.jsonl"))
    meta, records = profiling.read_flight_record(path)
    assert meta["flight_record"] == 1 and meta["reason"] == "explicit"
    assert meta["records"] == len(records) == 5
    assert all(r["kind"] == "step" for r in records)
    # every line is standalone JSON (the postmortem contract)
    with open(path) as fh:
        for line in fh:
            json.loads(line)
    snap = obs.REGISTRY.snapshot()["pt_flight_dumps_total"]
    assert snap["samples"][("explicit",)] >= 1.0


def test_slow_step_zscore_triggers_auto_dump(attribution):
    fluid.set_flags({"FLAGS_profile_slow_step_zscore": 4.0})
    for _ in range(20):
        profiling.note_step("dp", 0.01, first_run=False)
    assert profiling.flight_recorder().dumps == 0
    profiling.note_step("dp", 10.0, first_run=False)  # massive outlier
    fr = profiling.flight_recorder()
    assert fr.dumps == 1 and fr.last_dump_reason == "slow_step"
    meta, records = profiling.read_flight_record(fr.last_dump_path)
    assert records[-1]["slow_step"]["z"] > 4.0


def test_health_event_triggers_dump_and_rides_ring(attribution):
    profiling.note_step("single", 0.01, first_run=False)
    profiling.note_health_event("grad", "skip", "single", step=3)
    fr = profiling.flight_recorder()
    assert fr.dumps == 1 and fr.last_dump_reason == "health"
    _meta, records = profiling.read_flight_record(fr.last_dump_path)
    assert records[-1] == {
        **records[-1], "kind": "health", "event": "bad_step",
        "detect": "grad", "action": "skip", "lane": "single"}


def test_failed_dump_does_not_consume_rate_limit(attribution):
    """A write failure (unwritable dir) must not commit the dumps
    counter or reset the rate-limit window: the NEXT trigger must still
    attempt a postmortem, and /profilez must not report phantom dumps."""
    fluid.set_flags(
        {"FLAGS_flight_recorder_dir": "/proc/no/such/dir"})
    profiling.note_step("single", 0.01, first_run=False)
    with pytest.warns(UserWarning, match="dump failed"):
        assert profiling.dump_flight_record() is None
    fr = profiling.flight_recorder()
    assert fr.dumps == 0 and fr.last_dump_path is None
    # a health trigger right after the failure still attempts (and,
    # with a writable dir restored, succeeds)
    fluid.set_flags({"FLAGS_flight_recorder_dir": str(attribution)})
    profiling.note_health_event("grad", "skip", "single")
    assert fr.dumps == 1 and fr.last_dump_reason == "health"


def test_auto_dumps_rate_limited(attribution):
    fluid.set_flags({"FLAGS_flight_recorder_steps": 10})
    profiling.reset()  # pick up the smaller ring
    profiling.note_health_event("grad", "skip", "x")
    profiling.note_health_event("grad", "skip", "x")
    fr = profiling.flight_recorder()
    assert fr.dumps == 1  # second event inside the half-ring window
    for _ in range(6):
        fr.record({"kind": "step"})
    profiling.note_health_event("grad", "skip", "x")
    assert fr.dumps == 2  # window elapsed -> dump again


# ---------------------------------------------------------------------------
# end-to-end: injected bad step dumps a postmortem (acceptance)
# ---------------------------------------------------------------------------


def test_injected_nan_grad_dumps_postmortem(attribution):
    prior = fluid.get_flags(["FLAGS_health_sentinel",
                             "FLAGS_health_action"])
    fluid.set_flags({"FLAGS_health_sentinel": True,
                     "FLAGS_health_action": "skip"})
    fault_injection.install("nan:grad:step:2")
    try:
        main, startup, loss = _build()
        with scope_guard(Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for i in range(4):
                exe.run(main, feed=_feed(seed=i),
                        fetch_list=[loss.name])
        fr = profiling.flight_recorder()
        assert fr.dumps >= 1 and fr.last_dump_reason == "health"
        meta, records = profiling.read_flight_record(fr.last_dump_path)
        assert meta["flight_record"] == 1
        health = [r for r in records if r.get("kind") == "health"]
        assert health and health[0]["detect"] == "grad"
        steps = [r for r in records if r.get("kind") == "step"]
        assert steps and all("phases" in r for r in steps)
    finally:
        fluid.set_flags(prior)
        fault_injection.uninstall()


# ---------------------------------------------------------------------------
# acceptance: 20-step DP run — phase sum vs wall, /profilez scrape
# ---------------------------------------------------------------------------


def test_dp_phase_breakdown_sums_to_step_wall(attribution):
    from paddle_tpu.parallel import DataParallelRunner

    main, startup, loss = _build()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        runner = DataParallelRunner(main, loss.name)
        feed = _feed(batch=16)
        runner.run(exe, feed, [loss.name], scope)  # warm/compile
        profiling.reset()  # drop the compile step from both sides
        obs.REGISTRY.get("pt_step_phase_seconds").clear()
        obs.REGISTRY.get("pt_step_seconds").clear()
        t0 = time.perf_counter()
        for _ in range(20):
            runner.run(exe, feed, [loss.name], scope)
        wall = time.perf_counter() - t0
    snap = obs.REGISTRY.snapshot()
    phase_sum = sum(
        h["sum"] for key, h in
        snap["pt_step_phase_seconds"]["samples"].items()
        # the recorder's four phases: `lookup` and `fetch_wait` run
        # before it opens and after it closes, outside pt_step_seconds
        if key[1] == "dp" and key[0] in profiling.PHASES)
    step_hist = snap["pt_step_seconds"]["samples"][("dp",)]
    assert step_hist["count"] == 20
    # the acceptance bar: the named phases account for the step time —
    # within 10% of the measured per-step wall (phases nest inside the
    # step, so the gap is pure recorder/dispatch overhead)
    assert phase_sum <= step_hist["sum"] * 1.001
    assert phase_sum >= step_hist["sum"] * 0.90, (
        f"phase sum {phase_sum:.4f}s vs step sum "
        f"{step_hist['sum']:.4f}s — breakdown lost >10%")
    # and the step histogram itself tracks the loop wall
    assert step_hist["sum"] <= wall
    # per-signature stats populated for the dp label
    sigs = profiling.signature_stats()
    dp = [s for s in sigs.values() if s["lane"] == "dp"]
    assert dp and dp[0]["steps"] == 20


def test_profilez_served_through_real_scrape(attribution):
    from paddle_tpu.parallel import DataParallelRunner

    fluid.set_flags({"FLAGS_device_peak_flops": 1e9,
                     "FLAGS_device_peak_bandwidth": 1e9,
                     "FLAGS_device_peak_ici_bandwidth": 1e9})
    main, startup, loss = _build()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        runner = DataParallelRunner(main, loss.name)
        feed = _feed(batch=16)
        for _ in range(3):
            runner.run(exe, feed, [loss.name], scope)
        runner.cost_analysis(exe, feed, fetch_list=[loss.name],
                             scope=scope)
    srv = obs.MetricsServer(port=0)
    try:
        body = urllib.request.urlopen(
            f"http://{srv.host}:{srv.port}/profilez", timeout=10).read()
        page = json.loads(body)
    finally:
        srv.stop()
    # per-signature MFU + roofline verdict served over a real scrape
    dp_sigs = {k: v for k, v in page["signatures"].items()
               if v.get("lane") == "dp"}
    assert dp_sigs
    sig = next(iter(dp_sigs.values()))
    assert sig["mfu"] > 0
    assert sig["roofline"]["bound"] in ("compute", "memory", "comm")
    assert "feed_prep" in page["phase_seconds"]["dp"]
    assert page["feed"]["stall_fraction"] >= 0.0
    assert page["flight_recorder"]["size"] > 0
    assert page["device"]["phases_enabled"] is True
    # the attribution digest mirrors the same surface
    digest = profiling.attribution_digest()
    assert set(digest) == {"phase_seconds", "signatures", "feed",
                           "flight_recorder"}


# ---------------------------------------------------------------------------
# feed-bound verdict
# ---------------------------------------------------------------------------


def test_prefetch_stall_excludes_pipeline_fill(attribution):
    from paddle_tpu.fluid.prefetch import DatasetPrefetcher

    def slow_iter():
        for i in range(4):
            time.sleep(0.03)
            yield {"i": np.array([i])}

    def counter_value():
        fam = obs.REGISTRY.get("pt_prefetch_stall_seconds_total")
        if fam is None:
            return 0.0
        return fam._snapshot()["samples"].get((), 0.0)

    before = counter_value()  # process-cumulative across the suite
    pf = DatasetPrefetcher(slow_iter(), depth=1)
    list(pf)
    # waited on every batch, but batch 1's wait is pipeline fill
    assert pf.wait_seconds > pf.stall_seconds > 0
    assert counter_value() - before == pytest.approx(pf.stall_seconds,
                                                     rel=1e-6)


def test_feed_verdict_ratio(attribution):
    # the two families are process-cumulative: clear them so the ratio
    # below is exactly what this test booked
    for fam in ("pt_prefetch_stall_seconds_total", "pt_step_seconds"):
        f = obs.REGISTRY.get(fam)
        if f is not None:
            f.clear()
    obs.REGISTRY.counter(
        "pt_prefetch_stall_seconds_total", "test").inc(0.5)
    obs.REGISTRY.histogram("pt_step_seconds", "test",
                           labels=("path",)).labels(
        path="single").observe(1.0)
    v = profiling.feed_verdict()
    assert v["stall_seconds_total"] == pytest.approx(0.5)
    assert v["feed_bound"] is True
    assert v["stall_fraction"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# serving latency split (satellite)
# ---------------------------------------------------------------------------


def test_serving_latency_split_books_and_surfaces(attribution, tmp_path):
    from paddle_tpu import serving

    model_dir = str(tmp_path / "m")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        pred = fluid.layers.fc(x, size=2, act="softmax")
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    engine = serving.Engine({"m": model_dir}, auto_start=False)
    try:
        engine.warmup()
        engine.start()
        xb = np.random.rand(1, 4).astype("float32")
        for _ in range(3):
            engine.infer("m", {"x": xb}, timeout=30)
        stats = engine.stats()["models"]["m"]
        assert stats["queue_wait_seconds"]["count"] == 3
        assert stats["execute_seconds"]["count"] == 3
        assert stats["latency_seconds"]["p99"] >= 0
        snap = obs.REGISTRY.snapshot()
        for fam in ("pt_serve_queue_wait_seconds",
                    "pt_serve_execute_seconds"):
            h = snap[fam]["samples"][("m",)]
            assert h["count"] == 3
        # the split halves bound the total: wait + execute ≈ latency
        lat = snap["pt_serve_request_latency_seconds"]["samples"][("m",)]
        qw = snap["pt_serve_queue_wait_seconds"]["samples"][("m",)]
        ex = snap["pt_serve_execute_seconds"]["samples"][("m",)]
        assert qw["sum"] + ex["sum"] == pytest.approx(
            lat["sum"], rel=0.05, abs=0.05)
    finally:
        engine.close()
