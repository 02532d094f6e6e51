"""Elastic membership and preemption-aware restart (ISSUE 7).

Fast tests run in-process against real loopback sockets: the membership
epoch protocol (join mid-job, graceful leave at a round boundary, evict
on lease expiry with barrier-count renegotiation), span-id propagation
through the PS RPC frame, the drain handler, the FaultPlan grammar
additions, and the supervisor's drained-vs-crash classification.  The
subprocess acceptance scenario (preempt one of three trainers, shrink,
regrow, loss parity + merged-trace attribution) is marked `slow`.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from net_util import free_port
from paddle_tpu import native
from paddle_tpu.distributed import (DrainHandler, FaultPlan, elastic,
                                    fault_injection, resilience_stats,
                                    reset_resilience_stats)
from paddle_tpu.distributed._proc_group import ProcGroup
from paddle_tpu.fluid import flags
from paddle_tpu.observability import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
RUNNER = os.path.join(HERE, "dist_ps_runner.py")


@pytest.fixture
def el_flags():
    old = flags.get_flags(["FLAGS_elastic_ps", "FLAGS_ps_lease_timeout_ms",
                           "FLAGS_ps_lease_heartbeat_ms",
                           "FLAGS_ps_snapshot_interval_s",
                           "FLAGS_rpc_retry_times"])
    reset_resilience_stats()
    yield flags
    flags.set_flags(old)
    fault_injection.uninstall()
    fault_injection.set_membership_hooks()
    reset_resilience_stats()


def _driver(srv, rounds, publish=None):
    """Minimal sync-loop driver for membership tests: wait → (publish) →
    release → end, `rounds` times."""
    def run():
        for _ in range(rounds):
            if not srv.wait_round():
                return
            if publish:
                publish()
            srv.bump_version()
            srv.release_send()
            if not srv.end_round():
                return
    t = threading.Thread(target=run)
    t.start()
    return t


def _round(client, r):
    client.send_barrier(round=r)
    client.fetch_barrier(round=r)


# ---------------------------------------------------------------------------
# membership epoch protocol (in-process, real sockets)
# ---------------------------------------------------------------------------


def test_join_idle_activates_and_reports_index(el_flags):
    srv = native.PSServer(port=0, n_trainers=99)
    srv.enable_elastic(lease_timeout_ms=0)
    try:
        a = native.PSClient(port=srv.port, uid="t:a")
        b = native.PSClient(port=srv.port, uid="t:b")
        ia = a.join()
        assert ia["count"] == 1 and ia["index"] == 0 and ia["round"] == 0
        ib = b.join()
        # idle job (round 0, nothing in flight): immediate activation,
        # deterministic index = rank among sorted uids
        assert ib["count"] == 2 and ib["index"] == 1
        assert a.membership()["index"] == 0
        st = srv.stats()
        assert st["members"] == 2 and st["joins"] == 2 and st["epoch"] == 2
        a.close()
        b.close()
    finally:
        srv.stop()


def test_join_mid_job_is_pending_until_round_boundary(el_flags):
    srv = native.PSServer(port=0, n_trainers=99, barrier_timeout_ms=0)
    srv.enable_elastic(lease_timeout_ms=0)
    a = native.PSClient(port=srv.port, uid="t:a")
    try:
        a.join()
        # run one round so the job is no longer idle-at-start
        d = _driver(srv, 1)
        _round(a, 0)
        d.join(timeout=20)
        b = native.PSClient(port=srv.port, uid="t:b")
        ib = b.join()
        assert ib["index"] == -1  # pending: a round already completed
        assert srv.stats()["members"] == 1  # not yet in the quorum
        # the next round completes with quorum 1; b activates at its end
        d = _driver(srv, 1)
        _round(a, 1)
        d.join(timeout=20)
        got = b.membership()
        assert got["index"] >= 0 and got["count"] == 2
        assert got["round"] == 2
        b.close()
        a.close()
    finally:
        srv.stop()


def test_graceful_leave_applies_at_next_boundary(el_flags):
    srv = native.PSServer(port=0, n_trainers=99, barrier_timeout_ms=0)
    srv.enable_elastic(lease_timeout_ms=0)
    a = native.PSClient(port=srv.port, uid="t:a")
    b = native.PSClient(port=srv.port, uid="t:b")
    try:
        a.join()
        b.join()
        d = _driver(srv, 1)
        ts = [threading.Thread(target=_round, args=(c, 0)) for c in (a, b)]
        [t.start() for t in ts]
        [t.join(timeout=20) for t in ts]
        d.join(timeout=20)
        # b announces LEAVE, then still participates in the round it
        # announced before — the leave applies at THAT round's boundary
        b.leave()
        assert srv.stats()["members"] == 2  # queued, not applied
        d = _driver(srv, 1)
        ts = [threading.Thread(target=_round, args=(c, 1)) for c in (a, b)]
        [t.start() for t in ts]
        [t.join(timeout=20) for t in ts]
        d.join(timeout=20)
        st = srv.stats()
        assert st["members"] == 1 and st["leaves"] == 1
        # the shrunk quorum completes alone
        d = _driver(srv, 1)
        _round(a, 2)
        d.join(timeout=20)
        assert srv.stats()["rounds"] == 3
        a.close()
        b.close()
    finally:
        srv.stop()


def test_lease_eviction_renegotiates_barrier_count(el_flags):
    """THE renegotiation property: a dead member's round completes with
    the survivors after one lease window — decisively under
    FLAGS_ps_barrier_timeout_ms (300 s default), which is what used to
    wedge the round."""
    srv = native.PSServer(port=0, n_trainers=99, barrier_timeout_ms=0)
    srv.enable_elastic(lease_timeout_ms=400)
    a = native.PSClient(port=srv.port, uid="t:a")
    b = native.PSClient(port=srv.port, uid="t:b")
    try:
        a.join()
        b.join()
        d = _driver(srv, 1)
        ts = [threading.Thread(target=_round, args=(c, 0)) for c in (a, b)]
        [t.start() for t in ts]
        [t.join(timeout=20) for t in ts]
        d.join(timeout=20)
        # b dies silently (no LEAVE, no heartbeat); a's round must not
        # wait out the barrier deadline
        b.close()
        t0 = time.monotonic()
        d = _driver(srv, 1)
        _round(a, 1)
        d.join(timeout=30)
        dt = time.monotonic() - t0
        st = srv.stats()
        assert st["evictions"] == 1 and st["members"] == 1
        assert st["rounds"] == 2
        assert dt < 10, f"renegotiation took {dt:.1f}s"
        a.close()
    finally:
        srv.stop()


def test_parked_survivor_is_never_evicted_by_its_own_wait(el_flags):
    """A member parked in its own send barrier while the round waits out
    a dead peer's lease must survive the renegotiation (its lease renews
    when the park releases)."""
    srv = native.PSServer(port=0, n_trainers=99, barrier_timeout_ms=0)
    srv.enable_elastic(lease_timeout_ms=300)  # shorter than the park below
    a = native.PSClient(port=srv.port, uid="t:a")
    b = native.PSClient(port=srv.port, uid="t:b")
    try:
        a.join()
        b.join()
        d = _driver(srv, 1)
        # a arrives immediately and parks; b never arrives → a's park
        # outlives the lease while it waits for b's eviction
        _round(a, 0)
        d.join(timeout=30)
        st = srv.stats()
        assert st["rounds"] == 1
        assert st["evictions"] == 1 and st["members"] == 1
        assert a.membership()["index"] == 0  # a survived
        a.close()
    finally:
        srv.stop()


def test_snapshot_restores_membership_quorum(el_flags, tmp_path):
    """An elastic shard's restart must restore its quorum: without the
    member section, a restarted server would renegotiate down to the
    first arrival and complete rounds with partial gradients."""
    srv = native.PSServer(port=0, n_trainers=99)
    srv.enable_elastic(lease_timeout_ms=0)
    a = native.PSClient(port=srv.port, uid="t:a")
    b = native.PSClient(port=srv.port, uid="t:b")
    a.join()
    b.join()
    srv.publish("w", np.arange(4, dtype=np.float32))
    snap = str(tmp_path / "shard.ckpt")
    assert srv.save(snap)
    a.close()
    b.close()
    srv.stop()

    srv2 = native.PSServer(port=0, n_trainers=99)
    srv2.enable_elastic(lease_timeout_ms=0)
    try:
        assert srv2.load(snap)
        st = srv2.stats()
        assert st["members"] == 2 and st["epoch"] == 2
        np.testing.assert_allclose(srv2.table_get("w"), np.arange(4))
    finally:
        srv2.stop()


def test_barrier_arrival_implicitly_joins_unknown_uid(el_flags):
    """A mid-protocol arrival from a uid the member set never saw (e.g.
    the server restarted from a snapshot predating that trainer's join)
    implicitly joins under the kJoin activation rule — immediately while
    the job is idle at round 0 — instead of skewing the quorum math."""
    srv = native.PSServer(port=0, n_trainers=99, barrier_timeout_ms=0)
    srv.enable_elastic(lease_timeout_ms=0)
    c = native.PSClient(port=srv.port, uid="t:ghost")
    try:
        d = _driver(srv, 1)
        _round(c, 0)
        d.join(timeout=20)
        st = srv.stats()
        assert st["members"] == 1 and st["joins"] == 1
        assert st["rounds"] == 1
        c.close()
    finally:
        srv.stop()


def test_unknown_arrival_mid_job_pends_until_boundary(el_flags):
    """An unknown uid arriving MID-JOB (an evicted member's delayed
    frame, a post-snapshot joiner) must NOT activate mid-round: an
    immediate activation would mutate the (epoch, index, count) view
    peers already sliced the round's data by, and its counted arrival
    would leak a permanent +1 into the quorum arithmetic.  It pends, the
    active quorum completes alone, and it enters at the boundary."""
    srv = native.PSServer(port=0, n_trainers=99, barrier_timeout_ms=0)
    srv.enable_elastic(lease_timeout_ms=0)
    a = native.PSClient(port=srv.port, uid="t:a")
    ghost = native.PSClient(port=srv.port, uid="t:ghost")
    try:
        a.join()
        d = _driver(srv, 1)
        _round(a, 0)
        d.join(timeout=20)  # job is past round 0 now
        # ghost arrives without ever joining, concurrent with a's round 1
        d = _driver(srv, 1)
        gt = threading.Thread(target=_round, args=(ghost, 1))
        at = threading.Thread(target=_round, args=(a, 1))
        gt.start()
        at.start()
        at.join(timeout=20)
        d.join(timeout=20)
        # the round completed; ghost joined but whether it activated for
        # THIS boundary depends on arrival timing — drive one more round
        # with both and the quorum must be exactly 2 (no leaked +1)
        gt.join(timeout=20)
        got = ghost.membership()
        assert got["index"] >= 0 and got["count"] == 2
        d = _driver(srv, 1)
        ts = [threading.Thread(target=_round, args=(c, 2))
              for c in (a, ghost)]
        [t.start() for t in ts]
        [t.join(timeout=20) for t in ts]
        d.join(timeout=20)
        assert srv.stats()["rounds"] == 3
        a.close()
        ghost.close()
    finally:
        srv.stop()


def test_dead_job_reforms_from_pending_joins(el_flags):
    """Every active member dies → the quorum renegotiates to zero; a NEW
    cohort joining a job parked in wait_round must activate there (the
    end_round activation point is unreachable) and complete a round —
    the full-restart re-form path."""
    srv = native.PSServer(port=0, n_trainers=99, barrier_timeout_ms=0)
    srv.enable_elastic(lease_timeout_ms=300)
    a = native.PSClient(port=srv.port, uid="t:a")
    try:
        a.join()
        d = _driver(srv, 1)
        _round(a, 0)
        d.join(timeout=20)
        a.close()  # the whole quorum dies silently (lease will expire)
        # driver parks in wait_round; a fresh cohort joins mid-wait
        d = _driver(srv, 1)
        b = native.PSClient(port=srv.port, uid="t:b")
        info = b.join()  # pending at join time (round_id > 0)...
        deadline = time.monotonic() + 20
        while info["index"] < 0 and time.monotonic() < deadline:
            time.sleep(0.05)
            info = b.membership()
        assert info["index"] >= 0, "pending join never re-formed the job"
        _round(b, 1)
        d.join(timeout=20)
        st = srv.stats()
        assert st["rounds"] == 2
        assert st["members"] == 1 and st["evictions"] == 1
        b.close()
    finally:
        srv.stop()


def test_join_is_idempotent_and_cancels_queued_leave(el_flags):
    srv = native.PSServer(port=0, n_trainers=99)
    srv.enable_elastic(lease_timeout_ms=0)
    a = native.PSClient(port=srv.port, uid="t:a")
    try:
        i1 = a.join()
        i2 = a.join()  # relaunched trainer under its stable uid
        assert (i1["count"], i1["index"]) == (i2["count"], i2["index"])
        assert srv.stats()["joins"] == 1
        a.leave()
        a.join()  # re-join cancels the queued leave
        # drive a boundary: idle fast-path already consumed the leave
        assert srv.stats()["members"] == 1
        a.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# span-id propagation (telemetry phase-2)
# ---------------------------------------------------------------------------


def test_wire_span_roundtrip_format():
    wire, s = tracing.new_wire_span()
    assert tracing.format_wire_span(wire) == s
    assert s.split("-")[0] == f"{os.getpid():x}"


def test_rpc_span_propagates_to_server_journal(el_flags):
    srv = native.PSServer(port=0, n_trainers=1)
    cli = native.PSClient(port=srv.port, timeout=5)
    try:
        srv.publish("w", np.ones(2, np.float32))
        srv.bump_version()
        cli.get_param("w")
        cli.send_grad("g", np.ones(2, np.float32))
        # the server journals a frame AFTER it replied to it: wait for
        # the last frame's entry, then both are in
        spans, deadline = srv.drain_spans(), time.monotonic() + 5
        while len(spans) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
            spans += srv.drain_spans()
        cmds = [c for c, *_ in spans]
        assert "get_param" in cmds and "send_grad" in cmds
        pid_hex = f"{os.getpid():x}"
        for cmd, span, start_wall, dur in spans:
            # the client pid is recoverable from the span id — that is
            # the "attribution across a restart" property
            assert span.split("-")[0] == pid_hex
            assert start_wall > 0 and dur >= 0
        # drained means drained
        assert srv.drain_spans() == []
        cli.close()
    finally:
        srv.stop()


def test_serve_spans_reach_profiler_and_events(el_flags, tmp_path,
                                               monkeypatch):
    """_drain_server_spans re-emits the journal as rpc_serve profiler
    spans (args.client_span) and serve_rpc JSONL events."""
    from paddle_tpu.fluid import profiler
    from paddle_tpu.observability import events
    from paddle_tpu.ops.dist_ops import _drain_server_spans

    srv = native.PSServer(port=0, n_trainers=1)
    cli = native.PSClient(port=srv.port, timeout=5)
    evpath = str(tmp_path / "ev.jsonl")
    events.configure(evpath)
    profiler.start_profiler()
    try:
        srv.publish("w", np.ones(2, np.float32))
        srv.bump_version()
        # the server journals a frame AFTER it replied to it: the second
        # reply says the first frame's entry is written
        cli.get_param("w")
        cli.get_param("w")
        _drain_server_spans(srv)
        trace = str(tmp_path / "trace.json")
        profiler.export_chrome_trace(trace)
        data = json.load(open(trace))
        serve = [e for e in data["traceEvents"]
                 if e.get("name", "").startswith("rpc_serve:")]
        assert serve, "no rpc_serve spans exported"
        assert any(e["args"].get("client_span") for e in serve)
        evs = [e for e in events.read_events(evpath)
               if e["event"] == "serve_rpc"]
        assert evs and evs[0]["client_span"]
    finally:
        profiler.stop_profiler(profile_path=str(tmp_path / "prof.txt"))
        profiler.reset_profiler()
        events.configure("/dev/null")
        cli.close()
        srv.stop()
        monkeypatch.delenv("PT_EVENT_LOG_DIR", raising=False)


# ---------------------------------------------------------------------------
# elastic module: join_job / leave_job / LeaseHeartbeat over channels
# ---------------------------------------------------------------------------


def test_join_job_syncs_channel_rounds_and_heartbeat(el_flags):
    from paddle_tpu.ops import dist_ops

    flags.set_flags({"FLAGS_ps_lease_heartbeat_ms": 100})
    srv = native.PSServer(port=0, n_trainers=99, barrier_timeout_ms=0)
    srv.enable_elastic(lease_timeout_ms=800)
    ep = f"127.0.0.1:{srv.port}"
    try:
        info = elastic.join_job([ep], min_count=1, timeout_s=20)
        assert info["index"] >= 0 and info["count"] == 1
        ch = dist_ops.get_channel(ep)
        assert ch.round == info["round"] == 0
        hb = elastic.LeaseHeartbeat([ep]).start()
        try:
            time.sleep(0.5)  # several beats; lease must stay warm
            assert srv.stats()["members"] == 1
            # the sidecar renews the SAME uid (no phantom member)
            assert elastic.membership(ep)["count"] == 1
        finally:
            hb.stop()
        elastic.leave_job([ep])
    finally:
        dist_ops.reset_channels()
        srv.stop()


def test_leave_job_survives_dead_endpoint(el_flags):
    from paddle_tpu.ops import dist_ops

    flags.set_flags({"FLAGS_rpc_retry_times": 0})
    srv = native.PSServer(port=0, n_trainers=99)
    srv.enable_elastic(lease_timeout_ms=0)
    ep = f"127.0.0.1:{srv.port}"
    try:
        elastic.join_job([ep], min_count=1, timeout_s=20)
        srv.stop()
        elastic.leave_job([ep])  # dead server: recorded, not raised
        assert resilience_stats()["leave_failures"] >= 1
    finally:
        dist_ops.reset_channels()


# ---------------------------------------------------------------------------
# FaultPlan grammar: preempt / join / leave
# ---------------------------------------------------------------------------


def test_fault_plan_parses_elastic_actions(el_flags):
    plan = FaultPlan("preempt:step:4;preempt:round:2;join:step:6;"
                     "leave:round:3;kill:step:9")
    assert len(plan.rules) == 5
    with pytest.raises(ValueError, match="bad fault rule"):
        FaultPlan("preempt:banana:1")
    with pytest.raises(ValueError):
        FaultPlan("join:step")  # missing count


def test_fault_plan_membership_hooks_dispatch(el_flags):
    fired = []
    fault_injection.set_membership_hooks(
        join=lambda k: fired.append(("join", k)),
        leave=lambda k: fired.append(("leave", k)))
    plan = fault_injection.install("join:step:2;leave:step:3")
    plan.on_step(1)
    plan.on_step(2)
    plan.on_step(3)
    assert fired == [("join", 2), ("leave", 3)]
    assert resilience_stats()["injected_faults"] == 2
    # unregistered hooks are a no-op, not an error
    fault_injection.set_membership_hooks()
    plan.on_step(2)


def test_fault_plan_preempt_delivers_sigterm(el_flags):
    got = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: got.append(s))
    try:
        plan = FaultPlan("preempt:step:2")
        plan.on_step(1)
        assert got == []
        plan.on_step(2)
        assert got == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, prev)  # resilience: allow


# ---------------------------------------------------------------------------
# DrainHandler
# ---------------------------------------------------------------------------


def test_drain_handler_defers_then_chains(el_flags, tmp_path, monkeypatch):
    """SIGTERM only REQUESTS the drain; finish() writes the marker and
    re-delivers through the previously-installed handler."""
    monkeypatch.setenv(elastic.DRAIN_MARKER_ENV, str(tmp_path / "drain"))
    chained = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: chained.append(s))
    h = DrainHandler().install()
    try:
        assert not h.requested.is_set()
        os.kill(os.getpid(), signal.SIGTERM)
        assert h.requested.is_set()
        assert chained == []  # deferred: the round finishes first
        h.finish()
        assert chained == [signal.SIGTERM]  # chain ran at drain end
        marker = tmp_path / "drain" / f"drained.{os.getpid()}"
        assert marker.exists()
        h.finish()  # idempotent
        assert chained == [signal.SIGTERM]
    finally:
        h.uninstall()
        signal.signal(signal.SIGTERM, prev)  # resilience: allow


def test_drain_handler_finish_without_signal_returns(el_flags, tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv(elastic.DRAIN_MARKER_ENV, str(tmp_path / "d2"))
    h = DrainHandler().install()
    try:
        h.requested.set()  # a leave: action, no signal
        h.finish()  # must not raise/kill
        assert (tmp_path / "d2" / f"drained.{os.getpid()}").exists()
    finally:
        h.uninstall()


# ---------------------------------------------------------------------------
# ProcGroup: structured exit events + drained classification
# ---------------------------------------------------------------------------


def _exit_script(tmp_path, body):
    p = tmp_path / "child.py"
    p.write_text(body)
    return str(p)


def test_proc_group_drained_child_not_restarted(tmp_path):
    """A child that drops its drain marker and dies by SIGTERM is a clean
    LEAVE: no restart against max_restarts, no job failure."""
    script = _exit_script(tmp_path, (
        "import os, signal\n"
        "d = os.environ['PT_DRAIN_NOTIFY_DIR']\n"
        "open(os.path.join(d, f'drained.{os.getpid()}'), 'w').close()\n"
        "signal.signal(signal.SIGTERM, signal.SIG_DFL)\n"
        "signal.raise_signal(signal.SIGTERM)\n"))
    group = ProcGroup(str(tmp_path / "logs"), restart_backoff=0.05)
    with group:
        child = group.spawn(script, [], dict(os.environ), "drained.log",
                            max_restarts=3)
        group.wait(workers=[child])  # must NOT raise
        assert child.poll() == -signal.SIGTERM
        assert child.restarts == 0  # never charged against the budget
        assert child.drained()
    assert group.drains_observed >= 1
    assert group.restarts_performed == 0


def test_proc_group_emits_structured_exit_events(tmp_path, monkeypatch):
    from paddle_tpu.observability import events

    evdir = tmp_path / "events"
    monkeypatch.setenv("PT_EVENT_LOG_DIR", str(evdir))
    events.configure()  # re-probe env
    try:
        script = _exit_script(tmp_path, "import sys; sys.exit(7)\n")
        group = ProcGroup(str(tmp_path / "logs"), restart_backoff=0.05)
        with group:
            child = group.spawn(
                script, [],
                dict(os.environ, TRAINING_ROLE="TRAINER",
                     PADDLE_TRAINER_ID="2"), "crash.log", max_restarts=1)
            with pytest.raises(subprocess.CalledProcessError):
                group.wait(workers=[child])
        recs = []
        for f in sorted(evdir.glob("*.jsonl")):
            recs += [e for e in events.read_events(str(f))
                     if e["event"] == "supervisor_child_exit"]
        assert recs, "no supervisor_child_exit events"
        # one event per incarnation: first crash + post-restart crash
        assert len(recs) == 2
        for e in recs:
            assert e["exit_code"] == 7 and e["kind"] == "crash"
            assert e["role"] == "trainer" and e["rank"] == 2
        assert recs[0]["restarts"] == 0 and recs[1]["restarts"] == 1
    finally:
        monkeypatch.delenv("PT_EVENT_LOG_DIR", raising=False)
        events.configure()


# ---------------------------------------------------------------------------
# collective/hybrid lane rejoin surface
# ---------------------------------------------------------------------------


def test_reinit_collective_noop_for_single_process(monkeypatch):
    monkeypatch.delenv("PADDLE_TRAINER_ENDPOINTS", raising=False)
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
    assert elastic.reinit_collective() is False  # nothing to re-form


def test_hybrid_runner_rebuild_drops_stale_executables():
    from paddle_tpu.parallel import HybridParallelRunner

    mesh = elastic.rebuild_mesh()  # whatever devices this process has
    runner = HybridParallelRunner(fluid.Program(), mesh)
    runner._cache["sig"] = object()
    runner._ran_keys.add("sig")
    runner.last_hlo = "stale"
    mesh2 = elastic.rebuild_mesh()
    assert runner.rebuild(mesh2) is runner
    assert runner.mesh is mesh2
    assert not runner._cache and not runner._ran_keys
    assert runner.last_hlo is None


# ---------------------------------------------------------------------------
# snapshot cadence
# ---------------------------------------------------------------------------


def test_snapshot_cadence_rounds_and_interval():
    from paddle_tpu.ops.dist_ops import _SnapshotCadence

    clock = [0.0]
    c = _SnapshotCadence(interval_s=0.0, every_rounds=2,
                         _clock=lambda: clock[0])
    assert [c.due(r) for r in (1, 2, 3, 4)] == [False, True, False, True]
    assert c.due(None) is False  # round-free lane, no interval: never

    c = _SnapshotCadence(interval_s=5.0, _clock=lambda: clock[0])
    assert c.due() is False
    clock[0] = 4.9
    assert c.due() is False
    clock[0] = 5.1
    assert c.due() is True   # interval elapsed
    assert c.due() is False  # window reset
    clock[0] = 10.5
    assert c.due(3) is True  # interval wins over the rounds rule


# ---------------------------------------------------------------------------
# acceptance (subprocess, slow): preempt → shrink → rejoin → parity
# ---------------------------------------------------------------------------


def _sub_env(extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PT_FAULT_PLAN", None)
    env.update({"DIST_PS_ELASTIC": "1", "DIST_PS_STEPS": "12",
                "FLAGS_elastic_ps": "1",
                "FLAGS_ps_lease_timeout_ms": "6000",
                "FLAGS_ps_lease_heartbeat_ms": "500",
                "FLAGS_rpc_retry_times": "8",
                "FLAGS_rpc_retry_backoff_ms": "200",
                "FLAGS_rpc_deadline": "30000",
                "DIST_PS_STEP_DELAY": "0.25"})
    env.update(extra or {})
    return env


@pytest.mark.slow
def test_elastic_preempt_shrink_regrow_loss_parity(tmp_path):
    """THE acceptance scenario: a 3-trainer elastic PS job loses trainer
    1 to a graceful preemption (SIGTERM via `preempt:step:4`) — the job
    completes that round with all three, shrinks to 2 without waiting
    out FLAGS_ps_barrier_timeout_ms, keeps converging, accepts a NEW
    trainer (id 3) joining mid-job, grows back to 3, and finishes with
    final parameters matching the uninterrupted single-process baseline
    to ≤1e-4.  A merged chrome trace attributes at least one server-side
    RPC span to the preempted client's span ids."""
    local_out = str(tmp_path / "local.json")
    subprocess.run([sys.executable, RUNNER, "local", "sgd", local_out],
                   env=_sub_env(), check=True, timeout=300)
    local = json.load(open(local_out))

    ep = f"127.0.0.1:{free_port()}"
    trace_dir = str(tmp_path / "traces")
    ev_dir = str(tmp_path / "events")
    drain_dir = str(tmp_path / "drain")
    os.makedirs(drain_dir, exist_ok=True)
    common = {"PT_TRACE_DIR": trace_dir, "PT_EVENT_LOG_DIR": ev_dir,
              "PT_DRAIN_NOTIFY_DIR": drain_dir,
              "PADDLE_TRAINERS_NUM": "3",
              "PT_TRACE_ID": "elastictest0000"}
    logs = {}
    procs = {}

    def spawn(name, args, extra=None):
        logs[name] = open(str(tmp_path / f"{name}.log"), "w")
        procs[name] = subprocess.Popen(
            [sys.executable, RUNNER] + args, env=_sub_env({**common,
                                                           **(extra or {})}),
            stdout=logs[name], stderr=logs[name])

    outs = {i: str(tmp_path / f"t{i}.json") for i in (0, 1, 2, 3)}
    spawn("ps0", ["pserver", ep, ep, "3", "sgd"],
          {"PT_TRACE_ROLE": "pserver", "PT_TRACE_RANK": "0"})
    spawn("t0", ["trainer", "0", ep, "3", "sgd", outs[0]],
          {"PADDLE_TRAINER_ID": "0"})
    spawn("t1", ["trainer", "1", ep, "3", "sgd", outs[1]],
          {"PADDLE_TRAINER_ID": "1", "PT_FAULT_PLAN": "preempt:step:4"})
    spawn("t2", ["trainer", "2", ep, "3", "sgd", outs[2]],
          {"PADDLE_TRAINER_ID": "2"})
    # the replacement trainer boots now (jax import is slow) but only
    # JOINS once the job reaches round 6 — the scale-up choreography
    spawn("t3", ["trainer", "3", ep, "3", "sgd", outs[3]],
          {"PADDLE_TRAINER_ID": "3", "PT_ELASTIC_JOIN_MIN": "1",
           "PT_ELASTIC_JOIN_AT_ROUND": "6"})
    try:
        deadline = time.monotonic() + 420
        for name in ("t0", "t2", "t3", "t1"):
            while procs[name].poll() is None:
                assert time.monotonic() < deadline, f"{name} wedged"
                time.sleep(0.5)
    finally:
        fluid.transpiler.stop_pservers([ep], connect_timeout=2.0)
        for name, p in procs.items():
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
        for f in logs.values():
            f.close()

    assert procs["t0"].returncode == 0
    assert procs["t2"].returncode == 0
    assert procs["t3"].returncode == 0
    # the preempted trainer died by the re-delivered SIGTERM, with the
    # drain marker dropped for the supervisor
    assert procs["t1"].returncode == -signal.SIGTERM
    t1 = json.load(open(outs[1]))
    assert t1["drained"]
    markers = os.listdir(drain_dir)
    assert any(m.startswith("drained.") for m in markers)

    t0 = json.load(open(outs[0]))
    # the job actually shrank to 2 and grew back to 3
    assert 2 in t0["counts"] and t0["counts"][0] == 3
    assert t0["counts"][-1] == 3
    assert t0["rounds"] == list(range(12))  # every round ran exactly once
    t3 = json.load(open(outs[3]))
    assert t3["rounds"] and t3["rounds"][0] >= 6  # joined mid-job

    # loss/parameter parity with the uninterrupted baseline
    for name, vals in local["params"].items():
        got = np.array(t0["params"][name])
        np.testing.assert_allclose(got, np.array(vals), rtol=0, atol=1e-4,
                                   err_msg=f"param {name} diverged")

    # merged-trace attribution: at least one server-side rpc_serve span
    # carries a span id minted by the preempted trainer (its pid prefix)
    sys.path.insert(0, os.path.join(HERE, os.pardir, "tools"))
    from merge_traces import merge

    traces = [os.path.join(trace_dir, f) for f in os.listdir(trace_dir)]
    assert traces, "no chrome traces exported"
    merged = merge(traces)
    t1_pid_hex = f"{procs['t1'].pid:x}"
    serve_spans = [e for e in merged["traceEvents"]
                   if e.get("name", "").startswith("rpc_serve:")
                   and str(e.get("args", {}).get("client_span", ""))
                   .startswith(t1_pid_hex + "-")]
    assert serve_spans, (
        "no server-side span attributed to the preempted client")
    # and the preempted client logged the same span ids on its side
    t1_event_files = [f for f in os.listdir(ev_dir)
                      if f.startswith("events_trainer1_")]
    assert t1_event_files
    client_spans = set()
    from paddle_tpu.observability import events as _events
    for f in t1_event_files:
        for e in _events.read_events(os.path.join(ev_dir, f)):
            if e["event"] == "rpc" and e.get("span_id"):
                client_spans.add(e["span_id"])
    assert {e["args"]["client_span"] for e in serve_spans} & client_spans
