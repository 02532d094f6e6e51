"""Elastic membership and preemption-aware drain for distributed jobs.

Beyond parity (SURVEY §5: the reference has neither failure detection nor
elastic recovery; PR 3 added supervised restart but froze the job shape).
This module is the trainer-side half of the elastic PS protocol
(`native/src/ps_runtime.cc` kJoin/kLeave/kLease) plus the pieces both
lanes share:

- `join_job` / `leave_job` — membership lifecycle over the cached PS
  channels (`ops.dist_ops.get_channel`), with the launch-cohort
  rendezvous (`min_count`) and the poll-until-active join protocol.
- `LeaseHeartbeat` — a sidecar thread renewing each endpoint's lease on
  its OWN connection, so a member parked in a long compute phase (or a
  long barrier) is never mistaken for dead.
- `DrainHandler` — the graceful-preemption path: a chained SIGTERM hook
  (AutoCheckpoint precedent) that *requests* a drain; the training loop
  finishes the in-flight round, snapshots, announces LEAVE, then calls
  `finish()`, which writes the supervisor's drain marker and re-delivers
  the signal through the previously-installed handler chain.
- `reinit_collective` / `rebuild_mesh` — the collective/hybrid lane's
  rejoin: re-run the `jax.distributed` bootstrap (through the compat
  shim, tolerating older jax surfaces) and rebuild the device mesh at
  the new world size after a preemption changes it.

Per-shard membership: every pserver tracks its own member set (the same
join/leave/heartbeat traffic goes to each endpoint), and all shards see
the same graceful joins/leaves at the same round boundary.  For the
LIVE data-assignment view (epoch, index, count), trainers read one
reachable shard per round (`membership_any` walks the endpoint list, so
the loss of any single shard — including endpoints[0], the old sole
authority — never wedges the loop).  The RESUME position is stronger
than any single shard's view: trainers propose a quorum epoch record
(`commit_epoch`) to EVERY shard after each completed round, and
`agree_epoch` recovers the max-round record from the reachable quorum —
a relaunched shard reconciles its own snapshot against it instead of
trusting its file (docs/DISTRIBUTED.md §6 "Preemption and recovery").
"""

from __future__ import annotations

import os
import signal
import threading
import time

__all__ = ["join_job", "leave_job", "membership", "membership_any",
           "commit_epoch", "agree_epoch", "LeaseHeartbeat",
           "DrainHandler", "install_drain_handler", "drain_requested",
           "current_drain", "reinit_collective", "rebuild_mesh",
           "DRAIN_MARKER_ENV"]

# the supervisor (ProcGroup) exports this dir to children; a drained child
# drops `drained.<pid>` there so its exit-by-signal is classified as a
# clean LEAVE, not a crash charged against max_restarts
DRAIN_MARKER_ENV = "PT_DRAIN_NOTIFY_DIR"


def _heartbeats():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_ps_lease_heartbeats_total",
        "Client lease renewals by outcome (the sidecar heartbeat thread "
        "plus explicit membership() calls)", labels=("status",))


def membership(endpoint):
    """One lease renewal + membership view from `endpoint`: dict with
    epoch, round, version, count, index (-1 while pending / not a
    member)."""
    from paddle_tpu.ops import dist_ops

    info = dist_ops.get_channel(endpoint).client.lease_heartbeat()
    _heartbeats().labels(status="ok").inc()
    return info


_last_good_ep = None


def membership_any(endpoints):
    """The membership view from the first REACHABLE shard.  This
    replaces the hard shard-0 authority convention in trainer round
    loops: every shard applies joins/leaves at the same round boundary,
    so any live shard's view is a valid data-assignment view — and the
    loss of endpoints[0] mid-round no longer wedges every trainer's
    membership poll.

    Sticky ordering: the last endpoint that answered is tried FIRST, so
    a dead shard's full channel retry/backoff schedule is paid once at
    the failover, not on every subsequent poll.  (The query must ride
    the cached channel — its client uid is the membership being renewed;
    a fail-fast probe client would implicitly join a phantom member.)"""
    global _last_good_ep
    from paddle_tpu.distributed import resilience

    eps = list(endpoints)
    if _last_good_ep in eps:
        eps.remove(_last_good_ep)
        eps.insert(0, _last_good_ep)
    last_err = None
    for ep in eps:
        try:
            info = membership(ep)
            _last_good_ep = ep
            return info
        except IOError as e:
            last_err = e
            resilience.record("membership_fallbacks")
    raise IOError(
        f"membership_any: no reachable shard among {list(endpoints)}"
    ) from last_err


def commit_epoch(endpoints, round, epoch=0, position=None):
    """Propose the quorum epoch record (round + dataset position, and
    optionally the membership epoch) to EVERY shard; best-effort per
    endpoint — a dead shard is skipped (it reconciles from the quorum
    when it relaunches).  Returns the number of shards that acked, so a
    caller can assert majority when it needs the stronger guarantee.

    Rides the cached channels: the per-round caller
    (`_fetch_barrier_run`) commits immediately after every shard acked
    its fetch barrier, so the endpoints were provably alive moments
    earlier and the channel's retry schedule only engages in the tiny
    barrier→commit death window."""
    from paddle_tpu.distributed import resilience
    from paddle_tpu.ops import dist_ops

    acks = 0
    for ep in list(endpoints):
        try:
            dist_ops.get_channel(ep).client.commit_epoch(
                epoch, round, position)
            acks += 1
        except IOError:
            resilience.record("epoch_commit_failures")
    return acks


def agree_epoch(endpoints, timeout=None):
    """The QUORUM committed epoch record: query every reachable shard's
    kCommitEpoch record and return the max-round one (commits are
    monotone in round, so the max is the last record any majority
    accepted — it survives the loss of any single shard, including the
    old shard-0 data authority).  Returns the record dict extended with
    ``acks`` (shards that answered) — callers that need majority
    semantics check ``acks > len(endpoints) // 2``.  Raises IOError when
    NO shard is reachable."""
    from paddle_tpu import native
    from paddle_tpu.distributed import resilience

    endpoints = list(endpoints)
    best, acks, last_err = None, 0, None
    for ep in endpoints:
        host, port = ep.rsplit(":", 1)
        try:
            # a dedicated short-dial client, not the cached channel: the
            # agreement runs on the RESUME path where cached channels may
            # be parked in barrier rewaits or pointed at dead peers
            cli = native.PSClient(host=host, port=int(port),
                                  timeout=2.0 if timeout is None
                                  else timeout, retry_times=0,
                                  uid="epoch-agree")
            try:
                rec = cli.committed_epoch()
            finally:
                cli.close()
            acks += 1
            if best is None or (rec["round"], rec["epoch"]) > (
                    best["round"], best["epoch"]):
                best = rec
        except IOError as e:
            last_err = e
            resilience.record("epoch_agree_failures")
    if best is None:
        raise IOError(
            f"agree_epoch: no reachable shard among {endpoints}"
        ) from last_err
    return dict(best, acks=acks)


def join_job(endpoints, min_count=None, timeout_s=120.0, poll_s=0.05):
    """Join this trainer into an elastic PS job on every endpoint and
    block until the membership is ACTIVE everywhere (a mid-job join
    activates at the next round boundary).

    min_count: also wait until at least this many members are active on
    the authority shard — the launch-cohort rendezvous, so the initial
    trainers enter round 0 together with an agreed (epoch, index, count)
    instead of racing a smaller quorum ahead.  Defaults to
    PT_ELASTIC_JOIN_MIN, else PADDLE_TRAINERS_NUM for a fresh launch and
    1 for a supervised relaunch (the job is already running — waiting for
    the original cohort size would deadlock a shrunk job).

    Returns the authority shard's membership dict; each endpoint's
    channel round counter is synced to the join round so barriers and
    versioned pulls line up with the server."""
    from paddle_tpu.ops import dist_ops

    endpoints = list(endpoints)
    if min_count is None:
        env_min = os.environ.get("PT_ELASTIC_JOIN_MIN")
        if env_min:
            min_count = int(env_min)
        elif int(os.environ.get("PADDLE_RESTART_COUNT", "0") or 0) > 0:
            min_count = 1
        else:
            min_count = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1)
    deadline = time.monotonic() + float(timeout_s)
    for ep in endpoints:
        # the membership JOIN RPC (not a thread join): bounded by the
        # channel's rpc deadline + retry schedule
        dist_ops.get_channel(ep).client.join()  # resilience: allow
    info = None
    while True:
        active_everywhere = True
        for ep in endpoints:
            got = membership(ep)
            if ep == endpoints[0]:
                info = got
            if got["index"] < 0:
                active_everywhere = False
        if active_everywhere and info["count"] >= max(1, int(min_count)):
            break
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"join_job: not active on all of {endpoints} (or fewer "
                f"than {min_count} members) within {timeout_s}s; "
                f"last view: {info}")
        time.sleep(poll_s)
    # sync every channel's round counter to the join round: a mid-job
    # joiner's barriers and versioned recv waits must target the round it
    # is entering, not 0
    for ep in endpoints:
        ch = dist_ops.get_channel(ep)
        ch.round = max(ch.round, int(info["round"]))
        ch.client._rounds_done = ch.round
    from paddle_tpu.distributed import recovery
    from paddle_tpu.observability import events

    events.emit("elastic_join", endpoints=endpoints, **info)
    # recovery milestone: membership re-established (the drill harness's
    # `rejoin` phase anchor; no-op unless PT_RECOVERY_OUT is set)
    recovery.note("rejoin", round=info["round"], count=info["count"])
    return info


def leave_job(endpoints):
    """Announce a graceful LEAVE on every endpoint.  The leave applies at
    the next round boundary — the caller must still participate in the
    one in-flight round it announced the leave before (the drain sequence
    does exactly that).  Dead endpoints are skipped: leaving a job whose
    server already died must not raise on the way out."""
    from paddle_tpu.distributed import resilience
    from paddle_tpu.ops import dist_ops

    for ep in list(endpoints):
        try:
            dist_ops.get_channel(ep).client.leave()
        except IOError:
            resilience.record("leave_failures")
    from paddle_tpu.observability import events

    events.emit("elastic_leave", endpoints=list(endpoints))


class LeaseHeartbeat:
    """Sidecar lease renewal: one daemon thread, one DEDICATED connection
    per endpoint (the primary channel's connection may be parked in a
    barrier rendezvous for a whole round — a heartbeat queued behind it
    would defeat its purpose).  Each sidecar client shares the primary
    channel's uid so it renews the SAME membership."""

    def __init__(self, endpoints, interval_ms=None):
        from paddle_tpu.fluid import flags

        self._endpoints = list(endpoints)
        self._interval_s = (flags.flag("ps_lease_heartbeat_ms")
                            if interval_ms is None else interval_ms) / 1000.0
        self._stop = threading.Event()
        self._clients = {}
        self._thread = None

    def start(self):
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._run, name="pt-lease-heartbeat", daemon=True)
        self._thread.start()
        return self

    def _client(self, ep):
        from paddle_tpu import native
        from paddle_tpu.ops import dist_ops

        cli = self._clients.get(ep)
        if cli is None:
            host, port = ep.rsplit(":", 1)
            # short dial + no retry schedule: a missed beat is recorded
            # and the next tick re-dials — the heartbeat must never wedge
            # behind a dead endpoint for a full backoff schedule
            cli = native.PSClient(
                host=host, port=int(port), timeout=2.0, retry_times=0,
                uid=dist_ops.get_channel(ep).client.uid)
            self._clients[ep] = cli
        return cli

    def _run(self):
        from paddle_tpu.distributed import resilience

        while not self._stop.wait(self._interval_s):
            for ep in self._endpoints:
                try:
                    self._client(ep).lease_heartbeat()
                    _heartbeats().labels(status="ok").inc()
                except IOError:
                    _heartbeats().labels(status="error").inc()
                    resilience.record("lease_heartbeat_failures")
                    self._clients.pop(ep, None)  # re-dial next tick

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        for cli in self._clients.values():
            try:
                cli.close()
            except Exception:
                from paddle_tpu.distributed import resilience
                resilience.record("close_errors")
        self._clients.clear()


class DrainHandler:
    """Preemption-aware graceful drain: SIGTERM sets `requested` instead
    of killing the process; the training loop finishes the in-flight
    round (plus the one round its LEAVE was announced before), snapshots,
    and calls `finish()` — which drops the supervisor's drain marker,
    restores the previous handlers, and RE-DELIVERS the signal so the
    previously-installed chain (an AutoCheckpoint hook, the default
    action) runs at the right time: after the drain, not instead of it.

    The previous handlers are captured and chained (the bug class
    tools/lint_resilience.py's signal-no-chain check exists for): this
    handler defers the chain rather than invoking it inline, because the
    chain typically ENDS the process and the whole point is to finish the
    round first."""

    def __init__(self, signals=None):
        self.requested = threading.Event()
        self.signum = None
        self._signals = tuple(signals) if signals else (signal.SIGTERM,)
        self._prev = {}
        self._finished = False

    def install(self):
        for sig in self._signals:
            try:
                self._prev[sig] = signal.signal(sig, self._on_signal)
            except ValueError:  # non-main thread: cannot install
                break
        return self

    def _on_signal(self, signum, frame):
        # async-signal-safe on purpose: no locks, no IO — a real SIGTERM
        # can land while the main thread holds the event log's
        # non-reentrant lock, and an emit() here would deadlock the
        # process inside the handler.  The drain_requested event is
        # emitted from finish(), on a normal execution context.
        self.signum = signum
        self.requested.set()

    def marker_path(self):
        d = os.environ.get(DRAIN_MARKER_ENV, "")
        return os.path.join(d, f"drained.{os.getpid()}") if d else None

    def uninstall(self):
        """Restore the handlers active before install(); safe twice."""
        for sig in list(self._prev):
            prev = self._prev.pop(sig)
            try:
                # restoring, not registering a new hook: nothing to chain
                signal.signal(sig, prev if prev is not None  # resilience: allow
                              else signal.SIG_DFL)
            except ValueError:  # non-main thread: keep record for later
                self._prev[sig] = prev
                break

    def finish(self):
        """Complete the drain: marker for the supervisor, handlers
        restored, and — when a signal actually arrived — re-delivered so
        the previous chain (AutoCheckpoint snapshot, default termination)
        runs now that the round is finished.  Without a received signal
        (a `leave:` FaultPlan action or an API-driven drain) it simply
        returns and the caller exits normally."""
        import signal as _signal

        if self._finished:
            return
        self._finished = True
        marker = self.marker_path()
        if marker:
            try:
                os.makedirs(os.path.dirname(marker), exist_ok=True)
                with open(marker, "w") as f:
                    f.write(f"signum={self.signum}\n")
            except OSError:
                from paddle_tpu.distributed import resilience
                resilience.record("drain_marker_failures")
        from paddle_tpu.observability import events

        if self.signum is not None:
            events.emit("drain_requested", signum=int(self.signum))
        events.emit("drain_complete", signum=self.signum)
        self.uninstall()
        if self.signum is not None:
            _signal.raise_signal(self.signum)


_drain = None
_drain_lock = threading.Lock()


def install_drain_handler(signals=None):
    """Install (once) the process drain handler; returns it.  Idempotent:
    repeat calls return the existing handler."""
    global _drain
    with _drain_lock:
        if _drain is None:
            _drain = DrainHandler(signals=signals).install()
        return _drain


def current_drain():
    return _drain


def drain_requested() -> bool:
    return _drain is not None and _drain.requested.is_set()


# ---------------------------------------------------------------------------
# collective / hybrid lane: preemption-aware rejoin
# ---------------------------------------------------------------------------


def reinit_collective(coordinator_address=None, num_processes=None,
                      process_id=None):
    """Re-run the `jax.distributed` bootstrap after a membership change in
    the collective lane (a preempted host rejoining, or the job resized),
    tearing down an existing initialization first.  Defaults come from
    the launcher env contract (PADDLE_TRAINER_ENDPOINTS /
    PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ID), exactly what fleet.init
    reads."""
    import jax

    if coordinator_address is None:
        eps = [e for e in os.environ.get(
            "PADDLE_TRAINER_ENDPOINTS", "").split(",") if e]
        coordinator_address = eps[0] if eps else None
    if num_processes is None:
        num_processes = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1)
    if process_id is None:
        process_id = int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0)
    if coordinator_address is None or num_processes <= 1:
        return False  # single-process job: nothing to re-form
    if jax.distributed.is_initialized():
        jax.distributed.shutdown()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes), process_id=int(process_id))
    from paddle_tpu.observability import events

    events.emit("collective_reinit", coordinator=coordinator_address,
                num_processes=int(num_processes),
                process_id=int(process_id),
                n_devices=len(jax.devices()))
    return True


def rebuild_mesh(mp=1, sp=1, pp=1, ep=1, dp=None):
    """Rebuild the hybrid mesh over the CURRENT device set — after
    `reinit_collective` re-formed the job at a new size, the old mesh's
    device list is stale and every runner compiled against it must be
    re-specialized (`HybridParallelRunner.rebuild`)."""
    from paddle_tpu import parallel

    return parallel.build_hybrid_mesh(mp=mp, sp=sp, pp=pp, ep=ep, dp=dp)
