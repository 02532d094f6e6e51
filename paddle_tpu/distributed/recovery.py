"""Measured preempt→restore: recovery phases, MTTR, and the drill
harness (docs/DISTRIBUTED.md §6 "Preemption and recovery").

On preemptible fleets mean-time-to-recovery is a first-class perf
number: a job that recovers in 30 s on eviction beats one that recomputes
an epoch.  This module makes the preempt→restore path *measured* instead
of hoped-for:

- **Phase booking** (`pt_recovery_seconds{phase}`): every recovery
  decomposes into five phases —

    detect      signal delivered → process observed dead (teardown +
                supervision poll latency)
    relaunch    death observed → the replacement process spawned
                (supervisor backoff included: it is real recovery time)
    restore     process start → durable state restored (PS shard
                snapshot load + epoch reconcile, AutoCheckpoint /
                rollback-window restore)
    rejoin      restore → membership re-established (elastic join,
                quorum sync; a pserver counts its serve loop becoming
                round-ready)
    first_step  rejoin → the first training step/round completed by the
                new incarnation — the moment the job is actually moving

- **Milestone notes** (`note()`): library code on the restore path
  appends milestones to the JSONL file named by ``PT_RECOVERY_OUT``
  (exported per-child by the drill harness; zero cost when unset).

- **Drill harness** (`run_drill`): an orchestrated multi-process drill
  driven by the FaultPlan grammar (``drill:preempt+restore:step:N``) —
  the HARNESS delivers the signal (so the kill instant is a measured
  anchor, not a guess), supervises the relaunch (respawning a drained
  preempt target itself; a SIGKILL target rides the supervisor's
  restart budget), correlates its own clock with the child's milestone
  notes, books the phases, and reports per-target MTTR.

- **In-process drill** (`inprocess_drill`, ``make recovery-drill``):
  the fast rung — train, simulate a preemption by dropping every live
  object, restore through the persisted rollback window, and assert
  final-state parity against an uninterrupted baseline.  Books the
  restore/first_step phases (detect/relaunch are multi-process-only)
  and prints the report with its per-phase seconds and MTTR.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import time

__all__ = ["PHASES", "RECOVERY_OUT_ENV", "book_phase", "note",
           "read_notes", "run_drill", "inprocess_drill"]

PHASES = ("detect", "relaunch", "restore", "rejoin", "first_step")
RECOVERY_OUT_ENV = "PT_RECOVERY_OUT"


def _m_recovery():
    from paddle_tpu import observability as obs

    return obs.histogram(
        "pt_recovery_seconds",
        "Preemption-recovery time by phase (detect = death observed, "
        "relaunch = replacement spawned, restore = durable state "
        "loaded, rejoin = membership re-established, first_step = the "
        "new incarnation's first completed step) — one sample per "
        "recovered role per drill/real recovery",
        labels=("phase",))


def book_phase(phase, seconds):
    """Book one recovery-phase sample (clamped at 0 — cross-process
    wall-clock deltas on one host can jitter slightly negative)."""
    if phase not in PHASES:
        raise ValueError(f"unknown recovery phase {phase!r}; "
                         f"known: {PHASES}")
    _m_recovery().labels(phase=phase).observe(max(0.0, float(seconds)))


def note(milestone, **fields):
    """Append one recovery milestone to the file named by
    ``PT_RECOVERY_OUT`` (set per-child by the drill harness).  Wall
    timestamps let the harness correlate across processes on one host.
    Best-effort and near-zero-cost when the env is unset — library
    restore paths call this unconditionally."""
    path = os.environ.get(RECOVERY_OUT_ENV, "")
    if not path:
        return False
    rec = {"milestone": str(milestone),
           # cross-process wall anchor, not step timing
           "t": time.time(),  # observability: allow
           "pid": os.getpid(), **fields}
    try:
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        from paddle_tpu.distributed import resilience

        resilience.record("recovery_note_failures")
        return False
    return True


def read_notes(path):
    """Parse a PT_RECOVERY_OUT milestone file; torn trailing lines are
    dropped (the writer may have died mid-append — that is the point)."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    except OSError:
        # an absent notes file IS the "role never reached a milestone"
        # answer the caller handles — resilience: allow
        pass
    return out


# ---------------------------------------------------------------------------
# the multi-process drill harness
# ---------------------------------------------------------------------------


class _RoundWatch:
    """Poll the job's committed progress (the pserver round counter via
    the kLease non-member query) without joining it.  Walks the endpoint
    list so the loss of any one shard — including a drill target — never
    blinds the harness."""

    def __init__(self, endpoints):
        self._endpoints = list(endpoints)
        self._clients = {}

    def poll(self):
        from paddle_tpu import native

        for ep in self._endpoints:
            cli = self._clients.get(ep)
            try:
                if cli is None:
                    host, port = ep.rsplit(":", 1)
                    cli = native.PSClient(host=host, port=int(port),
                                          timeout=1.0, retry_times=0,
                                          uid="drill-watch")
                    self._clients[ep] = cli
                return cli.membership()["round"]
            except IOError:
                self._close_one(ep)
        return None

    def _close_one(self, ep):
        cli = self._clients.pop(ep, None)
        if cli is not None:
            try:
                cli.close()
            except Exception:
                from paddle_tpu.distributed import resilience

                resilience.record("close_errors")

    def close(self):
        for ep in list(self._clients):
            self._close_one(ep)


def _phases_from_notes(notes, t_spawn_wall, t_kill_wall):
    """Milestone wall times from the relaunched incarnation → per-phase
    durations.  Only milestones stamped AFTER the respawn count (the
    first incarnation may have noted its own cold start)."""
    t_restore = t_rejoin = t_first = None
    for rec in notes:
        t = float(rec.get("t", 0.0))
        if t < t_spawn_wall - 0.001:
            continue
        m = rec.get("milestone")
        if m == "restore" and t_restore is None:
            t_restore = t
        elif m == "rejoin" and t_rejoin is None:
            t_rejoin = t
        elif m == "first_step" and t_first is None:
            t_first = t
    phases = {}
    prev = t_spawn_wall
    # chain in OCCURRENCE order: a role may legitimately rejoin before
    # it restores (the elastic trainer joins the quorum, then pulls) —
    # each phase is the delta from the previous observed milestone
    seen = sorted((t, name) for name, t in (
        ("restore", t_restore), ("rejoin", t_rejoin),
        ("first_step", t_first)) if t is not None)
    for t, name in seen:
        phases[name] = max(0.0, t - prev)
        prev = max(prev, t)
    mttr = (t_first - t_kill_wall) if t_first is not None else None
    return phases, mttr


def run_drill(roles, watch_endpoints, *, spec=None, rules=None,
              log_dir, default_target=None, restart_backoff=0.25,
              poll_s=0.02, kill_settle_s=0.1, timeout_s=600.0):
    """Run an orchestrated preempt→restore drill.

    roles: [{"name", "script", "args", "env", "max_restarts"=0,
    "worker"=False}] spawned under one supervised ProcGroup; every child
    gets ``PT_RECOVERY_OUT`` pointing at its milestone file.

    rules (or a FaultPlan ``spec`` — default FLAGS_recovery_drill):
    the ``drill:`` grammar; each rule names the job step/round at which
    the harness delivers SIGTERM (``preempt+restore``) or SIGKILL
    (``kill+restore``) to its target role.  A drained preempt target is
    respawned BY THE HARNESS (the supervisor deliberately classifies a
    drain as clean); a SIGKILL target rides the supervisor's restart
    budget — give it ``max_restarts``.

    Progress is watched through ``watch_endpoints`` (the pserver round
    counter via a non-member lease query).  Both ``step:`` and
    ``round:`` rule spellings key on that WATCHED round counter: in the
    sync PS lane trainer steps and pserver rounds advance in lockstep
    (one round per step), so the spelling documents which role's clock
    the drill author means — the harness has no way to observe a
    trainer's private step count from outside.  Returns the report dict:
    per-rule phases + MTTR (also booked into ``pt_recovery_seconds``),
    and the supervisor's restart count.  Raises on job failure or when
    ``timeout_s`` elapses."""
    from paddle_tpu.distributed import fault_injection
    from paddle_tpu.distributed._proc_group import ProcGroup
    from paddle_tpu.fluid import flags as _flags
    from paddle_tpu.observability import events

    if rules is None:
        if spec is None:
            spec = _flags.flag("recovery_drill")
        rules = fault_injection.FaultPlan(spec or "").drill_rules()
    if not rules:
        raise ValueError(
            "run_drill: no drill rules (pass rules=, spec=, or set "
            "FLAGS_recovery_drill to e.g. 'drill:preempt+restore:step:4')")

    os.makedirs(log_dir, exist_ok=True)
    group = ProcGroup(log_dir, restart_backoff=restart_backoff)
    children, note_paths = {}, {}
    workers = []
    with group:
        for r in roles:
            env = dict(r["env"])
            npath = os.path.join(log_dir, f"recovery.{r['name']}.jsonl")
            env[RECOVERY_OUT_ENV] = npath
            child = group.spawn(r["script"], r["args"], env,
                                f"log.{r['name']}",
                                max_restarts=r.get("max_restarts", 0))
            children[r["name"]] = child
            note_paths[r["name"]] = npath
            if r.get("worker"):
                workers.append(child)
        if not workers:
            raise ValueError("run_drill: at least one role needs "
                             "worker=True (the job-completion signal)")

        states = []
        for rule in rules:
            target = rule["target"] or default_target
            if target not in children:
                raise ValueError(
                    f"run_drill: drill target {target!r} is not a "
                    f"spawned role ({sorted(children)})")
            states.append({"rule": rule, "name": target, "st": {}})

        watch = _RoundWatch(watch_endpoints)
        deadline = time.monotonic() + float(timeout_s)
        failed = None
        try:
            while failed is None:
                if time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"run_drill: job did not complete within "
                        f"{timeout_s}s (states: {states})")
                rnd = watch.poll()
                for ent in states:
                    self_rule, st = ent["rule"], ent["st"]
                    child = children[ent["name"]]
                    if "t_kill" not in st:
                        if (rnd is not None and rnd >= self_rule["n"]
                                and "t_armed" not in st):
                            # settle before delivering: a pserver's
                            # per-round snapshot lands milliseconds
                            # after the round counter this watch reads
                            # becomes observable — killing inside that
                            # sliver would make the "exact at a round
                            # boundary" recovery contract flaky
                            st["t_armed"] = time.monotonic()
                        if ("t_armed" in st and time.monotonic()
                                - st["t_armed"] >= kill_settle_s):
                            st["pid"] = child.proc.pid
                            st["t_kill"] = time.monotonic()
                            # cross-process wall anchor for the child's
                            # milestone notes, not step timing
                            st["t_kill_wall"] = time.time()  # observability: allow
                            sig = (signal.SIGTERM
                                   if self_rule["mode"].startswith(
                                       "preempt") else signal.SIGKILL)
                            try:
                                os.kill(st["pid"], sig)
                            except ProcessLookupError:
                                st["t_death"] = st["t_kill"]
                            events.emit("drill_fault", target=ent["name"],
                                        mode=self_rule["mode"],
                                        at=self_rule["n"], pid=st["pid"])
                    elif "t_death" not in st:
                        if (child.proc.pid == st["pid"]
                                and child.poll() is not None):
                            st["t_death"] = time.monotonic()
                    elif "t_respawn" not in st:
                        if self_rule["mode"].startswith("preempt"):
                            # the drain marker classifies this exit as
                            # clean, so the supervisor will NOT restart
                            # it — the harness respawns (that IS the
                            # "+restore" half of the drill)
                            group.respawn(child)
                            st["t_respawn"] = time.monotonic()
                            st["t_spawn_wall"] = time.time()  # observability: allow
                        elif child.proc.pid != st["pid"]:
                            # the supervisor's budget relaunched it
                            st["t_respawn"] = time.monotonic()
                            st["t_spawn_wall"] = time.time()  # observability: allow
                # one shared supervision pass (the exact ProcGroup.wait
                # semantics — failure/drain classification lives there)
                failed = group.supervise_once()
                if failed is None:
                    if all(c.finished_clean() for c in workers):
                        break
                    time.sleep(poll_s)
        finally:
            watch.close()
        if failed:
            raise subprocess.CalledProcessError(failed[0], failed[1])

        # -- phase booking ------------------------------------------------
        report = {"targets": [], "restarts": group.restarts_performed}
        for ent in states:
            st = ent["st"]
            if "t_kill" not in st:
                report["targets"].append(
                    {"target": ent["name"], "fired": False})
                continue
            phases = {}
            if "t_death" in st:
                phases["detect"] = st["t_death"] - st["t_kill"]
            if "t_respawn" in st and "t_death" in st:
                phases["relaunch"] = st["t_respawn"] - st["t_death"]
            mttr = None
            if "t_spawn_wall" in st:
                child_phases, mttr = _phases_from_notes(
                    read_notes(note_paths[ent["name"]]),
                    st["t_spawn_wall"], st["t_kill_wall"])
                phases.update(child_phases)
            for name, secs in phases.items():
                book_phase(name, secs)
            report["targets"].append({
                "target": ent["name"], "fired": True,
                "mode": ent["rule"]["mode"], "at": ent["rule"]["n"],
                "phases": {k: round(v, 4) for k, v in phases.items()},
                "mttr_s": None if mttr is None else round(mttr, 4)})
            events.emit("drill_recovered", target=ent["name"],
                        phases=phases, mttr_s=mttr)
    return report


# ---------------------------------------------------------------------------
# the fast in-process drill (make recovery-drill)
# ---------------------------------------------------------------------------


def _build_drill_model():
    """Deterministic fc regression (the dist_ps_runner model class) —
    small enough that the full drill runs in seconds on CPU."""
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[13], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _drill_batches(n_steps, batch=8):
    import numpy as np

    rng = np.random.RandomState(7)
    w = rng.uniform(-1, 1, (13, 1)).astype("float32")
    out = []
    for _ in range(n_steps):
        xb = rng.uniform(-1, 1, (batch, 13)).astype("float32")
        out.append({"x": xb, "y": xb @ w})
    return out


def inprocess_drill(dirname, steps=12, kill_after=8, keep=3):
    """The fast preempt→restore drill, single process: train
    ``kill_after`` steps with the health sentinel's rollback window
    persisting durably (AutoCheckpoint(sentinel=), no full checkpoint
    in range), SIMULATE the preemption by dropping every live object,
    then restore a fresh program/executor/scope from the persisted
    window and finish the run.  Asserts the restored run resumed at the
    window step (NOT step 0 — the thing a checkpoint-only restart would
    do) and that the final parameters bit-match an uninterrupted
    baseline.  Returns the report dict; restore/first_step phases are
    booked into ``pt_recovery_seconds``."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import Scope, scope_guard
    from paddle_tpu.fluid.incubate.checkpoint import AutoCheckpoint

    batches = _drill_batches(steps)
    old_flags = fluid.get_flags(["FLAGS_health_sentinel",
                                 "FLAGS_health_action",
                                 "FLAGS_health_rollback_keep",
                                 "FLAGS_rollback_persist_interval_s"])
    fluid.set_flags({"FLAGS_health_sentinel": True,
                     "FLAGS_health_action": "rollback",
                     "FLAGS_health_rollback_keep": int(keep),
                     # every step is within the cadence: the drill wants
                     # the freshest possible ring on "death"
                     "FLAGS_rollback_persist_interval_s": 1e-6})
    try:
        # -- uninterrupted baseline --------------------------------------
        main, startup, loss = _build_drill_model()
        base_scope = Scope()
        with scope_guard(base_scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for b in batches:
                exe.run(main, feed=b, fetch_list=[loss.name])
        base_params = {n: np.asarray(base_scope.get(n)).copy()
                       for n in _param_names(main)}

        # -- incarnation 1: train, persist the window, "die" -------------
        # step numbering: ck.step(i) after completing 0-based step i; a
        # FULL checkpoint resume would return i+1 (post-state), a WINDOW
        # resume returns i (the newest entry is step i's PRE-state — the
        # caller re-runs it, bit-identical on deterministic data)
        main1, startup1, loss1 = _build_drill_model()
        scope1 = Scope()
        with scope_guard(scope1):
            exe1 = fluid.Executor(fluid.CPUPlace())
            exe1.run(startup1)
            sent1 = exe1.health_sentinel(main1)
            assert sent1 is not None, "drill model must attach a sentinel"
            ck1 = AutoCheckpoint(dirname, exe1, main1, scope=scope1,
                                 save_interval=10 ** 9,
                                 install_signal_handler=False,
                                 sentinel=sent1)
            for i in range(kill_after):
                exe1.run(main1, feed=batches[i], fetch_list=[loss1.name])
                ck1.step(i)
            ck1.close()  # flushes the ring + stops the persist worker
        # (everything from incarnation 1 is now dropped — the simulated
        # SIGKILL; only the durable ring under `dirname` survives)

        # -- incarnation 2: restore + finish ------------------------------
        t_spawn = time.monotonic()
        main2, startup2, loss2 = _build_drill_model()
        scope2 = Scope()
        with scope_guard(scope2):
            exe2 = fluid.Executor(fluid.CPUPlace())
            exe2.run(startup2)
            sent2 = exe2.health_sentinel(main2)
            ck2 = AutoCheckpoint(dirname, exe2, main2, scope=scope2,
                                 save_interval=10 ** 9,
                                 install_signal_handler=False,
                                 sentinel=sent2)
            start = ck2.resume()
            t_restore = time.monotonic()
            if start != kill_after - 1:
                raise AssertionError(
                    f"window restore resumed at step {start}, expected "
                    f"{kill_after - 1} (a checkpoint-only restart would "
                    f"have resumed at 0)")
            first = None
            for i in range(start, steps):
                exe2.run(main2, feed=batches[i], fetch_list=[loss2.name])
                if first is None:
                    first = time.monotonic()
            ck2.close()
        final = {n: np.asarray(scope2.get(n)).copy()
                 for n in _param_names(main2)}
        parity = max(
            float(np.max(np.abs(final[n] - base_params[n])))
            for n in base_params)
        if parity > 1e-6:
            raise AssertionError(
                f"restored run diverged from the uninterrupted "
                f"baseline: max|Δparam| = {parity}")
        phases = {"restore": t_restore - t_spawn,
                  "first_step": (first - t_restore) if first else 0.0}
        for name, secs in phases.items():
            book_phase(name, secs)
        return {"resumed_at": start, "steps": steps,
                "parity_max_abs": parity,
                "phases": {k: round(v, 4) for k, v in phases.items()},
                "mttr_s": round((first or t_restore) - t_spawn, 4)}
    finally:
        fluid.set_flags(old_flags)


def _param_names(program):
    names = []
    for op in program.global_block().ops:
        if op.attrs.get("op_role") == "optimize" and op.input("Param"):
            p = op.input("Param")[0]
            if p not in names:
                names.append(p)
    return names


def main(argv=None):
    """`make recovery-drill`: run the fast in-process drill and print
    the phase report."""
    import tempfile

    with tempfile.TemporaryDirectory(prefix="pt_recovery_drill_") as d:
        report = inprocess_drill(d)
    # observability: allow — CLI entry point, report IS the output
    print(json.dumps({"recovery_drill": report}, indent=2))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
