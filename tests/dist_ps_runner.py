"""Subprocess roles for parameter-server tests (reference
test_dist_base.py pattern: real processes on 127.0.0.1 endpoints).

  python dist_ps_runner.py pserver   <ep> <endpoints> <n_trainers> <opt>
  python dist_ps_runner.py trainer   <tid> <endpoints> <n_trainers> <opt> <out.json>

The model is fit_a_line (fc regression) on deterministic synthetic data;
trainer t feeds rows [t*8:(t+1)*8) of each 16-row global batch.

Elastic mode (DIST_PS_ELASTIC=1 + FLAGS_elastic_ps=1): trainers join the
job under a lease and derive their PER-ROUND data slice from the
membership authority (endpoints[0]) — round r consumes global batch r,
split evenly across the CURRENT (epoch, index, count) view, so the
merged gradient equals the full-batch mean at EVERY membership size and
a drained-then-regrown job tracks the uninterrupted baseline exactly.
The elastic global batch is 12 rows (divisible by 1/2/3/4/6 members).
  PT_ELASTIC_JOIN_AT_ROUND=<r>  delay joining until the server reaches
                                round r (the scale-up choreography)
  PT_ELASTIC_JOIN_MIN=<n>       launch-cohort rendezvous floor
A SIGTERM (PT_FAULT_PLAN preempt:step:<k>) drains gracefully: finish the
in-flight round, announce LEAVE, run the announced round, dump results,
then finish() re-delivers the signal (drain marker for the supervisor).

Fault-tolerance hooks (tests/test_fault_tolerance.py):
  PT_FAULT_PLAN        fault plan for THIS process (kill:step:K fires in
                       the trainer loop; kill:round:K in the pserver sync
                       loop; the supervisor strips it on relaunch)
  PT_PS_SNAPSHOT_DIR   pserver shards auto-snapshot/resume through here
                       (consumed by the listen_and_serv host op)
  DIST_PS_CKPT_DIR     trainer-side AutoCheckpoint dir: every step is
                       snapshotted and a relaunched trainer resumes from
                       its last completed step (deterministic data makes
                       the replayed round bit-identical)

The trainer also dumps its process resilience counters into out.json so
tests can assert recovery actually exercised the retry path.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.fluid.executor import Scope, scope_guard  # noqa: E402

N_STEPS = int(os.environ.get("DIST_PS_STEPS", "12"))
ELASTIC = os.environ.get("DIST_PS_ELASTIC", "") not in ("", "0")
# elastic slices must divide evenly at every membership size (1/2/3/4/6)
GLOBAL_BATCH = 12 if ELASTIC else 16
MODE = os.environ.get("DIST_PS_MODE", "sync")  # sync | async | geo
SYNC_MODE = MODE == "sync"


MODEL = os.environ.get("DIST_PS_MODEL", "fc")
EMB_VOCAB = 40


def build(opt_name):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        if MODEL == "emb":
            # sparse-embedding model: with >1 pserver the table row-shards
            ids = fluid.layers.data(name="x", shape=[1, 1], dtype="int64")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            emb = fluid.layers.embedding(ids, size=[EMB_VOCAB, 8],
                                         is_sparse=True)
            pooled = fluid.layers.reduce_mean(emb, dim=1)
            pred = fluid.layers.fc(pooled, size=1)
        else:
            x = fluid.layers.data(name="x", shape=[13], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt = {"sgd": lambda: fluid.optimizer.SGD(learning_rate=0.05),
               "adam": lambda: fluid.optimizer.Adam(learning_rate=0.05),
               "momentum": lambda: fluid.optimizer.Momentum(
                   learning_rate=0.05, momentum=0.9)}[opt_name]()
        opt.minimize(loss)
    return main, startup, loss


def global_batches():
    rng = np.random.RandomState(0)
    out = []
    if MODEL == "emb":
        w = rng.uniform(-1, 1, EMB_VOCAB).astype("float32")
        half = EMB_VOCAB // 2
        for _ in range(N_STEPS):
            # skew 85% of ids into the first row-shard so some rounds leave
            # the second shard untouched by one trainer — exercising the
            # empty-partial protocol (server divisor == n_trainers)
            lo = rng.randint(0, half, (GLOBAL_BATCH, 1, 1))
            hi = rng.randint(half, EMB_VOCAB, (GLOBAL_BATCH, 1, 1))
            pick = rng.rand(GLOBAL_BATCH, 1, 1) < 0.85
            ids = np.where(pick, lo, hi).astype("int64")
            y = (1.0 + w[ids[:, :, 0]].mean(axis=1,
                                            keepdims=True)).astype("float32")
            out.append({"x": ids, "y": y})
        return out
    W = rng.uniform(-1, 1, (13, 1)).astype("float32")
    for _ in range(N_STEPS):
        xb = rng.uniform(-1, 1, (GLOBAL_BATCH, 13)).astype("float32")
        out.append({"x": xb, "y": xb @ W})
    return out


def _param_names(main):
    """The optimizer-updated parameters of the program (for final-state
    parity checks)."""
    names = []
    for op in main.global_block().ops:
        if op.attrs.get("op_role") == "optimize" and op.input("Param"):
            p = op.input("Param")[0]
            if p not in names:
                names.append(p)
    return names


def run_local(opt_name, out_path):
    from paddle_tpu.fluid.executor import global_scope

    main, startup, loss = build(opt_name)
    losses = []
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for b in global_batches():
            (lv,) = exe.run(main, feed=b, fetch_list=[loss.name])
            losses.append(float(np.asarray(lv)))
        cur = global_scope()
        finals = {p: np.asarray(cur.get(p)).ravel().tolist()
                  for p in _param_names(main) if cur.get(p) is not None}
    json.dump({"losses": losses, "params": finals}, open(out_path, "w"))


def _make_transpiler():
    if MODE == "geo":
        cfg = fluid.DistributeTranspilerConfig()
        cfg.geo_sgd_need_push_nums = int(
            os.environ.get("DIST_PS_GEO_K", "4"))
        return fluid.transpiler.GeoSgdTranspiler(cfg)
    return fluid.DistributeTranspiler()


def _trace_hooks(role, rank):
    """PT_TRACE_DIR: profile this process and export a per-role chrome
    trace on exit (merged across ranks by tools/merge_traces.py)."""
    trace_dir = os.environ.get("PT_TRACE_DIR")
    if not trace_dir:
        return lambda: None
    os.environ.setdefault("PT_TRACE_ROLE", role)
    os.environ.setdefault("PT_TRACE_RANK", str(rank))
    from paddle_tpu.fluid import profiler

    profiler.start_profiler()

    def export():
        os.makedirs(trace_dir, exist_ok=True)
        profiler.export_chrome_trace(
            os.path.join(trace_dir, f"trace_{role}{rank}.json"))

    return export


def run_pserver(ep, endpoints, n_trainers, opt_name):
    # rank = shard index within the endpoint list, matching the
    # PT_TRACE_RANK convention launch_ps uses for its pservers
    export_trace = _trace_hooks("pserver", endpoints.split(",").index(ep))
    main, startup, loss = build(opt_name)
    t = _make_transpiler()
    t.transpile(trainer_id=0, program=main, pservers=endpoints,
                trainers=n_trainers, sync_mode=SYNC_MODE,
                startup_program=startup)
    with scope_guard(Scope()):
        fluid.Executor(fluid.CPUPlace()).run(t.get_pserver_program(ep))
    export_trace()


def run_trainer_elastic(tid, endpoints, n_trainers, opt_name, out_path):
    """Elastic round loop: the SERVER round (membership authority
    endpoints[0]) selects the global batch, the (index, count) view
    selects this member's even slice.  Rounds with any membership size
    produce the same merged gradient (the full-batch mean), so a
    preempt-then-rejoin run reaches parity with the uninterrupted local
    baseline."""
    import time as _time

    from paddle_tpu.distributed import (elastic, fault_injection,
                                        resilience)
    from paddle_tpu.ops import dist_ops

    eps = endpoints.split(",")
    export_trace = _trace_hooks("trainer", tid)
    drain = elastic.install_drain_handler()
    # leave:step:<k> in PT_FAULT_PLAN drains without a signal
    fault_injection.set_membership_hooks(
        leave=lambda _k: drain.requested.set())
    join_at = int(os.environ.get("PT_ELASTIC_JOIN_AT_ROUND", "0") or 0)
    if join_at:
        # delayed joiner: watch the round counter (non-member lease
        # query) so the process is warm before it enters the job
        from paddle_tpu import native

        host, port = eps[0].rsplit(":", 1)
        watcher = native.PSClient(host=host, port=int(port), timeout=60.0,
                                  uid=f"watch:{tid}")
        while watcher.membership()["round"] < join_at:
            _time.sleep(0.05)
        watcher.close()
    main, startup, loss = build(opt_name)
    t = _make_transpiler()
    t.transpile(trainer_id=tid, program=main, pservers=endpoints,
                trainers=n_trainers, sync_mode=SYNC_MODE,
                startup_program=startup)
    trainer_prog = t.get_trainer_program()
    losses, counts, rounds_run = [], [], []
    step_delay = float(os.environ.get("DIST_PS_STEP_DELAY", "0") or 0)
    batches = global_batches()
    leaving = False
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)  # ps_init_sync: pull + elastic JOIN + heartbeat
        # round-partitioned input stream through the library prefetcher
        # (fluid.prefetch, ROADMAP elastic phase 2): the membership view
        # is applied at CONSUME time — each popped batch is sliced by
        # the epoch view of the round that actually feeds it, so an
        # elastic resize re-partitions the stream at the next round
        # instead of replaying slices a stale view produced ahead
        from paddle_tpu.fluid.prefetch import DatasetPrefetcher

        view = {"index": -1, "count": 1}
        # resume position: the QUORUM committed round wins over any one
        # shard's membership view — a relaunched shard 0 restored from a
        # stale snapshot must not drag the dataset position backwards
        start_rnd = elastic.membership_any(eps)["round"]
        try:
            start_rnd = max(start_rnd, elastic.agree_epoch(eps)["round"])
        except IOError:
            pass  # no committed record yet (fresh job)
        pf = DatasetPrefetcher(
            iter(batches[start_rnd:]), depth=1,
            partition=lambda: (view["index"], view["count"]),
            partition_stage="consume")
        next_rnd = start_rnd
        restart_count = int(os.environ.get("PADDLE_RESTART_COUNT",
                                           "0") or 0)
        try:
            while True:
                # any live shard is a valid per-round view (all shards
                # flip membership at the same boundary); walking the
                # list survives the loss of the old shard-0 authority
                info = elastic.membership_any(eps)
                rnd, count, index = (info["round"], info["count"],
                                     info["index"])
                if rnd >= N_STEPS:
                    break
                fault_injection.on_step(rnd + 1)  # preempt:step fires HERE
                if drain.requested.is_set() and not leaving:
                    # drain: announce LEAVE now — before this round's
                    # send, so it applies at THIS round's boundary; feed
                    # the announced round, then exit
                    elastic.leave_job(eps)
                    leaving = True
                view["index"], view["count"] = index, count
                while next_rnd < rnd:  # round advanced without us: skip
                    next(pf)
                    next_rnd += 1
                sub = next(pf)
                next_rnd += 1
                (lv,) = exe.run(trainer_prog, feed=sub,
                                fetch_list=[loss.name])
                if restart_count:  # recovery milestone, once
                    restart_count = 0
                    from paddle_tpu.distributed import recovery

                    recovery.note("first_step", round=rnd)
                losses.append(float(np.asarray(lv)))
                counts.append(count)
                rounds_run.append(rnd)
                if leaving:
                    break
                if step_delay:
                    _time.sleep(step_delay)
        finally:
            pf.close()
        finals = {}
        if not leaving:
            finals = {p: dist_ops.get_channel(ep).client.get_param(p)
                      .ravel().tolist()
                      for p, ep in sorted(t.param_endpoint.items())}
    export_trace()
    json.dump({"losses": losses, "counts": counts, "rounds": rounds_run,
               "params": finals, "drained": leaving,
               "restart_count": int(os.environ.get("PADDLE_RESTART_COUNT",
                                                   "0") or 0),
               "resilience": resilience.resilience_stats()},
              open(out_path, "w"))
    if leaving:
        drain.finish()  # marker + re-delivered SIGTERM ends the process
    else:
        elastic.leave_job(eps)
    dist_ops.stop_job_heartbeat()


def run_trainer(tid, endpoints, n_trainers, opt_name, out_path):
    from paddle_tpu.distributed import fault_injection, resilience

    if ELASTIC:
        return run_trainer_elastic(tid, endpoints, n_trainers, opt_name,
                                   out_path)

    export_trace = _trace_hooks("trainer", tid)
    main, startup, loss = build(opt_name)
    t = _make_transpiler()
    t.transpile(trainer_id=tid, program=main, pservers=endpoints,
                trainers=n_trainers, sync_mode=SYNC_MODE,
                startup_program=startup)
    trainer_prog = t.get_trainer_program()
    per = GLOBAL_BATCH // n_trainers
    losses = []
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ck, start_step = None, 0
        if os.environ.get("DIST_PS_CKPT_DIR"):
            from paddle_tpu.fluid.incubate.checkpoint import AutoCheckpoint

            # per-step local snapshots: a relaunched trainer resumes at
            # its last completed step and replays the identical batch
            ck = AutoCheckpoint(os.environ["DIST_PS_CKPT_DIR"] + f".t{tid}",
                                exe, trainer_prog, scope=scope,
                                save_interval=1,
                                install_signal_handler=False)
            start_step = ck.resume()
        noted_first = int(os.environ.get("PADDLE_RESTART_COUNT",
                                         "0") or 0) == 0
        for i, b in enumerate(global_batches()):
            step = i + 1
            if start_step and step < start_step:
                continue  # already done before the restart
            fault_injection.on_step(step)
            sub = {k: v[tid * per:(tid + 1) * per] for k, v in b.items()}
            (lv,) = exe.run(trainer_prog, feed=sub, fetch_list=[loss.name])
            if not noted_first:  # recovery milestone, once per relaunch
                noted_first = True
                from paddle_tpu.distributed import recovery

                recovery.note("first_step", step=step)
            losses.append(float(np.asarray(lv)))
            if ck is not None:
                ck.step(step)
    export_trace()
    json.dump({"losses": losses, "start_step": start_step,
               "restart_count": int(os.environ.get("PADDLE_RESTART_COUNT",
                                                   "0") or 0),
               "resilience": resilience.resilience_stats()},
              open(out_path, "w"))
    # pservers are stopped by the parent test once every trainer exited
    # (a trainer must not stop them while peers are mid-round)


if __name__ == "__main__":
    role = sys.argv[1]
    if role == "local":
        run_local(sys.argv[2], sys.argv[3])
    elif role == "pserver":
        run_pserver(sys.argv[2], sys.argv[3], int(sys.argv[4]), sys.argv[5])
    elif role == "trainer":
        run_trainer(int(sys.argv[2]), sys.argv[3], int(sys.argv[4]),
                    sys.argv[5], sys.argv[6])
    else:
        raise SystemExit(f"unknown role {role}")
