"""FaultPlan-driven serving fault drills (`make serve-drill`, i.e.
`python -m paddle_tpu.serving.drill`).

The PR-14 recovery-drill precedent, applied to serving: every claim the
resilience layer makes is MEASURED here, deterministically, with the
FaultPlan grammar — not asserted from code reading.

  failover_drill      2-replica decode group under closed-loop load; a
                      `replica_kill:` rule murders one scheduler
                      mid-decode; the router fails the victim sequences
                      over and every stream must finish TOKEN-EXACT vs
                      the uninterrupted single-replica baseline (greedy
                      determinism is the contract).  Books
                      pt_serve_failovers_total + pt_serve_recovery_
                      seconds; gates on zero steady-state compile
                      misses across the failover.
  promotion_drill     canary weight promotion over the live group:
                      clean (perturbed weights, gates pass, whole group
                      converges, background traffic sees zero drops —
                      and zero compiles: the swap is arrays-only) and
                      regression (a `serve_error:` rule fails the
                      canary's probe window → auto-rollback restores
                      the old arrays bit-exact).
  hedge_drill         two continuous-batch Engine replicas, one built
                      slow (large batch timeout); hedged requests beat
                      it to the fast replica and the win-rate is
                      recorded.

Each drill returns a plain report dict; `run_drill()` composes them and
`python -m paddle_tpu.serving.drill` prints one JSON report.

These drills build real engines and compile real (tiny) programs — the
subprocess test wrapper (tests/test_serve_drill.py) runs them in a
fresh child with the persistent compile cache off, the same isolation
tests/decode_e2e_checks.py needs on the brittle jaxlib.
"""

from __future__ import annotations

import threading
import time

import numpy as np

__all__ = ["failover_drill", "promotion_drill", "hedge_drill",
           "run_drill", "main"]

_GPT_CFG = dict(num_layers=2, hidden_dropout=0.0,
                use_flash_attention=False)


def _compile_misses():
    """Total executable-cache misses so far (every path) — the
    zero-compile gates are deltas of this."""
    from paddle_tpu import observability as obs

    fam = (obs.snapshot().get("pt_compile_cache_total") or {})
    return sum(int(v) for k, v in fam.get("samples", {}).items()
               if k[-1] == "miss")


def _recovery_hist(router_name):
    from paddle_tpu import observability as obs

    fam = obs.snapshot().get("pt_serve_recovery_seconds") or {}
    h = fam.get("samples", {}).get((router_name,))
    if not h:
        return {"count": 0, "sum": 0.0}
    return {"count": int(h["count"]), "sum": float(h["sum"])}


def _build_decode_group(n_replicas, *, pool_slots=2, seed=3):
    """One tiny random-init GPT; each replica gets its OWN scope holding
    a copy of the same parameters (a real group has per-replica scopes —
    promotion swaps one replica's arrays at a time) and its own
    DecodeEngine.  Greedy decode over identical weights is identical
    across replicas — the property both drills lean on."""
    from paddle_tpu import fluid, serving
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig.tiny(**_GPT_CFG)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        gpt.build_gpt_lm(cfg)
    scope0 = fluid.Scope()
    with fluid.scope_guard(scope0):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    param_names = [n for n in scope0.keys()
                   if scope0.get(n) is not None]
    scopes, engines = [], []
    for i in range(n_replicas):
        s = fluid.Scope()
        for n in param_names:
            s.set(n, np.array(scope0.get(n)))
        eng = serving.DecodeEngine(
            cfg, scope=s, pool_slots=pool_slots, page_size=4,
            prefill_chunk=4, max_len=32, name=f"replica{i}",
            auto_start=False, drain_on_sigterm=False)
        eng.warmup()
        eng.start()
        scopes.append(s)
        engines.append(eng)
    return cfg, scopes, engines, param_names


def _prompts(cfg, n, plen=4, seed=11):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, cfg.vocab_size, plen)) for _ in range(n)]


def failover_drill(n_requests=6, max_new_tokens=8, kill_after=2,
                   timeout_s=300.0, slo_clear_timeout_s=20.0):
    """replica_kill mid-decode under load → router failover, token-exact
    resumed streams, recovery seconds booked, zero compile misses —
    AND the availability SLO's page alert must FIRE during the kill and
    CLEAR after recovery (the drill measures alert latency, not just
    data-path recovery: an outage nobody is paged for is not survived,
    docs/OBSERVABILITY.md "SLOs & burn-rate alerts")."""
    from paddle_tpu.distributed import fault_injection as _fault
    from paddle_tpu.observability import reqtrace as _reqtrace
    from paddle_tpu.observability import slo as _slo
    from paddle_tpu.serving.router import Router

    cfg, _scopes, engines, _names = _build_decode_group(2)
    r0, r1 = engines
    router = None
    # the production spec shape over the production families, with the
    # SRE-workbook page window compressed to drill scale (seconds, not
    # hours): bad = failovers booked by THIS router, total = admitted
    # serving requests
    spec = _slo.parse_spec(
        "drill_availability|availability"
        "|bad=pt_serve_failovers_total{router=drill}"
        "|total=pt_serve_requests_total"
        "|objective=0.999")
    slo_eng = _slo.SLOEngine(
        [spec], windows=(_slo.BurnWindow("page", 1.0, 4.0, 14.4),))
    marks = {"t_kill": None, "t_fired": None, "t_cleared": None}
    stop_poll = threading.Event()

    def _poll_slo():
        # evaluate FIRST, wait after: the kill lands within ~100 ms of
        # submission — a wait-first loop could take its first sample
        # with the failovers already booked, and a window whose every
        # sample is post-failure has zero delta (no fire, ever)
        while True:
            if marks["t_kill"] is None and not r0.healthy():
                marks["t_kill"] = time.monotonic()
            slo_eng.evaluate()
            st = slo_eng.alert_state("drill_availability", "page")
            if st["active"] and marks["t_fired"] is None:
                marks["t_fired"] = time.monotonic()
            if (not st["active"] and marks["t_fired"] is not None
                    and marks["t_cleared"] is None):
                marks["t_cleared"] = time.monotonic()
                return
            if stop_poll.wait(0.02):
                return

    try:
        prompts = _prompts(cfg, n_requests)
        # uninterrupted baseline on replica0 alone (greedy oracle)
        baseline = r0.generate(prompts, max_new_tokens,
                               timeout=timeout_s)
        # arm the mid-decode death: kill replica0's scheduler a couple
        # of decode steps into the loaded run (its step counter kept
        # counting through the baseline)
        kill_step = r0.stats()["steps"] + int(kill_after)
        _fault.install(f"replica_kill:replica0:step:{kill_step}")
        misses_before = _compile_misses()
        router = Router([r0, r1], name="drill", hedge_ms=0,
                        probe_interval_ms=20)
        # pre-kill baseline sample: every burn window needs a healthy
        # base to delta against
        slo_eng.evaluate()
        poller = threading.Thread(target=_poll_slo, daemon=True)
        poller.start()
        t0 = time.monotonic()
        futs = [router.submit(p, max_new_tokens) for p in prompts]
        outs = [f.result(timeout=timeout_s) for f in futs]
        wall_s = time.monotonic() - t0
        t_recovered = time.monotonic()
        # the kill window is over and counters have stopped moving: the
        # short burn window must drain and the alert must CLEAR
        deadline = time.monotonic() + float(slo_clear_timeout_s)
        while marks["t_cleared"] is None and time.monotonic() < deadline:
            time.sleep(0.05)
        stop_poll.set()
        poller.join(timeout=5)
        misses_delta = _compile_misses() - misses_before
        token_exact = outs == baseline
        stats = router.stats()
        rec = _recovery_hist("drill")
        alert = slo_eng.alert_state("drill_availability", "page")
        slo_report = {
            "spec": spec.describe(),
            "alert_fired": marks["t_fired"] is not None,
            "alert_cleared": marks["t_cleared"] is not None,
            "fire_latency_s": round(
                marks["t_fired"] - marks["t_kill"], 3)
            if marks["t_fired"] is not None
            and marks["t_kill"] is not None else None,
            "clear_latency_s": round(
                marks["t_cleared"] - t_recovered, 3)
            if marks["t_cleared"] is not None else None,
            "fired_total": alert["fired_total"],
        }
        # trace-derived per-request quantiles (span tree, not the
        # aggregate histogram): the drill's requests are attributable
        quantiles = _reqtrace.request_quantiles()
        report = {
            "requests": n_requests,
            "max_new_tokens": max_new_tokens,
            "kill_step": kill_step,
            "replica0_died": not r0.healthy(),
            "token_exact": token_exact,
            "failovers": stats["failovers"],
            "recovery": rec,
            "mttr_s": round(rec["sum"] / rec["count"], 6)
            if rec["count"] else None,
            "compile_miss_delta": misses_delta,
            "wall_s": round(wall_s, 3),
            "slo": slo_report,
            "trace_quantiles": quantiles,
        }
        report["ok"] = (token_exact and report["replica0_died"]
                        and stats["failovers"] > 0
                        and rec["count"] > 0 and misses_delta == 0
                        and slo_report["alert_fired"]
                        and slo_report["alert_cleared"])
        return report
    finally:
        stop_poll.set()
        _fault.uninstall()
        if router is not None:
            router.close()
        for eng in engines:
            eng.close()


def promotion_drill(regress=False, n_traffic=4, max_new_tokens=6,
                    probe_count=3, timeout_s=300.0):
    """Canary promotion over a live 2-replica group.  ``regress=False``:
    perturbed weights pass the gates, the whole group converges, the
    background traffic completes with zero drops and the swap performs
    zero compiles.  ``regress=True``: a `serve_error:` rule lands in the
    canary's post-swap probe window → auto-rollback, old arrays restored
    bit-exact."""
    from paddle_tpu.distributed import fault_injection as _fault
    from paddle_tpu.serving import promote as _promote
    from paddle_tpu.serving.router import Router

    cfg, scopes, engines, param_names = _build_decode_group(2)
    router = None
    try:
        router = Router(engines, name="promo", hedge_ms=0,
                        probe_interval_ms=20)
        # the checkpoint being published: the same parameters nudged by
        # a small deterministic delta (a stand-in training delta — large
        # enough that a restored rollback is distinguishable)
        rng = np.random.RandomState(5)
        new_weights = _promote.WeightSet({
            n: np.asarray(scopes[0].get(n))
            + rng.normal(0, 1e-3, np.shape(scopes[0].get(n)))
            .astype(np.asarray(scopes[0].get(n)).dtype)
            for n in param_names})
        probe_prompts = _prompts(cfg, probe_count, seed=23)
        old_sample = {n: np.array(scopes[0].get(n))
                      for n in param_names[:2]}
        if regress:
            # fail the canary's FIRST post-swap probe: per-replica probe
            # counts run baseline (probe_count) then post-swap
            _fault.install(
                f"serve_error:replica0:req:{probe_count + 1}")
        traffic_outs, traffic_errors = [], []

        def _traffic():
            prompts = _prompts(cfg, n_traffic, seed=31)
            futs = [router.submit(p, max_new_tokens) for p in prompts]
            for f in futs:
                try:
                    traffic_outs.append(f.result(timeout=timeout_s))
                except Exception as e:  # surfaced in the report
                    traffic_errors.append(repr(e))

        misses_before = _compile_misses()
        traffic_thread = None
        if not regress:
            # background load proves zero dropped requests across the
            # rolling swap (regress runs un-loaded: router traffic would
            # consume the serve_error count aimed at the probe window)
            traffic_thread = threading.Thread(target=_traffic,
                                              daemon=True)
            traffic_thread.start()
        gates = _promote.PromotionGates(max_error_rate=0.0,
                                        max_latency_ratio=None,
                                        max_drift=None)
        report_p = _promote.promote(
            router, new_weights, probe_prompts=probe_prompts,
            probe_max_new_tokens=4, gates=gates,
            probe_timeout_s=timeout_s)
        if traffic_thread is not None:
            traffic_thread.join(timeout=timeout_s)
        misses_delta = _compile_misses() - misses_before
        restored = all(
            np.array_equal(np.asarray(scopes[0].get(n)), old_sample[n])
            for n in old_sample)
        converged = all(
            np.array_equal(np.asarray(s.get(param_names[0])),
                           new_weights.arrays[param_names[0]])
            for s in scopes)
        report = {
            "mode": "regress" if regress else "clean",
            "outcome": report_p["outcome"],
            "replicas": report_p["replicas"],
            "compile_miss_delta": misses_delta,
            "traffic_completed": len(traffic_outs),
            "traffic_errors": traffic_errors,
            "canary_restored_bit_exact": restored,
            "group_converged": converged,
        }
        if regress:
            report["ok"] = (report_p["outcome"] == "rolled_back"
                            and restored and misses_delta == 0)
        else:
            report["ok"] = (report_p["outcome"] == "promoted"
                            and converged and not traffic_errors
                            and len(traffic_outs) == n_traffic
                            and misses_delta == 0)
        return report
    finally:
        _fault.uninstall()
        if router is not None:
            router.close()
        for eng in engines:
            eng.close()


def hedge_drill(n_requests=12, hedge_ms=30, slow_wait_ms=300,
                timeout_s=120.0):
    """Two continuous-batch Engine replicas serving one model; the
    first is built SLOW (its batcher waits `slow_wait_ms` before
    dispatching) so the hedge timer beats it to the fast replica —
    hedge win-rate measured, not asserted."""
    import shutil
    import tempfile
    import warnings

    from paddle_tpu import fluid, serving
    from paddle_tpu.fluid.executor import Scope, scope_guard
    from paddle_tpu.serving.router import Router

    feature, hidden, classes = 16, 32, 8
    model_dir = tempfile.mkdtemp(prefix="pt_serve_drill_")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[feature], dtype="float32")
        h = fluid.layers.fc(x, size=hidden, act="relu")
        pred = fluid.layers.fc(h, size=classes, act="softmax")
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    engines, router = [], None
    try:
        with warnings.catch_warnings():
            # both replicas serve model name "m" on purpose (their
            # pt_serve_* series alias — the router is the one caller)
            warnings.simplefilter("ignore")
            for name, wait_ms in (("slow", slow_wait_ms), ("fast", 1)):
                eng = serving.Engine({"m": model_dir},
                                     max_wait_ms=wait_ms,
                                     name=f"hedge-{name}",
                                     auto_start=False)
                eng.warmup()
                eng.start()
                engines.append(eng)
        router = Router(engines, name="hedge", hedge_ms=hedge_ms,
                        probe_interval_ms=50)
        xb = np.arange(feature, dtype=np.float32).reshape(1, feature)
        t0 = time.monotonic()
        outs = [router.infer("m", {"x": xb}, timeout=timeout_s)
                for _ in range(n_requests)]
        wall_s = time.monotonic() - t0
        hedges = router.hedge_stats()
        fired = hedges["win"] + hedges["lose"]
        report = {
            "requests": n_requests,
            "completed": len(outs),
            "hedge_ms": hedge_ms,
            "hedges_fired": fired,
            "hedge_wins": hedges["win"],
            "hedge_win_rate": round(hedges["win"] / fired, 3)
            if fired else None,
            "wall_s": round(wall_s, 3),
        }
        report["ok"] = (len(outs) == n_requests and fired > 0
                        and hedges["win"] > 0)
        return report
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
        if router is not None:
            router.close()
        for eng in engines:
            eng.close()


def run_drill(include=("failover", "promotion_clean",
                       "promotion_rollback", "hedge")):
    """Compose the serving drills into one report (what `make
    serve-drill` prints)."""
    report = {}
    if "failover" in include:
        report["failover"] = failover_drill()
    if "promotion_clean" in include:
        report["promotion_clean"] = promotion_drill(regress=False)
    if "promotion_rollback" in include:
        report["promotion_rollback"] = promotion_drill(regress=True)
    if "hedge" in include:
        report["hedge"] = hedge_drill()
    report["ok"] = all(r.get("ok") for r in report.values()
                       if isinstance(r, dict))
    return report


def main(argv=None):
    import json
    import sys

    include = tuple(argv) if argv else ("failover", "promotion_clean",
                                        "promotion_rollback", "hedge")
    report = run_drill(include=include)
    print("SERVE_DRILL_RESULT "  # observability: allow — CLI surface
          + json.dumps(report, default=str), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv[1:] or None))
