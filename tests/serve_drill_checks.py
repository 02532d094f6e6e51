"""Serving fault-drill checks, run in ONE subprocess by
tests/test_serve_drill.py.

Same isolation story as tests/decode_e2e_checks.py: the drills build
real DecodeEngine/Engine replicas (real compiles) and the jaxlib-0.4.3x
XLA:CPU runtime is only stable for that in a FRESH process with the
persistent compile cache off.  All four drills share the process — the
in-process executor cache makes drills after the first nearly
compile-free.

Each check runs one `paddle_tpu.serving.drill` drill and raises unless
the drill's own `ok` gate holds; main() prints one
``SERVE_DRILL_RESULT {json}`` line mapping check name -> "ok" |
traceback (plus a ``reports`` section with the raw drill reports).

Run directly for debugging: ``python tests/serve_drill_checks.py
[names]``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cpu_mesh  # noqa: F401  (must precede any jax-using import)

# see decode_e2e_checks.py: warm persistent-cache DESERIALIZATION is
# what seeds the 0.4.3x heap corruption — cache-off children are stable
os.environ.setdefault("FLAGS_compile_cache_dir", "")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.serving import drill  # noqa: E402


def check_failover(reports):
    """replica_kill mid-decode under closed-loop load: victim sequences
    fail over to the survivor, every stream token-exact vs the
    uninterrupted baseline, pt_serve_recovery_seconds booked, compile
    misses flat across the failover — and the availability SLO's page
    alert FIRES during the kill and CLEARS after recovery, with both
    latencies in the report (the drill-asserts-alert gate)."""
    rep = drill.failover_drill()
    reports["failover"] = rep
    assert rep["replica0_died"], rep
    assert rep["token_exact"], rep
    assert rep["failovers"] > 0, rep
    assert rep["recovery"]["count"] > 0, rep
    assert rep["mttr_s"] is not None and rep["mttr_s"] >= 0, rep
    assert rep["compile_miss_delta"] == 0, rep
    slo = rep["slo"]
    assert slo["alert_fired"], rep
    assert slo["alert_cleared"], rep
    assert slo["fire_latency_s"] is not None \
        and slo["fire_latency_s"] >= 0, rep
    assert slo["clear_latency_s"] is not None \
        and slo["clear_latency_s"] >= 0, rep
    assert slo["fired_total"] >= 1, rep
    # trace-derived per-request quantiles (span tree, not the aggregate
    # histogram) rode along with the drill's requests
    q = rep["trace_quantiles"]
    assert q["count"] > 0, rep
    assert q["latency_s"]["p99"] >= q["latency_s"]["p50"] >= 0, rep


def check_promotion_clean(reports):
    """Clean canary promotion: gates pass on every replica, the whole
    group converges on the new weights, background router traffic sees
    zero dropped requests, and the swap performs zero compiles."""
    rep = drill.promotion_drill(regress=False)
    reports["promotion_clean"] = rep
    assert rep["outcome"] == "promoted", rep
    assert rep["group_converged"], rep
    assert not rep["traffic_errors"], rep
    assert rep["traffic_completed"] > 0, rep
    assert rep["compile_miss_delta"] == 0, rep


def check_promotion_rollback(reports):
    """Injected canary regression (`serve_error:` in the post-swap probe
    window) auto-rolls back: outcome booked `rolled_back`, the old
    arrays restored bit-exact, still zero compiles."""
    rep = drill.promotion_drill(regress=True)
    reports["promotion_rollback"] = rep
    assert rep["outcome"] == "rolled_back", rep
    assert rep["canary_restored_bit_exact"], rep
    assert not rep["group_converged"], rep
    assert rep["compile_miss_delta"] == 0, rep


def check_hedge(reports):
    """Hedged requests against a deliberately slow primary: every
    request completes, at least one hedge fires and wins."""
    rep = drill.hedge_drill()
    reports["hedge"] = rep
    assert rep["completed"] == rep["requests"], rep
    assert rep["hedges_fired"] > 0, rep
    assert rep["hedge_wins"] > 0, rep


CHECKS = {
    "failover": check_failover,
    "promotion_clean": check_promotion_clean,
    "promotion_rollback": check_promotion_rollback,
    "hedge": check_hedge,
}


def main(argv):
    import json
    import traceback

    names = argv or list(CHECKS)
    results = {}
    reports = {}
    for name in names:
        try:
            CHECKS[name](reports)
            results[name] = "ok"
        except Exception:
            results[name] = traceback.format_exc()
    results["reports"] = reports
    print("SERVE_DRILL_RESULT "  # observability: allow — child protocol
          + json.dumps(results, default=str), flush=True)
    return 0 if all(v == "ok" for k, v in results.items()
                    if k != "reports") else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
