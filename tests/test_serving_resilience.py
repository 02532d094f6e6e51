"""Serving resilience layer (ISSUE 18): router state machines, hedging,
retry budgets, decode failover, canary promotion, and the HTTP frontend
— all driven with fake replicas / real sockets, no device programs, so
every test here is fast tier-1 material.  The end-to-end drills (real
engines, real compiles, real `replica_kill`) live in
tests/test_serve_drill.py behind the subprocess wall.
"""

import concurrent.futures
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.distributed import fault_injection
from paddle_tpu.distributed.resilience import RetryPolicy
from paddle_tpu.fluid.executor import Scope
from paddle_tpu.serving import (Frontend, ModelNotLoadedError,
                                PromotionGates, Router, ServingOverloadError,
                                WeightSet)
from paddle_tpu.serving.promote import promote
from paddle_tpu.serving.router import (BREAKER_CLOSED, BREAKER_HALF_OPEN,
                                       BREAKER_OPEN, CircuitBreaker)

pytestmark = pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")


@pytest.fixture(autouse=True)
def _no_fault_plan():
    yield
    fault_injection.uninstall()


def _wait_for(pred, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.002)


# ---------------------------------------------------------------------------
# fakes
# ---------------------------------------------------------------------------


class FakeDecodeEngine:
    """Duck-typed decode replica: records submissions, exposes the
    health/load surface, raises typed scheduler_failed once killed
    (the real admission-edge behavior)."""

    def __init__(self, name, load=0):
        self.name = name
        self._load = load
        self._healthy = True
        self.requests = []

    def healthy(self):
        return self._healthy

    def load(self):
        return self._load

    def kill(self):
        self._healthy = False
        for req in self.requests:
            if not req.future.done():
                req.future.set_exception(ServingOverloadError(
                    f"{self.name} scheduler died",
                    reason="scheduler_failed"))

    def submit_request(self, prompt, max_new_tokens, eos_id=None,
                       tenant="default", prefix=None):
        if not self._healthy:
            raise ServingOverloadError(f"{self.name} scheduler died",
                                       reason="scheduler_failed")

        class _Req:
            pass

        req = _Req()
        req.prompt = list(prompt)
        req.max_new_tokens = max_new_tokens
        req.prefix = list(prefix or [])
        req.generated = list(prefix or [])
        req.future = concurrent.futures.Future()
        self.requests.append(req)
        return req


class FakeEngine:
    """Duck-typed stateless replica (no submit_request → kind='engine')."""

    def __init__(self, name, load=0):
        self.name = name
        self._load = load
        self._closed = False
        self.submits = []

    def submit(self, model, feed, tenant="default"):
        fut = concurrent.futures.Future()
        self.submits.append((model, fut))
        return fut


def _fast_retry(times=2):
    return RetryPolicy(times=times, backoff_ms=1, jitter=0.0)


def _router(replicas, **kw):
    kw.setdefault("retry", _fast_retry())
    kw.setdefault("hedge_ms", 0)
    kw.setdefault("auto_probe", False)
    return Router(replicas, **kw)


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


def test_breaker_trip_halfopen_close():
    t = [0.0]
    b = CircuitBreaker(failures=3, cooldown_ms=1000, clock=lambda: t[0])
    assert b.state == BREAKER_CLOSED and b.allow()
    b.record_failure()
    b.record_failure()
    assert b.state == BREAKER_CLOSED  # 2 < 3: still closed
    b.record_failure()
    assert b.state == BREAKER_OPEN
    assert not b.allow()  # open: nothing passes inside the cooldown
    t[0] = 0.9
    assert not b.allow()
    t[0] = 1.0  # cooldown elapsed: half-open, exactly one probe passes
    assert b.allow()
    assert b.state == BREAKER_HALF_OPEN
    assert not b.allow()  # the single-probe guard
    b.record_success()
    assert b.state == BREAKER_CLOSED and b.allow()


def test_breaker_halfopen_probe_failure_reopens():
    t = [0.0]
    b = CircuitBreaker(failures=1, cooldown_ms=500, clock=lambda: t[0])
    b.record_failure()
    assert b.state == BREAKER_OPEN
    t[0] = 0.6
    assert b.allow()  # the half-open probe
    b.record_failure()  # probe verdict: still broken
    assert b.state == BREAKER_OPEN
    assert not b.allow()  # cooldown re-armed from the re-trip
    t[0] = 1.2
    assert b.allow()


def test_breaker_success_resets_consecutive_count():
    b = CircuitBreaker(failures=2, cooldown_ms=1000)
    b.record_failure()
    b.record_success()
    b.record_failure()
    assert b.state == BREAKER_CLOSED  # never 2 consecutive


# ---------------------------------------------------------------------------
# router: selection / membership
# ---------------------------------------------------------------------------


def test_least_loaded_pick_and_held():
    a, b = FakeDecodeEngine("a", load=3), FakeDecodeEngine("b", load=1)
    with _router([a, b]) as router:
        fut = router.submit([1, 2], 4)
        assert len(b.requests) == 1 and not a.requests  # least loaded
        router.set_held("b", True)
        fut2 = router.submit([1, 2], 4)
        assert len(a.requests) == 1  # held replica left rotation
        router.set_held("b", False)
        with pytest.raises(KeyError):
            router.set_held("nope", True)
        a.requests[0].future.set_result([7])
        b.requests[0].future.set_result([7])
        assert fut.result(5) == [7] and fut2.result(5) == [7]


def test_duplicate_replica_name_rejected():
    with _router([FakeDecodeEngine("a")]) as router:
        with pytest.raises(ValueError, match="already enrolled"):
            router.add_replica(FakeDecodeEngine("a"))


def test_no_replicas_is_typed():
    with _router([]) as router:
        with pytest.raises(ModelNotLoadedError):
            router.submit([1], 4)
        with pytest.raises(ModelNotLoadedError):
            router.submit_feed("m", {"x": 1})


def test_probe_trips_breaker_of_dead_replica():
    a, b = FakeDecodeEngine("a"), FakeDecodeEngine("b")
    with _router([a, b]) as router:
        a._healthy = False
        router.probe_once()
        (rep_a,) = [r for r in router.replicas() if r.name == "a"]
        (rep_b,) = [r for r in router.replicas() if r.name == "b"]
        assert rep_a.breaker.state == BREAKER_OPEN
        assert rep_b.breaker.state == BREAKER_CLOSED


# ---------------------------------------------------------------------------
# router: decode failover
# ---------------------------------------------------------------------------


def test_decode_failover_resumes_from_prefix():
    a, b = FakeDecodeEngine("a"), FakeDecodeEngine("b", load=5)
    with _router([a, b]) as router:
        fut = router.submit([1, 2, 3], 8)
        (req,) = a.requests  # least loaded got it
        req.generated = [10, 11, 12]  # three tokens already emitted
        a.kill()  # fans scheduler_failed to the live future
        _wait_for(lambda: b.requests, msg="failover re-dispatch")
        (resumed,) = b.requests
        assert resumed.prompt == [1, 2, 3]
        assert resumed.prefix == [10, 11, 12]  # prefix carried over
        assert resumed.max_new_tokens == 8  # ORIGINAL budget
        resumed.generated = [10, 11, 12, 13]
        resumed.future.set_result(list(resumed.generated))
        assert fut.result(5) == [10, 11, 12, 13]
        stats = router.stats()
        assert stats["failovers"] == 1


def test_decode_failover_exhaustion_propagates_death():
    a, b = FakeDecodeEngine("a"), FakeDecodeEngine("b", load=5)
    with _router([a, b]) as router:
        fut = router.submit([1], 4)
        a.kill()
        _wait_for(lambda: b.requests, msg="first failover")
        b.kill()  # second death: no survivors left
        # terminal error is typed either way: the fanned scheduler
        # death, or no-available-replica once the retry budget is spent
        with pytest.raises(ServingOverloadError):
            fut.result(10)


def test_dispatch_edge_death_skips_to_survivor():
    # replica dead at ADMISSION (typed scheduler_failed raise) — the
    # router must step to the next replica without burning a retry
    a, b = FakeDecodeEngine("a"), FakeDecodeEngine("b", load=5)
    a._healthy = True  # healthy() true, but submit raises (race window)
    a.submit_request = FakeDecodeEngine("a").submit_request.__get__(a)
    a.kill_at_submit = True

    def _raise(*args, **kw):
        raise ServingOverloadError("a scheduler died",
                                   reason="scheduler_failed")

    a.submit_request = _raise
    with _router([a, b]) as router:
        fut = router.submit([1], 4)
        (req,) = b.requests
        req.future.set_result([5])
        assert fut.result(5) == [5]
        assert router.stats()["retries"] == 0


# ---------------------------------------------------------------------------
# router: retry budget
# ---------------------------------------------------------------------------


def test_retry_budget_exhaustion_reraises_typed():
    class Rejecting(FakeDecodeEngine):
        def submit_request(self, *a, **kw):
            raise ServingOverloadError("queue full", reason="overload")

    eng = Rejecting("a")
    with _router([eng], retry=_fast_retry(times=2)) as router:
        fut = router.submit([1], 4)
        with pytest.raises(ServingOverloadError, match="queue full"):
            fut.result(5)
        assert router.stats()["retries"] == 2  # budget spent, then typed


def test_retry_succeeds_after_transient_rejection():
    calls = []

    class Flaky(FakeDecodeEngine):
        def submit_request(self, *a, **kw):
            calls.append(1)
            if len(calls) == 1:
                raise ServingOverloadError("queue full",
                                           reason="overload")
            return super().submit_request(*a, **kw)

    eng = Flaky("a")
    with _router([eng], retry=_fast_retry(times=3)) as router:
        fut = router.submit([1], 4)
        _wait_for(lambda: eng.requests, msg="retry re-dispatch")
        eng.requests[0].future.set_result([9])
        assert fut.result(5) == [9]
        assert router.stats()["retries"] == 1


# ---------------------------------------------------------------------------
# router: hedging (stateless lane)
# ---------------------------------------------------------------------------


def test_hedge_win_cancels_primary():
    slow, fast = FakeEngine("slow"), FakeEngine("fast", load=5)
    with _router([slow, fast], hedge_ms=5) as router:
        fut = router.submit_feed("m", {"x": 1})
        (model, primary_fut), = slow.submits  # least loaded = slow
        assert model == "m"
        _wait_for(lambda: fast.submits, msg="hedge fire")
        (_, hedge_fut), = fast.submits
        hedge_fut.set_result({"y": 2})
        assert fut.result(5) == {"y": 2}
        _wait_for(primary_fut.cancelled, msg="loser cancellation")
        assert router.hedge_stats() == {"win": 1, "lose": 0}


def test_hedge_lose_cancels_hedge():
    slow, fast = FakeEngine("slow"), FakeEngine("fast", load=5)
    with _router([slow, fast], hedge_ms=5) as router:
        fut = router.submit_feed("m", {"x": 1})
        (_, primary_fut), = slow.submits
        _wait_for(lambda: fast.submits, msg="hedge fire")
        (_, hedge_fut), = fast.submits
        primary_fut.set_result({"y": 1})
        assert fut.result(5) == {"y": 1}
        _wait_for(hedge_fut.cancelled, msg="hedge cancellation")
        assert router.hedge_stats() == {"win": 0, "lose": 1}


def test_no_hedge_without_second_replica():
    only = FakeEngine("only")
    with _router([only], hedge_ms=1) as router:
        fut = router.submit_feed("m", {"x": 1})
        time.sleep(0.05)
        (_, primary_fut), = only.submits
        primary_fut.set_result({"y": 3})
        assert fut.result(5) == {"y": 3}
        assert router.hedge_stats() == {"win": 0, "lose": 0}


def test_hedge_adaptive_no_history_no_hedge():
    a, b = FakeEngine("a"), FakeEngine("b", load=5)
    with _router([a, b], hedge_ms=-1) as router:
        fut = router.submit_feed("m", {"x": 1})
        time.sleep(0.05)
        assert not b.submits  # no latency history: adaptive stays off
        a.submits[0][1].set_result({})
        fut.result(5)


# ---------------------------------------------------------------------------
# fault grammar: serving rules
# ---------------------------------------------------------------------------


def test_fault_plan_serving_grammar():
    plan = fault_injection.FaultPlan(
        "serve_error:m:req:2;serve_delay:n:req:1:5;"
        "replica_kill:step:3;replica_kill:r0:step:7")
    acts = [(r.action, r.cmd, r.n) for r in plan.rules]
    assert ("serve_error", "m", 2) in acts
    assert ("serve_delay", "n", 1) in acts
    assert ("replica_kill", "*", 3) in acts
    assert ("replica_kill", "r0", 7) in acts
    with pytest.raises(ValueError):
        fault_injection.FaultPlan("serve_error:m:2")  # missing req
    with pytest.raises(ValueError):
        fault_injection.FaultPlan("replica_kill:banana")


def test_serve_error_fires_on_nth_request():
    plan = fault_injection.FaultPlan("serve_error:m:req:2")
    plan.on_serve("m")  # request 1 passes
    with pytest.raises(fault_injection.InjectedServeError):
        plan.on_serve("m")
    plan.on_serve("m")  # request 3 passes (one-shot count)
    plan.on_serve("other")  # other models never match


def test_replica_kill_fires_on_step():
    plan = fault_injection.FaultPlan("replica_kill:r0:step:3")
    plan.on_replica_step("r0", 2)
    plan.on_replica_step("r1", 3)  # other replica untouched
    with pytest.raises(fault_injection.InjectedReplicaDeath):
        plan.on_replica_step("r0", 3)


def test_serving_rules_do_not_leak_into_rpc():
    plan = fault_injection.FaultPlan("serve_error:send_grad:req:1")
    plan.on_rpc("send_grad")  # an RPC named like the model: no fire


def test_router_routes_around_injected_dispatch_error():
    a, b = FakeDecodeEngine("a"), FakeDecodeEngine("b", load=5)
    fault_injection.install("serve_error:a:req:1")
    with _router([a, b]) as router:
        fut = router.submit([1], 4)
        # the injected dispatch-edge error on a sent the request to b
        (req,) = b.requests
        req.future.set_result([4])
        assert fut.result(5) == [4]
        assert not a.requests


# ---------------------------------------------------------------------------
# canary promotion (fake replicas, real scopes)
# ---------------------------------------------------------------------------


class FakeServedModel:
    """Decode-replica duck-alike whose greedy stream is a pure function
    of its scope's 'w' parameter — weight swaps visibly change the
    stream, which is exactly what the drift gate reads."""

    def __init__(self, name):
        self.name = name
        self.scope = Scope()
        self.scope.set("w", np.zeros(2, np.float32))
        self._exec_lock = threading.Lock()
        self._healthy = True

    def healthy(self):
        return self._healthy

    def load(self):
        return 0

    def submit_request(self, *a, **kw):  # kind tag only
        raise NotImplementedError

    def submit(self, prompt, max_new_tokens, eos_id=None,
               tenant="default"):
        fut = concurrent.futures.Future()
        w = int(np.asarray(self.scope.get("w")).sum())
        fut.set_result([w] * int(max_new_tokens))
        return fut


def test_weightset_roundtrip_scope():
    s = Scope()
    s.set("a", np.arange(4, dtype=np.float32))
    s.set("b", np.ones((2, 2), np.float32))
    ws = WeightSet.from_scope(s, ["a", "b"])
    assert ws.names() == ["a", "b"] and len(ws) == 2
    s2 = Scope()
    ws.apply(s2)
    assert np.array_equal(np.asarray(s2.get("a")), np.arange(4))
    with pytest.raises(KeyError, match="not in scope"):
        WeightSet.from_scope(s, ["a", "missing"])


def test_promotion_gates_verdict():
    base = {"streams": [[1, 2]], "error_rate": 0.0,
            "mean_latency_s": 0.01}
    ok, reasons = PromotionGates().verdict(dict(base), dict(base))
    assert ok and not reasons
    bad = dict(base, error_rate=0.5)
    ok, reasons = PromotionGates(max_error_rate=0.0).verdict(bad, base)
    assert not ok and "error_rate" in reasons[0]
    slow = dict(base, mean_latency_s=1.0)
    ok, reasons = PromotionGates(max_latency_ratio=2.0).verdict(slow,
                                                                base)
    assert not ok and "latency" in reasons[0]
    drifted = dict(base, streams=[[1, 9]])
    ok, reasons = PromotionGates(max_drift=0.0).verdict(drifted, base)
    assert not ok and "drift" in reasons[0]
    ok, _ = PromotionGates(max_drift=0.5).verdict(drifted, base)
    assert ok  # 1 of 2 positions drifted == the ceiling


def test_promote_converges_group():
    reps = [FakeServedModel("r0"), FakeServedModel("r1")]
    with _router(reps) as router:
        report = promote(
            router, WeightSet({"w": np.ones(2, np.float32)}),
            probe_prompts=[[1]], probe_max_new_tokens=2,
            gates=PromotionGates(max_drift=None))
        assert report["outcome"] == "promoted"
        assert [r["replica"] for r in report["replicas"]] == ["r0", "r1"]
        for rep in reps:
            assert np.asarray(rep.scope.get("w")).sum() == 2
            # the hold was released: back in rotation
        assert all(not r.held for r in router.replicas())


def test_promote_drift_gate_rolls_back_canary():
    reps = [FakeServedModel("r0"), FakeServedModel("r1")]
    with _router(reps) as router:
        report = promote(
            router, WeightSet({"w": np.ones(2, np.float32)}),
            probe_prompts=[[1]], probe_max_new_tokens=2,
            # any flip rolls back; a fake replica answers in microseconds,
            # so the latency ratio is the sandbox's load, not the swap's
            gates=PromotionGates(max_drift=0.0, max_latency_ratio=None))
        assert report["outcome"] == "rolled_back"
        assert report["rolled_back_on"] == "r0"
        assert "drift" in report["reasons"][0]
        for rep in reps:  # canary restored, r1 never touched
            assert np.asarray(rep.scope.get("w")).sum() == 0
        assert all(not r.held for r in router.replicas())


def test_promote_injected_probe_error_rolls_back():
    reps = [FakeServedModel("r0"), FakeServedModel("r1")]
    # land the injected error in r0's post-swap probe window:
    # baseline probes consume count 1, post-swap starts at 2
    fault_injection.install("serve_error:r0:req:2")
    with _router(reps) as router:
        report = promote(
            router, WeightSet({"w": np.ones(2, np.float32)}),
            probe_prompts=[[1]], probe_max_new_tokens=2,
            gates=PromotionGates(max_error_rate=0.0, max_drift=None))
        assert report["outcome"] == "rolled_back"
        assert np.asarray(reps[0].scope.get("w")).sum() == 0


def test_promote_validates_inputs():
    with _router([FakeServedModel("r0")]) as router:
        ws = WeightSet({"w": np.ones(2, np.float32)})
        with pytest.raises(ValueError, match="non-empty"):
            promote(router, ws, probe_prompts=[])
        with pytest.raises(KeyError, match="unknown replicas"):
            promote(router, ws, probe_prompts=[[1]], order=["nope"])


# ---------------------------------------------------------------------------
# HTTP frontend
# ---------------------------------------------------------------------------


class FakeBackend:
    """Router duck-alike for the frontend: canned decode results, a
    stats page, and recorded drain calls."""

    def __init__(self):
        self.gate = None  # a Future the next submit returns unresolved
        self.drained = []

    def submit(self, prompt, max_new_tokens, eos_id=None,
               tenant="default"):
        if self.gate is not None:
            fut, self.gate = self.gate, None
            return fut
        fut = concurrent.futures.Future()
        fut.set_result([int(t) + 1 for t in prompt][:max_new_tokens])
        return fut

    def stats(self):
        return {"router": "fake", "replicas": []}


def _post(url, payload, timeout=10):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_frontend_generate_and_pages():
    with Frontend(FakeBackend()) as fe:
        base = f"http://{fe.host}:{fe.port}"
        code, body = _get(f"{base}/healthz")
        assert code == 200 and body["ok"]
        code, body = _get(f"{base}/routerz")
        assert code == 200 and body["router"] == "fake"
        code, body = _post(f"{base}/v1/generate",
                           {"prompt": [1, 2, 3], "max_new_tokens": 2})
        assert code == 200 and body["tokens"] == [2, 3]
        assert body["latency_s"] >= 0


def test_frontend_error_mapping():
    class Erroring(FakeBackend):
        def __init__(self, exc):
            super().__init__()
            self.exc = exc

        def submit(self, *a, **kw):
            raise self.exc

    cases = [
        (ServingOverloadError("full", reason="overload"), 429),
        (ServingOverloadError("bye", reason="draining"), 503),
        (ModelNotLoadedError("no such model"), 404),
        (ValueError("bad"), 400),
    ]
    for exc, want in cases:
        with Frontend(Erroring(exc)) as fe:
            code, body = _post(f"http://{fe.host}:{fe.port}/v1/generate",
                               {"prompt": [1], "max_new_tokens": 1})
            assert code == want, (exc, code)
            assert "error" in body
    with Frontend(FakeBackend()) as fe:
        base = f"http://{fe.host}:{fe.port}"
        code, _ = _post(f"{base}/v1/generate", {"prompt": []})
        assert code == 400  # empty prompt
        code, _ = _post(f"{base}/nope", {})
        assert code == 404
        req = urllib.request.Request(f"{base}/v1/generate",
                                     data=b"not json{{")
        try:
            urllib.request.urlopen(req, timeout=10)
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400


def test_frontend_drain_finishes_inflight_then_closes(tmp_path):
    """Satellite 2: drain under an OPEN connection — the in-flight
    request gets its 200, new admissions get a typed 503, and only then
    does the listener close."""
    backend = FakeBackend()
    gate = concurrent.futures.Future()
    backend.gate = gate

    class DrainRecorder:
        name = "rec"

        def drain(self, timeout=None):
            backend.drained.append(time.monotonic())

    rec = DrainRecorder()

    class Rep:
        engine = rec

    backend.replicas = lambda: [Rep()]
    fe = Frontend(backend)
    base = f"http://{fe.host}:{fe.port}"
    got = {}

    def client():
        got["resp"] = _post(f"{base}/v1/generate",
                            {"prompt": [5], "max_new_tokens": 4})

    t = threading.Thread(target=client, daemon=True)
    t.start()
    _wait_for(lambda: fe.stats()["inflight"] == 1,
              msg="request in flight")
    drained_ok = {}

    def draining():
        drained_ok["ok"] = fe.drain(timeout=10)

    dt = threading.Thread(target=draining, daemon=True)
    dt.start()
    _wait_for(lambda: backend.drained, msg="engine drain call")
    # admission is closed while the first request is still in flight
    code, body = _post(f"{base}/v1/generate",
                       {"prompt": [1], "max_new_tokens": 1})
    assert code == 503 and body["reason"] == "draining"
    assert not fe.stats()["closed"]  # listener still up for the response
    gate.set_result([6, 7])  # in-flight batch completes
    t.join(timeout=10)
    dt.join(timeout=10)
    assert got["resp"][0] == 200 and got["resp"][1]["tokens"] == [6, 7]
    assert drained_ok["ok"] is True
    assert fe.stats()["closed"]
    # ordering: engines drained BEFORE the listener closed
    assert backend.drained[0] <= time.monotonic()
    fe.close()


def test_frontend_drain_idempotent_and_close():
    fe = Frontend(FakeBackend())
    assert fe.drain(timeout=1) is True
    assert fe.drain(timeout=1) is True  # second drain: no-op
    fe.close()


_SIGTERM_CHILD = r"""
import concurrent.futures, json, threading, time, urllib.request, os, signal
from paddle_tpu.serving.frontend import Frontend

class Backend:
    def submit(self, prompt, max_new_tokens, eos_id=None, tenant="default"):
        fut = concurrent.futures.Future()
        # resolve AFTER the SIGTERM lands: the drain must wait for us
        threading.Timer(0.4, fut.set_result, args=([42],)).start()
        return fut

fe = Frontend(Backend())
fe.install_drain(timeout=10, poll_s=0.02)
out = {}
def client():
    req = urllib.request.Request(
        f"http://{fe.host}:{fe.port}/v1/generate",
        data=json.dumps({"prompt": [1], "max_new_tokens": 1}).encode())
    with urllib.request.urlopen(req, timeout=10) as resp:
        out["body"] = json.loads(resp.read())
t = threading.Thread(target=client)
t.start()
while fe.stats()["inflight"] < 1:
    time.sleep(0.005)
os.kill(os.getpid(), signal.SIGTERM)  # drain, not drop
t.join(timeout=10)
print("CHILD_RESULT " + json.dumps(out.get("body")), flush=True)
"""


def test_frontend_sigterm_drain_completes_inflight_subprocess():
    """Satellite 2, end to end: SIGTERM during an open HTTP connection
    — the in-flight generation finishes and the response is written
    before the handler chain re-delivers the signal."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo_root + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _SIGTERM_CHILD], capture_output=True,
        text=True, timeout=120, env=env, cwd=repo_root)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("CHILD_RESULT ")]
    assert lines, (proc.stdout, proc.stderr)
    body = json.loads(lines[0][len("CHILD_RESULT "):])
    assert body["tokens"] == [42]
    # after the drain the chained handler re-delivers SIGTERM; from the
    # watcher thread the restore is deferred (signal.signal is
    # main-thread-only) and the process exits normally instead — both
    # shapes mean the drain finished BEFORE termination
    assert proc.returncode in (0, -signal.SIGTERM), proc.returncode
