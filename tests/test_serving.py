"""Production serving lane (paddle_tpu/serving, docs/SERVING.md).

Acceptance contract (ISSUE 6): an in-process engine under >= 8
concurrent clients forms multi-request batches (pt_serve_batch_size has
mass above 1), never recompiles after warmup for in-bucket shapes
(compile-cache miss counters flat across the steady state), rejects
over-admission traffic with a typed ServingOverloadError instead of
queueing unboundedly, and reports p50/p99 request latency through the
real /metricsz endpoint plus a /servez status page.  Runs on the plain
single-device executor — no GSPMD, so the container's XLA:CPU GSPMD
caveat does not apply and everything stays in-process.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.serving import (BucketPolicy, Engine, FeedValidationError,
                                ModelNotLoadedError, ServingDeadlineError,
                                ServingOverloadError)
from paddle_tpu.serving.batching import (Request, assemble_batch,
                                         split_outputs)


@pytest.fixture(scope="module")
def saved_model(tmp_path_factory):
    """An MLP saved_inference_model with a dynamic batch dim (the
    test_inference.py idiom), plus its reference forward outputs."""
    d = str(tmp_path_factory.mktemp("serve_model"))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        pred = fluid.layers.fc(h, size=3, act="softmax")
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=main)
        xb = np.random.RandomState(0).uniform(
            -1, 1, (4, 8)).astype("float32")
        (expect,) = exe.run(main, feed={"x": xb}, fetch_list=[pred.name])
    return d, xb, np.asarray(expect)


# ---------------------------------------------------------------------------
# bucket policy / batch assembly units
# ---------------------------------------------------------------------------


def test_bucket_policy_selection():
    p = BucketPolicy(batch_buckets="8,1,2,4", seq_buckets="32, 64")
    assert p.batch_buckets == (1, 2, 4, 8)
    assert p.batch_bucket(1) == 1
    assert p.batch_bucket(3) == 4
    assert p.batch_bucket(8) == 8
    assert p.batch_bucket(9) is None  # oversize: caller rejects
    assert p.seq_bucket(10) == 32
    assert p.seq_bucket(64) == 64
    assert p.seq_bucket(100) == 100  # beyond largest: pass-through
    assert p.max_rows == 8
    with pytest.raises(ValueError):
        BucketPolicy(batch_buckets="0,2")
    with pytest.raises(ValueError):
        BucketPolicy(batch_buckets="")
    # positivity holds on the list path too, not just the string spec
    with pytest.raises(ValueError, match="positive"):
        BucketPolicy(batch_buckets=[0])
    with pytest.raises(ValueError, match="positive"):
        BucketPolicy(batch_buckets=[4, -1])
    assert BucketPolicy(batch_buckets=[8, 2]).batch_buckets == (2, 8)


def test_bucket_policy_flag_defaults():
    fluid.set_flags({"FLAGS_serving_batch_buckets": "2,4"})
    try:
        assert BucketPolicy().batch_buckets == (2, 4)
    finally:
        fluid.set_flags({"FLAGS_serving_batch_buckets": "1,2,4,8,16"})
    assert BucketPolicy().batch_buckets == (1, 2, 4, 8, 16)


def test_assemble_and_split_round_trip():
    import concurrent.futures

    def req(rows, fill):
        feed = {"x": np.full((rows, 3), fill, "float32")}
        return Request(feed, rows, "t", concurrent.futures.Future(),
                       (("x", (3,), "float32"),))

    batch = [req(1, 1.0), req(2, 2.0)]
    feed, slices = assemble_batch(batch, 4)
    assert feed["x"].shape == (4, 3)  # padded to the bucket
    assert slices == [(0, 1), (1, 3)]
    np.testing.assert_array_equal(feed["x"][3], 0.0)  # zero padding
    outs = split_outputs({"y": feed["x"] * 10}, slices)
    assert outs[0]["y"].shape == (1, 3) and float(outs[0]["y"][0, 0]) == 10
    assert outs[1]["y"].shape == (2, 3) and float(outs[1]["y"][0, 0]) == 20


def test_split_outputs_copies_only_partial_slices():
    """A smaller-than-bucket slice is copied (a retained result must not
    pin the bucket-sized batch array), but a lone max-size request whose
    slice IS the whole array skips the pointless memcpy."""
    y = np.arange(12, dtype="float32").reshape(4, 3)
    (full,) = split_outputs({"y": y}, [(0, 4)])
    assert np.shares_memory(full["y"], y)  # nothing to pin: no copy
    part, rest = split_outputs({"y": y}, [(0, 1), (1, 4)])
    assert not np.shares_memory(part["y"], y)
    assert not np.shares_memory(rest["y"], y)
    # the skip must not leak a read-only view (np.asarray over a jax
    # output buffer is read-only): writability is uniform regardless of
    # whether the request landed bucket-exact
    ro = y.copy()
    ro.setflags(write=False)
    (full_ro,) = split_outputs({"y": ro}, [(0, 4)])
    assert full_ro["y"].flags.writeable
    assert not np.shares_memory(full_ro["y"], ro)


# ---------------------------------------------------------------------------
# engine end-to-end (the acceptance scenario)
# ---------------------------------------------------------------------------


def _scraped_hist(parsed, name, **labels):
    """Rebuild a hist_data()-shaped dict from a parse_text() family."""
    fam = parsed.get(name)
    assert fam is not None, f"{name} missing from /metricsz"
    buckets, count = [], 0
    for lbl, value in fam["samples"]:
        kind = lbl.get("__sample__")
        rest = {k: v for k, v in lbl.items()
                if k not in ("__sample__", "le")}
        if rest != labels:
            continue
        if kind == "bucket":
            buckets.append((float(lbl["le"]), int(value)))
        elif kind == "count":
            count = int(value)
    return {"buckets": sorted(buckets), "count": count}


def test_engine_end_to_end_slo(saved_model):
    """>= 8 concurrent closed-loop clients: multi-request batches form,
    nothing recompiles in the steady state, and p50/p99 request latency
    is served through the real /metricsz endpoint; /servez lists the
    model, bucket set and cache hit rate."""
    d, xb, expect = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1,2,4,8",
                 max_wait_ms=20, max_queue=256, name="e2e",
                 auto_start=False)
    warmed = eng.warmup()
    assert warmed == {"mlp": 4}  # one executable per batch bucket
    eng.start()

    def cache_misses():
        fam = obs.REGISTRY.get("pt_compile_cache_total")
        if fam is None:
            return 0
        return sum(v for k, v in fam._snapshot()["samples"].items()
                   if k[1] == "miss")

    def client(i, n=6):
        for _ in range(n):
            out = eng.infer("mlp", {"x": xb[i % 4:i % 4 + 1]},
                            tenant=f"tenant{i % 2}", timeout=30)
            (y,) = out.values()
            assert y.shape == (1, 3)
            np.testing.assert_allclose(y[0], expect[i % 4], rtol=1e-4)

    # wave 1 primes any residual first-dispatch work; the steady-state
    # gate measures wave 2 only
    threads = [threading.Thread(target=client, args=(i,)) for i in
               range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    steady0 = cache_misses()
    threads = [threading.Thread(target=client, args=(i,)) for i in
               range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cache_misses() == steady0, \
        "steady-state serving traffic recompiled an executable"

    # continuous batching formed multi-request batches
    snap = obs.snapshot()
    hist = snap["pt_serve_batch_size"]["samples"][("mlp",)]
    mass_above_1 = hist["count"] - hist["buckets"][0][1]
    assert mass_above_1 > 0, "no multi-request batch ever formed"
    # every dispatched batch after warmup hit a warm bucket executable
    cache = snap["pt_serve_executable_cache_total"]["samples"]
    assert cache.get(("mlp", "cold"), 0) == 0
    assert cache.get(("mlp", "warm"), 0) > 0

    # per-tenant accounting
    tenants = snap["pt_serve_requests_total"]["samples"]
    assert tenants[("mlp", "tenant0")] > 0
    assert tenants[("mlp", "tenant1")] > 0

    # SLO surfaces through the REAL endpoint: scrape /metricsz, rebuild
    # the latency histogram, quantile it; then read /servez
    server = obs.MetricsServer(port=0)
    try:
        text = urllib.request.urlopen(
            f"http://{server.host}:{server.port}/metricsz",
            timeout=10).read().decode()
        parsed = obs.parse_text(text)
        lat = _scraped_hist(parsed, "pt_serve_request_latency_seconds",
                            model="mlp")
        assert lat["count"] >= 96  # 2 waves x 8 clients x 6 requests
        p50 = obs.hist_quantile(lat, 0.50)
        p99 = obs.hist_quantile(lat, 0.99)
        assert p50 is not None and p99 is not None and p99 >= p50
        servez = json.loads(urllib.request.urlopen(
            f"http://{server.host}:{server.port}/servez",
            timeout=10).read().decode())
        entry = [e for e in servez["engines"] if e["engine"] == "e2e"]
        assert entry, f"/servez does not list the engine: {servez}"
        mstats = entry[0]["models"]["mlp"]
        assert entry[0]["buckets"]["batch"] == [1, 2, 4, 8]
        assert mstats["executable_cache"]["hit_rate"] == 1.0
        assert mstats["warm_executables"] == 4
        assert mstats["latency_seconds"]["p50"] is not None
    finally:
        server.stop()
        eng.close()


def test_admission_control_rejects_typed(saved_model):
    """Beyond FLAGS_serving_max_queue the engine sheds with a typed
    ServingOverloadError instead of queueing unboundedly; queued work
    still completes once the scheduler starts."""
    d, xb, _ = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1,2,4,8", max_queue=2,
                 name="adm", auto_start=False)  # not started: queue fills
    f1 = eng.submit("mlp", {"x": xb[:1]})
    f2 = eng.submit("mlp", {"x": xb[:1]})
    rej0 = obs.REGISTRY.get("pt_serve_rejected_total")
    rej0 = rej0._snapshot()["samples"].get(("mlp", "overload"), 0) \
        if rej0 else 0
    with pytest.raises(ServingOverloadError, match="admission limit"):
        eng.submit("mlp", {"x": xb[:1]})
    fam = obs.REGISTRY.get("pt_serve_rejected_total")
    assert fam._snapshot()["samples"][("mlp", "overload")] == rej0 + 1
    eng.start()  # drain: the admitted two complete
    assert f1.result(timeout=30)
    assert f2.result(timeout=30)
    eng.close()
    with pytest.raises(ServingOverloadError, match="closed"):
        eng.submit("mlp", {"x": xb[:1]})


def test_feed_validation_at_the_edge(saved_model):
    """Bad feeds fail at submit with typed errors naming the problem —
    never inside the shared XLA trace."""
    d, xb, _ = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1,2", name="val",
                 auto_start=False)
    with pytest.raises(FeedValidationError, match="missing"):
        eng.submit("mlp", {})
    with pytest.raises(FeedValidationError, match="unexpected"):
        eng.submit("mlp", {"x": xb[:1], "bogus": xb[:1]})
    with pytest.raises(FeedValidationError, match="static shape"):
        eng.submit("mlp", {"x": np.zeros((1, 9), "float32")})  # dim 1
    with pytest.raises(FeedValidationError, match="compatible"):
        eng.submit("mlp", {"x": np.zeros((1, 8), "int64")})
    with pytest.raises(FeedValidationError, match="largest batch bucket"):
        eng.submit("mlp", {"x": np.zeros((3, 8), "float32")})
    with pytest.raises(FeedValidationError, match="0 rows"):
        # a zero-row request would burn the batch timeout plus a device
        # dispatch on pure padding, then resolve empty
        eng.submit("mlp", {"x": np.empty((0, 8), "float32")})
    with pytest.raises(ModelNotLoadedError):
        eng.submit("nope", {"x": xb[:1]})
    # rejections booked under reason="invalid"
    fam = obs.REGISTRY.get("pt_serve_rejected_total")
    assert fam._snapshot()["samples"][("mlp", "invalid")] >= 6
    eng.close()


def test_multi_model_engine(saved_model, tmp_path):
    """Two models behind one engine: independent lanes, one shared
    bucket policy, distinct signatures in /servez."""
    d, xb, expect = saved_model
    d2 = str(tmp_path / "second")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="inp", shape=[5], dtype="float32")
        y = fluid.layers.fc(x, size=2)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d2, ["inp"], [y], exe,
                                      main_program=main)
    eng = Engine({"a": d, "b": d2}, batch_buckets="1,2",
                 name="multi")
    try:
        assert eng.models() == ["a", "b"]
        out_a = eng.infer("a", {"x": xb[:1]}, timeout=30)
        out_b = eng.infer("b", {"inp": np.ones((1, 5), "float32")},
                          timeout=30)
        (ya,) = out_a.values()
        (yb,) = out_b.values()
        assert ya.shape == (1, 3) and yb.shape == (1, 2)
        stats = eng.stats()
        sigs = {m["signature"] for m in stats["models"].values()}
        assert len(sigs) == 2  # distinct model signatures
        with pytest.raises(ValueError, match="already loaded"):
            eng.load_model("a", d)
    finally:
        eng.close()


def test_model_signature_feed_fetch_partition_distinct():
    """The signature delimits feeds from fetches: the same program
    exported as feeds=[a,b]/fetches=[c] vs feeds=[a]/fetches=[b,c] has
    a different serving interface and must not hash identically."""
    from paddle_tpu.serving.engine import model_signature

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        a = fluid.layers.data(name="a", shape=[4], dtype="float32")
        b = fluid.layers.data(name="b", shape=[4], dtype="float32")
        c = fluid.layers.elementwise_add(a, b)
    s1 = model_signature(main, ["a", "b"], [c.name])
    s2 = model_signature(main, ["a"], ["b", c.name])
    assert s1 != s2


def test_width_coerced_feeds_stay_warm(saved_model):
    """A same-kind width mismatch (float64 into a float32 var) is
    coerced at submit, so it lands in the SAME warm bucket executables
    as float32 traffic — no cold compile, no segregated batch lane."""
    d, xb, expect = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1,2", name="width",
                 auto_start=False)
    eng.warmup()
    eng.start()

    def cold_count():
        fam = obs.REGISTRY.get("pt_serve_executable_cache_total")
        return fam._snapshot()["samples"].get(("mlp", "cold"), 0) \
            if fam else 0

    try:
        cold0 = cold_count()
        out = eng.infer("mlp", {"x": xb[:1].astype("float64")},
                        timeout=30)
        (y,) = out.values()
        np.testing.assert_allclose(y, expect[:1], rtol=1e-4)
        assert cold_count() == cold0, \
            "width-coerced feed booked a cold executable"
    finally:
        eng.close()


def test_recreated_engine_does_not_inherit_stats(saved_model):
    """The registry is process-cumulative per model name; a fresh engine
    serving the same name must report ITS OWN cache counts and latency
    quantiles, not a closed predecessor's."""
    d, xb, _ = saved_model
    e1 = Engine({"mlp": d}, batch_buckets="1,2", name="gen1",
                auto_start=False)
    e1.warmup()
    e1.start()
    for _ in range(3):
        e1.infer("mlp", {"x": xb[:1]}, timeout=30)
    e1.close()
    e2 = Engine({"mlp": d}, batch_buckets="1,2", name="gen2",
                auto_start=False)
    try:
        st = e2.stats()["models"]["mlp"]
        assert st["latency_seconds"] == {}  # nothing inherited
        assert st["executable_cache"] == {
            "warmup": 0, "warm": 0, "cold": 0, "hit_rate": None}
        assert st["requests"] == 0 and st["batches"] == 0
    finally:
        e2.close()


def test_fixed_leading_dim_model_rejected(tmp_path):
    """A model whose feed var has a FIXED leading dim cannot be batched
    (no pad, no concat): load_model rejects it with the fix named,
    instead of the batcher feeding shape-violating batches into XLA."""
    d = str(tmp_path / "fixed_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[2, 8],
                              append_batch_size=False, dtype="float32")
        y = fluid.layers.fc(x, size=4)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [y], exe,
                                      main_program=main)
    with pytest.raises(ValueError, match="FIXED leading dim"):
        Engine({"fixed": d}, batch_buckets="1,2", name="fx",
               auto_start=False)


def test_scalar_feed_model_rejected(tmp_path):
    """A scalar-shaped feed var has no batch dim at all, so it can
    neither pad nor concatenate: load_model rejects it typed instead of
    loading a model every conforming request would then fail against."""
    d = str(tmp_path / "scalar_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="s", shape=[],
                              append_batch_size=False, dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["s"], [y], exe,
                                      main_program=main)
    with pytest.raises(ValueError, match="scalar-shaped"):
        Engine({"sc": d}, batch_buckets="1,2", name="sc",
               auto_start=False)


def test_unwarmable_dynamic_seq_model_warns(tmp_path):
    """A dynamic dim-1 feed with NO sequence buckets configured (the
    default) makes warmup() a silent no-op — every traffic length would
    compile cold in the request path, so load warns with the flag fix."""
    d = str(tmp_path / "dynseq_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="seq", shape=[-1], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["seq"], [y], exe,
                                      main_program=main)
    with pytest.warns(UserWarning, match="serving_seq_buckets"):
        eng = Engine({"m": d}, batch_buckets="1,2", seq_buckets="",
                     name="nowarm", auto_start=False)
    try:
        assert eng.warmup() == {"m": 0}  # nothing warmable, as warned
    finally:
        eng.close()


def test_model_not_loaded_error_str_unquoted(saved_model):
    """ModelNotLoadedError renders its message plain, not through
    KeyError.__str__'s repr (quotes + escapes in every log line)."""
    d, _, _ = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1", name="str",
                 auto_start=False)
    try:
        with pytest.raises(ModelNotLoadedError) as ei:
            eng.submit("nope", {})
        assert not str(ei.value).startswith('"')
        assert "not loaded" in str(ei.value)
    finally:
        eng.close()


def test_batch_reduced_output_model_rejected(tmp_path):
    """A fetch without a dynamic leading dim (e.g. a whole-batch mean)
    cannot be row-sliced back to requests: request 0 would silently get
    the aggregate computed over padding zeros and later requests empty
    arrays — load_model rejects it with the fix named."""
    d = str(tmp_path / "reduced_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.reduce_mean(fluid.layers.fc(x, size=4))
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [y], exe,
                                      main_program=main)
    with pytest.raises(ValueError, match="dynamic leading"):
        Engine({"red": d}, batch_buckets="1,2", name="rd",
               auto_start=False)


def test_engine_closed_guards(saved_model):
    """load_model()/start() after close() raise typed errors instead of
    creating un-closable lanes or hanging futures."""
    d, _, _ = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1", name="cg",
                 auto_start=False)
    eng.close()
    with pytest.raises(ServingOverloadError, match="closed"):
        eng.load_model("late", d)
    with pytest.raises(ServingOverloadError, match="closed"):
        eng.start()
    with pytest.raises(ServingOverloadError, match="closed"):
        eng.warmup()  # must not silently compile for a dead engine


def test_duplicate_model_name_across_engines_warns(saved_model):
    """pt_serve_* series are keyed by model name: a second engine
    serving the same name warns about metric aliasing instead of
    corrupting silently."""
    d, _, _ = saved_model
    e1 = Engine({"dup": d}, batch_buckets="1", name="w1",
                auto_start=False)
    try:
        with pytest.warns(UserWarning, match="alias"):
            e2 = Engine({"dup": d}, batch_buckets="1", name="w2",
                        auto_start=False)
        e2.close()
    finally:
        e1.close()


def test_sequence_bucketing_dynamic_dim(tmp_path):
    """A feed with a dynamic dim-1 pads to the configured sequence
    buckets; different lengths land in their buckets (and never mix in
    one batch), and zero padding is invisible through reduce_sum."""
    d = str(tmp_path / "seq_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="seq", shape=[-1], dtype="float32")
        y = fluid.layers.reduce_sum(x, dim=1, keep_dim=True)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["seq"], [y], exe,
                                      main_program=main)
    eng = Engine({"m": d}, batch_buckets="1,2,4", seq_buckets="4,8",
                 name="seq", auto_start=False)
    assert eng.warmup() == {"m": 6}  # 3 batch x 2 seq buckets
    eng.start()
    try:
        f_short = eng.submit("m", {"seq": np.ones((1, 3), "float32")})
        f_long = eng.submit("m", {"seq": np.ones((1, 7), "float32")})
        (s,) = f_short.result(timeout=30).values()
        (l,) = f_long.result(timeout=30).values()
        assert float(s[0, 0]) == 3.0  # padding contributed nothing
        assert float(l[0, 0]) == 7.0
        # steady state: both seq buckets were warmed, nothing cold
        cache = obs.snapshot()[
            "pt_serve_executable_cache_total"]["samples"]
        assert cache.get(("m", "cold"), 0) == 0
    finally:
        eng.close()


def test_seq_padding_sliced_off_outputs(tmp_path):
    """A per-position model (dynamic dim-1 output): sequence padding is
    sliced back off before the future resolves — a (1, 3) request comes
    back (1, 3), never (1, seq_bucket) with garbage padding positions."""
    d = str(tmp_path / "pos_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="seq", shape=[-1], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["seq"], [y], exe,
                                      main_program=main)
    eng = Engine({"pos": d}, batch_buckets="1,2", seq_buckets="4,8",
                 name="pos", auto_start=False)
    eng.warmup()
    eng.start()
    try:
        out = eng.infer("pos", {"seq": np.ones((1, 3), "float32")},
                        timeout=30)
        (y_out,) = out.values()
        assert y_out.shape == (1, 3), y_out.shape
        np.testing.assert_array_equal(y_out, 2.0)
        # exact-bucket-length requests pass through unsliced
        out = eng.infer("pos", {"seq": np.ones((1, 4), "float32")},
                        timeout=30)
        (y_out,) = out.values()
        assert y_out.shape == (1, 4)
    finally:
        eng.close()


def test_ambiguous_multi_seq_feed_rejected(tmp_path):
    """A model with dynamic-length outputs fed two dynamic dim-1
    lengths that pad onto the SAME bucket: no unambiguous original
    length to slice the padding back to, so the edge rejects typed
    instead of silently resolving the future with padded positions
    computed from zeros.  (Differing lengths on different buckets — the
    seq2seq src/tgt case — stay servable: each padded length maps to
    exactly one original.)"""
    d = str(tmp_path / "two_seq_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        a = fluid.layers.data(name="a", shape=[-1], dtype="float32")
        b = fluid.layers.data(name="b", shape=[-1], dtype="float32")
        y = fluid.layers.elementwise_add(a, b)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["a", "b"], [y], exe,
                                      main_program=main)
    eng = Engine({"m": d}, batch_buckets="1,2", seq_buckets="4,8",
                 name="twoseq", auto_start=False)
    # padded dyn-output traffic requires verified slice-back, so warm
    # first; elementwise_add needs EQUAL lengths, so the cross-product
    # warmup skips (and warns about) the mixed assignments instead of
    # failing the whole warmup
    with pytest.warns(UserWarning, match="mixed sequence-bucket"):
        eng.warmup()
    eng.start()
    try:
        with pytest.raises(FeedValidationError, match="differing"):
            # 3 and 4 both land on bucket 4: which original would an
            # output of length 4 slice back to?
            eng.submit("m", {"a": np.ones((1, 3), "float32"),
                             "b": np.ones((1, 4), "float32")})
        # equal lengths stay servable, padded together and sliced back
        out = eng.infer("m", {"a": np.ones((1, 3), "float32"),
                              "b": np.ones((1, 3), "float32")},
                        timeout=30)
        (y_out,) = out.values()
        assert y_out.shape == (1, 3)
        np.testing.assert_array_equal(y_out, 2.0)
    finally:
        eng.close()


def test_execution_failure_fails_futures_not_scheduler(saved_model):
    """An exception inside batch execution resolves every affected
    future with the error instead of killing the scheduler thread and
    leaving callers blocked forever; the lane keeps serving the next
    request."""
    d, xb, expect = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1,2", name="boom",
                 auto_start=False)
    eng.warmup()
    eng.start()
    lane = eng._lanes["mlp"]
    real_run = lane.predictor.run_feed_dict
    calls = {"n": 0}

    def flaky(feed, validate=True):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected predictor failure")
        return real_run(feed, validate=validate)

    lane.predictor.run_feed_dict = flaky
    try:
        with pytest.raises(RuntimeError, match="injected"):
            eng.infer("mlp", {"x": xb[:1]}, timeout=30)
        # the failed batch books NO executable-cache outcome (a phantom
        # warm/cold count per retry would skew the /servez hit rate)
        assert lane._cache_counts["warm"] == 0
        assert lane._cache_counts["cold"] == 0
        # the scheduler thread survived: the next request serves fine
        out = eng.infer("mlp", {"x": xb[:1]}, timeout=30)
        (y,) = out.values()
        np.testing.assert_allclose(y, expect[:1], rtol=1e-4)
        # exactly the successful dispatch was booked (warmup() warmed
        # the bucket, so it resolves warm), and the queued-rows
        # accounting drained with the queue
        assert lane._cache_counts["warm"] == 1
        assert lane._cache_counts["cold"] == 0
        assert not lane._queued_rows
    finally:
        del lane.predictor.run_feed_dict
        eng.close()


def test_warmup_covers_mixed_seq_bucket_combinations(tmp_path):
    """Two dynamic dim-1 feeds may pad to DIFFERENT buckets in one
    request (the seq2seq src/tgt case with static-shape outputs):
    warmup must compile the cross product of bucket assignments, not
    just the uniform diagonal, or mixed-length traffic pays a cold
    compile in the request path despite the steady-state contract."""
    d = str(tmp_path / "pair_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        a = fluid.layers.data(name="a", shape=[-1], dtype="float32")
        b = fluid.layers.data(name="b", shape=[-1], dtype="float32")
        y = fluid.layers.elementwise_add(
            fluid.layers.reduce_sum(a, dim=1, keep_dim=True),
            fluid.layers.reduce_sum(b, dim=1, keep_dim=True))
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["a", "b"], [y], exe,
                                      main_program=main)
    eng = Engine({"pair": d}, batch_buckets="1,2", seq_buckets="4,8",
                 name="pair", auto_start=False)
    # 2 batch buckets x (2 seq buckets ^ 2 dynamic feeds) assignments
    assert eng.warmup() == {"pair": 8}
    eng.start()
    try:
        out = eng.infer("pair", {"a": np.ones((1, 3), "float32"),
                                 "b": np.ones((1, 6), "float32")},
                        timeout=30)
        (y_out,) = out.values()
        assert float(y_out[0, 0]) == 9.0  # 3 + 6, padding contributed 0
        cache = obs.snapshot()[
            "pt_serve_executable_cache_total"]["samples"]
        assert cache.get(("pair", "cold"), 0) == 0, \
            "mixed seq-bucket request compiled cold after warmup"
    finally:
        eng.close()


def test_seq_sliceback_skipped_on_width_collision(tmp_path):
    """When a NON-padded feed shares a padded feed's bucket width, an
    output of that width can't be matched to its feed with certainty:
    the engine skips the slice-back there, so the caller sees zero
    padding — never silent truncation of positions that actually
    followed the other feed."""
    d = str(tmp_path / "collide_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        a = fluid.layers.data(name="a", shape=[-1], dtype="float32")
        b = fluid.layers.data(name="b", shape=[4], dtype="float32")
        ya = fluid.layers.scale(a, scale=2.0)
        yb = fluid.layers.fc(b, size=2)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["a", "b"], [ya, yb], exe,
                                      main_program=main)
    eng = Engine({"m": d}, batch_buckets="1,2", seq_buckets="4,8",
                 name="collide", auto_start=False)
    eng.start()
    try:
        outs = eng.infer("m", {"a": np.ones((1, 3), "float32"),
                               "b": np.ones((1, 4), "float32")},
                         timeout=30)
        y_a = outs[ya.name]
        # `a` padded 3 -> 4 collides with b's fixed width 4: the
        # dynamic-length output stays at the padded width (safe zero
        # padding), not sliced to 3 on an uncertain match
        assert y_a.shape == (1, 4), y_a.shape
        np.testing.assert_array_equal(y_a[0, :3], 2.0)
        np.testing.assert_array_equal(y_a[0, 3], 0.0)  # pad position
    finally:
        eng.close()


def test_constant_width_dyn_declared_output_not_truncated(tmp_path):
    """A dynamic-DECLARED output whose runtime width is actually
    constant must not be sliced back when that width coincides with a
    padded sequence bucket: warmup observes the width staying constant
    across varied seq buckets and drops the output from slice-back, so
    real columns are never silently truncated."""
    d = str(tmp_path / "constw_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="seq", shape=[-1], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["seq"], [y], exe,
                                      main_program=main)
    eng = Engine({"cw": d}, batch_buckets="1,2", seq_buckets="4,8",
                 name="cw", auto_start=False)
    lane = eng._lanes["cw"]
    (out_name,) = lane.predictor.get_output_names()
    real_run = lane.predictor.run_feed_dict

    def const_width_run(feed, validate=True):
        # simulate a model whose dyn-declared output is constant width 8
        out = real_run(feed, validate=validate)
        rows = out[out_name].shape[0]
        out[out_name] = np.arange(rows * 8, dtype="float32").reshape(
            rows, 8)
        return out

    lane.predictor.run_feed_dict = const_width_run
    try:
        assert out_name in lane._dyn_seq_outputs  # declared dynamic
        eng.warmup()
        # width stayed 8 while fed seqs varied over (4, 8): not
        # sequence-following, removed from the slice-back set
        assert out_name not in lane._dyn_seq_outputs
        eng.start()
        # length 5 pads to bucket 8 == the constant width: without the
        # warmup refinement this would slice (1, 8) down to (1, 5)
        out = eng.infer("cw", {"seq": np.ones((1, 5), "float32")},
                        timeout=30)
        y_out = out[out_name]
        assert y_out.shape == (1, 8), y_out.shape
        np.testing.assert_array_equal(y_out[0], np.arange(8))
    finally:
        del lane.predictor.run_feed_dict
        eng.close()


def test_unwarmed_padded_dyn_output_request_rejected(tmp_path):
    """Before warmup() has verified which dyn-declared outputs actually
    track the fed sequence length, slicing padding back off is a guess
    (a constant-width output colliding with the padded bucket would be
    truncated): padded requests reject typed, bucket-aligned lengths
    stay servable, and warmup() lifts the restriction."""
    d = str(tmp_path / "unwarmed_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="seq", shape=[-1], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["seq"], [y], exe,
                                      main_program=main)
    eng = Engine({"uw": d}, batch_buckets="1,2", seq_buckets="4,8",
                 name="unwarmed", auto_start=False)
    eng.start()
    try:
        with pytest.raises(FeedValidationError, match="warmup"):
            eng.submit("uw", {"seq": np.ones((1, 5), "float32")})
        # a bucket-aligned length needs no slice-back: served (cold)
        out = eng.infer("uw", {"seq": np.ones((1, 4), "float32")},
                        timeout=30)
        (y_out,) = out.values()
        assert y_out.shape == (1, 4)
        eng.warmup()  # observes widths tracking the fed lengths
        out = eng.infer("uw", {"seq": np.ones((1, 5), "float32")},
                        timeout=30)
        (y_out,) = out.values()
        assert y_out.shape == (1, 5), y_out.shape
        np.testing.assert_array_equal(y_out, 2.0)
    finally:
        eng.close()


def test_single_seq_bucket_warmup_probe_confirms_widths(tmp_path):
    """With ONE sequence bucket the warmed shapes alone can't tell a
    sequence-following output from a constant-width one (nothing
    varies): warmup adds an off-bucket probe shape so the refinement
    still runs — a constant-width output is exempted from slice-back
    even in single-bucket configs."""
    d = str(tmp_path / "onebucket_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="seq", shape=[-1], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["seq"], [y], exe,
                                      main_program=main)
    eng = Engine({"ob": d}, batch_buckets="1,2", seq_buckets="8",
                 name="onebucket", auto_start=False)
    lane = eng._lanes["ob"]
    (out_name,) = lane.predictor.get_output_names()
    real_run = lane.predictor.run_feed_dict

    def const_width_run(feed, validate=True):
        out = real_run(feed, validate=validate)
        rows = out[out_name].shape[0]
        out[out_name] = np.arange(rows * 8, dtype="float32").reshape(
            rows, 8)
        return out

    lane.predictor.run_feed_dict = const_width_run
    try:
        # 2 batch buckets x 1 seq bucket; the synthetic probe shape
        # compiles too but is not a bucket shape, so it never counts —
        # in the warmup() return or in /servez's warm_executables
        assert eng.warmup() == {"ob": 2}
        assert lane.stats()["warm_executables"] == 2
        assert out_name not in lane._dyn_seq_outputs
        eng.start()
        # length 5 pads to 8 == the constant width: stays (1, 8), the
        # first 8 values intact — never truncated to (1, 5)
        out = eng.infer("ob", {"seq": np.ones((1, 5), "float32")},
                        timeout=30)
        y_out = out[out_name]
        assert y_out.shape == (1, 8), y_out.shape
        np.testing.assert_array_equal(y_out[0], np.arange(8))
    finally:
        del lane.predictor.run_feed_dict
        eng.close()


def test_probe_failure_tolerated_sliceback_stays_unverified(tmp_path):
    """A length-sensitive model failing the synthetic off-bucket probe
    must not become unwarmable: the real bucket shapes still warm (with
    a warning), and because slice-back could not be verified, padded
    dyn-output requests keep rejecting typed while bucket-aligned
    lengths serve."""
    d = str(tmp_path / "picky_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="seq", shape=[-1], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["seq"], [y], exe,
                                      main_program=main)
    eng = Engine({"pk": d}, batch_buckets="1,2", seq_buckets="8",
                 name="picky", auto_start=False)
    lane = eng._lanes["pk"]
    real_run = lane.predictor.run_feed_dict

    def picky_run(feed, validate=True):
        if feed["seq"].shape[1] != 8:  # only the bucket length compiles
            raise RuntimeError("length-sensitive model")
        return real_run(feed, validate=validate)

    lane.predictor.run_feed_dict = picky_run
    try:
        with pytest.warns(UserWarning, match="probe"):
            warmed = eng.warmup()
        assert warmed == {"pk": 2}  # both batch buckets; probe skipped
        eng.start()
        out = eng.infer("pk", {"seq": np.ones((1, 8), "float32")},
                        timeout=30)
        (y_out,) = out.values()
        assert y_out.shape == (1, 8)
        with pytest.raises(FeedValidationError, match="warmup"):
            eng.submit("pk", {"seq": np.ones((1, 5), "float32")})
    finally:
        del lane.predictor.run_feed_dict
        eng.close()


def test_close_during_warmup_stops_compiling(saved_model):
    """close() racing a warmup() must stop the warmup loop at the next
    shape (typed), not let it keep compiling the whole bucket cross
    product for a dead engine."""
    d, _, _ = saved_model
    eng = Engine({"cw2": d}, batch_buckets="1,2,4,8", name="closewarm",
                 auto_start=False)
    lane = eng._lanes["cw2"]
    real_run = lane.predictor.run_feed_dict
    ran = []

    def closing_run(feed, validate=True):
        out = real_run(feed, validate=validate)
        ran.append(feed["x"].shape)
        eng.close()  # concurrent close lands mid-warmup
        return out

    lane.predictor.run_feed_dict = closing_run
    try:
        with pytest.raises(ServingOverloadError, match="during warmup"):
            eng.warmup()
        assert len(ran) == 1, ran  # later bucket shapes never compiled
    finally:
        del lane.predictor.run_feed_dict
        eng.close()


def test_metrics_rebind_after_registry_reset(saved_model):
    """observability.reset() mid-run must not orphan a live lane's
    cached metric label children (the registry contract is 'call sites
    re-register lazily'): the next request notices the registry epoch
    moved, rebinds, and the pt_serve_* families keep exporting."""
    d, xb, expect = saved_model
    eng = Engine({"rb": d}, batch_buckets="1,2", name="rebind",
                 auto_start=False)
    eng.warmup()
    eng.start()
    try:
        eng.infer("rb", {"x": xb[:1]}, timeout=30)
        obs.reset()
        assert "pt_serve_request_latency_seconds" not in obs.snapshot()
        (y,) = eng.infer("rb", {"x": xb[:1]}, timeout=30).values()
        np.testing.assert_allclose(y, expect[:1], rtol=1e-4)
        snap = obs.snapshot()
        assert snap["pt_serve_requests_total"]["samples"].get(
            ("rb", "default"), 0) >= 1
        assert "pt_serve_request_latency_seconds" in snap
        # /servez keeps working off the rebound children too
        assert eng.stats()["models"]["rb"]["requests"] >= 2
    finally:
        eng.close()


def test_concurrent_start_spawns_one_scheduler(saved_model):
    """Racing Engine.start() calls must not spawn two scheduler threads
    for one lane (the loser of the _thread overwrite would never be
    joined, and two schedulers would split coalescable batches)."""
    d, xb, expect = saved_model
    eng = Engine({"racelane": d}, batch_buckets="1,2", name="race",
                 auto_start=False)
    barrier = threading.Barrier(8)

    def go():
        barrier.wait()
        eng.start()

    threads = [threading.Thread(target=go) for _ in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        schedulers = [t for t in threading.enumerate()
                      if t.name == "pt-serve-racelane"]
        assert len(schedulers) == 1, schedulers
        (y,) = eng.infer("racelane", {"x": xb[:1]}, timeout=30).values()
        np.testing.assert_allclose(y, expect[:1], rtol=1e-4)
    finally:
        eng.close()


def test_engine_init_partial_load_failure_cleans_up(saved_model,
                                                    tmp_path):
    """A load failure on the Nth model during Engine construction closes
    the already-built lanes and leaves nothing tracked on /servez — the
    caller never gets a reference to close()."""
    from paddle_tpu.serving import status

    d, _, _ = saved_model
    bad = str(tmp_path / "bad_fixed_model")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[2, 8],
                              append_batch_size=False, dtype="float32")
        y = fluid.layers.fc(x, size=4)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(bad, ["x"], [y], exe,
                                      main_program=main)
    n0 = len(status.live_engines())
    with pytest.raises(ValueError, match="FIXED leading dim"):
        Engine({"good": d, "bad": bad}, batch_buckets="1,2",
               name="partial", auto_start=False)
    assert len(status.live_engines()) == n0  # never tracked
    # the half-built engine left no aliasing residue: serving the same
    # model name again neither warns nor fails
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = Engine({"good": d}, batch_buckets="1,2", name="retry",
                     auto_start=False)
    eng.close()


def test_engine_init_auto_start_failure_cleans_up(saved_model,
                                                  monkeypatch):
    """auto_start runs inside __init__'s cleanup block: a scheduler
    thread that fails to spawn (process thread limit) must close the
    built lanes and untrack the engine — the caller has no reference."""
    from paddle_tpu.serving import engine as engine_mod
    from paddle_tpu.serving import status

    d, _, _ = saved_model
    closed = []
    real_close = engine_mod._ModelLane.close

    def boom(self):
        raise RuntimeError("can't start new thread")

    def record_close(self):
        closed.append(self.name)
        return real_close(self)

    monkeypatch.setattr(engine_mod._ModelLane, "start", boom)
    monkeypatch.setattr(engine_mod._ModelLane, "close", record_close)
    n0 = len(status.live_engines())
    with pytest.raises(RuntimeError, match="can't start new thread"):
        Engine({"mlp": d}, batch_buckets="1,2", name="nothread")
    assert closed == ["mlp"]  # the built lane was shut down
    assert len(status.live_engines()) == n0  # and untracked


def test_tenant_label_cardinality_capped(saved_model):
    """tenant is caller-supplied and feeds a metric label: beyond 64
    distinct tenants per lane, new ones book under __other__ instead of
    minting unbounded registry series."""
    d, xb, _ = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1,2", max_queue=256,
                 name="tn", auto_start=False)
    try:
        for i in range(70):
            eng.submit("mlp", {"x": xb[:1]}, tenant=f"user-{i}")
        tenants = eng.stats()["models"]["mlp"]["tenants"]
        assert len(tenants) <= 65  # 64 distinct + __other__
        assert tenants["__other__"] == 70 - 64
        assert tenants["user-0"] == 1
    finally:
        eng.close()


def test_submit_returns_future_rows(saved_model):
    """A multi-row request resolves to exactly its rows (padding never
    escapes), and results match the training-program forward."""
    d, xb, expect = saved_model
    with Engine({"mlp": d}, batch_buckets="1,2,4,8", name="rows") as eng:
        out = eng.infer("mlp", {"x": xb[:3]}, timeout=30)
        (y,) = out.values()
        assert y.shape == (3, 3)
        np.testing.assert_allclose(y, expect[:3], rtol=1e-4)


def test_servez_reregisters_after_unregister(saved_model):
    """track_engine has no registered-once latch: an
    unregister_page('/servez') (test cleanup, page reset) must not leave
    every later engine skipping registration and /servez 404ing for the
    rest of the process."""
    from paddle_tpu.observability import exposition
    from paddle_tpu.serving import status

    d, xb, _ = saved_model
    obs.unregister_page("/servez")
    assert "/servez" not in exposition._extra_pages
    eng = Engine({"mlp": d}, batch_buckets="1,2", name="reregz",
                 auto_start=False)
    try:
        assert exposition._extra_pages.get("/servez") is \
            status.servez_payload
    finally:
        eng.close()


def test_engine_init_cleans_up_when_servez_taken(saved_model):
    """If another subsystem owns /servez with a different renderer,
    Engine construction fails typed AND closes the lanes it already
    built (the caller has no engine reference to clean up with); after
    the foreign page is unregistered, construction self-heals."""
    from paddle_tpu.serving import status

    d, xb, _ = saved_model
    obs.unregister_page("/servez")
    obs.register_page("/servez", lambda: {"foreign": True})
    try:
        with pytest.raises(ValueError, match="already registered"):
            Engine({"mlp": d}, batch_buckets="1,2", name="takenz",
                   auto_start=False)
        # the partially-built engine is not tracked anywhere
        assert not any(e.name == "takenz" for e in status.live_engines())
    finally:
        obs.unregister_page("/servez")
    eng = Engine({"mlp": d}, batch_buckets="1,2", name="takenz2",
                 auto_start=False)
    try:
        assert eng.infer is not None  # constructed fine
    finally:
        eng.close()


def test_register_page_validation():
    with pytest.raises(ValueError, match="built-in"):
        obs.register_page("/metricsz", lambda: {})
    with pytest.raises(ValueError, match="start with"):
        obs.register_page("servez", lambda: {})
    # a second renderer for a live path raises instead of silently
    # vanishing the first subsystem's page; re-registering the SAME
    # renderer stays an idempotent no-op
    mine = lambda: {"ok": True}  # noqa: E731
    obs.register_page("/dupz", mine)
    try:
        obs.register_page("/dupz", mine)  # no-op
        with pytest.raises(ValueError, match="already registered"):
            obs.register_page("/dupz", lambda: {"other": True})
    finally:
        obs.unregister_page("/dupz")
    # every documented body form renders correctly, including a
    # JSON-serializable body paired with an explicit content type
    obs.register_page("/tuplez", lambda: ({"a": 1}, "application/json"))
    try:
        server = obs.MetricsServer(port=0)
        try:
            got = json.loads(urllib.request.urlopen(
                f"http://{server.host}:{server.port}/tuplez",
                timeout=10).read().decode())
            assert got == {"a": 1}, got
        finally:
            server.stop()
    finally:
        obs.unregister_page("/tuplez")
    # a page whose RETURN VALUE fails serialization (circular dict)
    # must also 500, not drop the connection with a traceback
    circ: dict = {}
    circ["self"] = circ
    obs.register_page("/circz", lambda: circ)
    try:
        server = obs.MetricsServer(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://{server.host}:{server.port}/circz",
                    timeout=10)
            assert ei.value.code == 500
        finally:
            server.stop()
    finally:
        obs.unregister_page("/circz")
    # a page that raises is a 500 on that request, not a server crash
    obs.register_page("/boomz", lambda: 1 / 0)
    try:
        server = obs.MetricsServer(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://{server.host}:{server.port}/boomz",
                    timeout=10)
            assert ei.value.code == 500
            # and the server still answers
            assert urllib.request.urlopen(
                f"http://{server.host}:{server.port}/healthz",
                timeout=10).read() == b"ok\n"
        finally:
            server.stop()
    finally:
        obs.unregister_page("/boomz")


# ---------------------------------------------------------------------------
# per-request deadlines (ISSUE 10 satellite)
# ---------------------------------------------------------------------------


def _deadline_rejections():
    fam = obs.REGISTRY.get("pt_serve_rejected_total")
    if fam is None:
        return 0
    return fam._snapshot()["samples"].get(("mlp", "deadline"), 0)


def test_deadline_off_by_default(saved_model):
    """FLAGS_serving_deadline_ms=0: requests carry no deadline and wait
    as long as it takes (the pre-deadline contract)."""
    d, xb, expect = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1,2", name="nodl",
                 auto_start=False)
    fut = eng.submit("mlp", {"x": xb[:1]})
    assert eng._lanes["mlp"]._queue[0].deadline is None
    import time

    time.sleep(0.05)  # would expire any sub-50ms deadline
    eng.start()
    np.testing.assert_allclose(fut.result(timeout=30)["fc_1.tmp_2"],
                               expect[:1], rtol=1e-5)
    eng.close()


def test_queued_request_past_deadline_resolves_typed(saved_model):
    """A request that outlives FLAGS_serving_deadline_ms while QUEUED
    resolves ServingDeadlineError when the scheduler reaches it (instead
    of waiting forever) and books reason="deadline"."""
    import time

    d, xb, _ = saved_model
    before = _deadline_rejections()
    eng = Engine({"mlp": d}, batch_buckets="1,2", name="dl",
                 auto_start=False, deadline_ms=200)
    eng.warmup()  # the follow-up request must not pay a cold compile
    expired = eng.submit("mlp", {"x": xb[:1]})
    time.sleep(0.3)  # expires in the (unstarted) queue
    eng.start()
    with pytest.raises(ServingDeadlineError, match="deadline while queued"):
        expired.result(timeout=30)
    # a fresh request on the SAME lane still serves normally
    ok = eng.submit("mlp", {"x": xb[:1]})
    assert ok.result(timeout=30)
    assert _deadline_rejections() == before + 1
    eng.close()


def test_deadline_caps_the_batch_mate_wait(saved_model):
    """A lone head request whose deadline is shorter than the
    batch-fill max-wait is dispatched EARLY (at half its deadline
    budget, leaving the other half for execution) and SERVED — not held
    the full max_wait and then expired after a burned dispatch."""
    import time

    d, xb, expect = saved_model
    eng = Engine({"mlp": d}, batch_buckets="1,2,4", name="dlw",
                 auto_start=False, deadline_ms=2000, max_wait_ms=30000)
    eng.warmup()  # warm: execution fits comfortably in the half-budget
    eng.start()
    t0 = time.monotonic()
    out = eng.infer("mlp", {"x": xb[:1]}, timeout=30)
    elapsed = time.monotonic() - t0
    np.testing.assert_allclose(next(iter(out.values())),
                               expect[:1], rtol=1e-4)
    assert elapsed < 10.0, (  # nowhere near the 30 s mate-wait
        f"deadline-bearing head waited {elapsed:.2f}s")
    eng.close()


def test_impossible_deadline_expires_promptly(saved_model):
    """A deadline no batching window can honor still resolves typed at
    ~the deadline (queued or in-flight), never after the full
    max_wait."""
    import time

    d, xb, _ = saved_model
    before = _deadline_rejections()
    eng = Engine({"mlp": d}, batch_buckets="1,2,4", name="dli",
                 auto_start=False, deadline_ms=1, max_wait_ms=30000)
    eng.warmup()
    eng.start()
    t0 = time.monotonic()
    fut = eng.submit("mlp", {"x": xb[:1]})
    with pytest.raises(ServingDeadlineError):
        fut.result(timeout=30)
    elapsed = time.monotonic() - t0
    assert elapsed < 5.0, f"waited {elapsed:.2f}s for a 1 ms deadline"
    assert _deadline_rejections() == before + 1
    eng.close()


def test_inflight_request_past_deadline_resolves_typed(saved_model):
    """A request whose deadline expires while its batch is IN FLIGHT
    gets the typed error, not a stale result (its batch-mates are
    unaffected)."""
    import concurrent.futures
    import time

    d, xb, _ = saved_model
    before = _deadline_rejections()
    eng = Engine({"mlp": d}, batch_buckets="1,2", name="dlf",
                 auto_start=False, deadline_ms=30)
    lane = eng._lanes["mlp"]
    # assemble the batch by hand so expiry deterministically happens
    # between dispatch and fan-out (the in-flight window)
    padded, rows, key, seq_pad = lane._validate_and_pad({"x": xb[:1]})
    late = Request(padded, rows, "t", concurrent.futures.Future(), key,
                   seq_pad, deadline_s=0.02)
    fresh = Request(padded, rows, "t", concurrent.futures.Future(), key,
                    seq_pad, deadline_s=0.0)
    time.sleep(0.05)  # `late` is now past deadline, "in flight"
    lane._execute([late, fresh])
    with pytest.raises(ServingDeadlineError, match="deadline in flight"):
        late.future.result(timeout=5)
    assert fresh.future.result(timeout=5)  # batch-mate unaffected
    assert _deadline_rejections() == before + 1
    eng.close()


# ---------------------------------------------------------------------------
# graceful drain (ISSUE 14 satellite: the elastic.DrainHandler hookup)
# ---------------------------------------------------------------------------


def test_engine_drain_fails_queued_typed_and_stops_admission(saved_model):
    """Engine.drain(): queued futures fail typed with
    reason="draining" (booked on pt_serve_rejected_total), new submits
    reject typed, and the engine stays OPEN — close() still owns
    teardown.  auto_start=False keeps everything queued, so the whole
    path is admission-edge only."""
    d, xb, _expect = saved_model
    eng = Engine({"drainme": d}, auto_start=False)
    try:
        f1 = eng.submit("drainme", {"x": xb[:1]})
        f2 = eng.submit("drainme", {"x": xb[:2]})
        eng.drain()
        for f in (f1, f2):
            with pytest.raises(ServingOverloadError) as ei:
                f.result(timeout=10)
            assert ei.value.reason == "draining"
        with pytest.raises(ServingOverloadError) as ei:
            eng.submit("drainme", {"x": xb[:1]})
        assert ei.value.reason == "draining"
        st = eng.stats()["models"]["drainme"]
        assert st["draining"] is True and st["queue_depth"] == 0
        fam = obs.snapshot().get("pt_serve_rejected_total", {})
        assert fam.get("samples", {}).get(("drainme", "draining"),
                                          0) >= 3
        eng.drain()  # idempotent
    finally:
        eng.close()
    # closed beats draining in the rejection classification
    with pytest.raises(ServingOverloadError) as ei:
        eng.submit("drainme", {"x": xb[:1]})
    assert ei.value.reason == "closed"


def test_engine_idle_lane_observes_sigterm_drain(saved_model,
                                                 monkeypatch):
    """An IDLE lane (scheduler parked on an empty queue) must still
    observe a process-level SIGTERM drain: nothing ever queues on a
    draining lane, so no submit would wake it — the bounded scheduler
    wait polls elastic.drain_requested and flips the lane, after which
    admission rejects typed at the edge."""
    import time as _time

    from paddle_tpu.distributed import elastic

    d, xb, _expect = saved_model
    eng = Engine({"idledrain": d})  # auto-started, no traffic
    try:
        monkeypatch.setattr(elastic, "drain_requested", lambda: True)
        deadline = _time.monotonic() + 5.0
        while _time.monotonic() < deadline:
            if eng.stats()["models"]["idledrain"]["draining"]:
                break
            _time.sleep(0.05)
        assert eng.stats()["models"]["idledrain"]["draining"] is True
        with pytest.raises(ServingOverloadError) as ei:
            eng.submit("idledrain", {"x": xb[:1]})
        assert ei.value.reason == "draining"
    finally:
        eng.close()
