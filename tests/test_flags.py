"""Flag system + FLAGS_check_nan_inf (reference __bootstrap__ env flags,
operator.cc:953 nan/inf guard)."""

import numpy as np
import pytest

from paddle_tpu import fluid


def test_get_set_flags():
    assert fluid.get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"] in (
        True, False)
    fluid.set_flags({"FLAGS_rpc_deadline": 5000})
    assert fluid.get_flags("rpc_deadline")["rpc_deadline"] == 5000
    with pytest.raises(KeyError):
        fluid.set_flags({"FLAGS_nonexistent": 1})


def test_check_nan_inf_catches_bad_var():
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        main, startup = fluid.Program(), fluid.Program()
        scope = fluid.Scope()
        with fluid.scope_guard(scope), fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            x = fluid.data("x", [-1, 4], False, dtype="float32")
            y = fluid.layers.log(x)  # log of a negative → NaN
            exe = fluid.Executor(fluid.CPUPlace())
            with pytest.raises(RuntimeError, match="NaN/Inf"):
                exe.run(main, feed={"x": -np.ones((2, 4), "float32")},
                        fetch_list=[y.name])
        # clean runs pass
        with fluid.scope_guard(fluid.Scope()):
            exe2 = fluid.Executor(fluid.CPUPlace())
            (out,) = exe2.run(main, feed={"x": np.ones((2, 4), "float32")},
                              fetch_list=[y.name])
            assert np.all(np.isfinite(out))
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


def test_env_bootstrap(monkeypatch):
    import importlib

    from paddle_tpu.fluid import flags as fl

    monkeypatch.setenv("FLAGS_rpc_deadline", "1234")
    importlib.reload(fl)
    assert fl.get_flags("rpc_deadline")["rpc_deadline"] == 1234
    monkeypatch.delenv("FLAGS_rpc_deadline")
    importlib.reload(fl)  # restore defaults for other tests


def test_resilience_flags_roundtrip(monkeypatch):
    """The fault-tolerance flags register with reference-consistent
    defaults (grpc FLAGS_rpc_retry_times=3) and round-trip through env
    bootstrap and get/set like every other flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("rpc_retry_times")["rpc_retry_times"] == 3
    assert fl.get_flags("rpc_retry_backoff_ms")["rpc_retry_backoff_ms"] == 100
    assert fl.get_flags("ps_barrier_timeout_ms")[
        "ps_barrier_timeout_ms"] == 300000
    try:
        fl.set_flags({"FLAGS_rpc_retry_times": 7,
                      "FLAGS_rpc_retry_backoff_ms": "250",  # str parses
                      "ps_barrier_timeout_ms": 1000})
        assert fl.get_flags(["rpc_retry_times", "rpc_retry_backoff_ms",
                             "ps_barrier_timeout_ms"]) == {
            "rpc_retry_times": 7, "rpc_retry_backoff_ms": 250,
            "ps_barrier_timeout_ms": 1000}
    finally:
        fl.set_flags({"FLAGS_rpc_retry_times": 3,
                      "FLAGS_rpc_retry_backoff_ms": 100,
                      "FLAGS_ps_barrier_timeout_ms": 300000})
    monkeypatch.setenv("FLAGS_rpc_retry_times", "9")
    monkeypatch.setenv("FLAGS_ps_barrier_timeout_ms", "60000")
    importlib.reload(fl)
    assert fl.get_flags("rpc_retry_times")["rpc_retry_times"] == 9
    assert fl.get_flags("ps_barrier_timeout_ms")[
        "ps_barrier_timeout_ms"] == 60000
    monkeypatch.delenv("FLAGS_rpc_retry_times")
    monkeypatch.delenv("FLAGS_ps_barrier_timeout_ms")
    importlib.reload(fl)  # restore defaults for other tests


def test_observability_flags_roundtrip(monkeypatch):
    """The unified-telemetry flags register with off-by-default values
    (0 port = no endpoint, empty dir = no event log) and round-trip
    through env bootstrap and get/set like every other flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("metrics_port")["metrics_port"] == 0
    assert fl.get_flags("event_log_dir")["event_log_dir"] == ""
    try:
        fl.set_flags({"FLAGS_metrics_port": "9187",  # str parses
                      "event_log_dir": "/tmp/pt_events"})
        assert fl.get_flags(["metrics_port", "event_log_dir"]) == {
            "metrics_port": 9187, "event_log_dir": "/tmp/pt_events"}
    finally:
        fl.set_flags({"FLAGS_metrics_port": 0, "FLAGS_event_log_dir": ""})
    monkeypatch.setenv("FLAGS_metrics_port", "9188")
    monkeypatch.setenv("FLAGS_event_log_dir", "/tmp/ev")
    importlib.reload(fl)
    assert fl.get_flags("metrics_port")["metrics_port"] == 9188
    assert fl.get_flags("event_log_dir")["event_log_dir"] == "/tmp/ev"
    monkeypatch.delenv("FLAGS_metrics_port")
    monkeypatch.delenv("FLAGS_event_log_dir")
    importlib.reload(fl)  # restore defaults for other tests


def test_elastic_flags_roundtrip(monkeypatch):
    """The elastic-membership flags register with their documented
    defaults (elastic off — the frozen n_trainers contract is the
    reference behavior; 15 s lease, 3 s heartbeat, time-based snapshots
    off) and round-trip through env bootstrap and get/set like every
    other flag (ISSUE 7 satellite)."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("elastic_ps")["elastic_ps"] is False
    assert fl.get_flags("ps_lease_timeout_ms")["ps_lease_timeout_ms"] == 15000
    assert fl.get_flags("ps_lease_heartbeat_ms")[
        "ps_lease_heartbeat_ms"] == 3000
    assert fl.get_flags("ps_snapshot_interval_s")[
        "ps_snapshot_interval_s"] == 0.0
    try:
        fl.set_flags({"FLAGS_elastic_ps": True,
                      "ps_lease_timeout_ms": "2500",  # str parses
                      "FLAGS_ps_lease_heartbeat_ms": 750,
                      "ps_snapshot_interval_s": "1.5"})
        assert fl.get_flags(["elastic_ps", "ps_lease_timeout_ms",
                             "ps_lease_heartbeat_ms",
                             "ps_snapshot_interval_s"]) == {
            "elastic_ps": True, "ps_lease_timeout_ms": 2500,
            "ps_lease_heartbeat_ms": 750, "ps_snapshot_interval_s": 1.5}
    finally:
        fl.set_flags({"FLAGS_elastic_ps": False,
                      "FLAGS_ps_lease_timeout_ms": 15000,
                      "FLAGS_ps_lease_heartbeat_ms": 3000,
                      "FLAGS_ps_snapshot_interval_s": 0.0})
    monkeypatch.setenv("FLAGS_elastic_ps", "1")
    monkeypatch.setenv("FLAGS_ps_lease_timeout_ms", "9000")
    monkeypatch.setenv("FLAGS_ps_snapshot_interval_s", "30")
    importlib.reload(fl)
    assert fl.get_flags("elastic_ps")["elastic_ps"] is True
    assert fl.get_flags("ps_lease_timeout_ms")["ps_lease_timeout_ms"] == 9000
    assert fl.get_flags("ps_snapshot_interval_s")[
        "ps_snapshot_interval_s"] == 30.0
    monkeypatch.delenv("FLAGS_elastic_ps")
    monkeypatch.delenv("FLAGS_ps_lease_timeout_ms")
    monkeypatch.delenv("FLAGS_ps_snapshot_interval_s")
    importlib.reload(fl)  # restore defaults for other tests


def test_quant_allreduce_algo_flags_roundtrip(monkeypatch):
    """The size-adaptive collective-selection flags register with their
    documented defaults (auto; 256 KB crossover — read on the 8-device
    CPU mesh, not on the chip; ZeRO gather quant off) and
    round-trip through env bootstrap and get/set like every other flag
    (ISSUE 5 satellite, crossover retuned in ISSUE 8)."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("quant_allreduce_algo")[
        "quant_allreduce_algo"] == "auto"
    assert fl.get_flags("quant_allreduce_crossover_kb")[
        "quant_allreduce_crossover_kb"] == 256
    assert fl.get_flags("zero_gather_quant")["zero_gather_quant"] is False
    try:
        fl.set_flags({"FLAGS_quant_allreduce_algo": "ring",
                      "quant_allreduce_crossover_kb": "128",  # str parses
                      "FLAGS_zero_gather_quant": True})
        assert fl.get_flags(["quant_allreduce_algo",
                             "quant_allreduce_crossover_kb",
                             "zero_gather_quant"]) == {
            "quant_allreduce_algo": "ring",
            "quant_allreduce_crossover_kb": 128,
            "zero_gather_quant": True}
    finally:
        fl.set_flags({"FLAGS_quant_allreduce_algo": "auto",
                      "FLAGS_quant_allreduce_crossover_kb": 256,
                      "FLAGS_zero_gather_quant": False})
    monkeypatch.setenv("FLAGS_quant_allreduce_algo", "oneshot")
    monkeypatch.setenv("FLAGS_quant_allreduce_crossover_kb", "64")
    importlib.reload(fl)
    assert fl.get_flags("quant_allreduce_algo")[
        "quant_allreduce_algo"] == "oneshot"
    assert fl.get_flags("quant_allreduce_crossover_kb")[
        "quant_allreduce_crossover_kb"] == 64
    monkeypatch.delenv("FLAGS_quant_allreduce_algo")
    monkeypatch.delenv("FLAGS_quant_allreduce_crossover_kb")
    importlib.reload(fl)  # restore defaults for other tests


def test_overlap_and_fused_update_flags_roundtrip(monkeypatch):
    """The comm/compute-overlap flags (ISSUE 8): ready-order bucket
    dispatch and the fused dequant→update→requant step kernels both
    default ON (they only engage where the quant path / zero_gather_quant
    are already opted in) and round-trip through env bootstrap and
    get/set like every other flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("overlap_allreduce")["overlap_allreduce"] is True
    assert fl.get_flags("fused_update")["fused_update"] is True
    try:
        fl.set_flags({"FLAGS_overlap_allreduce": False,
                      "fused_update": "0"})  # str parses
        assert fl.get_flags(["overlap_allreduce", "fused_update"]) == {
            "overlap_allreduce": False, "fused_update": False}
    finally:
        fl.set_flags({"FLAGS_overlap_allreduce": True,
                      "FLAGS_fused_update": True})
    monkeypatch.setenv("FLAGS_overlap_allreduce", "off")
    monkeypatch.setenv("FLAGS_fused_update", "false")
    importlib.reload(fl)
    assert fl.get_flags("overlap_allreduce")["overlap_allreduce"] is False
    assert fl.get_flags("fused_update")["fused_update"] is False
    monkeypatch.delenv("FLAGS_overlap_allreduce")
    monkeypatch.delenv("FLAGS_fused_update")
    importlib.reload(fl)  # restore defaults for other tests


def test_serving_flags_roundtrip(monkeypatch):
    """The serving-lane flags register with their documented defaults
    (powers-of-two buckets, 5 ms max wait, 256-request admission bound,
    sequence bucketing off) and round-trip through env bootstrap and
    get/set like every other flag (ISSUE 6 satellite)."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("serving_batch_buckets")[
        "serving_batch_buckets"] == "1,2,4,8,16"
    assert fl.get_flags("serving_seq_buckets")["serving_seq_buckets"] == ""
    assert fl.get_flags("serving_batch_timeout_ms")[
        "serving_batch_timeout_ms"] == 5
    assert fl.get_flags("serving_max_queue")["serving_max_queue"] == 256
    try:
        fl.set_flags({"FLAGS_serving_batch_buckets": "1,4,32",
                      "serving_seq_buckets": "64,128",
                      "FLAGS_serving_batch_timeout_ms": "25",  # str parses
                      "serving_max_queue": 16})
        assert fl.get_flags(["serving_batch_buckets", "serving_seq_buckets",
                             "serving_batch_timeout_ms",
                             "serving_max_queue"]) == {
            "serving_batch_buckets": "1,4,32",
            "serving_seq_buckets": "64,128",
            "serving_batch_timeout_ms": 25,
            "serving_max_queue": 16}
    finally:
        fl.set_flags({"FLAGS_serving_batch_buckets": "1,2,4,8,16",
                      "FLAGS_serving_seq_buckets": "",
                      "FLAGS_serving_batch_timeout_ms": 5,
                      "FLAGS_serving_max_queue": 256})
    monkeypatch.setenv("FLAGS_serving_batch_buckets", "2,8")
    monkeypatch.setenv("FLAGS_serving_batch_timeout_ms", "50")
    monkeypatch.setenv("FLAGS_serving_max_queue", "32")
    importlib.reload(fl)
    assert fl.get_flags("serving_batch_buckets")[
        "serving_batch_buckets"] == "2,8"
    assert fl.get_flags("serving_batch_timeout_ms")[
        "serving_batch_timeout_ms"] == 50
    assert fl.get_flags("serving_max_queue")["serving_max_queue"] == 32
    monkeypatch.delenv("FLAGS_serving_batch_buckets")
    monkeypatch.delenv("FLAGS_serving_batch_timeout_ms")
    monkeypatch.delenv("FLAGS_serving_max_queue")
    importlib.reload(fl)  # restore defaults for other tests


def test_serving_resilience_flags_roundtrip(monkeypatch):
    """The serving-resilience flags (ISSUE 18 satellite): replica
    count, hedge delay (0=off, -1=adaptive p99), breaker thresholds —
    documented defaults, get/set, and env bootstrap."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("serving_replicas")["serving_replicas"] == 2
    assert fl.get_flags("serving_hedge_ms")["serving_hedge_ms"] == 0
    assert fl.get_flags("serving_breaker_failures")[
        "serving_breaker_failures"] == 5
    assert fl.get_flags("serving_breaker_cooldown_ms")[
        "serving_breaker_cooldown_ms"] == 1000
    try:
        fl.set_flags({"FLAGS_serving_replicas": 4,
                      "serving_hedge_ms": "-1",  # str parses; adaptive
                      "FLAGS_serving_breaker_failures": 3,
                      "serving_breaker_cooldown_ms": 250})
        assert fl.get_flags(["serving_replicas", "serving_hedge_ms",
                             "serving_breaker_failures",
                             "serving_breaker_cooldown_ms"]) == {
            "serving_replicas": 4,
            "serving_hedge_ms": -1,
            "serving_breaker_failures": 3,
            "serving_breaker_cooldown_ms": 250}
    finally:
        fl.set_flags({"FLAGS_serving_replicas": 2,
                      "FLAGS_serving_hedge_ms": 0,
                      "FLAGS_serving_breaker_failures": 5,
                      "FLAGS_serving_breaker_cooldown_ms": 1000})
    monkeypatch.setenv("FLAGS_serving_replicas", "3")
    monkeypatch.setenv("FLAGS_serving_hedge_ms", "20")
    monkeypatch.setenv("FLAGS_serving_breaker_failures", "7")
    monkeypatch.setenv("FLAGS_serving_breaker_cooldown_ms", "500")
    importlib.reload(fl)
    assert fl.get_flags("serving_replicas")["serving_replicas"] == 3
    assert fl.get_flags("serving_hedge_ms")["serving_hedge_ms"] == 20
    assert fl.get_flags("serving_breaker_failures")[
        "serving_breaker_failures"] == 7
    assert fl.get_flags("serving_breaker_cooldown_ms")[
        "serving_breaker_cooldown_ms"] == 500
    monkeypatch.delenv("FLAGS_serving_replicas")
    monkeypatch.delenv("FLAGS_serving_hedge_ms")
    monkeypatch.delenv("FLAGS_serving_breaker_failures")
    monkeypatch.delenv("FLAGS_serving_breaker_cooldown_ms")
    importlib.reload(fl)  # restore defaults for other tests


def test_reqtrace_slo_flags_roundtrip(monkeypatch):
    """The request-trace + SLO flags (ISSUE 19 satellite): tracing
    on/off, trace-ring capacity, SLO evaluation cadence, and the
    declarative spec string — documented defaults, get/set, and env
    bootstrap."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("reqtrace")["reqtrace"] is True
    assert fl.get_flags("reqtrace_ring")["reqtrace_ring"] == 256
    assert fl.get_flags("slo_eval_interval_s")[
        "slo_eval_interval_s"] == 10.0
    assert fl.get_flags("slo_specs")["slo_specs"] == ""
    spec = ("avail|availability|bad=pt_serve_rejected_total"
            "|total=pt_serve_requests_total|objective=0.99")
    try:
        fl.set_flags({"FLAGS_reqtrace": "false",  # str parses
                      "reqtrace_ring": 64,
                      "FLAGS_slo_eval_interval_s": "2.5",
                      "slo_specs": spec})
        assert fl.get_flags(["reqtrace", "reqtrace_ring",
                             "slo_eval_interval_s", "slo_specs"]) == {
            "reqtrace": False, "reqtrace_ring": 64,
            "slo_eval_interval_s": 2.5, "slo_specs": spec}
    finally:
        fl.set_flags({"FLAGS_reqtrace": True,
                      "FLAGS_reqtrace_ring": 256,
                      "FLAGS_slo_eval_interval_s": 10.0,
                      "FLAGS_slo_specs": ""})
    monkeypatch.setenv("FLAGS_reqtrace", "0")
    monkeypatch.setenv("FLAGS_reqtrace_ring", "32")
    monkeypatch.setenv("FLAGS_slo_eval_interval_s", "1.5")
    monkeypatch.setenv("FLAGS_slo_specs", spec)
    importlib.reload(fl)
    assert fl.get_flags("reqtrace")["reqtrace"] is False
    assert fl.get_flags("reqtrace_ring")["reqtrace_ring"] == 32
    assert fl.get_flags("slo_eval_interval_s")[
        "slo_eval_interval_s"] == 1.5
    assert fl.get_flags("slo_specs")["slo_specs"] == spec
    monkeypatch.delenv("FLAGS_reqtrace")
    monkeypatch.delenv("FLAGS_reqtrace_ring")
    monkeypatch.delenv("FLAGS_slo_eval_interval_s")
    monkeypatch.delenv("FLAGS_slo_specs")
    importlib.reload(fl)  # restore defaults for other tests


def test_malformed_env_flag_warns_not_crashes(monkeypatch):
    import importlib
    import warnings as w

    from paddle_tpu.fluid import flags as fl

    monkeypatch.setenv("FLAGS_rpc_deadline", "3m")  # malformed
    with w.catch_warnings(record=True) as rec:
        w.simplefilter("always")
        importlib.reload(fl)
    assert any("malformed" in str(r.message) for r in rec)
    assert fl.get_flags("rpc_deadline")["rpc_deadline"] == 180000  # default
    monkeypatch.delenv("FLAGS_rpc_deadline")
    importlib.reload(fl)


def test_falsy_spellings_parse_false():
    from paddle_tpu.fluid import flags as fl

    for spelling in ("0", "false", "FALSE", "off", "no"):
        fl.set_flags({"FLAGS_check_nan_inf": spelling})
        assert fl.get_flags("check_nan_inf")["check_nan_inf"] is False
    fl.set_flags({"FLAGS_check_nan_inf": "1"})
    assert fl.get_flags("check_nan_inf")["check_nan_inf"] is True
    fl.set_flags({"FLAGS_check_nan_inf": False})


def test_noop_flag_warns():
    import warnings as w

    from paddle_tpu.fluid import flags as fl

    with w.catch_warnings(record=True) as rec:
        w.simplefilter("always")
        fl.set_flags({"FLAGS_use_ngraph": True})
    assert any("no effect" in str(r.message) for r in rec)
    fl.set_flags({"FLAGS_use_ngraph": False})


def test_persistent_compile_cache_populates(tmp_path, monkeypatch):
    """FLAGS_compile_cache_dir routes XLA compilations to an on-disk cache
    (survives processes — the Prepare()-like persistent cache of SURVEY §7
    hard part 6)."""
    import numpy as np

    import jax

    import paddle_tpu.fluid.executor as ex
    from paddle_tpu import fluid
    from paddle_tpu.fluid import flags

    cache = str(tmp_path / "xla_cache")
    old_flag = flags.get_flags("FLAGS_compile_cache_dir")
    prior_jax_dir = jax.config.jax_compilation_cache_dir
    flags.set_flags({"FLAGS_compile_cache_dir": cache})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("cc_x", [4, 3], False, dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, 2))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={"cc_x": np.ones((4, 3), "float32")},
                fetch_list=[loss.name])
        import os

        assert os.path.isdir(cache)
        # jax may only persist compilations above the min-time threshold on
        # some backends; the directory being created and configured is the
        # contract we own
        assert jax.config.jax_compilation_cache_dir == cache
    finally:
        # restore the flag AND re-sync the applied state so later tests in
        # the session see a consistent (flag, jax config) pair
        flags.set_flags(old_flag)
        ex._cache_dir_last = object()
        ex._apply_compile_cache()
        assert jax.config.jax_compilation_cache_dir != cache or \
            old_flag["FLAGS_compile_cache_dir"] == cache
        if not old_flag["FLAGS_compile_cache_dir"]:
            jax.config.update("jax_compilation_cache_dir", prior_jax_dir)


def test_gspmd_flags_roundtrip(monkeypatch):
    """The GSPMD execution-core flags (ISSUE 9): the executor lane is
    off by default (the transpiler stays the benched baseline), the
    quant-hook impl defaults to auto (custom_partitioning on TPU, the
    shard_map island on the 0.4.3x CPU lane), and both round-trip
    through env bootstrap and get/set like every other flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("gspmd_executor")["gspmd_executor"] is False
    assert fl.get_flags("gspmd_quant_impl")["gspmd_quant_impl"] == "auto"
    try:
        fl.set_flags({"FLAGS_gspmd_executor": True,
                      "gspmd_quant_impl": "shard_map"})
        assert fl.get_flags(["gspmd_executor", "gspmd_quant_impl"]) == {
            "gspmd_executor": True, "gspmd_quant_impl": "shard_map"}
    finally:
        fl.set_flags({"FLAGS_gspmd_executor": False,
                      "FLAGS_gspmd_quant_impl": "auto"})
    monkeypatch.setenv("FLAGS_gspmd_executor", "1")
    monkeypatch.setenv("FLAGS_gspmd_quant_impl", "custom_partitioning")
    importlib.reload(fl)
    assert fl.get_flags("gspmd_executor")["gspmd_executor"] is True
    assert fl.get_flags("gspmd_quant_impl")["gspmd_quant_impl"] == \
        "custom_partitioning"
    monkeypatch.delenv("FLAGS_gspmd_executor")
    monkeypatch.delenv("FLAGS_gspmd_quant_impl")
    importlib.reload(fl)  # restore defaults for other tests


def test_profiling_flags_roundtrip(monkeypatch):
    """The step-time attribution flags (ISSUE 11): phase timing off by
    default (device_wait's per-step sync would serialize the pipelined
    dispatch methodology), flight recorder 256 steps, slow-step z 8.0,
    peak overrides 0 = use the platform table — all round-tripping
    through env bootstrap and get/set like every other flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("profile_phases")["profile_phases"] is False
    assert fl.get_flags("flight_recorder_steps")[
        "flight_recorder_steps"] == 256
    assert fl.get_flags("flight_recorder_dir")[
        "flight_recorder_dir"] == ""
    assert fl.get_flags("profile_slow_step_zscore")[
        "profile_slow_step_zscore"] == 8.0
    assert fl.get_flags("device_peak_flops")["device_peak_flops"] == 0.0
    assert fl.get_flags("device_peak_bandwidth")[
        "device_peak_bandwidth"] == 0.0
    assert fl.get_flags("device_peak_ici_bandwidth")[
        "device_peak_ici_bandwidth"] == 0.0
    try:
        fl.set_flags({"FLAGS_profile_phases": True,
                      "FLAGS_flight_recorder_steps": "64",  # str parses
                      "flight_recorder_dir": "/tmp/fr",
                      "FLAGS_profile_slow_step_zscore": 4.5,
                      "FLAGS_device_peak_flops": "1.97e14",
                      "FLAGS_device_peak_bandwidth": 8.19e11,
                      "FLAGS_device_peak_ici_bandwidth": 2e11})
        assert fl.get_flags(
            ["profile_phases", "flight_recorder_steps",
             "flight_recorder_dir", "profile_slow_step_zscore",
             "device_peak_flops", "device_peak_bandwidth",
             "device_peak_ici_bandwidth"]) == {
            "profile_phases": True, "flight_recorder_steps": 64,
            "flight_recorder_dir": "/tmp/fr",
            "profile_slow_step_zscore": 4.5,
            "device_peak_flops": 1.97e14,
            "device_peak_bandwidth": 8.19e11,
            "device_peak_ici_bandwidth": 2e11}
    finally:
        fl.set_flags({"FLAGS_profile_phases": False,
                      "FLAGS_flight_recorder_steps": 256,
                      "FLAGS_flight_recorder_dir": "",
                      "FLAGS_profile_slow_step_zscore": 8.0,
                      "FLAGS_device_peak_flops": 0.0,
                      "FLAGS_device_peak_bandwidth": 0.0,
                      "FLAGS_device_peak_ici_bandwidth": 0.0})
    monkeypatch.setenv("FLAGS_profile_phases", "1")
    monkeypatch.setenv("FLAGS_flight_recorder_steps", "128")
    monkeypatch.setenv("FLAGS_device_peak_flops", "2.75e14")
    importlib.reload(fl)
    assert fl.get_flags("profile_phases")["profile_phases"] is True
    assert fl.get_flags("flight_recorder_steps")[
        "flight_recorder_steps"] == 128
    assert fl.get_flags("device_peak_flops")[
        "device_peak_flops"] == 2.75e14
    monkeypatch.delenv("FLAGS_profile_phases")
    monkeypatch.delenv("FLAGS_flight_recorder_steps")
    monkeypatch.delenv("FLAGS_device_peak_flops")
    importlib.reload(fl)  # restore defaults for other tests


def test_graph_passes_flag_roundtrip(monkeypatch):
    """FLAGS_graph_passes (the pass-layer selection string,
    docs/PASSES.md) registers with the "default" pipeline as its
    default and round-trips through env bootstrap and get/set."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("graph_passes")["graph_passes"] == "default"
    try:
        fl.set_flags({"FLAGS_graph_passes": "none"})
        assert fl.get_flags("graph_passes")["graph_passes"] == "none"
        fl.set_flags({"graph_passes": "fuse_attention"})
        assert fl.get_flags("FLAGS_graph_passes")[
            "FLAGS_graph_passes"] == "fuse_attention"
    finally:
        fl.set_flags({"FLAGS_graph_passes": "default"})
    monkeypatch.setenv("FLAGS_graph_passes", "-fuse_attention")
    importlib.reload(fl)
    assert fl.get_flags("graph_passes")["graph_passes"] == \
        "-fuse_attention"
    monkeypatch.delenv("FLAGS_graph_passes")
    importlib.reload(fl)
    assert fl.get_flags("graph_passes")["graph_passes"] == "default"


def test_pipeline_policy_flags_roundtrip(monkeypatch):
    """The pipeline-as-policy flags (ISSUE 15): 1f1b is the default
    schedule (same bubble as gpipe, min(M,S) activation stash), 4
    microbatches when neither the policy nor the program pins one, and
    both round-trip through env bootstrap and get/set like every other
    flag.  An unknown schedule spelling fails loudly at resolution."""
    import importlib

    import pytest

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("pipeline_schedule")["pipeline_schedule"] == \
        "1f1b"
    assert fl.get_flags("pipeline_microbatches")[
        "pipeline_microbatches"] == 4
    try:
        fl.set_flags({"FLAGS_pipeline_schedule": "gpipe",
                      "pipeline_microbatches": "8"})  # str parses
        assert fl.get_flags(["pipeline_schedule",
                             "pipeline_microbatches"]) == {
            "pipeline_schedule": "gpipe", "pipeline_microbatches": 8}
        # resolution validates the spelling where it is consumed
        from paddle_tpu.parallel.gspmd import PipelinePolicy

        fl.set_flags({"FLAGS_pipeline_schedule": "zigzag"})
        with pytest.raises(ValueError, match="pipeline_schedule"):
            PipelinePolicy().resolve_schedule()
    finally:
        fl.set_flags({"FLAGS_pipeline_schedule": "1f1b",
                      "FLAGS_pipeline_microbatches": 4})
    monkeypatch.setenv("FLAGS_pipeline_schedule", "gpipe")
    monkeypatch.setenv("FLAGS_pipeline_microbatches", "16")
    importlib.reload(fl)
    assert fl.get_flags("pipeline_schedule")["pipeline_schedule"] == \
        "gpipe"
    assert fl.get_flags("pipeline_microbatches")[
        "pipeline_microbatches"] == 16
    monkeypatch.delenv("FLAGS_pipeline_schedule")
    monkeypatch.delenv("FLAGS_pipeline_microbatches")
    importlib.reload(fl)  # restore defaults for other tests


def test_aot_cache_flag_roundtrip(monkeypatch):
    """FLAGS_aot_cache_dir (fluid/aot_cache.py): off by default (empty
    string disables the AOT executable cache) and round-trips through
    set_flags and env bootstrap like every other flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("aot_cache_dir")["aot_cache_dir"] == ""
    try:
        fl.set_flags({"FLAGS_aot_cache_dir": "/tmp/aotx"})
        assert fl.get_flags("aot_cache_dir")["aot_cache_dir"] == \
            "/tmp/aotx"
    finally:
        fl.set_flags({"FLAGS_aot_cache_dir": ""})
    monkeypatch.setenv("FLAGS_aot_cache_dir", "/tmp/aotx2")
    importlib.reload(fl)
    assert fl.get_flags("aot_cache_dir")["aot_cache_dir"] == "/tmp/aotx2"
    monkeypatch.delenv("FLAGS_aot_cache_dir")
    importlib.reload(fl)  # restore defaults for other tests


def test_recovery_flags_roundtrip(monkeypatch):
    """The preemption-recovery flags (ISSUE 14): durable rollback-window
    cadence (0 = full-checkpoint/signal saves only), the standing drill
    spec, and the decode-lane per-tenant quota — registered with their
    documented defaults, round-tripping through env bootstrap and
    get/set like every other flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("rollback_persist_interval_s")[
        "rollback_persist_interval_s"] == 0.0
    assert fl.get_flags("recovery_drill")["recovery_drill"] == ""
    assert fl.get_flags("serving_tenant_quota")[
        "serving_tenant_quota"] == 0
    try:
        fl.set_flags({"FLAGS_rollback_persist_interval_s": "2.5",
                      "recovery_drill": "drill:preempt+restore:step:4",
                      "FLAGS_serving_tenant_quota": 8})
        assert fl.get_flags(["rollback_persist_interval_s",
                             "recovery_drill",
                             "serving_tenant_quota"]) == {
            "rollback_persist_interval_s": 2.5,
            "recovery_drill": "drill:preempt+restore:step:4",
            "serving_tenant_quota": 8}
    finally:
        fl.set_flags({"FLAGS_rollback_persist_interval_s": 0.0,
                      "FLAGS_recovery_drill": "",
                      "FLAGS_serving_tenant_quota": 0})
    monkeypatch.setenv("FLAGS_rollback_persist_interval_s", "30")
    monkeypatch.setenv("FLAGS_recovery_drill",
                       "drill:kill+restore:round:6:pserver0")
    monkeypatch.setenv("FLAGS_serving_tenant_quota", "4")
    importlib.reload(fl)
    assert fl.get_flags("rollback_persist_interval_s")[
        "rollback_persist_interval_s"] == 30.0
    assert fl.get_flags("recovery_drill")[
        "recovery_drill"] == "drill:kill+restore:round:6:pserver0"
    assert fl.get_flags("serving_tenant_quota")[
        "serving_tenant_quota"] == 4
    monkeypatch.delenv("FLAGS_rollback_persist_interval_s")
    monkeypatch.delenv("FLAGS_recovery_drill")
    monkeypatch.delenv("FLAGS_serving_tenant_quota")
    importlib.reload(fl)  # restore defaults for other tests


def test_program_verify_flag_roundtrip(monkeypatch):
    """FLAGS_program_verify (the static-verifier preflight gate,
    docs/ANALYSIS.md): defaults to "warn" (analyze on every
    executable-cache miss, one warning per program/lane, never block),
    escalates to "raise"/"strict", disables with "off" — round-tripping
    through env bootstrap and get/set like every other flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("program_verify")["program_verify"] == "warn"
    try:
        fl.set_flags({"FLAGS_program_verify": "raise"})
        assert fl.get_flags("program_verify")["program_verify"] == "raise"
        fl.set_flags({"program_verify": "off"})
        assert fl.get_flags("FLAGS_program_verify")[
            "FLAGS_program_verify"] == "off"
    finally:
        fl.set_flags({"FLAGS_program_verify": "warn"})
    monkeypatch.setenv("FLAGS_program_verify", "strict")
    importlib.reload(fl)
    assert fl.get_flags("program_verify")["program_verify"] == "strict"
    monkeypatch.delenv("FLAGS_program_verify")
    importlib.reload(fl)  # restore defaults for other tests
    assert fl.get_flags("program_verify")["program_verify"] == "warn"


def test_kernel_primitive_flags_roundtrip(monkeypatch):
    """The kernel-primitives flags (ISSUE 17) — autotune, ragged
    attention, int8 KV cache — register bool-typed with their documented
    off-by-default values and round-trip through env bootstrap and
    get/set like every other flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("kernel_autotune")["kernel_autotune"] is False
    assert fl.get_flags("ragged_attention")["ragged_attention"] is False
    assert fl.get_flags("int8_kv_cache")["int8_kv_cache"] is False
    try:
        fl.set_flags({"FLAGS_kernel_autotune": "true",  # str parses
                      "ragged_attention": 1,
                      "FLAGS_int8_kv_cache": True})
        assert fl.get_flags(["kernel_autotune", "ragged_attention",
                             "int8_kv_cache"]) == {
            "kernel_autotune": True,
            "ragged_attention": True,
            "int8_kv_cache": True}
    finally:
        fl.set_flags({"FLAGS_kernel_autotune": False,
                      "FLAGS_ragged_attention": False,
                      "FLAGS_int8_kv_cache": False})
    monkeypatch.setenv("FLAGS_kernel_autotune", "1")
    monkeypatch.setenv("FLAGS_int8_kv_cache", "true")
    importlib.reload(fl)
    assert fl.get_flags("kernel_autotune")["kernel_autotune"] is True
    assert fl.get_flags("int8_kv_cache")["int8_kv_cache"] is True
    assert fl.get_flags("ragged_attention")["ragged_attention"] is False
    monkeypatch.delenv("FLAGS_kernel_autotune")
    monkeypatch.delenv("FLAGS_int8_kv_cache")
    importlib.reload(fl)  # restore defaults for other tests
    assert fl.get_flags("kernel_autotune")["kernel_autotune"] is False


def test_autotune_flags_roundtrip(monkeypatch):
    """The mesh-autotuner flags (ISSUE 20): no standing report pin by
    default (empty path), top-3 shortlist, 6 measured steps — all
    round-trip through env bootstrap and get/set like every other
    flag."""
    import importlib

    from paddle_tpu.fluid import flags as fl

    assert fl.get_flags("autotune_report")["autotune_report"] == ""
    assert fl.get_flags("autotune_topk")["autotune_topk"] == 3
    assert fl.get_flags("autotune_steps")["autotune_steps"] == 6
    try:
        fl.set_flags({"FLAGS_autotune_report": "/tmp/at.json",
                      "autotune_topk": "5",  # str parses
                      "FLAGS_autotune_steps": 12})
        assert fl.get_flags(["autotune_report", "autotune_topk",
                             "autotune_steps"]) == {
            "autotune_report": "/tmp/at.json", "autotune_topk": 5,
            "autotune_steps": 12}
    finally:
        fl.set_flags({"FLAGS_autotune_report": "",
                      "FLAGS_autotune_topk": 3,
                      "FLAGS_autotune_steps": 6})
    monkeypatch.setenv("FLAGS_autotune_report", "/tmp/at2.json")
    monkeypatch.setenv("FLAGS_autotune_topk", "4")
    importlib.reload(fl)
    assert fl.get_flags("autotune_report")["autotune_report"] == \
        "/tmp/at2.json"
    assert fl.get_flags("autotune_topk")["autotune_topk"] == 4
    monkeypatch.delenv("FLAGS_autotune_report")
    monkeypatch.delenv("FLAGS_autotune_topk")
    importlib.reload(fl)  # restore defaults for other tests
    assert fl.get_flags("autotune_report")["autotune_report"] == ""
