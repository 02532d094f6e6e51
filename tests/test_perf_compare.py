"""tools/perf_compare.py (ISSUE 11 CI satellite): threshold
classification — regression, win, within-noise, missing-field tolerance
— against synthetic records, bare and in the driver-artifact shape."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import perf_compare  # noqa: E402


def _rec(value=100.0, metric="bert_tiny_pretrain_tokens_per_sec",
         config="bert-tiny b8 s128 devfeed pipelined", **extra):
    return {"metric": metric, "value": value, "unit": "tokens/sec/chip",
            "config": config, **extra}


def _write(tmp_path, name, rec, wrap=False):
    p = tmp_path / name
    p.write_text(json.dumps({"parsed": rec} if wrap else rec))
    return str(p)


# ---------------------------------------------------------------------------
# field classification
# ---------------------------------------------------------------------------


def test_higher_better_classification():
    row = perf_compare.compare_field("value", 100, 90, 5.0, True)
    assert row["status"] == "regression"
    assert row["delta_pct"] == pytest.approx(-10.0)
    assert perf_compare.compare_field(
        "value", 100, 112, 5.0, True)["status"] == "win"
    assert perf_compare.compare_field(
        "value", 100, 98, 5.0, True)["status"] == "within-noise"


def test_lower_better_classification():
    assert perf_compare.compare_field(
        "p50", 1.0, 1.2, 5.0, False)["status"] == "regression"
    assert perf_compare.compare_field(
        "p50", 1.0, 0.8, 5.0, False)["status"] == "win"
    assert perf_compare.compare_field(
        "p50", 1.0, 1.01, 5.0, False)["status"] == "within-noise"


def test_missing_and_zero_baseline_tolerated():
    assert perf_compare.compare_field(
        "mfu", None, 0.5, 5.0, True)["status"] == "missing"
    assert perf_compare.compare_field(
        "mfu", 0.5, None, 5.0, True)["status"] == "missing"
    assert perf_compare.compare_field(
        "mfu", "n/a", 0.5, 5.0, True)["status"] == "missing"
    # a zero baseline must not divide into an infinite regression
    assert perf_compare.compare_field(
        "p50", 0.0, 0.1, 5.0, False)["status"] == "missing"


def test_absolute_gate_for_stall_fraction():
    # 0 -> 0.002 is within a 5-point absolute band, not an infinite
    # ratio regression
    row = perf_compare.compare_field(
        "feed.stall_fraction", 0.0, 0.002, 5.0, False, absolute=True)
    assert row["status"] == "within-noise"
    row = perf_compare.compare_field(
        "feed.stall_fraction", 0.0, 0.2, 5.0, False, absolute=True)
    assert row["status"] == "regression"


# ---------------------------------------------------------------------------
# whole-record comparison + exit codes
# ---------------------------------------------------------------------------


def test_synthetic_regression_flags_nonzero(tmp_path, capsys):
    old = _rec(100.0, metrics={"step_seconds_quantiles": {
        "dp": {"p50": 0.10, "p95": 0.12, "max": 0.2, "count": 10}}})
    new = _rec(80.0, metrics={"step_seconds_quantiles": {
        "dp": {"p50": 0.14, "p95": 0.15, "max": 0.2, "count": 10}}})
    rc = perf_compare.main([_write(tmp_path, "old.json", old),
                            _write(tmp_path, "new.json", new)])
    assert rc == 1
    out = capsys.readouterr().out
    assert "regression" in out and "value" in out
    assert "metrics.step_seconds_quantiles.dp.p50" in out


def test_win_and_noise_exit_zero(tmp_path):
    old = _rec(100.0, mfu=0.45)
    new = _rec(120.0, mfu=0.46)
    rc = perf_compare.main([_write(tmp_path, "old.json", old, wrap=True),
                            _write(tmp_path, "new.json", new)])
    assert rc == 0


def test_attribution_phase_regression_detected(tmp_path):
    att_old = {"phase_seconds": {"dp": {"device_wait": {
        "p50": 0.01, "p95": 0.02, "sum": 1.0, "count": 100}}},
        "feed": {"stall_fraction": 0.0}}
    att_new = {"phase_seconds": {"dp": {"device_wait": {
        "p50": 0.02, "p95": 0.03, "sum": 2.0, "count": 100}}},
        "feed": {"stall_fraction": 0.01}}
    old = _rec(100.0, metrics={"attribution": att_old})
    new = _rec(100.0, metrics={"attribution": att_new})
    rows, _cfg = perf_compare.compare_records(old, new)
    by_field = {r["field"]: r for r in rows}
    key = "metrics.attribution.phase_seconds.dp.device_wait.p50"
    assert by_field[key]["status"] == "regression"
    assert by_field["metrics.attribution.feed.stall_fraction"][
        "status"] == "within-noise"


def test_metric_mismatch_and_bad_input_exit_two(tmp_path):
    good = _write(tmp_path, "a.json", _rec())
    other = _write(tmp_path, "b.json", _rec(metric="other_metric"))
    assert perf_compare.main([good, other]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("not json at all")
    assert perf_compare.main([good, str(bad)]) == 2


def test_config_mismatch_warns_or_escalates(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _rec(config="bert-tiny b8 s128"))
    b = _write(tmp_path, "b.json",
               _rec(110.0, config="bert-base b128 s128"))
    assert perf_compare.main([a, b]) == 0  # warning only
    assert "config mismatch" in capsys.readouterr().err
    assert perf_compare.main([a, b, "--require-config-match"]) == 2


def test_methodology_tokens_do_not_mismatch(tmp_path, capsys):
    # devfeed/pipelined are era markers — the same shape across the
    # default-methodology eras must compare without a warning
    a = _write(tmp_path, "a.json", _rec(config="bert-tiny b8 s128"))
    b = _write(tmp_path, "b.json",
               _rec(99.0, config="bert-tiny b8 s128 devfeed pipelined"))
    assert perf_compare.main([a, b]) == 0
    assert "config mismatch" not in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the driver-artifact shape ({"parsed": {...}} around the bench line)
# ---------------------------------------------------------------------------


def _driver_artifact(value):
    parsed = {"metric": "bert_base_pretrain_tokens_per_sec", "value": value,
              "unit": "tokens/sec/chip", "vs_baseline": 0.0,
              "config": "bert-base b128 s128 bf16-policy devfeed pipelined",
              "tflops_per_sec": 0.01}
    return {"n": 5, "cmd": "python bench.py", "rc": 0,
            "tail": json.dumps(parsed) + "\n", "parsed": parsed}


def test_driver_artifacts_compare(tmp_path, capsys):
    old = _write(tmp_path, "r04.json", _driver_artifact(7000.0))
    new = _write(tmp_path, "r05.json", _driver_artifact(6714.5))
    rc = perf_compare.main([old, new, "--threshold-pct", "5", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc in (0, 1)
    assert out["metric"] == "bert_base_pretrain_tokens_per_sec"
    statuses = {r["field"]: r["status"] for r in out["rows"]}
    # the headline value is present and classified on both records
    assert statuses["value"] in ("win", "regression", "within-noise")
    # fields the records predate are tolerated, not fatal
    assert statuses["latency_seconds.p50"] == "missing"


def test_driver_artifact_vs_scaled_regression(tmp_path):
    path = _write(tmp_path, "r05.json", _driver_artifact(6714.5))
    real = perf_compare.load_record(path)
    worse = dict(real, value=real["value"] * 0.5)
    rc = perf_compare.main([
        _write(tmp_path, "old.json", real),
        _write(tmp_path, "new.json", worse)])
    assert rc == 1
