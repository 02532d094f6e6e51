"""bench.py vs_baseline wiring: the baseline comes from the
BENCH_BASELINE env only — no number recorded on disk stands in for it."""

import importlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(monkeypatch):
    monkeypatch.delenv("BENCH_BASELINE", raising=False)
    monkeypatch.delenv("BENCH_BASELINE_CONFIG", raising=False)
    sys.path.insert(0, REPO)
    import bench

    return importlib.reload(bench)


def test_vs_baseline_comes_from_the_env_only(monkeypatch):
    bench = _bench(monkeypatch)
    # sentinels with no baseline: nothing on disk stands in for one
    assert bench._vs_baseline(100.0, "cfgA", True, default_metric=True) == 1.0
    assert bench._vs_baseline(100.0, "cfgA", False) == 0.0
    monkeypatch.setenv("BENCH_BASELINE", "25")
    monkeypatch.setenv("BENCH_BASELINE_CONFIG", "cfgA")
    assert bench._vs_baseline(100.0, "cfgA", True) == 4.0
    assert bench._vs_baseline(100.0, "cfgB", True) == 1.0  # cfg mismatch


def test_strip_methodology_tokens(monkeypatch):
    bench = _bench(monkeypatch)
    cfg = "bert-base b128 s128 bf16-policy devfeed chain32 quantar-dp4"
    assert (bench.strip_methodology(cfg)
            == "bert-base b128 s128 bf16-policy quantar-dp4")
    # every marker the suffix builder can emit is stripped
    for tok in bench.METHODOLOGY_MARKERS + ("chain8",):
        assert bench.strip_methodology(f"a {tok} b") == "a b"
    # a model token that merely starts with "chain" is NOT a marker
    assert bench.strip_methodology("chainer-v2 b8") == "chainer-v2 b8"


def test_vs_baseline_matches_across_methodology_change(monkeypatch):
    """A devfeed/pipelined re-capture must still match the
    older-methodology baseline of the same shape, and the match must
    stay shape-strict."""
    bench = _bench(monkeypatch)
    monkeypatch.setenv("BENCH_BASELINE", "50")
    monkeypatch.setenv("BENCH_BASELINE_CONFIG",
                       "bert-base b128 s128 bf16-policy")
    new_cfg = "bert-base b128 s128 bf16-policy devfeed pipelined"
    assert bench._vs_baseline(100.0, new_cfg, True) == 2.0
    # different shape under the same methodology: sentinel, not a ratio
    other = "bert-base b256 s128 bf16-policy devfeed pipelined"
    assert bench._vs_baseline(100.0, other, True) == 1.0
    # a deliberate A/B variant (syncfetch/hostfeed/chainK) must NEVER
    # ratio against the default-methodology record it contrasts with —
    # only the era markers (pipelined/devfeed) may be crossed
    for ab in (" syncfetch", " hostfeed", " chain32"):
        assert bench._vs_baseline(
            100.0, new_cfg + ab, True) == 1.0, ab


def test_cpu_suffix_feed_markers(monkeypatch):
    """The feed methodology is always labeled: devfeed by default,
    hostfeed under the A/B knob — records can never silently cross."""
    bench = _bench(monkeypatch)
    monkeypatch.delenv("PT_BENCH_SYNC_FETCH", raising=False)
    monkeypatch.delenv("PT_BENCH_HOST_FEED", raising=False)
    assert "devfeed" in bench._cpu_suffix()
    monkeypatch.setenv("PT_BENCH_HOST_FEED", "1")
    s = bench._cpu_suffix()
    assert "hostfeed" in s and "devfeed" not in s
