#!/usr/bin/env python
"""Mechanical before/after for BENCH records: diff two BENCH_*.json
files and exit nonzero on regression.

The on-chip capture sessions (and CI) get a deterministic verdict
instead of a human eyeballing two JSON blobs: every comparable metric is
classified as a WIN, a REGRESSION, or WITHIN-NOISE against a
configurable threshold, and missing fields are tolerated (reported as
``missing`` — older records predate newer fields, and a comparison must
not fail because the attribution digest or an A/B sub-rung is absent on
one side).

Input forms accepted per file (auto-detected):
  - a driver artifact ``{"parsed": {...}}`` (the BENCH_r0x.json shape)
  - a bare bench record ``{"metric": ..., "value": ...}``
  - a JSONL/last-line file whose final ``{``-line is the record

Compared fields (each skipped when absent on either side):
  value                      headline throughput — higher is better
  mfu                        higher is better
  tflops_per_sec             higher is better
  metrics.step_seconds_quantiles.<path>.p50/p95
                             lower is better, per execution path
  metrics.attribution.phase_seconds.<lane>.<phase>.p50
                             lower is better, per lane/phase
  metrics.attribution.feed.stall_fraction
                             lower is better (absolute-delta gate:
                             a 0 -> 0.002 change must not read as an
                             infinite regression)
  latency_seconds.p50/p99    (serving records) lower is better
  decode.tokens_per_sec      (PT_BENCH_DECODE records) higher is better
  decode.naive_tokens_per_sec
                             higher is better (the re-prefill baseline
                             arm of the decode A/B)
  decode.latency_seconds.p50/p99
                             per-token decode-step latency — lower is
                             better
  pipeline_ab.arms.<arm>.<mK>.p50_s
                             (PT_BENCH_PIPELINE records) pipelined step
                             p50 per arm (runner / gpipe / 1f1b) and
                             microbatch count — lower is better

Exit codes: 0 = no regression, 1 = at least one regression, 2 = unusable
input.  ``--threshold-pct`` (default 5) is the noise band;
``--require-config-match`` escalates a config mismatch (after
methodology-token stripping, bench.strip_methodology) from a warning to
exit 2, because cross-shape ratios are not comparisons.

Usage:
  python tools/perf_compare.py OLD.json NEW.json [--threshold-pct 5]
      [--require-config-match] [--json]
  make perf-compare [OLD=...] [NEW=...]   # defaults: two newest BENCH_*
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def load_record(path):
    """-> the bench record dict inside `path`, or None when unusable."""
    try:
        text = Path(path).read_text()
    except OSError as e:
        print(f"perf_compare: cannot read {path}: {e}", file=sys.stderr)
        return None
    rec = None
    try:
        obj = json.loads(text)
        if isinstance(obj, dict):
            rec = obj.get("parsed") if isinstance(obj.get("parsed"),
                                                  dict) else obj
    except json.JSONDecodeError:
        # JSONL / log tail: the last line that parses as a JSON object
        for line in reversed(text.splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict):
                rec = obj.get("parsed") if isinstance(obj.get("parsed"),
                                                      dict) else obj
                break
    if not isinstance(rec, dict) or "metric" not in rec:
        print(f"perf_compare: no bench record found in {path}",
              file=sys.stderr)
        return None
    return rec


def _dig(rec, dotted):
    cur = rec
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _num(v):
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


def compare_field(name, old, new, threshold_pct, higher_is_better,
                  absolute=False):
    """One classified comparison row.  `absolute` gates on the absolute
    delta instead of the ratio — for fields whose baseline is
    legitimately ~0 (a stall fraction), where a ratio would turn noise
    into an unbounded regression."""
    old_v, new_v = _num(old), _num(new)
    if old_v is None or new_v is None:
        return {"field": name, "status": "missing",
                "old": old, "new": new}
    thr = threshold_pct / 100.0
    if absolute:
        delta = new_v - old_v
        worse = delta > thr if higher_is_better is False else -delta > thr
        better = -delta > thr if higher_is_better is False else delta > thr
        pct = None
    else:
        if old_v == 0:
            return {"field": name, "status": "missing", "old": old_v,
                    "new": new_v, "note": "zero baseline"}
        ratio = new_v / old_v
        gain = ratio - 1.0 if higher_is_better else 1.0 - ratio
        better, worse = gain > thr, gain < -thr
        pct = round((ratio - 1.0) * 100.0, 2)
    status = ("regression" if worse
              else "win" if better else "within-noise")
    row = {"field": name, "status": status, "old": old_v, "new": new_v}
    if pct is not None:
        row["delta_pct"] = pct
    return row


def _quantile_fields(rec_old, rec_new):
    """Dotted paths of per-path/lane quantile fields present on either
    side (lower is better)."""
    fields = []
    for prefix, keys in (("metrics.step_seconds_quantiles",
                          ("p50", "p95")),
                         ("metrics.attribution.phase_seconds", ("p50",))):
        groups = set()
        for rec in (rec_old, rec_new):
            node = _dig(rec, prefix)
            if isinstance(node, dict):
                groups.update(node.keys())
        for g in sorted(groups):
            sub_old = _dig(rec_old, f"{prefix}.{g}") or {}
            sub_new = _dig(rec_new, f"{prefix}.{g}") or {}
            if prefix.endswith("phase_seconds"):
                # one more level: {lane: {phase: {p50...}}}
                phases = set(sub_old) | set(sub_new)
                for p in sorted(phases):
                    for q in keys:
                        fields.append(f"{prefix}.{g}.{p}.{q}")
            else:
                for q in keys:
                    fields.append(f"{prefix}.{g}.{q}")
    return fields


def compare_records(old, new, threshold_pct=5.0):
    """-> (rows, config_match).  Rows cover every comparable field."""
    rows = []
    for field in ("value", "mfu", "tflops_per_sec"):
        rows.append(compare_field(field, old.get(field), new.get(field),
                                  threshold_pct, higher_is_better=True))
    for field in ("latency_seconds.p50", "latency_seconds.p99",
                  "decode.latency_seconds.p50",
                  "decode.latency_seconds.p99"):
        rows.append(compare_field(field, _dig(old, field),
                                  _dig(new, field), threshold_pct,
                                  higher_is_better=False))
    # PT_BENCH_DECODE records: both arms of the lane-vs-naive A/B are
    # throughputs (absent on every older record — tolerated as missing)
    for field in ("decode.tokens_per_sec", "decode.naive_tokens_per_sec"):
        rows.append(compare_field(field, _dig(old, field),
                                  _dig(new, field), threshold_pct,
                                  higher_is_better=True))
    # PT_BENCH_PIPELINE records (pipeline_ab): per-arm p50 at every
    # swept microbatch count — lower is better; runner vs policy and
    # gpipe vs 1f1b regressions both gate through these rows
    pipe_arms = set()
    for rec in (old, new):
        arms = _dig(rec, "pipeline_ab.arms")
        if isinstance(arms, dict):
            pipe_arms.update(arms.keys())
    for arm in sorted(pipe_arms):
        ms = set()
        for rec in (old, new):
            node = _dig(rec, f"pipeline_ab.arms.{arm}")
            if isinstance(node, dict):
                ms.update(k for k in node if k.startswith("m"))
        for m in sorted(ms):
            rows.append(compare_field(
                f"pipeline_ab.arms.{arm}.{m}.p50_s",
                _dig(old, f"pipeline_ab.arms.{arm}.{m}.p50_s"),
                _dig(new, f"pipeline_ab.arms.{arm}.{m}.p50_s"),
                threshold_pct, higher_is_better=False))
    for field in _quantile_fields(old, new):
        rows.append(compare_field(field, _dig(old, field),
                                  _dig(new, field), threshold_pct,
                                  higher_is_better=False))
    rows.append(compare_field(
        "metrics.attribution.feed.stall_fraction",
        _dig(old, "metrics.attribution.feed.stall_fraction"),
        _dig(new, "metrics.attribution.feed.stall_fraction"),
        threshold_pct, higher_is_better=False, absolute=True))
    cfg_old = old.get("config", "")
    cfg_new = new.get("config", "")
    try:
        if str(REPO) not in sys.path:
            sys.path.insert(0, str(REPO))
        from bench import strip_methodology

        match = (strip_methodology(cfg_old, era_only=True)
                 == strip_methodology(cfg_new, era_only=True))
    except Exception:
        match = cfg_old == cfg_new
    return rows, match


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old")
    ap.add_argument("new")
    ap.add_argument("--threshold-pct", type=float, default=5.0,
                    help="noise band in percent (default 5)")
    ap.add_argument("--require-config-match", action="store_true",
                    help="exit 2 when the two records' configs differ "
                         "after methodology-token stripping")
    ap.add_argument("--json", action="store_true",
                    help="emit the comparison as one JSON object")
    args = ap.parse_args(argv)

    old = load_record(args.old)
    new = load_record(args.new)
    if old is None or new is None:
        return 2
    if old.get("metric") != new.get("metric"):
        print(f"perf_compare: different metrics "
              f"({old.get('metric')!r} vs {new.get('metric')!r}) — "
              f"not comparable", file=sys.stderr)
        return 2
    rows, cfg_match = compare_records(old, new,
                                      threshold_pct=args.threshold_pct)
    if not cfg_match:
        msg = (f"config mismatch: {old.get('config')!r} vs "
               f"{new.get('config')!r}")
        if args.require_config_match:
            print(f"perf_compare: {msg}", file=sys.stderr)
            return 2
        print(f"perf_compare: WARNING {msg} — ratios cross shapes",
              file=sys.stderr)

    regressions = [r for r in rows if r["status"] == "regression"]
    compared = [r for r in rows if r["status"] != "missing"]
    if args.json:
        print(json.dumps({
            "metric": new.get("metric"),
            "threshold_pct": args.threshold_pct,
            "config_match": cfg_match,
            "rows": rows,
            "regressions": len(regressions),
        }, indent=1))
    else:
        print(f"perf_compare: {old.get('metric')} "
              f"(threshold {args.threshold_pct:g}%)")
        for r in rows:
            if r["status"] == "missing":
                continue
            delta = (f" ({r['delta_pct']:+.2f}%)"
                     if "delta_pct" in r else "")
            print(f"  {r['status']:<12} {r['field']}: "
                  f"{r['old']} -> {r['new']}{delta}")
        missing = [r["field"] for r in rows if r["status"] == "missing"]
        if missing:
            print(f"  skipped (missing on a side): {len(missing)} field(s)")
        print(f"perf_compare: {len(compared)} compared, "
              f"{len(regressions)} regression(s)")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
