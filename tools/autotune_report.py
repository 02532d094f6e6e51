#!/usr/bin/env python
"""Pretty-print or diff autotune reports (docs/AUTOTUNE.md).

One report → a ranked candidate table (predicted vs measured, per-term
cost attribution, the winner and its pin line).  Two reports → a
mechanical diff: did the winner change, did a measured candidate's p50
regress past the noise threshold, did the prediction error drift.

Exit codes:
  0  printed / diffed, no winner change and no measured regression
  1  diff found a winner change or a measured p50 regression
  2  unreadable / schema-mismatched input
"""

import argparse
import json
import sys

SCHEMA = "paddle_tpu.autotune/v1"


def load(path):
    try:
        with open(path) as f:
            rep = json.load(f)
    except (OSError, ValueError) as e:
        print(f"autotune_report: cannot read {path}: {e}",
              file=sys.stderr)
        return None
    if rep.get("schema") != SCHEMA:
        print(f"autotune_report: {path} schema "
              f"{rep.get('schema')!r} != {SCHEMA!r}", file=sys.stderr)
        return None
    return rep


def _measured_by_label(rep):
    return {m["label"]: m.get("measured")
            for m in rep.get("measured", []) if m.get("measured")}


def _fmt_s(v):
    return "-" if v is None else f"{v:.6f}"


def show(rep):
    w = rep.get("workload", {})
    print(f"autotune report: {rep.get('n_devices')} devices, "
          f"{len(rep.get('candidates', []))} candidates, "
          f"workload={ {k: v for k, v in w.items() if k != 'feed_shapes'} }")
    ci = rep.get("cost_inputs", {})
    print(f"  cost inputs: flops={ci.get('flops'):.3e} "
          f"bytes={ci.get('bytes_accessed'):.3e} "
          f"batch_rows={ci.get('batch_rows')}")
    measured = _measured_by_label(rep)
    print(f"  {'rank':<4} {'candidate':<28} {'pred_s':>10} "
          f"{'meas_p50_s':>11} {'coll_bytes':>11} {'err':>7} conf")
    for c in rep.get("candidates", []):
        p = c["predicted"]
        m = measured.get(c["label"]) or {}
        err = m.get("prediction_error")
        print(f"  {p.get('rank', '-'):<4} {c['label']:<28} "
              f"{p['total_s']:>10.6f} {_fmt_s(m.get('p50_s')):>11} "
              f"{p.get('collective_bytes', 0):>11} "
              f"{'-' if err is None else f'{err:.3f}':>7} "
              f"{p.get('confidence')}")
        terms = {k: round(v, 9) for k, v in p.get("terms", {}).items()
                 if v}
        if terms:
            print(f"       terms: {terms}")
    winner = rep.get("winner")
    if winner:
        print(f"  winner: {winner['label']} "
              f"(analytic rank {rep.get('winner_rank')}, "
              f"top3_contains_winner="
              f"{rep.get('analytic_top3_contains_winner')})")
        print(f"  pin: DataParallelRunner(..., policy_pin="
              f"{json.dumps(winner['candidate'])})")
    gvt = rep.get("gspmd_vs_transpiler")
    if gvt:
        print(f"  gspmd_vs_transpiler: win_or_tie={gvt.get('win_or_tie')} "
              f"(gspmd {_fmt_s(gvt.get('gspmd_p50_s'))} vs transpiler "
              f"{_fmt_s(gvt.get('transpiler_p50_s'))})")


def diff(old, new, threshold_pct):
    bad = False
    ow = (old.get("winner") or {}).get("label")
    nw = (new.get("winner") or {}).get("label")
    if ow != nw:
        print(f"WINNER CHANGED: {ow!r} -> {nw!r}")
        bad = True
    else:
        print(f"winner unchanged: {nw!r}")
    om, nm = _measured_by_label(old), _measured_by_label(new)
    for label in sorted(set(om) & set(nm)):
        o, n = om[label]["p50_s"], nm[label]["p50_s"]
        delta = (n - o) / o * 100.0 if o else 0.0
        status = ("regression" if delta > threshold_pct
                  else "win" if delta < -threshold_pct else "within-noise")
        print(f"  {status:<12} {label}: p50 {o:.6f} -> {n:.6f} "
              f"({delta:+.2f}%)")
        if status == "regression":
            bad = True
        oe, ne = (om[label].get("prediction_error"),
                  nm[label].get("prediction_error"))
        if oe is not None and ne is not None and abs(ne - oe) > 0.02:
            print(f"               prediction_error drift "
                  f"{oe:.3f} -> {ne:.3f}")
    only = sorted(set(om) ^ set(nm))
    if only:
        print(f"  measured on one side only: {only}")
    return bad


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("report", help="autotune_report.json")
    ap.add_argument("other", nargs="?",
                    help="second report — diff mode when given")
    ap.add_argument("--threshold-pct", type=float, default=10.0,
                    help="p50 noise band in percent (default 10)")
    args = ap.parse_args(argv)

    rep = load(args.report)
    if rep is None:
        return 2
    if not args.other:
        show(rep)
        return 0
    new = load(args.other)
    if new is None:
        return 2
    return 1 if diff(rep, new, args.threshold_pct) else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # `... | head` closed the pipe mid-print
        sys.exit(0)
