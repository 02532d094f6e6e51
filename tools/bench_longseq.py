"""Long-sequence flash-attention sweep (VERDICT round 1 item 5).

Flash attention exists for the long-sequence regime where materializing
the [B, H, S, S] score tensor saturates HBM; at s128 it loses to the
XLA-fused baseline (measured round 1) and that was the only recorded
number.  This sweep measures bert-base tokens/sec with and without the
Pallas flash kernel at s in {512, 1024, 2048} (batch scaled to keep
~16k tokens per step) plus the GPT KV-cache decode metric, and writes
LONGSEQ_BENCH.json at the repo root:

    {"sweep": [{"seq_len": ..., "flash": ..., "tokens_per_sec": ...}...],
     "flash_speedup": {"512": r, "1024": r, "2048": r},
     "gpt_decode": {...}}

Run on the chip:
    PYTHONPATH=/root/repo python tools/bench_longseq.py
Each config runs in a watchdog child via bench.py's PT_BENCH_CHILD mode
(children run one after another and this parent never imports JAX, so
each child gets the chip), so one stuck compile cannot eat the sweep.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench.py")
# PT_LONGSEQ_OUT redirects the sweep artifact
OUT = os.environ.get("PT_LONGSEQ_OUT",
                     os.path.join(ROOT, "LONGSEQ_BENCH.json"))

TOKENS_PER_STEP = 16384
SEQ_LENS = (512, 1024, 2048)


def run_config(seq_len, flash, budget):
    env = dict(
        os.environ,
        PT_BENCH_CHILD="base",
        PT_BENCH_SEQLEN=str(seq_len),
        PT_BENCH_BATCH=str(max(1, TOKENS_PER_STEP // seq_len)),
        PT_BENCH_STEPS="6",
        PT_BENCH_FLASH="1" if flash else "0",
        # pin every dtype knob so ambient env can't mislabel an A/B leg
        PT_BENCH_BF16="1", PT_BENCH_FP32="0", PT_BENCH_AMP="0",
    )
    try:
        out = subprocess.run([sys.executable, BENCH], env=env,
                             capture_output=True, text=True, timeout=budget)
    except subprocess.TimeoutExpired:
        return {"seq_len": seq_len, "flash": flash,
                "error": f"timeout after {budget:.0f}s"}
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        return {"seq_len": seq_len, "flash": flash,
                "error": out.stderr[-500:]}
    rec = json.loads(lines[-1])
    return {"seq_len": seq_len, "flash": flash,
            "tokens_per_sec": rec["value"],
            "tflops_per_sec": rec.get("tflops_per_sec"),
            "mfu": rec.get("mfu"), "config": rec.get("config")}


def run_gpt_decode(budget, decode="scan", gen=None):
    """Explicit decode/gen overrides — ambient PT_BENCH_DECODE/PT_BENCH_GEN
    must not leak into labeled A/B runs."""
    env = dict(os.environ, PT_BENCH_CHILD="base", PT_BENCH_MODEL="gpt",
               PT_BENCH_DECODE=decode,
               PT_BENCH_BF16="1", PT_BENCH_FP32="0", PT_BENCH_AMP="0")
    if gen is not None:
        env["PT_BENCH_GEN"] = str(gen)
    else:
        env.pop("PT_BENCH_GEN", None)
    try:
        out = subprocess.run([sys.executable, BENCH], env=env,
                             capture_output=True, text=True, timeout=budget)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {budget:.0f}s"}
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        return {"error": out.stderr[-500:]}
    return json.loads(lines[-1])


def main():
    budget = float(os.environ.get("PT_BENCH_TIMEOUT", "900"))
    sweep, speedup = [], {}
    for s in SEQ_LENS:
        base = run_config(s, flash=False, budget=budget)
        fl = run_config(s, flash=True, budget=budget)
        sweep += [base, fl]
        if "tokens_per_sec" in base and "tokens_per_sec" in fl:
            speedup[str(s)] = round(
                fl["tokens_per_sec"] / base["tokens_per_sec"], 3)
        print(json.dumps(base), "\n", json.dumps(fl), flush=True)
    # scan decode (default) + the unrolled A/B, and a LONG generation the
    # unrolled program couldn't even compile in budget (g256 ≈ 26x compile
    # gap at g64 on CPU)
    decode = {"scan_g64": run_gpt_decode(budget, decode="scan"),
              "unrolled_g64": run_gpt_decode(budget, decode="unrolled"),
              "scan_g256": run_gpt_decode(budget, decode="scan", gen=256)}
    result = {"sweep": sweep, "flash_speedup": speedup,
              "gpt_decode": decode}
    with open(OUT, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"flash_speedup": speedup, "written": OUT}))


if __name__ == "__main__":
    main()
