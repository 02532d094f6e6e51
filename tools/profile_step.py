"""Step-time breakdown for the flagship BERT train step (PERF.md lever 2).

Splits the headline step into measured segments and pairs each with XLA's
own cost model for the compiled executable:

  forward        — the for_test clone (loss only)
  full_step      — fwd + bwd + Adam, the bench.py headline config
  bwd_optimizer  — derived: full - forward

and reports, per segment: wall ms, XLA-counted GFLOPs, bytes accessed,
arithmetic intensity (FLOP/byte), and the roofline bound implied by the
chip's peak FLOPs and HBM bandwidth — i.e. *which* resource the segment is
limited by and how close it runs to that limit.  The analytic dot-FLOPs
model (bench._bert_train_flops_per_step) is printed alongside so the XLA
count can be sanity-checked against it.

Honors the bench.py dtype knobs (PT_BENCH_FP32 / PT_BENCH_AMP, default =
bf16 policy) and PT_BENCH_BATCH / PT_BENCH_SEQLEN / PT_BENCH_STEPS /
PT_BENCH_SIZE.  Works on any backend; on TPU it fills the "where do the
non-dot milliseconds go" table that decides the next optimization.

  PYTHONPATH=/root/repo python tools/profile_step.py
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# v5e HBM bandwidth (public spec); override for other chips
HBM_GBPS = float(os.environ.get("PT_TPU_HBM_GBPS", "819"))


def _analyze(exe, prog, data, loss, dt_s, peak_tflops):
    """Merge measured time with the executable's cost analysis."""
    rec = {"ms": round(dt_s * 1e3, 2)}
    cost = exe.cost_analysis(prog, data, fetch_list=[loss])
    flops = float(cost["cost"].get("flops", 0.0))
    byt = float(cost["cost"].get("bytes accessed", 0.0))
    rec["xla_gflops"] = round(flops / 1e9, 2)
    rec["xla_gbytes"] = round(byt / 1e9, 3)
    if byt:
        rec["intensity_flop_per_byte"] = round(flops / byt, 1)
    if dt_s:
        rec["achieved_tflops"] = round(flops / dt_s / 1e12, 2)
        rec["achieved_gbps"] = round(byt / dt_s / 1e9, 1)
    if peak_tflops and byt:
        # roofline: which wall is closer at this intensity?
        t_compute = flops / (peak_tflops * 1e12)
        t_memory = byt / (HBM_GBPS * 1e9)
        rec["bound"] = "compute" if t_compute >= t_memory else "memory"
        floor = max(t_compute, t_memory)
        if floor:
            rec["roofline_frac"] = round(floor / dt_s, 3) if dt_s else None
    mem = cost.get("memory") or {}
    if mem:
        rec["memory_bytes"] = mem
    return rec


def main():
    import numpy as np  # noqa: F401  (kept for parity with bench imports)

    import bench
    from paddle_tpu import fluid
    from paddle_tpu.fluid.executor import Scope, scope_guard
    from paddle_tpu.models import bert

    size = os.environ.get("PT_BENCH_SIZE", "base")
    batch = int(os.environ.get("PT_BENCH_BATCH", "128"))
    seq_len = int(os.environ.get("PT_BENCH_SEQLEN", "128"))
    n_steps = int(os.environ.get("PT_BENCH_STEPS", "10"))
    amp = os.environ.get("PT_BENCH_AMP", "0") == "1"
    bf16 = bench._bf16_default()

    kw = dict(vocab_size=30528, use_flash_attention=False)
    cfg = bert.BertConfig.base(**kw) if size == "base" else \
        bert.BertConfig.tiny(**kw)

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup), fluid.unique_name.guard():
        feeds, loss, mlm_loss, nsp_acc = bert.build_bert_pretrain(
            cfg, is_test=False)
        fwd_prog = main_prog.clone(for_test=True)
        opt = fluid.optimizer.Adam(learning_rate=1e-4)
        if amp:
            from paddle_tpu.fluid.contrib import mixed_precision as mp

            opt = mp.decorate(opt)
        opt.minimize(loss)
    bench._maybe_enable_bf16(main_prog, bf16)
    bench._maybe_enable_bf16(fwd_prog, bf16)

    peak = bench._peak_tflops()
    flops_model = bench._bert_train_flops_per_step(cfg, batch, seq_len)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        data = bert.make_fake_batch(cfg, batch=batch, seq_len=seq_len,
                                    seed=0)
        # bench's shared warmup + timed loop, so the two tools can never
        # diverge on sync/warmup semantics
        dt_full = bench._timed_steps(exe, main_prog, data, loss.name,
                                     n_steps) / n_steps
        dt_fwd = bench._timed_steps(exe, fwd_prog, data, loss.name,
                                    n_steps) / n_steps

        out = {
            "config": (f"bert-{size} b{batch} s{seq_len}"
                       + (" bf16" if amp else "")
                       + (" bf16-policy" if bf16 else "")
                       + (" fp32" if not (amp or bf16) else "")
                       + bench._cpu_suffix()),
            "peak_tflops": peak,
            "hbm_gbps": HBM_GBPS,
            "analytic_train_gflops": round(flops_model / 1e9, 1),
            "tokens_per_sec": round(batch * seq_len / dt_full, 1),
            "forward": _analyze(exe, fwd_prog, data, loss.name, dt_fwd,
                                peak),
            "full_step": _analyze(exe, main_prog, data, loss.name, dt_full,
                                  peak),
            "bwd_optimizer": {"ms": round((dt_full - dt_fwd) * 1e3, 2)},
        }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
