"""A/B the PTQ int8-compute serving path against bf16/fp32 on one chip.

Two legs, each timing three predictor variants over identical batches:
  dense — an MLP classifier (the int8_matmul rewrite)
  cnn   — a conv stack (the int8_conv2d rewrite, r5: the reference's
          primary int8 target, mkldnn_quantizer.cc)
Variants:
  fp32      — the baseline program
  bf16      — the bf16 dtype policy
  int8      — calibrate + apply_int8_compute (REAL int8 MXU contraction)

v5e peak: 394 int8 TOPS vs 197 bf16 TFLOP/s — a dot-bound graph has 2×
headroom.  Prints one JSON line per variant per leg.

  PYTHONPATH=/root/repo python tools/bench_int8_serve.py
  (JAX_PLATFORMS=cpu for a machinery test; numbers then mean nothing)
  PT_I8_LEGS=dense,cnn selects legs.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.fluid import layers  # noqa: E402
from paddle_tpu.fluid.contrib import ptq  # noqa: E402
from paddle_tpu.fluid.executor import Scope, scope_guard  # noqa: E402

BATCH = int(os.environ.get("PT_I8_BATCH", "256"))
DIN = int(os.environ.get("PT_I8_DIN", "1024"))
HID = int(os.environ.get("PT_I8_HID", "4096"))
LAYERS = int(os.environ.get("PT_I8_LAYERS", "8"))
STEPS = int(os.environ.get("PT_I8_STEPS", "30"))


def _build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[DIN], dtype="float32")
        h = x
        for i in range(LAYERS):
            h = layers.fc(h, size=HID if i < LAYERS - 1 else DIN,
                          act="relu", param_attr=f"i8b_w{i}",
                          bias_attr=f"i8b_b{i}")
        out = layers.fc(h, size=16, param_attr="i8b_out_w",
                        bias_attr="i8b_out_b")
    return main, startup, out


def _flops():
    # layer widths mirror _build(): DIN → HID×(LAYERS−1) → DIN → 16
    widths = [DIN] + [HID] * (LAYERS - 1) + [DIN, 16]
    per = sum(a * b for a, b in zip(widths, widths[1:]))
    return 2.0 * BATCH * per


CNN_BATCH = int(os.environ.get("PT_I8_CNN_BATCH", "64"))
CNN_SIZE = int(os.environ.get("PT_I8_CNN_SIZE", "32"))
CNN_CH = int(os.environ.get("PT_I8_CNN_CH", "128"))
CNN_LAYERS = int(os.environ.get("PT_I8_CNN_LAYERS", "6"))


def _build_cnn():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="img", shape=[3, CNN_SIZE, CNN_SIZE],
                        dtype="float32")
        h = x
        for i in range(CNN_LAYERS):
            h = layers.conv2d(h, num_filters=CNN_CH, filter_size=3,
                              padding=1, act="relu",
                              param_attr=f"i8c_w{i}", bias_attr=f"i8c_b{i}")
        h = layers.pool2d(h, pool_type="avg", global_pooling=True)
        h = layers.reshape(h, shape=[-1, CNN_CH])
        out = layers.fc(h, size=16, param_attr="i8c_out_w",
                        bias_attr="i8c_out_b")
    return main, startup, out


def _cnn_flops():
    chans = [3] + [CNN_CH] * CNN_LAYERS
    per = sum(2.0 * cout * cin * 9 * CNN_SIZE * CNN_SIZE
              for cin, cout in zip(chans, chans[1:]))
    return CNN_BATCH * (per + 2.0 * CNN_CH * 16)


def _time(exe, prog, feed, fetch):
    import jax

    # return_numpy=False keeps fetches as device arrays so the loop
    # dispatches asynchronously; one block at the end drains the chain
    outs = exe.run(prog, feed=feed, fetch_list=fetch,
                   return_numpy=False)                  # compile + warm
    jax.block_until_ready(outs)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        outs = exe.run(prog, feed=feed, fetch_list=fetch,
                       return_numpy=False)
    jax.block_until_ready(outs)
    return (time.perf_counter() - t0) / STEPS


def _run_leg(leg, build, feed, flops, n_int8, config):
    results = {}
    for tag in ("fp32", "bf16", "int8"):
        main_p, startup, out = build()
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            if tag == "bf16":
                from paddle_tpu.fluid.contrib import mixed_precision as mp

                mp.enable_bf16_policy(main_p)
            elif tag == "int8":
                from paddle_tpu.fluid import ir

                ir.apply_pass(main_p, "fc_fuse_pass", keep_vars=[out.name])
                cfg = ptq.PTQConfig(calibration_feeds=[feed])
                scales = ptq.calibrate(exe, main_p, cfg)
                n = ptq.apply_int8_compute(main_p, scales)
                # ALL dot/conv layers must rewrite or the A/B silently
                # mixes precisions
                assert n == n_int8, f"{n}/{n_int8} layers rewrote to int8"
            dt = _time(exe, main_p, feed, [out.name])
        results[tag] = dt
        print(json.dumps({
            "metric": f"{leg}_serve_tflops", "variant": tag,
            "value": round(flops / dt / 1e12, 2), "unit": "TFLOP/s",
            "ms_per_batch": round(dt * 1e3, 3), "config": config,
        }), flush=True)
    speedup = (round(results["bf16"] / results["int8"], 3)
               if "bf16" in results and "int8" in results else None)
    if speedup is not None:
        print(json.dumps({
            "metric": f"{leg}_int8_speedup_vs_bf16",
            "value": speedup, "unit": "x"}), flush=True)
    rec = {tag: round(dt * 1e3, 3) for tag, dt in results.items()}
    if speedup is not None:
        rec["int8_speedup_vs_bf16"] = speedup
    return rec


def main():
    import jax

    rng = np.random.RandomState(0)
    legs = os.environ.get("PT_I8_LEGS", "dense,cnn").split(",")
    # every record names the device it ran on: a CPU machinery run can
    # never read as a chip number
    summary = {"metric": "int8_serve_summary",
               "platform": jax.devices()[0].platform}
    if "dense" in legs:
        summary["dense"] = _run_leg(
            "dense", _build,
            {"x": rng.randn(BATCH, DIN).astype("float32")}, _flops(),
            LAYERS + 1, f"mlp d{DIN} h{HID} x{LAYERS} b{BATCH}")
    if "cnn" in legs:
        summary["cnn"] = _run_leg(
            "cnn", _build_cnn,
            {"img": rng.randn(CNN_BATCH, 3, CNN_SIZE,
                              CNN_SIZE).astype("float32")},
            _cnn_flops(), CNN_LAYERS + 1,
            f"cnn c{CNN_CH} x{CNN_LAYERS} s{CNN_SIZE} b{CNN_BATCH}")
    # one final line carrying every number
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
