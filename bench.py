"""Benchmark: BERT-base pretraining throughput (tokens/sec) on one chip.

Runs the flagship training step (fwd + bwd + Adam, whole-step XLA
compilation, parameter buffers donated) under the bf16 dtype policy — the
north-star config (BASELINE.md: "BERT-base pretraining tokens/sec (bf16)",
fp32 master weights) — and prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The reference publishes no in-tree numbers (SURVEY.md §6, BASELINE.json
"published": {}), so vs_baseline is reported against our own first recorded
measurement (BENCH_BASELINE env or 1.0).

The measurement runs in a child process under a watchdog
(PT_BENCH_TIMEOUT, default 25 min); the parent never touches JAX, so the
one process that holds the chip is the child that measures.  If the
full-size config stalls a smaller one is tried.  A number only ever comes
from the TPU: no TPU, or no config completing, is a non-zero exit and no
record.

Env knobs: PT_BENCH_FP32=1 → plain-fp32 comparison rung; PT_BENCH_AMP=1 →
cast-insertion AMP rewrite; PT_BENCH_FLASH=1 → Pallas flash-attention path
(attention-probs dropout off, the usual flash trade); PT_BENCH_QUANTAR=1 →
data-parallel rung with the EQuARX-style quantized gradient all-reduce
(bucketed block-scaled int8 collectives; records bytes-accessed from the
executable's cost_analysis, both algorithms' modeled wire bytes
(oneshot vs ppermute ring — pin one with FLAGS_quant_allreduce_algo),
step-time p50/p95/max quantiles, a rung-end /metricsz scrape of the
pt_collective_* families, the ready-order dispatch schedule, and — unless
PT_BENCH_HOPLAT=0 — the hop-latency sub-rung: per-hop latency vs payload
for the ring vs the oneshot form plus the measured crossover that tunes
FLAGS_quant_allreduce_crossover_kb); PT_BENCH_OVERLAP=1 (with QUANTAR) →
overlap-on vs overlap-off A/B with per-arm p50/p95/max step quantiles
(FLAGS_overlap_allreduce toggled per arm); PT_BENCH_GSPMD=1 →
transpiler-lane vs GSPMD-executor-lane A/B (parallel/gspmd/): per-arm
p50/p95/max step quantiles plus the gspmd arm's XLA-inserted collective
counts and resharding bytes from compiled-HLO inspection;
PT_BENCH_HEALTH=1 → health-sentinel-on vs -off A/B
(paddle_tpu/health/): per-arm p50/p95/max step quantiles + the p50
overhead fraction of the in-graph finite check / skip gate (acceptance:
<=2% on the CPU smoke); PT_BENCH_PHASES=1 → phase-instrumentation
on/off A/B (FLAGS_profile_phases, observability/profiling.py):
interleaved arms, per-arm p50/p95/max + the overhead fraction, plus the
on-arm's measured per-phase p50s — and every record embeds the
step-time attribution digest (phase quantiles, per-signature MFU +
roofline verdict, feed-bound fraction) under metrics.attribution,
diffable with tools/perf_compare.py (make perf-compare); PT_BENCH_SERVE=1 → serving-lane load-generator
rung: a paddle_tpu.serving.Engine under closed-loop concurrent clients,
recording request throughput + p50/p99 latency quantiles and batch-size /
executable-cache figures (PT_BENCH_SERVE_CLIENTS, PT_BENCH_SERVE_REQUESTS
knobs); PT_BENCH_DECODE=1 → decode-lane load-generator rung (`make
decode-bench`): a serving.DecodeEngine (paged KV pool, token-level
continuous batching) under mixed prompt lengths, recording lane
tokens/s vs the naive re-prefill-every-token baseline, steady-state
executable-cache misses (acceptance: 0), per-token p50/p99 and the
short-vs-long-prompt step-time ratio (PT_BENCH_DECODE_REQS,
PT_BENCH_DECODE_GEN, PT_BENCH_DECODE_SLOTS knobs);
PT_BENCH_RAGGED=1 → ragged-serving A/B rung (`make ragged-bench`): the
SAME ragged-attention model served bucketed-padded vs ragged under
identical mixed-length traffic, recording real tokens/s per arm,
pt_serve_rows_total{kind=padding} deltas (ragged full waves pay zero
padding rows), warmup executable counts (ragged: one per batch bucket)
and the modeled fp32-vs-dual-int8 KV-pool bytes
(PT_BENCH_RAGGED_WAVES knob);
PT_BENCH_RECOVERY=1 → measured preempt→restore rung (`make
recovery-bench`): the in-process recovery drill
(distributed.recovery.inprocess_drill) restoring through the persisted
health rollback window, recording per-phase recovery seconds + MTTR
(PT_BENCH_RECOVERY_STEPS, PT_BENCH_RECOVERY_KILL knobs);
PT_BENCH_SERVE_DRILL=1 → serving resilience rung (`make serve-drill`):
the FaultPlan-driven serving drills (serving/drill.py — replica_kill
failover with token-exact resume, canary promotion clean + rollback,
hedged requests), recording failover MTTR and hedge win-rate;
PT_BENCH_PIPELINE=1 → pipeline-as-policy A/B rung
(parallel/gspmd/pipeline_policy.py): host-scheduled PipelineRunner vs
the one-jit PipelinePolicy, gpipe vs 1f1b, microbatch sweep with
per-arm step quantiles, modeled per-boundary wire bytes, and the
measured bubble fraction backed out of the sweep;
PT_BENCH_STEPS, PT_BENCH_BATCH, PT_BENCH_SEQLEN, BENCH_BASELINE.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# effective dispatch of the last _timed_steps call: "pipelined" when the
# fetch-free chain ran, "syncfetch" when per-step fetches did (either the
# env knob or the write-free-program fallback), "chainK" when K steps ran
# inside one compiled fori_loop (Executor.run_steps)
_last_dispatch = None

# timing-methodology config tokens (plus the dynamic "chainK" family).
# Two kinds:
#   era markers — labels the DEFAULT methodology gained over time
#     (pre-pipelining and pre-devfeed records carry none); a baseline
#     match may cross these, so a re-capture still finds the older-era
#     record of the same shape (the movement signal), visibly, because
#     the configs differ on disk.
#   A/B markers — deliberate variants (fetch-every-step, host feeds,
#     chainK dispatch); these must match EXACTLY, or an A/B leg would be
#     ratioed against the default-methodology record it exists to
#     contrast with.
ERA_MARKERS = ("devfeed", "pipelined")
AB_MARKERS = ("hostfeed", "syncfetch")
METHODOLOGY_MARKERS = ERA_MARKERS + AB_MARKERS


def is_chain_marker(tok):
    """True for the dynamic chainK dispatch marker ("chain32"), false for
    model tokens that merely start with "chain"."""
    return tok.startswith("chain") and tok[5:].isdigit()


def strip_methodology(config, era_only=False):
    """A config string with timing-methodology tokens removed.  The full
    strip is the shape-and-dtype identity; era_only keeps the A/B markers
    (hostfeed/syncfetch/chainK) so deliberate variants never alias the
    default methodology's records."""
    drop = ERA_MARKERS if era_only else METHODOLOGY_MARKERS
    return " ".join(
        t for t in config.split(" ")
        if not (t in drop or (not era_only and is_chain_marker(t))))


def _chain_steps():
    """PT_BENCH_CHAIN_STEPS=K: dispatch K steps as ONE XLA call
    (Executor.run_steps).  0/unset = per-step dispatch."""
    return int(os.environ.get("PT_BENCH_CHAIN_STEPS", "0") or 0)


def _cpu_suffix():
    suffix = ""
    if os.environ.get("PT_BENCH_SYNC_FETCH") == "1":
        # fetch-every-step A/B variant: labeled so it can never be compared
        # against a pipelined-dispatch record of the same shape
        suffix = " syncfetch" + suffix
    elif _last_dispatch and _last_dispatch.startswith("chain"):
        # on-device step loop: a different methodology again, so another
        # distinct marker (e.g. " chain32")
        suffix = f" {_last_dispatch}" + suffix
    elif _last_dispatch == "pipelined":
        # methodology marker: pre-pipelining records carry no marker, so an
        # exact config match can never silently cross methodologies (the
        # baseline fallback may still compare, but the configs differ on
        # the record for anyone reading it)
        suffix = " pipelined" + suffix
    if os.environ.get("PT_BENCH_HOST_FEED") == "1":
        # per-step host-feed A/B variant (feeds re-transferred every step
        # instead of device_put once) — distinct methodology, distinct label
        suffix = " hostfeed" + suffix
    else:
        # device-resident feed default (r5): marked like " pipelined" was
        # when it became the default — unmarked records are host-feed era,
        # so an exact config match never crosses the feed methodologies
        suffix = " devfeed" + suffix
    return suffix


# bf16 peak TFLOPs per chip by PJRT device_kind substring (public specs);
# first match wins, so "v5 lite"/"v5e" must precede the bare "v5" (v5p)
# entry.  Override with PT_TPU_PEAK_TFLOPS.  MFU is reported against this.
_TPU_PEAK_TFLOPS = (
    ("v6", 918.0), ("v5p", 459.0), ("v5e", 197.0), ("lite", 197.0),
    ("v5", 459.0), ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
)


def _peak_tflops():
    """Chip peak in TFLOPs for MFU; None off-TPU (the in-process CPU
    tests of the bench helpers).  A TPU kind that is not in the table is
    an error, not a default."""
    env = os.environ.get("PT_TPU_PEAK_TFLOPS")
    if env:
        return float(env)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = dev.device_kind.lower()
    for pat, peak in _TPU_PEAK_TFLOPS:
        if pat in kind:
            return peak
    raise ValueError(
        f"no bf16 peak recorded for TPU kind {dev.device_kind!r} — add it "
        f"to _TPU_PEAK_TFLOPS with its source")


def _bert_train_flops_per_step(cfg, batch, seq_len):
    """Analytic model FLOPs for one train step (fwd + bwd ≈ 3× fwd).

    Per layer fwd: QKVO projections 8·b·s·h², FFN 4·b·s·h·i, attention
    scores+context 4·b·s²·h.  MLM head runs over the M≈b·s/8 gathered
    masked positions: transform 2·M·h² + vocab projection 2·M·h·V.
    Embedding gathers ≈ 0 FLOPs."""
    b, s = batch, seq_len
    h, i, L, V = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    per_layer = 8 * b * s * h * h + 4 * b * s * h * i + 4 * b * s * s * h
    m = b * max(1, s // 8)
    head = 2 * m * h * h + 2 * m * h * V + 2 * b * h * h
    return 3.0 * (L * per_layer + head)


def _attach_flops(result, flops_per_step, n_steps, dt):
    """Add achieved TFLOP/s (always) and MFU (when a chip peak is known)."""
    tflops = flops_per_step * n_steps / dt / 1e12
    result["tflops_per_sec"] = round(tflops, 2)
    peak = _peak_tflops()
    if peak:
        result["mfu"] = round(tflops / peak, 4)
        result["peak_tflops"] = peak
    return result


def _timed_steps(exe, prog, data, loss_name, n_steps):
    """Shared warmup + timed loop.

    Default: steps dispatch WITHOUT per-step fetches so they pipeline on
    the device through the donated param chain — the real training pattern
    (losses are logged every ~100 steps, not every one); the final step
    fetches the loss, which transitively blocks on the whole chain, so the
    total time stays honest.  PT_BENCH_SYNC_FETCH=1 restores the
    fetch-every-step variant; the A/B isolates the per-step host
    round-trip.

    The synthetic feed is device_put ONCE before the timed loop (the
    executor keeps jax.Arrays device-resident) — the prefetched-input
    pattern real training uses: the ResNet leg's b128 image batch is
    ~77 MB/step, so per-step host feeds time the host link, not the
    chip.  The input pipeline is measured separately by the
    dataset_overlap leg; PT_BENCH_HOST_FEED=1 restores per-step host
    feeds for that A/B."""
    global _last_dispatch
    if os.environ.get("PT_BENCH_HOST_FEED") != "1":
        import jax

        data = jax.device_put(data)
    sync = os.environ.get("PT_BENCH_SYNC_FETCH") == "1"
    chain = _chain_steps()
    if chain > 1 and not sync:
        # K steps per XLA call (Executor.run_steps fori_loop): zero host
        # dispatch between steps — the true-device-throughput rung; the
        # delta vs "pipelined" is the residual per-step dispatch cost
        from paddle_tpu.fluid.executor import HostOpsUnsupported

        try:
            exe.run_steps(prog, feed=data, n_steps=chain,
                          fetch_list=[loss_name])  # warm/compile
        except HostOpsUnsupported as e:
            # ONLY the documented host-op rejection falls back — anything
            # else must fail loudly, or the chainK leg would silently time
            # the pipelined path and record a bogus ~0 dispatch delta
            print(f"bench: chain dispatch unavailable ({e}); "
                  "falling back to per-step", file=sys.stderr)
            chain = 0
        if chain:
            n_chains = max(1, n_steps // chain)
            t0 = time.perf_counter()
            for _ in range(n_chains):
                exe.run_steps(prog, feed=data, n_steps=chain,
                              fetch_list=[loss_name])
            dt = time.perf_counter() - t0
            _last_dispatch = f"chain{chain}"
            # report per-step time over the steps actually run
            return dt * (n_steps / float(n_chains * chain))
    # warm BOTH signatures (fetch and no-fetch compile separate
    # executables) so no compile lands inside the timed region
    for _ in range(2):
        exe.run(prog, feed=data, fetch_list=[loss_name])
    if not sync:
        exe.run(prog, feed=data, fetch_list=[])
        cb = exe._cache.get(exe._cache_key(
            prog, exe._coerce_feed(prog, data), ()))
        if cb is None or not cb.write_names:
            # write-free program (inference/decode): with nothing fetched
            # AND nothing written, XLA dead-code-eliminates the whole step,
            # so fetch-free iterations would time an empty executable —
            # keep the per-step fetch for these
            sync = True
        else:
            exe.run(prog, feed=data, fetch_list=[loss_name])  # drain chain
    _last_dispatch = "syncfetch" if sync else "pipelined"
    t0 = time.perf_counter()
    if sync:
        for _ in range(n_steps):
            exe.run(prog, feed=data, fetch_list=[loss_name])
    else:
        for _ in range(n_steps - 1):
            exe.run(prog, feed=data, fetch_list=[])
        exe.run(prog, feed=data, fetch_list=[loss_name])
    return time.perf_counter() - t0


def _timed_steps_dp(exe, prog, data, loss_name, n_steps):
    """Timed loop for a CompiledProgram (data-parallel) rung.  The DP
    runner shards feeds and assembles per-device fetches itself, so this
    stays on the simple fetch-every-step methodology rather than
    _timed_steps' donated-chain pipelining, which keys on the
    single-device executor cache.  The caller labels the record with the
    ``syncfetch`` A/B marker (_cpu_suffix only emits it from the env
    knob), so a future pipelined DP capture can never exact-match these
    records."""
    if os.environ.get("PT_BENCH_HOST_FEED") != "1":
        import jax

        data = jax.device_put(data)
    for _ in range(2):  # warm/compile
        exe.run(prog, feed=data, fetch_list=[loss_name])
    t0 = time.perf_counter()
    for _ in range(n_steps):
        exe.run(prog, feed=data, fetch_list=[loss_name])
    return time.perf_counter() - t0


def _vs_baseline(value, config, is_headline, default_metric=False):
    """Scalar vs_baseline ratio — see _vs_baseline_rec (record form)."""
    return _vs_baseline_rec(value, config, is_headline,
                            default_metric=default_metric)["vs_baseline"]


def _vs_baseline_rec(value, config, is_headline, default_metric=False):
    """BENCH_BASELINE (no baseline without it) only compares against the
    exact headline config it was recorded at (BENCH_BASELINE_CONFIG);
    anything else reports the sentinel (1.0 headline / 0.0 fallback rung).  Only the default (bert)
    metric may match an empty BENCH_BASELINE_CONFIG — for other metrics an
    exact config match is required, because a driver's ambient baseline is
    normally a bert tokens/sec number and dividing across metrics is
    meaningless.

    Returns {"vs_baseline": ratio, "baseline_config": cfg} — the matched
    baseline's config rides along on disk so a reader of one
    bench JSON line can SEE when the ratio crossed methodology eras
    (devfeed vs hostfeed captures), instead of trusting that the fallback
    matching stayed shape-strict."""
    baseline = float(os.environ.get("BENCH_BASELINE", "0") or 0)
    base_cfg = os.environ.get("BENCH_BASELINE_CONFIG", "")
    cfg_match = (base_cfg == config
                 or strip_methodology(base_cfg, era_only=True)
                 == strip_methodology(config, era_only=True)
                 or (default_metric and not base_cfg))
    comparable = baseline > 0 and is_headline and cfg_match
    return {
        "vs_baseline": round(value / baseline if comparable else
                             (1.0 if is_headline else 0.0), 3),
        "baseline_config": base_cfg if comparable else "",
    }


def _bf16_default():
    """Shared dtype-knob semantics for every bench mode: bf16 policy is
    the default; PT_BENCH_FP32=1 pins plain fp32; PT_BENCH_AMP selects the
    cast-insertion rewrite (bert only) and turns the policy off."""
    if os.environ.get("PT_BENCH_FP32") == "1":
        return False
    if os.environ.get("PT_BENCH_AMP") == "1":
        return False
    return os.environ.get("PT_BENCH_BF16", "1") == "1"


def _maybe_enable_bf16(main_prog, bf16):
    if bf16:
        from paddle_tpu.fluid.contrib import mixed_precision as mp

        mp.enable_bf16_policy(main_prog)


def measure_resnet(size):
    """ResNet-50 ImageNet images/sec/chip (BASELINE.md north-star #2).
    Selected with PT_BENCH_MODEL=resnet50; BERT stays the headline metric
    the driver records."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.models import resnet

    batch = int(os.environ.get("PT_BENCH_BATCH", "128"))
    n_steps = int(os.environ.get("PT_BENCH_STEPS", "10"))
    bf16 = _bf16_default()
    depth = 50 if size != "tiny" else 18
    image = (3, 224, 224) if size != "tiny" else (3, 64, 64)
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup), fluid.unique_name.guard():
        feeds, pred, loss, acc = resnet.build_resnet(
            depth=depth, class_dim=1000, image_shape=image)
        fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(
            loss)
    _maybe_enable_bf16(main_prog, bf16)  # BN stats stay fp32 islands
    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    data = {"img": rng.rand(batch, *image).astype("float32"),
            "label": rng.randint(0, 1000, (batch, 1)).astype("int64")}
    dt = _timed_steps(exe, main_prog, data, loss.name, n_steps)
    ips = n_steps * batch / dt
    config = (f"resnet{depth} b{batch} {image[1]}x{image[2]}"
              + (" bf16-policy" if bf16 else "") + _cpu_suffix())
    # fwd FLOPs/image: resnet50@224 ≈ 4.1e9, resnet18@224 ≈ 1.8e9 (public
    # figures), conv FLOPs scale with spatial area; train ≈ 3× fwd
    fwd = (4.1e9 if depth == 50 else 1.8e9) * (image[1] / 224.0) ** 2
    return _attach_flops({
        "metric": f"resnet{depth}_train_images_per_sec",
        "value": round(ips, 1),
        "unit": "images/sec/chip",
        **_vs_baseline_rec(ips, config, is_headline=size != "tiny"),
        "config": config,
    }, 3.0 * fwd * batch, n_steps, dt)


def measure_nmt(size):
    """Transformer NMT tokens/sec on VARIABLE-LENGTH batches
    (PT_BENCH_MODEL=nmt): BASELINE.md north-star #4, the dynamic-shape
    stress.  Ragged sentence lengths are bucketed (one XLA compile per
    bucket, reference-LoD semantics via label_weight masking), batches are
    token-budgeted (batch = tokens/bucket_len, the classic NMT recipe),
    and the metric counts EFFECTIVE (non-pad) target+source tokens — so
    padding waste shows up as a lower number, not a hidden flattery.
    MFU comes from XLA's own per-bucket flop counts (Executor.cost_analysis)
    rather than an analytic model."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.models import transformer as tfm

    tokens_budget = int(os.environ.get("PT_BENCH_TOKENS", "8192"))
    n_rounds = int(os.environ.get("PT_BENCH_STEPS", "3"))
    bf16 = _bf16_default()
    if size == "tiny":
        cfg = tfm.TransformerConfig.tiny()
        buckets = [16, 32]
        scale = "tiny"
    else:
        cfg = tfm.TransformerConfig.big()
        buckets = [32, 64, 128, 256]
        scale = "big"

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup), fluid.unique_name.guard():
        feeds, cost, acc = tfm.build_transformer_nmt(cfg)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
    _maybe_enable_bf16(main_prog, bf16)
    exe = fluid.Executor()
    exe.run(startup)

    rng = np.random.RandomState(0)

    def ragged_batch(bucket, lo):
        """Token-budget batch padded to `bucket`; true lengths are uniform
        in (lo, bucket], label_weight zeroes the padding.  Effective =
        non-pad source tokens + non-pad target tokens (the docstring's
        src+trg convention)."""
        batch = max(tokens_budget // bucket, 1)
        lens = rng.randint(lo + 1, bucket + 1, batch)
        data = tfm.make_fake_batch(cfg, batch=batch, src_len=bucket,
                                   trg_len=bucket - 1, seed=int(lens[0]))
        w = np.zeros_like(data["label_weight"])
        for i, ln in enumerate(lens):
            data["src_ids"][i, ln:] = 0  # pad_id
            w[i, :ln - 1] = 1.0
        data["label_weight"] = w
        effective = int(lens.sum()) + int(w.sum())
        return data, effective

    los = [0] + buckets[:-1]
    # one warmup step per bucket = one compile per bucket (the bucketing
    # contract: recompiles are bounded by the bucket list, not by the
    # number of distinct sentence lengths)
    schedule = []
    step_flops = 0.0
    for bucket, lo in zip(buckets, los):
        data, eff = ragged_batch(bucket, lo)
        exe.run(main_prog, feed=data, fetch_list=[cost.name])
        if os.environ.get("PT_BENCH_HOST_FEED") != "1":
            # device-resident like _timed_steps: the timed loop below
            # re-feeds these batches every round, and the ` devfeed`
            # config marker must describe what actually ran
            import jax

            data = jax.device_put(data)
        schedule.append((data, eff, bucket))
        if os.environ.get("PT_BENCH_SKIP_COST") == "1":
            # cost_analysis re-lowers AND re-compiles each bucket (an AOT
            # path beside the run cache), doubling the leg's 4
            # transformer-big compiles.  The knob trades the MFU
            # annotation for compile time; the tokens/sec metric is
            # unaffected.
            continue
        try:
            # XLA's own flop count for this bucket's executable — gathered
            # OUTSIDE the timed loop (lower() re-traces on every call)
            step_flops += float(
                exe.cost_analysis(main_prog, data, fetch_list=[cost.name])
                ["cost"].get("flops", 0.0))
        except Exception:
            pass  # cost model unavailable on this backend
    n_compiles = len(exe.compiled_for(main_prog))

    t0 = time.perf_counter()
    eff_tokens = pad_tokens = 0
    for _ in range(n_rounds):
        for data, eff, bucket in schedule:
            exe.run(main_prog, feed=data, fetch_list=[cost.name])
            eff_tokens += eff
            pad_tokens += data["src_ids"].size + data["labels"].size
    dt = time.perf_counter() - t0
    xla_flops = step_flops * n_rounds

    tps = eff_tokens / dt
    config = (f"transformer-{scale} nmt varlen buckets={buckets} "
              f"tok{tokens_budget}" + (" bf16-policy" if bf16 else "")
              + _cpu_suffix())
    rec = {
        "metric": f"transformer_{scale}_nmt_effective_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/sec/chip",
        **_vs_baseline_rec(tps, config, is_headline=False),
        "config": config,
        "padding_overhead": round(pad_tokens / max(eff_tokens, 1) - 1, 3),
        "bucket_compiles": n_compiles,
    }
    peak = _peak_tflops()
    if xla_flops and dt:
        rec["tflops_per_sec"] = round(xla_flops / dt / 1e12, 2)
        if peak:
            rec["mfu"] = round(xla_flops / dt / 1e12 / peak, 4)
            rec["peak_tflops"] = peak
    return rec


def measure_gpt_decode(size):
    """GPT autoregressive decode tokens/sec with the KV cache
    (PT_BENCH_MODEL=gpt): the latency-bound serving metric, complementing
    the throughput-bound training metrics."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.models import gpt

    batch = int(os.environ.get("PT_BENCH_BATCH", "16"))
    prompt_len = int(os.environ.get("PT_BENCH_PROMPT", "32"))
    gen_len = int(os.environ.get("PT_BENCH_GEN", "64"))
    maxp = prompt_len + gen_len + 8
    if size == "base":
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=768, num_heads=12,
                            num_layers=12, max_position=maxp)
    else:
        cfg = gpt.GPTConfig(vocab_size=1024, hidden_size=128, num_heads=4,
                            num_layers=2, intermediate_size=512,
                            max_position=maxp)
    # scan decode: ONE while-loop body compiled once — at g64 the unrolled
    # program takes ~26x longer to compile and ~1.5x longer per step (CPU
    # A/B; PT_BENCH_DECODE=unrolled reselects the old variant on chip)
    variant = os.environ.get("PT_BENCH_DECODE", "scan")
    if variant not in ("scan", "unrolled"):
        raise ValueError(
            f"PT_BENCH_DECODE={variant!r}: choose 'scan' or 'unrolled'")
    builder = (gpt.build_gpt_generate_scan if variant == "scan"
               else gpt.build_gpt_generate_cached)
    bf16 = _bf16_default()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup), fluid.unique_name.guard():
        prompt_var, out_var, _scores = builder(
            cfg, prompt_len=prompt_len, gen_len=gen_len)
    # decode is HBM-bound: bf16 weights + KV caches halve the traffic
    _maybe_enable_bf16(main_prog, bf16)
    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size,
                         (batch, prompt_len)).astype("int64")
    n_steps = int(os.environ.get("PT_BENCH_STEPS", "5"))
    dt = _timed_steps(exe, main_prog, {prompt_var.name: prompt},
                      out_var.name, n_steps)
    tps = n_steps * batch * gen_len / dt
    config = (f"gpt-{size} b{batch} p{prompt_len} g{gen_len} "
              f"kvcache-{variant}"
              + (" bf16-policy" if bf16 else "") + _cpu_suffix())
    return {
        "metric": f"gpt_{size}_decode_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/sec/chip",
        **_vs_baseline_rec(tps, config, is_headline=size == "base"),
        "config": config,
    }


def measure_serving(size):
    """Serving-lane load-generator rung (PT_BENCH_SERVE=1): drive a
    `paddle_tpu.serving.Engine` with closed-loop concurrent clients and
    record throughput + latency quantiles in the BENCH record beside the
    training tokens/sec rungs (ROADMAP "Production serving lane").

    Closed-loop: each client submits, waits for its result, submits
    again — so concurrency is exactly PT_BENCH_SERVE_CLIENTS and the
    continuous batcher's multi-request batch formation is what turns
    concurrency into device efficiency."""
    import threading

    import numpy as np

    from paddle_tpu import fluid, serving
    from paddle_tpu import observability as obs
    from paddle_tpu.fluid.executor import Scope, scope_guard

    n_clients = int(os.environ.get("PT_BENCH_SERVE_CLIENTS", "8"))
    n_requests = int(os.environ.get("PT_BENCH_SERVE_REQUESTS", "400"))
    timeout_ms = int(os.environ.get("PT_BENCH_SERVE_TIMEOUT_MS", "5"))
    feature, hidden, classes = ((256, 1024, 128) if size == "base"
                                else (32, 64, 8))
    import shutil
    import tempfile

    model_dir = tempfile.mkdtemp(prefix="pt_bench_serve_")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[feature], dtype="float32")
        h = fluid.layers.fc(x, size=hidden, act="relu")
        h = fluid.layers.fc(h, size=hidden, act="relu")
        pred = fluid.layers.fc(h, size=classes, act="softmax")
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)

    try:
        engine = serving.Engine({"bench": model_dir},
                                max_wait_ms=timeout_ms, auto_start=False)
    finally:
        # params are resident in the predictor's scope once loaded; the
        # on-disk export must not accumulate across bench runs
        shutil.rmtree(model_dir, ignore_errors=True)
    try:
        engine.warmup()
        engine.start()

        rng = np.random.RandomState(0)
        xb = rng.rand(1, feature).astype("float32")
        per_client = max(1, n_requests // n_clients)
        errors = []
        completed = [0] * n_clients

        def client(idx):
            try:
                for _ in range(per_client):
                    engine.infer("bench", {"x": xb}, tenant=f"client{idx}",
                                 timeout=60)
                    completed[idx] += 1
            except Exception as e:  # pragma: no cover - surfaced in record
                errors.append(repr(e))

        # prime the request path once (first traffic may still pay dispatch
        # warmth even though warmup() compiled every bucket)
        engine.infer("bench", {"x": xb}, timeout=60)
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        # throughput counts COMPLETED requests only: a client that died
        # mid-loop (overload, timeout) must not inflate the recorded number
        total = sum(completed)
        rps = total / dt

        snap = obs.snapshot()

        def hist(name):
            fam = snap.get(name)
            return (fam or {}).get("samples", {}).get(("bench",))

        lat = hist("pt_serve_request_latency_seconds")
        bs = hist("pt_serve_batch_size")
        cache = (snap.get("pt_serve_executable_cache_total") or
                 {}).get("samples", {})
        rec = {
            "metric": "serving_requests_per_sec",
            "value": round(rps, 1),
            "unit": "req/s",
            # the training-feed methodology markers (devfeed/pipelined) do
            # not apply to the serving rung
            "config": (f"serve mlp f{feature} h{hidden} clients{n_clients} "
                       f"reqs{total} timeout{timeout_ms}ms "
                       f"buckets={list(engine.policy.batch_buckets)}"),
            "latency_seconds": {
                "p50": _rq(obs.hist_quantile(lat, 0.50)) if lat else None,
                "p99": _rq(obs.hist_quantile(lat, 0.99)) if lat else None,
            },
            "mean_batch_size": (round(bs["sum"] / bs["count"], 2)
                                if bs and bs["count"] else None),
            "executable_cache": {",".join(k): int(v)
                                 for k, v in sorted(cache.items())},
            # per-request quantiles DERIVED FROM THE SPAN TREE (request-
            # scoped traces, docs/OBSERVABILITY.md "Request tracing") —
            # exact order statistics over individual requests, not the
            # bucket-interpolated aggregate histogram above
            "trace_quantiles": obs.reqtrace.request_quantiles(),
            "reqtrace_enabled": obs.reqtrace.enabled(),
            "client_errors": errors[:5],
        }
        rec.update(_vs_baseline_rec(rps, rec["config"],
                                    is_headline=False))
    finally:
        # close on EVERY path: a timed-out prime or a digest error must
        # not leak the scheduler thread and leave a dead engine on
        # /servez for the rest of the process
        engine.close()
    return rec


def _compile_misses():
    """Total executable-cache misses booked so far (every path) — the
    decode rung's steady-state gate is a DELTA of this going to zero."""
    from paddle_tpu import observability as obs

    fam = (obs.snapshot().get("pt_compile_cache_total") or {})
    return sum(int(v) for k, v in fam.get("samples", {}).items()
               if k[-1] == "miss")


def _decode_step_hist(engine_name):
    """(sum_seconds, count, samples) of pt_decode_step_seconds for one
    engine — per-token latency of the fixed-shape decode step."""
    from paddle_tpu import observability as obs

    fam = obs.snapshot().get("pt_decode_step_seconds") or {}
    h = fam.get("samples", {}).get((engine_name,))
    if not h:
        return 0.0, 0, None
    return float(h["sum"]), int(h["count"]), h


def measure_decode_lane(size):
    """Decode-lane load-generator rung (PT_BENCH_DECODE=1, `make
    decode-bench`): drive a `serving.DecodeEngine` (paged KV pool +
    token-level continuous batching) with MIXED prompt lengths and
    record the PT_BENCH_DECODE A/B the acceptance names:

      - tokens/s through the lane vs the NAIVE re-prefill-every-token
        baseline (one whole-prefix forward per generated token — what
        `generate()` traffic costs without the lane)
      - steady-state executable-cache misses across the timed window
        (must be 0: both lane executables are fixed-shape)
      - per-token decode latency p50/p99, plus a short-prompt vs
        long-prompt arm whose step-time ratio shows per-token latency
        independent of prompt length after prefill

    Closed over the SAME parameters for every arm (one scope), so the
    naive and lane arms run identical weights."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.fluid.executor import Scope, scope_guard
    from paddle_tpu.models import gpt

    n_requests = int(os.environ.get("PT_BENCH_DECODE_REQS", "12"))
    gen_len = int(os.environ.get("PT_BENCH_DECODE_GEN", "24"))
    slots = int(os.environ.get("PT_BENCH_DECODE_SLOTS",
                               "8" if size == "base" else "4"))
    if size == "base":
        page, max_len, prompt_mix = 32, 512, (16, 64, 128, 256)
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=768,
                            num_heads=12, num_layers=12,
                            max_position=max_len)
    else:
        page, max_len, prompt_mix = 16, 256, (8, 24, 48, 96)
        cfg = gpt.GPTConfig(vocab_size=1024, hidden_size=128, num_heads=4,
                            num_layers=2, intermediate_size=512,
                            max_position=max_len)

    scope = Scope()
    with scope_guard(scope):
        # declare + init the shared parameters once (the lane and the
        # naive arm run against the same scope — identical weights)
        lm_main, lm_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(lm_main, lm_start), \
                fluid.unique_name.guard():
            gpt.build_gpt_lm(cfg, is_test=True)
        exe = fluid.Executor()
        exe.run(lm_start)

        # naive arm program: ONE fixed-shape whole-prefix forward
        # ([1, max_len] padded — a single compile), run once per token
        nv_main, nv_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(nv_main, nv_start), \
                fluid.unique_name.guard():
            ids = fluid.data("nv_ids", [1, max_len], False, dtype="int64")
            pos = fluid.data("nv_pos", [1, max_len], False, dtype="int64")
            h = gpt.gpt_decoder(ids, pos, cfg, is_test=True)
            emb = nv_main.global_block().var("gpt_word_embedding")
            flat = fluid.layers.reshape(h, shape=[-1, cfg.hidden_size])
            nv_logits = fluid.layers.matmul(flat, emb, transpose_y=True)

        from paddle_tpu import serving

        engine = serving.DecodeEngine(cfg, scope=scope, pool_slots=slots,
                                      page_size=page, max_len=max_len,
                                      name="bench", auto_start=False)
        try:
            engine.warmup()
            engine.start()

            rng = np.random.RandomState(0)
            prompts = [rng.randint(1, cfg.vocab_size, plen).tolist()
                       for i in range(n_requests)
                       for plen in (prompt_mix[i % len(prompt_mix)],)]

            # naive baseline: greedy-extend a few sequences, one
            # whole-prefix forward per token (the re-prefill cost the
            # lane exists to delete) — measured over enough tokens to
            # average dispatch noise, extrapolated as tokens/s
            naive_tokens = 0
            pos_row = np.minimum(np.arange(max_len, dtype=np.int64),
                                 cfg.max_position - 1)[None, :]
            # warm the naive executable OUTSIDE the timed window (the
            # lane arm is primed below; the "after both warm"
            # methodology every A/B rung here uses) — the [1, max_len]
            # shape is the only one the arm dispatches, so one run
            # covers it
            warm_buf = np.zeros((1, max_len), np.int64)
            warm_buf[0, :len(prompts[0])] = prompts[0]
            exe.run(nv_main, feed={"nv_ids": warm_buf,
                                   "nv_pos": pos_row},
                    fetch_list=[nv_logits.name], scope=scope)
            t0 = time.perf_counter()
            for seq in (list(prompts[0]), list(prompts[1])):
                for _ in range(min(gen_len, 8)):
                    buf = np.zeros((1, max_len), np.int64)
                    buf[0, :len(seq)] = seq
                    (lg,) = exe.run(nv_main,
                                    feed={"nv_ids": buf,
                                          "nv_pos": pos_row},
                                    fetch_list=[nv_logits.name],
                                    scope=scope)
                    seq.append(int(np.argmax(
                        np.asarray(lg)[len(seq) - 1])))
                    naive_tokens += 1
            naive_tps = naive_tokens / (time.perf_counter() - t0)

            # prime the lane once, then the steady-state window: misses
            # across the timed load-gen MUST stay flat (both lane
            # executables are fixed-shape — zero recompiles)
            engine.generate([prompts[0]], max_new_tokens=2, timeout=300)
            misses_before = _compile_misses()
            s0, c0, _ = _decode_step_hist("bench")
            t0 = time.perf_counter()
            outs = engine.generate(prompts, max_new_tokens=gen_len,
                                   timeout=1200)
            dt = time.perf_counter() - t0
            steady_compiles = _compile_misses() - misses_before
            lane_tokens = sum(len(o) for o in outs)
            tps = lane_tokens / dt

            # prompt-length independence: one live request per arm, the
            # mean decode-step time must not grow with the prompt
            arms = {}
            for arm, plen in (("short", prompt_mix[0]),
                              ("long", max_len - 20)):
                p = rng.randint(1, cfg.vocab_size, plen).tolist()
                s1, c1, _ = _decode_step_hist("bench")
                engine.generate([p], max_new_tokens=16, timeout=600)
                s2, c2, _ = _decode_step_hist("bench")
                arms[arm] = {
                    "prompt_len": plen,
                    "step_ms": _rq((s2 - s1) / max(c2 - c1, 1) * 1e3),
                }
            ratio = (arms["long"]["step_ms"] / arms["short"]["step_ms"]
                     if arms["short"]["step_ms"] else None)

            _, _, hist = _decode_step_hist("bench")
            stats = engine.stats()
        finally:
            engine.close()

    config = (f"decode gpt-{size} slots{slots} page{page} "
              f"maxlen{max_len} reqs{n_requests} gen{gen_len} "
              f"prompts{list(prompt_mix)}" + _cpu_suffix())
    rec = {
        "metric": "decode_lane_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "config": config,
        **_vs_baseline_rec(tps, config, is_headline=False),
        "decode": {
            "tokens_per_sec": round(tps, 1),
            "naive_tokens_per_sec": round(naive_tps, 1),
            "speedup_vs_naive": (round(tps / naive_tps, 2)
                                 if naive_tps else None),
            "steady_state_compiles": int(steady_compiles),
            "latency_seconds": {
                "p50": _rq(obs.hist_quantile(hist, 0.50))
                if hist else None,
                "p99": _rq(obs.hist_quantile(hist, 0.99))
                if hist else None,
            },
            "prompt_len_independence": {**arms,
                                        "long_over_short": _rq(ratio)},
            "tokens": lane_tokens,
            "requests": n_requests,
            "evictions": stats["evictions"],
            "kv_pool": stats["kv_pool"],
        },
    }
    return rec


def measure_ragged_serving(size):
    """Ragged-serving A/B rung (PT_BENCH_RAGGED=1, `make ragged-bench`):
    the SAME ragged-attention model served two ways under identical
    mixed-length traffic — bucketed-padded (every request padded to its
    sequence bucket, one shape key per bucket) vs ragged (every request
    padded to ONE length, attention masked by the per-row lengths feed;
    docs/KERNELS.md "Ragged attention").  Records per arm:

      - real tokens/s through the lane (sum of UNPADDED lengths / wall)
      - pt_serve_rows_total{kind=padding} delta — the padding rows the
        batch former minted (ragged mixed-length waves batch together,
        so full waves stop paying padding rows entirely)
      - warmup executable count (ragged: one per batch bucket; bucketed:
        the seq-bucket cross product) and steady-state cold compiles

    plus the modeled KV-pool HBM bytes fp32 vs dual-int8 for the
    decode-lane config (serving/kv_pool.py modeled_bytes) — the
    denominator/numerator pair behind pt_int8_bytes_saved_total."""
    import shutil
    import tempfile

    import numpy as np

    from paddle_tpu import fluid, serving
    from paddle_tpu.fluid import layers as L
    from paddle_tpu.fluid.executor import Scope, scope_guard

    n_waves = int(os.environ.get("PT_BENCH_RAGGED_WAVES", "10"))
    if size == "base":
        vocab, hidden, heads, n_layers = 8192, 256, 8, 4
        seq_buckets, wave_lens = (32, 64, 128), (20, 50, 90, 126)
    else:
        # heads chosen so head_dim = 32: the per-vector scale overhead
        # amortizes (2n + 4n/32 vs 4n ≈ halving) and int8 meets the TPU
        # (32, 128) min-tile row constraint when this runs on chip
        vocab, hidden, heads, n_layers = 128, 64, 2, 2
        seq_buckets, wave_lens = (8, 16, 32), (5, 12, 20, 30)
    head_dim = hidden // heads
    batch_bucket = 2 * len(wave_lens)  # one full mixed wave

    # one model, one export: ids [-1, -1] + per-row lengths [-1]; the
    # ragged_attention layer masks the padded tail itself, so BOTH arms
    # compute identical real-token math — the A/B isolates the batching
    model_dir = tempfile.mkdtemp(prefix="pt_bench_ragged_")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.data("ids", [-1, -1], False, dtype="int64")
        lens = fluid.data("lens", [-1], False, dtype="int32")
        x = L.embedding(ids, size=[vocab, hidden])
        for _ in range(n_layers):
            qkv = [L.reshape(L.fc(x, size=hidden, num_flatten_dims=2),
                             shape=[0, 0, heads, head_dim])
                   for _ in range(3)]
            q, k, v = [L.transpose(t, perm=[0, 2, 1, 3]) for t in qkv]
            ctx = L.ragged_attention(q, k, v, lens, causal=True)
            ctx = L.reshape(L.transpose(ctx, perm=[0, 2, 1, 3]),
                            shape=[0, 0, hidden])
            x = L.elementwise_add(x, L.fc(ctx, size=hidden,
                                          num_flatten_dims=2))
        score = L.reduce_mean(x, dim=[1, 2])
        score = L.reshape(score, shape=[-1, 1])
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["ids", "lens"], [score],
                                      exe, main_program=main)

    rng = np.random.RandomState(0)

    def run_arm(ragged):
        from paddle_tpu import observability as obs

        name = "ragged" if ragged else "bucketed"
        eng = serving.Engine(batch_buckets=[batch_bucket],
                             seq_buckets=list(seq_buckets),
                             max_wait_ms=5, auto_start=False,
                             name=f"bench_{name}")
        try:
            eng.load_model(name, model_dir, ragged=ragged)
            warmed = eng.warmup()[name]
            eng.start()
            lane = eng._lanes[name]

            def rows(kind):
                fam = obs.REGISTRY.get("pt_serve_rows_total")
                samples = fam._snapshot()["samples"] if fam else {}
                return samples.get((name, kind), 0.0)

            def one_wave():
                futs = []
                for ln in wave_lens:
                    for _ in range(2):
                        feed = {"ids": rng.randint(
                                    1, vocab, (1, ln)).astype(np.int64),
                                "lens": np.full((1,), ln, np.int32)}
                        futs.append(eng.submit(name, feed))
                for f in futs:
                    f.result(timeout=300)

            one_wave()  # prime outside the timed window
            pad0, real0 = rows("padding"), rows("real")
            cold0 = lane._cache_counts["cold"]
            t0 = time.perf_counter()
            for _ in range(n_waves):
                one_wave()
            dt = time.perf_counter() - t0
            real_tokens = n_waves * 2 * sum(wave_lens)
            return {
                "tokens_per_sec": round(real_tokens / dt, 1),
                "real_rows": int(rows("real") - real0),
                "padding_rows": int(rows("padding") - pad0),
                "warmed_executables": int(warmed),
                "steady_state_cold": int(lane._cache_counts["cold"]
                                         - cold0),
            }
        finally:
            eng.close()

    try:
        arms = {"bucketed": run_arm(False), "ragged": run_arm(True)}
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)

    # modeled KV-pool HBM: the same decode-lane pool at fp32 vs dual-int8
    # (pure accounting — no device memory moves here)
    from paddle_tpu.serving.kv_pool import KVPool
    from paddle_tpu.serving.lane import kv_rows

    num_pages, page_size = 65, 16
    pools = {
        dt: KVPool(n_layers, kv_rows(heads, head_dim, dt), num_pages,
                   page_size, max_pages_per_seq=16)
        for dt in ("float32", "int8")
    }
    kv_bytes = {
        "fp32_bytes": pools["float32"].modeled_bytes(),
        "int8_bytes": pools["int8"].modeled_bytes(),
    }
    kv_bytes["int8_over_fp32"] = round(
        kv_bytes["int8_bytes"] / kv_bytes["fp32_bytes"], 4)

    tps = arms["ragged"]["tokens_per_sec"]
    config = (f"ragged-serving gpt-{size} h{hidden} n{heads} "
              f"L{n_layers} seqbuckets{list(seq_buckets)} "
              f"wave{wave_lens} waves{n_waves}" + _cpu_suffix())
    return {
        "metric": "ragged_serving_tokens_per_sec",
        "value": tps,
        "unit": "tokens/sec",
        "config": config,
        **_vs_baseline_rec(tps, config, is_headline=False),
        "ragged_serving": {
            **arms,
            "ragged_over_bucketed": (
                round(arms["ragged"]["tokens_per_sec"]
                      / arms["bucketed"]["tokens_per_sec"], 3)
                if arms["bucketed"]["tokens_per_sec"] else None),
            "kv_pool_modeled": kv_bytes,
        },
    }


def _hop_latency_bench(reps=10, payloads_kb=(16, 64, 256, 1024, 4096)):
    """PT_BENCH_QUANTAR hop-latency sub-rung: time the oneshot vs ring
    quantized all-reduce across payload sizes on the live mesh and derive
    the per-hop latency (ring wall / 2*(n-1) sequential hops) and the
    measured ring/oneshot crossover payload — the number that replaces
    the FLAGS_quant_allreduce_crossover_kb guess (the flag stays as the
    override).  Returns None on a single-device mesh."""
    import time as _time

    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.kernels import quantized_collectives as qc
    from paddle_tpu.kernels import ring_collectives as rc
    from paddle_tpu.parallel import mesh as pmesh

    n = jax.device_count()
    if n < 2:
        return None
    mesh = pmesh.build_mesh({pmesh.DATA_AXIS: n})
    axis = pmesh.DATA_AXIS
    res = {"n_devices": n, "reps": reps, "payloads_kb": list(payloads_kb),
           "oneshot_ms": [], "ring_ms": [], "ring_per_hop_ms": []}
    rng = np.random.RandomState(0)
    for kb in payloads_kb:
        elems = max(1024, kb * 1024 // 4)
        data = rng.randn(n, elems).astype("float32")
        row = {}
        for algo, fn in (("oneshot", qc.quantized_all_reduce),
                         ("ring", rc.ring_quantized_all_reduce)):
            f = jax.jit(jax.shard_map(
                lambda v, fn=fn: fn(v, axis), mesh=mesh, in_specs=P(axis),
                out_specs=P(axis), check_vma=False))
            jax.block_until_ready(f(data))  # compile + warm
            t0 = _time.perf_counter()
            for _ in range(reps):
                out = f(data)
            jax.block_until_ready(out)
            row[algo] = (_time.perf_counter() - t0) / reps * 1e3
        res["oneshot_ms"].append(round(row["oneshot"], 4))
        res["ring_ms"].append(round(row["ring"], 4))
        res["ring_per_hop_ms"].append(round(row["ring"] / (2 * (n - 1)), 4))
    # measured crossover: smallest swept payload where the ring wins
    # (None = oneshot won everywhere in the sweep)
    res["measured_crossover_kb"] = next(
        (kb for kb, o, r in zip(payloads_kb, res["oneshot_ms"],
                                res["ring_ms"]) if r <= o), None)
    return res


def _overlap_step_quantiles(size, batch, seq_len, n_steps, bf16):
    """PT_BENCH_OVERLAP=1 A/B rung: the quantized DP step with
    ready-order bucket dispatch (FLAGS_overlap_allreduce) ON vs OFF,
    per-step wall times fetched synchronously each step, p50/p95/max
    quantiles per arm.  Fresh program per arm — the transpile itself
    differs (that IS the A/B)."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.models import bert

    kw = dict(vocab_size=30528, attn_dropout=0.1)
    cfg = (bert.BertConfig.base(**kw) if size == "base"
           else bert.BertConfig.tiny(**kw))
    prior = fluid.get_flags("FLAGS_overlap_allreduce")[
        "FLAGS_overlap_allreduce"]
    out = {"methodology": "syncfetch per-step", "steps": n_steps}
    for arm, flag in (("on", True), ("off", False)):
        fluid.set_flags({"FLAGS_overlap_allreduce": flag})
        try:
            main_prog, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main_prog, startup), \
                    fluid.unique_name.guard():
                feeds, loss, _mlm, _nsp = bert.build_bert_pretrain(
                    cfg, is_test=False)
                fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
            _maybe_enable_bf16(main_prog, bf16)
            bs = fluid.compiler.BuildStrategy()
            bs.quant_allreduce = True
            data = bert.make_fake_batch(cfg, batch=batch, seq_len=seq_len,
                                        seed=0)
            times = []
            with fluid.scope_guard(fluid.Scope()):
                exe = fluid.Executor()
                exe.run(startup)
                prog = fluid.CompiledProgram(
                    main_prog, build_strategy=bs).with_data_parallel(
                        loss_name=loss.name)
                exe.run(prog, feed=data, fetch_list=[loss.name])  # warm
                for _ in range(n_steps):
                    t0 = time.perf_counter()
                    exe.run(prog, feed=data, fetch_list=[loss.name])
                    times.append(time.perf_counter() - t0)
            sched = getattr(main_prog, "_overlap_schedule", None) or {}
            out[arm] = {
                "p50_s": round(float(np.percentile(times, 50)), 6),
                "p95_s": round(float(np.percentile(times, 95)), 6),
                "max_s": round(float(np.max(times)), 6),
                "buckets": [
                    {k: b[k] for k in ("insert_at", "ready_frac", "algo")}
                    for b in sched.get("buckets", [])],
            }
        finally:
            # restore the CALLER'S value — a pinned overlap-off bench
            # must not silently flip back on for later rungs
            fluid.set_flags({"FLAGS_overlap_allreduce": prior})
    return out


def _health_ab(size, batch, seq_len, n_steps, bf16):
    """PT_BENCH_HEALTH=1 A/B rung: the DP step with the training health
    sentinel (FLAGS_health_sentinel, action=skip — the in-graph finite
    check + state gate + the host-side scalar read) ON vs OFF, per-step
    wall quantiles per arm and the p50 overhead fraction.  Fresh program
    per arm — the sentinel transpile itself is the A/B.  The acceptance
    bar (ISSUE 10): overhead <= 2% p50 on the CPU smoke."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import DataParallelRunner

    kw = dict(vocab_size=30528, attn_dropout=0.1)
    cfg = (bert.BertConfig.base(**kw) if size == "base"
           else bert.BertConfig.tiny(**kw))
    prior = fluid.get_flags(["FLAGS_health_sentinel",
                             "FLAGS_health_action"])
    out = {"methodology": "syncfetch per-step, arms interleaved",
           "steps": n_steps, "action": "skip"}
    data = bert.make_fake_batch(cfg, batch=batch, seq_len=seq_len,
                                seed=0)
    arms = {}
    try:
        # build + fully warm BOTH arms first, then interleave the timed
        # steps round-robin: a sequential A-then-B run measures compile
        # cache / page-cache warmth and allocator state as "overhead"
        # (observed 10x run-to-run swings on the 2-vCPU container) --
        # exactly the bias a <=2% gate cannot survive
        for arm, enabled in (("off", False), ("on", True)):
            fluid.set_flags({"FLAGS_health_sentinel": enabled,
                             "FLAGS_health_action": "skip"})
            main_prog, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main_prog, startup), \
                    fluid.unique_name.guard():
                feeds, loss, _mlm, _nsp = bert.build_bert_pretrain(
                    cfg, is_test=False)
                fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
            _maybe_enable_bf16(main_prog, bf16)
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor()
                exe.run(startup)
                runner = DataParallelRunner(main_prog, loss.name,
                                            quant_grads=True)
                runner.run(exe, data, [loss.name], scope)  # warm
                runner.run(exe, data, [loss.name], scope)
            arms[arm] = (runner, exe, scope, loss, [])
        for _ in range(n_steps):
            for arm, (runner, exe, scope, loss, times) in arms.items():
                with fluid.scope_guard(scope):
                    t0 = time.perf_counter()
                    runner.run(exe, data, [loss.name], scope)
                    times.append(time.perf_counter() - t0)
        for arm, (_r, _e, _s, _l, times) in arms.items():
            out[arm] = {
                "p50_s": round(float(np.percentile(times, 50)), 6),
                "p95_s": round(float(np.percentile(times, 95)), 6),
                "max_s": round(float(np.max(times)), 6),
            }
        if out["off"]["p50_s"] > 0:
            out["overhead_p50_pct"] = round(
                100.0 * (out["on"]["p50_s"] - out["off"]["p50_s"])
                / out["off"]["p50_s"], 2)
    finally:
        fluid.set_flags(prior)
    return out


def _passes_ab(size, batch, seq_len, n_steps, bf16):
    """PT_BENCH_PASSES=1 A/B rung: the SAME bert step (built UNFUSED —
    use_flash_attention=False, attn_dropout=0, so the attention pattern
    is actually on the table) with the graph-optimization pass layer
    (FLAGS_graph_passes=default) ON vs OFF, arms interleaved round-robin
    after both warm (the PT_BENCH_HEALTH precedent: sequential arms
    measure cache warmth as fake deltas on the 2-vCPU container).  The
    record carries per-arm step quantiles, the on-arm's pass report
    (sites, op deltas), and the measured per-pass cost_analysis
    attribution (flops / bytes_accessed deltas per pipeline prefix) —
    the pt_pass_bytes_saved_total surface, embedded."""
    import numpy as np

    from paddle_tpu import fluid, passes
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import DataParallelRunner

    kw = dict(vocab_size=30528, attn_dropout=0.0, hidden_dropout=0.0,
              use_flash_attention=False)
    cfg = (bert.BertConfig.base(**kw) if size == "base"
           else bert.BertConfig.tiny(**kw))
    prior = fluid.get_flags("FLAGS_graph_passes")["FLAGS_graph_passes"]
    out = {"methodology": "syncfetch per-step, arms interleaved",
           "steps": n_steps}
    data = bert.make_fake_batch(cfg, batch=batch, seq_len=seq_len,
                                seed=0)
    arms = {}

    def build():
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup), \
                fluid.unique_name.guard():
            feeds, loss, _mlm, _nsp = bert.build_bert_pretrain(
                cfg, is_test=False)
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
        return main_prog, startup, loss

    try:
        for arm, spec in (("off", "none"), ("on", "default")):
            fluid.set_flags({"FLAGS_graph_passes": spec})
            main_prog, startup, loss = build()
            _maybe_enable_bf16(main_prog, bf16)
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor()
                exe.run(startup)
                runner = DataParallelRunner(main_prog, loss.name)
                runner.run(exe, data, [loss.name], scope)  # warm
                runner.run(exe, data, [loss.name], scope)
            arms[arm] = (runner, exe, scope, loss, [])
            if arm == "on":
                rep = getattr(main_prog, "_pass_report", None)
                if rep:
                    out["pass_report"] = [
                        {k: v for k, v in e.items()}
                        for e in rep if e.get("changed")]
        for _ in range(n_steps):
            for arm, (runner, exe, scope, loss, times) in arms.items():
                with fluid.scope_guard(scope):
                    t0 = time.perf_counter()
                    runner.run(exe, data, [loss.name], scope)
                    times.append(time.perf_counter() - t0)
        for arm, (_r, _e, _s, _l, times) in arms.items():
            out[arm] = {
                "p50_s": round(float(np.percentile(times, 50)), 6),
                "p95_s": round(float(np.percentile(times, 95)), 6),
                "max_s": round(float(np.max(times)), 6),
            }
        if out["off"]["p50_s"] > 0:
            out["speedup_p50_pct"] = round(
                100.0 * (out["off"]["p50_s"] - out["on"]["p50_s"])
                / out["off"]["p50_s"], 2)
        # measured per-pass attribution on the single-device lane (the
        # CPU-measurable cost_analysis deltas)
        fluid.set_flags({"FLAGS_graph_passes": "default"})
        try:
            import jax

            loss_name = arms["on"][3].name
            # off-TPU the flash op falls back to the XLA reference —
            # force the interpret-mode kernel so the cost model sees the
            # kernel boundary (the S×S tensor's absence), like on-chip
            force = jax.default_backend() != "tpu"
            prior_force = os.environ.get("PT_FLASH_FORCE_PALLAS")
            if force:
                os.environ["PT_FLASH_FORCE_PALLAS"] = "1"
            try:
                out["per_pass_cost"] = passes.attribute_costs(
                    build, data, fetch_list=[loss_name], spec="default")
            finally:
                if force:
                    if prior_force is None:
                        os.environ.pop("PT_FLASH_FORCE_PALLAS", None)
                    else:
                        os.environ["PT_FLASH_FORCE_PALLAS"] = prior_force
            out["per_pass_cost"].pop("final_hlo", None)
        except Exception as e:
            out["per_pass_cost_error"] = str(e)
        # fuse_softmax_cross_entropy row (ISSUE 15 satellite): the bert
        # pretrain head already spells softmax_with_cross_entropy, so
        # the pass's sites live on the composed classifier/MLM-head
        # spelling — probe it on that spelling so the rung carries a
        # measured attribution for this pass too
        try:
            def build_sce():
                main_p, startup_p = fluid.Program(), fluid.Program()
                with fluid.program_guard(main_p, startup_p), \
                        fluid.unique_name.guard():
                    import numpy as _np

                    _np.random.seed(5)
                    xs = fluid.data("x", [64, 64], False,
                                    dtype="float32")
                    ys = fluid.data("y", [64, 1], False, dtype="int64")
                    h = fluid.layers.fc(xs, size=256, act="relu")
                    probs = fluid.layers.softmax(
                        fluid.layers.fc(h, size=512))
                    loss_p = fluid.layers.mean(
                        fluid.layers.cross_entropy(probs, ys))
                    fluid.optimizer.SGD(0.1).minimize(loss_p)
                return main_p, startup_p, loss_p

            import numpy as _np

            rng = _np.random.RandomState(0)
            sce_data = {"x": rng.randn(64, 64).astype("float32"),
                        "y": rng.randint(0, 512, (64, 1))
                        .astype("int64")}
            _m, _s, sce_loss = build_sce()
            out["sce_probe"] = passes.attribute_costs(
                build_sce, sce_data, fetch_list=[sce_loss.name],
                spec="fuse_softmax_cross_entropy")
        except Exception as e:
            out["sce_probe_error"] = str(e)
    finally:
        fluid.set_flags({"FLAGS_graph_passes": prior})
    return out


def _phase_overhead_ab(size, batch, seq_len, n_steps, bf16):
    """PT_BENCH_PHASES=1 A/B rung: the DP step with phase-decomposed
    step timing (FLAGS_profile_phases — the four step_phases brackets
    plus the per-step block_until_ready the device_wait phase needs) ON
    vs OFF, arms interleaved round-robin after both warm (the
    PT_BENCH_HEALTH precedent: sequential arms measure cache warmth as
    fake overhead on the 2-vCPU container).  The acceptance bar
    (ISSUE 11): overhead within noise (<=2% p50) on the CPU smoke —
    phase attribution must be cheap enough to leave on for any
    syncfetch-methodology run."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import DataParallelRunner

    kw = dict(vocab_size=30528, attn_dropout=0.1)
    cfg = (bert.BertConfig.base(**kw) if size == "base"
           else bert.BertConfig.tiny(**kw))
    prior = fluid.get_flags("FLAGS_profile_phases")["FLAGS_profile_phases"]
    out = {"methodology": "syncfetch per-step, arms interleaved",
           "steps": n_steps}
    data = bert.make_fake_batch(cfg, batch=batch, seq_len=seq_len,
                                seed=0)
    arms = {}
    try:
        for arm, enabled in (("off", False), ("on", True)):
            fluid.set_flags({"FLAGS_profile_phases": enabled})
            main_prog, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main_prog, startup), \
                    fluid.unique_name.guard():
                feeds, loss, _mlm, _nsp = bert.build_bert_pretrain(
                    cfg, is_test=False)
                fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
            _maybe_enable_bf16(main_prog, bf16)
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor()
                exe.run(startup)
                runner = DataParallelRunner(main_prog, loss.name)
                runner.run(exe, data, [loss.name], scope)  # warm
                runner.run(exe, data, [loss.name], scope)
            arms[arm] = (runner, exe, scope, loss, [], enabled)
        for _ in range(n_steps):
            for arm, (runner, exe, scope, loss, times,
                      enabled) in arms.items():
                fluid.set_flags({"FLAGS_profile_phases": enabled})
                with fluid.scope_guard(scope):
                    t0 = time.perf_counter()
                    runner.run(exe, data, [loss.name], scope)
                    times.append(time.perf_counter() - t0)
        for arm, (_r, _e, _s, _l, times, _en) in arms.items():
            out[arm] = {
                "p50_s": round(float(np.percentile(times, 50)), 6),
                "p95_s": round(float(np.percentile(times, 95)), 6),
                "max_s": round(float(np.max(times)), 6),
            }
        if out["off"]["p50_s"] > 0:
            out["overhead_p50_pct"] = round(
                100.0 * (out["on"]["p50_s"] - out["off"]["p50_s"])
                / out["off"]["p50_s"], 2)
        # the on-arm's measured phase decomposition rides along: the A/B
        # proves the cost, this proves the benefit (p50 per phase)
        from paddle_tpu import observability as obs

        out["phase_seconds"] = obs.profiling.attribution_digest()[
            "phase_seconds"].get("dp", {})
    finally:
        fluid.set_flags({"FLAGS_profile_phases": prior})
    return out


def _pipeline_ab(n_steps):
    """PT_BENCH_PIPELINE=1 A/B rung (ISSUE 15): the SAME pipelined
    program through the host-scheduled PipelineRunner (one dispatch per
    stage/microbatch/phase, activations through numpy) vs the gspmd
    PipelinePolicy (the whole GPipe/1F1B schedule in ONE jit-partitioned
    step), gpipe vs 1f1b, swept over microbatch counts.  Per arm/M:
    step-wall quantiles; per policy arm: the modeled per-boundary wire
    bytes and bubble fraction from the compiled schedule report, plus a
    MEASURED bubble fraction backed out of the microbatch sweep (the
    per-tick time is the slope of p50 vs tick count across the two
    largest Ms; bubble = 1 - compute_ticks*t_tick/p50).

    Small-net 2-stage pipeline on a pp2 CPU mesh: the rung measures the
    DISPATCH/SCHEDULE delta, which is exactly what the host-scheduled
    lane loses (S*M*3 Python dispatches per step vs 1)."""
    import jax
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.fluid.executor import Scope, scope_guard
    from paddle_tpu.parallel import PipelineRunner
    from paddle_tpu.parallel import mesh as pmesh
    from paddle_tpu.parallel.gspmd import GSPMDExecutor, PipelinePolicy
    from paddle_tpu.parallel.gspmd.pipeline_policy import schedule_ticks

    SWEEP = (1, 2, 4, 8)
    BATCH = 64
    S = 2
    if jax.device_count() < S:
        # belt-and-braces beside measure()'s XLA_FLAGS injection: jax
        # may already be initialized single-device by an earlier import
        return {"skipped": f"needs >= {S} devices, have "
                f"{jax.device_count()} — set "
                "--xla_force_host_platform_device_count"}

    def build(microbatches):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            np.random.seed(2)
            x = fluid.layers.data(name="x", shape=[64], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h1 = fluid.layers.fc(x, size=128, act="relu")
            h2 = fluid.layers.fc(h1, size=128, act="relu")
            pred = fluid.layers.fc(h2, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.PipelineOptimizer(
                fluid.optimizer.SGD(learning_rate=0.01),
                cut_list=[[h1]],
                num_microbatches=microbatches).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    data = {"x": rng.uniform(-1, 1, (BATCH, 64)).astype("float32"),
            "y": rng.uniform(-1, 1, (BATCH, 1)).astype("float32")}

    def init_scope(startup):
        s = Scope()
        with scope_guard(s):
            fluid.Executor(fluid.CPUPlace()).run(startup)
        return s

    def quantiles(times):
        return {"p50_s": round(float(np.percentile(times, 50)), 6),
                "p95_s": round(float(np.percentile(times, 95)), 6),
                "max_s": round(float(np.max(times)), 6)}

    out = {"methodology": "syncfetch per-step", "steps": n_steps,
           "batch": BATCH, "n_stages": S, "microbatch_sweep": list(SWEEP),
           "arms": {}}
    reports = {}
    for arm in ("runner", "gpipe", "1f1b"):
        out["arms"][arm] = {}
        for m in SWEEP:
            main, startup, loss = build(m)
            sc = init_scope(startup)
            if arm == "runner":
                with scope_guard(sc):
                    ex = PipelineRunner(main)
                    run = lambda: ex.run(feed=data,  # noqa: E731
                                         fetch_list=[loss.name])
            else:
                ex = GSPMDExecutor(
                    main, pmesh.build_3d_mesh(pp=S, batch=1),
                    PipelinePolicy(schedule=arm), scope=sc)
                run = lambda: ex.run(feed=data,  # noqa: E731
                                     fetch_list=[loss.name])
            with scope_guard(sc):
                run()  # warm/compile
                times = []
                for _ in range(n_steps):
                    t0 = time.perf_counter()
                    run()
                    times.append(time.perf_counter() - t0)
            out["arms"][arm][f"m{m}"] = quantiles(times)
            if arm != "runner":
                reports.setdefault(arm, {})[m] = main._pipeline_schedule
    # schedule reports: modeled bubble + per-boundary bytes (identical
    # across Ms except the M-dependent fields — keep the largest-M one
    # plus the per-M bubble table)
    for arm, by_m in reports.items():
        rep = by_m[max(by_m)]
        out["arms"][arm]["schedule_report"] = {
            "ticks": rep["ticks"],
            "bubble_frac_modeled": rep["bubble_frac"],
            "bubble_frac_per_microbatches":
                rep["bubble_frac_per_microbatches"],
            "stash_depth": rep["stash_depth"],
            "boundary_bytes_per_step":
                [b["bytes_per_step"] for b in rep["boundaries"]],
        }
        # measured bubble: t_tick from the sweep's two largest Ms
        m_hi, m_lo = sorted(by_m)[-1], sorted(by_m)[-2]
        p_hi = out["arms"][arm][f"m{m_hi}"]["p50_s"]
        p_lo = out["arms"][arm][f"m{m_lo}"]["p50_s"]
        ticks = {m: schedule_ticks(S, m) for m in (m_hi, m_lo)}
        if p_hi > p_lo and ticks[m_hi] > ticks[m_lo]:
            t_tick = (p_hi - p_lo) / (ticks[m_hi] - ticks[m_lo])
            out["arms"][arm]["bubble_frac_measured"] = {
                f"m{m}": round(
                    max(0.0, 1.0 - (2 * m * t_tick)
                        / out["arms"][arm][f"m{m}"]["p50_s"]), 4)
                for m in by_m}
    # the acceptance's verdict field: 1f1b vs gpipe at M >= 4, with the
    # design note when the wall clocks tie (both schedules lower to the
    # SAME 2*(M+S-1) slot count — 1f1b's win is the min(M,S) activation
    # stash, i.e. memory, not ticks; a wall-clock win here would come
    # from locality only)
    cmp_ms = [m for m in SWEEP if m >= 4]
    wins = {f"m{m}": out["arms"]["1f1b"][f"m{m}"]["p50_s"]
            < out["arms"]["gpipe"][f"m{m}"]["p50_s"] for m in cmp_ms}
    out["f1b_beats_gpipe_at_4plus"] = all(wins.values())
    out["f1b_vs_gpipe_note"] = (
        "both schedules lower to the same 2*(M+S-1) slot count in the "
        "lockstep single-program spelling; 1f1b's structural win is the "
        "min(M,S)-deep activation stash (memory) — wall-clock deltas on "
        "this rung are locality noise" if not all(wins.values()) else
        "1f1b p50 under gpipe at every M>=4 on this rung")
    out["f1b_gpipe_p50_ratio"] = {
        f"m{m}": round(out["arms"]["1f1b"][f"m{m}"]["p50_s"]
                       / max(out["arms"]["gpipe"][f"m{m}"]["p50_s"],
                             1e-12), 4)
        for m in cmp_ms}
    return out


def _gspmd_ab(size, batch, seq_len, n_steps, bf16):
    """PT_BENCH_GSPMD=1 A/B rung: the SAME bert step through the
    transpiler DP lane (explicit c_allreduce ops + shard_map) vs the
    GSPMD executor lane (sharding policy + XLA-inserted collectives,
    parallel/gspmd/), per-step wall quantiles per arm.  The gspmd arm
    additionally records what the partitioner chose: collective
    instruction counts and per-step resharding bytes from compiled-HLO
    inspection (the pt_gspmd_resharding_bytes surface)."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import DataParallelRunner
    from paddle_tpu.parallel.gspmd import (hlo_collective_bytes,
                                           hlo_collective_counts)

    kw = dict(vocab_size=30528, attn_dropout=0.1)
    cfg = (bert.BertConfig.base(**kw) if size == "base"
           else bert.BertConfig.tiny(**kw))
    out = {"methodology": "syncfetch per-step", "steps": n_steps}
    for arm, gspmd in (("transpiler", False), ("gspmd", True)):
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup), \
                fluid.unique_name.guard():
            feeds, loss, _mlm, _nsp = bert.build_bert_pretrain(
                cfg, is_test=False)
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
        _maybe_enable_bf16(main_prog, bf16)
        data = bert.make_fake_batch(cfg, batch=batch, seq_len=seq_len,
                                    seed=0)
        times = []
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            runner = DataParallelRunner(main_prog, loss.name, gspmd=gspmd)
            runner.run(exe, data, [loss.name], scope)  # warm/compile
            for _ in range(n_steps):
                t0 = time.perf_counter()
                runner.run(exe, data, [loss.name], scope)
                times.append(time.perf_counter() - t0)
            rec = {
                "p50_s": round(float(np.percentile(times, 50)), 6),
                "p95_s": round(float(np.percentile(times, 95)), 6),
                "max_s": round(float(np.max(times)), 6),
            }
            if gspmd:
                # stamp the arm's mesh dims + policy class so sweeps
                # across factorizations are distinguishable in BENCH
                # history (the config token alone never named them)
                from paddle_tpu.parallel import policy_summary

                rec["policy"] = policy_summary(
                    runner._gspmd_exec.mesh, runner._gspmd_exec.policy)
            if gspmd and runner._gspmd_exec.last_hlo:
                hlo = runner._gspmd_exec.last_hlo
                rec["resharding_bytes"] = hlo_collective_bytes(hlo)
                rec["collectives"] = hlo_collective_counts(hlo)
                rec["program_collective_ops"] = sum(
                    1 for op in runner.program.global_block().ops
                    if op.type.startswith("c_allreduce"))
        out[arm] = rec
    return out


def measure_recovery(size):
    """PT_BENCH_RECOVERY=1 (`make recovery-bench`): the measured
    preempt→restore rung.  Runs the fast in-process drill
    (distributed.recovery.inprocess_drill — train, drop every live
    object, restore through the persisted rollback window, finish) and
    records the recovery phases + MTTR in the BENCH record, so recovery
    time regressions gate like throughput regressions
    (tools/perf_compare.py).  The multi-process drill (trainer +
    pserver kill, epoch agreement) runs in
    tests/test_recovery_drill.py's slow acceptance — this rung stays
    fast enough for every bench invocation."""
    import tempfile

    from paddle_tpu.distributed import recovery
    from paddle_tpu import observability as obs

    steps = int(os.environ.get("PT_BENCH_RECOVERY_STEPS", "12"))
    kill_after = int(os.environ.get("PT_BENCH_RECOVERY_KILL", "8"))
    with tempfile.TemporaryDirectory(prefix="pt_bench_recovery_") as d:
        report = recovery.inprocess_drill(d, steps=steps,
                                          kill_after=kill_after)
    snap = obs.snapshot().get("pt_recovery_seconds") or {}
    phases_hist = {"|".join(k): {"sum": round(float(v["sum"]), 4),
                                 "count": int(v["count"])}
                   for k, v in snap.get("samples", {}).items()}
    return {
        "metric": "recovery_mttr_seconds",
        "value": report["mttr_s"],
        "unit": "s",
        "config": (f"recovery inprocess fc13 steps{steps} "
                   f"kill{kill_after} window-restore"),
        "recovery_drill": report,
        "recovery_phase_hist": phases_hist,
    }


def measure_autotune(size):
    """PT_BENCH_AUTOTUNE=1 (`make autotune`): the mesh-autotuner rung
    (ISSUE 20).  BERT-tiny sweep over the 8-virtual-device CPU mesh:
    enumerate legal (pp, dp, mp) × policy candidates, rank them with the
    analytic cost model, measure the top-K through `GSPMDExecutor`, then
    (a) A/B the measured winner against the transpiler DP lane —
    `gspmd_vs_transpiler` win-or-tie, the committed evidence the
    standing FLAGS_gspmd_executor flip is gated on — and (b) re-run the
    pinned winner through ``DataParallelRunner(policy_pin=report)``,
    recording its p50 and that the steady state compiles nothing (the
    AOT/compile cache owns every signature after warmup)."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import DataParallelRunner, autotune

    n_steps = int(os.environ.get("PT_BENCH_AUTOTUNE_STEPS", "6"))
    batch, seq_len = 16, 32
    kw = dict(vocab_size=30528, attn_dropout=0.1)
    cfg = (bert.BertConfig.base(**kw) if size == "base"
           else bert.BertConfig.tiny(**kw))

    loss_holder = {}

    def build():
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup), \
                fluid.unique_name.guard():
            feeds, loss, _mlm, _nsp = bert.build_bert_pretrain(
                cfg, is_test=False)
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
        loss_holder["name"] = loss.name
        return main_prog, startup

    build()  # populate loss_holder before the kwarg below evaluates
    feed = bert.make_fake_batch(cfg, batch=batch, seq_len=seq_len, seed=0)
    report_path = os.environ.get("PT_BENCH_AUTOTUNE_REPORT",
                                 "autotune_report.json")
    report = autotune.autotune(
        build, feed, loss_name=loss_holder["name"],
        top_k=3, steps=n_steps,
        workload={"model": f"bert-{size}", "batch": batch,
                  "seq_len": seq_len})

    # transpiler DP arm on the same workload → gspmd_vs_transpiler
    main_prog, startup = build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        runner = DataParallelRunner(main_prog, loss_holder["name"],
                                    gspmd=False)
        runner.run(exe, feed, [loss_holder["name"]], scope)  # warm
        times = []
        for _ in range(n_steps):
            t0 = time.perf_counter()
            runner.run(exe, feed, [loss_holder["name"]], scope)
            times.append(time.perf_counter() - t0)
    autotune.stamp_gspmd_vs_transpiler(
        report, float(np.percentile(times, 50)))

    # pinned re-run: the winner back through the runner pin path —
    # acceptance demands p50 reproduces within noise with zero
    # steady-state compiles
    pinned = None
    if report.get("winner"):
        main_prog, startup = build()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            runner = DataParallelRunner(main_prog, loss_holder["name"],
                                        policy_pin=report)
            runner.run(exe, feed, [loss_holder["name"]], scope)  # warm
            before = autotune._gspmd_cache_counts()
            times = []
            for _ in range(n_steps):
                t0 = time.perf_counter()
                runner.run(exe, feed, [loss_holder["name"]], scope)
                times.append(time.perf_counter() - t0)
            after = autotune._gspmd_cache_counts()
        p50 = float(np.percentile(times, 50))
        winner_p50 = report["winner"]["measured"]["p50_s"]
        pinned = {
            "label": report["winner"]["label"],
            "p50_s": round(p50, 6),
            "winner_measured_p50_s": winner_p50,
            "p50_ratio": round(p50 / max(winner_p50, 1e-12), 4),
            "steady_state_compiles": after["miss"] - before["miss"],
        }
        report["pinned_rerun"] = pinned
    autotune.save_report(report, report_path)

    winner = report.get("winner") or {}
    return {
        "metric": "autotune_winner_step_p50_s",
        "value": (winner.get("measured") or {}).get("p50_s"),
        "unit": "s",
        "config": (f"autotune bert-{size} b{batch} s{seq_len} "
                   f"dev{report['n_devices']} top3 steps{n_steps}"
                   + _cpu_suffix()),
        "winner": winner.get("label"),
        "winner_rank": report.get("winner_rank"),
        "analytic_top3_contains_winner":
            report.get("analytic_top3_contains_winner"),
        "prediction_error": {
            m["label"]: m["measured"].get("prediction_error")
            for m in report["measured"] if m.get("measured")},
        "gspmd_vs_transpiler": report.get("gspmd_vs_transpiler"),
        "pinned_rerun": pinned,
        "candidates_enumerated": len(report["candidates"]),
        "report_path": report_path,
    }


def measure_serve_drill(size):
    """PT_BENCH_SERVE_DRILL=1 (`make serve-drill`): the serving
    resilience rung.  Runs the full FaultPlan-driven serving drill
    (paddle_tpu/serving/drill.py — replica_kill failover with
    token-exact resume, canary promotion clean + rollback, hedged
    requests against a slow primary) and records the failover MTTR and
    hedge win-rate in the BENCH schema, so serving-recovery regressions
    gate like throughput regressions (tools/perf_compare.py)."""
    from paddle_tpu.serving import drill

    report = drill.run_drill()
    failover = report.get("failover", {})
    hedge = report.get("hedge", {})
    return {
        "metric": "serve_failover_mttr_seconds",
        "value": failover.get("mttr_s"),
        "unit": "s",
        "config": (f"serve drill 2-replica gpt-tiny "
                   f"req{failover.get('requests')} "
                   f"hedge{hedge.get('hedge_ms')}ms"),
        "serve_drill_ok": report.get("ok"),
        "serve_hedge_win_rate": hedge.get("hedge_win_rate"),
        "serve_hedges_fired": hedge.get("hedges_fired"),
        "serve_failovers": failover.get("failovers"),
        # SLO alert latencies from the drill-asserts-alert gate: the
        # availability page alert must fire during the kill and clear
        # after recovery — its latencies regress-gate like MTTR
        "slo_alert_fire_latency_s": failover.get("slo", {})
        .get("fire_latency_s"),
        "slo_alert_clear_latency_s": failover.get("slo", {})
        .get("clear_latency_s"),
        # trace-derived per-request TTFT/TPOT quantiles (span tree)
        "trace_quantiles": failover.get("trace_quantiles"),
        "serve_drill": report,
    }


def measure(size):
    if ((os.environ.get("PT_BENCH_PIPELINE") == "1"
         or os.environ.get("PT_BENCH_AUTOTUNE") == "1")
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        # the pipeline and autotune rungs need a >=2-device mesh: carve
        # 8 virtual host devices BEFORE jax initializes
        # (tests/cpu_mesh.py precedent; a real TPU backend ignores the
        # host-platform flag) — without this, `make pipeline-bench` /
        # `make autotune` on a CPU host would silently record no data
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    if os.environ.get("PT_BENCH_SERVE_DRILL") == "1":
        return measure_serve_drill(size)
    if os.environ.get("PT_BENCH_SERVE") == "1":
        return measure_serving(size)
    if os.environ.get("PT_BENCH_RAGGED") == "1":
        return measure_ragged_serving(size)
    if os.environ.get("PT_BENCH_RECOVERY") == "1":
        return measure_recovery(size)
    if os.environ.get("PT_BENCH_AUTOTUNE") == "1":
        return measure_autotune(size)
    if os.environ.get("PT_BENCH_DECODE") == "1":
        # NOTE: PT_BENCH_DECODE=scan|unrolled still selects the
        # whole-sequence generate variant inside the PT_BENCH_MODEL=gpt
        # rung; "1" is the decode-LANE load-gen rung (make decode-bench)
        return measure_decode_lane(size)
    model = os.environ.get("PT_BENCH_MODEL", "bert")
    if model in ("resnet", "resnet50"):
        return measure_resnet(size)
    if model == "gpt":
        return measure_gpt_decode(size)
    if model in ("nmt", "transformer"):
        return measure_nmt(size)
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.models import bert

    # The default is the bf16 dtype policy — BASELINE.md's north-star
    # config (bf16 compute, fp32 master weights); PT_BENCH_FP32=1 is the
    # plain-fp32 comparison rung.  Under jax 0.9.0 / libtpu 0.0.34 an
    # fp32 dot at default precision is NOT full precision on the v5e
    # (max relative error 2.5e-3 vs 1.4e-7 at Precision.HIGHEST, PR 21
    # chip probe), so "fp32" here means fp32 storage, not fp32 products.
    batch = int(os.environ.get("PT_BENCH_BATCH", "128"))
    seq_len = int(os.environ.get("PT_BENCH_SEQLEN", "128"))
    n_steps = int(os.environ.get("PT_BENCH_STEPS", "10"))
    flash = os.environ.get("PT_BENCH_FLASH", "0") == "1"
    amp = os.environ.get("PT_BENCH_AMP", "0") == "1"
    # quantized-allreduce rung: the data-parallel path over every local
    # device with bucketed block-scaled int8 gradient collectives
    # (FLAGS_quant_allreduce); on one device it degenerates to the plain
    # single-chip step, labeled dp1 so the config says so
    quantar = os.environ.get("PT_BENCH_QUANTAR", "0") == "1"
    n_dev = 1
    if quantar:
        import jax

        n_dev = jax.device_count()
        # feeds must shard evenly over dp; floor at one row per device so
        # a small PT_BENCH_BATCH can never round down to an empty feed
        batch = max(n_dev, batch - batch % n_dev)
    # the headline metric is the north-star config (BASELINE.md: "BERT-base
    # pretraining tokens/sec (bf16)") — the bf16 dtype policy, fp32 master
    # weights.  PT_BENCH_FP32=1 measures the plain-fp32 comparison rung.
    bf16 = _bf16_default()
    kw = dict(vocab_size=30528,  # pad vocab to /64 for MXU
              use_flash_attention=flash,
              attn_dropout=0.0 if flash else 0.1)
    cfg = bert.BertConfig.base(**kw) if size == "base" else \
        bert.BertConfig.tiny(**kw)
    main_prog = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main_prog, startup), fluid.unique_name.guard():
        feeds, loss, mlm_loss, nsp_acc = bert.build_bert_pretrain(
            cfg, is_test=False)
        opt = fluid.optimizer.Adam(learning_rate=1e-4)
        if amp:
            from paddle_tpu.fluid.contrib import mixed_precision as mp

            opt = mp.decorate(opt)  # bf16 compute, fp32 master weights
        opt.minimize(loss)
    # the dtype POLICY (bf16 compute, fp32 master weights) — the perf
    # path; PT_BENCH_AMP is the reference-style cast-insertion rewrite
    _maybe_enable_bf16(main_prog, bf16)
    exe = fluid.Executor()
    exe.run(startup)
    data = bert.make_fake_batch(cfg, batch=batch, seq_len=seq_len, seed=0,
                                shards=n_dev)
    if quantar:
        bs_quant = fluid.compiler.BuildStrategy()
        bs_quant.quant_allreduce = True
        run_prog = fluid.CompiledProgram(
            main_prog, build_strategy=bs_quant).with_data_parallel(
                loss_name=loss.name)
        if os.environ.get("PT_BENCH_HOST_FEED") != "1":
            # device_put HERE (not just inside the timed helper) so the
            # post-run cost_analysis presents the exact feed signature the
            # timed executable compiled for (x64-disabled backends narrow
            # int64 feeds on transfer — the key must see the same dtypes)
            import jax

            data = jax.device_put(data)
        dt = _timed_steps_dp(exe, run_prog, data, loss.name, n_steps)
    else:
        dt = _timed_steps(exe, main_prog, data, loss.name, n_steps)

    # the quantar rung spreads the global batch over n_dev chips: divide
    # throughput AND step-FLOPs by n_dev so the per-chip unit and the
    # single-chip-peak MFU stay honest (a dp8 record must not read 8x
    # faster per chip than the single-chip headline)
    tokens_per_sec = n_steps * batch * seq_len / dt / n_dev
    step_flops = _bert_train_flops_per_step(cfg, batch, seq_len) / n_dev
    # labels: " bf16" = the cast-insertion AMP rewrite (its historical
    # label — old baselines match); " bf16-policy" = the dtype policy.
    # " quantar-dpN" = the quantized-allreduce DP rung over N devices — a
    # shape token, so it can never alias a single-chip record — plus the
    # " syncfetch" A/B marker (_timed_steps_dp fetches every step; the
    # marker keeps a future pipelined DP capture from exact-matching it).
    quantar_tok = ""
    if quantar:
        quantar_tok = f" quantar-dp{n_dev}"
        from paddle_tpu.fluid import flags as _flags

        qalgo = _flags.flag("quant_allreduce_algo")
        if qalgo != "auto":
            # pinned-algorithm A/B leg: a shape token so a ring capture
            # can never alias an auto/oneshot record of the same shape
            quantar_tok += f" qar-{qalgo}"
        if os.environ.get("PT_BENCH_SYNC_FETCH") != "1":
            quantar_tok += " syncfetch"  # else _cpu_suffix adds it
    config = (f"bert-{size} b{batch} s{seq_len}"
              + (" flash" if flash else "") + (" bf16" if amp else "")
              + (" bf16-policy" if bf16 else "")
              + quantar_tok + _cpu_suffix())
    rec = _attach_flops({
        "metric": f"bert_{size}_pretrain_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        **_vs_baseline_rec(tokens_per_sec, config,
                           is_headline=size == "base",
                           default_metric=True),
        "config": config,
    }, step_flops, n_steps, dt)
    if quantar:
        # the rung's point: the executable's own cost model measures the
        # bytes the quantized collectives move vs the fp32 A/B — record it
        try:
            ca = run_prog.cost_analysis(exe, data, fetch_list=[loss.name])
            rec["bytes_accessed"] = ca["cost"].get("bytes accessed")
            rec["quant_allreduce"] = True
        except Exception as e:  # cost model unavailable on this backend
            print(f"bench: quantar cost_analysis unavailable ({e})",
                  file=sys.stderr)
        # modeled wire bytes for BOTH algorithms beside the one that ran
        # (wire_bytes(algo=...) over the transpiler's bucket plan), so the
        # record shows the ring-vs-oneshot byte delta without a re-run
        plan = getattr(main_prog, "_quant_allreduce_plan", None)
        if plan and plan.get("buckets"):
            from paddle_tpu.kernels import quantized_collectives as qc

            bs = plan["block_size"]
            rec["quant_wire_bytes"] = {
                algo: sum(qc.wire_bytes(b["elements"], block_size=bs,
                                        n_devices=n_dev, algo=algo)
                          for b in plan["buckets"])
                for algo in ("oneshot", "ring", "ring_bidir")
            }
            rec["quant_wire_bytes"]["selected"] = [
                b["algo"] for b in plan["buckets"]]
            rec["quant_wire_bytes"]["algo_flag"] = plan["algo"]
            rec["quant_wire_bytes"]["crossover_kb"] = plan["crossover_kb"]
            rec["quant_wire_bytes"]["fused_update"] = [
                bool(b.get("fused_update")) for b in plan["buckets"]]
        # ready-order dispatch schedule (the transpile summary): how far
        # into the backward each bucket's collective launched
        sched = getattr(main_prog, "_overlap_schedule", None)
        if sched:
            rec["overlap_schedule"] = sched
        # graph-optimization pass report (docs/PASSES.md): what each
        # pass rewrote in the measured program — sites + op-inventory
        # deltas ride in EVERY record so a claimed headline is
        # attributable to its rewrites
        prep = getattr(main_prog, "_pass_report", None)
        if prep:
            rec["graph_passes"] = [e for e in prep if e.get("changed")]
        # hop-latency sub-rung: per-hop latency vs payload + the measured
        # ring/oneshot crossover (tunes FLAGS_quant_allreduce_crossover_kb)
        if os.environ.get("PT_BENCH_HOPLAT", "1") == "1":
            try:
                hop = _hop_latency_bench()
                if hop:
                    rec["quant_hop_latency"] = hop
            except Exception as e:
                print(f"bench: hop-latency sub-rung failed ({e})",
                      file=sys.stderr)
        # overlap-on vs overlap-off step-quantile A/B
        if os.environ.get("PT_BENCH_OVERLAP") == "1":
            try:
                rec["overlap_ab"] = _overlap_step_quantiles(
                    size, batch, seq_len, n_steps, bf16)
            except Exception as e:
                print(f"bench: overlap A/B rung failed ({e})",
                      file=sys.stderr)
    # transpiler-lane vs GSPMD-executor-lane A/B (ISSUE 9): step
    # quantiles per arm + what XLA's partitioner inserted on the gspmd
    # arm (collective counts, resharding bytes from HLO inspection)
    if os.environ.get("PT_BENCH_GSPMD") == "1":
        try:
            rec["gspmd_ab"] = _gspmd_ab(size, batch, seq_len, n_steps,
                                        bf16)
        except Exception as e:
            print(f"bench: gspmd A/B rung failed ({e})", file=sys.stderr)
    # pipeline-as-policy A/B (ISSUE 15): PipelineRunner vs
    # PipelinePolicy, gpipe vs 1f1b, microbatch sweep + modeled boundary
    # bytes + measured bubble fraction
    if os.environ.get("PT_BENCH_PIPELINE") == "1":
        try:
            rec["pipeline_ab"] = _pipeline_ab(n_steps)
        except Exception as e:
            print(f"bench: pipeline A/B rung failed ({e})",
                  file=sys.stderr)
    # phase-instrumentation on vs off A/B (ISSUE 11): step_phases
    # bracket + per-step device_wait sync overhead, gated within noise
    # (<=2% p50) on the CPU smoke
    if os.environ.get("PT_BENCH_PHASES") == "1":
        try:
            rec["phase_ab"] = _phase_overhead_ab(size, batch, seq_len,
                                                 n_steps, bf16)
        except Exception as e:
            print(f"bench: phase A/B rung failed ({e})", file=sys.stderr)
    # graph-optimization passes on vs off A/B (ISSUE 12): fused
    # attention + fused bias/gelu/dropout step quantiles per arm plus
    # the measured per-pass cost_analysis attribution
    if os.environ.get("PT_BENCH_PASSES") == "1":
        try:
            rec["passes_ab"] = _passes_ab(size, batch, seq_len, n_steps,
                                          bf16)
        except Exception as e:
            print(f"bench: passes A/B rung failed ({e})", file=sys.stderr)
    # health-sentinel-on vs -off A/B (ISSUE 10): in-graph finite check +
    # skip gate overhead, gated at <=2% p50 on the CPU smoke
    if os.environ.get("PT_BENCH_HEALTH") == "1":
        try:
            rec["health_ab"] = _health_ab(size, batch, seq_len, n_steps,
                                          bf16)
        except Exception as e:
            print(f"bench: health A/B rung failed ({e})", file=sys.stderr)
    return rec


def _probe_device(budget):
    """Ask a short-lived child which platform jax.devices() answers with
    — a child, so this parent never touches JAX and the chip stays free
    for the measuring child.  Returns the platform string, or None when
    the child failed or gave no answer within ``budget`` seconds."""
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print('PLATFORM=' + jax.devices()[0].platform)"],
            env=dict(os.environ), capture_output=True, text=True,
            timeout=budget)
    except subprocess.TimeoutExpired:
        print(f"bench: device probe gave no answer in {budget:.0f}s",
              file=sys.stderr)
        return None
    for ln in out.stdout.splitlines():
        if ln.startswith("PLATFORM="):
            return ln.split("=", 1)[1]
    print(f"bench: device probe FAILED rc={out.returncode}\n"
          + out.stderr[-2000:], file=sys.stderr)
    return None


def _metrics_summary():
    """Observability-registry digest embedded in every BENCH_*.json record
    (docs/OBSERVABILITY.md): the perf trajectory carries compile-cache
    behavior, compile seconds and collective payload bytes alongside the
    headline timing instead of timings alone."""
    try:
        from paddle_tpu import observability as obs

        snap = obs.snapshot()

        def sum_family(name):
            fam = snap.get(name)
            if not fam:
                return None
            out = {}
            for key, v in fam["samples"].items():
                label = ",".join(key) if key else "total"
                out[label] = round(
                    v["sum"] if isinstance(v, dict) else v, 6)
            return out

        summary = {}
        for rec_key, fam in (("compile_cache", "pt_compile_cache_total"),
                             ("compile_seconds", "pt_compile_seconds_total"),
                             ("collective_bytes",
                              "pt_collective_payload_bytes_total"),
                             ("step_seconds_sum", "pt_step_seconds")):
            vals = sum_family(fam)
            if vals:
                summary[rec_key] = vals
        # histogram-quantile summaries (ROADMAP telemetry phase-2): the
        # step-time DISTRIBUTION rides in every record, not just the sum —
        # p50/p95/max per execution path, PromQL histogram_quantile
        # semantics (obs.hist_quantile)
        steps = snap.get("pt_step_seconds")
        if steps and steps.get("type") == "histogram":
            quants = {}
            for key, h in steps["samples"].items():
                label = ",".join(key) if key else "total"
                quants[label] = {
                    "p50": _rq(obs.hist_quantile(h, 0.50)),
                    "p95": _rq(obs.hist_quantile(h, 0.95)),
                    "max": _rq(obs.hist_quantile(h, 1.0)),
                    "count": h["count"],
                }
            if quants:
                summary["step_seconds_quantiles"] = quants
        # the step-time attribution digest (ISSUE 11): per-lane phase
        # quantiles, per-signature MFU + roofline verdict, and the
        # feed-bound fraction ride in EVERY record so
        # tools/perf_compare.py can diff where the time went, not just
        # how much there was
        summary["attribution"] = obs.profiling.attribution_digest()
        return summary
    except Exception as e:  # telemetry must never fail the bench
        print(f"bench: metrics summary unavailable ({e})", file=sys.stderr)
        return {}


def _rq(v):
    return None if v is None else round(float(v), 6)


def _scrape_collective_metrics():
    """Scrape THIS process's /metricsz for the pt_collective_* families
    and return them parsed (ROADMAP telemetry phase-2: bench rungs embed
    the scrape in their record).  Goes through the real HTTP endpoint +
    the strict text parser — the record then proves the exposition path
    end-to-end, not just the in-process registry.  Uses the flag-started
    server when one is up (FLAGS_metrics_port), else binds an ephemeral
    one for the scrape and tears it down."""
    try:
        from urllib.request import urlopen

        from paddle_tpu import observability as obs
        from paddle_tpu.observability import exposition as expo

        server = expo.active_server() or expo.ensure_from_flags()
        ephemeral = None
        if server is None:
            ephemeral = server = obs.MetricsServer(port=0)
        try:
            text = urlopen(
                f"http://{server.host}:{server.port}/metricsz",
                timeout=10).read().decode()
        finally:
            if ephemeral is not None:
                ephemeral.stop()
        out = {}
        for name, fam in obs.parse_text(text).items():
            if not name.startswith("pt_collective"):
                continue
            samples = {}
            for labels, value in fam["samples"]:
                key = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
                samples[key or "total"] = value
            out[name] = samples
        return out
    except Exception as e:  # telemetry must never fail the bench
        print(f"bench: /metricsz scrape unavailable ({e})", file=sys.stderr)
        return {}


def main():
    if os.environ.get("PT_BENCH_CHILD"):
        rec = measure(os.environ["PT_BENCH_CHILD"])
        rec.setdefault("metrics", _metrics_summary())
        # rung-end /metricsz scrape: the pt_collective_* gauges as served
        # over HTTP (empty unless a collective path ran — only then does
        # the record carry it)
        scraped = _scrape_collective_metrics()
        if scraped:
            rec.setdefault("metricsz_collectives", scraped)
        print(json.dumps(rec), flush=True)
        return

    _main_ladder()


def _main_ladder():
    # PT_BENCH_TIMEOUT is the TOTAL budget for the whole ladder (the driver
    # kills us somewhere around it): every rung gets a slice, and a global
    # deadline caps each slice to what is actually left.
    total = float(os.environ.get("PT_BENCH_TIMEOUT", "1500"))
    deadline = time.time() + total * 0.92
    model = os.environ.get("PT_BENCH_MODEL", "bert")

    probe_budget = float(os.environ.get("PT_BENCH_PROBE_TIMEOUT",
                                        min(90.0, total * 0.08)))
    platform = _probe_device(probe_budget)
    if platform != "tpu":
        sys.exit(f"bench: no TPU (device probe: platform={platform}) — a "
                 f"benchmark number only comes from the chip")

    # the mid rung must be strictly LIGHTER than the first (it runs in a
    # smaller slice after the first timed out): gpt/bert/resnet shrink the
    # batch; nmt is token-budgeted so it shrinks the per-bucket token
    # budget and round count instead (PT_BENCH_BATCH is ignored there)
    if model in ("nmt", "transformer"):
        mid_overrides = {"PT_BENCH_TOKENS": "4096", "PT_BENCH_STEPS": "2"}
    else:
        mid_overrides = {"PT_BENCH_BATCH": "8" if model == "gpt" else "64",
                         "PT_BENCH_STEPS": "6"}
    ladder = (
        ("base", {}, total * 0.50),
        ("base", mid_overrides, total * 0.28),
        ("tiny", {}, total * 0.14),
    )
    for size, overrides, alloc in ladder:
        budget = min(alloc, deadline - time.time())
        label = size + "".join(
            f" {k[len('PT_BENCH_'):].lower()}={v}"
            for k, v in sorted(overrides.items()))
        if budget < 30.0:
            print(f"bench: skipping {label} (only {budget:.0f}s left)",
                  file=sys.stderr)
            continue
        env = dict(os.environ, PT_BENCH_CHILD=size, **overrides)
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__)], env=env,
                capture_output=True, text=True, timeout=budget)
        except subprocess.TimeoutExpired:
            print(f"bench: {label} config timed out after {budget:.0f}s",
                  file=sys.stderr)
            continue
        lines = [ln for ln in out.stdout.splitlines()
                 if ln.startswith("{")]
        if out.returncode == 0 and lines:
            print(lines[-1])
            return
        print(f"bench: {label} config failed rc={out.returncode}\n"
              + out.stderr[-2000:], file=sys.stderr)
    sys.exit("bench: no config completed on the TPU")


if __name__ == "__main__":
    main()
