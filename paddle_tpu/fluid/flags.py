"""Global flag system (reference: ~95 C++ gflags surfaced to Python by
`__bootstrap__` reading FLAGS_* env vars + core.init_gflags,
python/paddle/fluid/__init__.py:124-180 / pybind.cc:988).

TPU-native subset: flags that change observable behavior here are
implemented (executor hooks); CUDA-memory / allocator flags are accepted for
script compatibility but are no-ops (PJRT owns device memory) — setting one
emits a warning.

Env bootstrap: any FLAGS_<name> environment variable seen at import time
seeds the corresponding flag, exactly like the reference's __bootstrap__.
A malformed value warns and keeps the default (an unimportable package is
worse than an ignored flag).
"""

from __future__ import annotations

import os
import warnings

__all__ = ["get_flags", "set_flags"]

_FALSY = ("0", "false", "off", "no", "")


def _parse_bool(v):
    return str(v).strip().lower() not in _FALSY


# name -> (default, parser, implemented?)  — `implemented` False means the
# flag is accepted for compatibility but changes nothing on TPU
_DEFS = {
    # debugging / determinism (executor hooks; RNG is deterministic by
    # design so cpu_deterministic=True is the native behavior)
    "FLAGS_check_nan_inf": (False, _parse_bool, True),
    "FLAGS_benchmark": (False, _parse_bool, True),
    "FLAGS_cpu_deterministic": (True, _parse_bool, True),
    # distributed (consumed by the PS/RPC host ops and the async
    # Communicator; reference __init__.py:187-196 reads the same env names)
    "FLAGS_rpc_deadline": (180000, int, True),
    # RPC retry/backoff (reference grpc flag FLAGS_rpc_retry_times=3;
    # backoff is TPU-native — the reference retries immediately).  0
    # retries = fail fast on the first transport error.  Consumed by
    # native.PSClient via distributed.resilience.RetryPolicy.
    "FLAGS_rpc_retry_times": (3, int, True),
    "FLAGS_rpc_retry_backoff_ms": (100, int, True),
    # liveness deadline on pserver-side barrier / versioned-get waits (the
    # heartbeat analog): a request parked longer than this answers with a
    # retryable timeout instead of wedging behind a dead peer; 0 = wait
    # forever (reference listen_and_serv behavior)
    "FLAGS_ps_barrier_timeout_ms": (300000, int, True),
    # elastic membership (docs/DISTRIBUTED.md §6 "Elastic membership"):
    # trainers JOIN/LEAVE a running sync-mode PS job under a lease; the
    # server's barrier quorum is the live member set, so a preempted
    # trainer's round completes with the survivors and a joiner enters at
    # the next epoch.  Off by default — the frozen n_trainers contract is
    # the reference behavior.
    "FLAGS_elastic_ps": (False, _parse_bool, True),
    # server-side lease deadline: an active member with no lease-renewing
    # frame (heartbeat or barrier arrival) for this long is evicted at the
    # next round wait and the quorum renegotiates; 0 = never expire
    "FLAGS_ps_lease_timeout_ms": (15000, int, True),
    # client-side heartbeat cadence (a sidecar connection renews the lease
    # through long compute phases); should be well under the lease timeout
    "FLAGS_ps_lease_heartbeat_ms": (3000, int, True),
    # time-based pserver snapshot cadence in seconds, decoupled from sync
    # rounds: >0 snapshots at most every N seconds (geo/async lanes get
    # crash recovery without per-round cost; the sync lane thins its
    # per-round snapshots); 0 keeps the per-round behavior
    # (PT_PS_SNAPSHOT_EVERY rounds)
    "FLAGS_ps_snapshot_interval_s": (0.0, float, True),
    # durable rollback windows (health/persist.py + AutoCheckpoint):
    # >0 offloads the health sentinel's on-device snapshot window to the
    # checkpoint dir at most every N seconds (async device->host copy +
    # temp+rename manifest, PTHWIN1), so a RESTARTED job can roll back
    # past a bad step that happened before the kill instead of resuming
    # at the last full checkpoint; 0 disables the time cadence (the
    # window still persists inside every full checkpoint save and on the
    # preemption signal path when a sentinel is attached)
    "FLAGS_rollback_persist_interval_s": (0.0, float, True),
    # recovery-drill spec consumed by distributed.recovery.run_drill /
    # `make recovery-drill` (FaultPlan grammar, e.g.
    # "drill:preempt+restore:step:4"); empty = no standing drill
    "FLAGS_recovery_drill": ("", str, True),
    "FLAGS_communicator_max_merge_var_num": (20, int, True),
    "FLAGS_communicator_send_queue_size": (20, int, True),
    "FLAGS_communicator_independent_recv_thread": (True, _parse_bool, False),
    "FLAGS_communicator_min_send_grad_num_before_recv": (20, int, False),
    "FLAGS_communicator_thread_pool_size": (5, int, False),
    "FLAGS_communicator_fake_rpc": (False, _parse_bool, False),
    "FLAGS_communicator_merge_sparse_grad": (True, _parse_bool, False),
    # persistent XLA compile cache (SURVEY §7 hard part 6: hide compile
    # latency behind a cache that survives processes).  Empty string
    # disables; the executor applies it lazily on first compile, and
    # not at all where JAX_COMPILATION_CACHE_DIR places the cache from
    # outside.  The default sits in the checkout at a FIXED path (the
    # path is part of jax's cache key; .gitignore lists it).
    "FLAGS_compile_cache_dir": (
        os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"), str, True),
    # AOT-serialized executables (fluid/aot_cache.py): beyond the warm
    # XLA cache above, the executor pickles each compiled executable
    # keyed by a restart-stable signature and a restarted process
    # DESERIALIZES it — no Python re-trace, no XLA compile, the
    # fleet-restart story (pt_compile_cache_total{result="aot_hit"}).
    # Empty disables (default); the key pins platform/device/jaxlib.
    "FLAGS_aot_cache_dir": ("", str, True),
    # quantized gradient all-reduce (EQuARX-style): the data-parallel
    # transpiler buckets same-dtype grads into fused buffers and
    # all-reduces them block-scaled int8 (ops/collective_ops.py
    # c_allreduce_quant).  DGC-encoded grads and batch-norm stats are
    # never quantized.  Off by default — opt in per run, or per runner
    # via DataParallelRunner(quant_grads=True).
    "FLAGS_quant_allreduce": (False, _parse_bool, True),
    "FLAGS_quant_allreduce_block_size": (256, int, True),
    # quantized-all-reduce algorithm selection
    # (kernels.ring_collectives.select_allreduce_algo): "oneshot" = the
    # two-phase all_to_all/all_gather form (O(1) launches, full payload
    # per phase), "ring" = the explicit ppermute ring with per-hop
    # requantization (2*(n-1)/n of payload bytes, 2*(n-1) hops deep),
    # "auto" = size crossover — tensors with at least
    # FLAGS_quant_allreduce_crossover_kb KB of fp32 payload take the ring
    # (the bidirectional one when the axis/payload clear bidir_eligible)
    "FLAGS_quant_allreduce_algo": ("auto", str, True),
    # crossover default: 256 KB of fp32 payload is where the ring first
    # beat oneshot on the 8-device CPU mesh (r8; a container reading, no
    # evidence for the chip); on the chip: not measured — this flag stays
    # the override either way
    "FLAGS_quant_allreduce_crossover_kb": (256, int, True),
    # ready-order bucket dispatch (parallel/data_parallel.py): each
    # quantized gradient bucket's collective is emitted immediately after
    # the last gradient it covers is produced, so XLA's async collective
    # scheduling overlaps the ring hops with the remaining backward
    # compute.  Off = every gradient collective defers to after the full
    # backward (the no-overlap baseline of an on/off A/B).  On by default
    # for the quant path.
    "FLAGS_overlap_allreduce": (True, _parse_bool, True),
    # graph-optimization pass layer (paddle_tpu/passes/, docs/PASSES.md):
    # program passes run between construction and executor compile on
    # every lane.  "default" = the standard pipeline (fuse_attention,
    # fuse_bias_act_dropout, fuse_softmax_cross_entropy); "none" = off
    # (programs bit-identical to
    # the pre-pass layer); otherwise a comma-separated ordered list of
    # registered pass names, with "-name" dropping one from the default
    # set (e.g. "default,-fuse_attention" or just "-fuse_attention").
    "FLAGS_graph_passes": ("default", str, True),
    # fused dequant->optimizer-update->requant step kernels
    # (kernels/fused_update.py): eligible buckets keep the reduced
    # gradient in the int8+scales wire format straight into the rewritten
    # sgd/adam ops (c_allreduce_quant_keep), and ZeRO-1 gathers ride the
    # requantized updated-parameter payload — the fp32 intermediates
    # never round-trip HBM.  On by default; engages only where the quant
    # path / zero_gather_quant are already opted in.
    "FLAGS_fused_update": (True, _parse_bool, True),
    # GSPMD-native execution core (parallel/gspmd/, docs/DISTRIBUTED.md
    # "GSPMD execution core"): route DataParallelRunner /
    # HybridParallelRunner through the one jit-partitioned executor —
    # sharding policies + XLA-inserted collectives instead of the
    # transpiler's per-gradient c_allreduce rewrite.  Off by default
    # while the transpiler lane is the one the benchmark's dp4 cell runs
    # (ROADMAP D2); flip per run or per runner via gspmd=True.
    "FLAGS_gspmd_executor": (False, _parse_bool, True),
    # mesh-autotuner pin (parallel/autotune.py, docs/AUTOTUNE.md): path
    # to a committed autotune_report.json whose measured winner both
    # runners pin when no explicit policy_pin= is passed — the
    # "derive the (pp, batch, model) policy from measurement, then pin
    # it everywhere" loop.  Empty = no pin (hand-picked policies keep
    # working unchanged).
    "FLAGS_autotune_report": ("", str, True),
    # measured-shortlist size of the autotune sweep: the analytic cost
    # model ranks every legal candidate, the top-K get real compiles
    # through GSPMDExecutor
    "FLAGS_autotune_topk": (3, int, True),
    # timed steps per measured candidate (after the warm/compile step)
    "FLAGS_autotune_steps": (6, int, True),
    # pipeline-as-policy schedule (parallel/gspmd/pipeline_policy.py,
    # docs/DISTRIBUTED.md "Pipeline as a policy"): "1f1b" = one-forward-
    # one-backward interleaving — same bubble fraction as gpipe but the
    # activation stash holds min(M, S) microbatches instead of M (the
    # memory win that lets microbatch counts scale); "gpipe" = plain
    # fill/drain (all forwards, then all backwards).  Consumed by
    # PipelinePolicy when the schedule isn't pinned per policy.
    "FLAGS_pipeline_schedule": ("1f1b", str, True),
    # microbatch count for PipelinePolicy when neither the policy nor
    # the program's PipelineOptimizer metadata pins one
    "FLAGS_pipeline_microbatches": (4, int, True),
    # static program verification at the executors' compile boundary
    # (paddle_tpu/analysis/, docs/ANALYSIS.md): "warn" (default) emits
    # one ProgramVerifyWarning per (program, lane) summarizing the
    # findings, "raise" turns error-severity findings into a
    # ProgramVerifyError BEFORE the XLA trace (a named diagnostic
    # instead of an opaque trace failure), "strict" raises on warnings
    # too, "off" disables the preflight entirely.
    "FLAGS_program_verify": ("warn", str, True),
    # quant-hook integration form (parallel/gspmd/quant_hook.py):
    # "shard_map" = the fwd/bwd island reducing gradients on the
    # dual-int8 ring (works everywhere), "custom_partitioning" = the
    # reduction as a jax.custom_partitioning rule GSPMD integrates
    # natively, "auto" = custom_partitioning on TPU backends only (the
    # jaxlib-0.4.3x XLA:CPU GSPMD lane cannot be trusted with it —
    # documented fallback)
    "FLAGS_gspmd_quant_impl": ("auto", str, True),
    # ZeRO-1 weight-update gather quantization (parallel/hybrid.py
    # zero_gather_quant default): the dp-sharded parameter update
    # re-replicates through a block-scaled int8 all-gather instead of the
    # implicit fp32 one; optimizer-state shards never gather, so
    # optimizer state stays fp32-exact regardless.  Off by default.
    "FLAGS_zero_gather_quant": (False, _parse_bool, True),
    # fused-gradient bucket cap in MB (reference
    # FLAGS_fuse_parameter_memory_size analog): grads coalesce into
    # buckets up to this size so scale overhead and collective-launch
    # count amortize without one giant liveness-hungry buffer
    "FLAGS_fuse_grad_size_in_MB": (32, int, True),
    # production serving lane (paddle_tpu/serving, docs/SERVING.md).
    # Batch buckets: comma-separated request-row counts; the continuous
    # batcher pads every formed batch up to the smallest bucket >= its
    # row count so ONE compiled executable per bucket serves all traffic
    # (powers of two by default — the classic shape-bucketing recipe).
    "FLAGS_serving_batch_buckets": ("1,2,4,8,16", str, True),
    # optional sequence-length buckets for feeds whose dim-1 is dynamic
    # (var shape -1): "" disables sequence padding; e.g. "32,64,128"
    "FLAGS_serving_seq_buckets": ("", str, True),
    # continuous-batching max wait: after the first request of a batch
    # arrives, the scheduler waits at most this long for more requests
    # before dispatching a partial bucket (the latency/throughput knob)
    "FLAGS_serving_batch_timeout_ms": (5, int, True),
    # admission control: max requests queued per model; submissions
    # beyond it are rejected with ServingOverloadError instead of
    # queueing unboundedly (callers retry/shed — bounded worst-case
    # latency under overload)
    "FLAGS_serving_max_queue": (256, int, True),
    # per-request serving deadline in ms (docs/SERVING.md): a queued or
    # in-flight request older than this resolves its future with a typed
    # ServingDeadlineError instead of waiting forever (booked as
    # pt_serve_rejected_total{reason="deadline"}); 0 = no deadline
    "FLAGS_serving_deadline_ms": (0, int, True),
    # per-tenant admission quota on the decode lane (docs/SERVING.md
    # "Decode lane"): max LIVE requests (queued + prefilling + decoding)
    # any one tenant may hold per engine; beyond it submissions reject
    # with ServingOverloadError(reason="tenant_quota") and book
    # pt_serve_rejected_total{reason="tenant_quota"} — one chatty tenant
    # cannot starve the shared decode queue.  0 = unlimited.
    "FLAGS_serving_tenant_quota": (0, int, True),
    # serving resilience layer (serving/router.py, docs/SERVING.md
    # "Resilience").  Replica-group size the drill harness / launchers
    # build per model — the router itself holds however many replicas
    # are add_replica()'d, this is the provisioning default.
    "FLAGS_serving_replicas": (2, int, True),
    # hedged requests on the stateless (prefill-only) lane: after this
    # many ms without a primary result, a second replica gets a copy
    # and the first result wins (pt_serve_hedges_total{outcome}).
    # 0 = off; -1 = adaptive, arm from the router's rolling p99.
    "FLAGS_serving_hedge_ms": (0, int, True),
    # per-replica circuit breaker: this many CONSECUTIVE failures open
    # the breaker (replica out of rotation), after
    # FLAGS_serving_breaker_cooldown_ms one half-open probe request is
    # let through — success closes, failure re-opens
    # (pt_serve_breaker_state{replica}: 0=closed 1=half-open 2=open).
    "FLAGS_serving_breaker_failures": (5, int, True),
    "FLAGS_serving_breaker_cooldown_ms": (1000, int, True),
    # kernel-primitives layer (paddle_tpu/kernels/primitives/,
    # docs/KERNELS.md).  Measured tile-size autotune: when on, a
    # primitive that exposes candidates + a measure hook times them on
    # the first call per shape signature and caches the winner
    # (pt_kernel_autotune_total{source="measured"}).  Off by default —
    # candidate compiles are not free; PT_KERNEL_TILE_TABLE pins tiles
    # without measuring.
    "FLAGS_kernel_autotune": (False, _parse_bool, True),
    # ragged serving (docs/SERVING.md "Ragged serving"): models built
    # on ragged_attention pad every dynamic-dim-1 feed to ONE fixed
    # length and carry true lengths in a feed, so mixed-length traffic
    # batches together (padding rows → 0) and warmup compiles one
    # executable per batch bucket instead of the seq-bucket cross
    # product.  Engine.load_model(ragged=None) resolves from this flag.
    "FLAGS_ragged_attention": (False, _parse_bool, True),
    # int8 KV cache on the decode lane (docs/KERNELS.md "int8 KV"):
    # DecodeEngine(pool_dtype=None) resolves to "int8" when set — the
    # pool stores the dual-int8 block-scale format (quantize at append,
    # dequant inside the paged kernel), halving modeled KV HBM
    # (pt_int8_bytes_saved_total{kind="kv_cache"}).
    "FLAGS_int8_kv_cache": (False, _parse_bool, True),
    # training health sentinel (paddle_tpu/health/, docs/DISTRIBUTED.md
    # §6 "Numeric fault tolerance"): on-device NaN/Inf gradient
    # detection (one found_inf scalar per step, no host scan), loss-
    # spike detection, automatic skip/rollback, dynamic loss scaling —
    # wired into every runner lane.  Off by default: the fail-fast
    # FLAGS_check_nan_inf host scan stays the reference behavior.
    "FLAGS_health_sentinel": (False, _parse_bool, True),
    # response to a bad step: "raise" = fail fast (the check_nan_inf
    # contract), "skip" = mask the optimizer update in-graph and keep
    # training, "rollback" = restore params+optimizer state from the
    # rolling snapshot window and replay the step
    "FLAGS_health_action": ("skip", str, True),
    # rollback snapshot window depth (steps of params+opt state held as
    # on-device copies; ZeRO-1 shards snapshot only their residents)
    "FLAGS_health_rollback_keep": (2, int, True),
    # loss-spike detector: flag a step whose fetched loss deviates from
    # the rolling EMA by more than this many EMA standard deviations
    # (0 disables); warmup = good steps observed before it can fire
    "FLAGS_health_spike_zscore": (6.0, float, True),
    "FLAGS_health_spike_warmup": (8, int, True),
    # dynamic loss scaling (update_loss_scaling semantics): multiply the
    # backward seed by @HEALTH@loss_scale, unscale at the optimizer
    # edge, halve on every bad step, double after N consecutive good
    # steps.  Off by default — bf16 (the benchmark's policy) has fp32's
    # exponent range, so scaling is an fp16-parity knob.
    "FLAGS_health_loss_scaling": (False, _parse_bool, True),
    "FLAGS_health_loss_scale_init": (65536.0, float, True),
    "FLAGS_health_scale_growth_steps": (1000, int, True),
    # step-time attribution (observability/profiling.py,
    # docs/OBSERVABILITY.md "Step-time attribution").  The feed_prep /
    # dispatch / device_wait / fetch_sync phase spans of every executed
    # step are ALWAYS recorded (pt_step_phase_seconds, the span ring);
    # profile_phases keeps only what changes timing: the per-step
    # block_until_ready that makes device_wait read real device time.
    # Off by default: that block serializes the donated-buffer dispatch
    # pipelining the fetch-free training loop relies on — opt in per
    # run.
    "FLAGS_profile_phases": (False, _parse_bool, True),
    # flight recorder: bounded ring of the last N steps' attribution
    # records (phase breakdowns, queue depth, health events), dumped as
    # a JSONL postmortem on anomaly or on demand
    # (profiling.dump_flight_record)
    "FLAGS_flight_recorder_steps": (256, int, True),
    # where flight-record postmortems land; empty = the event-log dir
    # (PT_EVENT_LOG_DIR / FLAGS_event_log_dir), else the system tempdir
    "FLAGS_flight_recorder_dir": ("", str, True),
    # slow-step auto-dump trigger: a non-first-run step slower than the
    # per-lane rolling EMA by more than this many EMA standard
    # deviations dumps the flight record (0 disables the trigger)
    "FLAGS_profile_slow_step_zscore": (8.0, float, True),
    # roofline peak overrides (0 = the per-platform table in
    # profiling.device_peaks): peak flops/s, peak HBM bytes/s, peak ICI
    # bytes/s of one chip — MFU and the compute/memory/comm roofline
    # verdict are computed against these
    "FLAGS_device_peak_flops": (0.0, float, True),
    "FLAGS_device_peak_bandwidth": (0.0, float, True),
    "FLAGS_device_peak_ici_bandwidth": (0.0, float, True),
    # observability (docs/OBSERVABILITY.md): nonzero port serves
    # /metricsz + /statusz + /healthz from this process (started lazily
    # by the executor via observability.exposition.ensure_from_flags);
    # 0 = off.  Every process needs its OWN port — the launchers pass a
    # distinct FLAGS_metrics_port per child.
    "FLAGS_metrics_port": (0, int, True),
    # directory for the structured JSONL event log (step/round lifecycle
    # events, observability.events); empty = disabled.  The env override
    # PT_EVENT_LOG_DIR wins (launcher contract for children).
    "FLAGS_event_log_dir": ("", str, True),
    # request-scoped serving traces (observability/reqtrace.py,
    # docs/OBSERVABILITY.md "Request tracing"): every serving request
    # becomes a span tree (request → attempt → serve → shared batch)
    # with tail-based sampling into a bounded ring.  Default ON — the
    # hot-path cost was within a serving CPU smoke's noise floor; on
    # the chip it is not measured (PERF.md).
    "FLAGS_reqtrace": (True, _parse_bool, True),
    # completed-trace ring capacity (the tail-sampling window /tracez
    # and reqtrace.request_quantiles read from)
    "FLAGS_reqtrace_ring": (256, int, True),
    # background SLO burn-rate evaluation period (observability/slo.py);
    # the drill drives evaluate() itself at sub-second scale
    "FLAGS_slo_eval_interval_s": (10.0, float, True),
    # declarative SLO specs for the flag-driven evaluator, ';'-separated
    # (slo.parse_specs grammar, e.g. "avail|availability|bad=pt_serve_
    # failovers_total|total=pt_serve_requests_total|objective=0.999");
    # empty = no background evaluator
    "FLAGS_slo_specs": ("", str, True),
    # accepted no-ops (CUDA/allocator knobs with no TPU meaning)
    "FLAGS_fraction_of_gpu_memory_to_use": (0.92, float, False),
    "FLAGS_eager_delete_tensor_gb": (-1.0, float, False),
    "FLAGS_allocator_strategy": ("naive_best_fit", str, False),
    "FLAGS_use_ngraph": (False, _parse_bool, False),
    "FLAGS_fast_eager_deletion_mode": (True, _parse_bool, False),
    "FLAGS_use_pinned_memory": (True, _parse_bool, False),
    "FLAGS_init_allocated_mem": (False, _parse_bool, False),
    "FLAGS_limit_of_tmp_allocation": (-1, int, False),
}

_VALUES = {}


def _bootstrap():
    """Seed flags from FLAGS_* env vars (reference __bootstrap__)."""
    for name, (default, parser, _impl) in _DEFS.items():
        _VALUES[name] = default
        env = os.environ.get(name)
        if env is None:
            continue
        try:
            _VALUES[name] = parser(env)
        except (ValueError, TypeError):
            warnings.warn(
                f"ignoring malformed env {name}={env!r} (expected "
                f"{parser.__name__}); using default {default!r}")


def _norm(name):
    return name if name.startswith("FLAGS_") else "FLAGS_" + name


def get_flags(names):
    """Read flag values.  names: str or list of str (with or without the
    FLAGS_ prefix).  Returns a dict keyed by the given names."""
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = _norm(n)
        if key not in _VALUES:
            raise KeyError(f"unknown flag {n!r}; known: {sorted(_DEFS)}")
        out[n] = _VALUES[key]
    return out


def set_flags(flags):
    """Set flag values from a dict (paddle.set_flags API shape).  Setting a
    compatibility no-op flag warns that it has no TPU effect."""
    for n, v in flags.items():
        key = _norm(n)
        if key not in _DEFS:
            raise KeyError(f"unknown flag {n!r}; known: {sorted(_DEFS)}")
        _default, parser, implemented = _DEFS[key]
        _VALUES[key] = parser(v) if isinstance(v, str) else v
        if not implemented:
            warnings.warn(f"{key} is accepted for compatibility but has no "
                          f"effect on TPU")


def flag(name):
    """Internal fast accessor used by the executor hot path."""
    return _VALUES[_norm(name)]


_bootstrap()
