"""Data-parallel execution: shard_map over a device mesh.

Reference analog: paddle/fluid/framework/parallel_executor.cc + the
multi_devices_graph_pass (multi_devices_graph_pass.cc:169) which clones every
op onto every GPU, inserts ScaleLossGradOpHandle (1/ndev seed, :267) and one
AllReduceOpHandle per gradient (:594), then schedules the SSA graph with a
thread pool per device and NCCL rings.

TPU-native redesign: ONE program, compiled ONCE under jax.shard_map over a
Mesh({'dp': n}).  The transpiler below performs the same graph rewrite the
reference's pass does — scale the loss-grad seed by 1/ndev, insert a
`c_allreduce_sum` op on every parameter gradient before its optimizer op —
but the collectives lower to lax.psum over ICI and XLA overlaps them with the
backward computation (the fuse_all_reduce/all_reduce_deps passes are subsumed
by XLA's all-reduce combiner).  Feeds are batch-sharded on dim 0; parameters
stay replicated; fetches are concatenated across devices like the reference's
FetchOpHandle (scalar fetches become per-device [n] vectors).
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.fluid.executor import _JitExecutable
from paddle_tpu.fluid.framework import grad_var_name
from . import mesh as pmesh

__all__ = ["DataParallelRunner", "transpile_data_parallel"]


def collective_payload_counter():
    """The one schema for ``pt_collective_payload_bytes_total`` —
    shared by the DP runner's per-step estimate and the hybrid runner's
    ZeRO-gather booking, so the two call sites cannot drift into the
    registry's re-registration conflict."""
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_collective_payload_bytes_total",
        "Estimated per-device ICI payload moved by gradient/BN "
        "collectives (both phases counted; static shapes only)",
        labels=("collective",))


def overlap_buckets_counter():
    """Gradient buckets whose collective dispatched in READY ORDER
    (immediately after the last member gradient was produced, so the ring
    hops overlap the remaining backward compute) — emitted per executed
    step from the transpile-time schedule (docs/OBSERVABILITY.md)."""
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_overlap_buckets_ready_total",
        "Gradient buckets dispatched in ready order (overlap with "
        "backward compute) per step")


def fused_update_bytes_counter():
    """Modeled HBM bytes the fused dequant->update->requant step kernels
    avoid per step (the fp32 intermediate's write+read,
    kernels.fused_update.bytes_saved) — shared by the DP and hybrid
    runners' bookings."""
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_fused_update_bytes_saved_total",
        "Modeled fp32 HBM round-trip bytes avoided by fused "
        "dequant->optimizer-update->requant step kernels")


# optimizer ops the fused-update rewrite can absorb: their Grad input is
# replaced by the bucket's wire-format image (int8 + scales), the update
# dequantizes the member's block-aligned slice inline
_FUSED_UPDATE_OPS = {"sgd": "fused_sgd_quant_grad",
                     "adam": "fused_adam_quant_grad",
                     "adamw": "fused_adamw_quant_grad",
                     "lamb": "fused_lamb_quant_grad",
                     "momentum": "fused_momentum_quant_grad"}


def _plan_quant_buckets(block, grads, prod_index, block_size, bucket_mb):
    """fuse_all_reduce_op_pass analog: group same-dtype grads into fused
    buckets (capped at ``bucket_mb`` MB) so one quantized collective per
    bucket replaces one fp32 collective per grad — per-block scale
    overhead and collective-launch count amortize over the bucket.

    Returns (buckets, leftovers): each bucket is a dict with the member
    grad names (production order), their shapes, dtype, and the op index
    after which the fused ops insert (= last member's producer).
    Leftovers are grads that cannot be bucketed (dynamic shape / no var /
    non-float dtype) and keep the per-grad fp32 allreduce.
    """
    cap_bytes = max(1, int(float(bucket_mb) * (1 << 20)))
    eligible, leftovers = [], []
    for g in sorted(grads, key=lambda g: prod_index[g]):
        v = block._find_var_recursive(g)
        shape = tuple(v.shape) if (v is not None and v.shape) else None
        dtype = v.dtype if v is not None else None
        if (shape is None or any(d is None or d < 0 for d in shape)
                or dtype not in ("float32", "float16", "bfloat16")):
            leftovers.append(g)
            continue
        eligible.append((g, shape, dtype))

    itemsize = {"float32": 4, "float16": 2, "bfloat16": 2}
    buckets = []
    open_by_dtype = {}
    for g, shape, dtype in eligible:
        nbytes = int(np.prod(shape)) * itemsize[dtype]
        b = open_by_dtype.get(dtype)
        if b is None or b["bytes"] + nbytes > cap_bytes:
            b = {"grads": [], "shapes": [], "dtype": dtype, "bytes": 0,
                 "insert_at": -1}
            buckets.append(b)
            open_by_dtype[dtype] = b
        b["grads"].append(g)
        b["shapes"].append(list(shape))
        b["bytes"] += nbytes
        b["insert_at"] = max(b["insert_at"], prod_index[g])
    return buckets, leftovers


def _plan_fused_updates(block, buckets, block_size):
    """Fused-update eligibility (FLAGS_fused_update): a bucket qualifies
    when EVERY member gradient has exactly one consumer in the original
    program and that consumer is an sgd/adam op taking it as `Grad` —
    then the bucket's collective can keep the wire format
    (`c_allreduce_quant_keep`), the uncoalesce disappears, and each
    optimizer op is rewritten to its fused dequant→update variant.  Any
    other consumer (gradient clip, weight decay reading the raw grad, a
    fetch-feeding op) keeps the whole bucket on the unfused path: with
    the uncoalesce gone, nothing would rewrite the member var to its
    reduced value.  Returns {id(optimizer op): (bucket, grad)} and
    annotates qualifying buckets with block-aligned member offsets."""
    member = {g: b for b in buckets for g in b["grads"]}
    consumers = {}
    for op in block.ops:
        for g in set(op.input_arg_names):
            if g in member:
                consumers.setdefault(g, []).append(op)
    rewrites = {}
    bs_q = int(block_size)
    for b in buckets:
        ops_for = []
        for g in b["grads"]:
            cons = consumers.get(g, [])
            if (len(cons) == 1 and cons[0].type in _FUSED_UPDATE_OPS
                    and cons[0].inputs.get("Grad") == [g]):
                ops_for.append(cons[0])
            else:
                ops_for = None
                break
        if not ops_for:
            continue
        # block-aligned packing: each member starts on a quantization
        # block boundary so its slice of the wire image is whole blocks
        off, offsets = 0, []
        for s in b["shapes"]:
            offsets.append(off // bs_q)
            numel = int(np.prod(s))
            off += numel + (-numel) % bs_q
        raw = sum(int(np.prod(s)) for s in b["shapes"])
        if off > 2 * raw:
            # sub-block members: alignment padding would more than double
            # the wire payload — the HBM round-trip saved is worth less
            # than the extra ICI bytes, keep the unfused form (the same
            # size-adaptivity the ZeRO gather's sub-block gate applies)
            continue
        b["fused_update"] = True
        b["offsets"], b["aligned_elems"] = offsets, off
        for g, op in zip(b["grads"], ops_for):
            rewrites[id(op)] = (b, g)
    return rewrites


def _create_bucket_vars(block, buckets, num_devices, block_size,
                        quant_algo, quant_crossover_kb):
    """Resolve each bucket's collective algorithm (stamped once, used by
    the emission, the wire-bytes accounting and the q-var shapes) and
    create the fused buffer — plus, for fused-update buckets, the
    wire-format output vars of `c_allreduce_quant_keep` with the exact
    padded shapes the lowering produces."""
    from paddle_tpu.kernels import quantized_collectives as qc
    from paddle_tpu.kernels.ring_collectives import select_allreduce_algo

    bs_q = int(block_size)
    for k, b in enumerate(buckets):
        b["elements"] = (b["aligned_elems"] if b.get("fused_update")
                         else sum(int(np.prod(s)) for s in b["shapes"]))
        b["algo"] = select_allreduce_algo(
            b["elements"], num_devices, algo=quant_algo,
            crossover_kb=quant_crossover_kb, block_size=bs_q)
        b["fused"] = block.create_var(
            name=f"@FUSED_GRAD_QUANT@_{b['dtype']}_{k}",
            dtype=b["dtype"], shape=[b["elements"]])
        if b.get("fused_update"):
            padded = qc.quant_padded_elems(b["elements"], num_devices,
                                           bs_q, algo=b["algo"])
            base = f"@FUSED_GRAD_QUANT@_{b['dtype']}_{k}"
            b["qhi"] = block.create_var(name=base + "@QHI", dtype="int8",
                                        shape=[padded])
            b["qlo"] = block.create_var(name=base + "@QLO", dtype="int8",
                                        shape=[padded])
            b["qsc"] = block.create_var(name=base + "@QSCALE",
                                        dtype="float32",
                                        shape=[padded // bs_q])


def _make_fused_update_op(block, op, b, g, block_size):
    """Rewrite one sgd/adam op into its fused dequant→update variant:
    the `Grad` input becomes the bucket's wire-format triple plus the
    member's block offset/size attrs (kernels/fused_update.py)."""
    from paddle_tpu.fluid.framework import Operator

    i = b["grads"].index(g)
    inputs = {slot: list(names) for slot, names in op.inputs.items()
              if slot != "Grad"}
    inputs["QHi"] = [b["qhi"].name]
    inputs["QLo"] = [b["qlo"].name]
    inputs["QScale"] = [b["qsc"].name]
    attrs = dict(op.attrs)
    attrs.update(offset_blocks=int(b["offsets"][i]),
                 numel=int(np.prod(b["shapes"][i])),
                 block_size=int(block_size))
    return Operator(block, _FUSED_UPDATE_OPS[op.type], inputs=inputs,
                    outputs={s: list(n) for s, n in op.outputs.items()},
                    attrs=attrs)


def transpile_data_parallel(program, loss_name, num_devices,
                            gradient_scale="coeff_num_device",
                            sync_batch_norm_stats=True,
                            quant_grads=False, quant_block_size=None,
                            quant_bucket_mb=None, quant_algo=None,
                            quant_crossover_kb=None, overlap=None,
                            fused_update=None):
    """Rewrite `program` in place for data-parallel execution.

    Mirrors multi_devices_graph_pass: (1) the loss-gradient seed becomes
    1/ndev, (2) every optimizer-consumed gradient gets a c_allreduce_sum
    (ring 0 = the dp axis), (3) batch-norm running stats are averaged across
    devices so the single written copy is well-defined.

    quant_grads=True (FLAGS_quant_allreduce / DataParallelRunner knob)
    additionally runs the fuse_all_reduce_op_pass analog: same-dtype
    gradients coalesce into a few fused buffers and each buffer takes ONE
    block-scaled int8 `c_allreduce_quant` instead of a per-grad fp32
    `c_allreduce_sum`.  Explicitly excluded from quantization: DGC-encoded
    gradients (already compressed — requantizing would destroy the top-k
    sparsity the reference's SparseAllReduce relies on) and batch-norm
    running stats (small, fp32-averaged, quality-critical); both keep
    their exact collectives.

    quant_algo / quant_crossover_kb (FLAGS_quant_allreduce_algo /
    FLAGS_quant_allreduce_crossover_kb when None): each bucket's
    collective algorithm is resolved HERE — at transpile time, per bucket
    size, via kernels.ring_collectives.select_allreduce_algo — and
    stamped onto the op's `algo` attr, so the lowering runs exactly what
    the wire-bytes accounting models.  "auto"
    sends small buckets through the one-shot O(1)-launch form and large
    ones through the ppermute ring (2*(n-1)/n of payload bytes, int8 on
    every hop) — the BIDIRECTIONAL ring (`ring_bidir`, both ICI
    directions at once) when the bucket clears `bidir_eligible`.

    overlap (FLAGS_overlap_allreduce when None, default ON): READY-ORDER
    bucket dispatch — each bucket's collective is emitted immediately
    after the last gradient it covers is produced (reverse-topological
    order of the backward), so XLA's async collective scheduling can
    overlap the ring hops with the remaining backward compute.  Off =
    every gradient collective (bucketed AND per-grad fp32) defers to
    after the full backward — the no-overlap baseline an on/off A/B of
    this flag compares against.  The schedule lands in
    ``program._overlap_schedule`` (per-bucket insert point + the fraction
    of the backward already executed at dispatch) and feeds
    ``pt_overlap_buckets_ready_total``.

    fused_update (FLAGS_fused_update when None, default ON): buckets
    whose members each feed EXACTLY ONE sgd/adam optimizer op are kept in
    the wire format end to end — members pack block-ALIGNED
    (`coalesce_tensor` attr align), the collective becomes
    `c_allreduce_quant_keep` (int8 + scales out, no final dequant), the
    `uncoalesce_tensor` disappears, and each member's optimizer op is
    rewritten to its fused variant (`fused_adam_quant_grad` /
    `fused_sgd_quant_grad`) that dequantizes its block slice inline with
    the update — the reduced fp32 bucket never round-trips HBM
    (kernels/fused_update.py; saved bytes booked on
    ``pt_fused_update_bytes_saved_total``).  A gradient with any OTHER
    consumer (clip/regularizer/a second op) keeps the unfused form; note
    that fetching a fused-away gradient by name returns the local
    pre-reduce value, since nothing rewrites it in the fused program.
    """
    block = program.global_block()
    if loss_name is not None and gradient_scale == "coeff_num_device":
        seed_name = grad_var_name(loss_name)
        for op in block.ops:
            if op.type == "fill_constant" and seed_name in op.output_arg_names:
                op.attrs["value"] = float(op.attrs.get("value", 1.0)) / num_devices

    # Allreduce each RAW parameter gradient right after it is produced —
    # the reference inserts AllReduceOpHandle at the same point
    # (multi_devices_graph_pass.cc:594), so weight decay / gradient clipping
    # downstream operate on the full (averaged) gradient, not per-device
    # partials.  Raw grad names are recorded by Optimizer.apply_gradients.
    from paddle_tpu.fluid.framework import Operator

    raw_grads = {g for _, g in getattr(program, "_params_grads", [])}
    if not raw_grads:  # fallback: grads feeding optimizer ops directly
        raw_grads = {op.inputs["Grad"][0] for op in block.ops
                     if op.attrs.get("op_role") == "optimize" and "Grad" in op.inputs}
    # DGC moves the allreduce onto the compressed gradient (the reference's
    # SparseAllReduceOpHandle placement): watch the encoded var instead
    dgc_map = getattr(program, "_dgc_encoded", {})
    dgc_encoded = set(dgc_map.values())
    raw_grads = {dgc_map.get(g, g) for g in raw_grads}

    from paddle_tpu.fluid import flags as _flags

    if overlap is None:
        overlap = _flags.flag("overlap_allreduce")
    overlap = bool(overlap)
    if fused_update is None:
        fused_update = _flags.flag("fused_update")
    fused_update = bool(fused_update)

    # producer indices against the ORIGINAL op list (ops are only ever
    # appended after, so indices stay valid while the rewritten list
    # grows); backward_end = the op after which every raw gradient exists
    # (the no-overlap dispatch point), backward_start = the first
    # grad-producing op — ready_frac measures position WITHIN the
    # backward span, else a long forward would inflate every bucket
    # toward 1.0 and the overlap telemetry would read as no-headroom
    prod_index = {}
    backward_start = None
    for i, op in enumerate(block.ops):
        if backward_start is None and any(
                "@GRAD" in n for n in op.output_arg_names):
            backward_start = i
        for g in raw_grads.intersection(op.output_arg_names):
            prod_index[g] = i  # last producer wins
    backward_end = max(prod_index.values()) if prod_index else -1
    if backward_start is None or backward_start > backward_end:
        backward_start = 0

    # plan the quantized buckets
    buckets, bucketed = [], {}
    fused_rewrites = {}  # id(optimizer op) -> (bucket, grad name)
    if quant_grads:
        if quant_block_size is None:
            quant_block_size = _flags.flag("quant_allreduce_block_size")
        if quant_bucket_mb is None:
            quant_bucket_mb = _flags.flag("fuse_grad_size_in_MB")
        if quant_algo is None:
            quant_algo = _flags.flag("quant_allreduce_algo")
        if quant_crossover_kb is None:
            quant_crossover_kb = _flags.flag("quant_allreduce_crossover_kb")
        candidates = {g for g in raw_grads
                      if g in prod_index and g not in dgc_encoded}
        buckets, _left = _plan_quant_buckets(
            block, candidates, prod_index, quant_block_size,
            quant_bucket_mb)
        for b in buckets:
            for g in b["grads"]:
                bucketed[g] = b
        if fused_update and num_devices > 1:
            fused_rewrites = _plan_fused_updates(block, buckets,
                                                 quant_block_size)
        _create_bucket_vars(block, buckets, num_devices, quant_block_size,
                            quant_algo, quant_crossover_kb)

    # standing collective-payload accounting (docs/OBSERVABILITY.md):
    # per-device ICI bytes one step moves, both phases of each collective
    # counted (reduce-scatter + all-gather for fp32, the two int8 phase
    # boundaries for quant) — the runner adds these to
    # pt_collective_payload_bytes_total every step.  Dynamic-shape grads
    # are skipped (estimate, documented as such).
    collective_bytes = {"c_allreduce_sum": 0, "c_allreduce_quant": 0,
                        "c_allreduce_avg": 0}
    _itemsize = {"float32": 4, "float16": 2, "bfloat16": 2, "float64": 8}

    def _static_bytes(name):
        v = block._find_var_recursive(name)
        if v is None or not v.shape or any(
                d is None or d < 0 for d in v.shape):
            return 0
        return int(np.prod(v.shape)) * _itemsize.get(v.dtype, 4)

    quant_plan = {"block_size": int(quant_block_size or 0),
                  "algo": quant_algo, "crossover_kb": quant_crossover_kb,
                  "buckets": []}
    schedule = {"enabled": overlap, "backward_start": backward_start,
                "backward_end": backward_end, "buckets": []}
    bwd_span = max(1, backward_end - backward_start)
    fused_saved_bytes = 0

    def _emit_bucket(b, out, insert_at):
        from paddle_tpu.kernels import fused_update as fu
        from paddle_tpu.kernels import quantized_collectives as qc

        nonlocal fused_saved_bytes
        fused = b["fused"].name
        n_elems, algo = b["elements"], b["algo"]
        is_fused = bool(b.get("fused_update"))
        out.append(Operator(
            block, "coalesce_tensor",
            inputs={"Input": list(b["grads"])},
            outputs={"FusedOutput": [fused]},
            attrs={"dtype": b["dtype"], "op_role": "backward",
                   **({"align": int(quant_block_size)} if is_fused
                      else {})}))
        if is_fused:
            # keep the reduced bucket in the wire format — the rewritten
            # optimizer ops dequantize their block slice inline
            out.append(Operator(
                block, "c_allreduce_quant_keep",
                inputs={"X": [fused]},
                outputs={"QHi": [b["qhi"].name], "QLo": [b["qlo"].name],
                         "QScale": [b["qsc"].name]},
                attrs={"ring_id": 0, "use_calc_stream": True,
                       "block_size": int(quant_block_size),
                       "algo": algo, "op_role": "backward"}))
            fused_saved_bytes += fu.bytes_saved(n_elems)
        else:
            out.append(Operator(
                block, "c_allreduce_quant",
                inputs={"X": [fused]}, outputs={"Out": [fused]},
                attrs={"ring_id": 0, "use_calc_stream": True,
                       "block_size": int(quant_block_size),
                       "algo": algo, "op_role": "backward"}))
            out.append(Operator(
                block, "uncoalesce_tensor",
                inputs={"X": [fused]}, outputs={"Out": list(b["grads"])},
                attrs={"shapes": [list(s) for s in b["shapes"]],
                       "op_role": "backward"}))
        collective_bytes["c_allreduce_quant"] += qc.wire_bytes(
            n_elems, block_size=int(quant_block_size),
            n_devices=num_devices, algo=algo)
        quant_plan["buckets"].append({"elements": n_elems, "algo": algo,
                                      "fused_update": is_fused})
        schedule["buckets"].append({
            "elements": n_elems, "algo": algo, "fused_update": is_fused,
            "insert_at": insert_at,
            # fraction of the BACKWARD SPAN already executed when this
            # bucket's collective dispatches — 1.0 means zero overlap
            "ready_frac": round(min(1.0, max(
                0.0, (insert_at - backward_start) / bwd_span)), 4)
            if backward_end >= 0 else 1.0})

    new_ops = []
    deferred = []  # collectives held back until after the full backward
    pending = set(raw_grads)
    for op_idx, op in enumerate(block.ops):
        if id(op) in fused_rewrites:
            b, g = fused_rewrites[id(op)]
            new_ops.append(_make_fused_update_op(block, op, b, g,
                                                 quant_block_size))
            continue
        new_ops.append(op)
        produced = pending.intersection(op.output_arg_names)
        for g in produced:
            pending.discard(g)
            if g in bucketed:
                continue  # fused collective emitted at the bucket boundary
            ar = Operator(
                block, "c_allreduce_sum",
                inputs={"X": [g]}, outputs={"Out": [g]},
                attrs={"ring_id": 0, "use_calc_stream": True,
                       "op_role": "backward"})
            (new_ops if overlap else deferred).append(ar)
            collective_bytes["c_allreduce_sum"] += 2 * _static_bytes(g)
        for b in buckets:
            if b["insert_at"] == op_idx:
                _emit_bucket(b, new_ops if overlap else deferred,
                             op_idx if overlap else backward_end)
        if not overlap and op_idx == backward_end and deferred:
            # no-overlap baseline: every gradient collective dispatches
            # here, after the last gradient producer
            new_ops.extend(deferred)
            deferred = []
        if sync_batch_norm_stats and op.type == "batch_norm" and not op.attrs.get("is_test"):
            for slot in ("MeanOut", "VarianceOut"):
                names = op.outputs.get(slot, [])
                if names:
                    new_ops.append(Operator(
                        block, "c_allreduce_avg",
                        inputs={"X": [names[0]]}, outputs={"Out": [names[0]]},
                        attrs={"ring_id": 0, "op_role": "forward"}))
                    collective_bytes["c_allreduce_avg"] += \
                        2 * _static_bytes(names[0])
    block.ops = new_ops
    if num_devices <= 1:  # psum over one device moves nothing
        collective_bytes = {k: 0 for k in collective_bytes}
        fused_saved_bytes = 0
    program._collective_bytes_per_step = collective_bytes
    # per-bucket algorithm/size report: BOTH algorithms' modeled bytes
    # beside the one that actually ran
    program._quant_allreduce_plan = quant_plan if quant_grads else None
    # ready-order scheduling report (the transpile summary): feeds
    # pt_overlap_buckets_ready_total
    program._overlap_schedule = schedule if quant_grads else None
    program._fused_update_bytes_saved = fused_saved_bytes
    program._bump_version()
    return program


class DataParallelRunner:
    """Compiles + runs a data-parallel program over all local devices.

    Two execution lanes behind one API (docs/DISTRIBUTED.md "GSPMD
    execution core" decision matrix):

    - transpiler (default): the in-place multi-device graph rewrite
      below plus a shard_map — every gradient collective is an explicit
      program op this runner inserted.
    - gspmd=True (FLAGS_gspmd_executor / BuildStrategy.gspmd_executor):
      the UNmodified program compiles under the one jit-partitioned
      `parallel.gspmd.GSPMDExecutor` with a `DataParallelPolicy` — no
      collective ops inserted by Python, XLA places them all; the
      quantized wire format survives through the quant hook when
      ``quant_grads`` is on.  This runner is then a thin policy
      selection.  Fetch convention difference (documented): global-view
      fetches are the GLOBAL value (the loss is the global-batch mean
      scalar), where the transpiler lane stacks per-device values —
      `np.mean` of a scalar fetch agrees across both.
    """

    def __init__(self, program, loss_name, build_strategy=None, places=None,
                 quant_grads=None, quant_algo=None, overlap=None,
                 fused_update=None, gspmd=None, policy_pin=None):
        import jax

        n = len(places) if places else jax.device_count()
        self.num_devices = n
        self.mesh = pmesh.build_mesh({pmesh.DATA_AXIS: n})
        # autotune pin (docs/AUTOTUNE.md "Pinning"): an explicit pin — a
        # Candidate, a saved report (dict or path) — or the standing
        # FLAGS_autotune_report path overrides the lane/mesh/policy
        # selection below with the tuner's measured winner.
        if policy_pin is None:
            from paddle_tpu.fluid import flags as _flags

            policy_pin = _flags.flag("autotune_report") or None
        self.policy_pin = None
        if policy_pin is not None:
            from . import autotune as _autotune

            pin = _autotune.resolve_pin(policy_pin)
            if pin.n_devices != n:
                raise ValueError(
                    f"autotune pin {pin.label()} was tuned for "
                    f"{pin.n_devices} devices but this runner has {n}")
            self.policy_pin = pin
            gspmd = True          # a pin is always a GSPMD assignment
            quant_grads = pin.quant
            self.mesh = pin.build_mesh()
        # quantized-collective knob: explicit arg > BuildStrategy attr >
        # FLAGS_quant_allreduce (each layer may leave it None = defer)
        if quant_grads is None:
            quant_grads = getattr(build_strategy, "quant_allreduce", None)
        if quant_grads is None:
            from paddle_tpu.fluid import flags as _flags

            quant_grads = _flags.flag("quant_allreduce")
        self.quant_grads = bool(quant_grads)
        # same layering for the algorithm choice; None defers all the way
        # to FLAGS_quant_allreduce_algo inside the transpile — ditto the
        # ready-order overlap, fused-update and gspmd knobs
        if quant_algo is None:
            quant_algo = getattr(build_strategy, "quant_allreduce_algo",
                                 None)
        self.quant_algo = quant_algo
        if overlap is None:
            overlap = getattr(build_strategy, "overlap_allreduce", None)
        if fused_update is None:
            fused_update = getattr(build_strategy, "fused_update", None)
        if gspmd is None:
            gspmd = getattr(build_strategy, "gspmd_executor", None)
        if gspmd is None:
            from paddle_tpu.fluid import flags as _flags

            gspmd = _flags.flag("gspmd_executor")
        self.gspmd = bool(gspmd)
        # graph-optimization passes (FLAGS_graph_passes) run BEFORE any
        # lane transpile — framework.PASS_ORDER's declared contract (the
        # fused-update/bucket scans must see the final forward graph).
        # The gspmd branch applies them inside GSPMDExecutor instead.
        if not self.gspmd:
            from paddle_tpu import passes as _graph_passes

            _graph_passes.apply_graph_passes(program, lane="dp",
                                             loss_name=loss_name)
        self._gspmd_exec = None
        if self.gspmd:
            # GSPMD lane: the program stays UNTOUCHED — the global-view
            # loss mean over the sharded batch already yields averaged
            # gradients, and XLA inserts the collectives.  policy_for is
            # the one selection rule shared with the hybrid runner.
            from .gspmd import GSPMDExecutor, policy_for

            self.program = program
            policy = (self.policy_pin.build_policy()
                      if self.policy_pin is not None
                      else policy_for(self.mesh))
            self._gspmd_exec = GSPMDExecutor(
                program, self.mesh, policy,
                quant_hook=self.quant_grads, quant_algo=quant_algo,
                loss_name=loss_name)
            self._sentinel = None  # the shared executor owns it there
            self._cache = {}
            return
        # rewrite in place, like the reference's multi-device pass
        self.program = transpile_data_parallel(
            program, loss_name, n,
            sync_batch_norm_stats=(build_strategy is None
                                   or getattr(build_strategy, "sync_batch_norm", True) is not False),
            quant_grads=self.quant_grads, quant_algo=quant_algo,
            overlap=overlap, fused_update=fused_update)
        # health sentinel (FLAGS_health_sentinel, docs/DISTRIBUTED.md §6):
        # inserted AFTER the bucket pass so detection rides the fused
        # buckets' wire format (QScale) where they exist
        from paddle_tpu import health

        self._sentinel = health.attach(self.program, loss_name=loss_name,
                                       lane="dp")
        self._cache = {}

    def _cache_key(self, feed, fetch_names):
        feed_sig = tuple(
            (k, tuple(np.shape(v)),
             str(v.dtype if hasattr(v, "dtype") else np.asarray(v).dtype))
            for k, v in sorted(feed.items()))
        return (id(self.program), self.program._version, feed_sig,
                tuple(fetch_names))

    def run(self, executor, feed, fetch_list, scope, return_numpy=True):
        import time as _time

        from paddle_tpu.fluid import executor as ex
        from paddle_tpu.fluid.executor import (_m_cache, _m_compile_seconds,
                                               _record_step)

        from paddle_tpu.observability import profiling as _profiling

        scope = scope or ex.global_scope()
        step = executor._step
        with _profiling.span("lookup", "dp", number=step):
            feed = executor._coerce_feed(self.program, feed or {})
            fetch_names = [f.name if not isinstance(f, str) else f
                           for f in (fetch_list or [])]
            for k, v in feed.items():
                if np.shape(v) and np.shape(v)[0] % self.num_devices != 0:
                    raise ValueError(
                        f"feed {k!r} batch {np.shape(v)[0]} not divisible by "
                        f"{self.num_devices} devices")
            cb = None
            if self._gspmd_exec is None:
                key = self._cache_key(feed, fetch_names)
                cb = self._cache.get(key)
                if cb is not None:
                    _m_cache().labels(path="dp", result="hit").inc()
        if self._gspmd_exec is not None:
            out = self._gspmd_exec.run(scope=scope, feed=feed,
                                       fetch_list=fetch_names,
                                       return_numpy=return_numpy)
            executor._step += 1
            return out
        sent = self._sentinel

        def attempt():
            first_run = not getattr(cb, "_obs_ran", False)
            t0 = _time.perf_counter()  # observability: allow
            fetches = cb.run(scope, feed, executor._step)
            step_s = _time.perf_counter() - t0  # observability: allow
            _record_step("dp", step_s, first_run)
            cb._obs_ran = True
            self._report_throughput(feed, step_s)
            executor._step += 1
            return fetches

        from paddle_tpu.health import run_guarded

        if cb is not None:
            fetches = run_guarded(sent, scope, fetch_names, attempt)
        else:
            # the signature's first run as one `compile` span: says of a
            # slow set-up whether it was a compile, and whose
            with ex.compile_span("dp", number=step) as booked:
                _m_cache().labels(path="dp", result="miss").inc()
                if sent is not None:
                    sent.ensure_state(scope)  # before BlockPlan scope checks
                t0 = _time.perf_counter()  # observability: allow
                cb = _ShardedBlock(self.program, feed.keys(), fetch_names,
                                   self.mesh, scope)
                self._cache[key] = cb
                booked["program"] = cb.plan.name
                _m_compile_seconds().labels(
                    path="dp", phase="trace").inc(_time.perf_counter() - t0)  # observability: allow
                fetches = run_guarded(sent, scope, fetch_names, attempt)
        if return_numpy:
            with _profiling.span("fetch_wait", "dp", number=step):
                return [np.asarray(f) for f in fetches]
        return fetches

    def _report_throughput(self, feed, step_s):
        """Per-step throughput + collective-payload telemetry
        (docs/OBSERVABILITY.md): global examples ingested, last-step
        examples/sec, and the transpiler's per-step ICI byte estimate."""
        from paddle_tpu.fluid.executor import _feed_batch, _report_examples

        _report_examples("dp", _feed_batch(feed), step_s)
        per_step = getattr(self.program, "_collective_bytes_per_step", None)
        if per_step:
            fam = collective_payload_counter()
            for coll, nbytes in per_step.items():
                if nbytes:
                    fam.labels(collective=coll).inc(nbytes)
        sched = getattr(self.program, "_overlap_schedule", None)
        if sched and sched["enabled"] and sched["buckets"]:
            overlap_buckets_counter().inc(len(sched["buckets"]))
        saved = getattr(self.program, "_fused_update_bytes_saved", 0)
        if saved:
            fused_update_bytes_counter().inc(saved)

    def cost_analysis(self, executor, feed, fetch_list=None, scope=None):
        """XLA cost/memory analysis of the sharded step executable (the
        single-device Executor.cost_analysis counterpart): flops and
        bytes accessed.
        The (feed, fetch) signature must have run once already."""
        from paddle_tpu.fluid import executor as ex

        scope = scope or ex.global_scope()
        feed = executor._coerce_feed(self.program, feed or {})
        fetch_names = [f.name if not isinstance(f, str) else f
                       for f in (fetch_list or [])]
        if self._gspmd_exec is not None:
            return self._gspmd_exec.cost_analysis(feed,
                                                  fetch_list=fetch_names,
                                                  scope=scope)
        cb = self._cache.get(self._cache_key(feed, fetch_names))
        if cb is None:
            raise ValueError(
                "no compiled data-parallel executable for this (feed, "
                "fetch_list) signature — run the step once first")
        return cb.cost_analysis(scope, feed)

    def lower(self, executor, feed, fetch_list=None, scope=None, mesh=None):
        """AOT-lower the sharded step :meth:`run` would dispatch, over
        ``mesh`` (default: this runner's) with parameters replicated and
        feeds batch-split — a mesh of ``jax.experimental.topologies``
        devices compiles the four-chip step without a chip
        (_JitExecutable.lower).  Transpiler lane only."""
        from paddle_tpu.fluid import executor as ex

        from .gspmd.specs import named_sharding

        scope = scope or ex.global_scope()
        feed = executor._coerce_feed(self.program, feed or {})
        fetch_names = [f.name if not isinstance(f, str) else f
                       for f in (fetch_list or [])]
        mesh = mesh or self.mesh
        cb = _ShardedBlock(self.program, feed.keys(), fetch_names, mesh,
                           scope)
        return cb.lower(scope, feed, (
            named_sharding(mesh, ()),
            named_sharding(mesh, (pmesh.DATA_AXIS,))))


class _ShardedBlock(_JitExecutable):
    """One (program-version, feed-signature) → sharded XLA executable.
    _JitExecutable supplies cost_analysis/_jit_args over the shared
    (donated, readonly, feeds, step) calling convention, so the sharded
    executable introspects exactly like the single-device one."""

    def __init__(self, program, feed_names, fetch_names, mesh, scope):
        import jax
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.fluid.executor import BlockPlan

        plan = BlockPlan(program, program.global_block(), feed_names,
                         fetch_names, scope)
        if plan.host_pre_ops:
            raise NotImplementedError(
                "pre-stage host ops (distributed lookup) are only "
                "supported by the single-device Executor")
        self.plan = plan
        self.feed_names = plan.feed_names
        self.fetch_names = plan.fetch_names
        self.ops = plan.ops
        self.donated_names = plan.donated_names
        self.readonly_names = plan.readonly_names
        self.write_names = plan.write_names
        axis = pmesh.DATA_AXIS
        from paddle_tpu.health import wrap_body as _health_gate

        # the health gate sits INSIDE the shard_map: found_inf is
        # computed from post-allreduce (replica-identical) gradients, so
        # the masking needs no extra collective
        inner = _health_gate(program, plan.make_body(mesh_axes=(axis,)))

        def body(donated, readonly, feeds, step):
            import jax.numpy as jnp

            raw_fetches, out_writes = inner(donated, readonly, feeds, step)
            # scalar fetches become per-device [1] vectors so the dp-axis
            # concat (FetchOpHandle semantics) has a dim to stack on
            fetches = [jnp.reshape(v, (1,) + tuple(jnp.shape(v)))
                       if jnp.ndim(v) == 0 else v for v in raw_fetches]
            return fetches, out_writes

        in_specs = (
            {n: P() for n in self.donated_names},
            {n: P() for n in self.readonly_names},
            {n: P(axis) for n in self.feed_names},
            P(),
        )
        out_specs = ([P(axis) for _ in plan.jit_fetch_names],
                     {n: P() for n in self.write_names})
        sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=False)
        # same naming rule as the single-device lane: jit_train_step
        sharded.__name__ = sharded.__qualname__ = plan.name
        self._jitted = jax.jit(sharded, donate_argnums=(0,))
        self.mesh = mesh
        self.label = f"dp_block@{id(self):x}"

    def run(self, scope, feeds, step):
        import warnings

        from paddle_tpu.fluid import profiler as _prof
        from paddle_tpu.observability import profiling as _profiling

        if not hasattr(self, "_prof_state"):
            self._prof_state = {"ran": False}
        # step_phases outermost; timed_run keeps its historic region
        # (staging..scope-writes) so the "run" span never absorbs the
        # host RPC tail — fetch_sync brackets accumulate across both
        with _profiling.step_phases("dp", self.label, number=step) as ph:
            with _prof.timed_run(f"dp_block@{id(self):x}",
                                 self._prof_state) as timer:
                with ph.phase("feed_prep"):
                    donated = {n: scope.get(n)
                               for n in self.donated_names}
                    readonly = {n: scope.get(n)
                                for n in self.readonly_names}
                with ph.phase("dispatch"):
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore")
                        fetches, out_writes = self._jitted(
                            donated, readonly, dict(feeds),
                            np.uint32(step))
                    # counted in the in-flight ledger and never marked done:
                    # the chip is not known empty while this lane runs
                    _profiling.enqueued(self.label)
                with ph.phase("device_wait"):
                    ph.wait((fetches, out_writes))
                with ph.phase("fetch_sync"):
                    for n, v in out_writes.items():
                        scope.set(n, v)
                    timer.done(fetches, out_writes)
            with ph.phase("fetch_sync"):
                # PS-mode programs carry host RPC ops — run them, don't
                # drop them
                self.plan.run_host_ops(scope)
                out = self.plan.assemble_fetches(fetches, scope)
                del donated, readonly  # freed inside the span
        return out
