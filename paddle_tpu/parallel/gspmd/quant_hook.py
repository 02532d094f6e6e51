"""Quantized gradient reduction INSIDE the partitioned graph.

The transpiler lane routes gradients through explicit `c_allreduce_quant`
ops.  The GSPMD executor inserts no collective ops at all — XLA reduces
gradients implicitly because the loss is a mean over the globally-sharded
batch — which would silently drop the EQuARX dual-int8 wire format
(arXiv:2506.17615) the comms lane depends on.  This module puts it back
without touching the program:

  **shard_map island** (the 0.4.3x-safe default): the executor splits the
  pruned op list at the gradient frontier (the last raw-gradient
  producer).  Forward+backward trace inside ONE `jax.shard_map` mapped
  over the batch axis, so each device computes the partial gradients of
  its local batch shard; the island scales them by 1/n (the transpiler's
  ScaleLossGradOp seed, applied at the boundary — backward is linear in
  the seed) and reduces the same-dtype concatenation through
  `kernels.ring_collectives.adaptive_quantized_all_reduce` — identical
  FLAGS_quant_allreduce semantics: block size, algorithm selection,
  crossover, `wire_bytes` accounting.  The optimizer leg then traces in
  global view where the policy's sharding specs (ZeRO-1) partition it.
  The island is manual partitioning embedded inside the jit-partitioned
  computation — exactly the "shard_map island" escape GSPMD reserves for
  collectives XLA cannot be trusted to pick.

  **custom_partitioning** (`FLAGS_gspmd_quant_impl=custom_partitioning`,
  ``auto`` selects it on TPU backends): the island instead emits the
  per-device partials STACKED over the batch axis, and the reduction is a
  `jnp.sum(axis=0)` carrying a `jax.custom_partitioning` rule whose
  per-device lowering is the quantized ring — GSPMD integrates (and can
  reschedule) the reduction like any other partitioned op.  Documented
  fallback: the jaxlib-0.4.3x XLA:CPU GSPMD lane miscompiles
  custom-partitioned calls (the same line that aborts multi-axis GSPMD,
  see tests/cpu_mesh.py), so ``auto`` never picks it off-TPU and a build
  failure demotes to the island with a warning.

Contract and limits (docs/DISTRIBUTED.md "GSPMD execution core"):

  - Applies to float gradients only; DGC-encoded gradients keep the
    exact fp32 psum (requantizing a top-k-sparse payload destroys it).
  - Demotes itself (warning) on policies that shard parameters over a
    non-batch axis: the island maps only the batch axis, so a
    model-split parameter would be materialized full-size per device —
    defeating tensor parallelism to quantize its gradient.
  - batch_norm running stats produced in the island are averaged across
    the axis (the transpiler's c_allreduce_avg semantics); other
    island-produced carries are computed from replicated inputs and
    leave as-is.
  - Fetches produced by the forward/backward stack per-device over the
    batch axis — the DataParallelRunner's FetchOpHandle convention, so
    loss parity gates compare like with like.
"""

from __future__ import annotations

import warnings

import numpy as np

from paddle_tpu.fluid.framework import grad_var_name  # noqa: F401 (doc ref)
from .. import mesh as pmesh

__all__ = ["QuantHookPlan", "plan_quant_hook", "resolve_quant_impl"]

_QUANT_IMPLS = ("auto", "shard_map", "custom_partitioning")


def resolve_quant_impl(impl=None):
    """Resolve FLAGS_gspmd_quant_impl: ``auto`` = custom_partitioning
    where traces lower for a TPU, the shard_map island everywhere
    else."""
    if impl in (None, "auto"):
        from paddle_tpu.fluid import flags as _flags

        impl = _flags.flag("gspmd_quant_impl")
    if impl not in _QUANT_IMPLS:
        raise ValueError(
            f"gspmd_quant_impl must be one of {_QUANT_IMPLS}, got {impl!r}")
    if impl != "auto":
        return impl
    from paddle_tpu.fluid.platform_utils import is_tpu

    return "custom_partitioning" if is_tpu() else "shard_map"


class QuantHookPlan:
    """The executor-side compilation plan for one hooked program: the
    op-list split, the gradient/carry/fetch classification, and the
    modeled per-step wire bytes (booked by the executor on
    ``pt_collective_payload_bytes_total{collective="c_allreduce_quant"}``,
    the same family the transpiler path uses)."""

    def __init__(self, plan, program, mesh, axis, block_size, algo,
                 crossover_kb, impl, fused_update=None):
        self.plan = plan
        self.program = program
        self.mesh = mesh
        self.axis = axis
        self.n = int(mesh.shape[axis])
        self.block_size = int(block_size)
        self.algo = algo
        self.crossover_kb = crossover_kb
        self.impl = impl
        if fused_update is None:
            from paddle_tpu.fluid import flags as _flags

            fused_update = _flags.flag("fused_update")
        self.fused_update = bool(fused_update)
        # per-feed island in_spec axes, set by the executor from its
        # RESOLVED feed specs (feed_specs override > policy.feed_spec,
        # projected onto the batch axis — the only axis the island
        # maps); default: dim 0 on the batch axis
        self.feed_island_specs = {}
        self._classify()
        self._plan_fused_updates()
        self._model_wire_bytes()

    # -- planning ------------------------------------------------------
    def _classify(self):
        plan, program = self.plan, self.program
        block = plan.block
        raw = {g for _, g in getattr(program, "_params_grads", [])}
        if not raw:
            raw = {op.inputs["Grad"][0] for op in plan.ops
                   if op.attrs.get("op_role") == "optimize"
                   and "Grad" in op.inputs}
        # _dgc_encoded maps RAW grad name -> encoded var name; the raw
        # names are what `raw` holds here (no transpiler remap on this
        # lane), so exempt by KEY — values included for robustness
        # against a caller that pre-remapped
        dgc_map = getattr(program, "_dgc_encoded", {})
        dgc = set(dgc_map.keys()) | set(dgc_map.values())
        prod = {}
        for i, op in enumerate(plan.ops):
            for g in raw.intersection(op.output_arg_names):
                prod[g] = i
        if not prod:
            raise ValueError(
                "gspmd quant hook: program has no raw parameter "
                "gradients (forward-only or optimizer-less program)")
        self.cut = max(prod.values()) + 1
        self.ops_fwdbwd = plan.ops[: self.cut]
        self.ops_opt = plan.ops[self.cut:]
        produced1 = set()
        for op in self.ops_fwdbwd:
            produced1.update(op.output_arg_names)
        consumed2 = set()
        for op in self.ops_opt:
            consumed2.update(op.input_arg_names)
        self.grads = sorted(g for g in raw if g in produced1)
        self.exact_grads = [g for g in self.grads if g in dgc]
        quant = []
        for g in self.grads:
            v = block._find_var_recursive(g)
            dt = v.dtype if v is not None else None
            if g in dgc or dt not in ("float32", "float16", "bfloat16"):
                if g not in self.exact_grads:
                    self.exact_grads.append(g)
            else:
                quant.append(g)
        self.quant_grads = quant
        gset = set(self.grads)
        # values the optimizer leg (or the scope write-back / fetch
        # assembly) needs from the island, beyond the gradients
        self.carries = sorted(
            (consumed2 | set(plan.write_names)).intersection(produced1)
            - gset)
        # gradient fetches are NOT island fetches: the reduced gradient
        # is replicated, and fetching it from the post-reduction env
        # keeps the value (global mean) and shape identical across the
        # shard_map and custom_partitioning impls — stacking the
        # island-local value would return raw unscaled partials on the
        # cp impl, where reduction happens outside the island
        self.island_fetches = [n for n in plan.jit_fetch_names
                               if n in produced1 and n not in gset]
        # batch_norm running stats get the transpiler's c_allreduce_avg
        self.mean_carries = set()
        for op in self.ops_fwdbwd:
            if op.type == "batch_norm" and not op.attrs.get("is_test"):
                for slot in ("MeanOut", "VarianceOut"):
                    for n in op.outputs.get(slot, []):
                        if n in self.carries:
                            self.mean_carries.add(n)
        # scope vars the island stage reads (the optimizer leg reads
        # straight from the body's full scope_vals dict)
        reads1 = set()
        scope_vars = set(plan.donated_names) | set(plan.readonly_names)
        for op in self.ops_fwdbwd:
            reads1.update(set(op.input_arg_names) & scope_vars)
        self.scope_reads_island = sorted(reads1)

    # fused dequant→update→requant leg (the DP transpiler rewrite ported
    # to this lane, plan-level — the PROGRAM stays unrewritten, the
    # "zero c_allreduce ops in program" contract holds)
    _FUSED_OPT_TYPES = {"sgd": "fused_sgd_quant_grad",
                        "adam": "fused_adam_quant_grad",
                        "adamw": "fused_adamw_quant_grad",
                        "lamb": "fused_lamb_quant_grad",
                        "momentum": "fused_momentum_quant_grad"}
    FUSED_Q_HI = "@GSPMD_FUSED_Q@HI"
    FUSED_Q_LO = "@GSPMD_FUSED_Q@LO"
    FUSED_Q_SCALE = "@GSPMD_FUSED_Q@SCALE"

    def _plan_fused_updates(self):
        """FLAGS_fused_update on this lane: quant grads whose ONLY
        consumer is one sgd/adam/adamw/momentum op keep the reduced
        bucket in the wire format (``adaptive_quantized_all_reduce_keep``
        inside the island) and their optimizer ops are replaced — in the
        TRACE op list only, never the program — by the fused
        ``*_quant_grad`` forms that dequant their block slice inline.

        Demotions (each leaves the grad on the plain dequantized path):
        a second consumer (gradient clip, a health_check op covering raw
        grads — the sentinel's detection surface), a fetch of the grad,
        the custom_partitioning impl (its reducer returns one fp32
        tensor; the keep-quant form is island-only), 1-device axes, and
        alignment bloat past 2x the raw payload (the DP transpiler's
        sub-block guard)."""
        self.fused_grads = []
        self.plain_quant_grads = list(self.quant_grads)
        self.ops_opt_fused = list(self.ops_opt)
        self.fused_offsets = {}
        self.fused_elems = 0
        self.fused_bytes_saved = 0
        if (not self.fused_update or self.n <= 1
                or self.impl == "custom_partitioning"
                or not self.quant_grads):
            return
        from paddle_tpu.fluid.framework import Operator
        from paddle_tpu.kernels import fused_update as fu

        block = self.plan.block
        consumers = {}
        for op in self.plan.ops:
            for g in set(op.input_arg_names):
                if g in self.quant_grads:
                    consumers.setdefault(g, []).append(op)
        fetched = set(self.plan.jit_fetch_names)
        opt_ids = {id(op) for op in self.ops_opt}
        cand = []
        for g in self.quant_grads:
            cons = consumers.get(g, [])
            if (g not in fetched and len(cons) == 1
                    and id(cons[0]) in opt_ids
                    and cons[0].type in self._FUSED_OPT_TYPES
                    and cons[0].inputs.get("Grad") == [g]):
                cand.append((g, cons[0]))
        if not cand:
            return
        bs = self.block_size
        off, offsets, shapes = 0, {}, {}
        raw = 0
        for g, _op in cand:
            v = block._find_var_recursive(g)
            numel = int(np.prod(v.shape))
            shapes[g] = tuple(v.shape)
            offsets[g] = off // bs
            raw += numel
            off += numel + (-numel) % bs
        if off > 2 * raw:
            return  # alignment bloat: keep the plain path (DP guard)
        rewritten = {}
        for g, op in cand:
            inputs = {slot: list(names) for slot, names in op.inputs.items()
                      if slot != "Grad"}
            inputs["QHi"] = [self.FUSED_Q_HI]
            inputs["QLo"] = [self.FUSED_Q_LO]
            inputs["QScale"] = [self.FUSED_Q_SCALE]
            attrs = dict(op.attrs)
            attrs.update(offset_blocks=int(offsets[g]),
                         numel=int(np.prod(shapes[g])),
                         block_size=int(bs))
            rewritten[id(op)] = Operator(
                block, self._FUSED_OPT_TYPES[op.type], inputs=inputs,
                outputs={s: list(n) for s, n in op.outputs.items()},
                attrs=attrs)
        self.ops_opt_fused = [rewritten.get(id(op), op)
                              for op in self.ops_opt]
        self.fused_grads = [g for g, _op in cand]
        self.plain_quant_grads = [g for g in self.quant_grads
                                  if g not in set(self.fused_grads)]
        self.fused_offsets = offsets
        self.fused_elems = off
        self.fused_bytes_saved = fu.bytes_saved(off)

    def _model_wire_bytes(self):
        from paddle_tpu.kernels import quantized_collectives as qc
        from paddle_tpu.kernels.ring_collectives import select_allreduce_algo

        block = self.plan.block
        total, buckets = 0, []
        if self.n > 1:
            elems = 0
            for g in self.plain_quant_grads:
                v = block._find_var_recursive(g)
                if v is not None and v.shape and not any(
                        d is None or d < 0 for d in v.shape):
                    elems += int(np.prod(v.shape))
            for nelems, fused in ((elems, False),
                                  (self.fused_elems, True)):
                if not nelems:
                    continue
                resolved = select_allreduce_algo(
                    nelems, self.n, algo=self.algo,
                    crossover_kb=self.crossover_kb,
                    block_size=self.block_size)
                total += qc.wire_bytes(nelems, block_size=self.block_size,
                                       n_devices=self.n, algo=resolved)
                buckets.append({"elements": nelems, "algo": resolved,
                                "fused_update": fused})
        self.wire_bytes_per_step = total
        self.bucket_report = buckets

    # -- the reduction -------------------------------------------------
    def _reduce_quant_bucket(self, env):
        """Concatenate the plain (non-fused) quantizable gradients into
        one bucket — the fuse_all_reduce analog at trace level — scale
        by 1/n, reduce on the adaptive dual-int8 ring, split back."""
        import jax.numpy as jnp

        from paddle_tpu.kernels.ring_collectives import (
            adaptive_quantized_all_reduce)

        if not self.plain_quant_grads:
            return
        shapes = [jnp.shape(env[g]) for g in self.plain_quant_grads]
        flat = jnp.concatenate(
            [jnp.ravel(env[g]).astype(jnp.float32)
             for g in self.plain_quant_grads]) / self.n
        red = adaptive_quantized_all_reduce(
            flat, self.axis, block_size=self.block_size,
            algo=self.algo or "auto", crossover_kb=self.crossover_kb)
        off = 0
        for g, s in zip(self.plain_quant_grads, shapes):
            size = int(np.prod(s)) if s else 1
            env[g] = red[off:off + size].reshape(s).astype(env[g].dtype)
            off += size

    def _reduce_fused_bucket(self, env):
        """Reduce the fused-update bucket KEEPING the wire format: each
        member pads to a block boundary (the dequant_slice layout the
        rewritten optimizer ops address by ``offset_blocks``), the
        concatenation scales by 1/n and rides
        ``adaptive_quantized_all_reduce_keep`` — the reduced fp32 bucket
        never materializes (the DP lane's ``c_allreduce_quant_keep``
        semantics, at trace level)."""
        import jax.numpy as jnp

        from paddle_tpu.kernels.ring_collectives import (
            adaptive_quantized_all_reduce_keep)

        if not self.fused_grads:
            return {}
        bs = self.block_size
        parts = []
        for g in self.fused_grads:
            flat = jnp.ravel(env[g]).astype(jnp.float32)
            pad = (-flat.size) % bs
            if pad:
                flat = jnp.pad(flat, (0, pad))
            parts.append(flat)
        bucket = jnp.concatenate(parts) / self.n
        hi, lo, sc = adaptive_quantized_all_reduce_keep(
            bucket, self.axis, block_size=bs, algo=self.algo or "auto",
            crossover_kb=self.crossover_kb)
        return {self.FUSED_Q_HI: hi, self.FUSED_Q_LO: lo,
                self.FUSED_Q_SCALE: sc}

    def _reduce_exact(self, env):
        from jax import lax

        for g in self.exact_grads:
            # exact fp32 mean for payloads the wire format must not
            # touch (DGC-encoded, non-float) — transpiler parity
            env[g] = lax.psum(env[g] / self.n, self.axis)  # collective: allow

    def _average_carries(self, env):
        from jax import lax

        for n in self.mean_carries:
            # batch_norm running stats: the transpiler's c_allreduce_avg
            env[n] = lax.pmean(env[n], self.axis)  # collective: allow

    # -- body construction ----------------------------------------------
    def island_body(self, trace_stage):
        """Build fn(scope_vals, feeds, step) -> (carry, grads, stacked
        fetches) where the forward+backward trace runs under shard_map
        over the batch axis and gradients leave reduced (shard_map impl)
        or as ONE stacked partial bucket (custom_partitioning impl — the
        same concatenated bucket the island impl and the wire-bytes
        model use, so the metric books what actually moves).
        ``trace_stage(env, step, ops)`` is the executor's trace callback
        (one LowerContext assembly point, shared with the global-view
        stage)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        axis, n = self.axis, self.n
        cp = self.impl == "custom_partitioning" and n > 1
        carries = self.carries
        fused = set(self.fused_grads)
        # fused grads leave as the wire triple, never as fp32 tensors
        gset = [g for g in self.grads if g not in fused]
        fetches = self.island_fetches
        # the trace records each quant grad's (shape, dtype) here so the
        # post-island bucket split (with_cp_reduce below, traced strictly
        # AFTER the island in the same jit trace) can restore them
        meta = {}

        def island(scope_vals, feeds, step):
            env = dict(scope_vals)
            env.update(feeds)
            trace_stage(env, step, self.ops_fwdbwd, mesh_axes=(axis,))
            if cp:
                # exact grads leave as raw [1, ...] partials (the
                # P(axis) out_spec CONCATENATES on dim 0, so the stacked
                # global is [n, ...] and a plain sum is the exact fp32
                # reduction); quant grads leave as ONE flat [1, total]
                # bucket the custom_partitioning sum reduces on the ring
                grads = {g: jnp.reshape(env[g],
                                        (1,) + tuple(jnp.shape(env[g])))
                         for g in self.exact_grads}
                bucket = None
                if self.quant_grads:
                    meta["quant"] = [(jnp.shape(env[g]), env[g].dtype)
                                     for g in self.quant_grads]
                    bucket = jnp.reshape(jnp.concatenate(
                        [jnp.ravel(env[g]).astype(jnp.float32)
                         for g in self.quant_grads]), (1, -1))
            else:
                self._reduce_quant_bucket(env)
                self._reduce_exact(env)
                grads = {g: env[g] for g in gset}
                bucket = None
                fusedq = self._reduce_fused_bucket(env)
            self._average_carries(env)
            carry = {c: env[c] for c in carries if c in env}
            stacked = [jnp.reshape(env[f], (1,) + tuple(jnp.shape(env[f])))
                       if jnp.ndim(env[f]) == 0 else env[f]
                       for f in fetches]
            if cp:
                fusedq = {}
            return carry, grads, bucket, fusedq, stacked

        in_specs = (
            {nme: P() for nme in self.scope_reads_island},
            # honor the executor's resolved feed placement, projected
            # onto the batch axis: a feed the user declared replicated
            # (a shared table) enters the island WHOLE, not sliced
            {nme: P(*self.feed_island_specs.get(nme, (axis,)))
             for nme in self.plan.feed_names},
            P(),
        )
        grad_names = self.exact_grads if cp else gset
        bucket_spec = P(axis) if (cp and self.quant_grads) else None
        # the keep-quant wire triple is replica-identical post-reduction
        fusedq_names = ((self.FUSED_Q_HI, self.FUSED_Q_LO,
                         self.FUSED_Q_SCALE)
                        if (not cp and self.fused_grads) else ())
        out_specs = ({c: P() for c in carries},
                     {g: (P(axis) if cp else P()) for g in grad_names},
                     bucket_spec,
                     {nme: P() for nme in fusedq_names},
                     [P(axis) for _ in fetches])
        mapped = jax.shard_map(island, mesh=self.mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        if not cp:
            def plain(scope_vals, feeds, step):
                carry, grads, _bucket, fusedq, stacked = mapped(
                    scope_vals, feeds, step)
                return carry, grads, fusedq, stacked

            return plain

        reduce_quant = _cp_sum_reducer(
            self.mesh, axis, self.block_size, self.algo,
            self.crossover_kb)

        def with_cp_reduce(scope_vals, feeds, step):
            carry, grads, bucket, _fusedq, stacked = mapped(
                scope_vals, feeds, step)
            # exact grads: stacked partials [n, ...] — sum is the exact
            # fp32 reduction, scale folded in
            out = {g: jnp.sum(v, axis=0) / n for g, v in grads.items()}
            if bucket is not None:
                red = reduce_quant(bucket / n)  # [total], ring-reduced
                off = 0
                for g, (shape, dtype) in zip(self.quant_grads,
                                             meta["quant"]):
                    size = int(np.prod(shape)) if shape else 1
                    out[g] = red[off:off + size].reshape(shape) \
                        .astype(dtype)
                    off += size
            return carry, out, {}, stacked

        return with_cp_reduce


def _cp_sum_reducer(mesh, axis, block_size, algo, crossover_kb):
    """`jnp.sum(x, axis=0)` over shard-stacked partials, carrying a
    `custom_partitioning` rule whose per-device lowering is the
    dual-int8 adaptive ring — the TPU-native spelling of the hook."""
    import jax.numpy as jnp
    from jax.experimental.custom_partitioning import (
        custom_partitioning as cp)

    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.kernels.ring_collectives import (
        adaptive_quantized_all_reduce)

    @cp
    def qsum(x):
        return jnp.sum(x, axis=0)

    def _infer(mesh_, arg_shapes, result_shape):
        return NamedSharding(mesh, P())

    def _partition(mesh_, arg_shapes, result_shape):
        arg_sh = (NamedSharding(mesh, P(axis)),)
        res_sh = NamedSharding(mesh, P())

        def lower_fn(x):
            local = jnp.sum(x, axis=0)  # this shard's partial(s)
            return adaptive_quantized_all_reduce(
                local, axis, block_size=block_size, algo=algo or "auto",
                crossover_kb=crossover_kb)

        return mesh, lower_fn, res_sh, arg_sh

    qsum.def_partition(partition=_partition,
                       infer_sharding_from_operands=_infer)
    return qsum


def plan_quant_hook(plan, program, mesh, policy, block_size=None,
                    algo=None, crossover_kb=None, impl=None):
    """Build the QuantHookPlan for one compilation, or None when the hook
    must stay off: 1-device batch axis (nothing to reduce), a policy that
    shards parameters over a non-batch axis (island would defeat TP), or
    a program without raw gradients.  Demotions warn — silent fp32 wire
    traffic is the failure mode this hook exists to prevent."""
    from paddle_tpu.fluid import flags as _flags

    axis = policy.batch_axis
    if axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return None
    if policy.uses_model_axis(program, mesh):
        warnings.warn(
            "gspmd quant hook demoted: the policy shards parameters over "
            "a non-batch axis and the hook's island maps only the batch "
            "axis — gradient reduction stays on XLA's fp32 collectives")
        return None
    if block_size is None:
        block_size = _flags.flag("quant_allreduce_block_size")
    if algo is None:
        algo = _flags.flag("quant_allreduce_algo")
    if crossover_kb is None:
        crossover_kb = _flags.flag("quant_allreduce_crossover_kb")
    try:
        return QuantHookPlan(plan, program, mesh, axis, block_size, algo,
                             crossover_kb, resolve_quant_impl(impl))
    except ValueError as e:
        warnings.warn(f"gspmd quant hook demoted: {e}")
        return None
