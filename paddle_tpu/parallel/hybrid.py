"""Hybrid parallelism: GSPMD partitioning of a whole Program over a mesh.

Reference analog: the reference composes parallelism out of explicit graph
rewrites — multi_devices_graph_pass clones ops per device and inserts
AllReduceOpHandles (multi_devices_graph_pass.cc:594), the collective
transpiler inserts `c_allreduce_sum` ops (transpiler/collective.py:208), and
tensor parallelism simply does not exist (SURVEY §2.8).

TPU-native redesign: ONE program, compiled ONCE under `jax.jit` with
`in_shardings` over a multi-axis `jax.sharding.Mesh` (dp × mp × sp × ...).
Parameters are annotated with PartitionSpecs by *name pattern* (the Megatron
column/row layout for transformers); feeds are sharded on the batch axis (and
optionally the sequence axis).  XLA GSPMD propagates shardings through the
whole forward+backward+optimizer computation and inserts every collective
(all-reduce, all-gather, reduce-scatter) over ICI by itself — the
fuse_all_reduce / all_reduce_deps / coalesce_grad_tensor passes of the
reference are all subsumed by the XLA all-reduce combiner.

Because `jit` has *global-view* semantics, a loss averaged over the (globally
sharded) batch yields gradients that are already averaged across data-parallel
shards: no ScaleLossGradOpHandle, no explicit grad all-reduce insertion.
"""

from __future__ import annotations

import re
import warnings

import numpy as np

from . import mesh as pmesh

__all__ = [
    "ShardingRule",
    "HybridParallelRunner",
    "megatron_rules",
    "build_hybrid_mesh",
]


class ShardingRule:
    """Maps parameter names to PartitionSpecs by regex.

    rules: list of (pattern, spec) where spec is a tuple of mesh-axis names /
    None per tensor dim, e.g. (None, 'mp') to split columns over the model
    axis.  First match wins; no match → replicated.  Axis names accept the
    paper spellings too ('batch'/'model' → 'dp'/'mp', mesh.canonical_axis).
    """

    def __init__(self, rules):
        self._rules = [(re.compile(p),
                        tuple(pmesh.canonical_axis(a) for a in s))
                       for p, s in rules]

    def spec_for(self, name, shape=None, mesh=None):
        for pat, spec in self._rules:
            if pat.search(name):
                if mesh is not None:
                    # drop axes the mesh doesn't have (e.g. rules mention 'mp'
                    # but the mesh is dp-only) → that dim stays replicated
                    spec = tuple(a if (a is None or a in mesh.axis_names) else None
                                 for a in spec)
                if shape is not None:
                    # keep only axes that evenly divide the dim — protects
                    # scalar optimizer accumulators (beta_pow: shape [1]) that
                    # share the parameter's name prefix
                    spec = spec[:len(shape)]
                    spec = tuple(
                        a if (a is None or (mesh is None or shape[d] % mesh.shape[a] == 0))
                        else None
                        for d, a in enumerate(spec))
                    spec = spec + (None,) * (len(shape) - len(spec))
                return spec
        return ()


def megatron_rules(extra=()):
    """Megatron column/row-parallel layout for the transformer param naming
    used by paddle_tpu.models.bert (and any model following it):

      - QKV and FFN-in weights: columns (output features) split over 'mp'
      - attention-output and FFN-out weights: rows (input features) split
      - word embedding: vocab dim split (logits become mp-sharded; GSPMD
        all-gathers only where needed)

    One all-reduce per transformer block in fwd and bwd — the classic layout,
    expressed as annotations instead of c_identity/c_allreduce op rewrites.
    """
    # patterns deliberately match optimizer accumulators too, which are named
    # `<param>_<acc>_<n>` (optimizer.py _add_accumulator) and must be sharded
    # exactly like their parameter
    rules = list(extra) + [
        # MoE expert weights: expert dim over 'ep' (beyond-parity; no
        # reference analog — SURVEY §2.8 lists expert parallel as absent)
        (r"_moe_(w1|w2)\.w_0($|_)", ("ep", None, None)),
        (r"_moe_(w1|w2)\.b_0($|_)", ("ep", None)),
        (r"(_query_fc|_key_fc|_value_fc|_qkv_fc|_ffn_fc_0)\.w_0($|_)", (None, "mp")),
        (r"(_query_fc|_key_fc|_value_fc|_qkv_fc|_ffn_fc_0)\.b_0($|_)", ("mp",)),
        (r"(_output_fc|_ffn_fc_1)\.w_0($|_)", ("mp", None)),
        (r"^(word_embedding|src_word_emb_table|trg_word_emb_table)($|_)", ("mp", None)),
    ]
    return ShardingRule(rules)


def build_hybrid_mesh(n_devices=None, dp=None, mp=1, sp=1, pp=1, ep=1,
                      devices=None):
    """Build a Mesh with the standard axis order (pp, dp, ep, sp, mp).

    mp innermost: tensor-parallel collectives are the most latency-sensitive,
    so they ride the fastest/nearest ICI links; pp outermost (stage-to-stage
    transfers are point-to-point and infrequent).
    """
    import jax

    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices % (mp * sp * pp * ep) != 0:
        raise ValueError(
            f"n_devices={n_devices} not divisible by mp*sp*pp*ep="
            f"{mp * sp * pp * ep}")
    if dp is None:
        dp = n_devices // (mp * sp * pp * ep)
    shape = {}
    if pp > 1:
        shape[pmesh.PIPE_AXIS] = pp
    shape[pmesh.DATA_AXIS] = dp
    if ep > 1:
        shape[pmesh.EXPERT_AXIS] = ep
    if sp > 1:
        shape[pmesh.SEQ_AXIS] = sp
    shape[pmesh.MODEL_AXIS] = mp
    return pmesh.build_mesh(shape, devices=devices[:n_devices])


class HybridParallelRunner:
    """Compile and run a Program SPMD-partitioned over a hybrid mesh.

    feed_specs: dict feed-name → PartitionSpec tuple.  Default: dim 0 on
    'dp' (batch sharding); pass e.g. ('dp', 'sp') for [B, S] token ids to add
    sequence parallelism.
    """

    def __init__(self, program, mesh, rules: ShardingRule | None = None,
                 feed_specs=None, scope=None, zero_stage=0,
                 zero_gather_quant=None, fused_update=None, gspmd=None,
                 policy_pin=None):
        """zero_stage=1: shard optimizer-state vars (moment accumulators,
        tagged is_optimizer_state) over the 'dp' axis on dim 0 — the
        cross-replica weight-update sharding of arXiv:2004.13336 (ZeRO-1).
        XLA GSPMD then keeps each replica's accumulator shard resident and
        all-gathers the updated parameters, cutting optimizer-state memory
        by the dp degree at the cost of one all-gather per step.

        zero_gather_quant (None = FLAGS_zero_gather_quant): with
        zero_stage>=1, the weight-update all-gather of every ZeRO-eligible
        parameter (replicated by the rules, dim 0 divisible by dp) moves a
        block-scaled int8 wire format instead of fp32
        (kernels.ring_collectives.quantized_all_gather): each dp shard
        quantizes its slice of the updated parameter, int8 payload +
        per-block fp32 scales ride the gather, and the full tensor
        dequantizes on arrival — halving (dual-int8) the gather bytes the
        ZeRO-1 trade costs.  Optimizer-state shards never gather at all,
        so optimizer state stays fp32-exact regardless of this knob.

        fused_update (None = FLAGS_fused_update): with zero_gather_quant
        on, the sgd/adam ops of ZeRO-gather-eligible parameters are
        rewritten to their fused update→requant variants
        (`fused_sgd_quant_gather` / `fused_adam_quant_gather`,
        kernels/fused_update.py): the op itself emits the block-scaled
        int8 image of the updated parameter, the gather rides THAT
        payload (gather_quantized_shards), and the fp32 updated parameter
        between update and requant never round-trips HBM — saved bytes
        book on ``pt_fused_update_bytes_saved_total``.  ``ParamOut``
        stays the exact fp32 update, so the same program run outside this
        runner is bit-identical to the unfused ops.

        gspmd (None = FLAGS_gspmd_executor): route compilation through
        the shared `parallel.gspmd.GSPMDExecutor` with a
        `TensorParallelPolicy` wrapping these rules (+ ZeRO-1 state
        sharding when zero_stage >= 1) — this runner becomes a thin
        policy selection over the one partitioned executor, sharing its
        compile cache/metrics/HLO-inspection plumbing with the DP lane.
        The fused-update / zero_gather_quant op rewrites stay on the
        classic path (their gather already rides the quantized wire
        format); the gspmd lane's quantized gradient hook engages via
        FLAGS_quant_allreduce instead."""
        self.program = program
        self.mesh = mesh
        self.rules = rules or ShardingRule([])
        # autotune pin (docs/AUTOTUNE.md "Pinning"): explicit pin or the
        # standing FLAGS_autotune_report path.  Unlike the DP runner the
        # mesh here is caller-supplied, so the pin must AGREE with it —
        # a silent re-mesh would invalidate the caller's feed_specs.
        if policy_pin is None:
            from paddle_tpu.fluid import flags as _flags

            policy_pin = _flags.flag("autotune_report") or None
        self.policy_pin = None
        if policy_pin is not None:
            from . import autotune as _autotune

            pin = _autotune.resolve_pin(policy_pin)
            shape = dict(getattr(mesh, "shape", {}) or {})
            got = {ax: int(shape.get(ax, 1))
                   for ax in (pmesh.PIPE_AXIS, pmesh.DATA_AXIS,
                              pmesh.MODEL_AXIS)}
            if got != pin.mesh_dims:
                raise ValueError(
                    f"autotune pin {pin.label()} names mesh dims "
                    f"{pin.mesh_dims} but this runner's mesh is {got}")
            self.policy_pin = pin
            gspmd = True          # a pin is always a GSPMD assignment
            zero_stage = pin.zero_stage
        self.feed_specs = dict(feed_specs or {})
        self._default_scope = scope
        self._cache = {}
        self._ran_keys = set()  # signatures that executed at least once
        self._step = 0
        self.zero_stage = int(zero_stage)
        if zero_gather_quant is None:
            from paddle_tpu.fluid import flags as _flags

            zero_gather_quant = _flags.flag("zero_gather_quant")
        self.zero_gather_quant = bool(zero_gather_quant)
        if fused_update is None:
            from paddle_tpu.fluid import flags as _flags

            fused_update = _flags.flag("fused_update")
        self.fused_update = bool(fused_update)
        if gspmd is None:
            from paddle_tpu.fluid import flags as _flags

            gspmd = _flags.flag("gspmd_executor")
        self.gspmd = bool(gspmd)
        # graph-optimization passes (FLAGS_graph_passes) BEFORE the
        # fused-gather rewrite and the health transpile — the declared
        # PASS_ORDER; the gspmd branch applies them inside GSPMDExecutor.
        if not self.gspmd:
            from paddle_tpu import passes as _graph_passes

            _graph_passes.apply_graph_passes(program, lane="hybrid")
        self._gspmd_exec = None
        if self.gspmd:
            # thin policy selection over the shared partitioned executor
            # (policy_for — the one rule the DP lane shares); the
            # program stays unrewritten (no fused-gather op rewrite — the
            # hook owns the wire format on this lane)
            from .gspmd import GSPMDExecutor, policy_for

            if self.policy_pin is not None:
                policy = self.policy_pin.build_policy(rules=self.rules)
                quant_hook = self.policy_pin.quant
            else:
                policy = policy_for(mesh, rules=rules,
                                    zero_stage=self.zero_stage)
                quant_hook = None
            self._gspmd_exec = GSPMDExecutor(
                program, mesh, policy, scope=scope,
                feed_specs=self.feed_specs, quant_hook=quant_hook)
            self._sentinel = None  # the shared executor owns it there
            self._fused_gather = {}
            # capture_hlo/last_hlo stay live on this lane through the
            # properties below (delegated to the executor), so the
            # classic dryrun/driver contract keeps working
            return
        # {param: {"shape", "padded", "qhi", "qlo", "qsc"}} for optimizer
        # ops rewritten to the fused update→requant→gather form
        self._fused_gather = (self._rewrite_fused_updates()
                              if (self.fused_update and self.zero_stage >= 1
                                  and self.zero_gather_quant) else {})
        # health sentinel (FLAGS_health_sentinel, docs/DISTRIBUTED.md
        # §6): inserted AFTER the fused-gather rewrite so the check
        # covers the final optimizer op forms; ZeRO-1 NOTE — snapshots
        # copy the scope's sharded arrays, so each process holds only
        # its resident moment shards
        from paddle_tpu import health

        self._sentinel = health.attach(program, lane="hybrid")
        # capture_hlo=True records the OPTIMIZED (post-GSPMD-partitioner)
        # HLO of the first compiled step in .last_hlo so callers can assert
        # which collectives XLA inserted (the dryrun/driver check does).
        # Costs one extra AOT compile of the same tiny computation.
        self.capture_hlo = False
        self.last_hlo = None

    # capture_hlo/last_hlo: plain attributes on the classic lane, live
    # delegation to the shared executor on the gspmd lane — the
    # documented dryrun/driver contract (set capture_hlo, run once, read
    # last_hlo) works identically on both
    @property
    def capture_hlo(self):
        if getattr(self, "_gspmd_exec", None) is not None:
            return self._gspmd_exec.capture_hlo
        return getattr(self, "_capture_hlo_flag", False)

    @capture_hlo.setter
    def capture_hlo(self, value):
        if getattr(self, "_gspmd_exec", None) is not None:
            self._gspmd_exec.capture_hlo = bool(value)
        else:
            self._capture_hlo_flag = bool(value)

    @property
    def last_hlo(self):
        if getattr(self, "_gspmd_exec", None) is not None:
            return self._gspmd_exec.last_hlo
        return getattr(self, "_last_hlo", None)

    @last_hlo.setter
    def last_hlo(self, value):
        self._last_hlo = value

    def rebuild(self, mesh):
        """Re-specialize the runner onto a new mesh — the elastic-rejoin
        hook (docs/DISTRIBUTED.md §6 "Elastic membership"): after a
        preemption resized the collective job and
        `distributed.elastic.reinit_collective` re-formed
        `jax.distributed`, every compiled executable is specialized to
        the OLD device set and sharding layout.  Dropping the caches and
        swapping the mesh re-lowers on next run; scope-resident device
        arrays re-shard on the fly through jax.device_put.  Returns self
        for chaining (`runner.rebuild(elastic.rebuild_mesh(mp=2))`)."""
        self.mesh = mesh
        self._cache.clear()
        self._ran_keys.clear()
        self.last_hlo = None
        if self._gspmd_exec is not None:
            # re-specialize the shared executor onto the new mesh: the
            # policy is mesh-independent, the compiled blocks are not
            from .gspmd import GSPMDExecutor

            old = self._gspmd_exec
            self._gspmd_exec = GSPMDExecutor(
                self.program, mesh, old.policy,
                scope=self._default_scope, feed_specs=self.feed_specs,
                quant_hook=old.quant_hook, quant_algo=old.quant_algo,
                capture_hlo=old.capture_hlo)
        if self._fused_gather:
            self._restamp_fused_updates()
        from paddle_tpu.observability import events

        events.emit("hybrid_rebuild",
                    mesh_shape={k: int(v) for k, v in mesh.shape.items()},
                    n_devices=int(len(mesh.devices.reshape(-1))))
        return self

    def _spec(self, *axes):
        from jax.sharding import NamedSharding, PartitionSpec as P

        axes = tuple(a for a in axes)
        return NamedSharding(self.mesh, P(*axes))

    def _param_sharding(self, name, shape):
        spec = self.rules.spec_for(name, shape=shape, mesh=self.mesh)
        if self.zero_stage >= 1 and not any(spec):
            spec = self._zero1_spec(name, shape) or spec
        return self._spec(*spec)

    def _zero1_spec(self, name, shape):
        """dp-shard dim 0 of optimizer-state vars (ZeRO-1) when possible."""
        if pmesh.DATA_AXIS not in self.mesh.axis_names:
            return None
        dp = self.mesh.shape[pmesh.DATA_AXIS]
        if dp <= 1 or not shape or shape[0] % dp != 0:
            return None
        v = self.program.global_block()._find_var_recursive(name)
        if v is None or not getattr(v, "is_optimizer_state", False):
            return None
        return (pmesh.DATA_AXIS,) + (None,) * (len(shape) - 1)

    def _zero_gather_params(self, scope, donated_names):
        """Parameters whose weight-update gather takes the quantized wire
        format (zero_gather_quant): trainable Parameters left replicated
        by the rules with dim 0 divisible by dp — the same eligibility
        gate `_zero1_spec` applies to their optimizer state.  Optimizer
        state itself is never in this set: its shards stay resident and
        fp32-exact.  Parameters whose per-device shard is smaller than
        one quantization block also stay fp32: block padding + scales
        would move MORE bytes than the fp32 gather they replace (the same
        size-adaptivity the all-reduce crossover applies)."""
        from paddle_tpu.fluid import flags as _flags
        from paddle_tpu.fluid.framework import Parameter

        if (not self.zero_gather_quant or self.zero_stage < 1
                or pmesh.DATA_AXIS not in self.mesh.axis_names):
            return {}
        dp = self.mesh.shape[pmesh.DATA_AXIS]
        if dp <= 1:
            return {}
        block = int(_flags.flag("quant_allreduce_block_size"))
        out = {}
        for name in donated_names:
            v = self.program.global_block()._find_var_recursive(name)
            if not isinstance(v, Parameter):
                continue
            val = scope.get(name)
            shape = tuple(np.shape(val)) if val is not None else None
            if not shape or shape[0] % dp != 0:
                continue
            if int(np.prod(shape)) // dp < block:
                continue  # sub-block shard: fp32 gather is cheaper
            if any(self.rules.spec_for(name, shape=shape, mesh=self.mesh)):
                continue  # mp/ep-sharded params: GSPMD owns their layout
            out[name] = shape
        return out

    _FUSED_GATHER_OPS = {"sgd": "fused_sgd_quant_gather",
                         "adam": "fused_adam_quant_gather",
                         "adamw": "fused_adamw_quant_gather",
                         "lamb": "fused_lamb_quant_gather",
                         "momentum": "fused_momentum_quant_gather"}

    def _fused_gather_eligible(self, name):
        """ZeRO-gather eligibility from program metadata (the same gates
        `_zero_gather_params` applies from the scope, minus the live
        values — the op rewrite happens at construction, before any
        scope is bound): trainable Parameter, static shape, dim 0
        divisible by dp, at least one quantization block per shard, not
        mp/ep-sharded by the rules."""
        from paddle_tpu.fluid import flags as _flags
        from paddle_tpu.fluid.framework import Parameter

        if pmesh.DATA_AXIS not in self.mesh.axis_names:
            return None
        dp = self.mesh.shape[pmesh.DATA_AXIS]
        if dp <= 1:
            return None
        v = self.program.global_block()._find_var_recursive(name)
        if not isinstance(v, Parameter) or not v.shape:
            return None
        shape = tuple(v.shape)
        if any(d is None or d < 0 for d in shape) or shape[0] % dp != 0:
            return None
        block = int(_flags.flag("quant_allreduce_block_size"))
        if int(np.prod(shape)) // dp < block:
            return None
        if any(self.rules.spec_for(name, shape=shape, mesh=self.mesh)):
            return None
        return shape

    def _rewrite_fused_updates(self):
        """Rewrite eligible sgd/adam ops to their fused
        update→requant→gather variants (in place, the DP transpiler's
        precedent): same slots plus QHi/QLo/QScale outputs carrying the
        block-scaled int8 image of the updated parameter, padded to
        dp*block so per-shard blocks never straddle the gather's shard
        boundary.  Returns {param: q-var info} for `_wrap_fused_gather`."""
        from paddle_tpu.fluid import flags as _flags
        from paddle_tpu.fluid.framework import Operator

        block = int(_flags.flag("quant_allreduce_block_size"))
        dp = self.mesh.shape.get(pmesh.DATA_AXIS, 1)
        blk = self.program.global_block()
        fused = {}
        for i, op in enumerate(blk.ops):
            if op.type not in self._FUSED_GATHER_OPS:
                continue
            pname = (op.inputs.get("Param") or [None])[0]
            if pname is None or pname in fused:
                continue
            shape = self._fused_gather_eligible(pname)
            if shape is None:
                continue
            numel = int(np.prod(shape))
            padded = numel + (-numel) % (dp * block)
            qhi = blk.create_var(name=pname + "@ZGQ_HI", dtype="int8",
                                 shape=[padded])
            qlo = blk.create_var(name=pname + "@ZGQ_LO", dtype="int8",
                                 shape=[padded])
            qsc = blk.create_var(name=pname + "@ZGQ_SCALE",
                                 dtype="float32", shape=[padded // block])
            outputs = {s: list(n) for s, n in op.outputs.items()}
            outputs.update(QHi=[qhi.name], QLo=[qlo.name],
                           QScale=[qsc.name])
            attrs = dict(op.attrs)
            attrs.update(block_size=block, pad_multiple=dp * block)
            blk.ops[i] = Operator(
                blk, self._FUSED_GATHER_OPS[op.type],
                inputs={s: list(n) for s, n in op.inputs.items()},
                outputs=outputs, attrs=attrs)
            fused[pname] = {"shape": shape, "padded": padded,
                            "qhi": qhi.name, "qlo": qlo.name,
                            "qsc": qsc.name}
        if fused:
            self.program._bump_version()
        return fused

    def _restamp_fused_updates(self):
        """Re-specialize the fused update→requant ops onto the current
        mesh (rebuild() path): the gather payload pads to dp*block, so
        the op attrs and the q-var shapes are dp-dependent — and
        eligibility itself is mesh-dependent, so a parameter the NEW mesh
        disqualifies (dp resized to 1, dim-0 divisibility lost, the dp
        axis gone entirely) REVERTS to its base optimizer op: leaving it
        fused would quantize-round-trip every step on a configuration
        that is exact by contract (dp=1) or crash the gather wrapper."""
        from paddle_tpu.fluid import flags as _flags
        from paddle_tpu.fluid.framework import Operator

        block = int(_flags.flag("quant_allreduce_block_size"))
        dp = self.mesh.shape.get(pmesh.DATA_AXIS, 1)
        base_of = {v: k for k, v in self._FUSED_GATHER_OPS.items()}
        blk = self.program.global_block()
        for i, op in enumerate(blk.ops):
            if op.type not in base_of:
                continue
            pname = (op.inputs.get("Param") or [None])[0]
            info = self._fused_gather.get(pname)
            if info is None:
                continue
            if self._fused_gather_eligible(pname) is None:
                # demote back to the exact base op on the new mesh
                attrs = {k: v for k, v in op.attrs.items()
                         if k not in ("block_size", "pad_multiple")}
                outputs = {s: list(n) for s, n in op.outputs.items()
                           if s not in ("QHi", "QLo", "QScale")}
                blk.ops[i] = Operator(
                    blk, base_of[op.type],
                    inputs={s: list(n) for s, n in op.inputs.items()},
                    outputs=outputs, attrs=attrs)
                del self._fused_gather[pname]
                continue
            numel = int(np.prod(info["shape"]))
            padded = numel + (-numel) % (dp * block)
            op.attrs.update(block_size=block, pad_multiple=dp * block)
            info["padded"] = padded
            blk.vars[info["qhi"]].shape = (padded,)
            blk.vars[info["qlo"]].shape = (padded,)
            blk.vars[info["qsc"]].shape = (padded // block,)
        self.program._bump_version()

    def _make_inner_body(self, plan):
        """The traced step body.  With fused update→requant ops in the
        program, returns a 3-tuple body that also exposes the quantized
        updated-parameter images (non-persistable op outputs, invisible
        to out_writes) so `_wrap_fused_gather` can ride them through the
        ZeRO gather; otherwise the plain BlockPlan body."""
        if not self._fused_gather:
            return plan.make_body(), False
        fetch_names, write_names = plan.jit_fetch_names, plan.write_names
        qnames = {p: (i["qhi"], i["qlo"], i["qsc"])
                  for p, i in self._fused_gather.items()}

        def fn(donated, readonly, feeds, step):
            env = plan.trace_env(donated, readonly, feeds, step)
            fetches = [env[n] for n in fetch_names]
            out_writes = {n: env[n] for n in write_names if n in env}
            extras = {p: (env[h], env[l], env[s])
                      for p, (h, l, s) in qnames.items() if h in env}
            return fetches, out_writes, extras

        return fn, True

    def _wrap_fused_gather(self, inner3, live_writes):
        """Close the fused chain: each rewritten parameter's quantized
        image (already padded to dp*block by the op) rides the ZeRO-1
        weight-update gather as int8 + scales
        (gather_quantized_shards), dequantizing only on arrival — the
        parameter write the next step reads is the gathered value, and
        the op's exact fp32 ParamOut is dead code XLA removes.  Returns
        (2-tuple body, modeled wire bytes/step, modeled HBM bytes
        saved/step)."""
        import jax
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.fluid import flags as _flags
        from paddle_tpu.kernels import fused_update as fu
        from paddle_tpu.kernels import quantized_collectives as qc
        from paddle_tpu.kernels import ring_collectives as rcol

        axis = pmesh.DATA_AXIS
        dp = self.mesh.shape[axis]
        block = int(_flags.flag("quant_allreduce_block_size"))
        # one shard_map serves every parameter: the payloads are all flat
        # 1-D images with identical specs/axis/block (unlike the plain
        # zero-gather wrapper, whose in_specs depend on each shape)
        gather_fn = jax.shard_map(
            lambda h, l, s: rcol.gather_quantized_shards(
                h, l, s, axis, block),
            mesh=self.mesh, in_specs=(P(axis), P(axis), P(axis)),
            out_specs=P(), check_vma=False)
        gathered, wire, saved = set(), 0, 0
        for name, info in self._fused_gather.items():
            if name not in live_writes:
                continue
            gathered.add(name)
            wire += qc.gather_wire_bytes(info["padded"] // dp,
                                         block_size=block, n_devices=dp)
            saved += fu.bytes_saved(int(np.prod(info["shape"])))

        def body(donated, readonly, feeds, step):
            fetches, out_writes, extras = inner3(donated, readonly, feeds,
                                                 step)
            out_writes = dict(out_writes)
            for name, (qh, ql, qsc) in extras.items():
                if name not in gathered:
                    continue
                info = self._fused_gather[name]
                flat = gather_fn(qh, ql, qsc)
                numel = int(np.prod(info["shape"]))
                val = flat[:numel].reshape(info["shape"])
                prev = out_writes.get(name)
                out_writes[name] = (val.astype(prev.dtype)
                                    if prev is not None else val)
            return fetches, out_writes

        return body, wire, saved

    def _wrap_zero_gather(self, inner, zgq_params):
        """Wrap a compiled step body so every ZeRO-gather-eligible
        parameter write re-replicates through the block-scaled int8
        all-gather: the nested shard_map's in_spec pins the updated
        parameter dp-sharded on dim 0 (which is how the ZeRO-sharded
        optimizer state computes it anyway), the int8 payload + scales
        ride the gather, and the out_spec hands the replicated fp32
        tensor back to GSPMD.  Returns (wrapped_body, modeled per-step
        wire bytes)."""
        import jax
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.fluid import flags as _flags
        from paddle_tpu.kernels import quantized_collectives as qc
        from paddle_tpu.kernels import ring_collectives as rcol

        axis = pmesh.DATA_AXIS
        dp = self.mesh.shape[axis]
        block = int(_flags.flag("quant_allreduce_block_size"))
        gathers, total = {}, 0
        for name, shape in zgq_params.items():
            in_spec = P(*((axis,) + (None,) * (len(shape) - 1)))
            gathers[name] = jax.shard_map(
                lambda s: rcol.quantized_all_gather(s, axis, block),
                mesh=self.mesh, in_specs=in_spec,
                out_specs=P(*((None,) * len(shape))), check_vma=False)
            total += qc.gather_wire_bytes(
                int(np.prod(shape)) // dp, block_size=block, n_devices=dp)

        def body(donated, readonly, feeds, step):
            fetches, out_writes = inner(donated, readonly, feeds, step)
            out_writes = dict(out_writes)
            for name, fn in gathers.items():
                if name in out_writes:
                    out_writes[name] = fn(out_writes[name])
            return fetches, out_writes

        return body, total

    def _resolve_scope(self, scope):
        if scope is not None:
            return scope
        if self._default_scope is not None:
            return self._default_scope
        from paddle_tpu.fluid.executor import global_scope

        return global_scope()

    @staticmethod
    def _prep(feed, fetch_list):
        """The shared dispatch-key helper (gspmd.executor.prep_feed) —
        one implementation so the two partitioned lanes' cache-key
        semantics cannot drift."""
        from .gspmd.executor import prep_feed

        return prep_feed(feed, fetch_list)

    def _dispatch(self, key, scope, feed, fetch_names, n_steps,
                  stacked_feed, return_numpy):
        import time as _time

        from paddle_tpu.fluid.executor import (_feed_batch, _m_cache,
                                               _m_compile_seconds,
                                               _record_step,
                                               _report_examples)

        sent = self._sentinel
        cb = self._cache.get(key)
        if cb is None:
            _m_cache().labels(path="hybrid", result="miss").inc()
            if sent is not None:
                sent.ensure_state(scope)  # before BlockPlan scope checks
            t0 = _time.perf_counter()  # observability: allow
            cb = self._compile(scope, list(feed.keys()), fetch_names,
                               n_steps=n_steps, stacked_feed=stacked_feed)
            self._cache[key] = cb
            _m_compile_seconds().labels(
                path="hybrid", phase="trace").inc(_time.perf_counter() - t0)  # observability: allow
        else:
            _m_cache().labels(path="hybrid", result="hit").inc()
        # health sentinel at dispatch granularity (one run() step, or one
        # whole run_steps chain — a rollback restores the pre-chain state
        # and replays the chain)
        def attempt():
            first_run = key not in self._ran_keys
            t0 = _time.perf_counter()  # observability: allow
            fetches = cb(scope, feed, self._step)
            step_s = _time.perf_counter() - t0  # observability: allow
            _record_step("hybrid", step_s, first_run)
            zgq_bytes = getattr(cb, "_zgq_bytes_per_step", 0)
            if zgq_bytes:
                from .data_parallel import collective_payload_counter

                collective_payload_counter().labels(
                    collective="zero_gather_quant").inc(
                    zgq_bytes * n_steps)
            fused_saved = getattr(cb, "_fused_saved_per_step", 0)
            if fused_saved:
                from .data_parallel import fused_update_bytes_counter

                fused_update_bytes_counter().inc(fused_saved * n_steps)
            self._ran_keys.add(key)
            # stacked_feed: leading feed axis is the step index, not batch
            batch = 0 if stacked_feed else _feed_batch(feed) * n_steps
            _report_examples("hybrid", batch, step_s)
            self._step += n_steps
            return fetches

        from paddle_tpu.health import run_guarded

        fetches = run_guarded(sent, scope, fetch_names, attempt,
                              chain=n_steps > 1)
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return fetches

    def run(self, scope=None, feed=None, fetch_list=None, return_numpy=True):
        if self._gspmd_exec is not None:
            return self._gspmd_exec.run(scope=scope, feed=feed,
                                        fetch_list=fetch_list,
                                        return_numpy=return_numpy)
        scope = self._resolve_scope(scope)
        feed, fetch_names, feed_sig = self._prep(feed, fetch_list)
        key = (self.program._version, feed_sig, tuple(fetch_names))
        return self._dispatch(key, scope, feed, fetch_names, 1, False,
                              return_numpy)

    def run_steps(self, feed, n_steps, fetch_list=None, scope=None,
                  return_numpy=True, stacked_feed=False):
        """`n_steps` GSPMD-partitioned steps in ONE jitted call — the
        fori_loop carries the sharded params/opt-state on-device (the
        big-training scan-over-steps pattern), with the step counter
        advancing per iteration exactly like n run() calls.
        stacked_feed=True: feed arrays carry a leading [n_steps] axis
        (replicated across the mesh), one slice per iteration.  Only the
        final step's fetches return."""
        if self._gspmd_exec is not None:
            # the shared executor chains the loop on-device now (one
            # jitted fori_loop call, stacked_feed included) — dispatch
            # amortization on the gspmd lane instead of n Python run()s
            return self._gspmd_exec.run_steps(
                feed, n_steps, fetch_list=fetch_list, scope=scope,
                return_numpy=return_numpy, stacked_feed=stacked_feed)
        scope = self._resolve_scope(scope)
        n = int(n_steps)
        if n < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps!r}")
        feed, fetch_names, feed_sig = self._prep(feed, fetch_list)
        if stacked_feed:
            bad = {k: np.shape(v) for k, v in feed.items()
                   if not np.shape(v) or np.shape(v)[0] != n}
            if bad:
                raise ValueError(
                    f"stacked_feed arrays need a leading [{n}] axis; "
                    f"got {bad}")
        key = (self.program._version, feed_sig, tuple(fetch_names),
               "chain", n, bool(stacked_feed))
        return self._dispatch(key, scope, feed, fetch_names, n,
                              bool(stacked_feed), return_numpy)

    def _compile(self, scope, feed_names, fetch_names, n_steps=1,
                 stacked_feed=False):
        import jax
        from paddle_tpu.fluid.executor import BlockPlan, HostOpsUnsupported

        program, mesh = self.program, self.mesh
        plan = BlockPlan(program, program.global_block(), feed_names,
                         fetch_names, scope)
        if plan.host_pre_ops:
            raise NotImplementedError(
                "pre-stage host ops (distributed lookup) are only "
                "supported by the single-device Executor")
        chain_mode = n_steps > 1 or stacked_feed
        if chain_mode and (plan.host_ops or plan.host_fetch_names):
            raise HostOpsUnsupported(
                "run_steps chains the whole loop on-device; host ops "
                f"({[op.type for op in plan.host_ops]}) need the host "
                "between steps — use run() per step")
        inner_body, has_extras = self._make_inner_body(plan)
        zgq_bytes = fused_saved = 0
        if has_extras:
            # fused update→requant ops: their quantized images ride the
            # gather; wrapped BEFORE the chain wrap so every chained
            # iteration's parameter writes re-replicate through it.
            # Only params this plan actually WRITES count (a forward-only
            # fetch prunes the optimizer ops — no gather, no booking).
            live = set(plan.write_names)
            inner_body, fused_wire, fused_saved = \
                self._wrap_fused_gather(inner_body, live)
            zgq_bytes += fused_wire
        zgq = self._zero_gather_params(scope, plan.donated_names)
        # params on the fused path already gather quantized — the plain
        # quantize-then-gather wrapper covers only the rest (momentum /
        # other optimizers the fused rewrite doesn't absorb)
        zgq = {k: v for k, v in zgq.items() if k not in self._fused_gather}
        if zgq:
            inner_body, plain_bytes = self._wrap_zero_gather(inner_body,
                                                             zgq)
            zgq_bytes += plain_bytes
        # the health gate wraps OUTERMOST (after the gather wrappers, so
        # a parameter write replaced by a gathered quantized image is
        # gated too) but INSIDE the chain loop (per-iteration masking)
        from paddle_tpu.health import wrap_body as _health_gate

        inner_body = _health_gate(program, inner_body)

        if chain_mode:
            # the ONE chain combinator every lane shares
            # (fluid.executor.chain_step_body)
            from paddle_tpu.fluid.executor import chain_step_body

            inner_body = chain_step_body(inner_body, n_steps,
                                         stacked_feed)

        def body(*args):
            # ops that adapt their lowering to the mesh (ring attention on
            # the sp axis) read current_mesh() at trace time
            with pmesh.mesh_guard(mesh):
                return inner_body(*args)
        donated, readonly = plan.donated_names, plan.readonly_names
        writes = plan.write_names

        def shard_of(n, v):
            return self._param_sharding(n, tuple(np.shape(v)))

        don_sh = {n: shard_of(n, scope.get(n)) for n in donated}
        ro_sh = {n: shard_of(n, scope.get(n)) for n in readonly}

        def feed_shard(name):
            if name in self.feed_specs:
                axes = tuple(self.feed_specs[name])
            else:
                ax = (pmesh.DATA_AXIS
                      if pmesh.DATA_AXIS in mesh.axis_names else None)
                axes = (ax,) if ax else ()
            if stacked_feed:
                # leading [n_steps] axis is the loop index — replicated;
                # the batch dim (now dim 1) keeps its dp sharding
                axes = (None,) + axes
            return self._spec(*axes)

        feeds_sh = {n: feed_shard(n) for n in feed_names}
        out_sh = ([self._spec() for _ in fetch_names],
                  {n: don_sh.get(n, self._spec()) for n in writes})
        jitted = jax.jit(
            body,
            in_shardings=(don_sh, ro_sh, feeds_sh, self._spec()),
            out_shardings=out_sh,
            donate_argnums=(0,))
        prof_state = {"ran": False}

        def stage_global(value, sharding):
            """Multi-process SPMD staging: jit refuses numpy (or
            process-local jax) inputs with non-trivial shardings when the
            mesh spans processes.  Host values are the GLOBAL content,
            identical on every process (functional RNG makes startup
            deterministic; feeds are built from shared seeds), so each
            process materializes its addressable shards in place.
            Single-process: identity — no copy, no behavior change."""
            if jax.process_count() == 1:
                return value
            if (isinstance(value, jax.Array)
                    and value.sharding.device_set == sharding.device_set):
                return value  # already a global array on this mesh
            arr = np.asarray(value)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])

        def compiled(scope_, feeds, step):
            from paddle_tpu.fluid import profiler as _prof
            from paddle_tpu.observability import profiling as _profiling

            # step_phases outermost; timed_run keeps its historic region
            # (the jitted call + scope writes only — staging/HLO capture
            # before it, host ops after) so the "run" span semantics are
            # unchanged; fetch_sync brackets accumulate across both
            with _profiling.step_phases(
                    "hybrid", f"hybrid_block@{id(jitted):x}") as ph:
                with ph.phase("feed_prep"):
                    don_vals = {n: stage_global(scope_.get(n), don_sh[n])
                                for n in donated}
                    ro_vals = {n: stage_global(scope_.get(n), ro_sh[n])
                               for n in readonly}
                    feeds = {n: stage_global(v, feeds_sh[n])
                             for n, v in feeds.items()}
                    if self.capture_hlo and self.last_hlo is None:
                        self.last_hlo = (
                            jitted.lower(don_vals, ro_vals, dict(feeds),
                                         np.uint32(step))
                            .compile().as_text())
                with _prof.timed_run(f"hybrid_block@{id(jitted):x}",
                                     prof_state) as timer:
                    with ph.phase("dispatch"):
                        with warnings.catch_warnings():
                            warnings.simplefilter("ignore")  # donation unsupported on CPU
                            fetches, out_writes = jitted(
                                don_vals, ro_vals, dict(feeds),
                                np.uint32(step))
                        # counted in the in-flight ledger and never marked done:
                        # the chip is not known empty while this lane runs
                        _profiling.enqueued("hybrid_block")
                    with ph.phase("device_wait"):
                        ph.wait((fetches, out_writes))
                    with ph.phase("fetch_sync"):
                        for n, v in out_writes.items():
                            scope_.set(n, v)
                        timer.done(fetches, out_writes)
                with ph.phase("fetch_sync"):
                    plan.run_host_ops(scope_)
                    out = plan.assemble_fetches(fetches, scope_)
            return out

        # modeled ZeRO-gather wire bytes (and fused-update HBM savings)
        # ride on the compiled closure so _dispatch can book them per
        # executed step
        compiled._zgq_bytes_per_step = zgq_bytes
        compiled._fused_saved_per_step = fused_saved
        return compiled
