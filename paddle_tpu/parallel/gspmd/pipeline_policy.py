"""Pipeline parallelism as a ShardingPolicy — stages over a ``pp`` mesh
axis INSIDE the one jit-partitioned step.

The legacy lane (`parallel/pipeline.py` PipelineRunner) cuts the program
into per-stage XLA programs and runs the GPipe schedule from the HOST:
one dispatch per (stage, microbatch, phase), activations round-tripping
through numpy between stages.  That spelling cannot compose with the
gspmd policy layer (no shared jit, no policy-resolved shardings), cannot
ride the quantized ring inside the partitioned graph, and pays
Python-loop dispatch ~S*M times per step — the perf ceiling this module
removes.

Here the whole schedule lowers into ONE computation:

  - ``PipelinePolicy`` composes with the existing policies: an ``inner``
    policy (DataParallel / ZeRO-1 by default) resolves parameter and
    feed placement on the non-pipeline axes, and the stage assignment
    (`parallel.pipeline.assign_stages` — the same dataflow cut the
    legacy lane uses) maps stages onto the ``pp`` axis of a 3-D
    ``(pp, batch, model)`` mesh (`mesh.build_3d_mesh`, paper-spelling
    aliases preserved).
  - The executor lowers the microbatched schedule as a ``lax.scan`` over
    schedule ticks inside a ``shard_map`` island mapped over
    ``(pp, batch)``: every device selects its stage's computation with
    ``lax.switch`` on ``lax.axis_index('pp')``, and stage-boundary
    activations/cotangents ride non-wrapping ``ppermute`` shifts through
    the lint-sanctioned `kernels.pipeline_collectives` surface.  Both
    ``FLAGS_pipeline_schedule`` spellings share the one tick body; only
    the slot formulas differ:

      ``gpipe``  fill/drain — all M forwards (M+S-1 ticks), then all M
                 backwards (M+S-1 ticks); the activation stash holds all
                 M microbatches.
      ``1f1b``   one-forward-one-backward interleaving — the SAME
                 2*(M+S-1) tick count and bubble fraction
                 ((S-1)/(M+S-1)), but a stage starts draining backwards
                 after at most S forwards, so the activation stash holds
                 ``min(M, S)`` microbatches instead of M (the memory win
                 that lets M scale; docs/DISTRIBUTED.md "Pipeline as a
                 policy").

  - Backward recomputes the stage forward from the stashed boundary
    activations (the legacy lane's stage-granular rematerialization,
    now in-graph), parameter gradients accumulate across microbatches,
    merge across stages (`stage_merge` — a zero-elsewhere ownership
    broadcast), and the batch-axis reduction keeps the EQuARX dual-int8
    adaptive ring (`adaptive_quantized_all_reduce`) with the same flags,
    wire-bytes model and payload-counter booking as the plain gspmd
    quant hook.  The optimizer leg traces in global view AFTER the
    island, where the inner policy's specs (ZeRO-1 state sharding)
    partition it.

Contract and limits:

  - The ``pp`` mesh axis size must equal the number of stages the cut
    produces.
  - Parameter specs the inner policy resolves onto a non-batch axis
    demote to replicated with a warning (the island maps ``(pp,
    batch)`` only — model-axis tensor parallelism inside a stage is the
    documented next step, not silently wrong math).
  - Stage-produced scope writes (batch_norm running stats) and
    island-produced optimizer-leg inputs beyond gradients are rejected
    loudly (NotImplementedError) instead of silently mis-averaged.
  - The schedule report (`program._pipeline_schedule`) and the
    ``pt_pipeline_bubble_frac`` / per-boundary
    ``pt_gspmd_resharding_bytes`` gauges are stamped at compile, the
    way the DP lane stamps ``_overlap_schedule``.
"""

from __future__ import annotations

import warnings

import numpy as np

from paddle_tpu.fluid.framework import grad_var_name

from .. import mesh as pmesh
from ..pipeline import boundary_sets, stage_partition
from . import specs as gspecs

__all__ = ["PipelinePolicy", "PipelinePlan", "plan_pipeline",
           "modeled_bubble_fraction", "schedule_slots", "SCHEDULES"]

SCHEDULES = ("gpipe", "1f1b")


def modeled_bubble_fraction(n_stages, n_microbatches):
    """Idle-slot fraction of the lockstep schedule: both spellings run
    2*(M+S-1) ticks of which each stage computes 2*M — the classic
    (S-1)/(M+S-1) pipeline bubble."""
    S, M = int(n_stages), int(n_microbatches)
    return float(S - 1) / float(M + S - 1) if S > 1 else 0.0


def schedule_ticks(n_stages, n_microbatches):
    return 2 * (int(n_microbatches) + int(n_stages) - 1)


def schedule_slots(schedule, n_stages, n_microbatches):
    """The per-tick slot formulas of one schedule, shared by the traced
    island (jnp inputs) and the tests/report (concrete ints — the same
    arithmetic evaluates eagerly).

    Returns ``(K, slots)`` where ``slots(t, stage)`` yields
    ``(m_f, fwd_valid, m_b, bwd_valid, m_arr, arr_valid)``:

      m_f / m_b    the microbatch this stage forwards / backwards at t
      m_arr        the stash slot of the activation payload ARRIVING at
                   t (sent by stage-1 at t-1 over the ppermute wire)

    Invariants (asserted by tests/test_pipeline_policy.py): every
    (stage, microbatch) gets exactly one forward and one backward slot;
    forwards respect the stage chain (+1 tick per hop); a backward's
    incoming cotangent is produced by stage+1 exactly one tick earlier
    (the backward wavefront — which is why the d-wire needs no stash).
    """
    import jax.numpy as jnp

    S, M = int(n_stages), int(n_microbatches)
    K = schedule_ticks(S, M)
    if schedule == "gpipe":

        def slots(t, stage):
            m_f = t - stage
            fv = (m_f >= 0) & (m_f < M) & (t <= M + S - 2)
            m_b = (2 * M + 2 * S - 3) - stage - t
            bv = (m_b >= 0) & (m_b < M) & (t >= M + S - 1)
            # sender (stage-1, t-1): m = (t-1)-(stage-1) = m_f — the
            # arrival lands in the slot consumed this same tick
            av = fv & (stage > 0)
            return m_f, fv, m_b, bv, m_f, av

        return K, slots
    if schedule != "1f1b":
        raise ValueError(
            f"pipeline_schedule must be one of {SCHEDULES}, got "
            f"{schedule!r}")

    def fwd_slot(t, stage):
        # warmup: stage s runs its first min(S-s, M) microbatches
        # back-to-back at t = s+m; steady state: one forward every
        # second tick at t = 2m+s, interleaved with backwards
        mw = t - stage
        wv = (mw >= 0) & (mw <= jnp.minimum(S - 1 - stage, M - 1))
        d = t - stage
        ms = d // 2
        sv = (d >= 0) & (d % 2 == 0) & (ms >= S - stage) & (ms < M)
        return jnp.where(wv, mw, ms), wv | sv

    def slots(t, stage):
        m_f, fv = fwd_slot(t, stage)
        db = t - (2 * S - 1) + stage  # t_b(s, m) = 2m + 2S-1 - s
        m_b = db // 2
        bv = (db >= 0) & (db % 2 == 0) & (m_b < M)
        m_arr, av = fwd_slot(t - 1, stage - 1)
        av = av & (stage > 0)
        return m_f, fv, m_b, bv, m_arr, av

    return K, slots


def _m_bubble():
    from paddle_tpu import observability as obs

    return obs.gauge(
        "pt_pipeline_bubble_frac",
        "Modeled pipeline bubble fraction (idle schedule slots / total "
        "slots, (S-1)/(M+S-1)) of the compiled gspmd pipeline "
        "schedule, per signature and schedule spelling",
        labels=("signature", "schedule"))


# ---------------------------------------------------------------------------
# the policy
# ---------------------------------------------------------------------------


class PipelinePolicy(gspecs.ShardingPolicy):
    """Pipeline stages over the ``pp`` mesh axis, everything else
    delegated to an ``inner`` policy (DataParallelPolicy by default,
    Zero1Policy with ``zero_stage=1``, or any explicit policy).

    ``cut_vars``/``num_microbatches``/``schedule`` default to the
    program's PipelineOptimizer metadata (``program._pipeline``) and the
    ``FLAGS_pipeline_*`` flags, so a program built for the legacy
    PipelineRunner runs on this lane unchanged."""

    name = "pipeline"

    def __init__(self, cut_vars=None, num_microbatches=None, schedule=None,
                 inner=None, zero_stage=0, batch_axis=pmesh.DATA_AXIS,
                 pipe_axis=pmesh.PIPE_AXIS):
        super().__init__(batch_axis=batch_axis)
        self.pipe_axis = pmesh.canonical_axis(pipe_axis)
        if inner is None:
            inner = (gspecs.Zero1Policy(batch_axis=batch_axis)
                     if int(zero_stage) >= 1
                     else gspecs.DataParallelPolicy(batch_axis=batch_axis))
        self.inner = inner
        cut = [getattr(v, "name", v) for v in (cut_vars or [])]
        self.cut_vars = cut or None
        self.num_microbatches = (int(num_microbatches)
                                 if num_microbatches else None)
        if schedule is not None and schedule not in SCHEDULES:
            raise ValueError(
                f"pipeline schedule must be one of {SCHEDULES}, got "
                f"{schedule!r}")
        self.schedule = schedule
        self._demote_warned = False

    # -- resolution ----------------------------------------------------
    def resolve_schedule(self):
        sched = self.schedule
        if sched is None:
            from paddle_tpu.fluid import flags as _flags

            sched = _flags.flag("pipeline_schedule")
        if sched not in SCHEDULES:
            raise ValueError(
                f"FLAGS_pipeline_schedule must be one of {SCHEDULES}, "
                f"got {sched!r}")
        return sched

    def resolve_cut_vars(self, program):
        if self.cut_vars:
            return list(self.cut_vars)
        meta = getattr(program, "_pipeline", None)
        if meta and meta.get("cut_vars"):
            return list(meta["cut_vars"])
        raise ValueError(
            "PipelinePolicy needs cut variables: pass cut_vars= or "
            "minimize() with PipelineOptimizer first")

    def resolve_microbatches(self, program):
        # precedence: explicit policy arg > the program's
        # PipelineOptimizer metadata (honored even at 1 — a pinned
        # M=1 must not silently become the flag default) > the flag
        if self.num_microbatches:
            return self.num_microbatches
        meta = getattr(program, "_pipeline", None)
        if meta and meta.get("num_microbatches"):
            return int(meta["num_microbatches"])
        from paddle_tpu.fluid import flags as _flags

        return int(_flags.flag("pipeline_microbatches"))

    # -- ShardingPolicy surface ----------------------------------------
    def param_spec(self, program, name, shape, mesh):
        spec = self.inner.param_spec(program, name, shape, mesh)
        if any(a and a != self.batch_axis for a in spec):
            # the island maps (pp, batch) only: a model-axis split
            # parameter would be materialized full-size per device —
            # demote to replicated and say so (once), the quant-hook
            # demotion precedent
            if not self._demote_warned:
                warnings.warn(
                    "PipelinePolicy demoted a non-batch-axis parameter "
                    f"spec ({name}: {spec}) to replicated — the pipeline "
                    "island maps (pp, batch) only; model-axis tensor "
                    "parallelism inside a stage is not yet composed")
                self._demote_warned = True
            spec = tuple(a if a == self.batch_axis else None for a in spec)
        return spec

    def feed_spec(self, program, name, shape, mesh):
        return self.inner.feed_spec(program, name, shape, mesh)

    def uses_model_axis(self, program, mesh):
        return False


# ---------------------------------------------------------------------------
# the compilation plan
# ---------------------------------------------------------------------------


class PipelinePlan:
    """Executor-side plan for one pipelined compilation: the stage
    partition, boundary wire layouts, gradient-bucket layout, fetch
    classification, modeled bubble/boundary bytes, and the island body
    builder the executor jits."""

    def __init__(self, plan, program, mesh, policy, feed_shapes,
                 feed_dtypes, feed_specs, scope, quant_hook,
                 block_size=None, algo=None, crossover_kb=None,
                 declared_feed_specs=None):
        from paddle_tpu.fluid import flags as _flags

        self.plan = plan
        self.program = program
        self.mesh = mesh
        self.policy = policy
        self.pipe_axis = policy.pipe_axis
        self.batch_axis = policy.batch_axis
        self.schedule = policy.resolve_schedule()
        self.M = policy.resolve_microbatches(program)
        cut_vars = policy.resolve_cut_vars(program)

        if self.pipe_axis not in mesh.axis_names:
            raise ValueError(
                f"PipelinePolicy needs a {self.pipe_axis!r} mesh axis; "
                f"mesh has {tuple(mesh.axis_names)} — build one with "
                "mesh.build_3d_mesh(pp=...)")
        self.stages, self._stage_of = stage_partition(
            program, plan.ops, cut_vars)
        self.S = len(self.stages)
        pp = int(mesh.shape[self.pipe_axis])
        if pp != self.S:
            raise ValueError(
                f"mesh pp axis {pp} != pipeline stages {self.S}")
        if self.S < 2:
            raise ValueError("pipeline needs at least 2 stages")
        self.dp = int(mesh.shape.get(self.batch_axis, 1))
        self.mapped_axes = (self.pipe_axis,) + (
            (self.batch_axis,) if self.batch_axis in mesh.axis_names
            else ())
        self.boundaries = boundary_sets(self.stages)
        self._validate_structure()

        # feed classification: feeds the CALLER declared replicated
        # (executor feed_specs={name: ()} — shared tables) enter the
        # island WHOLE; everything else splits into M microbatches on
        # dim 0 (the PipelineRunner contract) and keeps the batch-axis
        # component of its resolved placement.  The policy-RESOLVED spec
        # being empty (pp-only mesh, non-divisible batch) does NOT mean
        # replicated — those feeds still microbatch.
        self.feed_specs = dict(feed_specs or {})
        declared = dict(declared_feed_specs or {})
        self.split_feeds, self.whole_feeds = [], []
        self._feed_dp = {}
        for n in plan.feed_names:
            shape = tuple(feed_shapes.get(n) or ())
            if n in declared and not any(a for a in declared[n]):
                self.whole_feeds.append(n)
                continue
            # dp-sharded dim 0 (resolved by the executor) → the island
            # device sees B/dp local rows and splits THOSE into M
            # microbatches, so divisibility is over M*dp
            has_dp = (self.dp > 1 and bool(feed_specs.get(n))
                      and feed_specs[n][0] == self.batch_axis)
            denom = self.M * (self.dp if has_dp else 1)
            if not shape or shape[0] % denom:
                raise ValueError(
                    f"feed {n!r} batch {shape and shape[0]} not "
                    f"divisible by num_microbatches={self.M}"
                    + (f" x dp={self.dp}" if has_dp else "")
                    + " — declare it replicated via feed_specs="
                    "{name: ()} if it is not batch-like")
            self.split_feeds.append(n)
            self._feed_dp[n] = has_dp
        self._feed_shapes = {n: tuple(feed_shapes[n])
                             for n in plan.feed_names}
        self._feed_dtypes = dict(feed_dtypes or {})

        # scope vars the island branches read (params, not opt state)
        reads = set()
        scope_vars = set(plan.donated_names) | set(plan.readonly_names)
        for st in self.stages:
            for op in st.fwd_ops + st.bwd_ops:
                reads.update(set(op.input_arg_names) & scope_vars)
        self.scope_reads_island = sorted(reads)

        # the optimizer leg: global view, original program order
        self.ops_opt = [op for op in plan.ops
                        if op.attrs.get("op_role") == "optimize"]

        # gradient bucket: [quant..., exact...] — the quant section
        # rides the adaptive dual-int8 ring over the batch axis exactly
        # like the plain gspmd quant hook (same flags, same wire model)
        self.quant_hook = bool(quant_hook) and self.dp > 1
        self.block_size = int(block_size if block_size is not None
                              else _flags.flag("quant_allreduce_block_size"))
        self.algo = (algo if algo is not None
                     else _flags.flag("quant_allreduce_algo"))
        self.crossover_kb = crossover_kb
        self._plan_grad_bucket(scope)
        self._discovered = False
        self._model_wire_bytes()

    # -- validation ----------------------------------------------------
    @staticmethod
    def _grad_base(name):
        return name.split("@GRAD")[0] if "@GRAD" in name else None

    def _validate_structure(self):
        plan, program = self.plan, self.program
        # incoming backward cotangents must be gradients OF the boundary
        # the wire carries (a multi-consumer cut activation crosses
        # under its accumulated spelling, `v@GRAD@ACC`); anything else
        # is beyond the ring topology.  The resolved per-boundary wire
        # name map (`dnames[b][var]`) is what the island packs/unpacks.
        self.dnames = []
        for st in self.stages:
            if st.index == self.S - 1:
                if st.grads_in:
                    raise NotImplementedError(
                        "last pipeline stage expects no incoming "
                        f"gradients, got {st.grads_in}")
                continue
            boundary = list(self.boundaries[st.index])
            dmap = {}
            extra = []
            for n in st.grads_in:
                base = self._grad_base(n)
                if base in boundary and base not in dmap:
                    dmap[base] = n
                else:
                    extra.append(n)
            if extra:
                raise NotImplementedError(
                    f"stage {st.index} consumes backward values {extra} "
                    "that are not boundary-activation gradients — this "
                    "program's cross-stage gradient topology needs the "
                    "host-scheduled PipelineRunner")
            # boundary vars nobody differentiates (stop_gradient
            # pass-throughs) still occupy a wire slot: zeros cross
            for v in boundary:
                dmap.setdefault(v, grad_var_name(v))
            self.dnames.append(dmap)
        # island-produced values the optimizer leg (or scope write-back)
        # would need beyond gradients: reject loudly
        produced = set()
        for st in self.stages:
            for op in st.fwd_ops + st.bwd_ops:
                produced.update(op.output_arg_names)
        consumed_opt = set()
        for op in plan.ops:
            if op.attrs.get("op_role") == "optimize":
                consumed_opt.update(op.input_arg_names)
        grads = {g for _p, g in getattr(program, "_params_grads", [])}
        carries = sorted(
            ((consumed_opt | set(plan.write_names)) & produced) - grads)
        if carries:
            raise NotImplementedError(
                f"pipeline policy cannot carry {carries} out of the "
                "stage island (batch_norm running stats / non-gradient "
                "optimizer inputs) — use the host-scheduled "
                "PipelineRunner for this program")

    # -- gradient bucket -----------------------------------------------
    def _plan_grad_bucket(self, scope):
        block = self.plan.block
        pg = dict(getattr(self.program, "_params_grads", []))
        dgc = set(getattr(self.program, "_dgc_encoded", {}).keys()) | \
            set(getattr(self.program, "_dgc_encoded", {}).values())
        owned = []  # (param, grad, stage)
        for st in self.stages:
            for p, g in st.param_grads:
                owned.append((p, g, st.index))
        missing = sorted(set(pg.values())
                         - {g for _p, g, _s in owned})
        if missing:
            raise NotImplementedError(
                f"gradients {missing} are produced by no pipeline "
                "stage's backward ops")

        def info(p, g):
            v = block._find_var_recursive(g)
            dtype = getattr(v, "dtype", None) or "float32"
            shape = getattr(v, "shape", None)
            if shape is None or any(d is None or d < 0 for d in shape):
                pv = scope.get(p)
                shape = tuple(np.shape(pv)) if pv is not None else None
            if shape is None:
                raise ValueError(f"cannot resolve shape of gradient {g}")
            return tuple(shape), str(dtype)

        quant, exact = [], []
        for p, g, s in owned:
            shape, dtype = info(p, g)
            if dtype not in ("float32", "float16", "bfloat16",
                             "float64"):
                # the gradient bucket is one fp32 buffer (packed,
                # psum-merged over pp, mean-divided) — a non-float
                # payload would be silently corrupted by the round
                # trip, so reject it loudly (the module's contract)
                raise NotImplementedError(
                    f"gradient {g} has non-float dtype {dtype} — the "
                    "pipeline policy's fp32 gradient bucket cannot "
                    "carry it; use the host-scheduled PipelineRunner")
            entry = (p, g, s, shape, dtype)
            if self.quant_hook and g not in dgc and dtype != "float64":
                quant.append(entry)
            else:
                exact.append(entry)
        layout, off = [], 0
        for p, g, s, shape, dtype in quant + exact:
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            layout.append({"param": p, "grad": g, "stage": s,
                           "shape": shape, "dtype": dtype,
                           "offset": off, "size": size})
            off += size
        self.grad_layout = layout
        self.quant_elems = sum(e["size"] for e in layout[:len(quant)])
        self.total_grad_elems = max(off, 1)

    def _model_wire_bytes(self):
        from paddle_tpu.kernels import quantized_collectives as qc
        from paddle_tpu.kernels.ring_collectives import (
            select_allreduce_algo)

        total, buckets = 0, []
        if self.quant_hook and self.quant_elems:
            resolved = select_allreduce_algo(
                self.quant_elems, self.dp, algo=self.algo,
                crossover_kb=self.crossover_kb,
                block_size=self.block_size)
            total = qc.wire_bytes(self.quant_elems,
                                  block_size=self.block_size,
                                  n_devices=self.dp, algo=resolved)
            buckets.append({"elements": self.quant_elems,
                            "algo": resolved, "fused_update": False})
        self.wire_bytes_per_step = total
        self.bucket_report = buckets

    # -- shape discovery + layouts --------------------------------------
    def _discover(self, trace_stage, scope):
        """Chain jax.eval_shape over the stage forwards to resolve every
        boundary activation's LOCAL (per-device microbatch) shape/dtype
        plus the island-fetch shapes — no reliance on the program's
        declared (-1) shapes, and any unsupported topology fails here
        with a stage-indexed error instead of deep inside the jit."""
        import jax
        import jax.numpy as jnp

        def canon(dt):
            return jax.dtypes.canonicalize_dtype(np.dtype(str(dt)))

        def abs_of(v):
            return jax.ShapeDtypeStruct(tuple(np.shape(v)),
                                        canon(v.dtype))

        scope_abs = {n: abs_of(scope.get(n))
                     for n in self.scope_reads_island}
        mb_abs = {}
        for n in self.plan.feed_names:
            shape = self._feed_shapes[n]
            dt = canon(self._feed_dtypes.get(n, "float32"))
            if n in self.split_feeds:
                denom = self.M * (self.dp if self._feed_dp[n] else 1)
                shape = (shape[0] // denom,) + tuple(shape[1:])
            mb_abs[n] = jax.ShapeDtypeStruct(tuple(shape), dt)
        step_abs = jax.ShapeDtypeStruct((), jnp.uint32)

        # owner stage of each island fetch (produced by a stage forward)
        fwd_producer = {}
        for st in self.stages:
            for op in st.fwd_ops:
                for n in op.output_arg_names:
                    fwd_producer.setdefault(n, st.index)
        self.island_fetches = [n for n in self.plan.jit_fetch_names
                               if n in fwd_producer]
        self.fetch_owner = {n: fwd_producer[n]
                            for n in self.island_fetches}

        known = {}  # boundary var -> (shape, dtype)
        fetch_info = {}
        for st in self.stages:
            s = st.index
            acts_abs = {}
            if s > 0:
                acts_abs = {n: jax.ShapeDtypeStruct(*known[n])
                            for n in self.boundaries[s - 1]}
            wanted = list(self.boundaries[s]) if s < self.S - 1 else []
            wanted += [n for n, o in self.fetch_owner.items() if o == s]

            def f(scope_a, mb_a, acts_a, step_a, _s=s, _w=wanted):
                env = {}
                env.update(scope_a)
                env.update(mb_a)
                env.update(acts_a)
                trace_stage(env, step_a, self.stages[_s].fwd_ops,
                            mesh_axes=self.mapped_axes)
                return {n: env[n] for n in _w}

            try:
                out = jax.eval_shape(f, scope_abs, mb_abs, acts_abs,
                                     step_abs)
            except KeyError as e:
                raise NotImplementedError(
                    f"pipeline stage {s} forward needs value {e} that "
                    "crosses stages outside the boundary wire — use "
                    "the host-scheduled PipelineRunner") from None
            for n, a in out.items():
                if n in (self.boundaries[s] if s < self.S - 1 else ()) \
                        and not jnp.issubdtype(a.dtype, jnp.floating):
                    # the stage wire is one fp32 buffer: an integer
                    # activation above 2^24 (or a bool) would be
                    # silently quantized by the int->f32->int round
                    # trip — reject loudly instead
                    raise NotImplementedError(
                        f"boundary activation {n} has non-float dtype "
                        f"{a.dtype} — the pipeline policy's fp32 stage "
                        "wire cannot carry it; use the host-scheduled "
                        "PipelineRunner")
                known[n] = (tuple(a.shape), a.dtype)
                if n in self.fetch_owner:
                    fetch_info[n] = (tuple(a.shape), a.dtype)

        def layout_of(names):
            out, off = [], 0
            for n in names:
                shape, dt = known[n]
                size = int(np.prod(shape, dtype=np.int64)) if shape else 1
                out.append({"name": n, "shape": shape, "dtype": dt,
                            "offset": off, "size": size})
                off += size
            return out, off

        self.b_layout, widths = [], []
        for b in range(self.S - 1):
            lay, w = layout_of(self.boundaries[b])
            self.b_layout.append(lay)
            widths.append(w)
        self.wire_elems = max(widths + [1])
        self.f_layout, off = [], 0
        for n in self.island_fetches:
            shape, dt = fetch_info[n]
            if not jnp.issubdtype(dt, jnp.floating):
                raise NotImplementedError(
                    f"island fetch {n} has non-float dtype {dt} — the "
                    "fp32 fetch stash cannot carry it; fetch it from a "
                    "non-pipelined program")
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            self.f_layout.append({"name": n, "shape": shape, "dtype": dt,
                                  "offset": off, "size": size})
            off += size
        self.fetch_elems = max(off, 1)
        self.boundary_elems = widths
        self._discovered = True

    # -- schedule report -------------------------------------------------
    def schedule_report(self):
        """The per-stage schedule report stamped on the program
        (`program._pipeline_schedule`), the `_overlap_schedule` way:
        bubble fraction per microbatch count, boundary payloads, stash
        depth — what the docs table reads."""
        from paddle_tpu.kernels import pipeline_collectives as pcol

        S, M = self.S, self.M
        per_m = {m: round(modeled_bubble_fraction(S, m), 6)
                 for m in (1, 2, 4, 8, 16, 32) if m >= 1}
        report = {
            "schedule": self.schedule,
            "n_stages": S,
            "num_microbatches": M,
            "ticks": schedule_ticks(S, M),
            "bubble_frac": round(modeled_bubble_fraction(S, M), 6),
            "bubble_frac_per_microbatches": per_m,
            "stash_depth": min(M, S) if self.schedule == "1f1b" else M,
            "wire_elems": getattr(self, "wire_elems", None),
            "boundaries": [
                {"link": f"{b}->{b + 1}",
                 "vars": list(self.boundaries[b]),
                 "elements": self.boundary_elems[b],
                 "bytes_per_step": pcol.boundary_wire_bytes(
                     self.boundary_elems[b], M)}
                for b in range(S - 1)
            ] if self._discovered else [],
            "grad_reduction": {
                "batch_axis_devices": self.dp,
                "quant_hook": self.quant_hook,
                "quant_elements": self.quant_elems,
                "wire_bytes_per_step": self.wire_bytes_per_step,
                "buckets": self.bucket_report,
            },
        }
        return report

    # -- the island ------------------------------------------------------
    def island_body(self, trace_stage, scope):
        """Build ``fn(scope_vals, feeds, step) -> (grads, fetches)``: the
        whole microbatched schedule under ONE shard_map over
        ``(pp, batch)``.  ``trace_stage`` is the executor's one
        LowerContext assembly point, shared with the optimizer leg."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.kernels import pipeline_collectives as pcol
        from paddle_tpu.kernels.ring_collectives import (
            adaptive_quantized_all_reduce)

        if not self._discovered:
            self._discover(trace_stage, scope)

        S, M = self.S, self.M
        D = min(M, S) if self.schedule == "1f1b" else M
        K, slots = schedule_slots(self.schedule, S, M)
        W, G, F = self.wire_elems, self.total_grad_elems, self.fetch_elems
        pp, axis = self.pipe_axis, self.batch_axis
        dp_mapped = axis in self.mapped_axes
        f32 = jnp.float32
        grad_names = [e["grad"] for e in self.grad_layout]

        def pack(env, layout, width):
            flat = jnp.zeros((width,), f32)
            for e in layout:
                flat = flat.at[e["offset"]:e["offset"] + e["size"]].set(
                    jnp.ravel(env[e["name"]]).astype(f32))
            return flat

        def unpack(flat, layout, rename=None):
            out = {}
            for e in layout:
                v = flat[e["offset"]:e["offset"] + e["size"]] \
                    .reshape(e["shape"]).astype(e["dtype"])
                out[rename[e["name"]] if rename else e["name"]] = v
            return out

        def island(scope_vals, feeds, step):
            stage = lax.axis_index(pp)

            # stacked-microbatch feeds: [M, micro, ...] on dim 0
            stacked = {}
            for n, v in feeds.items():
                if n in self.split_feeds:
                    stacked[n] = jnp.reshape(
                        v, (M, v.shape[0] // M) + tuple(v.shape[1:]))
                else:
                    stacked[n] = v

            def mb_at(m):
                return {n: (lax.dynamic_index_in_dim(v, m, 0,
                                                     keepdims=False)
                            if n in self.split_feeds else v)
                        for n, v in stacked.items()}

            def fwd_branch(s):
                def br(a_slot, d_recv, mb, mstep, _s=s):
                    env = dict(scope_vals)
                    env.update(mb)
                    if _s > 0:
                        env.update(unpack(a_slot, self.b_layout[_s - 1]))
                    trace_stage(env, mstep, self.stages[_s].fwd_ops,
                                mesh_axes=self.mapped_axes)
                    wire = (pack(env, self.b_layout[_s], W)
                            if _s < S - 1 else jnp.zeros((W,), f32))
                    fl = [e for e in self.f_layout
                          if self.fetch_owner[e["name"]] == _s]
                    fb = pack({e["name"]: env[e["name"]] for e in fl},
                              fl, F) if fl else jnp.zeros((F,), f32)
                    return (wire, jnp.zeros((W,), f32),
                            jnp.zeros((G,), f32), fb)
                return br

            def bwd_branch(s):
                def br(a_slot, d_recv, mb, mstep, _s=s):
                    env = dict(scope_vals)
                    env.update(mb)
                    incoming = {}
                    if _s > 0:
                        env.update(unpack(a_slot, self.b_layout[_s - 1]))
                    if _s < S - 1:
                        incoming = unpack(d_recv, self.b_layout[_s],
                                          rename=self.dnames[_s])
                        env.update(incoming)
                    st = self.stages[_s]
                    trace_stage(env, mstep, st.fwd_ops + st.bwd_ops,
                                mesh_axes=self.mapped_axes)
                    if _s > 0:
                        dparts = {}
                        passthru = (set(self.boundaries[_s])
                                    if _s < S - 1 else set())
                        for e in self.b_layout[_s - 1]:
                            # the consumer stage's expected spelling
                            # (possibly the accumulated `@GRAD@ACC`
                            # form) — produced by this stage's traced
                            # backward under the SAME program var name
                            gname = self.dnames[_s - 1][e["name"]]
                            mine = env.get(gname)
                            thru = (incoming.get(
                                self.dnames[_s][e["name"]])
                                    if e["name"] in passthru else None)
                            # a stage both consuming AND forwarding a
                            # skip activation owns the sum of its own
                            # cotangent and the downstream one
                            if mine is not None and thru is not None \
                                    and mine is not thru:
                                dparts[gname] = (
                                    mine.astype(f32) + thru.astype(f32))
                            elif mine is not None:
                                dparts[gname] = mine
                            elif thru is not None:
                                dparts[gname] = thru
                            else:
                                dparts[gname] = jnp.zeros(e["shape"], f32)
                        dwire = pack(
                            {e["name"]:
                             dparts[self.dnames[_s - 1][e["name"]]]
                             for e in self.b_layout[_s - 1]},
                            self.b_layout[_s - 1], W)
                    else:
                        dwire = jnp.zeros((W,), f32)
                    gb = jnp.zeros((G,), f32)
                    for e in self.grad_layout:
                        if e["stage"] != _s:
                            continue
                        gb = gb.at[e["offset"]:e["offset"] + e["size"]] \
                            .set(jnp.ravel(env[e["grad"]]).astype(f32))
                    return (jnp.zeros((W,), f32), dwire, gb,
                            jnp.zeros((F,), f32))
                return br

            def noop(a_slot, d_recv, mb, mstep):
                return (jnp.zeros((W,), f32), jnp.zeros((W,), f32),
                        jnp.zeros((G,), f32), jnp.zeros((F,), f32))

            branches = ([fwd_branch(s) for s in range(S)]
                        + [bwd_branch(s) for s in range(S)] + [noop])

            def tick(carry, t):
                wire, dwire, stash, gacc, fstash = carry
                # stage-boundary transfers: the lint-sanctioned surface
                wire_r = pcol.stage_shift(wire, pp, S)
                dwire_r = pcol.stage_shift(dwire, pp, S, reverse=True)
                m_f, fv, m_b, bv, m_arr, av = slots(t, stage)
                slot_arr = jnp.clip(m_arr, 0, M - 1) % D
                stash = stash.at[slot_arr].set(
                    jnp.where(av, wire_r, stash[slot_arr]))
                m_sel = jnp.clip(jnp.where(fv, m_f, m_b), 0, M - 1)
                mb = mb_at(m_sel)
                mstep = (step * np.uint32(M)
                         + m_sel.astype(jnp.uint32))
                a_slot = stash[m_sel % D]
                idx = jnp.where(fv, stage,
                                jnp.where(bv, S + stage, 2 * S))
                w_out, d_out, gb, fb = lax.switch(
                    idx, branches, a_slot, dwire_r, mb, mstep)
                fstash = fstash.at[m_sel].set(
                    jnp.where(fv, fb, fstash[m_sel]))
                return (w_out, d_out, stash, gacc + gb, fstash), None

            carry0 = (jnp.zeros((W,), f32), jnp.zeros((W,), f32),
                      jnp.zeros((D, W), f32), jnp.zeros((G,), f32),
                      jnp.zeros((M, F), f32))
            (_, _, _, gacc, fstash), _ = lax.scan(
                tick, carry0, jnp.arange(K, dtype=jnp.int32))

            # ownership merges over pp (zero off-stage, bit-exact)
            g = pcol.stage_merge(gacc, pp) / M
            fstash = pcol.stage_merge(fstash, pp)

            # batch-axis gradient reduction: the EQuARX dual-int8 ring
            # for the quant section (transpiler seed scaling at the
            # boundary), exact fp32 mean for the rest
            if dp_mapped and self.dp > 1:
                parts = []
                if self.quant_elems:
                    parts.append(adaptive_quantized_all_reduce(
                        g[:self.quant_elems] / self.dp, axis,
                        block_size=self.block_size,
                        algo=self.algo or "auto",
                        crossover_kb=self.crossover_kb))
                if self.quant_elems < G:
                    # exact fp32 mean (DGC/non-float payloads the wire
                    # format must not touch — quant_hook._reduce_exact
                    # parity)
                    parts.append(lax.psum(                       # collective: allow
                        g[self.quant_elems:] / self.dp, axis))
                g = jnp.concatenate(parts) if len(parts) > 1 else parts[0]

            grads = {}
            for e in self.grad_layout:
                grads[e["grad"]] = (
                    g[e["offset"]:e["offset"] + e["size"]]
                    .reshape(e["shape"]).astype(e["dtype"]))
            fetches = [
                fstash[:, e["offset"]:e["offset"] + e["size"]]
                .reshape((M,) + tuple(e["shape"])).astype(e["dtype"])
                for e in self.f_layout]
            return grads, fetches

        def feed_spec(n):
            # the microbatch reshape happens INSIDE the island, so the
            # in_spec covers the raw [B, ...] feed: dp-sharded dim 0
            # when the executor resolved one, replicated otherwise
            rank = len(self._feed_shapes[n])
            if n in self.split_feeds and self._feed_dp[n]:
                return P(*((axis,) + (None,) * max(0, rank - 1)))
            return P(*((None,) * rank))

        in_specs = (
            {n: P() for n in self.scope_reads_island},
            {n: feed_spec(n) for n in self.plan.feed_names},
            P(),
        )
        fetch_spec = P(axis) if (dp_mapped and self.dp > 1) else P()
        out_specs = ({n: P() for n in grad_names},
                     [fetch_spec for _ in self.f_layout])
        mapped = jax.shard_map(island, mesh=self.mesh,
                               in_specs=in_specs, out_specs=out_specs,
                               check_vma=False)

        def body(scope_vals, feeds, step):
            # stacked split feeds enter as [M, micro, ...] inside the
            # island; the reshape itself traces in the island so the
            # global dispatch keeps the executor's plain feed signature
            return mapped(scope_vals, dict(feeds), step)

        return body


def plan_pipeline(plan, program, mesh, policy, feed_shapes, feed_dtypes,
                  feed_specs, scope, quant_hook, block_size=None,
                  algo=None, crossover_kb=None,
                  declared_feed_specs=None):
    """Build the PipelinePlan for one compilation.  Pipeline execution
    is an EXPLICIT policy choice, so structural problems raise instead
    of demoting (the quant hook demotes because it is an optimization;
    a pipeline that silently fell back to no-pipeline would train a
    different program than asked for)."""
    return PipelinePlan(plan, program, mesh, policy, feed_shapes,
                        feed_dtypes, feed_specs, scope, quant_hook,
                        block_size=block_size, algo=algo,
                        crossover_kb=crossover_kb,
                        declared_feed_specs=declared_feed_specs)
