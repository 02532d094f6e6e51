"""The one jit-partitioned GSPMD executor.

Compiles a whole Program ONCE under `jax.jit` with in/out shardings
resolved by a `ShardingPolicy` (specs.py) and
`with_sharding_constraint` annotations applied at the producing op
during the trace — no per-gradient collective ops are ever inserted by
Python.  XLA's SPMD partitioner places every collective; the compiled
HLO is inspected to publish how many bytes of resharding/collective
traffic it chose (``pt_gspmd_resharding_bytes``), which is also how the
tests PROVE the collectives came from XLA and not from the program
(tests/test_gspmd_core.py asserts no ``c_allreduce*`` op types exist in
the program it runs).

Shares the `_JitExecutable` plumbing of `fluid/executor.py` — the
compile-cache counters (``pt_compile_cache_total{path="gspmd"}``), step
histograms (``pt_step_seconds``), cost/memory analysis, and the
BlockPlan prune/analyze/write-back contract — so a GSPMD step
introspects exactly like a single-device or shard_map one.

The DP and hybrid runners are thin policy selections over this class
(`DataParallelRunner(gspmd=True)` / `HybridParallelRunner(gspmd=True)`,
FLAGS_gspmd_executor); the quantized gradient wire format rides along
through `quant_hook.py` when the quant path is opted in.
"""

from __future__ import annotations

import warnings

import numpy as np

from paddle_tpu.fluid import registry
from paddle_tpu.fluid.executor import _JitExecutable, trace_block
from paddle_tpu.observability.profiling import (hlo_collective_bytes,
                                                hlo_collective_counts,
                                                hlo_inventory)

from .. import mesh as pmesh
from . import specs as gspecs
from .quant_hook import plan_quant_hook

__all__ = ["GSPMDExecutor", "hlo_collective_bytes",
           "hlo_collective_counts", "hlo_inventory", "prep_feed"]


def prep_feed(feed, fetch_list):
    """Coerce feed values and build the (feed_sig, fetch_names) cache
    identity — THE shared dispatch-key helper of the partitioned lanes
    (HybridParallelRunner._prep delegates here).  v.dtype directly:
    np.asarray on a device-resident jax array would force a host
    transfer just to read the dtype."""
    feed = {k: np.asarray(v) if not hasattr(v, "dtype") else v
            for k, v in (feed or {}).items()}
    fetch_names = [f if isinstance(f, str) else f.name
                   for f in (fetch_list or [])]
    feed_sig = tuple((k, tuple(np.shape(v)), str(v.dtype))
                     for k, v in sorted(feed.items()))
    return feed, fetch_names, feed_sig


# ---------------------------------------------------------------------------
# compiled-HLO inspection: what did XLA's partitioner insert?
#
# The parser lives in observability/profiling.py now (promoted into the
# general per-category HLO inventory the MFU/roofline accounting reads);
# re-exported from the module imports above because this module is where
# the GSPMD acceptance gates import it.
# ---------------------------------------------------------------------------


def _m_resharding():
    from paddle_tpu import observability as obs

    return obs.gauge(
        "pt_gspmd_resharding_bytes",
        "Per-step collective/resharding bytes the GSPMD-partitioned "
        "executable moves, from compiled-HLO inspection, per signature",
        labels=("signature",))


# ---------------------------------------------------------------------------
# the compiled block
# ---------------------------------------------------------------------------


class _GSPMDBlock(_JitExecutable):
    """One (program version, feed signature, fetch list) → GSPMD-
    partitioned XLA executable, with policy-resolved in/out shardings."""

    def __init__(self, executor, scope, feed_names, fetch_names,
                 feed_shapes=None, feed_dtypes=None, n_steps=1,
                 stacked_feed=False):
        import jax

        from paddle_tpu.fluid.executor import (BlockPlan,
                                               HostOpsUnsupported)

        program, mesh, policy = (executor.program, executor.mesh,
                                 executor.policy)
        feed_shapes = dict(feed_shapes or {})
        plan = BlockPlan(program, program.global_block(), feed_names,
                         fetch_names, scope)
        if plan.host_pre_ops:
            raise NotImplementedError(
                "pre-stage host ops (distributed lookup) are only "
                "supported by the single-device Executor")
        n_steps = int(n_steps)
        chain_mode = n_steps > 1 or stacked_feed
        if chain_mode and (plan.host_ops or plan.host_fetch_names):
            raise HostOpsUnsupported(
                "run_steps chains the whole loop on-device; host ops "
                f"({[op.type for op in plan.host_ops]}) need the host "
                "between steps — use run() per step")
        self.n_steps = n_steps
        self.stacked_feed = bool(stacked_feed)
        self.plan = plan
        self.program = program
        self.mesh = mesh
        self.policy = policy
        self.feed_names = plan.feed_names
        self.fetch_names = plan.fetch_names
        self.donated_names = plan.donated_names
        self.readonly_names = plan.readonly_names
        self.write_names = plan.write_names
        self.label = (f"gspmd@{id(program):x}/v{program._version}"
                      f"/{policy.name}")
        self.last_hlo = None
        self._prof_state = {"ran": False}

        # resolved feed placement, ONE source for the jit in_shardings
        # and the quant island's in_specs: explicit executor.feed_specs
        # win (alias-canonicalized); otherwise the policy resolves
        # against the REAL feed shape, so feed_spec's divisibility gate
        # (non-divisible batch -> graceful replication) actually engages.
        # stacked_feed: the leading [n_steps] axis is the loop index —
        # the policy resolves against the PER-STEP shape and the jit
        # shardings prepend a replicated dim.
        axis = policy.batch_axis

        def per_step_shape(n):
            shape = feed_shapes.get(n)
            if shape is not None and self.stacked_feed:
                return tuple(shape[1:])
            return shape

        self._feed_specs = {}
        for n in self.feed_names:
            if n in executor.feed_specs:
                spec = tuple(pmesh.canonical_axis(a)
                             for a in executor.feed_specs[n])
            else:
                spec = policy.feed_spec(program, n, per_step_shape(n),
                                        mesh)
            self._feed_specs[n] = spec

        # pipeline policy: the microbatched stage island replaces BOTH
        # the plain trace and the quant-hook split — its batch-axis
        # gradient reduction embeds the same EQuARX ring
        # (pipeline_policy.py), so executor.quant_hook still decides the
        # wire format
        self.pplan = None
        self.qplan = None
        from .pipeline_policy import PipelinePolicy, plan_pipeline

        if isinstance(policy, PipelinePolicy):
            self.pplan = plan_pipeline(
                plan, program, mesh, policy,
                {n: per_step_shape(n) for n in self.feed_names},
                feed_dtypes, self._feed_specs, scope,
                executor.quant_hook,
                block_size=executor.quant_block_size,
                algo=executor.quant_algo,
                crossover_kb=executor.quant_crossover_kb,
                declared_feed_specs=executor.feed_specs)
        elif executor.quant_hook:
            self.qplan = plan_quant_hook(
                plan, program, mesh, policy,
                block_size=executor.quant_block_size,
                algo=executor.quant_algo,
                crossover_kb=executor.quant_crossover_kb,
                impl=executor.quant_impl)
            if self.qplan is not None:
                # the island maps only the batch axis: keep the batch
                # component of each feed's placement, replicate the rest
                self.qplan.feed_island_specs = {
                    n: tuple(a if a == axis else None for a in spec)
                    for n, spec in self._feed_specs.items()}

        cons_specs = policy.activation_constraints(program, mesh)
        cons = {n: (lambda v, s=s: gspecs.constrain(v, mesh, s))
                for n, s in cons_specs.items()}
        self.constraint_names = sorted(cons_specs)

        def trace_stage(env, step, ops, mesh_axes=()):
            """The ONE LowerContext assembly point for both stages —
            constraints apply only in global view (inside the quant
            island the batch axis is mapped, not partitioned)."""
            ctx = registry.LowerContext(
                step=step,
                is_test=getattr(program, "_is_test", False),
                block=plan.block, mesh_axes=mesh_axes)
            ctx.program = program
            ctx.dtype_policy = getattr(program, "_dtype_policy", None)
            ctx.place = None
            if not mesh_axes and cons:
                ctx.sharding_constraints = cons
            trace_block(plan.block, env, ctx, ops=ops)
            return env

        if self.pplan is not None:
            pl = self.pplan
            island = pl.island_body(
                lambda env, step, ops, mesh_axes=(): trace_stage(
                    env, step, ops, mesh_axes), scope)
            fetch_names_jit = plan.jit_fetch_names
            write_names = plan.write_names
            island_fetch_pos = {n: i
                                for i, n in enumerate(pl.island_fetches)}
            self._island_fetches = list(pl.island_fetches)

            def body(donated, readonly, feeds, step):
                scope_vals = {}
                scope_vals.update(donated)
                scope_vals.update(readonly)
                island_in = {n: scope_vals[n]
                             for n in pl.scope_reads_island}
                grads, stacked = island(island_in, dict(feeds), step)
                env = dict(scope_vals)
                env.update(grads)
                # optimizer leg in GLOBAL view: the inner policy's specs
                # (ZeRO-1 state sharding) partition it
                trace_stage(env, step, pl.ops_opt)
                fetches = [stacked[island_fetch_pos[n]]
                           if n in island_fetch_pos else env[n]
                           for n in fetch_names_jit]
                out_writes = {n: env[n] for n in write_names if n in env}
                return fetches, out_writes

            # stamp the schedule report the _overlap_schedule way, and
            # book the modeled surfaces: bubble fraction per signature +
            # per-stage-boundary payloads on the resharding gauge
            from paddle_tpu.kernels import pipeline_collectives as pcol

            from .pipeline_policy import _m_bubble

            report = pl.schedule_report()
            program._pipeline_schedule = report
            _m_bubble().labels(signature=self.label,
                               schedule=pl.schedule).set(
                report["bubble_frac"])
            for b, elems in enumerate(pl.boundary_elems):
                _m_resharding().labels(
                    signature=f"{self.label}/pp{b}-{b + 1}").set(
                    float(pcol.boundary_wire_bytes(elems, pl.M)))
        elif self.qplan is None:
            ops_all = plan.ops
            fetch_names_jit = plan.jit_fetch_names
            write_names = plan.write_names

            def body(donated, readonly, feeds, step):
                env = {}
                env.update(donated)
                env.update(readonly)
                env.update(feeds)
                trace_stage(env, step, ops_all)
                fetches = [env[n] for n in fetch_names_jit]
                out_writes = {n: env[n] for n in write_names if n in env}
                return fetches, out_writes

            self._island_fetches = []
        else:
            qp = self.qplan
            island = qp.island_body(
                lambda env, step, ops, mesh_axes=(): trace_stage(
                    env, step, ops, mesh_axes))
            fetch_names_jit = plan.jit_fetch_names
            write_names = plan.write_names
            island_fetch_pos = {n: i
                                for i, n in enumerate(qp.island_fetches)}
            self._island_fetches = list(qp.island_fetches)

            def body(donated, readonly, feeds, step):
                scope_vals = {}
                scope_vals.update(donated)
                scope_vals.update(readonly)
                island_in = {n: scope_vals[n]
                             for n in qp.scope_reads_island}
                carry, grads, fusedq, stacked = island(
                    island_in, dict(feeds), step)
                env = dict(scope_vals)
                env.update(carry)
                env.update(grads)
                # the fused-update leg's keep-quant wire triple: the
                # rewritten optimizer ops (qp.ops_opt_fused) dequantize
                # their block slice inline — the reduced fp32 bucket
                # never materializes on this lane either
                env.update(fusedq)
                trace_stage(env, step, qp.ops_opt_fused)
                fetches = [stacked[island_fetch_pos[n]]
                           if n in island_fetch_pos else env[n]
                           for n in fetch_names_jit]
                out_writes = {n: env[n] for n in write_names if n in env}
                return fetches, out_writes

        # read AFTER island_body construction: a demoted
        # custom_partitioning reducer zeroes the plan's modeled bytes
        active_plan = self.pplan or self.qplan
        self.wire_bytes_per_step = (active_plan.wire_bytes_per_step
                                    if active_plan else 0)
        self.fused_bytes_saved = (self.qplan.fused_bytes_saved
                                  if self.qplan else 0)

        from paddle_tpu.health import wrap_body as _health_gate

        body = _health_gate(program, body)

        if chain_mode:
            # run_steps: the whole n-step loop in ONE jitted call — the
            # ONE chain combinator every lane shares
            # (fluid.executor.chain_step_body): fori_loop threads the
            # donated params/opt-state on-device, the step counter
            # advances per iteration, only the final step's fetches
            # return.
            from paddle_tpu.fluid.executor import chain_step_body

            body = chain_step_body(body, n_steps, self.stacked_feed)

        def mesh_body(*args):
            # mesh-adaptive lowerings (ring attention) read current_mesh()
            with pmesh.mesh_guard(mesh):
                return body(*args)

        def shard_of(name, v):
            shape = tuple(np.shape(v)) if v is not None else None
            return gspecs.named_sharding(
                mesh, policy.param_spec(program, name, shape, mesh))

        don_sh = {n: shard_of(n, scope.get(n)) for n in self.donated_names}
        ro_sh = {n: shard_of(n, scope.get(n)) for n in self.readonly_names}

        def feed_sharding(n):
            spec = self._feed_specs[n]
            if self.stacked_feed:
                # leading [n_steps] axis is the loop index — replicated;
                # the batch dim (now dim 1) keeps its resolved sharding
                spec = (None,) + tuple(spec)
            return gspecs.named_sharding(mesh, spec)

        feeds_sh = {n: feed_sharding(n) for n in self.feed_names}
        repl = gspecs.named_sharding(mesh, ())
        stacked_sh = gspecs.named_sharding(mesh, (axis,)) \
            if axis in mesh.axis_names else repl
        fetch_sh = [stacked_sh if n in self._island_fetches else repl
                    for n in plan.jit_fetch_names]
        out_sh = (fetch_sh,
                  {n: don_sh.get(n, repl) for n in self.write_names})
        self._in_shardings = (don_sh, ro_sh, feeds_sh, repl)
        self._jitted = jax.jit(mesh_body,
                               in_shardings=self._in_shardings,
                               out_shardings=out_sh,
                               donate_argnums=(0,))
        self._don_sh, self._ro_sh, self._feeds_sh = don_sh, ro_sh, feeds_sh
        self.capture_hlo = executor.capture_hlo

    def _capture_hlo(self, args):
        """AOT-lower the same computation and record its OPTIMIZED
        (post-partitioner) HLO: feeds .last_hlo, the resharding gauge and
        the acceptance gates.  The XLA compile dedupes against the
        dispatch compile through jax's compilation cache, so this costs
        one extra trace, not one extra compile.  A failure latches
        (_hlo_capture_failed) — retrying the whole-program retrace every
        step would tax pt_step_seconds and re-warn forever."""
        try:
            self.last_hlo = self._jitted.lower(*args).compile().as_text()
        except Exception as e:  # backend without as_text
            self._hlo_capture_failed = True
            warnings.warn(f"gspmd HLO capture failed: {e}")
            return
        inv = hlo_inventory(self.last_hlo)
        _m_resharding().labels(signature=self.label).set(
            float(inv["total"]["bytes"]))
        # feed the attribution layer: the collective inventory joins the
        # cost-model flops/bytes into the per-signature roofline verdict
        from paddle_tpu.observability import profiling as _profiling

        _profiling.note_collectives(
            self.label, inv["total"]["bytes"],
            counts={k: v["count"] for k, v in inv.items()
                    if k != "total"})

    def run(self, scope, feeds, step):
        from paddle_tpu.fluid import profiler as _prof
        from paddle_tpu.observability import profiling as _profiling

        # step_phases outermost; timed_run keeps its historic region
        # (staging..scope-writes) so the "run" span never absorbs the
        # host-op tail — fetch_sync brackets accumulate across both
        with _profiling.step_phases("gspmd", self.label) as ph:
            with _prof.timed_run(self.label, self._prof_state) as timer:
                with ph.phase("feed_prep"):
                    donated = {n: scope.get(n)
                               for n in self.donated_names}
                    readonly = {n: scope.get(n)
                                for n in self.readonly_names}
                    args = (donated, readonly, dict(feeds),
                            np.uint32(step))
                    if (self.capture_hlo and self.last_hlo is None
                            and not getattr(self, "_hlo_capture_failed",
                                            False)):
                        self._capture_hlo(
                            self._jit_args(scope, feeds, step))
                with ph.phase("dispatch"):
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore")  # donation unsupported on CPU
                        fetches, out_writes = self._jitted(*args)
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
                self.plan.run_host_ops(scope)
                out = self.plan.assemble_fetches(fetches, scope)
        return out


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------


class GSPMDExecutor:
    """Compile + run a Program GSPMD-partitioned under one policy.

    The runners' shared core: `DataParallelRunner(gspmd=True)` selects a
    `DataParallelPolicy`, `HybridParallelRunner(gspmd=True)` a
    `TensorParallelPolicy` — both delegate run/cost_analysis here, so
    there is exactly one partitioned compile path (ROADMAP "GSPMD-native
    sharding core").

    quant_hook (None = FLAGS_quant_allreduce): keep gradient reduction
    on the dual-int8 adaptive ring inside the partitioned graph
    (quant_hook.py) — wire bytes book on the same
    ``pt_collective_payload_bytes_total{collective="c_allreduce_quant"}``
    family the transpiler lane uses.
    """

    def __init__(self, program, mesh, policy=None, scope=None,
                 feed_specs=None, quant_hook=None, quant_block_size=None,
                 quant_algo=None, quant_crossover_kb=None,
                 quant_impl=None, capture_hlo=True, loss_name=None):
        from paddle_tpu.fluid import flags as _flags

        self.program = program
        self.mesh = mesh
        self.policy = policy or gspecs.DataParallelPolicy()
        self.feed_specs = dict(feed_specs or {})
        self._default_scope = scope
        # graph-optimization passes (FLAGS_graph_passes) BEFORE the
        # health transpile and any compile — the program stays free of
        # collective ops (the pass layer only rewrites compute
        # subgraphs), so the "zero c_allreduce in program" contract of
        # this lane is untouched
        from paddle_tpu import passes as _graph_passes

        _graph_passes.apply_graph_passes(program, lane="gspmd",
                                         loss_name=loss_name)
        # health sentinel (FLAGS_health_sentinel, docs/DISTRIBUTED.md
        # §6): transpiled into the program BEFORE any compile — the
        # check lands in the optimizer leg (post-reduction, global
        # view), and the gspmd lane's in-graph gate rides wrap_body
        from paddle_tpu import health

        self._sentinel = health.attach(program, loss_name=loss_name,
                                       lane="gspmd")
        if quant_hook is None:
            quant_hook = _flags.flag("quant_allreduce")
        self.quant_hook = bool(quant_hook)
        self.quant_block_size = quant_block_size
        self.quant_algo = quant_algo
        self.quant_crossover_kb = quant_crossover_kb
        self.quant_impl = quant_impl
        self.capture_hlo = bool(capture_hlo)
        self._cache = {}
        self._ran_keys = set()
        self._step = 0

    # -- introspection -------------------------------------------------
    def describe_policy(self, scope=None):
        """The resolved ParamSpec table (specs.ShardingPolicy.describe)
        against the bound scope — what docs/DISTRIBUTED.md's policy table
        renders."""
        scope = self._resolve_scope(scope)
        return self.policy.describe(self.program, scope, self.mesh)

    def compiled_blocks(self):
        return list(self._cache.values())

    @property
    def last_hlo(self):
        for cb in self._cache.values():
            if cb.last_hlo:
                return cb.last_hlo
        return None

    # -- dispatch ------------------------------------------------------
    def _resolve_scope(self, scope):
        if scope is not None:
            return scope
        if self._default_scope is not None:
            return self._default_scope
        from paddle_tpu.fluid.executor import global_scope

        return global_scope()

    _prep = staticmethod(prep_feed)

    def run(self, scope=None, feed=None, fetch_list=None,
            return_numpy=True):
        scope = self._resolve_scope(scope)
        feed, fetch_names, feed_sig = self._prep(feed, fetch_list)
        key = (self.program._version, feed_sig, tuple(fetch_names))
        return self._dispatch(key, scope, feed, fetch_names, 1, False,
                              return_numpy)

    def run_steps(self, feed, n_steps, fetch_list=None, scope=None,
                  return_numpy=True, stacked_feed=False):
        """``n_steps`` partitioned steps in ONE jitted call — the
        fori_loop carries the policy-sharded params/opt-state on-device
        (the big-training scan-over-steps pattern), amortizing dispatch
        exactly like the classic lane's chain (fluid/executor.py
        run_steps).  stacked_feed=True: feed arrays carry a leading
        [n_steps] axis (replicated across the mesh), one slice per
        iteration.  Only the final step's fetches return."""
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

    def _verify_preflight(self, feed, fetch_names, scope,
                          stacked_feed=False):
        """FLAGS_program_verify hook for the gspmd lane: the shared
        dataflow/shape families plus (mesh, policy, quant-hook)
        legality.  ProgramVerifyError propagates; analyzer crashes
        degrade to a warning (the executor must never die on its own
        diagnostics)."""
        from paddle_tpu.fluid import flags as _flags

        if str(_flags.flag("program_verify")).lower() in (
                "off", "0", "false", "none", ""):
            return
        import warnings

        from paddle_tpu import analysis

        feed_shapes, feed_dtypes = {}, {}
        for name, val in (feed or {}).items():
            shp = tuple(np.shape(val))
            if stacked_feed and shp:
                shp = shp[1:]  # leading dim is the step axis
            feed_shapes[name] = shp
            feed_dtypes[name] = str(getattr(val, "dtype", "") or "") or None
        try:
            analysis.preflight(
                self.program, lane="gspmd", mesh=self.mesh,
                policy=self.policy, quant_hook=self.quant_hook,
                feed_names=list((feed or {}).keys()),
                feed_shapes=feed_shapes, feed_dtypes=feed_dtypes,
                fetch_names=list(fetch_names or []),
                scope_keys=list(scope.keys()) if scope is not None else None)
        except analysis.ProgramVerifyError:
            raise
        except Exception as e:
            warnings.warn(f"program verification failed to run "
                          f"({type(e).__name__}: {e}) — continuing "
                          f"without preflight")

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
            _m_cache().labels(path="gspmd", result="miss").inc()
            # static verification at the compile boundary: the gspmd
            # lane adds (mesh, policy, quant hook) legality on top of
            # the dataflow/shape families (FLAGS_program_verify)
            self._verify_preflight(feed, fetch_names, scope,
                                   stacked_feed=bool(stacked_feed))
            if sent is not None:
                sent.ensure_state(scope)  # before BlockPlan scope checks
            t0 = _time.perf_counter()  # observability: allow
            cb = _GSPMDBlock(self, scope, list(feed.keys()), fetch_names,
                             feed_shapes={k: tuple(np.shape(v))
                                          for k, v in feed.items()},
                             feed_dtypes={k: str(v.dtype)
                                          for k, v in feed.items()},
                             n_steps=n_steps, stacked_feed=stacked_feed)
            self._cache[key] = cb
            _m_compile_seconds().labels(
                path="gspmd", phase="trace").inc(_time.perf_counter() - t0)  # observability: allow
        else:
            _m_cache().labels(path="gspmd", result="hit").inc()
        def attempt():
            first_run = key not in self._ran_keys
            t0 = _time.perf_counter()  # observability: allow
            fetches = cb.run(scope, feed, self._step)
            step_s = _time.perf_counter() - t0  # observability: allow
            _record_step("gspmd", step_s, first_run)
            self._ran_keys.add(key)
            if cb.wire_bytes_per_step:
                from ..data_parallel import collective_payload_counter

                collective_payload_counter().labels(
                    collective="c_allreduce_quant").inc(
                    cb.wire_bytes_per_step * n_steps)
            if cb.fused_bytes_saved:
                from ..data_parallel import fused_update_bytes_counter

                fused_update_bytes_counter().inc(
                    cb.fused_bytes_saved * n_steps)
            # stacked_feed: leading feed axis is the step index, not batch
            batch = 0 if stacked_feed else _feed_batch(feed) * n_steps
            _report_examples("gspmd", batch, step_s)
            self._step += n_steps
            return fetches

        from paddle_tpu.health import run_guarded

        fetches = run_guarded(sent, scope, fetch_names, attempt,
                              chain=n_steps > 1)
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return fetches

    def cost_analysis(self, feed, fetch_list=None, scope=None):
        """XLA cost/memory analysis of an already-run signature — the
        shared _JitExecutable surface (pt_xla_* gauges included)."""
        scope = self._resolve_scope(scope)
        feed, fetch_names, feed_sig = self._prep(feed, fetch_list)
        cb = self._cache.get((self.program._version, feed_sig,
                              tuple(fetch_names)))
        if cb is None:
            raise ValueError(
                "no compiled GSPMD executable for this (feed, fetch_list) "
                "signature — run the step once first")
        return cb.cost_analysis(scope, feed)
