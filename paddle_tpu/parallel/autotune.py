"""Mesh autotuner: derive the (pp, batch, model) policy from measurement.

Every parallelism the system grew — DP, ZeRO-1, TP, pipeline — composes
over one 3-D mesh (`mesh.build_3d_mesh`), and the substrate already
measures everything a search needs: per-signature XLA flops/bytes
(`observability.profiling`), the exact-to-HLO `wire_bytes` collective
models (`kernels.quantized_collectives`, PR-8 precedent), and the
modeled pipeline bubble (`gspmd.pipeline_policy`).  This module closes
the loop (ROADMAP "Mesh autotuning"; arXiv:2004.13336 is the precedent
that sharding choice is derivable rather than hand-specified,
arXiv:2301.13062 the precedent for validating an analytic cost model
against what the compiler actually emits):

  1. **enumerate** every legal mesh factorization ``(pp, dp, mp)`` of N
     devices crossed with policy assignments (pure DP, `Zero1Policy`,
     `TensorParallelPolicy`, `PipelinePolicy` × schedule × microbatch
     count), rejecting illegal combos through the PR-16 verifier's
     sharding family (`analysis.verify`, device-free `AbstractMesh`) —
     NOT ad-hoc checks;
  2. **prune** with an analytic cost model — compute/memory roofline
     (`profiling.roofline` over XLA cost-analysis numbers), collective
     cost from the existing `wire_bytes`/`gather_wire_bytes`/ring-algo
     models (256 KB oneshot→ring crossover included), pipeline bubble
     from `modeled_bubble_fraction` — yielding a ranked candidate list
     with per-term attribution;
  3. **measure** the top-K shortlist with real compiles through
     `GSPMDExecutor` (AOT-/compile-cache-aware: re-tuning a seen shape
     is zero-compile), reading `hlo_collective_bytes` and step
     quantiles per candidate;
  4. **emit** a versioned JSON report (`autotune_report.json`) the
     runners accept as a pin (``DataParallelRunner(policy_pin=...)`` /
     ``HybridParallelRunner(policy_pin=...)`` / `FLAGS_autotune_report`).

Collective-bytes prediction is term-wise honest about its confidence
(validated against compiled HLO on the 8-device CPU mesh,
tests/test_autotune.py):

  dp grad all-reduce (fp32)   4 bytes × Σ grad elements — the SPMD
                              all-reduce's per-device image IS the full
                              tensor (measured exact + one 4-byte loss
                              scalar).
  dp grad reduce (quant)      the gspmd quant hook's own bucket model
                              replicated statically (plain bucket raw
                              elems + fused bucket block-padded elems,
                              `wire_bytes` each with the ring crossover)
                              — measured EXACT (ratio 1.0, PR-8 gate).
  zero1 param re-gather       4 bytes × Σ full param image over params
                              whose optimizer state shards (dim0
                              divisible by dp) — measured exact.
  tp activations              modeled (row-parallel psum images); the
                              partitioner's actual gather/reshard
                              choices vary — confidence "modeled", kept
                              out of the exactness gate.
  pipeline boundaries         `boundary_wire_bytes` per stage link —
                              confidence "modeled".

See docs/AUTOTUNE.md for the search space, report schema and pinning
workflow.
"""

from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from paddle_tpu import observability as obs

from . import mesh as pmesh
from .gspmd import specs as gspecs

__all__ = [
    "Candidate",
    "CostInputs",
    "autotune",
    "enumerate_candidates",
    "load_report",
    "measure_candidates",
    "policy_summary",
    "predict",
    "predict_collective_bytes",
    "resolve_pin",
    "save_report",
]

REPORT_SCHEMA = "paddle_tpu.autotune/v1"
REPORT_VERSION = 1

_FUSED_OPT_TYPES = ("sgd", "adam", "adamw", "lamb", "momentum")
_QUANT_DTYPES = ("float32", "float16", "bfloat16")
DEFAULT_MICROBATCHES = (2, 4, 8)


def _m_candidates():
    return obs.counter(
        "pt_autotune_candidates_total",
        "mesh-autotuner candidates by stage (enumerated / legal / "
        "rejected / measured)", labels=("stage",))


def _m_pred_err():
    return obs.gauge(
        "pt_autotune_prediction_error",
        "relative error |predicted - measured| / measured of the "
        "analytic collective-bytes model per measured candidate",
        labels=("candidate",))


def _m_winner_rank():
    return obs.gauge(
        "pt_autotune_winner_rank",
        "analytic rank (0 = predicted fastest) of the measured-fastest "
        "candidate — the cost model's headline accuracy")


# ---------------------------------------------------------------------------
# candidates
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the search space: a mesh factorization
    ``(pp, dp, mp)`` of N devices plus the policy assignment riding it.
    Frozen/hashable so symmetric duplicates dedup through a set."""

    pp: int = 1
    dp: int = 1
    mp: int = 1
    policy: str = "dp"  # "dp" | "zero1" | "tp" | "pipeline"
    zero_stage: int = 0
    schedule: str = None  # pipeline only: "gpipe" | "1f1b"
    microbatches: int = None  # pipeline only
    quant: bool = False

    @property
    def n_devices(self):
        return self.pp * self.dp * self.mp

    @property
    def mesh_dims(self):
        return {pmesh.PIPE_AXIS: self.pp, pmesh.DATA_AXIS: self.dp,
                pmesh.MODEL_AXIS: self.mp}

    def label(self):
        s = f"pp{self.pp}.dp{self.dp}.mp{self.mp}/{self.policy}"
        if self.policy == "tp" and self.zero_stage:
            s += f"+zero{self.zero_stage}"
        if self.policy == "pipeline":
            s += f"[{self.schedule},m{self.microbatches}"
            s += f",zero{self.zero_stage}]" if self.zero_stage else "]"
        if self.quant:
            s += "+quant"
        return s

    def abstract_mesh(self):
        """Device-free mesh stand-in for the verifier preflight —
        mirrors `build_3d_mesh`'s axis elision (size-1 pp/mp dropped,
        dp always present)."""
        from paddle_tpu.analysis import AbstractMesh

        axes = {}
        if self.pp > 1:
            axes[pmesh.PIPE_AXIS] = self.pp
        axes[pmesh.DATA_AXIS] = self.dp
        if self.mp > 1:
            axes[pmesh.MODEL_AXIS] = self.mp
        return AbstractMesh(axes)

    def build_mesh(self, devices=None):
        return pmesh.build_3d_mesh(pp=self.pp, batch=self.dp,
                                   model=self.mp, devices=devices)

    def build_policy(self, rules=None):
        """Instantiate the ShardingPolicy this candidate names — the
        same classes `policy_for` selects, made explicit so a pinned
        report reconstructs the exact assignment."""
        if self.policy == "dp":
            return gspecs.DataParallelPolicy()
        if self.policy == "zero1":
            return gspecs.Zero1Policy()
        if self.policy == "tp":
            return gspecs.TensorParallelPolicy(rules=rules,
                                               zero_stage=self.zero_stage)
        if self.policy == "pipeline":
            from .gspmd.pipeline_policy import PipelinePolicy

            return PipelinePolicy(schedule=self.schedule,
                                  num_microbatches=self.microbatches,
                                  zero_stage=self.zero_stage)
        raise ValueError(f"unknown candidate policy {self.policy!r}")

    def to_json(self):
        return {k: v for k, v in dataclasses.asdict(self).items()
                if v is not None}

    @classmethod
    def from_json(cls, d):
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - fields
        if unknown:
            raise ValueError(
                f"autotune candidate has unknown fields {sorted(unknown)}"
                f" — report from a newer schema? ({REPORT_SCHEMA})")
        return cls(**d)


def _factorizations(n):
    """All ordered triples (pp, dp, mp) with pp*dp*mp == n."""
    out = []
    for pp in range(1, n + 1):
        if n % pp:
            continue
        rest = n // pp
        for dp in range(1, rest + 1):
            if rest % dp:
                continue
            out.append((pp, dp, rest // dp))
    return out


def _pipeline_stages(program):
    """Stage count the program's PipelineOptimizer metadata pins, or 0
    when the program carries no cut — pipeline candidates only exist
    where a cut does (resolve_cut_vars would raise otherwise)."""
    meta = getattr(program, "_pipeline", None)
    if not meta or not meta.get("cut_vars"):
        return 0
    return len(meta["cut_vars"]) + 1


def enumerate_candidates(program, n_devices, rules=None, quant=None,
                         microbatch_counts=DEFAULT_MICROBATCHES,
                         feed_shapes=None, verify=True):
    """Phase 1: every legal (mesh factorization × policy assignment)
    for ``program`` on ``n_devices``.

    The policy crossing only emits combos where each >1 mesh axis is
    actually consumed (mp>1 ⇒ TP, pp>1 ⇒ pipeline, ZeRO-1 ⇒ dp>1) —
    that IS the symmetric dedup: a pure-DP assignment on an (1, 1, 8)
    mesh is the replicated single-device program wearing a costume.
    pp>1 × mp>1 combos are excluded — PipelinePolicy's island maps
    (pp, batch) only and demotes model-axis params (its documented
    limit), so such a candidate would silently measure as pipeline-only.

    Legality is the PR-16 verifier's sharding family over a device-free
    `AbstractMesh` — one error-severity finding rejects the candidate.
    """
    if quant is None:
        from paddle_tpu.fluid import flags as _flags

        quant = bool(_flags.flag("quant_allreduce"))
    n = int(n_devices)
    if n < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices!r}")
    stages = _pipeline_stages(program)
    raw = set()
    for pp, dp, mp in _factorizations(n):
        if pp > 1 and (mp > 1 or pp != stages):
            continue
        if pp == 1 and mp == 1:
            raw.add(Candidate(pp=pp, dp=dp, mp=mp, policy="dp",
                              quant=quant and dp > 1))
            if dp > 1:
                raw.add(Candidate(pp=pp, dp=dp, mp=mp, policy="zero1",
                                  zero_stage=1, quant=quant))
        elif pp == 1:
            raw.add(Candidate(pp=pp, dp=dp, mp=mp, policy="tp",
                              quant=quant and dp > 1))
            if dp > 1:
                raw.add(Candidate(pp=pp, dp=dp, mp=mp, policy="tp",
                                  zero_stage=1, quant=quant))
        else:
            for sched in ("gpipe", "1f1b"):
                for m in microbatch_counts:
                    raw.add(Candidate(pp=pp, dp=dp, mp=mp,
                                      policy="pipeline", schedule=sched,
                                      microbatches=int(m),
                                      quant=quant and dp > 1))
                    if dp > 1:
                        raw.add(Candidate(
                            pp=pp, dp=dp, mp=mp, policy="pipeline",
                            schedule=sched, microbatches=int(m),
                            zero_stage=1, quant=quant))
    ordered = sorted(raw, key=lambda c: (c.pp, c.mp, c.dp, c.policy,
                                         c.zero_stage,
                                         c.schedule or "",
                                         c.microbatches or 0))
    _m_candidates().labels(stage="enumerated").inc(len(ordered))
    if not verify:
        return ordered
    from paddle_tpu import analysis

    legal = []
    for cand in ordered:
        report = analysis.verify(
            program, mesh=cand.abstract_mesh(),
            policy=cand.build_policy(rules=rules),
            feed_shapes=feed_shapes, quant_hook=cand.quant,
            families={"sharding"})
        if report.errors:
            _m_candidates().labels(stage="rejected").inc()
            continue
        legal.append(cand)
    _m_candidates().labels(stage="legal").inc(len(legal))
    return legal


# ---------------------------------------------------------------------------
# analytic cost model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CostInputs:
    """The per-step workload gauges the cost model consumes — XLA
    cost-analysis numbers of the UNPARTITIONED step (the
    `pt_xla_flops` / `pt_xla_bytes_accessed` surface) plus the feed's
    batch rows."""

    flops: float
    bytes_accessed: float
    batch_rows: int = 1


def _params_grads(program):
    pg = getattr(program, "_params_grads", None)
    if not pg:
        raise ValueError(
            "autotune needs an optimized program (minimize() stamps "
            "_params_grads) — got a forward-only program")
    block = program.global_block()
    out = []
    for p, g in pg:
        v = block._find_var_recursive(p)
        if v is None or not v.shape or any(d is None or d < 0
                                           for d in v.shape):
            continue
        gv = block._find_var_recursive(g)
        out.append((p, g, tuple(v.shape),
                    gv.dtype if gv is not None else "float32"))
    return out


def _quant_bucket_split(program, block_size=None):
    """Static replica of the gspmd quant hook's bucket planning
    (`quant_hook._plan_fused_updates` / `_model_wire_bytes`): grads
    whose ONLY consumer is their one fused-eligible optimizer op ride
    the block-padded fused bucket; everything else quantizable rides
    the plain bucket at raw element count.  Keeping this arithmetic
    identical is what makes the quant term measured-exact (ratio 1.0)
    against the compiled HLO."""
    from paddle_tpu.fluid import flags as _flags
    from paddle_tpu.kernels.quantized_collectives import DEFAULT_BLOCK_SIZE

    bs = int(block_size or _flags.flag("quant_allreduce_block_size")
             or DEFAULT_BLOCK_SIZE)
    fused_on = bool(_flags.flag("fused_update"))
    dgc = getattr(program, "_dgc_encoded", {})
    exempt = set(dgc.keys()) | set(dgc.values())
    quant = [(g, shape) for _p, g, shape, dt in _params_grads(program)
             if dt in _QUANT_DTYPES and g not in exempt]
    ops = program.global_block().ops
    consumers = {}
    for op in ops:
        for g in set(op.input_arg_names):
            consumers.setdefault(g, []).append(op)
    fused_padded, plain = 0, 0
    fused_raw = 0
    for g, shape in quant:
        elems = int(np.prod(shape))
        cons = consumers.get(g, [])
        if (fused_on and len(cons) == 1
                and cons[0].type in _FUSED_OPT_TYPES
                and cons[0].inputs.get("Grad") == [g]):
            fused_raw += elems
            fused_padded += elems + (-elems) % bs
        else:
            plain += elems
    if fused_padded > 2 * fused_raw:  # the hook's alignment-bloat guard
        plain += fused_raw
        fused_padded = 0
    return plain, fused_padded, bs


def predict_collective_bytes(program, candidate, rules=None,
                             batch_rows=1):
    """Per-step collective bytes the compiled executable will move
    (the `hlo_collective_bytes` surface), term-attributed.  Returns
    ``(total, terms, confidence)`` where confidence is "exact" when
    every non-zero term is HLO-validated (dp fp32/quant, zero1 gather)
    and "modeled" when a tp/pipeline estimate contributes."""
    from paddle_tpu.kernels import quantized_collectives as qc
    from paddle_tpu.kernels.ring_collectives import select_allreduce_algo

    dp, mp, pp = candidate.dp, candidate.mp, candidate.pp
    pg = _params_grads(program)
    terms = {}
    confidence = "exact"
    policy = candidate.build_policy(rules=rules)
    uses_model = mp > 1 and policy.uses_model_axis(
        program, candidate.abstract_mesh())
    if dp > 1:
        quant_active = candidate.quant and not uses_model
        if quant_active:
            plain, fused, bs = _quant_bucket_split(program)
            total_q = 0
            for elems in (plain, fused):
                if elems:
                    algo = select_allreduce_algo(elems, dp, block_size=bs)
                    total_q += qc.wire_bytes(elems, block_size=bs,
                                             n_devices=dp, algo=algo)
            terms["quant_allreduce"] = total_q
        else:
            grad_elems = sum(int(np.prod(shape)) for _p, _g, shape, dt
                             in pg)
            # + one 4-byte scalar: the global loss-mean all-reduce
            terms["grad_allreduce"] = 4 * grad_elems + 4
        if candidate.zero_stage >= 1 or candidate.policy == "zero1":
            gather = sum(4 * int(np.prod(shape))
                         for _p, _g, shape, _dt in pg
                         if shape and shape[0] % dp == 0)
            terms["zero1_gather"] = gather
    if uses_model:
        # modeled: row-parallel contractions psum a full activation
        # image forward and backward; the partitioner's own
        # gather/reshard choices on top are NOT predicted
        mesh = candidate.abstract_mesh()
        act = policy.activation_constraints(program, mesh)
        block = program.global_block()
        rows = max(int(batch_rows), 1) // max(dp, 1) or 1
        psum = 0
        for name, spec in act.items():
            if any(a for a in spec):
                continue  # column-parallel stays sharded — no psum
            v = block._find_var_recursive(name)
            if v is None or not v.shape:
                continue
            elems = int(np.prod([rows if d is None or d < 0 else d
                                 for d in v.shape]))
            psum += 2 * 4 * elems  # fwd psum + bwd input-grad psum
        terms["tp_activations"] = psum
        confidence = "modeled"
    if pp > 1:
        from paddle_tpu.kernels.pipeline_collectives import (
            boundary_wire_bytes)
        from .pipeline import boundary_sets, stage_partition

        # one microbatch's slice of the per-device batch crosses each
        # link per tick
        micro_rows = (max(int(batch_rows), 1)
                      // max(dp * (candidate.microbatches or 1), 1)) or 1
        try:
            cut_vars = policy.resolve_cut_vars(program)
            block = program.global_block()
            stages, _stage_of = stage_partition(program, list(block.ops),
                                                cut_vars)
            elems = 0
            for bset in boundary_sets(stages):
                for nm in bset:
                    v = block._find_var_recursive(nm)
                    if v is not None and v.shape:
                        elems += int(np.prod(
                            [micro_rows if d is None or d < 0 else d
                             for d in v.shape]))
            terms["pipeline_boundary"] = boundary_wire_bytes(
                elems, candidate.microbatches or 1)
        except Exception:
            terms["pipeline_boundary"] = 0
        confidence = "modeled"
    return sum(terms.values()), terms, confidence


def predict(program, candidate, cost_inputs, rules=None, peaks=None):
    """Phase 2 scoring: modeled step seconds with per-term attribution.

    compute/memory divide by the devices the policy actually uses
    (an unconsumed mesh axis buys nothing); collectives ride the ICI
    peak; the pipeline bubble inflates the compute leg by
    bubble/(1-bubble) per `modeled_bubble_fraction`."""
    from paddle_tpu.observability import profiling

    if peaks is None:
        _plat, pf, pbw, pici = profiling.device_peaks()
    else:
        pf, pbw, pici = peaks
    policy = candidate.build_policy(rules=rules)
    n_eff = candidate.dp * candidate.pp
    if candidate.mp > 1 and policy.uses_model_axis(
            program, candidate.abstract_mesh()):
        n_eff *= candidate.mp
    compute_s = float(cost_inputs.flops or 0) / n_eff / pf
    memory_s = float(cost_inputs.bytes_accessed or 0) / n_eff / pbw
    roofline_s = max(compute_s, memory_s)
    coll_bytes, coll_terms, confidence = predict_collective_bytes(
        program, candidate, rules=rules,
        batch_rows=cost_inputs.batch_rows)
    collective_s = coll_bytes / pici
    bubble_s = 0.0
    bubble_frac = 0.0
    if candidate.policy == "pipeline":
        from .gspmd.pipeline_policy import modeled_bubble_fraction

        bubble_frac = modeled_bubble_fraction(candidate.pp,
                                              candidate.microbatches or 1)
        bubble_s = roofline_s * bubble_frac / max(1.0 - bubble_frac, 1e-9)
    total_s = roofline_s + collective_s + bubble_s
    return {
        "total_s": total_s,
        "terms": {"compute_s": compute_s, "memory_s": memory_s,
                  "collective_s": collective_s, "bubble_s": bubble_s},
        "collective_bytes": int(coll_bytes),
        "collective_terms": {k: int(v) for k, v in coll_terms.items()},
        "bubble_fraction": bubble_frac,
        "effective_devices": n_eff,
        "confidence": confidence,
    }


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def _gspmd_cache_counts():
    """``pt_compile_cache_total{path="gspmd"}`` by result — the sample
    keys are (path, result) label tuples (metrics.snapshot contract)."""
    snap = obs.snapshot().get("pt_compile_cache_total") or {}
    out = {"hit": 0, "miss": 0, "aot_hit": 0, "aot_saved": 0}
    for key, v in (snap.get("samples") or {}).items():
        parts = tuple(key) if isinstance(key, (tuple, list)) else (key,)
        if "gspmd" not in parts:
            continue
        for res in out:
            if res in parts:
                out[res] += int(v)
    return out


def measure_candidates(build, candidates, feed, loss_name=None,
                       steps=None, rules=None, devices=None,
                       predictions=None):
    """Phase 3: real compiles for the shortlist through the one
    jit-partitioned executor.  ``build()`` must return a fresh
    ``(program, startup_program)`` pair per call (GSPMDExecutor attaches
    passes/sentinel in place, so candidates never share a program).

    The compile/AOT caches stay on: a re-tune of a seen (program, mesh,
    policy) shape books `pt_compile_cache_total{path="gspmd"}` hits and
    zero fresh compiles — the report records the per-candidate delta.
    Returns one record per candidate (None-measured entries mean the
    candidate failed to compile; the failure is recorded, not raised)."""
    import jax

    from paddle_tpu import fluid
    from .gspmd import GSPMDExecutor, hlo_collective_bytes

    if steps is None:
        from paddle_tpu.fluid import flags as _flags

        steps = int(_flags.flag("autotune_steps"))
    devices = devices or jax.devices()
    records = []
    for cand in candidates:
        rec = {"candidate": cand.to_json(), "label": cand.label()}
        pred = (predictions or {}).get(cand)
        before = _gspmd_cache_counts()
        try:
            program, startup = build()
            mesh = cand.build_mesh(devices=devices)
            policy = cand.build_policy(rules=rules)
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor()
                exe.run(startup)
                g = GSPMDExecutor(program, mesh, policy, scope=scope,
                                  quant_hook=cand.quant,
                                  loss_name=loss_name)
                fetch = [loss_name] if loss_name else None
                g.run(scope=scope, feed=feed, fetch_list=fetch)  # warm
                times = []
                for _ in range(int(steps)):
                    # candidate A/B quantiles, not a training step —
                    # deliberately outside the step_phases timer
                    t0 = time.perf_counter()  # observability: allow
                    g.run(scope=scope, feed=feed, fetch_list=fetch)
                    times.append(
                        time.perf_counter() - t0)  # observability: allow
                after = _gspmd_cache_counts()
                measured = {
                    "p50_s": round(float(np.percentile(times, 50)), 6),
                    "p95_s": round(float(np.percentile(times, 95)), 6),
                    "steps": int(steps),
                    "compile_cache": {k: after[k] - before[k]
                                      for k in after},
                }
                hlo = g.last_hlo
                if hlo:
                    measured["hlo_collective_bytes"] = \
                        hlo_collective_bytes(hlo)
                rec["measured"] = measured
        except Exception as e:  # candidate dies, sweep survives
            rec["measured"] = None
            rec["error"] = f"{type(e).__name__}: {e}"
            records.append(rec)
            continue
        _m_candidates().labels(stage="measured").inc()
        if pred is not None:
            rec["predicted"] = pred
            mb = rec["measured"].get("hlo_collective_bytes")
            pb = pred.get("collective_bytes")
            if mb and pb is not None:
                err = abs(pb - mb) / mb
                rec["measured"]["prediction_error"] = round(err, 4)
                _m_pred_err().labels(candidate=cand.label()).set(err)
        records.append(rec)
    return records


# ---------------------------------------------------------------------------
# the full loop + report
# ---------------------------------------------------------------------------


def autotune(build, feed, loss_name=None, n_devices=None, rules=None,
             cost_inputs=None, quant=None, top_k=None, steps=None,
             microbatch_counts=DEFAULT_MICROBATCHES, workload=None,
             report_path=None, devices=None):
    """Enumerate → prune → measure → report, end to end.

    ``build()`` returns a fresh ``(main_program, startup_program)``;
    ``cost_inputs`` (a `CostInputs`) defaults to a 1-device
    `GSPMDExecutor.cost_analysis` probe of the same program.  Returns
    the report dict (written to ``report_path`` when given)."""
    import jax

    from paddle_tpu.fluid import flags as _flags

    devices = devices or jax.devices()
    n = int(n_devices or len(devices))
    top_k = int(top_k or _flags.flag("autotune_topk"))
    program, _startup = build()
    feed_shapes = {k: tuple(np.shape(v)) for k, v in (feed or {}).items()}
    candidates = enumerate_candidates(
        program, n, rules=rules, quant=quant,
        microbatch_counts=microbatch_counts, feed_shapes=feed_shapes)
    if not candidates:
        raise ValueError(f"no legal candidates for {n} devices")
    if cost_inputs is None:
        cost_inputs = probe_cost_inputs(build, feed, loss_name=loss_name,
                                        devices=devices)
    predictions = {c: predict(program, c, cost_inputs, rules=rules)
                   for c in candidates}
    ranked = sorted(candidates,
                    key=lambda c: predictions[c]["total_s"])
    for i, c in enumerate(ranked):
        predictions[c]["rank"] = i
    shortlist = ranked[:top_k]
    measured = measure_candidates(
        build, shortlist, feed, loss_name=loss_name, steps=steps,
        rules=rules, devices=devices, predictions=predictions)
    ok = [r for r in measured if r.get("measured")]
    winner = (min(ok, key=lambda r: r["measured"]["p50_s"])
              if ok else None)
    report = {
        "schema": REPORT_SCHEMA,
        "version": REPORT_VERSION,
        "generated_unix": int(time.time()),  # observability: allow
        "n_devices": n,
        "workload": dict(workload or {}, feed_shapes={
            k: list(v) for k, v in feed_shapes.items()}),
        "cost_inputs": dataclasses.asdict(cost_inputs),
        "candidates": [
            dict(predicted=predictions[c], label=c.label(),
                 candidate=c.to_json())
            for c in ranked],
        "measured": measured,
        "winner": winner,
    }
    if winner is not None:
        winner_rank = predictions[
            Candidate.from_json(winner["candidate"])]["rank"]
        report["winner_rank"] = winner_rank
        report["analytic_top3_contains_winner"] = winner_rank < 3
        _m_winner_rank().set(winner_rank)
    if report_path:
        save_report(report, report_path)
    return report


def probe_cost_inputs(build, feed, loss_name=None, devices=None):
    """XLA cost-analysis numbers of the unpartitioned step (1-device
    mesh) — the same `pt_xla_flops`/`pt_xla_bytes_accessed` figures the
    roofline gauges publish, read straight from the probe compile."""
    from paddle_tpu import fluid
    from .gspmd import GSPMDExecutor

    import jax

    program, startup = build()
    devices = list(devices or jax.devices())
    mesh = pmesh.build_mesh({pmesh.DATA_AXIS: 1}, devices=devices[:1])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        g = GSPMDExecutor(program, mesh, gspecs.DataParallelPolicy(),
                          scope=scope, quant_hook=False,
                          loss_name=loss_name)
        fetch = [loss_name] if loss_name else None
        g.run(scope=scope, feed=feed, fetch_list=fetch)
        cost = g.cost_analysis(feed, fetch_list=fetch, scope=scope) or {}
    # cost_analysis nests: {"cost": {...xla keys...}, "memory": {...}}.
    inner = cost.get("cost", cost) or {}
    rows = 0
    for v in (feed or {}).values():
        shape = np.shape(v)
        if shape:
            rows = max(rows, int(shape[0]))
    return CostInputs(flops=float(inner.get("flops") or 0.0),
                      bytes_accessed=float(inner.get("bytes accessed")
                                           or 0.0),
                      batch_rows=rows)


def save_report(report, path):
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")


def load_report(path):
    with open(path) as f:
        report = json.load(f)
    schema = report.get("schema")
    if schema != REPORT_SCHEMA:
        raise ValueError(
            f"{path}: not an autotune report (schema {schema!r}, "
            f"expected {REPORT_SCHEMA!r})")
    return report


def resolve_pin(pin):
    """Runner pin plumbing: accept a `Candidate`, a report dict, a
    candidate-json dict, or a path to a saved report — return the
    `Candidate` to pin.  The ONE deserialization point both runners and
    `FLAGS_autotune_report` share."""
    if isinstance(pin, Candidate):
        return pin
    if isinstance(pin, str):
        pin = load_report(pin)
    if not isinstance(pin, dict):
        raise TypeError(
            f"policy_pin must be a Candidate, report dict or report "
            f"path, got {type(pin).__name__}")
    if pin.get("schema") == REPORT_SCHEMA:
        winner = pin.get("winner")
        if not winner:
            raise ValueError(
                "autotune report has no measured winner to pin")
        return Candidate.from_json(winner["candidate"])
    if "candidate" in pin:
        return Candidate.from_json(pin["candidate"])
    return Candidate.from_json(pin)


def stamp_gspmd_vs_transpiler(report, transpiler_p50_s, rel_tol=0.05):
    """Add the ``gspmd_vs_transpiler`` field (ISSUE 20 satellite): a
    win-or-tie check of the report's measured winner against the
    transpiler DP lane's p50 on the same workload.  The standing
    `FLAGS_gspmd_executor` default flip is gated on a committed report
    carrying ``win_or_tie: true`` from a run on the chip — instead of
    a hand-run A/B.  Tie = within ``rel_tol`` of the
    transpiler p50."""
    winner = report.get("winner") or {}
    gp = (winner.get("measured") or {}).get("p50_s")
    tp_ = float(transpiler_p50_s)
    entry = {"transpiler_p50_s": tp_, "gspmd_p50_s": gp,
             "rel_tol": rel_tol}
    if gp is None or tp_ <= 0:
        entry["win_or_tie"] = None
    else:
        entry["win_or_tie"] = bool(gp <= tp_ * (1.0 + rel_tol))
        entry["p50_ratio"] = round(gp / tp_, 4)
    report["gspmd_vs_transpiler"] = entry
    return entry


def policy_summary(mesh, policy):
    """``pp2.dp2.mp2/tp2d`` — mesh dims (canonical axis order, elided
    axes printed at 1) + the policy's class name.  The token
    `describe_policy` consumers stamp so sweeps across
    factorizations stay distinguishable after the fact."""
    shape = dict(getattr(mesh, "shape", {}) or {})
    dims = ".".join(f"{ax}{int(shape.get(ax, 1))}"
                    for ax in (pmesh.PIPE_AXIS, pmesh.DATA_AXIS,
                               pmesh.MODEL_AXIS))
    name = getattr(policy, "name", None) or type(policy).__name__
    inner = getattr(policy, "inner", None)
    if inner is not None:
        name += f"({getattr(inner, 'name', type(inner).__name__)})"
    return f"{dims}/{name}"
