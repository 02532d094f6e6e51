"""Executor: lowers a whole Program block to one jitted XLA computation.

Reference analog: python/paddle/fluid/executor.py:294 (Executor.run) driving
paddle/fluid/framework/executor.cc:172 — an op-by-op interpreter whose hot loop
(executor.cc:433-438) pays kernel lookup + InferShape + possible device
transfer per op.  TPU-native redesign: the *entire block* (forward + backward +
optimizer ops) is traced once into a single XLA computation, compiled once, and
cached keyed on (program version, feed signature).  Per-op dispatch disappears;
XLA does fusion, layout, scheduling.  The reference's in-place optimizer
updates (ParamOut aliases Param) become XLA buffer donation so parameter
memory is not doubled.

Scope semantics follow the reference (framework/scope.cc): a name → tensor
map; persistable vars (parameters, optimizer accumulators, BN stats) live in
the scope across runs as device-resident jax.Arrays — they are NOT fetched to
host between steps.
"""

from __future__ import annotations

import contextlib
import functools
import os
import logging
import threading
import warnings
import weakref

import numpy as np

from . import framework, registry
from .framework import Program, Variable

from paddle_tpu.observability import profiling as _profiling

logger = logging.getLogger(__name__)

__all__ = ["Executor", "Scope", "global_scope", "scope_guard", "as_numpy"]

# every lane imports this module before it builds, compiles or runs
# anything (and `framework` above has imported jax): from here on a
# collection is counted and a compile stage is a span beneath whatever
# span is open (also the caller's own jax, under no span)
_profiling.install_runtime_hooks()


# ---------------------------------------------------------------------------
# telemetry (docs/OBSERVABILITY.md): the executor owns the compile-side
# metrics — cache hit/miss, compile seconds, per-signature cost-model
# numbers — shared by every execution path (single-device, shard_map DP,
# GSPMD hybrid, on-device chain) through these accessors
# ---------------------------------------------------------------------------


def _m_cache():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_compile_cache_total",
        "Executable-cache lookups by execution path and result",
        labels=("path", "result"))


def _m_compile_seconds():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_compile_seconds_total",
        "Seconds spent building executables: phase=trace is the Python "
        "Program->jaxpr trace, phase=jit_first_run the signature's first "
        "execution (which includes the lazy XLA compile)",
        labels=("path", "phase"))


def _m_step_seconds():
    from paddle_tpu import observability as obs

    return obs.histogram(
        "pt_step_seconds",
        "Wall time of one executed step (first sample per signature "
        "includes the lazy XLA compile)", labels=("path",))


def _m_cost(kind):
    from paddle_tpu import observability as obs

    return obs.gauge(
        f"pt_xla_{kind}",
        f"XLA cost-model {kind.replace('_', ' ')} of the last analyzed "
        f"executable, per signature", labels=("signature",))


def _m_program_compile_seconds():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_program_compile_seconds_total",
        "Seconds from an executable-cache miss to the end of the "
        "signature's first run (plan, trace, XLA compile or cache load, "
        "first execution), by the program's jit name and where the "
        "executable came from: miss (compiled, no persistent cache "
        "consulted), persistent_hit / persistent_miss (jax's on-disk "
        "cache), aot_hit (deserialized)", labels=("program", "outcome"))


def _m_staged_arrays():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_exec_staged_arrays_total",
        "Arguments (scope reads and feeds) the single and chain lanes "
        "handed to their executables, added once a run: kind=any counts "
        "all of them, kind=put those that needed a jax.device_put, "
        "kind=kept the scope reads served from the executor's kept "
        "staging (the scope still holds the object staged last time), "
        "kind=host the feeds handed to the call as host arrays",
        labels=("lane", "kind"))


# what every lane's run_steps chain (chain_step_body) is jitted as
CHAIN_NAME = "train_chain"


def jit_name(program, ops):
    """The stable ``__name__`` a program's body is jitted under, so a
    device trace reads ``jit_<name>`` instead of ``jit_fn``: the name its
    builder gave the Program (the decode engine's ``decode_step`` /
    ``prefill_chunk``), else ``train_step`` where an optimizer's ops
    survive pruning, ``startup`` where no op reads anything, else
    ``program``."""
    name = getattr(program, "name", None)
    if name:
        return "".join(c if c.isalnum() or c == "_" else "_"
                       for c in str(name))
    if any(op.attrs.get("op_role") == "optimize" for op in ops):
        return "train_step"
    if ops and not any(op.input_arg_names for op in ops):
        return "startup"
    return "program"


# jax's persistent-cache events seen so far in this process: a compile
# span reads the pair before and after to name where its executable
# came from
_persistent_seen = {"hit": 0, "miss": 0}


@contextlib.contextmanager
def compile_span(lane, number=None):
    """Span + counter around a signature's first run, from the
    executable-cache miss to the end of the first execution.  Yields a
    dict: the lane sets ``["program"]`` (the plan's jit name) once the
    plan is built, and ``["outcome"] = "aot_hit"`` where it knows better
    than the persistent cache's events."""
    from paddle_tpu.observability import profiling as _profiling

    hit0, miss0 = _persistent_seen["hit"], _persistent_seen["miss"]
    booked = {"program": "program", "outcome": None}
    with _profiling.span("compile", lane, number=number) as sp:
        try:
            yield booked
        finally:
            if booked["outcome"] is None:
                booked["outcome"] = (
                    "persistent_miss" if _persistent_seen["miss"] > miss0
                    else "persistent_hit" if _persistent_seen["hit"] > hit0
                    else "miss")
            sp.note = f"{booked['program']}:{booked['outcome']}"
    _m_program_compile_seconds().labels(
        program=booked["program"],
        outcome=booked["outcome"]).inc(sp.seconds)


def _record_step(path, seconds, first_run):
    """Book one step into the shared step/compile metrics, the step-time
    attribution layer (per-signature stats, MFU, flight recorder —
    observability/profiling.py consumes the phase breakdown the lane's
    step_phases recorder deposited on this thread) and the JSONL event
    log (when enabled)."""
    _m_step_seconds().labels(path=path).observe(seconds)
    if first_run:
        _m_compile_seconds().labels(
            path=path, phase="jit_first_run").inc(seconds)
    from paddle_tpu.observability import profiling as _profiling

    _profiling.note_step(path, seconds, first_run=bool(first_run))
    from paddle_tpu.observability import events as _events

    if _events.enabled():
        _events.emit("step", path=path, seconds=round(seconds, 6),
                     first_run=bool(first_run))


def _feed_batch(feed):
    """Global batch size of a feed dict: the largest leading dim (shared
    by both parallel runners so the examples metric can't diverge)."""
    return max((int(np.shape(v)[0]) for v in feed.values()
                if np.shape(v)), default=0)


def _report_examples(path, batch, seconds):
    """Examples-ingested counter + last-step throughput gauge, shared by
    the parallel runners (one registration site — name/help can't drift)."""
    if not batch:
        return
    from paddle_tpu import observability as obs

    obs.counter("pt_examples_total",
                "Examples consumed by executed steps",
                labels=("path",)).labels(path=path).inc(batch)
    if seconds > 0:
        obs.gauge("pt_examples_per_sec",
                  "Throughput of the most recent step",
                  labels=("path",)).labels(path=path).set(batch / seconds)


# ---------------------------------------------------------------------------
# Scope
# ---------------------------------------------------------------------------


class _ScopeVar:
    """Parity shim for core.Variable: .get_tensor() → settable tensor view."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return _ScopeTensor(self._scope, self._name)


class _ScopeTensor:
    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def __array__(self, dtype=None):
        a = np.asarray(self._scope._vars[self._name])
        return a.astype(dtype) if dtype is not None else a

    def set(self, value, place=None):
        self._scope._vars[self._name] = np.asarray(value)

    def shape(self):
        return list(np.shape(self._scope._vars[self._name]))


class Scope:
    """Name→value map with reference kid-scope semantics: find_var walks
    the ancestor chain (reference scope.cc Scope::FindVar), creation and
    the executor's get/set stay local (Scope::Var).  Scopes without kids
    behave exactly as the flat map the executor always used."""

    def __init__(self, parent=None):
        self._vars = {}
        self._parent = parent
        self._kids = []

    def new_scope(self):
        kid = Scope(parent=self)
        self._kids.append(kid)
        return kid

    def parent_scope(self):
        return self._parent

    def find_var(self, name):
        scope = self
        while scope is not None:
            if name in scope._vars:
                return _ScopeVar(scope, name)
            scope = scope._parent
        return None

    def var(self, name):
        self._vars.setdefault(name, None)
        return _ScopeVar(self, name)

    def get(self, name):
        # deliberately LOCAL-only (find_var walks ancestors): the executor
        # reads donated params with get(), and a parent-scope hit would let
        # a kid-scope run donate (invalidate) a buffer the parent still
        # references — the post-run write lands in the kid, the parent
        # keeps a deleted jax.Array.  Local-only get keeps the old clean
        # "must exist in scope" error for that case.
        return self._vars.get(name)

    def set(self, name, value):
        self._vars[name] = value

    def drop_kids(self):
        self._kids.clear()

    def keys(self):
        return self._vars.keys()


_default_scope = Scope()
_scope_tls = threading.local()


def global_scope() -> Scope:
    """The ambient scope: thread-local override (scope_guard) falling back
    to one process-wide default.  Thread-local matters: a pserver thread's
    listen loop guards its own scope and must not hijack the trainer
    thread's (the reference's C++ scopes are per-executor objects, so it
    never had this hazard)."""
    return getattr(_scope_tls, "scope", None) or _default_scope


@contextlib.contextmanager
def scope_guard(scope):
    old = getattr(_scope_tls, "scope", None)
    _scope_tls.scope = scope
    try:
        yield
    finally:
        _scope_tls.scope = old


def as_numpy(x):
    return np.asarray(x)


# ---------------------------------------------------------------------------
# Block lowering
# ---------------------------------------------------------------------------


def _gather_inputs(op, info, env, optional_ok=True):
    """Collect lowering args for `op` from env, honoring variadic/optional."""
    vals = []
    for slot in info.input_slots:
        cslot = slot.rstrip("*")
        names = op.inputs.get(cslot, [])
        if info.is_variadic(slot):
            vals.append([env[n] for n in names])
        elif not names:
            vals.append(None)
        else:
            vals.append(env.get(names[0]))
    return vals


# numerically sensitive ops that stay fp32 islands under the bf16 policy:
# inputs are upcast and the lowering runs in fp32; outputs stay fp32, and
# any bf16 consumer downcasts its own inputs, so the chain stays narrow
# (losses — the standard mixed-precision blocklist, reference
# fp16_lists.py black_list).  softmax/log_softmax/softmax_with_cross_
# entropy/layer_norm/batch_norm are NOT islands: their lowerings upcast
# internally (fp32 statistics/exp-sum on the VPU) but return the input
# dtype, so the big saved-for-backward tensors — attention scores
# [B, heads, S, S], LN/BN outputs, the MLM softmax [positions, vocab] —
# stay bf16 and their HBM round-trip halves.
_BF16_FP32_OPS = frozenset({
    "cross_entropy", "cross_entropy2", "mean", "reduce_mean",
    "sigmoid_cross_entropy_with_logits",
})

# fp32-internal ops whose PARAM/STAT inputs must not be downcast: the
# activations ride bf16, but scale/bias and (for BN) the donated running
# mean/variance buffers are fp32 masters — a bf16 round-trip would both
# round the masters and flip the written-back buffer dtype.
# {op type: top-level input indices the policy leaves untouched}
_BF16_KEEP_FP32_INPUTS = {
    "layer_norm": (1, 2),             # Scale, Bias
    "layer_norm_grad": (1, 2),
    "batch_norm": (1, 2, 3, 4),       # Scale, Bias, Mean, Variance
    "batch_norm_grad": (1, 2, 3, 4),
}


def _map_floats(vals, fn):
    import jax.numpy as jnp

    from .struct_values import is_struct_value

    def one(v):
        if v is None:
            return None
        if is_struct_value(v):
            # tensor-array/rank-table values pass through opaquely; their
            # buffer dtype was set by the (policy-applied) producing op
            return v
        if isinstance(v, (list, tuple)):
            return [one(x) for x in v]
        try:
            dt = jnp.asarray(v).dtype
        except TypeError:
            return v
        return fn(v, dt)
    return [one(v) for v in vals]


def _apply_bf16_policy(op, vals):
    """The bf16 dtype policy, applied at the lowering (NOT a program
    rewrite): forward/backward compute runs in bfloat16 — halved HBM
    traffic for weights/activations, native MXU dtype — while optimizer
    ops and the _BF16_FP32_OPS islands see fp32 (params in env are the
    fp32 master copies; grads are upcast at the optimizer edge, the one
    place precision pays).  fp32 islands need no output downcast: any
    bf16 consumer casts its own inputs down, so the chain stays narrow
    and the loss fetch stays fp32."""
    import jax.numpy as jnp

    def _all_float_inputs_scalar():
        # a loss tail (add of two scalar means) or an lr-schedule chain:
        # scalars gain nothing from bf16, and keeping them fp32 preserves
        # the "loss fetch is fp32" contract past non-island tail ops
        found = False
        stack = list(vals)
        while stack:
            v = stack.pop()
            if v is None:
                continue
            if isinstance(v, (list, tuple)):
                stack.extend(v)
                continue
            try:
                a = jnp.asarray(v)
            except TypeError:
                continue
            if jnp.issubdtype(a.dtype, jnp.floating):
                found = True
                if a.size > 1:
                    return False
        return found

    role = op.attrs.get("op_role")
    if (role == "optimize" or op.type in _BF16_FP32_OPS
            or _all_float_inputs_scalar()):
        return _map_floats(vals, lambda v, dt: (
            jnp.asarray(v, jnp.float32) if dt == jnp.bfloat16 else v))
    out = _map_floats(vals, lambda v, dt: (
        jnp.asarray(v, jnp.bfloat16) if dt == jnp.float32 else v))
    for i in _BF16_KEEP_FP32_INPUTS.get(op.type, ()):
        if i < len(out):
            out[i] = vals[i]
    return out


_OP_TRACE_LOG = os.environ.get("PT_TRACE_OP_LOG")
_traced_op_types: set = set()
if _OP_TRACE_LOG:
    import atexit

    @atexit.register
    def _flush_traced_op_types():
        # ONE os.write to an O_APPEND fd: concurrent exits (pytest-xdist
        # workers) can't interleave mid-line; the consumer de-duplicates
        try:
            payload = "".join(t + "\n" for t in sorted(_traced_op_types))
            fd = os.open(_OP_TRACE_LOG,
                         os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
            try:
                os.write(fd, payload.encode())
            finally:
                os.close(fd)
        except OSError:
            pass


def trace_block(block, env, ctx, ops=None):
    """Trace every op of `block` into JAX ops, mutating `env` (name→array).

    This is the TPU replacement for the reference executor's hot loop
    (executor.cc:433-438): it runs once per compilation, not once per step.

    PT_TRACE_OP_LOG=<file>: record every op type that actually LOWERS
    (appended at exit) — the execution-coverage measurement behind
    tools/op_exec_coverage.py; a registered-but-never-lowered op can hide
    a trace-time landmine (where_index, r5)."""
    ctx.block = block
    ctx.env = env
    policy = getattr(ctx, "dtype_policy", None)
    for op_index, op in enumerate(block.ops if ops is None else ops):
        if op.type in ("feed", "fetch"):
            continue
        info = registry.get_op(op.type)
        vals = _gather_inputs(op, info, env)
        if policy == "bf16":
            vals = _apply_bf16_policy(op, vals)
        ctx.op_index = (block.idx << 16) | op_index
        ctx.cur_op = op  # slot-name access for imported-signature ops
        out = info.lower(ctx, *vals, attrs=op.attrs)
        if _OP_TRACE_LOG:
            # AFTER lower() returns: a lowering that crashes at trace
            # time must not count as covered (that's the landmine class
            # the sweep exists to expose)
            _traced_op_types.add(op.type)
        outs = out if isinstance(out, tuple) else (out,)
        for slot, val in zip(info.output_slots, outs):
            cslot = slot.rstrip("*")
            names = op.outputs.get(cslot, [])
            if info.is_variadic(slot):
                for n, v in zip(names, val or []):
                    env[n] = v
            elif names and val is not None:
                env[names[0]] = val
        # GSPMD activation annotations (parallel/gspmd/specs.py): a
        # sharding policy may pin selected op outputs with
        # with_sharding_constraint AT THE PRODUCING SITE, so XLA's
        # propagation is anchored in both directions — the constraint
        # callables are supplied via ctx by the partitioned executor and
        # absent on every other path.
        cons = getattr(ctx, "sharding_constraints", None)
        if cons:
            for n in op.output_arg_names:
                if n in cons and n in env:
                    env[n] = cons[n](env[n])
    return env


def _prune_ops(block, fetch_names):
    """Dead-op elimination before compilation: keep ops that contribute to a
    fetch target or write a persistable var (optimizer updates, BN stats run
    regardless of fetch_list, matching reference executor semantics).  This
    lets a `clone(for_test=True)` program run without feeding `label` when
    only the prediction is fetched — a whole-block-compilation advantage the
    reference's op-by-op interpreter can't offer."""
    needed = set(fetch_names)
    kept = []
    for op in reversed(block.ops):
        if op.type in ("feed", "fetch"):
            continue
        keep = op.type == "print"
        for n in op.output_arg_names:
            if n in needed:
                keep = True
            else:
                v = block._find_var_recursive(n)
                if v is not None and v.persistable:
                    keep = True
        if not op.output_arg_names:  # side-effect/bootstrap ops (c_comm_init)
            keep = True
        if keep:
            kept.append(op)
            needed.update(op.input_arg_names)
    return list(reversed(kept))


def _analyze_block(ops, block, feed_names):
    """Classify var usage: what must come from scope, what goes back."""
    produced = set(feed_names)
    scope_reads, writes = [], []
    seen_reads, seen_writes = set(), set()
    for op in ops:
        if op.type in ("feed", "fetch"):
            continue
        # a NON-PERSISTABLE optional in-out input (write_to_array's Array
        # on the first write) is a run-local value this very op creates
        # when absent — not a scope dependency.  Persistable in-outs
        # (fake_quantize_range_abs_max's window state) and mandatory ones
        # (adam's Param) stay scope reads.  Keyed on the program's static
        # persistable flag, NOT scope contents — the compiled plan is
        # cached across scopes.
        info = registry.get_op(op.type)
        out_names = set(op.output_arg_names)
        opt_inout = set()
        for slot in info.optional:
            for n in op.inputs.get(slot, []):
                if n not in out_names:
                    continue
                v = block._find_var_recursive(n)
                if v is None or not v.persistable:
                    opt_inout.add(n)
        for n in op.input_arg_names:
            if (n not in produced and n not in seen_reads
                    and n not in opt_inout):
                seen_reads.add(n)
                scope_reads.append(n)
        for n in op.output_arg_names:
            produced.add(n)
            v = block._find_var_recursive(n)
            persistable = v.persistable if v is not None else False
            if (persistable or n in seen_reads) and n not in seen_writes:
                seen_writes.add(n)
                writes.append(n)
    return scope_reads, writes


class BlockPlan:
    """Shared compilation plan for a block: pruned op list, scope dataflow
    classification, fetch validation, and the traceable body function.  Used
    by the single-device executor, the shard_map data-parallel runner, and the
    GSPMD hybrid runner — one implementation of prune/analyze/write-back."""

    def __init__(self, program, block, feed_names, fetch_names, scope,
                 place=None):
        # every compile path (single-device, shard_map DP, GSPMD hybrid,
        # LocalSGD) builds a BlockPlan first — apply the persistent XLA
        # cache config here so all of them benefit
        _apply_compile_cache()
        # the Place the trace targets (None for mesh runners) — lowerings
        # that need host callbacks (py_func) check it to fail loudly on TPU
        self.place = place
        self.program = program
        self.block = block
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        all_ops = _prune_ops(block, fetch_names)
        # host ops (RPC send/recv, listen_and_serv, ...) run outside the
        # jitted computation, in program order.  "pre"-stage host ops run
        # BEFORE the device step and produce jit inputs (e.g. distributed
        # embedding lookup fetching rows for the fed ids); "post"-stage run
        # after it and consume jit outputs (e.g. grad sends).
        host = [op for op in all_ops
                if registry.get_op(op.type).host_run is not None]
        self.host_pre_ops = [op for op in host
                             if registry.get_op(op.type).host_stage == "pre"]
        self.host_ops = [op for op in host
                         if registry.get_op(op.type).host_stage != "pre"]
        self.ops = [op for op in all_ops
                    if registry.get_op(op.type).host_run is None]
        scope_reads, writes = _analyze_block(self.ops, block, self.feed_names)
        # values the host ops consume must be materialized to scope even if
        # no fetch asks for them (e.g. grads feeding a `send` op)
        jit_produced = set()
        for op in self.ops:
            jit_produced.update(op.output_arg_names)
        for hop in self.host_ops:
            for n in hop.input_arg_names:
                if n in jit_produced and n not in writes:
                    writes.append(n)
        pre_out = set()
        for hop in self.host_pre_ops:
            pre_out.update(hop.output_arg_names)
        self._host_pre_out = pre_out
        missing = [n for n in scope_reads
                   if n not in pre_out and scope.get(n) is None]
        if missing:
            raise RuntimeError(
                f"Variables {missing} must exist in scope before running this "
                f"program (did you run the startup program?)"
            )
        produced = set(self.feed_names) | set(scope_reads)
        for op in self.ops:
            produced.update(op.output_arg_names)
        host_out = set()
        for hop in self.host_ops:
            host_out.update(hop.output_arg_names)
        # a fetch written by a host op must be read from scope AFTER the host
        # ops ran (env never sees it; and even when it aliases a scope var,
        # the pre-host value would be stale)
        self.host_fetch_names = [n for n in self.fetch_names if n in host_out]
        self.jit_fetch_names = [n for n in self.fetch_names
                                if n not in host_out]
        # a fetch some device op computed: reading it on the host waits
        # for the program (one that is a plain scope read or a feed may
        # be handed back without it)
        self.fetch_proves_done = any(n in jit_produced
                                     for n in self.jit_fetch_names)
        bad_fetch = [n for n in self.fetch_names
                     if n not in produced and n not in host_out]
        # a fetch no op produces but that LIVES in the scope is a plain
        # scope read (reference: fetch ops read any scope var — e.g. the
        # Evaluator pattern fetches accumulated state through an op-less
        # eval program)
        rescued = [n for n in bad_fetch if scope.get(n) is not None]
        if rescued:
            scope_reads.extend(rescued)
            produced.update(rescued)
            bad_fetch = [n for n in bad_fetch if n not in rescued]
        if bad_fetch:
            raise ValueError(
                f"fetch target(s) {bad_fetch} are not produced by this program "
                f"(not an op output, feed, or scope variable)"
            )
        self.name = jit_name(program, self.ops)
        wset = set(writes)
        self.donated_names = [n for n in scope_reads if n in wset]
        self.readonly_names = [n for n in scope_reads if n not in wset]
        self.write_names = list(writes)

    def trace_env(self, donated, readonly, feeds, step, mesh_axes=()):
        """Trace the block over the given buffers and return the full var
        env — the ONE place the lowering context is assembled, shared by
        make_body and introspection (tests/test_perf_budget.py captures
        residual dtypes through it so the gate can't trace a different
        program than the executor runs)."""
        env = {}
        env.update(donated)
        env.update(readonly)
        env.update(feeds)
        ctx = registry.LowerContext(
            step=step, is_test=getattr(self.program, "_is_test", False),
            block=self.block, mesh_axes=mesh_axes)
        ctx.program = self.program
        ctx.dtype_policy = getattr(self.program, "_dtype_policy", None)
        ctx.place = self.place
        trace_block(self.block, env, ctx, ops=self.ops)
        return env

    def make_body(self, mesh_axes=()):
        """fn(donated, readonly, feeds, step) -> (fetches, out_writes).
        Fetches cover jit_fetch_names only; host-op-produced fetches are
        filled in by assemble_fetches after run_host_ops.  The function
        is named ``self.name`` (`jit_name`)."""
        fetch_names, write_names = self.jit_fetch_names, self.write_names
        name = self.name

        def fn(donated, readonly, feeds, step):
            import jax

            # one scope around the whole step, not per op: every HLO
            # op's metadata then starts with the program's name
            with jax.named_scope(name):
                env = self.trace_env(donated, readonly, feeds, step,
                                     mesh_axes=mesh_axes)
            fetches = [env[n] for n in fetch_names]
            out_writes = {n: env[n] for n in write_names if n in env}
            return fetches, out_writes

        # jax names the executable jit_<__name__>: what a device trace's
        # "XLA Modules" line and the benchmark's idle gaps print
        fn.__name__ = fn.__qualname__ = name
        return fn

    def run_host_ops(self, scope, place=None, feeds=None):
        """Run the block's host ops (RPC/IO) in program order, after the
        device step.  They read/write the scope directly; feed values are
        visible to reads (a sparse grad send needs the fed ids)."""
        view = _FeedScopeView(scope, feeds) if feeds else scope
        for op in self.host_ops:
            registry.get_op(op.type).host_run(view, op, place)

    def run_host_pre_ops(self, scope, feeds, place=None):
        """Run "pre"-stage host ops before the device step.  They see feed
        values transparently (reads check feeds first, writes go to scope) —
        a distributed lookup consumes fed ids that never enter the scope."""
        if not self.host_pre_ops:
            return
        view = _FeedScopeView(scope, feeds)
        for op in self.host_pre_ops:
            registry.get_op(op.type).host_run(view, op, place)

    def assemble_fetches(self, jit_fetches, scope):
        """Merge jit fetches with host-op-produced ones (read from scope,
        post run_host_ops) back into fetch_list order."""
        if not self.host_fetch_names:
            return jit_fetches
        by_name = dict(zip(self.jit_fetch_names, jit_fetches))
        return [by_name[n] if n in by_name else scope.get(n)
                for n in self.fetch_names]


_cache_dir_last = object()  # sentinel: not yet applied
_cache_listener_on = False


def _book_persistent_cache(event, **_kw):
    """jax.monitoring listener: jax's own persistent-cache hit/miss
    events land on pt_compile_cache_total{path="xla_persistent"} — how a
    second process reports that its compiles came off disk."""
    result = {"/jax/compilation_cache/cache_hits": "hit",
              "/jax/compilation_cache/cache_misses": "miss"}.get(event)
    if result:
        _persistent_seen[result] += 1
        _m_cache().labels(path="xla_persistent", result=result).inc()


def _apply_compile_cache():
    """Point jax at a persistent on-disk compilation cache so re-runs of
    the same program skip the first XLA compile (SURVEY §7 hard part 6).
    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside and jax reads it itself: nothing is set here.  Otherwise
    FLAGS_compile_cache_dir (default ``<checkout>/.jax_cache``; "" =
    off) is applied lazily before each compile and re-applied when the
    flag changes.  The path is part of jax's cache key, so it is a fixed
    one — no host fingerprint, pid or time in it."""
    global _cache_dir_last, _cache_listener_on
    import jax

    if not _cache_listener_on:
        jax.monitoring.register_event_listener(_book_persistent_cache)
        _cache_listener_on = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    from . import flags as _flags

    cache_dir = _flags.flag("compile_cache_dir")
    if cache_dir == _cache_dir_last:
        return
    _cache_dir_last = cache_dir
    if not cache_dir:
        jax.config.update("jax_compilation_cache_dir", None)
        return
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything that took meaningful compile time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


class _FeedScopeView:
    """Scope facade for pre-stage host ops: get() resolves feed values
    first, set() always lands in the real scope."""

    def __init__(self, scope, feeds):
        self._scope = scope
        self._feeds = feeds or {}

    def get(self, name):
        if name in self._feeds:
            return self._feeds[name]
        return self._scope.get(name)

    def set(self, name, value):
        self._scope.set(name, value)


class _Kept(dict):
    """An Executor's kept staging, shared by its executables (they run on
    one place): ``{name: (weakref to the scope's object, what went to the
    call in its stead or None where it went itself)}``.  A dict that can
    be referenced weakly, for `_forget_view`."""

    __slots__ = ("__weakref__",)


def _forget_view(owner, name, ref):
    """Weakref callback: the scope's array died, its view goes with it."""
    kept = owner()
    if kept is not None and kept.get(name, (None,))[0] is ref:
        del kept[name]


def _stage_scope_reads(scope, names, device, kept, views=False):
    """`names` from `scope` as arguments committed to `device`; how many of
    them needed a ``jax.device_put``, and how many `kept` served.  The
    scope is left as it is: other lanes read it too, and their jits take an
    uncommitted array but refuse one committed elsewhere.

    `kept` says what each name's scope object was staged as last time, and
    holds while ``scope.get(n)`` IS that object: identity, never the name
    alone, so ``scope.set``, ``get_tensor().set``, another lane's write and
    another scope under the same names are all seen.  An executable's run
    notes its write-back there (`_write_back`), so a donated name takes the
    committed output this program, or another of the same executor, left.

    Otherwise: a committed ``jax.Array`` whose only device is `device` goes
    by identity.  An uncommitted one that lives there (what a jitted
    initializer or ``jnp.zeros`` returns) is put once: the put makes a
    committed view of the same buffer, which `kept` holds, with `views`,
    for as long as the scope's array lives (not for a donated name, whose
    view dies in the call).  It cannot go as it is: committedness is part
    of a jitted call's signature, so a step would compile again once its
    own committed outputs come back as inputs.  Everything else is put on
    every run and never kept: an array resident elsewhere (a copy nobody
    should keep), and host values (numpy, scalars, what
    ``get_tensor().set`` stores), whose in-place change must be seen.

    Fails with the variable's NAME on a miss — a cached plan may classify
    a var as a scope read against a scope that held it; None reaching
    jax.device_put would surface as an opaque pytree/TypeError instead."""
    import jax

    target = {device}
    # Scope.get is its own dict's get (local only); a bound one saves a
    # Python call a name
    get, hit_of = scope._vars.get, kept.get
    staged, n_put, n_kept = {}, 0, 0
    for n in names:
        v = get(n)
        if v is None:
            raise ValueError(
                f"variable {n!r} is read by this program but absent "
                "from the current scope")
        hit = hit_of(n)
        if hit is not None and hit[0]() is v:
            staged[n] = v if hit[1] is None else hit[1]
            n_kept += 1
            continue
        resident = (isinstance(v, jax.Array)
                    and v.sharding.device_set == target)
        if resident and v.committed:
            # no callback: a dead reference serves nothing, and the
            # name's next staging replaces it
            kept[n] = (weakref.ref(v), None)
            staged[n] = v
            continue
        staged[n] = jax.device_put(v, device)
        n_put += 1
        if resident and views:
            # weak both ways: the view neither outlives the array it views
            # nor ties the table into a cycle
            kept[n] = (weakref.ref(v, functools.partial(
                _forget_view, weakref.ref(kept), n)), staged[n])
    return staged, n_put, n_kept


def _stage_feeds(feeds, device, pinned):
    """`feeds` as they go to the call, and how many were put / went as host
    arrays.  Where the call is `pinned` (it has an argument committed to
    `device`, which fixes where it runs and where its uncommitted
    arguments go), a host feed rides the call: the transfer is the call's
    own, and a ``jax.device_put`` beforehand buys nothing.  Where nothing
    pins, the feeds are put and pin it themselves (a place that is not the
    default device).  A ``jax.Array`` already committed there goes by
    identity (the dataset prefetcher's); any other is put, so that a
    program keeps one signature whatever array it is fed."""
    import jax

    target = {device}
    vals, n_put, n_host = {}, 0, 0
    for k, v in feeds.items():
        if isinstance(v, jax.Array):
            if not (v.committed and v.sharding.device_set == target):
                v = jax.device_put(v, device)
                n_put += 1
        elif pinned:
            n_host += 1
        else:
            v = jax.device_put(v, device)
            n_put += 1
        vals[k] = v
    return vals, n_put, n_host


def _stage_args(lane, exe, scope, feeds):
    """The (donated, readonly, feeds) arguments of one run of `exe`'s
    jitted body, booked once a run into
    ``pt_exec_staged_arrays_total{lane}``.  Every scope read arrives
    committed to the place's device (so each program keeps ONE signature)
    and so pins the call; see `_stage_scope_reads` and `_stage_feeds`."""
    device = exe.place.jax_device()
    donated, put_d, kept_d = _stage_scope_reads(
        scope, exe.donated_names, device, exe._kept)
    readonly, put_r, kept_r = _stage_scope_reads(
        scope, exe.readonly_names, device, exe._kept, views=True)
    feed_vals, put_f, n_host = _stage_feeds(
        feeds, device, pinned=bool(donated or readonly))
    staged = _m_staged_arrays()
    # every kind on every run, a 0 too: a reader of put / any finds both
    for kind, n in (("put", put_d + put_r + put_f),
                    ("kept", kept_d + kept_r), ("host", n_host),
                    ("any", len(donated) + len(readonly) + len(feed_vals))):
        staged.labels(lane=lane, kind=kind).inc(n)
    return donated, readonly, feed_vals


def _write_back(kept, scope, out_writes):
    """A run's scope writes, noted in `kept` where they came back committed
    (a jitted step's, whose committed arguments placed it on the place's
    device): the next run that reads a name, this program's or another's,
    takes the array as it is.  A start-up program's outputs and an
    AOT-compiled step's come back uncommitted, and are staged as any
    uncommitted array."""
    for n, v in out_writes.items():
        scope.set(n, v)
        if v.committed:
            kept[n] = (weakref.ref(v), None)


class _JitExecutable:
    """Shared introspection surface of a cached jitted executable
    (`_CompiledBlock` per-step, `_CompiledChain` n-steps-per-call):
    abstract arg specs for AOT lowering, XLA cost/memory analysis, and
    the FLAGS_check_nan_inf scan.  Subclasses provide `plan`, `label`,
    `_jitted`, `donated_names`, `readonly_names`, `jit_name` (the module
    name a device trace shows) and, once run, `ordinal` (the in-flight
    ledger's number of the program the last run enqueued:
    observability/profiling.py)."""

    def _jit_args(self, scope, feeds, step, shardings=(None, None)):
        """The (donated, readonly, feeds, step) pytrees run() passes to the
        jitted body, as abstract ShapeDtypeStructs — enough for AOT
        lowering without touching device memory.  ``shardings`` =
        (state, feed) places the scope-resident arrays and the feeds
        explicitly (see :meth:`lower`); None leaves placement to jit."""
        import jax

        state_s, feed_s = shardings

        def spec(n, v, sharding):
            if v is None:
                # same guard as run(): name the variable instead of letting
                # np.asarray(None) produce an opaque object-dtype error
                raise ValueError(
                    f"variable {n!r} is read by this program but absent "
                    "from the current scope")
            a = np.asarray(v) if not hasattr(v, "dtype") else v
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                        sharding=sharding)

        donated = {n: spec(n, scope.get(n), state_s)
                   for n in self.donated_names}
        readonly = {n: spec(n, scope.get(n), state_s)
                    for n in self.readonly_names}
        feed_vals = {k: spec(k, v, feed_s) for k, v in feeds.items()}
        return donated, readonly, feed_vals, jax.ShapeDtypeStruct(
            (), np.uint32, sharding=state_s)

    def lower(self, scope, feeds, shardings=(None, None)):
        """AOT-lower this step (``.compile()`` the result) with every
        argument placed by ``shardings`` = (state, feed).  Shardings
        over ``jax.experimental.topologies`` devices, inside
        ``platform_utils.lowering_for("tpu")``, compile the step for a
        TPU from a host that has none (tests/test_mosaic_aot.py)."""
        return self._jitted.lower(
            *self._jit_args(scope, feeds, 0, shardings))

    def cost_analysis(self, scope, feeds, step=0):
        """XLA's per-executable cost model for this step: flops, bytes
        accessed (total and per memory space), transcendentals.  AOT
        (`jit.lower(...).compile()`), so the shapes must match a prior or
        future run; the executable cache makes this free after a warmup.
        TPU analog of the reference's per-op profiler tables
        (platform/profiler.cc) at whole-program granularity."""
        lowered = self.lower(scope, feeds)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # donation unsupported on CPU
            compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # older jax returns [dict]
            cost = cost[0] if cost else {}
        mem = {}
        try:
            ma = compiled.memory_analysis()
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    mem[k] = int(v)
        except Exception:  # backend without memory analysis
            pass
        # publish the cost-model headline numbers as per-signature gauges
        # (docs/OBSERVABILITY.md)
        sig = getattr(self, "label", f"exe@{id(self):x}")
        for kind, key in (("flops", "flops"),
                          ("bytes_accessed", "bytes accessed"),
                          ("transcendentals", "transcendentals")):
            v = cost.get(key) if hasattr(cost, "get") else None
            if v is not None:
                _m_cost(kind).labels(signature=sig).set(float(v))
        # feed the attribution layer: cost numbers + measured device
        # time become pt_mfu / pt_roofline_bound for this signature
        from paddle_tpu.observability import profiling as _profiling

        _profiling.note_cost(sig, cost if hasattr(cost, "get") else {})
        return {"cost": dict(cost), "memory": mem}

    def _check_nan_inf(self, out_writes, fetches):
        _check_nan_inf(self.plan, self.label, out_writes, fetches)


class _CompiledBlock(_JitExecutable):
    """One (program-version, feed-signature) → jitted XLA executable."""

    def __init__(self, program, block, feed_names, fetch_names, place, scope,
                 kept):
        import jax

        plan = BlockPlan(program, block, feed_names, fetch_names, scope,
                         place=place)
        self.plan = plan
        self.block = block
        self.feed_names = plan.feed_names
        self.fetch_names = plan.fetch_names
        self.ops = plan.ops
        self.donated_names = plan.donated_names
        self.readonly_names = plan.readonly_names
        self.write_names = plan.write_names
        from paddle_tpu.health import wrap_body as _health_gate

        self._jitted = jax.jit(_health_gate(program, plan.make_body()),
                               donate_argnums=(0,))
        self.place = place
        self.label = f"program@{id(program):x}/v{program._version}"
        self.jit_name = "jit_" + plan.name
        self._prof_state = {"ran": False}
        self._kept = kept  # the executor's, see _stage_scope_reads
        # AOT-loaded/compiled executable (fluid/aot_cache.py) — when
        # set, run() dispatches it instead of the lazy jit
        self._aot = None

    def setup_aot(self, scope, feeds):
        """FLAGS_aot_cache_dir path: try to DESERIALIZE this signature's
        executable ("aot_hit" — no trace, no compile); on a cache miss,
        AOT-compile now and serialize it for the next restart
        ("aot_saved").  Returns the outcome ("aot_hit" / "aot_saved" /
        None = disabled, or compiled but not serializable)."""
        from . import aot_cache

        if not aot_cache.enabled():
            return None
        import time as _time

        args = self._jit_args(scope, feeds, 0)
        key = aot_cache.executable_key(self.plan.program, args,
                                       self.fetch_names)
        t0 = _time.perf_counter()  # observability: allow
        loaded = aot_cache.load(key)
        if loaded is not None:
            self._aot = loaded
            _m_compile_seconds().labels(path="single", phase="aot_load") \
                .inc(_time.perf_counter() - t0)  # observability: allow
            return "aot_hit"
        t0 = _time.perf_counter()  # observability: allow
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # donation unsupported on CPU
            compiled = self._jitted.lower(*args).compile()
        _m_compile_seconds().labels(
            path="single", phase="aot_compile").inc(
            _time.perf_counter() - t0)  # observability: allow
        if aot_cache.save(key, compiled):
            self._aot = compiled
            return "aot_saved"
        self._aot = compiled  # still usable in-process
        return None

    def run(self, scope, feeds, step):
        import jax

        from paddle_tpu.observability import profiling as _profiling

        from . import profiler as _prof

        # step_phases OUTERMOST, timed_run covering exactly its historic
        # region (staging..scope-writes): the chrome-trace "run" span
        # must not absorb the host RPC/IO ops that follow — that
        # misattribution is what this layer exists to remove.  Phase
        # brackets of the same name accumulate, so fetch_sync spans both
        # the scope write-back (inside timed_run) and the host tail.
        with _profiling.step_phases("single", self.label,
                                    number=step) as ph:
            with _prof.timed_run(self.label, self._prof_state) as timer:
                with ph.phase("feed_prep"):
                    # pre-stage host ops (distributed lookup/prefetch)
                    # populate the scope vars the device step is about
                    # to read
                    self.plan.run_host_pre_ops(scope, feeds, self.place)
                    donated, readonly, feed_vals = _stage_args(
                        "single", self, scope, feeds)
                with ph.phase("dispatch") as dispatch:
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore")  # donation unsupported on CPU backend
                        fetches, out_writes = (self._aot or self._jitted)(
                            donated, readonly, feed_vals, np.uint32(step)
                        )
                    # the in-flight ledger: which program this span enqueued
                    self.ordinal = k = _profiling.enqueued(self.jit_name)
                    dispatch.note = f"{self.jit_name}#{k}"
                with ph.phase("device_wait") as waited:
                    ph.wait((fetches, out_writes))
                    if ph.blocked:
                        waited.note = _profiling.done(k)
                with ph.phase("fetch_sync"):
                    _write_back(self._kept, scope, out_writes)
                    # block on scope writes too — a run with an empty
                    # fetch_list (or a startup run) would otherwise
                    # record async-dispatch time only
                    timer.done(fetches, out_writes)
            with ph.phase("fetch_sync") as tail:
                from . import flags as _flags

                if _flags.flag("benchmark"):
                    # force completion each step (reference operator.cc:949
                    # forces a dev_ctx->Wait() per op under FLAGS_benchmark)
                    jax.block_until_ready((fetches, out_writes))
                    tail.note = _profiling.done(k)
                elif timer.enabled:  # timed_run blocked as it closed
                    tail.note = _profiling.done(k)
                if _flags.flag("check_nan_inf"):
                    self._check_nan_inf(out_writes, fetches)
                # RPC/IO ops run host-side after the device step, in
                # program order
                self.plan.run_host_ops(scope, self.place, feeds=feeds)
                out = self.plan.assemble_fetches(fetches, scope)
                # release the step's argument arrays inside the span (the
                # donated ones are dead buffers by now): several hundred
                # array objects cost ms to free, which would otherwise
                # fall between this recorder and the caller's clock
                del donated, readonly, feed_vals
        return out

def _check_nan_inf(plan, label, out_writes, fetches):
    """FLAGS_check_nan_inf (reference operator.cc:953-984): scan every
    written float var and raise naming the first non-finite one.  Thin
    wrapper over the health sentinel's audited scan
    (paddle_tpu/health/detect.py) — the in-graph sentinel
    (FLAGS_health_sentinel) supersedes this host-side sweep for the
    runner lanes; this stays the op-by-op debugging contract."""
    from paddle_tpu.health import detect

    named = list(out_writes.items()) + list(
        zip(plan.jit_fetch_names, fetches))
    detect.host_scan(named, label)


class HostOpsUnsupported(ValueError):
    """Raised when an on-device step chain meets a program whose host ops
    (RPC/IO) need the host between steps.  A distinct type so fallback
    logic (train_from_dataset chaining) can classify
    it exactly instead of matching error text."""


def chain_step_body(body, n_steps, stacked_feed):
    """THE one spelling of the on-device step chain, shared by every
    lane that offers run_steps (`_CompiledChain` below, the hybrid
    runner's chain mode, the gspmd executor's run_steps): returns
    ``chained(donated, readonly, feeds, step0) -> (fetches,
    out_writes)`` running ``body`` n_steps times in ONE computation —
    the fori_loop threads the donated state dict between iterations,
    ``stacked_feed`` slices a leading [n_steps] feed axis per
    iteration, and the step counter advances per iteration exactly like
    n separate run() calls.  Only the final step's fetches return."""
    import jax.numpy as jnp
    from jax import lax

    n = int(n_steps)

    def feed_at(feeds, i):
        if not stacked_feed:
            return feeds
        return {k: lax.dynamic_index_in_dim(v, i, axis=0,
                                            keepdims=False)
                for k, v in feeds.items()}

    def chained(donated, readonly, feeds, step0):
        def one(i, d):
            _, out_writes = body(d, readonly, feed_at(feeds, i),
                                 step0 + i.astype(jnp.uint32))
            return {k: out_writes.get(k, v) for k, v in d.items()}

        d = (lax.fori_loop(0, n - 1, one, donated) if n > 1
             else donated)
        return body(d, readonly, feed_at(feeds, n - 1),
                    step0 + np.uint32(n - 1))

    chained.__name__ = chained.__qualname__ = CHAIN_NAME
    return chained


class _CompiledChain(_JitExecutable):
    """`n_steps` iterations of a block chained inside ONE jitted call.

    A `lax.fori_loop` threads each iteration's scope writes into the next
    iteration's reads (params/opt-state/BN stats advance on-device); only
    the final step's fetches and writes come back to the host.  This is
    the TPU analog of the reference C++ trainer's tight loop
    (multi_trainer.cc — no Python between steps): one host→device
    dispatch per `n_steps` instead of per step, which matters exactly
    when dispatch is expensive relative to the step (small steps).
    """

    def __init__(self, program, block, feed_names, fetch_names, place,
                 scope, n_steps, stacked_feed, kept):
        import jax

        plan = BlockPlan(program, block, feed_names, fetch_names, scope,
                         place=place)
        if plan.host_ops or plan.host_pre_ops:
            raise HostOpsUnsupported(
                "run_steps chains the whole loop on-device; host ops "
                f"({[op.type for op in plan.host_pre_ops + plan.host_ops]}) "
                "need the host between steps — use run() per step")
        if plan.host_fetch_names:
            raise HostOpsUnsupported(
                f"fetches {plan.host_fetch_names} are host-op outputs")
        self.plan = plan
        self.place = place
        self.donated_names = plan.donated_names
        self.readonly_names = plan.readonly_names
        self.n_steps = n = int(n_steps)
        if n < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        from paddle_tpu.health import wrap_body as _health_gate

        # the health gate wraps the PER-ITERATION body, inside the
        # fori_loop: a mid-chain bad step masks its own state writes and
        # the remaining iterations continue from clean state
        body = _health_gate(program, plan.make_body())
        chained = chain_step_body(body, n, stacked_feed)

        self._jitted = jax.jit(chained, donate_argnums=(0,))
        self.label = (f"program@{id(program):x}/v{program._version}"
                      f"/chain{n}")
        self.jit_name = "jit_" + CHAIN_NAME
        self._prof_state = {"ran": False}
        self._kept = kept  # the executor's, see _stage_scope_reads

    def run(self, scope, feeds, step):
        import jax

        from paddle_tpu.observability import profiling as _profiling

        from . import profiler as _prof

        with _profiling.step_phases("chain", self.label,
                                    number=step) as ph:
            with _prof.timed_run(self.label, self._prof_state) as timer:
                with ph.phase("feed_prep"):
                    donated, readonly, feed_vals = _stage_args(
                        "chain", self, scope, feeds)
                with ph.phase("dispatch") as dispatch:
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore")  # donation unsupported on CPU
                        fetches, out_writes = self._jitted(
                            donated, readonly, feed_vals, np.uint32(step))
                    # the in-flight ledger: which program this span enqueued
                    self.ordinal = k = _profiling.enqueued(self.jit_name)
                    dispatch.note = f"{self.jit_name}#{k}"
                with ph.phase("device_wait") as waited:
                    ph.wait((fetches, out_writes))
                    if ph.blocked:
                        waited.note = _profiling.done(k)
                with ph.phase("fetch_sync"):
                    _write_back(self._kept, scope, out_writes)
                    timer.done(fetches, out_writes)
            with ph.phase("fetch_sync") as tail:
                # the host tail rides the trailing fetch_sync bracket
                # like every other lane — a large stacked fetch list's
                # host conversion must not vanish from the phase sum
                from . import flags as _flags

                if _flags.flag("benchmark"):
                    jax.block_until_ready((fetches, out_writes))
                    tail.note = _profiling.done(k)
                elif timer.enabled:  # timed_run blocked as it closed
                    tail.note = _profiling.done(k)
                if _flags.flag("check_nan_inf"):
                    # chain granularity: a NaN born mid-chain propagates
                    # through the remaining iterations (params/opt-state
                    # carry it), so the final-state scan still fails
                    # loudly — just n_steps later than run()'s per-step
                    # scan would
                    _check_nan_inf(self.plan, self.label, out_writes,
                                   fetches)
                out = self.plan.assemble_fetches(fetches, scope)
                del donated, readonly, feed_vals  # freed inside the span
        return out


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class Executor:
    """Drop-in for fluid.Executor (reference executor.py:294)."""

    def __init__(self, place=None):
        self.place = place if place is not None else framework._current_expected_place()
        self._cache: dict = {}
        self._kept = _Kept()  # what its executables staged last
        self._step = 0
        # the in-flight ledger's ordinal of the program the last run()
        # / run_steps() enqueued (observability/profiling.py): a caller
        # that takes `return_numpy=False` and waits on the outputs
        # itself marks `profiling.done(ordinal)` there
        self.ordinal = 0
        self._sentinels: dict = {}  # id(program) -> HealthSentinel|None
        # opt-in /metricsz endpoint (FLAGS_metrics_port): every process
        # that runs programs — trainer, pserver, benchmark runner — exposes
        # itself; a no-op when the flag is 0 or a server already runs
        from paddle_tpu.observability import exposition as _expo

        _expo.ensure_from_flags()

    def compiled_for(self, program):
        """The compiled-block handles cached for `program` (one per feed
        signature / fetch list) — profiling/introspection surface; see
        _CompiledBlock.cost_analysis."""
        return [cb for key, cb in self._cache.items()
                if isinstance(key, tuple) and key
                and key[0] == id(program)]

    def _cache_key(self, program, feed, fetch_names):
        """Executable-cache key: one compiled block per (program version,
        feed signature, fetch list, place).  Single source of truth shared
        by run() and cost_analysis() — the two must agree or introspection
        misses executables that ran."""
        # v.dtype directly: np.asarray on a device-resident jax array would
        # force a host transfer just to read the dtype
        feed_sig = tuple(
            (k, tuple(np.shape(v)),
             str(v.dtype if hasattr(v, "dtype") else np.asarray(v).dtype))
            for k, v in sorted(feed.items()))
        return (id(program), program._version, feed_sig,
                tuple(fetch_names), self.place)

    def cost_analysis(self, program, feed, fetch_list=None, scope=None):
        """XLA cost/memory analysis for an already-run (program, feed,
        fetch_list) step — see _CompiledBlock.cost_analysis.  Coerces the
        feed exactly as run() does (the bf16 policy narrows float feeds),
        so the AOT lowering hits the executable run() compiled rather than
        silently analyzing a differently-typed variant."""
        scope = scope or global_scope()
        feed = self._coerce_feed(program, feed)
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]
        cb = self._cache.get(self._cache_key(program, feed, fetch_names))
        if cb is None:
            raise ValueError(
                "no compiled executable for this (program, feed, "
                "fetch_list) signature — run the step once first")
        return cb.cost_analysis(scope, feed)

    def lower(self, program, feed, fetch_list=None, scope=None,
              sharding=None):
        """AOT-lower the step :meth:`run` would dispatch for this
        (program, feed, fetch_list) — same feed coercion, graph passes
        and health transpile — without executing it; ``sharding`` places
        every argument (_JitExecutable.lower)."""
        scope = scope or global_scope()
        feed = self._coerce_feed(program, feed)
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]
        self._graph_passes(program, fetch_names)
        sent = self._health(program)
        if sent is not None:
            sent.ensure_state(scope)
        cb = _CompiledBlock(program, program.global_block(), feed.keys(),
                            fetch_names, self.place, scope, self._kept)
        return cb.lower(scope, feed, (sharding, sharding))

    def close(self):
        self._cache.clear()
        self._kept.clear()
        self._sentinels.clear()

    def _graph_passes(self, program, fetch_names=()):
        """Graph-optimization passes (FLAGS_graph_passes, docs/PASSES.md):
        applied once per program, BEFORE the health sentinel and the
        executable-cache key (the pass rewrite bumps the program version,
        so stale executables can never be reused).  The first run's
        fetch list pins keep_vars — a fetch target must keep its
        producer even when single-use in-program.  Re-entry is a no-op
        inside apply_graph_passes (which also warns when the flag
        flipped after this program was already decided)."""
        from paddle_tpu import passes as _passes

        _passes.apply_graph_passes(program, lane="single",
                                   keep_vars=fetch_names)

    def _health(self, program):
        """Per-program health sentinel (FLAGS_health_sentinel, the
        single-device lane of docs/DISTRIBUTED.md §6): resolved once per
        program — `health.attach` transpiles the sentinel into it
        (bumping the version BEFORE the executable cache is keyed) and
        returns None when the flag is off or there is nothing to
        guard."""
        key = id(program)
        if key not in self._sentinels:
            from paddle_tpu import health

            self._sentinels[key] = health.attach(program, lane="single")
        return self._sentinels[key]

    def health_sentinel(self, program):
        """The health sentinel this executor attached to `program`
        (attaching it now if needed); None when FLAGS_health_sentinel is
        off or the program has nothing to guard.  The public accessor
        callers use to wire the sentinel into
        ``AutoCheckpoint(sentinel=...)`` for durable rollback windows
        (docs/DISTRIBUTED.md §6 "Preemption and recovery")."""
        return self._health(program)

    def _verify_preflight(self, program, feed, fetch_names, scope,
                          stacked_feed=False, lane="executor"):
        """FLAGS_program_verify hook (paddle_tpu/analysis/): static
        verification of (program, feeds, fetches) before the compile
        this cache miss is about to pay.  ProgramVerifyError (raise
        mode) propagates; an analyzer crash must never take the
        executor down, so anything else degrades to a warning."""
        from . import flags as _flags

        if str(_flags.flag("program_verify")).lower() in (
                "off", "0", "false", "none", ""):
            return
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
                program, lane=lane, feed_names=list((feed or {}).keys()),
                feed_shapes=feed_shapes, feed_dtypes=feed_dtypes,
                fetch_names=list(fetch_names or []),
                scope_keys=list(scope.keys()) if scope is not None else None)
        except analysis.ProgramVerifyError:
            raise
        except Exception as e:  # analyzer bug: warn, never block the run
            warnings.warn(f"program verification failed to run "
                          f"({type(e).__name__}: {e}) — continuing "
                          f"without preflight")

    def _coerce_feed(self, program, feed):
        import jax

        out = {}
        for name, val in (feed or {}).items():
            var = None
            for b in program.blocks:
                var = b._find_var_recursive(name)
                if var is not None:
                    break
            if isinstance(val, jax.Array):
                # already device-resident (dataset prefetcher device_puts
                # ahead) — keep it there; cast on-device only if needed
                if (var is not None and var.dtype is not None
                        and str(val.dtype) != var.dtype):
                    val = val.astype(var.dtype)
                out[name] = val
                continue
            a = np.asarray(val)
            if var is not None and var.dtype is not None:
                target = var.dtype
                if target == "bfloat16":
                    import jax.numpy as jnp

                    if a.dtype != jnp.bfloat16:
                        a = a.astype(jnp.bfloat16)
                elif str(a.dtype) != target:
                    a = a.astype(target)
            out[name] = a
        return out

    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope=None,
        return_numpy=True,
        use_program_cache=True,
    ):
        # CompiledProgram (data-parallel) path
        from . import compiler

        if isinstance(program, compiler.CompiledProgram):
            return program._run(self, feed, fetch_list, scope, return_numpy)

        if program is None:
            program = framework.default_main_program()
        scope = scope or global_scope()

        import time as _time

        from paddle_tpu.observability import profiling as _profiling

        step = self._step
        # everything between the caller and the recorder that
        # _CompiledBlock.run opens: feed coercion, graph passes, health,
        # the cache key and the cache hit
        with _profiling.span("lookup", "single", number=step):
            feed = self._coerce_feed(program, feed)
            fetch_list = list(fetch_list or [])
            fetch_names = [f.name if isinstance(f, Variable) else f
                           for f in fetch_list]
            block = program.global_block()
            self._graph_passes(program, fetch_names)  # before cache key
            sent = self._health(program)  # may transpile: before cache key
            key = self._cache_key(program, feed, fetch_names)
            cb = self._cache.get(key)
            if cb is not None:
                _m_cache().labels(path="single", result="hit").inc()

        # run timing ("compile+run" on a signature's first run — jit compiles
        # lazily — then "run") is recorded inside _CompiledBlock.run so every
        # execution path shares the instrumentation
        def attempt():
            first_run = not getattr(cb, "_obs_ran", False)
            t0 = _time.perf_counter()  # observability: allow
            fetches = cb.run(scope, feed, self._step)
            _record_step("single", _time.perf_counter() - t0, first_run)  # observability: allow
            cb._obs_ran = True
            self._step += 1
            return fetches

        from paddle_tpu.health import run_guarded

        if cb is not None:
            fetches = run_guarded(sent, scope, fetch_names, attempt)
        else:
            from . import profiler as _prof

            # a signature's first run, from the miss to the end of the
            # first execution, is one `compile` span
            with compile_span("single", number=step) as booked:
                # static verification rides the compile boundary: pay it
                # once per executable, never on steady-state steps
                self._verify_preflight(program, feed, fetch_names, scope)
                if sent is not None:
                    sent.ensure_state(scope)  # before BlockPlan scope checks
                t0 = _time.perf_counter()  # observability: allow
                cb = _CompiledBlock(program, block, feed.keys(), fetch_names,
                                    self.place, scope, self._kept)
                self._cache[key] = cb
                self._cache[(key, "pin")] = program  # hold program ref: id() stays unique
                booked["program"] = cb.plan.name
                trace_s = _time.perf_counter() - t0  # observability: allow
                _prof._record("trace", cb.label, trace_s)
                _m_compile_seconds().labels(path="single",
                                            phase="trace").inc(trace_s)
                # AOT path (FLAGS_aot_cache_dir): a deserialized executable
                # books "aot_hit" — NOT "miss" — and its first run carries
                # no compile, so the jit_first_run booking is skipped too
                # (the zero-compile-restart contract the decode lane's
                # acceptance measures).  An AOT save still counts as a miss
                # (the compile ran, booked under phase="aot_compile").
                aot = cb.setup_aot(scope, feed)
                if aot == "aot_hit":
                    _m_cache().labels(path="single", result="aot_hit").inc()
                    booked["outcome"] = "aot_hit"
                else:
                    _m_cache().labels(path="single", result="miss").inc()
                if aot is not None:
                    cb._obs_ran = True  # first run has no lazy compile
                fetches = run_guarded(sent, scope, fetch_names, attempt)
        self.ordinal = cb.ordinal
        if return_numpy:
            # where a lane that fetches really waits for the device
            with _profiling.span("fetch_wait", "single", number=step) as sp:
                out = [np.asarray(f) for f in fetches]
                if cb.plan.fetch_proves_done:
                    sp.note = _profiling.done(cb.ordinal)
                return out
        return fetches

    def run_steps(
        self,
        program=None,
        feed=None,
        n_steps=1,
        fetch_list=None,
        scope=None,
        return_numpy=True,
        stacked_feed=False,
    ):
        """Run `n_steps` iterations of `program` as ONE compiled XLA call.

        Semantically identical to calling run() `n_steps` times with the
        same feed (scope writes thread into the next iteration's reads,
        the executor step counter advances per iteration so random-op
        streams match), but with a single host→device dispatch — the
        reference C++ trainer's no-Python-between-steps loop
        (multi_trainer.cc), which removes the per-step host round-trip
        entirely.

        stacked_feed=True: each feed array carries a leading [n_steps]
        axis, one slice consumed per iteration (the infeed pattern).
        Only the FINAL step's fetches are returned.  Programs with host
        ops (RPC/IO) are rejected — those need the host between steps."""
        from . import compiler

        if isinstance(program, compiler.CompiledProgram):
            raise ValueError(
                "run_steps does not support CompiledProgram (data-parallel "
                "programs shard feeds in their own run path) — use run() "
                "per step")
        if isinstance(n_steps, bool) or int(n_steps) != n_steps:
            raise ValueError(f"n_steps must be an int, got {n_steps!r}")
        program = program if program is not None \
            else framework.default_main_program()
        scope = scope or global_scope()
        import time as _time

        from paddle_tpu.observability import profiling as _profiling

        step = self._step
        with _profiling.span("lookup", "chain", number=step):
            feed = self._coerce_feed(program, feed)
            if stacked_feed:
                bad = {k: np.shape(v) for k, v in feed.items()
                       if not np.shape(v) or np.shape(v)[0] != int(n_steps)}
                if bad:
                    raise ValueError(
                        f"stacked_feed arrays need a leading [{n_steps}] "
                        f"axis; got {bad}")
            fetch_list = list(fetch_list or [])
            fetch_names = [f.name if isinstance(f, Variable) else f
                           for f in fetch_list]
            # FLAT key extension: key[0] stays id(program) so
            # compiled_for() (and anything else scanning the cache by
            # program) sees chain executables too
            self._graph_passes(program, fetch_names)  # before cache key
            sent = self._health(program)  # may transpile: before cache key
            key = self._cache_key(program, feed, fetch_names) + (
                "chain", int(n_steps), bool(stacked_feed))
            cc = self._cache.get(key)
            if cc is not None:
                _m_cache().labels(path="chain", result="hit").inc()

        # sentinel at CHAIN granularity: a mid-chain bad step was masked
        # in-graph; post_step books it via the cumulative counter, and a
        # rollback restores the pre-CHAIN state and replays the chain
        def attempt():
            first_run = not getattr(cc, "_obs_ran", False)
            t0 = _time.perf_counter()  # observability: allow
            fetches = cc.run(scope, feed, self._step)
            _record_step("chain", _time.perf_counter() - t0, first_run)  # observability: allow
            cc._obs_ran = True
            self._step += int(n_steps)
            return fetches

        from paddle_tpu.health import run_guarded

        if cc is not None:
            fetches = run_guarded(sent, scope, fetch_names, attempt,
                                  chain=int(n_steps) > 1)
        else:
            from . import profiler as _prof

            with compile_span("chain", number=step) as booked:
                _m_cache().labels(path="chain", result="miss").inc()
                self._verify_preflight(program, feed, fetch_names, scope,
                                       stacked_feed=bool(stacked_feed))
                if sent is not None:
                    sent.ensure_state(scope)
                t0 = _time.perf_counter()  # observability: allow
                cc = _CompiledChain(program, program.global_block(),
                                    feed.keys(), fetch_names, self.place,
                                    scope, int(n_steps), bool(stacked_feed),
                                    self._kept)
                self._cache[key] = cc
                self._cache[(key, "pin")] = program
                booked["program"] = CHAIN_NAME
                trace_s = _time.perf_counter() - t0  # observability: allow
                _prof._record("trace", cc.label, trace_s)
                _m_compile_seconds().labels(path="chain",
                                            phase="trace").inc(trace_s)
                fetches = run_guarded(sent, scope, fetch_names, attempt,
                                      chain=int(n_steps) > 1)
        self.ordinal = cc.ordinal
        if return_numpy:
            with _profiling.span("fetch_wait", "chain", number=step) as sp:
                out = [np.asarray(f) for f in fetches]
                if cc.plan.fetch_proves_done:
                    sp.note = _profiling.done(cc.ordinal)
                return out
        return fetches

    # ------------------------------------------------------------------
    # train_from_dataset / infer_from_dataset parity (reference
    # executor.py:815 → C++ trainer path).  Here: an in-process loop over the
    # dataset's batches through the same compiled-block path.
    # ------------------------------------------------------------------
    def train_from_dataset(
        self, program=None, dataset=None, scope=None, thread=0,
        debug=False, fetch_list=None, fetch_info=None, print_period=100,
    ):
        """Step over a Dataset via the trainer/device-worker layer
        (reference executor.py:815 _prepare_trainer → TrainerFactory →
        C++ trainer threads).  The trainer class comes from
        ``program._fleet_opt`` ({"trainer": ..., "device_worker": ...});
        default is MultiTrainer+Hogwild = the prefetch loop below."""
        from .trainer_factory import TrainerFactory

        from . import compiler as _compiler

        if dataset is None:
            raise ValueError("dataset is required")
        program_ = program if program is not None \
            else framework.default_main_program()
        raw = (program_._program
               if isinstance(program_, _compiler.CompiledProgram)
               else program_)
        opt_info = getattr(raw, "_fleet_opt", None)
        trainer = TrainerFactory()._create_trainer(opt_info)
        trainer._set_program(program_)
        if thread:
            trainer._set_thread(thread)
        trainer._set_debug(debug)
        trainer._set_fetch_var_and_info(fetch_list, fetch_info, print_period)
        return trainer._run(self, program_, dataset, scope,
                            fetch_list=fetch_list)

    def _dataset_step_loop(
        self, program=None, dataset=None, scope=None,
        debug=False, fetch_list=None, fetch_info=None, print_period=100,
    ):
        """The Hogwild/Downpour step path: ingestion OVERLAPPED with steps
        (reference multi_trainer.cc + buffered_reader.cc double-buffering):
        a reader thread drains the native parser queue, coerces dtypes and
        device_puts each batch ahead, buffering 2 batches (override the
        depth with PT_DATASET_PREFETCH; 0 disables — synchronous loop).
        `thread` keeps its reference meaning (worker parallelism) and maps
        to parser threads via dataset.set_thread, NOT to buffer depth —
        each buffered batch is device-resident, so depth costs HBM.
        Input-bound time is recorded in the profiler ("dataset_wait") and
        summarized in `self.last_dataset_stats["input_bound_fraction"]`."""
        import os
        import time as _time

        import jax

        from . import compiler as _compiler
        from . import profiler as _prof
        from .prefetch import DatasetPrefetcher

        if dataset is None:
            raise ValueError("dataset is required")
        fetch_list = fetch_list or []
        program = program if program is not None else framework.default_main_program()
        depth = int(os.environ.get("PT_DATASET_PREFETCH", "2"))
        t_start = _time.perf_counter()  # observability: allow

        if depth <= 0:
            it, pf = dataset._iter_batches(), None
        elif isinstance(program, _compiler.CompiledProgram):
            # data-parallel programs shard feeds across devices in their own
            # run path — overlap the parsing only, hand over host batches
            it = pf = DatasetPrefetcher(dataset._iter_batches(), depth=depth)
        else:
            device = self.place.jax_device()

            def transform(batch):
                coerced = self._coerce_feed(program, batch)
                return {k: jax.device_put(v, device)
                        for k, v in coerced.items()}

            it = pf = DatasetPrefetcher(dataset._iter_batches(),
                                        transform=transform, depth=depth)
        # PT_DATASET_CHAIN=K: dispatch K same-shaped batches as ONE
        # run_steps call (stacked_feed fori_loop) — the DeviceWorker-loop
        # analog with zero host dispatch between steps.  Ragged tails and
        # shape changes flush per-step (no surprise per-length compiles);
        # CompiledProgram (DP) keeps its own run path.
        chain = int(os.environ.get("PT_DATASET_CHAIN", "0") or 0)
        if isinstance(program, _compiler.CompiledProgram):
            chain = 0
        steps = 0
        pending = []

        def _shape_sig(batch):
            return tuple(sorted((k, tuple(np.shape(v)))
                                for k, v in batch.items()))

        def _flush():
            """Dispatch pending batches: a full chunk of exactly `chain`
            goes as one run_steps call, anything else per-step."""
            nonlocal steps, chain
            res = None
            if chain > 1 and len(pending) == chain:
                import jax.numpy as jnp

                chunk = list(pending)
                pending.clear()
                stacked = {k: jnp.stack([b[k] for b in chunk])
                           for k in chunk[0]}
                try:
                    res = self.run_steps(
                        program, feed=stacked, n_steps=chain,
                        fetch_list=fetch_list, scope=scope,
                        stacked_feed=True)
                    steps += chain
                    return res
                except HostOpsUnsupported:
                    chain = 0  # host ops — chaining permanently off
                    pending[:] = chunk
            while pending:
                res = self.run(program=program, feed=pending.pop(0),
                               fetch_list=fetch_list, scope=scope)
                steps += 1
            return res

        next_log = 0  # log by STEP count, not loop index — under chaining
        # the loop only observes flush indices, which can never hit
        # `i % print_period == 0` for most (chain, period) pairs

        def _maybe_log(res):
            nonlocal next_log
            if debug and fetch_list and res is not None \
                    and steps > next_log:
                names = fetch_info or [
                    f if isinstance(f, str) else f.name
                    for f in fetch_list]
                logger.info("step %d: %s", steps - 1,
                            dict(zip(names, res)))
                next_log += print_period

        try:
            sig = None
            for batch in it:
                if chain > 1:
                    bsig = _shape_sig(batch)
                    if pending and bsig != sig:
                        _maybe_log(_flush())  # shape change: drain per-step
                    sig = bsig
                    pending.append(batch)
                    if len(pending) < chain:
                        continue
                    _maybe_log(_flush())
                else:
                    res = self.run(program=program, feed=batch,
                                   fetch_list=fetch_list, scope=scope)
                    steps += 1
                    _maybe_log(res)
            _maybe_log(_flush())  # ragged tail drains per-step
        finally:
            if pf is not None:
                pf.close()
                total = _time.perf_counter() - t_start  # observability: allow
                self.last_dataset_stats = {
                    "steps": steps,
                    "prefetch_depth": depth,
                    "input_wait_s": round(pf.wait_seconds, 4),
                    "produce_s": round(pf.produce_seconds, 4),
                    "total_s": round(total, 4),
                    "input_bound_fraction": round(
                        pf.wait_seconds / total, 4) if total > 0 else 0.0,
                }
                _prof._record("dataset_wait", "train_from_dataset",
                              pf.wait_seconds)

    def infer_from_dataset(self, *args, **kw):
        return self.train_from_dataset(*args, **kw)
