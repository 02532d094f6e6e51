"""Program-pass framework: the ONE place program rewrites are ordered,
validated, and attributed.

The reference Fluid routes every program rewrite through an
``ir::Graph`` + ``Pass`` layer (``build_strategy.cc:52-145`` declares
the pipeline; ~60 registered passes).  This framework is its TPU-native
program-level analog and the successor to our four ad-hoc rewriters (DP
transpiler, fused-update rewrite, health transpile, inference
transpiler): passes run BETWEEN program construction and executor
compile on every lane, their order is declared once (``PASS_ORDER``),
and every application records what it changed — op-inventory delta,
matched sites, modeled bytes saved — into ``program._pass_report`` so a
claimed win is attributed, not asserted.

Contracts every ``ProgramPass`` must honor:

- **in-place**: ``apply(program, ctx)`` mutates the program and returns
  a report dict with at least ``{"changed": bool, "sites": int}``.
- **idempotence**: a second ``apply`` on the already-rewritten program
  must be a no-op (``changed=False``).  ``PT_PASS_SELFCHECK=1`` makes
  the manager enforce this after every application (test/CI mode).
- **off = identity**: with the pass disabled (FLAGS_graph_passes) the
  program is bit-identical to today's — passes never run partially.

Selection (``FLAGS_graph_passes``): ``"default"``/``"auto"`` = the
DEFAULT_PASSES pipeline; ``"none"``/``""`` = off; otherwise a
comma-separated ordered list of registered pass names, each optionally
prefixed with ``-`` to drop it from the default set (``"default``
semantics with exclusions: ``-fuse_attention`` runs everything default
except that pass).

Cost attribution: the eager report carries the structural delta (op
inventory, sites, statically-modeled bytes).  ``attribute_costs``
(acceptance tests) measures the REAL per-pass
``cost_analysis`` delta — flops, bytes_accessed, compiled-HLO op
inventory — by compiling each pipeline prefix, and books the measured
bytes reduction on ``pt_pass_bytes_saved_total{pass}``.
"""

from __future__ import annotations

import collections
import os
import warnings

import numpy as np

__all__ = [
    "ProgramPass",
    "PassManager",
    "PassContext",
    "register_program_pass",
    "get_program_pass",
    "list_program_passes",
    "resolve_passes",
    "apply_graph_passes",
    "attribute_costs",
    "op_inventory",
    "DEFAULT_PASSES",
    "PASS_ORDER",
]

# the default pipeline FLAGS_graph_passes="default" expands to
DEFAULT_PASSES = ["fuse_attention", "fuse_bias_act_dropout",
                  "fuse_softmax_cross_entropy"]

# THE ordering contract (docs/PASSES.md): when two entries both appear
# in a pipeline they must run in this relative order.  The transpile
# adapters (paddle_tpu/passes/adapters.py) register here too, so the
# ordering between fusion passes and the DP/health transpiles is
# declared in ONE place instead of implied by runner call sites:
# fusion first (the DP fused-update rewrite must see the final forward
# graph), the collective/fused-update transpile next, the health
# sentinel LAST (its detection point depends on the fused buckets).
PASS_ORDER = [
    "fuse_attention",
    "fuse_bias_act_dropout",
    "fuse_softmax_cross_entropy",
    "int8_weight_storage",       # after fusion: rewrites surviving muls
    "data_parallel_transpile",   # includes the fused-update DP rewrite
    "health_sentinel",
]


class PassContext:
    """What a pass application may know about its caller: the execution
    lane (``single``/``chain``/``dp``/``hybrid``/``gspmd``/``serving``),
    var names that must keep a producer (fetch targets live OUTSIDE the
    program here — the executor pins the first run's fetch list), and
    the loss name where the lane knows it."""

    def __init__(self, lane="single", keep_vars=(), loss_name=None,
                 **extra):
        self.lane = lane
        self.keep_vars = frozenset(keep_vars or ())
        self.loss_name = loss_name
        self.extra = dict(extra)


class ProgramPass:
    """Base pass.  Subclasses set ``name`` and implement
    ``apply(program, ctx) -> report dict``; ``validate(program, ctx)``
    runs after apply and should raise on a broken invariant."""

    name = "program_pass"

    def apply(self, program, ctx):
        raise NotImplementedError

    def validate(self, program, ctx):
        """Post-apply invariant check (override where cheap proofs
        exist).  Default: every op in the program still has a
        registered lowering — a rewrite must never emit an op the
        executor cannot trace."""
        from paddle_tpu.fluid import registry

        for b in program.blocks:
            for op in b.ops:
                if op.type in ("feed", "fetch"):
                    continue
                try:
                    # get_op, not has_op: higher-order grad ops
                    # (recurrent_grad_grad) materialize lazily on first
                    # lookup — absent from the registry dict yet valid
                    registry.get_op(op.type)
                except KeyError:
                    raise AssertionError(
                        f"pass {self.name!r} left unregistered op "
                        f"{op.type!r} in block {b.idx}") from None


_PASS_REGISTRY: dict = {}


def register_program_pass(cls):
    """Class decorator: register a ProgramPass subclass by its ``name``
    (also mirrored into fluid.ir.PassRegistry for enumeration parity
    with the reference-style pass registry)."""
    _PASS_REGISTRY[cls.name] = cls

    from paddle_tpu.fluid import ir as _ir

    class _IrShim(_ir.Pass):
        name = cls.name

        def apply(self, graph):  # pragma: no cover - thin mirror
            PassManager([cls.name]).run(graph.program, PassContext())
            return graph

    if not _ir.PassRegistry.has(cls.name):
        _ir.PassRegistry.register(cls.name, lambda **kw: _IrShim())
    return cls


def get_program_pass(name):
    if name not in _PASS_REGISTRY:
        raise KeyError(f"unknown program pass {name!r}; registered: "
                       f"{sorted(_PASS_REGISTRY)}")
    return _PASS_REGISTRY[name]()


def list_program_passes():
    return sorted(_PASS_REGISTRY)


def resolve_passes(spec=None):
    """Expand a FLAGS_graph_passes selection string into an ordered pass
    name list (see module docstring for the grammar)."""
    if spec is None:
        from paddle_tpu.fluid import flags as _flags

        spec = _flags.flag("graph_passes")
    spec = (spec or "").strip()
    if spec.lower() in ("", "none", "off", "0"):
        return []
    toks = [t.strip() for t in spec.split(",") if t.strip()]
    out, dropped = [], set()
    expand_default = False
    for t in toks:
        if t.lower() in ("default", "auto"):
            expand_default = True
        elif t.startswith("-"):
            dropped.add(t[1:].strip())
            expand_default = True  # exclusions imply the default base
        else:
            out.append(t)
    if expand_default:
        out = [p for p in DEFAULT_PASSES if p not in dropped] + \
            [p for p in out if p not in DEFAULT_PASSES]
    # a typo'd "-name" must fail loudly, not silently leave the pass on
    unknown = sorted(dropped - set(_PASS_REGISTRY)) + \
        [p for p in out if p not in _PASS_REGISTRY]
    if unknown:
        raise KeyError(
            f"FLAGS_graph_passes names unknown pass(es) {unknown}; "
            f"registered: {sorted(_PASS_REGISTRY)}")
    _check_order(out)
    return out


def _check_order(names):
    """Enforce the declared partial order: any two selected passes that
    both appear in PASS_ORDER must run in that relative order."""
    pos = {n: i for i, n in enumerate(PASS_ORDER)}
    ranked = [(n, pos[n]) for n in names if n in pos]
    for (a, ra), (b, rb) in zip(ranked, ranked[1:]):
        if ra > rb:
            raise ValueError(
                f"pass order violation: {a!r} must run after {b!r} "
                f"(declared order: {PASS_ORDER})")


# ops whose lowering draws an op_rng_key: their stream is keyed on the
# TRACE index, which a rewrite upstream of them would silently shift.
# The manager pins each one's pre-pass identity (`rng_op_index`) before
# the first pass runs, so fused and unfused programs draw the same
# streams (the cross-program parity contract; see ops/common.py).
RANDOM_OP_TYPES = frozenset({
    "dropout", "uniform_random", "gaussian_random",
    "truncated_gaussian_random", "randint", "sampling_id",
    "uniform_random_batch_size_like", "gaussian_random_batch_size_like",
    "random_crop", "dpsgd", "sampled_softmax_with_cross_entropy",
    "sample_logits", "fused_bias_act_dropout",
})


def pin_random_streams(program):
    """Stamp ``rng_op_index`` on every block-0 random op that lacks one
    (sub-blocks never shift: passes rewrite block 0 only)."""
    blk = program.global_block()
    for i, op in enumerate(blk.ops):
        if op.type in RANDOM_OP_TYPES and "rng_op_index" not in op.attrs:
            op.attrs["rng_op_index"] = (blk.idx << 16) | i


def op_inventory(program):
    """Op-type -> count over every block (the program-level analog of
    the compiled-HLO inventory the cost probe records)."""
    inv = collections.Counter()
    for b in program.blocks:
        for op in b.ops:
            inv[op.type] += 1
    return dict(inv)


def _inventory_delta(before, after):
    """{op_type: after-before} keeping only nonzero entries."""
    out = {}
    for t in set(before) | set(after):
        d = after.get(t, 0) - before.get(t, 0)
        if d:
            out[t] = d
    return out


def _m_applied():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_pass_applied_total",
        "Graph-optimization pass applications by pass and outcome",
        labels=("pass", "changed"))


def _m_sites():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_pass_sites_total",
        "Subgraph sites rewritten by graph-optimization passes",
        labels=("pass",))


def _m_bytes_saved():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_pass_bytes_saved_total",
        "Per-step bytes_accessed reduction attributed to graph-"
        "optimization passes: statically modeled at apply time where "
        "shapes allow, and the measured cost_analysis delta when a "
        "cost attribution runs (bench/acceptance)",
        labels=("pass",))


class PassManager:
    """Ordered pass pipeline over a Program.

    ``run(program, ctx)`` applies each pass, validates it, records the
    per-pass report entry into ``program._pass_report`` (a list — one
    entry per application, so a caller or test can read exactly
    what happened), books the pt_pass_* metrics, and enforces the
    idempotence contract when ``selfcheck`` (default: the
    ``PT_PASS_SELFCHECK`` env) is on."""

    def __init__(self, names):
        _check_order(list(names))
        self.names = list(names)

    def run(self, program, ctx=None, selfcheck=None):
        ctx = ctx or PassContext()
        if selfcheck is None:
            selfcheck = os.environ.get("PT_PASS_SELFCHECK", "") not in (
                "", "0")
        report = getattr(program, "_pass_report", None)
        if report is None:
            report = []
            program._pass_report = report
        if self.names:
            pin_random_streams(program)
        for name in self.names:
            p = get_program_pass(name)
            before = op_inventory(program)
            entry = p.apply(program, ctx) or {}
            entry.setdefault("changed", False)
            entry.setdefault("sites", 0)
            entry["pass"] = name
            entry["lane"] = ctx.lane
            after = op_inventory(program)
            entry["op_delta"] = _inventory_delta(before, after)
            p.validate(program, ctx)
            if selfcheck and entry["changed"]:
                second = p.apply(program, ctx) or {}
                if second.get("changed"):
                    raise AssertionError(
                        f"pass {name!r} violated the idempotence "
                        f"contract: second apply still reports changes "
                        f"({second})")
            report.append(entry)
            _m_applied().labels(
                **{"pass": name,
                   "changed": "yes" if entry["changed"] else "no"}).inc()
            if entry["sites"]:
                _m_sites().labels(**{"pass": name}).inc(entry["sites"])
            modeled = entry.get("modeled_bytes_saved")
            if modeled:
                _m_bytes_saved().labels(**{"pass": name}).inc(modeled)
        if self.names and any(e["changed"]
                              for e in report[-len(self.names):]):
            program._bump_version()
        return report


def apply_graph_passes(program, lane="single", spec=None, keep_vars=(),
                       loss_name=None):
    """The one lane entry point: resolve FLAGS_graph_passes and run the
    pipeline once per program (idempotent — re-entry with the same spec
    is a no-op; the guard records the spec so a flag flip between runs
    of the SAME program object surfaces as a loud error instead of a
    silent half-rewritten state).  Returns the pass report (possibly
    empty) or None when passes are off."""
    raw = spec
    if raw is None:
        from paddle_tpu.fluid import flags as _flags

        raw = _flags.flag("graph_passes")
    done = getattr(program, "_graph_passes_done", None)
    if done is not None:
        # hot-path early-out: this runs on EVERY Executor step — an
        # unchanged selection string must cost one attribute compare,
        # not a grammar re-resolution (the ±2% step-overhead bar)
        if raw == getattr(program, "_graph_passes_spec", None):
            return getattr(program, "_pass_report", None)
        names = resolve_passes(raw)
        if done != tuple(names):
            warnings.warn(
                "FLAGS_graph_passes changed after this program was "
                f"already rewritten (was {list(done)}, now {names}); "
                "keeping the original rewrite — build a fresh program "
                "to change pass selection")
        else:  # equivalent spelling: remember it so the fast path hits
            program._graph_passes_spec = raw
        return getattr(program, "_pass_report", None)
    names = resolve_passes(raw)
    if not names:
        # off-configuration: bit-identical program, and remember the
        # decision so a later flag flip cannot rewrite a program that
        # already compiled
        program._graph_passes_done = ()
        program._graph_passes_spec = raw
        return None
    ctx = PassContext(lane=lane, keep_vars=keep_vars, loss_name=loss_name)
    report = PassManager(names).run(program, ctx)
    program._graph_passes_done = tuple(names)
    program._graph_passes_spec = raw
    return report


# ---------------------------------------------------------------------------
# cost attribution: the measured per-pass delta
# ---------------------------------------------------------------------------


def _cost_probe(build_fn, pass_names, feed, fetch_list, place=None,
                want_hlo=False):
    """Build a FRESH program via ``build_fn()``, apply exactly
    ``pass_names``, run one step and return its cost_analysis numbers
    (+ optimized-HLO text when asked).  ``build_fn() -> (main, startup,
    loss_or_none)``; feed/fetch_list as for Executor.run."""
    from paddle_tpu import fluid

    main, startup, _loss = build_fn()
    # pin the selection so the executor's default application cannot
    # stack on top of the probe's explicit prefix
    main._graph_passes_done = ()
    startup._graph_passes_done = ()
    if pass_names:
        main._graph_passes_done = None
        ctx = PassContext(lane="probe",
                          keep_vars=[f if isinstance(f, str) else f.name
                                     for f in fetch_list])
        PassManager(list(pass_names)).run(main, ctx)
        main._graph_passes_done = tuple(pass_names)
    scope = fluid.Scope()
    with fluid.scope_guard(scope), warnings.catch_warnings():
        # pinning a pipeline PREFIX deliberately diverges from the live
        # flag — the mismatch warning is the probe's design, not a bug
        warnings.filterwarnings("ignore",
                                message="FLAGS_graph_passes changed")
        exe = fluid.Executor(place or fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=fetch_list)
        cost = exe.cost_analysis(main, feed, fetch_list=fetch_list)
    out = {
        "flops": float(cost["cost"].get("flops", 0.0)),
        "bytes_accessed": float(cost["cost"].get("bytes accessed", 0.0)),
    }
    if want_hlo:
        (cb,) = [c for c in exe.compiled_for(main)]
        hlo = cb._jitted.lower(
            *cb._jit_args(scope, exe._coerce_feed(main, feed),
                          0)).compile().as_text()
        out["hlo"] = hlo
    return out


def attribute_costs(build_fn, feed, fetch_list, spec=None, place=None,
                    want_hlo=False):
    """Measure the REAL per-pass cost_analysis delta: compile each
    pipeline prefix ([], [p1], [p1,p2], ...) of the resolved selection
    against a fresh build and diff consecutive flops / bytes_accessed.

    Returns ``{"baseline": {...}, "per_pass": [{"pass", "flops_delta",
    "bytes_accessed_delta", ...}], "final": {...}}`` and books each
    pass's measured bytes reduction (when positive) on
    ``pt_pass_bytes_saved_total{pass}``.  With ``want_hlo`` the final
    stage's optimized HLO text rides along (the fusion-proof surface).
    CPU-measurable cost-model counts; the on-chip effect is not
    measured (PERF.md).
    """
    names = resolve_passes(spec)
    stages = [names[:i] for i in range(len(names) + 1)]
    probes = []
    for i, prefix in enumerate(stages):
        probes.append(_cost_probe(
            build_fn, prefix, feed, fetch_list, place=place,
            want_hlo=want_hlo and i == len(stages) - 1))
    per_pass = []
    for name, prev, cur in zip(names, probes, probes[1:]):
        d_bytes = prev["bytes_accessed"] - cur["bytes_accessed"]
        d_flops = prev["flops"] - cur["flops"]
        per_pass.append({
            "pass": name,
            "bytes_accessed_delta": d_bytes,
            "flops_delta": d_flops,
            "bytes_accessed": cur["bytes_accessed"],
            "flops": cur["flops"],
        })
        if d_bytes > 0:
            _m_bytes_saved().labels(**{"pass": name}).inc(int(d_bytes))
    out = {"baseline": {k: v for k, v in probes[0].items() if k != "hlo"},
           "per_pass": per_pass,
           "final": {k: v for k, v in probes[-1].items() if k != "hlo"}}
    if want_hlo and "hlo" in probes[-1]:
        out["final_hlo"] = probes[-1]["hlo"]
    return out


# ---------------------------------------------------------------------------
# shared matcher plumbing for the fusion passes
# ---------------------------------------------------------------------------


def consumer_map(program):
    """var name -> list of ops reading it, across EVERY block (a
    sub-block read must veto fusing the producer away)."""
    cons = collections.defaultdict(list)
    for b in program.blocks:
        for op in b.ops:
            for n in set(op.input_arg_names):
                cons[n].append(op)
    return cons


def is_backward(op):
    return op.attrs.get("op_role") in ("backward", "optimize")


def single_forward_consumer(cons, name, block=None):
    """The unique non-backward consumer of ``name``, or None.  With
    ``block`` given, the consumer must also LIVE in that block — a
    sub-block (while/cond body) consumer means the var escapes the
    rewrite scope, so the chain walk must stop rather than absorb an op
    the matcher's block-0 index doesn't know."""
    fwd = [op for op in cons.get(name, []) if not is_backward(op)]
    if len(fwd) != 1:
        return None
    if block is not None and fwd[0].block is not block:
        return None
    return fwd[0]


def grad_groups(block):
    """fwd op index -> grad ops differentiating it (append_backward
    stamps ``fwd_op_idx`` on every grad desc)."""
    groups = collections.defaultdict(list)
    for op in block.ops:
        idx = op.attrs.get("fwd_op_idx")
        if idx is not None and is_backward(op):
            groups[int(idx)].append(op)
    return groups


def static_numel(block, name):
    """Element count when the var's shape is fully static, else None."""
    v = block._find_var_recursive(name)
    if v is None or v.shape is None or any(
            d is None or d < 0 for d in v.shape):
        return None
    return int(np.prod(v.shape, dtype=np.int64)) if v.shape else 1


def rebuild_block(block, remove_ids, inserts):
    """Rebuild ``block.ops`` removing ops whose id() is in
    ``remove_ids`` and inserting new ops at anchors: ``inserts`` maps
    id(anchor op) -> list of new ops placed AT the anchor's position
    (the anchor itself may also be in remove_ids).  Afterwards every
    retained/inserted op's ``fwd_op_idx`` attr is renumbered to the new
    index of the forward op it references; removed forward indices remap
    through ``fwd_redirect`` (old idx -> anchor op whose new position
    stands in for the fused subgraph) passed inside ``inserts`` via the
    optional second tuple element.

    inserts: {anchor_id: (new_ops, redirected_old_fwd_idxs)} — every
    old fwd index in the redirect set maps to the FIRST new op's final
    position.
    """
    new_ops = []
    old_index_of = {id(op): i for i, op in enumerate(block.ops)}
    # old fwd idx -> marker object whose final position stands in
    redirect_target = {}
    for anchor_id, (ops_new, redirects) in inserts.items():
        for old in redirects:
            redirect_target[old] = id(ops_new[0]) if ops_new else None
    for op in block.ops:
        ins = inserts.get(id(op))
        if ins is not None:
            new_ops.extend(ins[0])
        if id(op) not in remove_ids:
            new_ops.append(op)
    new_index_of = {id(op): i for i, op in enumerate(new_ops)}
    remap = {}
    for oid, old in old_index_of.items():
        if oid in new_index_of:
            remap[old] = new_index_of[oid]
    for old, target in redirect_target.items():
        if target is not None and target in new_index_of:
            remap[old] = new_index_of[target]
    for op in new_ops:
        idx = op.attrs.get("fwd_op_idx")
        if idx is not None and int(idx) in remap:
            op.attrs["fwd_op_idx"] = remap[int(idx)]
    block.ops = new_ops
    block.program._bump_version()
    return remap
