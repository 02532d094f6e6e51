"""Control-flow ops: while / conditional_block / static_rnn.

Reference analogs: paddle/fluid/operators/controlflow/while_op.cc (runs a
sub-block with an inner Executor per iteration, scopes chained),
conditional_block_op.cc, and recurrent_op.cc (static RNN over a sub-block).

TPU-native redesign: each op still owns a sub-block of op descs (so
transpilers see and can rewrite the loop body), but the lowering is a
*functional* XLA control-flow primitive:

  while             → lax.while_loop   (not differentiable; use static_rnn
                                        for trainable recurrence)
  conditional_block → lax.cond         (differentiable through both branches)
  static_rnn        → lax.scan         (differentiable; the TPU-idiomatic
                                        recurrence — compiler-friendly, no
                                        per-step dispatch like while_op.cc)

Crucial design point: the reference's sub-blocks read enclosing-scope
variables implicitly; XLA control flow is functional, so the Python layer
(fluid/layers/control_flow.py) performs capture analysis and declares every
external read as an explicit op input:

  Carry*   — loop-carried vars (written in the body, live in an outer block)
  Extra*   — read-only float captures (weights!) — declared so append_backward
             emits grads for them through the auto-vjp grad op
  ExtraNG* — read-only non-float captures (int ids, masks)

Name lists ride in attrs so the lowering can rebuild the sub-block's env
without relying on ambient state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.fluid.registry import register_op, simple_op


def _sub_env(attrs, carries, extras, extras_ng):
    env = dict(zip(attrs["extra_names"], extras or []))
    env.update(zip(attrs["extra_ng_names"], extras_ng or []))
    env.update(zip(attrs["carry_names"], carries or []))
    return env


def _trace_sub(ctx, sub_block, env):
    from paddle_tpu.fluid.executor import trace_block

    sub_ctx = type(ctx)(step=ctx.step, is_test=ctx.is_test,
                        executor=ctx.executor, block=sub_block,
                        mesh_axes=ctx.mesh_axes, env=env)
    sub_ctx.program = sub_block.program
    # optional trace-wide state must survive into sub-blocks: the target
    # place (py_func/print callback gating) and the dtype policy
    for attr in ("place", "dtype_policy"):
        if hasattr(ctx, attr):
            setattr(sub_ctx, attr, getattr(ctx, attr))
    trace_block(sub_block, env, sub_ctx)
    return env


def _as_pred(c):
    return jnp.reshape(c, ()).astype(bool)


def _match_carry(ref, val):
    """Coerce a body/branch output back to its carry's dtype.  The bf16
    dtype policy decides per-op dtypes from operand sizes, so a loop body
    can legitimately produce fp32 where the init carry was downcast to
    bf16 (e.g. an all-scalar accumulator tail) — lax.while_loop/cond
    require exactly matching carry types."""
    from paddle_tpu.fluid.struct_values import is_struct_value

    if is_struct_value(val) or is_struct_value(ref):
        return val
    r = jnp.asarray(ref)
    v = jnp.asarray(val)
    return v.astype(r.dtype) if v.dtype != r.dtype else v


@simple_op("while", ["Condition", "Carry*", "Extra*", "ExtraNG*"], ["Out*"],
           grad=None)
def _while(ctx, cond, carries, extras, extras_ng, attrs):
    """Run sub_block until the carried condition var goes false.

    The condition var MUST be among the carries (the body re-computes it, the
    standard Fluid pattern: `layers.less_than(i, n, cond=cond)` at body end).
    """
    sub = ctx.block.program.block(attrs["sub_block"])
    carry_names = attrs["carry_names"]
    cond_name = attrs["cond_name"]
    if cond_name not in carry_names:
        raise ValueError(
            f"while: condition var {cond_name!r} is never written in the loop "
            f"body (infinite loop) — update it, e.g. layers.less_than(i, n, "
            f"cond=cond)")
    ci = carry_names.index(cond_name)
    base = _sub_env(attrs, [], extras, extras_ng)

    def cond_fn(c):
        return _as_pred(c[ci])

    def body_fn(c):
        env = dict(base)
        env.update(zip(carry_names, c))
        _trace_sub(ctx, sub, env)
        return tuple(_match_carry(ref, env[n])
                     for ref, n in zip(c, carry_names))

    from paddle_tpu.fluid.struct_values import is_struct_value

    init = tuple(c if is_struct_value(c) else jnp.asarray(c)
                 for c in carries)
    final = lax.while_loop(cond_fn, body_fn, init)
    return (tuple(final),)


@simple_op("conditional_block", ["Cond", "Carry*", "Extra*", "ExtraNG*"],
           ["Out*"], no_grad_inputs=("Cond", "ExtraNG"))
def _conditional_block(ctx, cond, carries, extras, extras_ng, attrs):
    """Out_i = cond ? sub_block(...)[carry_i] : carry_i.

    Both branches are compiled (lax.cond); the false branch passes the
    carried values through unchanged — same observable behavior as the
    reference's skip-the-block, expressed functionally.
    """
    sub = ctx.block.program.block(attrs["sub_block"])
    carry_names = attrs["carry_names"]

    def true_fn(c, ex):
        env = dict(zip(attrs["extra_names"], ex))
        env.update(zip(attrs["extra_ng_names"], extras_ng or []))
        env.update(zip(carry_names, c))
        _trace_sub(ctx, sub, env)
        return tuple(_match_carry(ref, env[n])
                     for ref, n in zip(c, carry_names))

    def false_fn(c, ex):
        return tuple(c)

    outs = lax.cond(_as_pred(cond), true_fn, false_fn,
                    tuple(carries), tuple(extras or []))
    return (tuple(outs),)


@simple_op("static_rnn", ["StepIn*", "Init*", "Extra*", "ExtraNG*"],
           ["StackedOut*", "LastMem*"], no_grad_inputs=("ExtraNG",))
def _static_rnn(ctx, step_ins, inits, extras, extras_ng, attrs):
    """lax.scan over dim 0 of the step inputs.

    attrs: sub_block, step_in_names (local per-step var names), mem_names
    (local memory var names, carried), update_map (mem local name → local name
    of its next value), out_names (local per-step output var names).
    Outputs: per-step outputs stacked on dim 0, and the final memory values.
    Fully differentiable (jax.vjp through scan) — this is the trainable
    recurrence, unlike `while`.
    """
    sub = ctx.block.program.block(attrs["sub_block"])
    step_in_names = attrs["step_in_names"]
    mem_names = attrs["mem_names"]
    update_map = attrs["update_map"]
    out_names = attrs["out_names"]
    base = {}
    base.update(zip(attrs["extra_names"], extras or []))
    base.update(zip(attrs["extra_ng_names"], extras_ng or []))

    def f(mems, xs):
        env = dict(base)
        env.update(zip(mem_names, mems))
        env.update(zip(step_in_names, xs))
        _trace_sub(ctx, sub, env)
        new_mems = tuple(_match_carry(ref, env[update_map[m]])
                         for ref, m in zip(mems, mem_names))
        outs = tuple(env[n] for n in out_names)
        return new_mems, outs

    final_mems, stacked = lax.scan(f, tuple(inits), tuple(step_ins))
    return (tuple(stacked), tuple(final_mems))


@simple_op("print", ["X"], ["Out"])
def _print(ctx, x, attrs):
    """Pass-through with host-side printing (reference print_op)."""
    import jax

    msg = (attrs.get("message") or "print")
    # user text must not be treated as format fields (jax's formatter
    # rejects {{-escapes, so substitute plain parens)
    msg = msg.replace("{", "(").replace("}", ")")
    jax.debug.print(msg + ": {x}", x=x)
    return x


@simple_op("recurrent",
           ["inputs*", "initial_states*", "parameters*"],
           ["outputs*", "step_scopes"])
def _recurrent(ctx, seq_ins, init_states, params, attrs):
    """The reference StaticRNN's exported op (recurrent_op.cc), lowered to
    lax.scan so imported reference programs run.

    Name contract (reference layers/control_flow.py _complete_op): the
    sub-block shadows each sequence input and each stacked output under
    the SAME name as the outer var; `ex_states`/`states` attrs carry the
    in-block names of the previous/updated memories, zipped with the
    `initial_states` input order.  Sequence inputs are time-major [T, ...]
    sliced on dim 0; `reverse` walks time backward (outputs flipped back
    so out[t] still corresponds to in[t]).  Differentiable via the scan.
    """
    op = ctx.cur_op
    in_names = op.inputs.get("inputs", [])
    param_names = op.inputs.get("parameters", [])
    out_names = op.outputs.get("outputs", [])
    ex_states = attrs.get("ex_states", [])
    states = attrs.get("states", [])
    sub = ctx.block.program.block(attrs["sub_block"])
    reverse = bool(attrs.get("reverse", False))

    base = dict(zip(param_names, params or []))
    xs = [jnp.flip(v, axis=0) if reverse else v for v in (seq_ins or [])]

    def f(mems, step_slices):
        env = dict(base)
        env.update(zip(ex_states, mems))
        env.update(zip(in_names, step_slices))
        _trace_sub(ctx, sub, env)
        new_mems = tuple(_match_carry(ref, env[n])
                         for ref, n in zip(mems, states))
        return new_mems, tuple(env[n] for n in out_names)

    init = tuple(jnp.asarray(v) for v in (init_states or []))
    _, stacked = lax.scan(f, init, tuple(xs))
    outs = [jnp.flip(o, axis=0) if reverse else o for o in stacked]
    return tuple(outs), None
