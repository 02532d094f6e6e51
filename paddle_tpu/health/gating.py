"""The one in-graph skip mechanism, shared by every execution lane.

`wrap_body` wraps a traced step body (the `(donated, readonly, feeds,
step) -> (fetches, out_writes)` convention every compiled block uses) so
that when the program's ``@HEALTH@found_inf`` scalar fires, every
in-place state write — parameters, optimizer moments, BN running stats:
exactly the donated buffers — reverts to its pre-step value.  This is a
TRUE step skip (adaptive moments do not decay toward zero, the
documented deviation of the reference's grad-zeroing gate vanishes),
selected per step by an on-device `where`, so it works inside
`run_steps` chains and costs nothing when the step is healthy.

Health-owned state (the ``@HEALTH@`` vars: loss scale, good/bad-step
counters, the cumulative bad-step total, fault-injection countdowns) is
exempt — a bad step must still halve the loss scale and advance the
counters, which is the whole point of dynamic loss scaling.

Applied OUTERMOST in each lane (after the hybrid runner's ZeRO-gather /
fused-gather wrappers, inside any fori_loop chain wrapper), so a
parameter whose write was replaced by a gathered quantized image is
gated too.  Programs without a health plan get the body back untouched.
"""

from __future__ import annotations

__all__ = ["wrap_body"]


def wrap_body(program, body):
    """Wrap `body` with the found_inf state gate; identity when the
    program carries no health plan."""
    plan = getattr(program, "_health_plan", None)
    if not plan or not plan.get("gate"):
        return body
    found_var = plan["found_var"]
    from .transpile import HEALTH_PREFIX

    def gated(donated, readonly, feeds, step):
        import jax.numpy as jnp

        fetches, out_writes = body(donated, readonly, feeds, step)
        if found_var not in out_writes:
            # forward-only fetch pruned the optimizer leg (and with it
            # the check op): nothing to gate
            return fetches, out_writes
        found = jnp.reshape(
            jnp.asarray(out_writes[found_var]).astype(jnp.float32),
            ()) > 0
        gated_writes = {}
        for name, new in out_writes.items():
            old = donated.get(name)
            if old is None or name.startswith(HEALTH_PREFIX):
                gated_writes[name] = new
                continue
            try:
                ov, nv = jnp.asarray(old), jnp.asarray(new)
            except TypeError:  # structured value (tensor array): pass
                gated_writes[name] = new
                continue
            if ov.shape != nv.shape or ov.dtype != nv.dtype:
                # not an in-place state update (shape/dtype changed):
                # reverting would break the write-back contract
                gated_writes[name] = new
                continue
            gated_writes[name] = jnp.where(found, ov, nv)
        return fetches, gated_writes

    # the executable is named after the function jax.jit is handed: keep
    # the body's (BlockPlan.make_body: train_step, decode_step, ...)
    gated.__name__ = gated.__qualname__ = getattr(body, "__name__", "gated")
    return gated
