"""The pick counters of a held-expert layer (ops/mla_ops.py
``moe_ffn_held`` "Stats"): their names in the decode lane's two programs
and their booking onto the ``pt_moe_*`` families.  The one place every
model file with held experts (models/glm.py, models/trinity.py) takes
them from.

A model's config needs ``moe_layers`` (the expert layers' indices),
``held_experts`` and ``first_expert``.
"""

from __future__ import annotations

EXPERT_STATS_PREFIX = "@MOESTATS@"
# the decode lane's two programs, each with pick counters of its own
STATS_PROGRAMS = ("decode", "prefill")


def expert_stats_counters(cfg):
    """The ``lane.DeviceCounter`` list of ``cfg``'s expert layers."""
    from paddle_tpu.serving import lane

    return [lane.DeviceCounter(expert_stats_var_name(i, program),
                               cfg.held_experts + 2)
            for i in cfg.moe_layers for program in STATS_PROGRAMS]


def expert_stats_var(cfg, layer, program):
    """The layer's pick counter in the program being built: persistable,
    updated in place, fetched by no step (the engine installs and reads
    it)."""
    from paddle_tpu import fluid

    return fluid.default_main_program().global_block().create_var(
        name=expert_stats_var_name(layer, program),
        shape=[cfg.held_experts + 2], dtype="int32", persistable=True)


def expert_stats_var_name(layer, program):
    """The pick counter of expert layer ``layer`` in ``program`` (one of
    STATS_PROGRAMS): int32 [held_experts + 2], ops/mla_ops.py
    ``moe_ffn_held`` "Stats"."""
    return f"{EXPERT_STATS_PREFIX}l{layer}@{program}"


def _m_moe_picks():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_moe_picks_total",
        "Router picks of valid tokens by where they landed: held (an "
        "expert this chip holds), absent (an expert of another chip), "
        "any (both).  Counted on the device, booked when "
        "DecodeEngine.book_device_counters() is called",
        labels=("engine", "where"))


def _m_moe_expert_tokens():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_moe_expert_tokens_total",
        "Picks each held expert got (its tokens), by layer and expert "
        "id.  Counted on the device, booked with pt_moe_picks_total",
        labels=("engine", "layer", "expert"))


def _m_moe_touched():
    from paddle_tpu import observability as obs

    return obs.counter(
        "pt_moe_experts_touched_total",
        "Held experts that got at least one pick, summed over the "
        "expert layers of every run of the program (decode step / "
        "prefill chunk): the expert weights a run had to read.  Counted "
        "on the device, booked with pt_moe_picks_total",
        labels=("engine", "program"))


def book_expert_stats(cfg, engine, gained):
    """``DecodeLane.book_counters`` of a model with held experts: what the pick counters
    gained (``{var name: int64 [held + 2]}``: picks by held expert,
    picks on absent experts, held experts touched) onto the three
    ``pt_moe_*`` families."""
    picks, experts, touched = (_m_moe_picks(), _m_moe_expert_tokens(),
                               _m_moe_touched())
    for layer in cfg.moe_layers:
        for program in STATS_PROGRAMS:
            g = gained[expert_stats_var_name(layer, program)]
            held, absent = int(g[:-2].sum()), int(g[-2])
            picks.labels(engine=engine, where="held").inc(held)
            picks.labels(engine=engine, where="absent").inc(absent)
            picks.labels(engine=engine, where="any").inc(held + absent)
            touched.labels(engine=engine, program=program).inc(int(g[-1]))
            for e, n in enumerate(g[:-2]):
                if n:
                    experts.labels(
                        engine=engine, layer=str(layer),
                        expert=str(cfg.first_expert + e)).inc(int(n))
