"""Runner of the Qwen3-Next decode-lane cells: ``decode_lane.py`` (engine,
clients, stamps, window, every number it reports) in a copy of its own,
with what the earlier runners brought for a model of long prompts, held
experts and a state a sequence: the first wave at its whole outputs, the
device counters read at the traced interval's edges and how far every
prompt is prefilled there (``mimo_decode_lane.serve``), statistics of
every served token's gap as the limits of ``correct``
(``trinity_decode_lane.gap_stats``), the same served tokens held against
the reference in the STATED precision (``stated_gap_<statistic>``, as
``olmo_hybrid_decode_lane.py``: what a recurrent state kept in bfloat16
would add is drowned by the operands' rounding under the float32
reference), and the control with a bfloat16 state
(``olmo_hybrid_decode_lane.control``, whose readings come from the
configuration's reference by name).

The work (traced runs; ``qwen3_next_work.py``; a context token is 2048 B
a full layer, an expert 6.29 MB, a state 2 097 152 B a layer):

    work.full_attn_bytes_per_decode_step   K and V bytes the two full
                                           layers had to read a traced
                                           decode step, from the traced
                                           steps' own contexts
    work.moe_bytes_per_decode_step         held experts the traced decode
                                           steps touched x an expert's
                                           bytes
    work.held_expert_reads_possible        held experts x expert layers x
                                           decode steps traced
    work.gdn_step_bytes_per_decode_step    state bytes the traced decode
                                           steps' ACTIVE rows read and
                                           wrote / steps
    work.gdn_chunk_flop_per_chunk          the rule's FLOP of the positions
                                           prefilled in the traced
                                           interval / chunks run

``work.kv_bytes_per_decode_step`` (decode_lane.py) is here the full
layers' bytes too.  The pool's counters
(``pt_kv_pages_*{bench,full|state,...}``) are the program's own and
reach the numbers as every counter does.
"""

from __future__ import annotations

from benchmark import harness

glm = harness.load_module("runners", "glm_decode_lane.py")
trinity = harness.load_module("runners", "trinity_decode_lane.py")
mimo = harness.load_module("runners", "mimo_decode_lane.py")
olmo = harness.load_module("runners", "olmo_hybrid_decode_lane.py")
base = glm._own_copy("runners", "decode_lane.py")

gap_stats = trinity.gap_stats
# one reference pass a precision and a state dtype over a window served
# without a trace: the configuration names the reference
control = olmo.control

base.serve = mimo.serve


def run(ctx):
    config, checks = ctx["config"], ctx["checks"]
    work = harness.load_module(config["work"]["module"])
    per_token, contexts, samples = [], [], []

    def traced_kv_bytes(records, traced, config):
        contexts.extend(trinity.traced_contexts(records, traced, work))
        return work.full_attn_bytes(config, contexts)

    def served_gaps(config, seed, sample):
        samples.append(sample)
        return glm.served_gaps(config, seed, sample, per_token=per_token)

    base.traced_kv_bytes = traced_kv_bytes
    base.served_gaps = served_gaps
    out = base.run(ctx)
    stats = gap_stats(per_token)
    print(f"INFO served-token gaps over {len(per_token)} tokens: {stats}",
          flush=True)
    for name, value in stats.items():
        limit = config["correct"].get(f"served_logit_gap_{name}")
        if limit is not None:
            checks.limit(f"served_logit_gap_{name}", value, limit)
    stated_limits = {k: v for k, v in config["correct"].items()
                     if k.startswith("stated_gap_")}
    if samples and stated_limits:
        t_ref = harness.now()
        stated = []
        glm.served_gaps(
            config, ctx["seed"], samples[0],
            harness.load_module("reference", "lowprec.py").bf16_matmul,
            per_token=stated)
        stats = gap_stats(stated)
        print(f"INFO served-token gaps under the reference in the stated "
              f"precision, {harness.now() - t_ref:.1f}s: {stats}", flush=True)
        for key, limit in stated_limits.items():
            checks.limit(key, stats[key[len("stated_gap_"):]], limit)
    numbers, traced = out["numbers"], out.get("trace")
    evicted = numbers.get("pt_decode_evictions_total{bench}", 0.0)
    checks.equal("evictions_in_window", evicted, 0.0)
    print(f"INFO the pool's counters over the window "
          f"{ {k: v for k, v in numbers.items() if k.startswith('pt_kv_pages_')} }",
          flush=True)
    if traced and traced["steps"]:
        steps = traced["steps"]
        counts = traced["device_counts"]
        numbers.update(counts)
        touched = counts.get("pt_moe_experts_touched_total{bench,decode}",
                             0.0)
        numbers["work.full_attn_bytes_per_decode_step"] = (
            work.full_attn_bytes(config, contexts) / steps)
        numbers["work.moe_bytes_per_decode_step"] = (
            touched / steps * work.expert_bytes(config))
        numbers["work.held_expert_reads_possible"] = float(
            work.held_expert_reads_possible(config, steps))
        numbers["work.gdn_step_bytes_per_decode_step"] = (
            len(contexts) * work.state_bytes_per_row(config) / steps)
        for calls in ("full_attn", "grouped", "gdn"):
            numbers[f"work.{calls}_calls_per_decode_step"] = float(
                config["work"][f"{calls}_calls_per_decode_step"])
        spans = mimo.prefilled_spans(traced)
        positions = sum(b - a for a, b in spans)
        if traced["chunks"]:
            numbers["work.gdn_chunk_flop_per_chunk"] = (
                positions * work.rule_flop_per_token(config)
                / traced["chunks"])
            numbers["work.gdn_calls_per_chunk"] = float(
                config["work"]["gdn_calls_per_chunk"])
        print(f"INFO traced {steps} decode steps over {len(contexts)} "
              f"active rows, mean context "
              f"{sum(contexts) / max(len(contexts), 1):.0f} tokens; "
              f"{touched / max(steps, 1):.1f} held experts touched a step "
              f"of {work.held_expert_reads_possible(config, 1)}; "
              f"{traced['chunks']:.0f} chunks over {positions} positions",
              flush=True)
    return out
