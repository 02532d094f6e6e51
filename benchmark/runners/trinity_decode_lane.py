"""Runner of the Trinity decode-lane cells: ``decode_lane.py`` (engine,
clients, stamps, window, every number it reports) in a copy of its own,
with what ``glm_decode_lane.py`` brought for a model of long prompts and
held experts — the first wave at its whole outputs, the device counters
read at the traced interval's edges, every served token's gap, the
control from one reference pass a precision — and this configuration's
own limits and work.

``correct`` compares, beside the largest gap a served token reads
(``served_logit_gap``, decode_lane.py), statistics of EVERY served
token's gap (``gap_stats``): each key ``served_logit_gap_<statistic>`` of
the configuration's ``correct`` is a limit on that statistic.  The
largest gap is a flipped pick of the router (a whole expert changes
hands: ~1 for the sound program and for a lower precision alike); how
MANY tokens lie under the reference's best, and by how much on average,
is what separates a precision (PERF.md section 2).

The work:

    work.full_attn_bytes_per_decode_step    K and V bytes the full layers
    work.window_attn_bytes_per_decode_step  and the sliding layers had to
                                            read a traced decode step, from
                                            the traced steps' own contexts
                                            (``trinity_work.py``: the whole
                                            context; its last
                                            ``sliding_window`` tokens)
    work.moe_bytes_per_decode_step          held experts the traced decode
                                            steps touched x an expert's
                                            bytes

``work.kv_bytes_per_decode_step`` (decode_lane.py) is here the full
layers' bytes too.  The pool's page counters (``pt_kv_pages_*``) are the
program's own and reach the numbers as every counter does.
"""

from __future__ import annotations

import gc
import statistics

from benchmark import harness

glm = harness.load_module("runners", "glm_decode_lane.py")
base = glm._own_copy("runners", "decode_lane.py")
base.serve = glm.serve

# a served token counts as "under the best" from this gap on: float32
# sums in another order move equal logits by less
UNDER = 1e-3


def gap_stats(gaps):
    """Statistics of the served tokens' gaps (how far each token's logit
    lies under the reference's best at its position)."""
    if not gaps:
        return {}
    return {"max": max(gaps), "median": statistics.median(gaps),
            "mean": statistics.fmean(gaps),
            "p90": harness.percentile(gaps, 90),
            "p99": harness.percentile(gaps, 99),
            "under_share": sum(g > UNDER for g in gaps) / len(gaps)}


def control(config, mix, devices, seeds, lowprec, seconds):
    """glm_decode_lane.control with every statistic of ``gap_stats``: per
    seed, over a window's sample, the sound program's served tokens, and
    the token that the reference computed in bf16 and in fp8 puts first
    at each position of the same prompts and served tokens."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", config["reference"])
    n = int(config["correct"]["sample_requests"])
    for seed in seeds:
        engine, scope = base.build_engine(config, devices, seed)
        try:
            w = glm.serve(engine, config, mix, seed, seconds, False)
        finally:
            engine.close()
        del engine, scope
        gc.collect()
        finished = [r for r in w["records"] if r["tokens"] is not None
                    and w["t_open"] <= r["t_done"] <= w["t_end"]]
        sample = base.pick_sample(finished, seed, n)
        gaps = {"program": [], "bf16": [], "control_fp8": []}
        with jax.default_matmul_precision("highest"):
            params = ref.init_weights(config, seed)
            for rec in sample:
                logits = ref.served_logits(params, config, rec["prompt"],
                                           rec["tokens"])
                gaps["program"] += glm.token_gaps(logits, rec["tokens"])
                for name, matmul in (("bf16", lowprec.bf16_matmul),
                                     ("control_fp8", lowprec.fp8_matmul)):
                    low = ref.served_logits(params, config, rec["prompt"],
                                            rec["tokens"], matmul)
                    gaps[name] += glm.token_gaps(logits,
                                                 jnp.argmax(low, axis=1))
            del params
        row = {"seed": seed, "requests": len(sample),
               "contexts": [len(r["prompt"]) + len(r["tokens"])
                            for r in sample],
               "served_tokens": len(gaps["program"])}
        for name, g in gaps.items():
            row[name] = max(g)
            row.update({f"{name}_{k}": v for k, v in gap_stats(g).items()
                        if k != "max"})
        yield row


def traced_contexts(records, traced, work):
    """The context of every token a decode step produced between the two
    progress snapshots (decode_lane.traced_kv_bytes's walk)."""
    p0, p1 = traced["progress"]
    out = []
    for i, rec in enumerate(records):
        if i in p1:
            last = p1[i]
        elif i in p0 and rec["tokens"] is not None:
            last = len(rec["tokens"])  # finished inside the interval
        else:
            continue
        out += work.decode_contexts(len(rec["prompt"]), p0.get(i, 0), last)
    return out


def run(ctx):
    config, checks = ctx["config"], ctx["checks"]
    work = harness.load_module(config["work"]["module"])
    per_token, contexts = [], []

    def traced_kv_bytes(records, traced, config):
        contexts.extend(traced_contexts(records, traced, work))
        return work.full_attn_bytes(config, contexts)

    base.traced_kv_bytes = traced_kv_bytes
    base.served_gaps = lambda *a, **kw: glm.served_gaps(
        *a, per_token=per_token, **kw)
    out = base.run(ctx)
    stats = gap_stats(per_token)
    print(f"INFO served-token gaps over {len(per_token)} tokens: {stats}",
          flush=True)
    for name, value in stats.items():
        limit = config["correct"].get(f"served_logit_gap_{name}")
        if limit is not None:
            checks.limit(f"served_logit_gap_{name}", value, limit)
    numbers, traced = out["numbers"], out.get("trace")
    if traced and traced["steps"]:
        steps = traced["steps"]
        counts = traced["device_counts"]
        numbers.update(counts)
        numbers["work.full_attn_bytes_per_decode_step"] = (
            work.full_attn_bytes(config, contexts) / steps)
        numbers["work.window_attn_bytes_per_decode_step"] = (
            work.window_attn_bytes(config, contexts) / steps)
        numbers["work.moe_bytes_per_decode_step"] = (
            counts.get("pt_moe_experts_touched_total{bench,decode}", 0.0)
            / steps * work.expert_bytes(config))
        for calls in ("full_attn", "window_attn", "grouped"):
            numbers[f"work.{calls}_calls_per_decode_step"] = float(
                config["work"][f"{calls}_calls_per_decode_step"])
        print(f"INFO traced {steps} decode steps over {len(contexts)} "
              f"contexts, mean {sum(contexts) / max(len(contexts), 1):.0f} "
              f"tokens; the pool's page counters over the window "
              f"{ {k: v for k, v in numbers.items() if k.startswith('pt_kv_pages_')} }",
              flush=True)
    return out
