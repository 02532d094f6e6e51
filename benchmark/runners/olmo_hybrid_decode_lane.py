"""Runner of the Olmo-Hybrid decode-lane cells: ``decode_lane.py`` (engine,
clients, stamps, window, every number it reports) in a copy of its own,
with what ``glm_decode_lane.py`` and ``trinity_decode_lane.py`` brought
for a model of long prompts (the first wave at its whole outputs,
statistics of every served token's gap as the limits of ``correct``, the
control from one reference pass a precision) and, at the traced
interval's edges, how far every prompt is prefilled and how many chunks
ran (``kimi_vl_decode_lane.py``'s walk).

``correct`` compares the largest gap a served token reads
(``served_logit_gap``, decode_lane.py) and each statistic of EVERY served
token's gap that the configuration's ``correct`` names
(``served_logit_gap_<statistic>``).  There is no router and no selection,
so no discontinuity: the gaps stay near rounding.

Against the float32 reference the bfloat16 operands of every matmul are
the program's whole gap, and they drown what a recurrent state kept in
bfloat16 would add.  So ``correct`` also holds the same served tokens
against the reference computed in the STATED precision (bfloat16
operands through ``lowprec.bf16_matmul``, float32 state: one reference
pass more, outside the window): each key ``stated_gap_<statistic>`` of
the configuration's ``correct`` is a limit on that statistic of the
served tokens' gaps under THAT reference's best.  Program and reference
then round the same operands, and what is left is the recurrence's own
arithmetic.

The control (``benchmark/control.py``) reads, beside the reference in
bf16 and in fp8, the reference with its recurrent state rounded to
bfloat16 after every token (``bf16_state``): what a program that kept
the state in bfloat16 would serve; and under the stated-precision
reference the program's tokens (``program_stated``) and that
reference's own first tokens with a bfloat16 state
(``bf16_state_stated``).

The work (traced runs; ``olmo_work.py``):

    work.kv_bytes_per_decode_step          K and V bytes the two full
                                           layers had to read a traced
                                           decode step, from the traced
                                           steps' own contexts
    work.gdn_step_bytes_per_decode_step    state bytes the traced decode
                                           steps' ACTIVE rows read and
                                           wrote / steps
    work.gdn_chunk_flop_per_chunk          the rule's FLOP of the positions
                                           prefilled in the traced
                                           interval / chunks run

The pool's counters (``pt_kv_pages_*{bench,state,...}``) are the
program's own and reach the numbers as every counter does.
"""

from __future__ import annotations

import gc
import time

from benchmark import generator, harness

glm = harness.load_module("runners", "glm_decode_lane.py")
trinity = harness.load_module("runners", "trinity_decode_lane.py")
kimi = harness.load_module("runners", "kimi_vl_decode_lane.py")
base = glm._own_copy("runners", "decode_lane.py")

gap_stats = trinity.gap_stats
CHUNKS = "pt_decode_prefill_chunks_total{bench}"


def serve(engine, config, mix, seed, seconds, trace):
    """glm_decode_lane.serve with, at the traced interval's edges, how far
    every prompt is prefilled and how many chunks ran."""
    clients = base.Clients(engine, glm.whole_first_wave(
        generator.closed_loop_requests(mix, seed, config["vocab_size"]),
        mix))
    clients.start()
    engine.start()
    while not clients.slots_filled.wait(timeout=0.05):
        if clients.errors or not engine.healthy():
            raise SystemExit(f"olmo_hybrid_decode_lane: first wave failed: "
                             f"{clients.errors} {engine.stats()}")
    before = harness.counters()
    stats0 = engine.stats()
    t_open = clients.t_filled
    open_perf = harness.now() - (time.monotonic() - t_open)
    traced = None
    if trace:
        time.sleep(max(0.0, t_open + seconds / 2.0 - time.monotonic()))
        path = harness.trace_dir()
        with harness.tracing(path):
            n0 = harness.counters()
            s0, p0, f0 = engine.stats(), clients.progress(), \
                kimi.prefilled(clients)
            time.sleep(float(mix["trace_seconds"]))
            n1 = harness.counters()
            s1, p1, f1 = engine.stats(), clients.progress(), \
                kimi.prefilled(clients)
        with clients._lock:
            lengths = [len(r["prompt"]) for r in clients.records]
        traced = {"dir": path, "progress": (p0, p1),
                  "steps": s1["steps"] - s0["steps"],
                  "prefilled": (f0, f1), "prompt_lengths": lengths,
                  "chunks": n1.get(CHUNKS, 0.0) - n0.get(CHUNKS, 0.0)}
    time.sleep(max(0.0, t_open + seconds - time.monotonic()))
    t_end = time.monotonic()
    after = harness.counters()
    stats1 = engine.stats()
    clients.stop()
    with clients._lock:
        records = list(clients.records)
    for r in records:
        req = r.pop("req")
        r["stamps"] = [t for t in r["stamps"] if t <= t_end]
        r["program_ttft"] = (None if req.t_first is None
                             else req.t_first - req.t_arrival)
    return {"records": records, "t_open": t_open, "t_end": t_end,
            "open_perf": open_perf, "before": before, "after": after,
            "stats": (stats0, stats1), "traced": traced,
            "errors": clients.errors}


def control(config, mix, devices, seeds, lowprec, seconds):
    """trinity_decode_lane.control and one reading more: per seed, over a
    window's sample, every statistic of the sound program's served
    tokens, and of the token that the reference computed in bf16, in fp8
    and with a bfloat16 recurrent state puts first at each position of
    the same prompts and served tokens."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", config["reference"])
    n = int(config["correct"]["sample_requests"])
    for seed in seeds:
        engine, scope = base.build_engine(config, devices, seed)
        try:
            w = serve(engine, config, mix, seed, seconds, False)
        finally:
            engine.close()
        del engine, scope
        gc.collect()
        finished = [r for r in w["records"] if r["tokens"] is not None
                    and w["t_open"] <= r["t_done"] <= w["t_end"]]
        sample = base.pick_sample(finished, seed, n)
        gaps = {"program": [], "bf16": [], "control_fp8": [],
                "bf16_state": [], "program_stated": [],
                "bf16_state_stated": []}
        with jax.default_matmul_precision("highest"):
            params = ref.init_weights(config, seed)
            for rec in sample:
                logits = ref.served_logits(params, config, rec["prompt"],
                                           rec["tokens"])
                gaps["program"] += glm.token_gaps(logits, rec["tokens"])
                firsts = {}
                for name, kw in (
                        ("bf16", {"matmul": lowprec.bf16_matmul}),
                        ("control_fp8", {"matmul": lowprec.fp8_matmul}),
                        ("bf16_state", {"state_dtype": jnp.bfloat16}),
                        ("stated_bf16_state", {
                            "matmul": lowprec.bf16_matmul,
                            "state_dtype": jnp.bfloat16})):
                    firsts[name] = ref.served_logits(
                        params, config, rec["prompt"], rec["tokens"], **kw)
                    if name != "stated_bf16_state":
                        gaps[name] += glm.token_gaps(
                            logits, jnp.argmax(firsts[name], axis=1))
                # under the reference in the STATED precision (bf16
                # operands, float32 state): the program's tokens, and the
                # first tokens of the same reference with a bf16 state
                stated = firsts["bf16"]
                gaps["program_stated"] += glm.token_gaps(stated,
                                                         rec["tokens"])
                gaps["bf16_state_stated"] += glm.token_gaps(
                    stated, jnp.argmax(firsts["stated_bf16_state"], axis=1))
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


base.serve = serve


def run(ctx):
    config, checks = ctx["config"], ctx["checks"]
    work = harness.load_module(config["work"]["module"])
    per_token, contexts, samples = [], [], []

    def traced_kv_bytes(records, traced, config):
        contexts.extend(trinity.traced_contexts(records, traced, work))
        return work.kv_bytes(config, contexts)

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
    print(f"INFO the pool's counters over the window "
          f"{ {k: v for k, v in numbers.items() if k.startswith('pt_kv_pages_')} }",
          flush=True)
    if traced and traced["steps"]:
        steps = traced["steps"]
        numbers["work.gdn_step_bytes_per_decode_step"] = (
            len(contexts) * work.state_bytes_per_row(config) / steps)
        numbers["work.gdn_calls_per_decode_step"] = float(
            config["work"]["gdn_calls_per_decode_step"])
        spans = kimi.prefilled_spans(traced)
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
              f"{traced['chunks']:.0f} chunks over {positions} positions",
              flush=True)
    return out
