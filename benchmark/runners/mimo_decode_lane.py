"""Runner of the MiMo-V2 decode-lane cells: ``decode_lane.py`` (engine,
clients, stamps, window, every number it reports) in a copy of its own,
with what ``trinity_decode_lane.py`` brought for a model of window and
full layers and held experts — the first wave at its whole outputs, the
device counters read at the traced interval's edges, every served
token's gap under the limits ``served_logit_gap_<statistic>``, the
control from one reference pass a precision — and, as
``kimi_vl_decode_lane.py`` reads them, how far every prompt is prefilled
at the traced interval's two edges, for the chunk's attention work.

The work (``mimo_work.py``; a full layer's token leaves 2560 B, a window
layer's 5120 B):

    work.full_attn_bytes_per_decode_step    K and V bytes the two full
    work.window_attn_bytes_per_decode_step  layers and the five window
                                            layers had to read a traced
                                            decode step, from the traced
                                            steps' own contexts (the whole
                                            context; its last 128 tokens)
    work.asym_chunk_flop_per_chunk          attention FLOP of the
                                            positions prefilled in the
                                            traced interval, both kinds
                                            of layer / chunks run
    work.moe_bytes_per_decode_step          held experts the traced decode
                                            steps touched x an expert's
                                            bytes

``work.kv_bytes_per_decode_step`` (decode_lane.py) is here the full
layers' bytes too.  The pool's page counters (``pt_kv_pages_*``) are the
program's own and reach the numbers as every counter does.
"""

from __future__ import annotations

import time

from benchmark import generator, harness

glm = harness.load_module("runners", "glm_decode_lane.py")
trinity = harness.load_module("runners", "trinity_decode_lane.py")
base = glm._own_copy("runners", "decode_lane.py")

gap_stats = trinity.gap_stats
CHUNKS = "pt_decode_prefill_chunks_total{bench}"


def prefilled(clients):
    """{record index: prompt positions in the pool} of the requests not
    yet done."""
    with clients._lock:
        recs = list(enumerate(clients.records))
    return {i: r["req"].prefilled for i, r in recs
            if r["t_done"] is None and "req" in r}


def serve(engine, config, mix, seed, seconds, trace):
    """glm_decode_lane.serve with, at the traced interval's edges, how
    far every prompt is prefilled and how many chunks have run."""
    clients = base.Clients(engine, glm.whole_first_wave(
        generator.closed_loop_requests(mix, seed, config["vocab_size"]),
        mix))
    clients.start()
    engine.start()
    while not clients.slots_filled.wait(timeout=0.05):
        if clients.errors or not engine.healthy():
            raise SystemExit(f"mimo_decode_lane: first wave failed: "
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
            c0 = glm.device_counts(engine)
            n0 = harness.counters().get(CHUNKS, 0.0)
            s0, p0, f0 = (engine.stats(), clients.progress(),
                          prefilled(clients))
            time.sleep(float(mix["trace_seconds"]))
            c1 = glm.device_counts(engine)
            n1 = harness.counters().get(CHUNKS, 0.0)
            s1, p1, f1 = (engine.stats(), clients.progress(),
                          prefilled(clients))
        with clients._lock:
            lengths = [len(r["prompt"]) for r in clients.records]
        traced = {"dir": path, "progress": (p0, p1),
                  "steps": s1["steps"] - s0["steps"],
                  "device_counts": harness.delta(c1, c0),
                  "prefilled": (f0, f1), "prompt_lengths": lengths,
                  "chunks": n1 - n0}
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


def prefilled_spans(traced):
    """[(first, last)] prompt positions each request had prefilled
    between the traced interval's two edges."""
    f0, f1 = traced["prefilled"]
    lengths = traced["prompt_lengths"]
    spans = []
    for i in set(f0) | set(f1):
        first = f0.get(i, 0)
        # gone by the second edge: it finished, so its prompt was whole
        last = f1.get(i, lengths[i] if i < len(lengths) else first)
        if last > first:
            spans.append((first, min(last, lengths[i])))
    return spans


# one reference pass a precision over a window served without a trace:
# there this runner's serve is glm_decode_lane's
control = trinity.control


base.serve = serve


def run(ctx):
    config, checks = ctx["config"], ctx["checks"]
    work = harness.load_module(config["work"]["module"])
    per_token, contexts = [], []

    def traced_kv_bytes(records, traced, config):
        contexts.extend(trinity.traced_contexts(records, traced, work))
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
    evicted = numbers.get("pt_decode_evictions_total{bench}", 0.0)
    checks.equal("evictions_in_window", evicted, 0.0)
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
        spans = prefilled_spans(traced)
        if traced["chunks"]:
            numbers["work.asym_chunk_flop_per_chunk"] = (
                work.chunk_attention_flop(config, spans) / traced["chunks"])
            numbers["work.attn_calls_per_chunk"] = float(
                config["work"]["attn_calls_per_chunk"])
        print(f"INFO traced {steps} decode steps over {len(contexts)} "
              f"contexts, mean {sum(contexts) / max(len(contexts), 1):.0f} "
              f"tokens; {traced['chunks']:.0f} chunks over "
              f"{sum(b - a for a, b in spans)} positions; the pool's page "
              f"counters over the window "
              f"{ {k: v for k, v in numbers.items() if k.startswith('pt_kv_pages_')} }",
              flush=True)
    return out
