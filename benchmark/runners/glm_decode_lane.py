"""Runner of the GLM-5 decode-lane cells: ``decode_lane.py`` (engine,
clients, stamps, window, every number it reports) with what this
configuration needs replaced, in a copy of that module loaded for this
runner alone:

``serve``  the same first wave and window, with two differences.  The
  first wave keeps its WHOLE outputs: at one 512-token chunk a turn a
  prompt of 4k-32k tokens takes 8-64 turns to prefill, so the clients
  leave step by themselves, and the window opens (at the first wave's
  last first token) on the state the closed loop keeps returning to:
  the last few requests prefilled still decoding, each at its own
  phase, the other clients' next requests queued.  With outputs cut to
  a share, as the generator cuts them for short prompts, all but one
  had finished by then and the window was a ramp (PERF.md section 5).
  And in a traced run the engine's device counters are read at the two
  edges of the traced interval (``DecodeEngine.book_device_counters``:
  a read that waits for the step under way, so nothing else asks for
  it), which gives the per-layer metrics exact counts:

    work.moe_bytes_per_decode_step   held experts the traced decode
                                     steps touched x an expert's bytes
    pt_moe_picks_total{bench,*}      picks of the traced interval

``served_gaps``  also keeps every served token's gap, for a second
  limit: the MEDIAN gap a served token.  A flipped pick of the router
  or of the selection moves single logits by about one (the largest
  gap reads 0.4-1.1 for the sound program), but the token the program
  serves is the reference's first at most positions, so its median is
  0; the reference in fp8 puts another token first more often than not.

``control``  the same readings from one reference pass a precision (the
  reference takes about a minute a pass at 33k tokens).

``work.kv_bytes_per_decode_step`` (decode_lane.py: bytes per context
token x the traced steps' context tokens) is here the INDEXER cache's
bytes: the configuration's ``work.kv_bytes_per_context_token`` names
``glm_work.index_bytes_per_context_token``.
"""

from __future__ import annotations

import gc
import importlib.util
import os
import statistics
import time

from benchmark import generator, harness


def _own_copy(*parts):
    """A module of the benchmark loaded apart from ``harness.load_module``'s
    shared one, so that what this runner replaces in it is replaced for
    this runner alone."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_runners_glm_decode_lane_base",
        os.path.join(harness.HERE, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _own_copy("runners", "decode_lane.py")

MOE_FAMILIES = ("pt_moe_picks_total{", "pt_moe_experts_touched_total{")


def whole_first_wave(queues, mix):
    """The generator's queues with each client's first request at its
    whole output length."""
    sizes = generator.request_sizes(mix)

    def client(c, queue):
        prompt, _ = next(queue)
        yield prompt, int(sizes[c % len(sizes)][1])
        yield from queue

    return [client(c, q) for c, q in enumerate(queues)]


def device_counts(engine):
    """The expert layers' counts so far, read off the device now."""
    engine.book_device_counters()
    return {k: v for k, v in harness.counters().items()
            if k.startswith(MOE_FAMILIES)}


def serve(engine, config, mix, seed, seconds, trace):
    """decode_lane.serve with the first wave's outputs whole and the
    device counters read at the traced interval's edges."""
    clients = base.Clients(engine, whole_first_wave(
        generator.closed_loop_requests(mix, seed, config["vocab_size"]),
        mix))
    clients.start()
    engine.start()
    while not clients.slots_filled.wait(timeout=0.05):
        if clients.errors or not engine.healthy():
            raise SystemExit(f"glm_decode_lane: first wave failed: "
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
            c0 = device_counts(engine)
            s0, p0 = engine.stats(), clients.progress()
            time.sleep(float(mix["trace_seconds"]))
            c1 = device_counts(engine)
            s1, p1 = engine.stats(), clients.progress()
        traced = {"dir": path, "progress": (p0, p1),
                  "steps": s1["steps"] - s0["steps"],
                  "device_counts": harness.delta(c1, c0)}
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


def token_gaps(logits, tokens):
    """Per position, how far ``tokens``' logit lies under the best."""
    import jax.numpy as jnp

    picked = jnp.take_along_axis(
        logits, jnp.asarray(tokens, jnp.int32)[:, None], axis=1)[:, 0]
    return [float(g) for g in jnp.max(logits, axis=1) - picked]


def served_gaps(config, seed, sample, matmul=None, per_token=None):
    """decode_lane.served_gaps; every served token's gap is appended to
    ``per_token``."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", config["reference"])
    with jax.default_matmul_precision("highest"):
        params = ref.init_weights(config, seed)
        out = []
        for rec in sample:
            gaps = token_gaps(
                ref.served_logits(params, dict(config), rec["prompt"],
                                  rec["tokens"], matmul or jnp.matmul),
                rec["tokens"])
            out.append(max(gaps))
            if per_token is not None:
                per_token.extend(gaps)
    return out


def control(config, mix, devices, seeds, lowprec, seconds):
    """Per seed, over a window's sample: the sound program's widest and
    median served-token gap, and the same two for the token that the
    reference computed in bf16 and in fp8 puts first at each position
    of the same prompts and served tokens."""
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
        gaps = {"program": [], "bf16": [], "control_fp8": []}
        with jax.default_matmul_precision("highest"):
            params = ref.init_weights(config, seed)
            for rec in sample:
                logits = ref.served_logits(params, config, rec["prompt"],
                                           rec["tokens"])
                gaps["program"] += token_gaps(logits, rec["tokens"])
                for name, matmul in (("bf16", lowprec.bf16_matmul),
                                     ("control_fp8", lowprec.fp8_matmul)):
                    low = ref.served_logits(params, config, rec["prompt"],
                                            rec["tokens"], matmul)
                    gaps[name] += token_gaps(logits,
                                             jnp.argmax(low, axis=1))
            del params
        row = {"seed": seed, "requests": len(sample),
               "served_tokens": len(gaps["program"])}
        for name, g in gaps.items():
            row[name] = max(g)
            row[name + "_median"] = statistics.median(g)
        yield row


base.serve = serve


def run(ctx):
    config, checks = ctx["config"], ctx["checks"]
    per_token = []
    base.served_gaps = lambda *a, **kw: served_gaps(*a, per_token=per_token,
                                                    **kw)
    out = base.run(ctx)
    if per_token:
        checks.limit("served_logit_gap_median",
                     statistics.median(per_token),
                     config["correct"]["served_logit_gap_median"])
    numbers, traced = out["numbers"], out.get("trace")
    steps = numbers.get("engine.steps")
    if traced and steps and traced["steps"]:
        work = harness.load_module(config["work"]["module"])
        active = numbers["engine.decode_tokens"] / steps
        numbers["work.active_sequences_per_decode_step"] = active
        numbers["work.sparse_attn_bytes_per_decode_step"] = (
            active * work.selected_latent_bytes_per_query(config))
        counts = traced["device_counts"]
        numbers.update(counts)
        numbers["work.moe_bytes_per_decode_step"] = (
            counts.get("pt_moe_experts_touched_total{bench,decode}", 0.0)
            / traced["steps"] * work.expert_bytes(config))
        numbers["work.grouped_calls_per_decode_step"] = float(
            config["work"]["grouped_calls_per_decode_step"])
    return out
