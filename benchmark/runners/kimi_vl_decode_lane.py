"""Runner of the Kimi-VL decode-lane cells: ``decode_lane.py`` (engine,
clients, stamps, window, every number it reports) in a copy of its own,
with what ``glm_decode_lane.py`` and ``trinity_decode_lane.py`` brought
for a model of long prompts and routed experts (the first wave at its
whole outputs, the device counters read at the traced interval's edges,
statistics of every served token's gap as the limits of ``correct``, the
control from one reference pass a precision), and what prompts with
images need.

**Prompts.**  The generator that is there gives each request its sizes,
its order and its token ids; the traffic file's ``images`` lays images
over it (``lay_out``): image rows are ``share_of_prompt`` of the prompt,
in images of the file's sizes in rotation (one that no longer fits the
share is skipped), each followed by ``text_after_image`` tokens; what
text is left is halved into a lead and the question.  An image position
holds the model's placeholder id; a text id that happens to be it is
moved one down.

**Pixels.**  ``bank_per_size`` images a size, normal(0, 1) values from
the run's seed, prepared once in set-up as the lane's encoder takes them
(patches, host work that a client would do as it sends); a request's
k-th image is one of its size's bank, by the request's number.  The
reference is handed the same pixel arrays, as it is handed the same
token ids.

**The work** (traced runs):

    work.mla_decode_bytes_per_decode_step  latent rows the traced decode
                                           steps had to read, from their
                                           own contexts (kimi_work.py)
    work.mla_chunk_flop_per_chunk          head-space attention FLOP of
                                           the positions prefilled in the
                                           traced interval / chunks run
    work.moe_bytes_per_decode_step         experts the traced decode steps
                                           touched x an expert's bytes
    work.vit_attn_flop_per_run             the tower's attention FLOP of
                                           the images encoded in the
                                           traced interval / encoder runs
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from benchmark import generator, harness

glm = harness.load_module("runners", "glm_decode_lane.py")
trinity = harness.load_module("runners", "trinity_decode_lane.py")
base = glm._own_copy("runners", "decode_lane.py")

gap_stats = trinity.gap_stats
RUNS = "pt_decode_encoder_runs_total{bench,"


class Prompt(list):
    """A request's token ids, with its images: ``images`` as the lane's
    encoder takes them, ``pixels`` as the reference does."""

    images = ()
    pixels = ()


class WithImages:
    """The engine as ``decode_lane.Clients`` calls it, each prompt's
    images sent with it."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def submit_request(self, prompt, max_new_tokens):
        return self._engine.submit_request(
            list(prompt), max_new_tokens, images=list(prompt.images))


def grid_of(pixels, config):
    p = config["vision_config"]["patch_size"]
    return pixels[0] // p, pixels[1] // p


def lay_out(length, spec, config):
    """Where a prompt of ``length`` tokens holds what: a list of
    ("text", n) and ("image", size number) parts."""
    sizes = [grid_of(px, config) for px in spec["pixels"]]
    rows = [(g[0] // 2) * (g[1] // 2) for g in sizes]
    budget = int(spec["share_of_prompt"] * length)
    gap, last_min = int(spec["text_after_image"]), int(spec["text_last_min"])
    picked, used, k = [], 0, 0
    while True:
        for _ in range(len(sizes)):      # the next in rotation that fits
            n = rows[k % len(sizes)]
            if (used + n <= budget and used + n + gap * len(picked)
                    + last_min <= length):
                break
            k += 1
        else:
            break
        picked.append(k % len(sizes))
        used += n
        k += 1
    text = length - used - gap * max(len(picked) - 1, 0)
    lead = text // 2 if picked else 0
    parts = [("text", lead)]
    for j, size in enumerate(picked):
        parts.append(("image", size))
        if j + 1 < len(picked):
            parts.append(("text", gap))
    return parts + [("text", text - lead)]


def image_bank(engine, config, mix, seed):
    """Per size, ``bank_per_size`` (pixels, prepared image) from the
    seed."""
    spec = mix["images"]
    prepare = engine.lane.encoder.prepare
    rng = generator.rng_for(seed, 7)
    bank = []
    for h, w in spec["pixels"]:
        images = []
        for _ in range(int(spec["bank_per_size"])):
            pixels = rng.standard_normal((h, w, 3), dtype=np.float32)
            images.append((pixels, prepare(pixels)))
        bank.append(images)
    return bank


def with_images(queues, mix, config, bank):
    """The generator's queues, each prompt laid out with its images."""
    spec = mix["images"]
    hold = int(config["media_placeholder_token_id"])

    def client(c, queue):
        for number, (ids, n_new) in enumerate(queue):
            ids = np.asarray(ids, np.int64)
            ids[ids == hold] = hold - 1
            images, at = [], 0
            for kind, n in lay_out(len(ids), spec, config):
                if kind == "text":
                    at += n
                    continue
                pick = bank[n][(c + 7 * number + len(images))
                               % len(bank[n])]
                rows = pick[1].rows
                ids[at:at + rows] = hold
                at += rows
                images.append(pick)
            prompt = Prompt(ids.tolist())
            prompt.pixels = [px for px, _ in images]
            prompt.images = [im for _, im in images]
            yield prompt, n_new

    return [client(c, q) for c, q in enumerate(queues)]


def build_engine(config, devices, seed):
    """decode_lane.build_engine, the program's parameters listed over its
    whole-sequence program and one encoder."""
    from paddle_tpu import fluid, serving

    t0 = harness.now()
    ref = harness.load_module("reference", config["reference"])
    b = config["builder"]
    model = importlib.import_module(b["module"])
    model_cfg = getattr(model, b["config"])(**b["config_args"])
    lm, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(lm, start), fluid.unique_name.guard():
        getattr(model, b["build"])(model_cfg, is_test=True)
    enc, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(enc, start), fluid.unique_name.guard():
        _, prepare = getattr(model, b["build_encoder"])(
            model_cfg, *model_cfg.image_grids[0], 8)
    want = {p.name: tuple(p.shape) for prog in (lm, enc, prepare)
            for p in prog.global_block().all_parameters()}
    weights = ref.init_weights(config, seed)
    have = {n: tuple(w.shape) for n, w in weights.items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise SystemExit(f"kimi_vl_decode_lane: the program's parameters "
                         f"are not the reference's: {odd}")
    scope = fluid.Scope()
    for name, w in weights.items():
        scope.set(name, w)
    del weights
    t1 = harness.now()
    tpu = devices[0].platform == "tpu"
    place = fluid.TPUPlace(0) if tpu else fluid.CPUPlace()
    e = config["engine"]
    engine = serving.DecodeEngine(
        model_cfg, scope=scope, place=place, pool_slots=e["pool_slots"],
        page_size=e["page_size"], max_len=e["max_len"], name="bench",
        auto_start=False)
    t2 = harness.now()
    warmed = engine.warmup()
    print(f"INFO set-up: weights {t1 - t0:.1f}s, engine built {t2 - t1:.1f}s, "
          f"warm-up of {warmed} executables {harness.now() - t2:.1f}s",
          flush=True)
    return engine, scope


def prefilled(clients):
    """{record index: prompt positions in the pool} of the requests not
    yet done."""
    with clients._lock:
        recs = list(enumerate(clients.records))
    return {i: r["req"].prefilled for i, r in recs
            if r["t_done"] is None and "req" in r}


def encoder_runs(snapshot):
    return {k[len(RUNS):-1]: v for k, v in snapshot.items()
            if k.startswith(RUNS)}


def serve(engine, config, mix, seed, seconds, trace):
    """glm_decode_lane.serve with the prompts' images sent along and, at
    the traced interval's edges, how far every prompt is prefilled and
    how many images of each shape are encoded."""
    t_bank = harness.now()
    bank = image_bank(engine, config, mix, seed)
    print(f"INFO image bank of {sum(map(len, bank))} images prepared in "
          f"{harness.now() - t_bank:.1f}s", flush=True)
    clients = base.Clients(WithImages(engine), with_images(
        glm.whole_first_wave(generator.closed_loop_requests(
            mix, seed, config["vocab_size"]), mix), mix, config, bank))
    clients.start()
    engine.start()
    while not clients.slots_filled.wait(timeout=0.05):
        if clients.errors or not engine.healthy():
            raise SystemExit(f"kimi_vl_decode_lane: first wave failed: "
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
            n0 = harness.counters()
            s0, p0, f0 = engine.stats(), clients.progress(), \
                prefilled(clients)
            time.sleep(float(mix["trace_seconds"]))
            c1 = glm.device_counts(engine)
            n1 = harness.counters()
            s1, p1, f1 = engine.stats(), clients.progress(), \
                prefilled(clients)
        with clients._lock:
            lengths = [len(r["prompt"]) for r in clients.records]
        traced = {"dir": path, "progress": (p0, p1),
                  "steps": s1["steps"] - s0["steps"],
                  "device_counts": harness.delta(c1, c0),
                  "prefilled": (f0, f1), "prompt_lengths": lengths,
                  "chunks": harness.delta(n1, n0).get(
                      "pt_decode_prefill_chunks_total{bench}", 0.0),
                  "encoder_runs": harness.delta(encoder_runs(n1),
                                                encoder_runs(n0))}
    time.sleep(max(0.0, t_open + seconds - time.monotonic()))
    t_end = time.monotonic()
    after = harness.counters()
    stats1 = engine.stats()
    clients.stop()
    print(f"INFO row staging {stats1['image_rows']}", flush=True)
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


def served_logits(ref, params, config, rec, matmul=None):
    import jax.numpy as jnp

    return ref.served_logits(params, dict(config), list(rec["prompt"]),
                             rec["tokens"], matmul or jnp.matmul,
                             images=rec["prompt"].pixels)


def served_gaps(config, seed, sample, matmul=None, per_token=None):
    """glm_decode_lane.served_gaps with each request's images handed to
    the reference."""
    import jax

    ref = harness.load_module("reference", config["reference"])
    with jax.default_matmul_precision("highest"):
        params = ref.init_weights(config, seed)
        out = []
        for rec in sample:
            gaps = glm.token_gaps(
                served_logits(ref, params, config, rec, matmul),
                rec["tokens"])
            out.append(max(gaps))
            if per_token is not None:
                per_token.extend(gaps)
    return out


def control(config, mix, devices, seeds, lowprec, seconds):
    """trinity_decode_lane.control with the images: per seed, over a
    window's sample, every statistic of the sound program's served
    tokens, and of the token that the reference computed in bf16 and in
    fp8 puts first at each position of the same prompts, images and
    served tokens."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", config["reference"])
    n = int(config["correct"]["sample_requests"])
    for seed in seeds:
        engine, scope = build_engine(config, devices, seed)
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
                logits = served_logits(ref, params, config, rec)
                gaps["program"] += glm.token_gaps(logits, rec["tokens"])
                for name, matmul in (("bf16", lowprec.bf16_matmul),
                                     ("control_fp8", lowprec.fp8_matmul)):
                    low = served_logits(ref, params, config, rec, matmul)
                    gaps[name] += glm.token_gaps(logits,
                                                 jnp.argmax(low, axis=1))
            del params
        row = {"seed": seed, "requests": len(sample),
               "contexts": [len(r["prompt"]) + len(r["tokens"])
                            for r in sample],
               "images": [len(r["prompt"].pixels) for r in sample],
               "served_tokens": len(gaps["program"])}
        for name, g in gaps.items():
            row[name] = max(g)
            row.update({f"{name}_{k}": v for k, v in gap_stats(g).items()
                        if k != "max"})
        yield row


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
            spans.append((first, last))
    return spans


base.build_engine = build_engine
base.serve = serve


def run(ctx):
    config, checks = ctx["config"], ctx["checks"]
    work = harness.load_module(config["work"]["module"])
    per_token, contexts = [], []

    def traced_kv_bytes(records, traced, config):
        contexts.extend(trinity.traced_contexts(records, traced, work))
        return work.latent_bytes(config, contexts)

    base.traced_kv_bytes = traced_kv_bytes
    base.served_gaps = lambda *a, **kw: served_gaps(
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
    image = numbers.get("pt_decode_prompt_tokens_total{bench,image}", 0.0)
    text = numbers.get("pt_decode_prompt_tokens_total{bench,text}", 0.0)
    if image + text:
        print(f"INFO prompt positions admitted in the window: "
              f"{image:.0f} image rows, {text:.0f} text tokens, image share "
              f"{image / (image + text):.3f}", flush=True)
    if traced and traced["steps"]:
        steps = traced["steps"]
        counts = traced["device_counts"]
        numbers.update(counts)
        numbers["work.mla_decode_bytes_per_decode_step"] = (
            work.latent_bytes(config, contexts) / steps)
        numbers["work.moe_bytes_per_decode_step"] = (
            counts.get("pt_moe_experts_touched_total{bench,decode}", 0.0)
            / steps * work.expert_bytes(config))
        for calls in ("attn", "grouped"):
            numbers[f"work.{calls}_calls_per_decode_step"] = float(
                config["work"][f"{calls}_calls_per_decode_step"])
        spans = prefilled_spans(traced)
        if traced["chunks"]:
            numbers["work.mla_chunk_flop_per_chunk"] = (
                work.chunk_attention_flop(config, spans) / traced["chunks"])
            numbers["work.attn_calls_per_chunk"] = float(
                config["work"]["attn_calls_per_chunk"])
        runs = {shape: n for shape, n in traced["encoder_runs"].items()
                if n}
        if runs:
            flop = sum(n * work.tower_attention_flop(
                config, tuple(map(int, shape.split("x"))))
                for shape, n in runs.items())
            numbers["work.vit_attn_flop_per_run"] = flop / sum(runs.values())
            numbers["work.vit_attn_calls_per_run"] = float(
                config["work"]["vit_attn_calls_per_run"])
        print(f"INFO traced {steps} decode steps over {len(contexts)} "
              f"contexts, mean {sum(contexts) / max(len(contexts), 1):.0f} "
              f"tokens; {traced['chunks']:.0f} chunks over "
              f"{sum(b - a for a, b in spans)} positions; encoder runs "
              f"{runs}", flush=True)
    return out
