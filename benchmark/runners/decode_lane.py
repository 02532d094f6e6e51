"""Runner of decode-lane cells: a model behind ``serving.DecodeEngine``
under closed-loop clients, then `correct` from a sample of the requests the
window finished.

The engine is the program's; the clients, the clocks and the counting are
the benchmark's.  The program has no token callback, so every request's
``generated`` list is replaced, as it is submitted, by a list that stamps
each token with this clock as the scheduler appends it.  The window and
every end-to-end metric are made of those stamps: the window opens at the
token that fills the last slot (the first wave's last first token) and
closes at the last token inside ``--seconds``, so both edges are token
events and the rate is whole scheduler turns over their time.
"""

from __future__ import annotations

import gc
import importlib
import threading
import time

from benchmark import flops, generator, harness


class Stamped(list):
    """A request's generated tokens; ``stamps[k]`` is when token k was
    appended (the scheduler appends right after the step's blocking
    fetch)."""

    def __init__(self, on_first):
        super().__init__()
        self.stamps = []
        self._on_first = on_first

    def append(self, token):
        self.stamps.append(time.monotonic())
        super().append(token)
        if len(self.stamps) == 1:
            self._on_first(self.stamps[0])


class Clients:
    """Closed loop: every client sends its next request from the callback
    that completes its last one.  ``records`` holds one dict per request
    sent; the first ``len(queues)`` of them are the first wave."""

    def __init__(self, engine, queues):
        self.engine = engine
        self.queues = queues
        self.records = []
        self.errors = []
        self.open = True
        self.slots_filled = threading.Event()
        self.t_filled = None
        self._first_wave_waiting = len(queues)
        self._lock = threading.Lock()

    def start(self):
        for c in range(len(self.queues)):
            self._send(c)

    def _send(self, c):
        prompt, n_new = next(self.queues[c])
        first_wave = len(self.records) < len(self.queues)
        rec = {"client": c, "prompt": prompt, "max_new": n_new,
               "first_wave": first_wave, "t_submit": time.monotonic(),
               "t_done": None, "tokens": None, "error": None}
        req = self.engine.submit_request(prompt, n_new)
        if req.generated:  # no prefill can have finished yet
            raise RuntimeError("a request had tokens as it was submitted")
        req.generated = Stamped(self._first_wave_token if first_wave
                                else lambda t: None)
        rec["req"], rec["stamps"] = req, req.generated.stamps
        with self._lock:
            self.records.append(rec)
        req.future.add_done_callback(
            lambda fut, rec=rec: self._done(fut, rec))

    def _first_wave_token(self, t):
        self._first_wave_waiting -= 1  # scheduler thread only
        if not self._first_wave_waiting:
            self.t_filled = t
            self.slots_filled.set()

    def _done(self, fut, rec):
        rec["t_done"] = time.monotonic()
        try:
            exc = fut.exception()
            if exc is not None:
                rec["error"] = repr(exc)
            else:
                rec["tokens"] = fut.result()
            if self.open:
                self._send(rec["client"])
        except BaseException as e:  # a callback's error is otherwise lost
            self.errors.append(repr(e))

    def progress(self):
        """{record index: tokens generated so far} of the requests not yet
        done, and of none else."""
        with self._lock:
            recs = list(enumerate(self.records))
        return {i: len(r["stamps"]) for i, r in recs if r["t_done"] is None}

    def stop(self):
        self.open = False


def build_engine(config, devices, seed):
    """Scope with the seed's weights, engine warmed up, not yet started."""
    from paddle_tpu import fluid, serving

    t0 = harness.now()

    ref = harness.load_module("reference", config["reference"])
    b = config["builder"]
    model = importlib.import_module(b["module"])
    model_cfg = getattr(model, b["config"])(**b["config_args"])
    lm, lm_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(lm, lm_start), fluid.unique_name.guard():
        getattr(model, b["build"])(model_cfg, is_test=True)
    want = {p.name: tuple(p.shape) for p in
            lm.global_block().all_parameters()}
    weights = ref.init_weights(config, seed)
    have = {n: tuple(w.shape) for n, w in weights.items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise SystemExit(f"decode_lane: the program's parameters are not "
                         f"the reference's: {odd}")
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
    engine.warmup()
    print(f"INFO set-up: weights {t1 - t0:.1f}s, engine built {t2 - t1:.1f}s, "
          f"warm-up of both executables {harness.now() - t2:.1f}s", flush=True)
    return engine, scope


def serve(engine, config, mix, seed, seconds, trace):
    """First wave, then the window.  Returns what the window measured."""
    queues = generator.closed_loop_requests(mix, seed, config["vocab_size"])
    clients = Clients(engine, queues)
    # the first wave is queued before the scheduler runs, the later
    # requests from the scheduler's own thread: no request can get a token
    # before its list is the stamping one
    clients.start()
    engine.start()

    # The window opens when every client's first request has its first
    # token: pool and slots are full.  Requests, their order and the engine
    # are deterministic, so every run's window starts from the same state.
    while not clients.slots_filled.wait(timeout=0.05):
        if clients.errors or not engine.healthy():
            raise SystemExit(f"decode_lane: first wave failed: "
                             f"{clients.errors} {engine.stats()}")
    before = harness.counters()
    stats0 = engine.stats()
    t_open = clients.t_filled
    open_perf = harness.now() - (time.monotonic() - t_open)
    traced = None
    if trace:
        # the traced run measures half a window, then the traced interval
        time.sleep(max(0.0, t_open + seconds / 2.0 - time.monotonic()))
        path = harness.trace_dir()
        with harness.tracing(path):
            s0, p0 = engine.stats(), clients.progress()
            time.sleep(float(mix["trace_seconds"]))
            s1, p1 = engine.stats(), clients.progress()
        traced = {"dir": path, "progress": (p0, p1),
                  "steps": s1["steps"] - s0["steps"]}
    time.sleep(max(0.0, t_open + seconds - time.monotonic()))
    t_end = time.monotonic()
    after = harness.counters()
    stats1 = engine.stats()
    clients.stop()
    with clients._lock:
        records = list(clients.records)
    # plain records: the requests (and through their callbacks the engine
    # and its pool) must not outlive the engine
    for r in records:
        req = r.pop("req")
        r["stamps"] = [t for t in r["stamps"] if t <= t_end]
        r["program_ttft"] = (None if req.t_first is None
                             else req.t_first - req.t_arrival)
    return {"records": records, "t_open": t_open, "t_end": t_end,
            "open_perf": open_perf, "before": before, "after": after,
            "stats": (stats0, stats1), "traced": traced,
            "errors": clients.errors}


def window_numbers(records, t_open, t_end):
    """What the stamps say of the window (t_open, t_close], t_close the
    last token event up to t_end: tokens, seconds, every gap between two
    tokens of one request that ended in it, and the first-token times of
    the requests whose first token fell in it."""
    inside = [t for r in records for t in r["stamps"] if t_open < t <= t_end]
    t_close = max(inside) if inside else t_end
    gaps = [1e3 * (b - a) for r in records
            for a, b in zip(r["stamps"], r["stamps"][1:])
            if t_open < b <= t_close]
    ttft = [1e3 * (r["stamps"][0] - r["t_submit"]) for r in records
            if r["stamps"] and t_open < r["stamps"][0] <= t_close]
    return {"tokens": len(inside), "seconds": t_close - t_open,
            "t_close": t_close, "gaps_ms": gaps, "ttft_ms": ttft}


def traced_kv_bytes(records, traced, config):
    """K and V bytes the decode steps between the two progress snapshots
    had to read: for every token a step produced, its sequence's context."""
    p0, p1 = traced["progress"]
    ctx = 0
    for i, rec in enumerate(records):
        if i not in p0 and i not in p1 and (
                rec["t_done"] is None or rec["tokens"] is None):
            continue
        first = p0.get(i, 0)
        if i in p1:
            last = p1[i]
        elif i in p0 and rec["tokens"] is not None:
            last = len(rec["tokens"])  # finished inside the interval
        else:
            continue
        ctx += flops.decode_context_tokens(len(rec["prompt"]), first, last)
    work = harness.load_module(config["work"]["module"])
    return ctx * getattr(
        work, config["work"]["kv_bytes_per_context_token"])(config)


def served_gaps(config, seed, sample, matmul=None):
    """Per sampled request, the widest gap by which a served token's logit
    lies below the reference's best, over its served tokens."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", config["reference"])
    cfg = dict(config)
    with jax.default_matmul_precision("highest"):
        params = ref.init_weights(config, seed)
        out = []
        for rec in sample:
            logits = ref.served_logits(params, cfg, rec["prompt"],
                                       rec["tokens"], matmul or jnp.matmul)
            served = jnp.asarray(rec["tokens"], jnp.int32)
            got = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
            out.append(float(jnp.max(jnp.max(logits, axis=1) - got)))
    return out


def control(config, mix, devices, seeds, lowprec, seconds):
    """Per seed: the sound program's widest served-logit gap over a short
    window's sample, and the control's: at each position of the same
    prompts and served tokens, the gap of the token that the reference
    computed in fp8 puts first."""
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
        sample = pick_sample(finished, seed, n)
        row = {"seed": seed, "requests": len(sample),
               "served_tokens": sum(len(r["tokens"]) for r in sample),
               "program": max(served_gaps(config, seed, sample))}
        with jax.default_matmul_precision("highest"):
            params = ref.init_weights(config, seed)
            for name, matmul in (("bf16", lowprec.bf16_matmul),
                                 ("control_fp8", lowprec.fp8_matmul)):
                worst = 0.0
                for rec in sample:
                    base = ref.served_logits(params, config, rec["prompt"],
                                             rec["tokens"])
                    low = ref.served_logits(params, config, rec["prompt"],
                                            rec["tokens"], matmul)
                    pick = jnp.argmax(low, axis=1)
                    got = jnp.take_along_axis(base, pick[:, None],
                                              axis=1)[:, 0]
                    worst = max(worst, float(jnp.max(
                        jnp.max(base, axis=1) - got)))
                row[name] = worst
            del params
        yield row


def pick_sample(finished, seed, n):
    """The longest finished request and n - 1 more, drawn from the seed."""
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    rest = order[1:]
    rng = generator.rng_for(seed, 3)
    more = [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [finished[i] for i in order[:1] + more]


def run(ctx):
    config, mix, devices = ctx["config"], ctx["mix"], ctx["devices"]
    seed, seconds, checks = ctx["seed"], ctx["seconds"], ctx["checks"]

    engine, scope = build_engine(config, devices, seed)
    try:
        w = serve(engine, config, mix, seed, seconds, ctx["trace"])
        setup_s = w["open_perf"] - ctx["t_start"]
        memory = harness.memory_peak_bytes(devices)
        print(f"INFO memory counters {harness.memory_report(devices)}",
              flush=True)
        forms = harness.kernel_forms(w["after"])
    finally:
        engine.close()
    del engine, scope
    gc.collect()

    t_open, t_end = w["t_open"], w["t_end"]
    win = window_numbers(w["records"], t_open, t_end)
    in_window = [r for r in w["records"]
                 if r["t_done"] is not None and t_open <= r["t_done"] <= t_end]
    finished = [r for r in in_window if r["tokens"] is not None]
    failed = len(in_window) - len(finished)
    own = [1e3 * abs((r["stamps"][0] - r["t_submit"]) - r["program_ttft"])
           for r in finished if r["program_ttft"] is not None]
    quarter = win["seconds"] / 4.0
    by_quarter = [sum(t_open + k * quarter < t <= t_open + (k + 1) * quarter
                      for r in w["records"] for t in r["stamps"]) / quarter
                  for k in range(4)]
    print(f"INFO set-up {setup_s:.1f}s of which the first wave took "
          f"{t_open - w['records'][0]['t_submit']:.1f}s", flush=True)
    print(f"INFO finished {len(finished)} requests; {win['tokens']} tokens in "
          f"{win['seconds']:.3f}s ({len(win['gaps_ms'])} gaps; by quarter of "
          f"the window {[round(q, 2) for q in by_quarter]} tokens/s); gap "
          f"p50 {harness.percentile(win['gaps_ms'], 50):.2f} ms; "
          f"{len(win['ttft_ms'])} first tokens, ttft p50 "
          f"{harness.percentile(win['ttft_ms'], 50):.1f} ms, largest "
          f"{max(win['ttft_ms'], default=float('nan')):.1f} ms; first-token "
          f"time differs from the program's own by at most "
          f"{max(own) if own else float('nan'):.2f} ms; kernel forms "
          f"{ {k: sorted(v) for k, v in forms.items()} }", flush=True)

    checks.equal("client_errors", len(w["errors"]), 0)
    checks.equal("compiles_in_window", harness.compiles(w["after"])
                 - harness.compiles(w["before"]), 0)
    checks.equal("failed_requests", failed, 0)
    checks.equal("short_outputs", sum(
        len(r["tokens"]) != r["max_new"] for r in finished), 0)
    checks.equal("unstamped_tokens", sum(
        len(r["tokens"]) != len(r["stamps"]) for r in finished), 0)
    checks.floor("finished_requests", len(finished),
                 int(config["correct"]["sample_requests"]))
    for primitive, want in config["expect"]["kernel_forms"].items():
        checks.equal(f"kernel_form.{primitive}",
                     sorted(forms.get(primitive, ())), want)
    if finished:
        t_ref = harness.now()
        sample = pick_sample(finished, seed,
                             int(config["correct"]["sample_requests"]))
        gaps = served_gaps(config, seed, sample)
        print(f"INFO reference over {len(sample)} requests, "
              f"{sum(len(r['tokens']) for r in sample)} served tokens, took "
              f"{harness.now() - t_ref:.1f}s; gaps {gaps}", flush=True)
        checks.limit("served_logit_gap", max(gaps),
                     config["correct"]["served_logit_gap"])

    d = harness.delta(w["after"], w["before"])
    s0, s1 = w["stats"]
    steps = s1["steps"] - s0["steps"]
    firsts = sum(bool(r["stamps"]) and t_open < r["stamps"][0] <= t_end
                 for r in w["records"])
    numbers = {**d, "engine.steps": float(steps),
               "engine.slot_steps": float(steps * s1["pool_slots"]),
               # every prefill's last chunk yields a token too: not a
               # decode step's
               "engine.decode_tokens": float(s1["tokens"] - s0["tokens"]
                                             - firsts)}
    step_n = d.get("pt_decode_step_seconds{bench}.count")
    chunk_n = d.get("pt_decode_prefill_chunks_total{bench}")
    if step_n and chunk_n:  # where a slow run lost its time
        print(f"INFO program's host clock in the window: {step_n:.0f} decode "
              f"steps of "
              f"{1e3 * d['pt_decode_step_seconds{bench}.sum'] / step_n:.2f}"
              f" ms, {chunk_n:.0f} prefill chunks of "
              f"{1e3 * d['pt_decode_phase_seconds_total{bench,prefill}'] / chunk_n:.2f}"
              f" ms", flush=True)
    if w["traced"]:
        tr = w["traced"]
        kv = traced_kv_bytes(w["records"], tr, config)
        numbers["work.kv_bytes_per_decode_step"] = (
            kv / tr["steps"] if tr["steps"] else 0.0)
        numbers["work.traced_steps"] = float(tr["steps"])
        numbers["work.paged_calls_per_decode_step"] = float(
            config["work"]["attn_calls_per_decode_step"])
    return {
        "attempted": len(in_window), "failed": failed, "setup_s": setup_s,
        "memory_peak_bytes": memory,
        "end_to_end": {
            "decode_tokens_per_s": win["tokens"] / win["seconds"],
            "tpot_p95_ms": harness.percentile(win["gaps_ms"], 95)},
        "numbers": numbers,
        "trace": w["traced"],
    }
