#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of models the repo supports (depth uncut here; weights
random from a seed; synthetic data from a seed):

  trainer  BERT-base b128 s128, bf16 dtype policy, Adam, default FLAGS
           (graph passes on), built with ``bert.build_bert_pretrain`` and
           run by ``fluid.Executor(fluid.TPUPlace(0))``
  server   GPT-base behind ``serving.DecodeEngine`` (page 32, max_len 512,
           8 slots): warmup -> start -> mixed-length prompts through
           ``generate()``, checked against the whole-sequence greedy
           reference over the same scope
  dp       (only when four chips are visible) the BERT-base step through
           ``CompiledProgram(...).with_data_parallel`` in one process

and checks numbers, not exit codes: finite falling losses, parameters on
the expected platform, one executable per signature, zero compiles after
warm-up, token agreement with the reference, and that every Pallas
primitive a phase reached resolved to the form the phase names — read
from ``pt_kernel_dispatch_total`` and the program's pass report.

Run it on the chip:   python chip_smoke.py
It never accepts a CPU: without a TPU it exits non-zero and prints no
result line.  All wall times below are SMOKE TIMINGS (one run, host
clock, includes dispatch) — not benchmark numbers.

The phases are plain functions so tests/test_chip_smoke.py can drive them
at tiny width on the CPU mesh.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


class SmokeFailure(AssertionError):
    """A phase ran but what came out is wrong."""


def _require(ok, what):
    if not ok:
        raise SmokeFailure(what)


def device_report():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# counters the phases read instead of assuming
# ---------------------------------------------------------------------------


def _samples(name):
    from paddle_tpu import observability as obs

    return dict((obs.snapshot().get(name) or {}).get("samples", {}))


def _delta(after, before):
    return {k: int(v - before.get(k, 0)) for k, v in after.items()
            if v != before.get(k, 0)}


def _compile_misses():
    """Executables built so far, over every executor lane."""
    return sum(v for (path, result), v in
               _samples("pt_compile_cache_total").items()
               if result == "miss" and path != "xla_persistent")


class _Counters:
    """Kernel-dispatch and compile-cache movement across one phase."""

    def __init__(self):
        self._dispatch = _samples("pt_kernel_dispatch_total")
        self._cache = _samples("pt_compile_cache_total")

    def report(self, expect_mode):
        dispatch = _delta(_samples("pt_kernel_dispatch_total"),
                          self._dispatch)
        kernels = {}
        for (primitive, mode), n in sorted(dispatch.items()):
            kernels.setdefault(primitive, {})[mode] = n
        wrong = {p: m for p, m in kernels.items() if set(m) != {expect_mode}}
        _require(not wrong,
                 f"kernels resolved to another form than {expect_mode!r}: "
                 f"{wrong}")
        cache = _delta(_samples("pt_compile_cache_total"), self._cache)
        return {
            "kernels": {p: expect_mode for p in kernels},
            "pt_compile_cache_total": {
                f"{path}/{result}": n
                for (path, result), n in sorted(cache.items())},
        }


def _finite_and_falling(losses, what):
    _require(all(np.isfinite(losses)), f"{what}: loss not finite: {losses}")
    _require(losses[-1] < losses[0],
             f"{what}: loss did not fall: {losses}")


def _on_platform(array, platform, what):
    got = sorted({d.platform for d in array.devices()})
    _require(got == [platform], f"{what} lives on {got}, not {platform!r}")


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------


def _build_bert_train(cfg):
    from paddle_tpu import fluid
    from paddle_tpu.fluid.contrib import mixed_precision as mp
    from paddle_tpu.models import bert

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, loss, _, _ = bert.build_bert_pretrain(cfg, is_test=False)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
    mp.enable_bf16_policy(main)  # bf16 compute, fp32 master weights
    return main, startup, loss


def run_trainer(cfg, *, batch, seq_len, steps, place, platform,
                expect_mode):
    """Startup, one warm-up step (trace + compile), then ``steps`` steps
    on one repeated batch."""
    from paddle_tpu import fluid
    from paddle_tpu.models import bert

    counters = _Counters()
    main, startup, loss = _build_bert_train(cfg)
    data = bert.make_fake_batch(cfg, batch=batch, seq_len=seq_len, seed=0)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(startup)
        t0 = time.perf_counter()
        losses = [float(exe.run(main, feed=data,
                                fetch_list=[loss.name])[0])]
        compile_s = time.perf_counter() - t0
        warm = _compile_misses()
        t0 = time.perf_counter()
        for _ in range(steps):
            losses.append(float(exe.run(main, feed=data,
                                        fetch_list=[loss.name])[0]))
        step_s = (time.perf_counter() - t0) / steps
        recompiles = _compile_misses() - warm
        _on_platform(scope.get("word_embedding"), platform,
                     "trainer parameter word_embedding")
    _finite_and_falling(losses, "trainer")
    _require(recompiles == 0 and len(exe.compiled_for(main)) == 1,
             f"trainer: {recompiles} compiles after warm-up, "
             f"{len(exe.compiled_for(main))} train executables (want 0, 1)")
    passes = {e["pass"]: {k: e[k] for k in ("sites", "kernel") if k in e}
              for e in main._pass_report if e.get("changed")}
    bias_act = passes.get("fuse_bias_act_dropout", {})
    # every FFN plus the masked-LM transform
    _require(bias_act.get("sites") == cfg.num_layers + 1
             and bias_act.get("kernel") == "xla",
             f"trainer: fuse_bias_act_dropout report {bias_act}, want "
             f"{cfg.num_layers + 1} sites in the xla form")
    return {"phase": "trainer", "losses": [round(v, 4) for v in losses],
            "compile_s": round(compile_s, 2), "step_s": round(step_s, 4),
            "graph_passes": passes, **counters.report(expect_mode)}


# ---------------------------------------------------------------------------
# decode server
# ---------------------------------------------------------------------------

# log-prob gap under which the reference's own top two tokens count as a
# tie: two lanes that sum in different orders may pick either
_TIE_MARGIN = 1e-2


def _greedy_reference(exe, scope, cfg, max_len, prompts, gen_len):
    """Whole-sequence greedy decode: one fixed-shape [1, max_len] causal
    forward per token (one executable for every prompt).  Returns
    (ids, margins) per prompt: margins[i] is the log-prob gap between
    the reference's best and second-best token at step i."""
    from paddle_tpu import fluid
    from paddle_tpu.models import gpt

    ref, ref_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(ref, ref_start), fluid.unique_name.guard():
        ids = fluid.data("ref_ids", [1, max_len], False, dtype="int64")
        pos = fluid.data("ref_pos", [1, max_len], False, dtype="int64")
        last = fluid.data("ref_last", [1], False, dtype="int64")
        h = gpt.gpt_decoder(ids, pos, cfg, is_test=True)
        flat = fluid.layers.reshape(h, shape=[-1, cfg.hidden_size])
        emb = ref.global_block().var("gpt_word_embedding")
        logits = fluid.layers.matmul(fluid.layers.gather(flat, last), emb,
                                     transpose_y=True)
        logp = fluid.layers.log_softmax(logits)
    pos_row = np.arange(max_len, dtype=np.int64)[None, :]
    out = []
    for prompt in prompts:
        seq, margins = list(prompt), []
        for _ in range(gen_len):
            buf = np.zeros((1, max_len), np.int64)
            buf[0, :len(seq)] = seq
            (row,) = exe.run(ref, feed={
                "ref_ids": buf, "ref_pos": pos_row,
                "ref_last": np.array([len(seq) - 1], np.int64)},
                fetch_list=[logp.name], scope=scope)
            row = np.asarray(row)[0]
            top2 = np.partition(row, -2)[-2:]
            margins.append(float(top2[1] - top2[0]))
            seq.append(int(np.argmax(row)))
        out.append((seq[len(prompt):], margins))
    return out


def run_server(cfg, *, slots, page, max_len, prompt_lens, gen_len, place,
               platform, expect_mode):
    """warmup -> start -> mixed-length prompts through generate() ->
    close, then the greedy reference over the same scope."""
    from paddle_tpu import fluid, serving
    from paddle_tpu.models import gpt

    counters = _Counters()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist()
               for n in prompt_lens]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        lm, lm_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(lm, lm_start), fluid.unique_name.guard():
            gpt.build_gpt_lm(cfg, is_test=True)
        exe = fluid.Executor(place)
        exe.run(lm_start)  # the seeded random weights every lane shares
        engine = serving.DecodeEngine(
            cfg, scope=scope, place=place, pool_slots=slots, page_size=page,
            max_len=max_len, name="smoke", auto_start=False)
        try:
            t0 = time.perf_counter()
            engine.warmup()
            compile_s = time.perf_counter() - t0
            engine.start()
            warm = _compile_misses()
            t0 = time.perf_counter()
            outs = engine.generate(prompts, max_new_tokens=gen_len,
                                   timeout=600)
            serve_s = time.perf_counter() - t0
            recompiles = _compile_misses() - warm
            stats = engine.stats()
        finally:
            engine.close()
        _on_platform(scope.get("gpt_word_embedding"), platform,
                     "server parameter gpt_word_embedding")
        t0 = time.perf_counter()
        refs = _greedy_reference(exe, scope, cfg, max_len, prompts,
                                 gen_len)
        reference_s = time.perf_counter() - t0
    _require(recompiles == 0,
             f"server: {recompiles} compiles after warm-up")
    _require(all(len(o) == gen_len for o in outs),
             f"server: wrong output lengths {[len(o) for o in outs]}")
    exact, ties = 0, []
    for n, out, (ref_ids, margins) in zip(prompt_lens, outs, refs):
        if list(out) == ref_ids:
            exact += 1
            continue
        i = next(i for i, (a, b) in enumerate(zip(out, ref_ids)) if a != b)
        _require(margins[i] < _TIE_MARGIN,
                 f"server: prompt of {n} tokens leaves the greedy "
                 f"reference at token {i} ({out[i]} vs {ref_ids[i]}) "
                 f"where the reference's margin is {margins[i]:.4f}")
        ties.append({"prompt_len": n, "token": i,
                     "margin": round(margins[i], 5)})
    _require(exact >= 1, "server: no prompt matched the greedy reference")
    return {"phase": "server", "prompts": list(prompt_lens),
            "gen_len": gen_len, "token_exact_prompts": exact,
            "reference_ties": ties, "decode_steps": stats["steps"],
            "evictions": stats["evictions"],
            "compile_s": round(compile_s, 2), "serve_s": round(serve_s, 3),
            "reference_s": round(reference_s, 2),
            **counters.report(expect_mode)}


# ---------------------------------------------------------------------------
# data parallel, one process driving every local chip
# ---------------------------------------------------------------------------


def run_dp(cfg, *, seq_len, parity_batch, batch, steps, place, places,
           platform, expect_mode):
    """Parity first — two steps at ``parity_batch`` must give the losses
    the one-device step gives (the second only if gradients were
    averaged) — then ``steps`` steps at ``batch``.  ``cfg`` has dropout
    off so the two lanes are comparable."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu import fluid
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import mesh as pmesh

    counters = _Counters()
    n = len(places) if places else jax.device_count()
    _require(n >= 2, f"dp: {n} device(s) — nothing to parallelise")
    # mask_pos indexes a device's own flat rows: shard-local for the
    # data-parallel feed, offset back to global rows for one device
    small = bert.make_fake_batch(cfg, batch=parity_batch, seq_len=seq_len,
                                 seed=1, shards=n)
    rows = parity_batch // n * seq_len
    small_global = dict(small, mask_pos=small["mask_pos"] + np.repeat(
        np.arange(n) * rows, len(small["mask_pos"]) // n)[:, None])

    # one device: the numbers to match
    main1, startup1, loss1 = _build_bert_train(cfg)
    scope1 = fluid.Scope()
    with fluid.scope_guard(scope1):
        exe1 = fluid.Executor(place)
        exe1.run(startup1)
        single = [float(exe1.run(main1, feed=small_global,
                                 fetch_list=[loss1.name])[0])
                  for _ in range(2)]
    del scope1

    main, startup, loss = _build_bert_train(cfg)
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=places)
    mesh = pmesh.build_mesh({pmesh.DATA_AXIS: n})
    split = NamedSharding(mesh, P(pmesh.DATA_AXIS))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(startup)
        feed = {k: jax.device_put(v, split) for k, v in small.items()}
        feed_devices = {s.device for s in
                        feed["src_ids"].addressable_shards}
        t0 = time.perf_counter()
        # per-device losses come back stacked on the dp axis
        parity = [float(np.mean(exe.run(compiled, feed=feed,
                                        fetch_list=[loss.name])[0]))
                  for _ in range(2)]
        compile_s = time.perf_counter() - t0
        param = scope.get("word_embedding")
        param_devices = {s.device for s in param.addressable_shards}
        hlo = compiled.lower(exe, feed, fetch_list=[loss.name]).as_text()

        big = {k: jax.device_put(v, split) for k, v in bert.make_fake_batch(
            cfg, batch=batch, seq_len=seq_len, seed=2, shards=n).items()}
        losses = [float(np.mean(exe.run(compiled, feed=big,
                                        fetch_list=[loss.name])[0]))]
        warm = _compile_misses()
        t0 = time.perf_counter()
        for _ in range(steps):
            losses.append(float(np.mean(exe.run(
                compiled, feed=big, fetch_list=[loss.name])[0])))
        step_s = (time.perf_counter() - t0) / steps
        recompiles = _compile_misses() - warm
    for what, devs in (("feed", feed_devices), ("parameter", param_devices)):
        _require(len(devs) == n and {d.platform for d in devs} == {platform},
                 f"dp: {what} shards sit on {sorted(map(str, devs))}, want "
                 f"{n} distinct {platform} devices")
    all_reduces = hlo.count("all_reduce")
    _require(all_reduces >= 1, "dp: the lowered step holds no all-reduce")
    # bf16 compute: 8 bits of mantissa
    _require(np.allclose(parity, single, rtol=1e-2),
             f"dp: losses {parity} over {n} devices, {single} on one")
    _finite_and_falling(losses, "dp")
    _require(recompiles == 0, f"dp: {recompiles} compiles after warm-up")
    return {"phase": "dp", "devices": n,
            "parity": {"batch": parity_batch, "one_device": single,
                       "data_parallel": parity},
            "batch": batch, "losses": [round(v, 4) for v in losses],
            "all_reduce_ops": all_reduces,
            "compile_s": round(compile_s, 2), "step_s": round(step_s, 4),
            **counters.report(expect_mode)}


# ---------------------------------------------------------------------------
# the chip run
# ---------------------------------------------------------------------------


def main():
    device = device_report()
    print("platform={platform} device_kind={kind!r} count={count}".format(
        **device), flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: platform={device['platform']} is not a TPU — "
                 f"this script only runs on the chip")

    from paddle_tpu import fluid
    from paddle_tpu.models import bert, gpt

    place = fluid.TPUPlace(0)
    common = dict(place=place, platform="tpu", expect_mode="pallas")
    t0 = time.perf_counter()
    reports = [
        run_trainer(bert.BertConfig.base(vocab_size=30528), batch=128,
                    seq_len=128, steps=6, **common),
        run_server(gpt.GPTConfig(vocab_size=50304, hidden_size=768,
                                 num_heads=12, num_layers=12,
                                 max_position=512),
                   slots=8, page=32, max_len=512,
                   prompt_lens=(16, 64, 128, 256, 40, 200), gen_len=8,
                   **common),
    ]
    if device["count"] == 4:
        reports.append(run_dp(
            bert.BertConfig.base(vocab_size=30528, hidden_dropout=0.0,
                                 attn_dropout=0.0),
            seq_len=128, parity_batch=128, batch=512, steps=3, places=None,
            **common))
    for r in reports:
        print("SMOKE " + json.dumps(r), flush=True)
    print(f"smoke wall {time.perf_counter() - t0:.1f}s (smoke timings, "
          f"not benchmark numbers)", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
