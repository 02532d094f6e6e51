"""Decode-lane e2e checks, run in ONE subprocess by tests/test_decode.py.

Why a child process: the jaxlib-0.4.3x XLA:CPU runtime nondeterministically
corrupts the heap when the decode lane's paged gather/scatter programs run
in a process that already compiled other suites' programs (observed 5/6
with tests/book first; see tests/cpu_mesh.py — same class as the GSPMD
abort, under BOTH runtimes).  A FRESH process running exactly this file is
stable, so the e2e gates execute here and tests/test_decode.py asserts the
reported results — isolation without giving up coverage (the
test_ring_collectives subprocess precedent).

Each check function takes the shared trained fixture and raises on
failure; main() runs all of them and prints one
``DECODE_E2E_RESULT {json}`` line mapping check name -> "ok" | traceback.

Run directly for debugging: ``python tests/decode_e2e_checks.py [names]``.
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cpu_mesh  # noqa: F401  (must precede any jax-using import)

# No persistent compile cache in this process: on the 0.4.3x jaxlib the
# corruption is seeded while DESERIALIZING warm entries (the fixture's
# own programs suffice) and only manifests later, under the engine's
# allocation churn — cache-off runs are stable (3/3) where warm-cache
# runs abort.  setdefault: an explicit caller override still wins.
os.environ.setdefault("FLAGS_compile_cache_dir", "")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from paddle_tpu import fluid, serving  # noqa: E402
from paddle_tpu.models import gpt  # noqa: E402

CFG = dict(num_layers=2, hidden_dropout=0.0, use_flash_attention=False)


def build_fixture(cfg=None):
    """One tiny GPT trained for 30 steps, plus the whole-sequence greedy
    reference ids for 4 prompts — the parity oracle every check shares."""
    cfg = cfg or gpt.GPTConfig.tiny(**CFG)
    data = gpt.make_fake_lm_batch(cfg, 8, 10, seed=3)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, loss = gpt.build_gpt_lm(cfg)
        fluid.optimizer.Adam(learning_rate=3e-3).minimize(loss)
    gen, gen_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen, gen_start), fluid.unique_name.guard():
        _, sent_v, _ = gpt.build_gpt_generate(cfg, prompt_len=4,
                                              gen_len=6, beam_size=1,
                                              end_id=0)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for _ in range(30):
            exe.run(main, feed=data, fetch_list=[loss.name])
        prompts = gpt.make_fake_lm_batch(cfg, 4, 4, seed=11)["gpt_ids"]
        (ref_ids,) = exe.run(gen, feed={"gpt_prompt": prompts},
                             fetch_list=[sent_v.name])
    ref_ids = np.asarray(ref_ids)[:, 0]  # [4, 6] greedy beam
    # two degeneracies would make the parity gate vacuous or flaky:
    # a prompt ENDING in end_id starts the whole-seq beam "finished"
    # (beam_search freezes it to end_id regardless of the model — the
    # decode lane has no such notion), and a mid-stream end_id emission
    # freezes the remaining reference positions the same way
    assert not (prompts[:, -1] == 0).any(), "prompt ends in end_id"
    assert not (ref_ids == 0).any(), "reference emitted end_id"
    return cfg, scope, prompts, ref_ids


def check_parity_greedy_bit_exact(cfg, scope, prompts, ref_ids):
    """THE acceptance gate: greedy generate() via the paged decode lane
    (chunked prefill + token-level continuous batching + paged
    attention) reproduces the whole-sequence build_gpt_generate lane's
    token ids EXACTLY — same weights, same prompts."""
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=4,
                               page_size=4, prefill_chunk=4, max_len=32,
                               name="parity", auto_start=False)
    try:
        eng.warmup()
        eng.start()
        outs = eng.generate([list(p) for p in prompts],
                            max_new_tokens=6, timeout=300)
    finally:
        eng.close()
    np.testing.assert_array_equal(np.asarray(outs), ref_ids)


@functools.lru_cache(maxsize=None)
def _wide_fixture():
    """build_fixture at 20 heads of 64, shared by the checks that ask."""
    return build_fixture(gpt.GPTConfig(
        vocab_size=256, hidden_size=1280, num_heads=20,
        intermediate_size=256, max_position=32, **CFG))


def _check_parity_at_benchmark_heads(attn_force):
    """The parity gate at GPT-2-large's own head geometry: 20 heads of
    64 in a 1280-wide lane dimension (two layers, a small vocabulary and
    FFN), so that the split of the pool's flat last dimension into heads
    — 64-lane slices inside the kernel, a reshape of the gathered pages
    in the XLA reference — is checked where the benchmark runs it."""
    cfg, scope, prompts, ref_ids = _wide_fixture()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=4,
                               page_size=4, prefill_chunk=4, max_len=16,
                               attn_force=attn_force,
                               name=f"wide-{attn_force}",
                               auto_start=False)
    try:
        pool = np.asarray(scope.get(eng.pool.var_names[0][0]))
        assert pool.shape == (eng.pool.num_pages, 4, 1280), pool.shape
        eng.warmup()
        eng.start()
        outs = eng.generate([list(p) for p in prompts],
                            max_new_tokens=6, timeout=600)
    finally:
        eng.close()
    np.testing.assert_array_equal(np.asarray(outs), ref_ids)


def check_parity_heads20x64_pallas(cfg, scope, prompts, ref_ids):
    """Pallas kernel, interpret mode (the chip's algorithm on the CPU)."""
    _check_parity_at_benchmark_heads("pallas")


def check_parity_heads20x64_reference(cfg, scope, prompts, ref_ids):
    """XLA reference (what a CPU engine dispatches to)."""
    _check_parity_at_benchmark_heads("reference")


def check_zero_steady_state_compiles(cfg, scope, prompts, ref_ids):
    """After warmup, traffic of ANY mix of prompt lengths and request
    counts runs on exactly two executables: the single-path compile-miss
    counter must not move (the fixed-shape decode-step contract)."""
    from paddle_tpu import observability as obs

    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=3,
                               page_size=4, prefill_chunk=8, max_len=32,
                               name="steady", auto_start=False)
    try:
        eng.warmup()
        eng.start()

        def misses():
            fam = obs.REGISTRY.get("pt_compile_cache_total")
            samples = fam._snapshot()["samples"] if fam else {}
            return sum(v for k, v in samples.items()
                       if k[0] == "single" and k[1] != "hit")

        before = misses()
        rng = np.random.RandomState(0)
        futs = []
        for plen in (3, 7, 11, 5, 2):  # mixed prompt lengths
            prompt = list(rng.randint(1, cfg.vocab_size, plen))
            futs.append(eng.submit(prompt, max_new_tokens=4))
        outs = [f.result(timeout=300) for f in futs]
        assert all(len(o) == 4 for o in outs)
        assert misses() == before, \
            "steady-state decode traffic recompiled"
    finally:
        eng.close()


def check_eviction_under_pressure_matches_unpressured(cfg, scope,
                                                      prompts, ref_ids):
    """A pool sized BELOW the concurrent working set forces evictions;
    evicted sequences re-prefill their prompt + generated prefix and —
    greedy decode being deterministic — finish with the SAME tokens the
    unpressured run produces."""
    # 6 tokens generated from 4-token prompts -> 10 positions -> 3 pages
    # of 4 per sequence; 5 allocatable pages cannot hold 4x3 -> churn
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=4,
                               page_size=4, prefill_chunk=4, max_len=16,
                               num_pages=6, name="pressure",
                               auto_start=False)
    try:
        eng.warmup()
        eng.start()
        outs = eng.generate([list(p) for p in prompts],
                            max_new_tokens=6, timeout=300)
    finally:
        eng.close()
    np.testing.assert_array_equal(np.asarray(outs), ref_ids)
    assert eng.stats()["evictions"] > 0, \
        "pool sized for pressure never evicted — test is vacuous"


def check_long_prompt_chunked_prefill(cfg, scope, prompts, ref_ids):
    """A prompt longer than the chunk streams through several prefill
    executions (the phase split) and still matches the one-chunk
    configuration token for token."""
    rng = np.random.RandomState(7)
    prompt = list(rng.randint(1, cfg.vocab_size, 19))
    outs = {}
    for chunk in (4, 24):  # 19-token prompt: 5 chunks vs 1
        eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                                   page_size=4, prefill_chunk=chunk,
                                   max_len=32, name=f"chunk{chunk}",
                                   auto_start=False)
        try:
            eng.warmup()
            eng.start()
            outs[chunk] = eng.generate([prompt], max_new_tokens=5,
                                       timeout=300)[0]
            stats = eng.stats()
            if chunk == 4:
                assert stats["kv_pool"]["page_size"] == 4
        finally:
            eng.close()
    assert outs[4] == outs[24]


def check_eos_and_single_token(cfg, scope, prompts, ref_ids):
    """max_new_tokens=1 finishes on the prefill seed alone (no decode
    step); an eos_id equal to the seed stops immediately too."""
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=32,
                               name="eos", auto_start=False)
    try:
        eng.warmup()
        eng.start()
        one = eng.generate([list(prompts[0])], max_new_tokens=1,
                           timeout=300)[0]
        assert one == [int(ref_ids[0, 0])]
        stopped = eng.generate([list(prompts[0])], max_new_tokens=6,
                               eos_id=int(ref_ids[0, 2]),
                               timeout=300)[0]
        assert stopped == [int(t) for t in ref_ids[0, :3]]
    finally:
        eng.close()


def check_int8_kv_generate_matches_fp32(cfg, scope, prompts, ref_ids):
    """The int8-KV serving gate: a DecodeEngine whose pool stores the
    dual-int8 wire format (pool_dtype="int8" — quantize once at append,
    dequant inside the paged kernel) greedy-generates the SAME token ids
    as the fp32-pool reference lane, and books the modeled HBM saving on
    pt_int8_bytes_saved_total{kind="kv_cache"}."""
    from paddle_tpu import observability as obs

    def saved():
        fam = obs.REGISTRY.get("pt_int8_bytes_saved_total")
        samples = fam._snapshot()["samples"] if fam else {}
        return samples.get(("kv_cache",), 0.0)

    before = saved()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=4,
                               page_size=4, prefill_chunk=4, max_len=32,
                               pool_dtype="int8", name="int8kv",
                               auto_start=False)
    try:
        assert saved() > before, "int8 pool never booked its saving"
        eng.warmup()
        eng.start()
        outs = eng.generate([list(p) for p in prompts],
                            max_new_tokens=6, timeout=300)
    finally:
        eng.close()
    np.testing.assert_array_equal(np.asarray(outs), ref_ids)


def check_int8_kv_logprob_drift(cfg, scope, prompts, ref_ids):
    """The int8-KV numerics gate: the SAME trained weights decoding the
    SAME 20 tokens through an fp32 pool vs a dual-int8 pool keep every
    per-step logprob row within a tight bound and agree on every greedy
    argmax — quantization happens once per append, so the error does
    not compound across steps."""
    from paddle_tpu.serving import lane
    from paddle_tpu.serving.kv_pool import KVPool
    from paddle_tpu.serving.lane import kv_rows

    n, d = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    page_size, max_pages, num_pages, steps = 4, 8, 9, 20

    progs = {}
    for dtype in ("float32", "int8"):
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start), fluid.unique_name.guard():
            _, _, logp = cfg.decode_lane().build_decode_step(
                pool_slots=1, num_pages=num_pages, page_size=page_size,
                max_pages=max_pages, pool_dtype=dtype)
        progs[dtype] = (main, logp.name)

    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        # both pools in the one scope, each in the one shape every pool
        # var has: the int8 rows' vars are named apart from the float32
        # rows' (lane.kv_rows)
        for dtype in ("float32", "int8"):
            KVPool(cfg.num_layers, kv_rows(n, d, dtype), num_pages,
                   page_size, max_pages).install(scope)

        toks = np.random.RandomState(0).randint(
            1, cfg.vocab_size, steps)
        table = np.zeros((1, max_pages), np.int32)
        n_used = -(-steps // page_size)
        table[0, :n_used] = np.arange(1, 1 + n_used)
        logps = {}
        for dtype in ("float32", "int8"):
            main, logp_name = progs[dtype]
            rows = []
            for t in range(steps):
                feed = lane.decode_feed(
                    np.array([[toks[t]]], np.int64),
                    np.array([[t]], np.int64), {lane.FULL: table},
                    {lane.FULL: np.array([table[0, t // page_size]],
                                         np.int32)},
                    np.array([t % page_size], np.int32))
                (lp,) = exe.run(main, feed=feed, fetch_list=[logp_name])
                rows.append(np.asarray(lp)[0])
            logps[dtype] = np.stack(rows)

    drift = np.abs(logps["int8"] - logps["float32"]).max()
    assert drift < 0.05, f"20-step int8-KV logprob drift {drift}"
    assert (logps["int8"].argmax(-1)
            == logps["float32"].argmax(-1)).all(), \
        "int8 pool flipped a greedy argmax inside the drift window"


def check_int8_weights_generate_matches_fp32(cfg, scope, prompts,
                                             ref_ids):
    """The int8-WEIGHT serving gate: DecodeEngine(int8_weights=True)
    rewrites both lane programs through the int8_weight_storage pass,
    quantizes the scope's matmul weights to dual-int8 (dropping the fp32
    arrays), books pt_int8_bytes_saved_total{kind="weights"} — and still
    greedy-generates the SAME token ids as the fp32 reference lane
    (dual-int8 keeps ~14.6 significant bits; see docs/KERNELS.md)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.passes.int8_weights import storage_var_names

    def saved():
        fam = obs.REGISTRY.get("pt_int8_bytes_saved_total")
        samples = fam._snapshot()["samples"] if fam else {}
        return samples.get(("weights",), 0.0)

    # quantize_scope_weights DROPS the fp32 weights — work on a copy so
    # the shared fixture scope stays intact for other checks
    qscope = fluid.Scope()
    for nm in list(scope.keys()):
        qscope.set(nm, scope.get(nm))
    before = saved()
    eng = serving.DecodeEngine(cfg, scope=qscope, pool_slots=4,
                               page_size=4, prefill_chunk=4, max_len=32,
                               name="int8w", auto_start=False,
                               int8_weights=True)
    try:
        deq = [op for op in eng._dec_prog.global_block().ops
               if op.type == "dequantize_weight_storage"]
        assert deq, "int8_weights engaged but no weight was rewritten"
        assert saved() > before, "int8 weights never booked their saving"
        # the fp32 arrays are gone from the scope, the triples installed
        w0 = deq[0].output("Out")[0]
        assert qscope.get(w0) is None
        assert all(qscope.get(nm) is not None
                   for nm in storage_var_names(w0))
        eng.warmup()
        eng.start()
        outs = eng.generate([list(p) for p in prompts],
                            max_new_tokens=6, timeout=300)
    finally:
        eng.close()
    np.testing.assert_array_equal(np.asarray(outs), ref_ids)


CHECKS = {
    "parity_greedy_bit_exact": check_parity_greedy_bit_exact,
    "int8_kv_generate_matches_fp32": check_int8_kv_generate_matches_fp32,
    "int8_kv_logprob_drift": check_int8_kv_logprob_drift,
    "int8_weights_generate_matches_fp32":
        check_int8_weights_generate_matches_fp32,
    "parity_heads20x64_pallas": check_parity_heads20x64_pallas,
    "parity_heads20x64_reference": check_parity_heads20x64_reference,
    "zero_steady_state_compiles": check_zero_steady_state_compiles,
    "eviction_under_pressure_matches_unpressured":
        check_eviction_under_pressure_matches_unpressured,
    "long_prompt_chunked_prefill": check_long_prompt_chunked_prefill,
    "eos_and_single_token": check_eos_and_single_token,
}


def main(names=None):
    import json
    import traceback

    print("DECODE_E2E building fixture", flush=True)  # observability: allow
    fixture = build_fixture()
    results = {}
    for name in (names or CHECKS):
        # progress markers bracket each check so a native crash (the
        # corruption class this file isolates) names its victim
        print(f"DECODE_E2E running {name}", flush=True)  # observability: allow
        try:
            CHECKS[name](*fixture)
            results[name] = "ok"
        except Exception:  # resilience: allow — reported to the parent
            results[name] = traceback.format_exc()
    print("DECODE_E2E_RESULT " + json.dumps(results), flush=True)
    return 0 if all(v == "ok" for v in results.values()) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or None))
