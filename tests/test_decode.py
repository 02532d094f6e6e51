"""Token-level continuous-batching decode lane (ISSUE 13): paged
KV-cache slot pool, prefill/decode split, and the parity gate — greedy
generate() through the paged decode lane reproduces the
build_gpt_generate whole-sequence lane token for token."""

import numpy as np
import pytest

from paddle_tpu import fluid, serving
from paddle_tpu.models import gpt
from paddle_tpu.serving.errors import PoolExhaustedError
from paddle_tpu.serving.kv_pool import KVPool
from paddle_tpu.serving.lane import kv_rows

CFG = dict(num_layers=2, hidden_dropout=0.0, use_flash_attention=False)


# ---------------------------------------------------------------------------
# KV pool units
# ---------------------------------------------------------------------------


def _pool(num_pages=9, page_size=4, max_pages=4):
    return KVPool(num_layers=2, rows=kv_rows(4, 16),
                  num_pages=num_pages, page_size=page_size,
                  max_pages_per_seq=max_pages)


def test_pool_alloc_and_free():
    p = _pool()
    p.open_seq("a")
    t = p.ensure_capacity("a", 5)  # 2 pages of 4
    assert len(t) == 2 and all(pg != 0 for pg in t)  # trash never handed out
    assert p.pages_in_use() == 2
    t2 = p.ensure_capacity("a", 8)  # still 2 pages
    assert t2 == t
    p.ensure_capacity("a", 9)  # grows to 3
    assert p.pages_in_use() == 3
    assert p.free_seq("a") == 3
    assert p.pages_in_use() == 0
    assert p.free_seq("a") == 0  # idempotent


def test_pool_exhaustion_and_lifo_reuse():
    p = _pool(num_pages=5, page_size=4, max_pages=4)  # 4 allocatable
    p.open_seq("a")
    pages_a = list(p.ensure_capacity("a", 12))  # 3 pages
    p.open_seq("b")
    p.ensure_capacity("b", 4)  # the last page
    with pytest.raises(PoolExhaustedError):
        p.ensure_capacity("b", 8)
    p.free_seq("a")
    # LIFO: the next allocation reuses a's pages (head page first — the
    # freed set comes back in held order); cross-step reuse keeps the
    # warm working set on the same physical pages
    p.ensure_capacity("b", 8)
    assert p.table("b")[1] == pages_a[0]
    assert p.reused_allocs >= 1


def test_pool_rejects_sub_sequence_sizing():
    with pytest.raises(ValueError, match="cannot hold one full"):
        KVPool(num_layers=1, rows=kv_rows(2, 8), num_pages=4,
               page_size=4, max_pages_per_seq=4)


def test_pool_padded_table_and_install():
    p = _pool()
    p.open_seq("s")
    p.ensure_capacity("s", 6)
    row = p.padded_table("s")
    assert row.shape == (4,) and row.dtype == np.int32
    assert list(row[:2]) == p.table("s") and all(row[2:] == 0)
    assert all(p.padded_table(None) == 0)
    scope = fluid.Scope()
    p.install(scope)
    arr = scope.get(p.var_names[0][0])
    # the pool's one shape: 4 heads of 16 side by side in the last dim
    assert arr.shape == (9, 4, 64) and str(arr.dtype) == "float32"
    # idempotent on shape match: the resident pool is kept
    scope.set(p.var_names[0][0], arr + 1.0)
    p.install(scope)
    assert np.asarray(scope.get(p.var_names[0][0])).max() == 1.0
    # ... but NOT on a dtype change: a rebuild with a different
    # pool_dtype must re-install, or every later write trips the dtype
    # guard blaming the payload instead of the stale resident pool
    p16 = KVPool(num_layers=2, rows=kv_rows(4, 16, "float16"), num_pages=9,
                 page_size=4, max_pages_per_seq=4)
    p16.install(scope)
    re = np.asarray(scope.get(p16.var_names[0][0]))
    assert str(re.dtype) == "float16" and re.max() == 0.0


# ---------------------------------------------------------------------------
# paged-attention kernel
# ---------------------------------------------------------------------------


def _paged_case(seed=0, b=3, n=2, d=8, pgs=4, maxp=3, t=1):
    rng = np.random.RandomState(seed)
    # drawn with the heads apart, handed over in the pool's one shape
    k_pages = rng.randn(8, pgs, n, d).astype("float32").reshape(8, pgs, -1)
    v_pages = rng.randn(8, pgs, n, d).astype("float32").reshape(8, pgs, -1)
    pt = np.array([[1, 2, 3], [4, 5, 0], [6, 7, 0]], np.int32)[:b]
    q_start = np.array([9, 5, 2], np.int32)[:b]
    q = rng.randn(b, n, t, d).astype("float32")
    return q, k_pages, v_pages, pt, q_start


def test_paged_attention_reference_matches_dense():
    from paddle_tpu.kernels import paged_attention as pa

    q, kp, vp, pt, qs = _paged_case()
    out = np.asarray(pa.paged_attention(q, kp, vp, pt, qs,
                                        force="reference"))
    n, d = q.shape[1], q.shape[-1]
    for b in range(q.shape[0]):
        L = qs[b] + 1
        ks = kp[pt[b]].reshape(-1, n, d).transpose(1, 0, 2)[:, :L]
        vs = vp[pt[b]].reshape(-1, n, d).transpose(1, 0, 2)[:, :L]
        s = np.einsum("ntd,nld->ntl", q[b], ks) / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        oracle = np.einsum("ntl,nld->ntd", p, vs)
        np.testing.assert_allclose(out[b], oracle, atol=1e-5)


@pytest.mark.parametrize("t", [1, 4])
def test_paged_attention_pallas_interpret_matches_reference(t):
    """The Pallas kernel (scalar-prefetched page table, online softmax
    over pages, dead blocks skipped) matches the XLA reference <= 1e-5
    for both the decode (T=1) and prefill-chunk (T>1) shapes."""
    from paddle_tpu.kernels import paged_attention as pa

    q, kp, vp, pt, qs = _paged_case(seed=t, t=t)
    ref = np.asarray(pa.paged_attention(q, kp, vp, pt, qs,
                                        force="reference"))
    pal = np.asarray(pa.paged_attention(q, kp, vp, pt, qs,
                                        force="pallas"))
    np.testing.assert_allclose(pal, ref, atol=1e-5)


def test_kv_cache_write_dtype_guard():
    """The pool-write lowerings refuse a payload whose dtype mismatches
    the pool — the bf16-prefill-into-fp32-pool mix fails at trace time
    with both dtypes named (ISSUE 13 kv_sink bugfix)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.decode_ops import (_kv_cache_write,
                                           _kv_cache_write_pages)

    pages = jnp.zeros((4, 2, 2 * 4), jnp.float32)
    new16 = jnp.zeros((3, 2, 4), jnp.bfloat16)
    idx = jnp.zeros(3, jnp.int32)
    with pytest.raises(ValueError, match="does not match the KV pool"):
        _kv_cache_write(None, pages, new16, idx, idx, {})
    with pytest.raises(ValueError, match="does not match the KV pool"):
        _kv_cache_write_pages(None, pages, jnp.zeros((2, 2, 4),
                                                     jnp.bfloat16),
                              jnp.zeros(1, jnp.int32), {})
    # matched dtype writes land at the addressed coordinates
    out = _kv_cache_write(None, pages,
                          jnp.ones((3, 2, 4), jnp.float32), idx,
                          jnp.asarray([0, 1, 1], jnp.int32), {})
    assert np.asarray(out)[0, 1].max() == 1.0


def test_kv_cache_write_trash_duplicates_leave_other_pages_alone():
    """A decode step with ONE live slot: the idle slots all write the
    trash page at (0, 0) — duplicate scatter coordinates — and the live
    one writes (3, 1).  On the flat pool every other page, and every
    other row of page 3, comes back bit-identical, and the live row
    holds its heads side by side."""
    import jax.numpy as jnp

    from paddle_tpu.ops.decode_ops import _kv_cache_write

    rng = np.random.RandomState(11)
    n, d = 3, 4
    base = rng.randn(5, 2, n * d).astype("float32")
    new = rng.randn(4, n, d).astype("float32")
    page_idx = jnp.asarray([0, 3, 0, 0], jnp.int32)
    offset = jnp.asarray([0, 1, 0, 0], jnp.int32)
    out = np.asarray(_kv_cache_write(None, jnp.asarray(base),
                                     jnp.asarray(new), page_idx, offset,
                                     {}))
    assert out.shape == base.shape
    np.testing.assert_array_equal(out[3, 1], new[1].reshape(-1))
    np.testing.assert_array_equal(out[3, 1].reshape(n, d)[2], new[1, 2])
    touched = np.zeros(base.shape[:2], bool)
    touched[0, 0] = touched[3, 1] = True
    np.testing.assert_array_equal(out[~touched], base[~touched])
    # the trash row holds one of the idle slots' payloads, whole
    assert any(np.array_equal(out[0, 0], new[b].reshape(-1))
               for b in (0, 2, 3))


def test_kv_cache_write_ops_numeric():
    """Program-level numeric pin for both pool-write ops:
    layers.kv_cache_write scatters per-slot (page, offset) rows and
    layers.kv_cache_write_pages scatters whole prefill pages, each
    matching the numpy oracle — and the persistable pool var carries
    the update back to the scope (the in-place PagesOut contract)."""
    L = fluid.layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        blk = main.global_block()
        pool_t = blk.create_var(name="kvw_pool_t", shape=[5, 2, 2 * 3],
                                dtype="float32", persistable=True)
        new = fluid.data("kvw_new", [3, 2, 3], False, dtype="float32")
        pg = fluid.data("kvw_pg", [3], False, dtype="int32")
        off = fluid.data("kvw_off", [3], False, dtype="int32")
        L.kv_cache_write(pool_t, new, pg, off)
        pool_p = blk.create_var(name="kvw_pool_p", shape=[5, 2, 2 * 3],
                                dtype="float32", persistable=True)
        chunk = fluid.data("kvw_chunk", [4, 2, 3], False,
                           dtype="float32")
        cpg = fluid.data("kvw_cpg", [2], False, dtype="int32")
        L.kv_cache_write_pages(pool_p, chunk, cpg)
    rng = np.random.RandomState(7)
    base = rng.randn(5, 2, 2 * 3).astype("float32")
    new_v = rng.randn(3, 2, 3).astype("float32")    # [B, n, d]
    pg_v = np.array([1, 3, 3], np.int32)
    off_v = np.array([0, 1, 0], np.int32)
    chunk_v = rng.randn(4, 2, 3).astype("float32")
    cpg_v = np.array([4, 2], np.int32)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        scope.set("kvw_pool_t", base.copy())
        scope.set("kvw_pool_p", base.copy())
        exe = fluid.Executor(fluid.CPUPlace())
        got_t, got_p = exe.run(
            main, feed={"kvw_new": new_v, "kvw_pg": pg_v,
                        "kvw_off": off_v, "kvw_chunk": chunk_v,
                        "kvw_cpg": cpg_v},
            fetch_list=["kvw_pool_t", "kvw_pool_p"])
        back_t = np.asarray(scope.get("kvw_pool_t"))
        back_p = np.asarray(scope.get("kvw_pool_p"))
    want_t = base.copy()
    for b in range(3):
        want_t[pg_v[b], off_v[b]] = new_v[b].reshape(-1)
    want_p = base.copy()
    want_p[cpg_v] = chunk_v.reshape(2, 2, 2 * 3)
    np.testing.assert_array_equal(np.asarray(got_t), want_t)
    np.testing.assert_array_equal(np.asarray(got_p), want_p)
    np.testing.assert_array_equal(back_t, want_t)
    np.testing.assert_array_equal(back_p, want_p)


def test_kvsink_stamps_cache_dtype():
    """KVSink(dtype=...) inserts an explicit cast op on every captured
    K/V — the program CARRIES the cache dtype instead of inheriting the
    lowering policy's; a plain list keeps the historic pass-through."""
    cfg = gpt.GPTConfig.tiny(**CFG)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.data("i", [-1, 8], False, dtype="int64")
        pos = fluid.data("p", [-1, 8], False, dtype="int64")
        sink = gpt.KVSink(dtype="float32")
        gpt.gpt_decoder(ids, pos, cfg, is_test=True, kv_sink=sink)
    assert len(sink) == cfg.num_layers
    assert sink.shapes and all(len(s) == 4 for s in sink.shapes)
    blk = main.global_block()
    producers = {}
    for op in blk.ops:
        for o in op.output_arg_names:
            producers[o] = op
    for k, v in sink:
        assert producers[k.name].type == "cast"
        assert producers[v.name].type == "cast"
        assert producers[k.name].attrs.get("out_dtype") in (
            "float32", 5)  # proto enum tolerated
    # plain list: no cast stamped (back-compat for the in-graph lanes)
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2), fluid.unique_name.guard():
        ids = fluid.data("i", [-1, 8], False, dtype="int64")
        pos = fluid.data("p", [-1, 8], False, dtype="int64")
        sink2 = []
        gpt.gpt_decoder(ids, pos, cfg, is_test=True, kv_sink=sink2)
    prod2 = {}
    for op in main2.global_block().ops:
        for o in op.output_arg_names:
            prod2[o] = op
    for k, v in sink2:
        assert prod2[k.name].type != "cast"


# ---------------------------------------------------------------------------
# decode lane end to end — one subprocess child, results asserted here
# ---------------------------------------------------------------------------
#
# The device-running e2e gates (parity, zero steady-state compiles,
# eviction replay, chunked prefill, eos) execute in ONE child process
# running tests/decode_e2e_checks.py with the persistent compile cache
# OFF: the jaxlib-0.4.3x XLA:CPU runtime corrupts the heap while
# DESERIALIZING warm compilation-cache entries (the fixture's own
# programs suffice; same class as the aborts cpu_mesh.py documents) and
# the corruption manifests under the engine's allocation churn — warm
# in-process runs aborted 5/6 while cache-off child runs pass 5/5.  The
# test_ring_collectives subprocess precedent: isolation without giving
# up executed coverage.


@pytest.fixture(scope="module")
def e2e():
    """Run the decode e2e child once; returns {check name: "ok"|traceback}."""
    import json
    import os
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "decode_e2e_checks.py")
    last = None
    for attempt in range(2):
        r = subprocess.run(
            [sys.executable, script], capture_output=True, text=True,
            timeout=1200,
            cwd=os.path.dirname(os.path.dirname(script)))
        lines = [ln for ln in r.stdout.splitlines()
                 if ln.startswith("DECODE_E2E_RESULT ")]
        if lines:
            return json.loads(lines[-1][len("DECODE_E2E_RESULT "):])
        last = r
        if r.returncode >= 0:
            break  # a plain failure will not improve on retry
    if last.returncode < 0:  # signal on BOTH attempts: the known abort
        pytest.skip(f"decode e2e child died with signal "
                    f"{-last.returncode} twice (0.4.3x XLA:CPU heap "
                    f"corruption — stable standalone, see "
                    f"decode_e2e_checks.py)")
    raise AssertionError(
        f"decode e2e child produced no result rc={last.returncode}\n"
        f"{last.stderr[-3000:]}")


def _e2e_check(e2e, name):
    res = e2e.get(name)
    assert res is not None, f"child never ran check {name!r}"
    assert res == "ok", f"decode e2e check {name} failed in child:\n{res}"


def test_decode_parity_greedy_bit_exact(e2e):
    """THE acceptance gate: greedy generate() via the paged decode lane
    (chunked prefill + token-level continuous batching + paged
    attention) reproduces the whole-sequence build_gpt_generate lane's
    token ids EXACTLY — same weights, same prompts (child check)."""
    _e2e_check(e2e, "parity_greedy_bit_exact")


@pytest.mark.parametrize("impl", ["pallas", "reference"])
def test_decode_parity_at_benchmark_head_geometry(e2e, impl):
    """The same gate at 20 heads of 64 (GPT-2-large's geometry, two
    layers): the flat pool's lane dimension splits into the heads it was
    written from, in the Pallas kernel (interpret mode) and in the XLA
    reference alike (child checks)."""
    _e2e_check(e2e, f"parity_heads20x64_{impl}")


def test_decode_zero_steady_state_compiles(e2e):
    """After warmup, traffic of ANY mix of prompt lengths and request
    counts runs on exactly two executables (child check)."""
    _e2e_check(e2e, "zero_steady_state_compiles")


def test_decode_eviction_under_pressure_matches_unpressured(e2e):
    """Evicted sequences re-prefill prompt + generated prefix and finish
    with the same tokens as the unpressured run (child check)."""
    _e2e_check(e2e, "eviction_under_pressure_matches_unpressured")


def test_decode_long_prompt_chunked_prefill(e2e):
    """A prompt longer than the chunk streams through several prefill
    executions and matches the one-chunk config (child check)."""
    _e2e_check(e2e, "long_prompt_chunked_prefill")


def test_decode_eos_and_single_token(e2e):
    """max_new_tokens=1 finishes on the prefill seed alone; eos_id stops
    the stream (child check)."""
    _e2e_check(e2e, "eos_and_single_token")


def test_decode_int8_kv_generate_matches_fp32(e2e):
    """DecodeEngine(pool_dtype="int8") — dual-int8 KV pool, dequant
    inside the paged kernel — greedy-generates the same token ids as
    the fp32 lane and books pt_int8_bytes_saved_total (child check)."""
    _e2e_check(e2e, "int8_kv_generate_matches_fp32")


def test_decode_int8_kv_logprob_drift(e2e):
    """20 decode steps through fp32 vs dual-int8 pools: per-step
    logprobs within 0.05 and every greedy argmax agrees (child
    check)."""
    _e2e_check(e2e, "int8_kv_logprob_drift")


def test_decode_int8_weights_generate_matches_fp32(e2e):
    """DecodeEngine(int8_weights=True) — matmul weights stored
    dual-int8 at rest, reconstructed on-chip by
    dequantize_weight_storage — greedy-generates the same token ids as
    the fp32 lane and books pt_int8_bytes_saved_total{kind="weights"}
    (child check)."""
    _e2e_check(e2e, "int8_weights_generate_matches_fp32")


# ---------------------------------------------------------------------------
# host-side engine surface (no device execution — safe in-process)
# ---------------------------------------------------------------------------


def test_decode_submit_validation_and_close():
    """Typed submit-edge validation and close() failing leftover futures
    — pure host paths, no program ever executes (untrained scope)."""
    cfg = gpt.GPTConfig.tiny(**CFG)
    scope = fluid.Scope()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=8,
                               name="valid", auto_start=False)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], 0)
    with pytest.raises(ValueError, match="exceeds the engine's max_len"):
        eng.submit([1, 2, 3, 4, 5], 4)
    fut = eng.submit([1, 2], 2)
    eng.close()
    with pytest.raises(serving.ServingOverloadError):
        fut.result(timeout=10)
    with pytest.raises(serving.ServingOverloadError):
        eng.submit([1, 2], 2)


def test_decode_prefill_chunk_floors_and_validates():
    """A page_size above max_len used to round the derived
    prefill_chunk down to 0 — a zero-token chunk never advances prefill
    and the scheduler livelocks; the derived default now floors at one
    whole page, and an explicit non-positive / non-page-multiple chunk
    fails typed at construction."""
    cfg = gpt.GPTConfig.tiny(**CFG)
    scope = fluid.Scope()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=32, max_len=16,
                               name="floor", auto_start=False)
    try:
        assert eng.prefill_chunk == 32  # one whole page, not 0
    finally:
        eng.close()
    for bad in (0, -4, 6):  # 6 is not a multiple of page_size 4
        with pytest.raises(ValueError, match="positive multiple"):
            serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                                 page_size=4, prefill_chunk=bad,
                                 max_len=16, name="bad",
                                 auto_start=False)


def test_decode_dead_scheduler_rejects_submits_typed():
    """After an executor failure kills the scheduler (every live future
    fails), the engine must not accept new work into the dead queue —
    a submitted future would hang forever; submit() rejects typed and
    stats() names the failure."""
    cfg = gpt.GPTConfig.tiny(**CFG)
    scope = fluid.Scope()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=16,
                               name="dead", auto_start=False)
    try:
        fut = eng.submit([1, 2], 2)
        eng._fail_all(RuntimeError("device fell over"))
        with pytest.raises(RuntimeError, match="device fell over"):
            fut.result(timeout=10)
        with pytest.raises(serving.ServingOverloadError,
                           match="scheduler died"):
            eng.submit([1, 2], 2)
        assert "device fell over" in eng.stats()["failed"]
    finally:
        eng.close()


def test_decode_servez_section():
    """DecodeEngine registers on /servez: the payload carries a decode
    section with slot/pool/eviction figures while the engine lives and
    drops it at close (no device execution — untrained scope)."""
    from paddle_tpu.serving import status

    cfg = gpt.GPTConfig.tiny(**CFG)
    scope = fluid.Scope()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=16,
                               name="servez-decode", auto_start=False)
    try:
        payload = status.servez_payload()
        names = [d["engine"] for d in payload["decode"]]
        assert "servez-decode" in names
        entry = [d for d in payload["decode"]
                 if d["engine"] == "servez-decode"][0]
        assert entry["pool_slots"] == 2
        assert "kv_pool" in entry and "evictions" in entry
    finally:
        eng.close()
    assert "servez-decode" not in [
        d["engine"] for d in status.servez_payload()["decode"]]


# ---------------------------------------------------------------------------
# per-tenant quotas + graceful drain (ISSUE 14 satellites — host-side,
# no device execution: untrained scope, auto_start=False)
# ---------------------------------------------------------------------------


def test_decode_tenant_quota_rejects_typed():
    """FLAGS_serving_tenant_quota (here the ctor override): one tenant's
    LIVE footprint (queued + ready + decoding) is capped; the rejection
    is typed with reason="tenant_quota" and books
    pt_serve_rejected_total{model,reason} — while OTHER tenants keep
    being admitted (per-tenant pressure, not engine overload)."""
    cfg = gpt.GPTConfig.tiny(**CFG)
    scope = fluid.Scope()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=16,
                               name="quota", auto_start=False,
                               tenant_quota=2)
    try:
        eng.submit([1, 2], 2, tenant="acme")
        eng.submit([1, 2], 2, tenant="acme")
        with pytest.raises(serving.ServingOverloadError,
                           match="tenant") as ei:
            eng.submit([1, 2], 2, tenant="acme")
        assert ei.value.reason == "tenant_quota"
        # a different tenant still gets in
        eng.submit([1, 2], 2, tenant="other")
        from paddle_tpu import observability as obs

        fam = obs.snapshot().get("pt_serve_rejected_total", {})
        assert fam.get("samples", {}).get(("quota", "tenant_quota"),
                                          0) >= 1
        assert eng.stats()["tenant_quota"] == 2
    finally:
        eng.close()


def test_decode_tenant_quota_zero_is_unlimited():
    cfg = gpt.GPTConfig.tiny(**CFG)
    scope = fluid.Scope()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=16,
                               name="noquota", auto_start=False,
                               tenant_quota=0)
    try:
        for _ in range(5):
            eng.submit([1, 2], 2, tenant="acme")
    finally:
        eng.close()


def test_decode_drain_fails_queued_typed_and_stops_admission():
    """drain(): queued futures fail typed with reason="draining" (their
    pool pages return), new submits reject typed, and the scheduler's
    flush half (_flush_for_drain — exercised synchronously here, no
    device) marks the engine drained once nothing is in flight."""
    cfg = gpt.GPTConfig.tiny(**CFG)
    scope = fluid.Scope()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=16,
                               name="drainage", auto_start=False)
    try:
        f1 = eng.submit([1, 2], 2)
        f2 = eng.submit([3, 4, 5], 4)
        assert eng.drain() is True
        eng._flush_for_drain()  # the scheduler-thread half, run inline
        for f in (f1, f2):
            with pytest.raises(serving.ServingOverloadError) as ei:
                f.result(timeout=10)
            assert ei.value.reason == "draining"
        with pytest.raises(serving.ServingOverloadError) as ei:
            eng.submit([1, 2], 2)
        assert ei.value.reason == "draining"
        assert eng._drained.is_set()
        assert eng.stats()["draining"] is True
        assert eng.pool.pages_in_use() == 0  # victims freed their pages
    finally:
        eng.close()


def test_decode_drain_on_sigterm_hook(monkeypatch):
    """The elastic.DrainHandler hookup: when the process drain handler
    reports a SIGTERM, the next scheduler iteration flips the lane into
    draining WITHOUT anyone calling drain() — admission stops typed.
    (drain_requested is monkeypatched; a real signal would race the
    test runner.)  _step_once on an empty engine performs no device
    work."""
    from paddle_tpu.serving import decode as decode_mod

    cfg = gpt.GPTConfig.tiny(**CFG)
    scope = fluid.Scope()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=16,
                               name="sigdrain", auto_start=False)
    try:
        from paddle_tpu.distributed import elastic

        monkeypatch.setattr(elastic, "drain_requested", lambda: True)
        eng._step_once()  # one scheduler iteration, empty engine
        assert eng.stats()["draining"] is True
        with pytest.raises(serving.ServingOverloadError) as ei:
            eng.submit([1, 2], 2)
        assert ei.value.reason == "draining"
    finally:
        eng.close()


def test_decode_drain_on_sigterm_opt_out(monkeypatch):
    """drain_on_sigterm=False: a replica that owns its own drain
    choreography is not flipped by the process handler."""
    cfg = gpt.GPTConfig.tiny(**CFG)
    scope = fluid.Scope()
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=16,
                               name="optout", auto_start=False,
                               drain_on_sigterm=False)
    try:
        from paddle_tpu.distributed import elastic

        monkeypatch.setattr(elastic, "drain_requested", lambda: True)
        eng._step_once()
        assert eng.stats()["draining"] is False
        eng.submit([1, 2], 2)  # admission unaffected
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# which prefill chunks are waited for (ISSUE 50): a chunk whose token
# nobody reads is enqueued, and the turn goes on while it runs.  Untrained
# weights, in-process, tiny: what is checked is the scheduler, not the model
# ---------------------------------------------------------------------------

S_NAME, S_ID, S_PARENT = 0, 4, 5


def _overlap_engine(name, auto_start=False, **kw):
    cfg = gpt.GPTConfig.tiny(**CFG)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        gpt.build_gpt_lm(cfg)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    kw = dict(dict(pool_slots=2, page_size=4, prefill_chunk=4, max_len=32),
              **kw)
    eng = serving.DecodeEngine(cfg, scope=scope, name=name,
                               auto_start=False, **kw)
    eng.warmup()
    if auto_start:
        eng.start()
    return eng


def _counts(name):
    from paddle_tpu import observability as obs

    snap = obs.snapshot()
    return {k: snap.get(f"pt_decode_prefill_{k}_total", {}).get(
        "samples", {}).get((name,), 0)
        for k in ("chunks", "unawaited", "head_runs")}


def _drive(eng, *futures, turns=400):
    for _ in range(turns):
        if all(f.done() for f in futures):
            return
        eng._step_once()
    raise AssertionError("the requests did not finish")


class _Handle:
    """A chunk's output as the engine keeps it, with what became of it."""

    def __init__(self, out, fail=None, delay=0.0):
        self.out, self.fail, self.delay = out, fail, delay
        self.awaited = False

    def block_until_ready(self):
        import time

        import jax

        time.sleep(self.delay)
        jax.block_until_ready(self.out)
        self.awaited = True
        if self.fail is not None:
            raise self.fail
        return self


def _wrap_unawaited(monkeypatch, eng, make):
    """Every run the engine does not wait for hands back ``make(out, k)``
    (k counts such runs from 0) in place of its one output; returns the
    list that holds them as they are made."""
    run, made = eng._exe.run, []

    def wrapped(*a, **kw):
        outs = run(*a, **kw)
        if kw.get("return_numpy", True):
            return outs
        made.append(make(outs[0], len(made)))
        return [made[-1]]

    monkeypatch.setattr(eng._exe, "run", wrapped)
    return made


@pytest.mark.parametrize("prompt_len, prefix, n_chunks, awaited", [
    (6, None, 2, 1),      # a fresh prompt: its last chunk seeds the stream
    (15, None, 4, 1),
    # a resumed request replays prompt + prefix[:-1] = 9 tokens and reads
    # no chunk's token: none of its chunks is waited for
    (7, [9, 8, 7], 3, 0),
])
def test_prefill_waits_only_for_the_chunk_whose_token_is_read(
        prompt_len, prefix, n_chunks, awaited):
    from paddle_tpu.observability import profiling

    eng = _overlap_engine(f"unawaited-{prompt_len}")
    try:
        before = _counts(eng.name)
        profiling.reset()
        req = eng.submit_request(list(range(1, prompt_len + 1)), 6,
                                 prefix=prefix)
        _drive(eng, req.future)
        assert len(req.future.result()) == 6
        got = {k: v - before[k] for k, v in _counts(eng.name).items()}
        assert got == {"chunks": n_chunks, "unawaited": n_chunks - awaited,
                       "head_runs": awaited}
        spans = profiling.spans()
        runs = {s[S_ID] for s in spans if s[S_NAME] == "prefill.run"}
        assert len(runs) == awaited
        waits = [s for s in spans if s[S_NAME] == "fetch_wait"
                 and s[S_PARENT] in runs]
        assert len(waits) == awaited
        # the other chunks' executor spans lie directly under their turn
        turns = {s[S_ID] for s in spans if s[S_NAME] == "turn"}
        enqueued = [s for s in spans if s[S_NAME] == "dispatch"
                    and s[S_PARENT] in turns]
        assert len(enqueued) == n_chunks - awaited
        # every fetch_wait of the window belongs to a `*.run` span
        run_ids = {s[S_ID] for s in spans if s[S_NAME].endswith(".run")}
        assert all(s[S_PARENT] in run_ids for s in spans
                   if s[S_NAME] == "fetch_wait")
        assert not eng._in_flight  # the steps that followed settled them
    finally:
        eng.close()


def test_overlapped_chunks_serve_the_tokens_of_blocking_chunks(monkeypatch):
    """Multi-chunk prompts interleaved with live decode rows on a pool too
    small for them, so sequences are evicted while a chunk is in flight:
    token for token what the same engine serves when every chunk blocks."""
    eng = _overlap_engine("overlap-parity", pool_slots=3, num_pages=13,
                          max_len=28)
    rng = np.random.RandomState(50)
    prompts = [list(rng.randint(1, 50, size=n)) for n in
               (5, 14, 9, 17, 3, 11)]
    try:
        in_flight_at_eviction = []
        evict = eng._evict_one

        def evicting(protect):
            in_flight_at_eviction.append(len(eng._in_flight))
            return evict(protect)

        monkeypatch.setattr(eng, "_evict_one", evicting)

        def serve():
            futs = []
            for p in prompts:  # arrivals spread over the turns
                futs.append(eng.submit(p, 10))
                eng._step_once()
                eng._step_once()
            _drive(eng, *futs)
            return [f.result() for f in futs]

        before = _counts(eng.name)
        overlapped = serve()
        assert _counts(eng.name)["unawaited"] > before["unawaited"]
        assert eng.stats()["evictions"] > 0
        assert max(in_flight_at_eviction) >= 1
        # the same engine with every chunk's run made a blocking one
        run = eng._exe.run
        monkeypatch.setattr(
            eng._exe, "run",
            lambda *a, **kw: run(*a, **dict(kw, return_numpy=True)))
        blocking = serve()
        assert overlapped == blocking
        assert all(len(t) == 10 for t in overlapped)
    finally:
        eng.close()


def test_no_more_than_two_chunks_run_ahead_of_the_host(monkeypatch):
    """A prompt of seven chunks and no live slot: no decode step blocks,
    so the engine itself waits for the older chunk before a third is
    enqueued."""
    eng = _overlap_engine("two-ahead")
    try:
        made = _wrap_unawaited(monkeypatch, eng, lambda out, k: _Handle(out))
        fut = eng.submit(list(range(1, 27)), 2)
        ahead = []
        for _ in range(6):  # the six chunks nobody reads
            eng._step_once()
            ahead.append(sum(not h.awaited for h in made))
        assert ahead == [1, 2, 2, 2, 2, 2] and len(made) == 6
        assert len(eng._in_flight) == 2
        _drive(eng, fut)
        assert all(h.awaited for h in made)
        assert len(fut.result()) == 2
    finally:
        eng.close()


def test_a_failed_unawaited_chunk_fails_the_live_futures(monkeypatch):
    """The device error of a chunk nobody waited for is raised on the
    scheduler thread at its next blocking call (here the decode step of
    the same turn) and goes the way of any run error: every live future
    fails with it, and the engine takes no more work."""
    eng = _overlap_engine("chunk-fails")
    try:
        boom = RuntimeError("the chunk fell over on the device")
        made = _wrap_unawaited(monkeypatch, eng, lambda out, k: _Handle(
            out, fail=boom if k == 1 else None))
        live = eng.submit([1, 2, 3], 25)  # one chunk, then decoding
        eng._step_once()
        long = eng.submit(list(range(1, 20)), 4)   # five chunks
        queued = eng.submit([4, 5], 4)
        eng.start()
        for fut in (live, long, queued):
            with pytest.raises(RuntimeError, match="fell over") as ei:
                fut.result(timeout=60)
            assert ei.value is boom
        eng._thread.join(timeout=30)
        assert not eng._thread.is_alive() and not eng._in_flight
        assert "fell over" in eng.stats()["failed"]
        assert len(made) == 2  # no chunk went out after the error
        with pytest.raises(serving.ServingOverloadError,
                           match="scheduler died"):
            eng.submit([1, 2], 2)
    finally:
        eng.close()


@pytest.mark.parametrize("how", ["drain", "close"])
def test_drain_and_close_return_after_the_chunk_in_flight(monkeypatch, how):
    """`drain(timeout)` and `close()` mean nothing is in flight: both
    return only once the chunk that nobody waited for has run."""
    import time

    eng = _overlap_engine(f"settle-{how}", auto_start=True)
    try:
        made = _wrap_unawaited(monkeypatch, eng,
                               lambda out, k: _Handle(out, delay=0.3))
        fut = eng.submit(list(range(1, 27)), 2)  # seven chunks
        deadline = time.monotonic() + 60
        while not made and time.monotonic() < deadline:
            time.sleep(0.005)
        assert made, "no chunk was enqueued"
        if how == "drain":
            assert eng.drain(timeout=60) is True
            assert eng.pool.pages_in_use() == 0  # the victim's came back
        else:
            eng.close()
        assert all(h.awaited for h in made)
        assert not eng._in_flight
        with pytest.raises(serving.ServingOverloadError) as ei:
            fut.result(timeout=10)
        assert ei.value.reason == {"drain": "draining", "close": "closed"}[how]
    finally:
        eng.close()
