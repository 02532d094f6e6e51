"""Trinity through the decode lane (models/trinity.py, serving/lane.py
``layer_windows``, serving/kv_pool.py kinds): window and full
grouped-query layers in a pool with a page list a kind, window pages
given back mid-request, gated attention, held experts — against the
plain reference (benchmark/reference/trinity.py, which imports nothing of
the program) at a tiny size with seeded float32 weights: hidden 64, 6
query / 2 K/V heads, W 8, page 4, layers s,s,s,f,s, 16 experts top-2
with 4 held."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import fluid, serving
from paddle_tpu import observability as obs
from paddle_tpu.models import trinity
from paddle_tpu.serving import lane
from paddle_tpu.serving.errors import PoolExhaustedError
from paddle_tpu.serving.kv_pool import KVPool, TRASH_PAGE

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "trinity-tiny.json")) as _f:
    CONFIG = json.load(_f)
ref = harness.load_module("reference", "trinity.py")
W, PAGE, CHUNK = 8, 4, 8


def _cfg(**over):
    return trinity.TrinityConfig(**dict(CONFIG["builder"]["config_args"],
                                        **over))


def _scope_with(weights):
    scope = fluid.Scope()
    for name, w in weights.items():
        scope.set(name, w)
    return scope


@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(CONFIG, 20260928)


def _served_gaps(weights, prompts, outs, config=CONFIG):
    gaps = []
    with jax.default_matmul_precision("highest"):
        for p, o in zip(prompts, outs):
            logits = ref.served_logits(weights, config, p, o)
            got = jnp.take_along_axis(
                logits, jnp.asarray(o, jnp.int32)[:, None], axis=1)[:, 0]
            gaps.append(float(jnp.max(jnp.max(logits, axis=1) - got)))
    return gaps


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "trinity.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+(paddle_tpu|benchmark)", src,
                         re.M)


def test_program_parameters_are_the_references():
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        trinity.build_trinity_lm(_cfg())
    want = {p.name: tuple(p.shape)
            for p in main.global_block().all_parameters()}
    have = {n: tuple(s) for n, (s, _, _) in ref.param_shapes(CONFIG).items()}
    assert have == want


def test_the_lane_declares_a_kind_a_layer_and_refuses_an_int8_pool():
    cfg = _cfg()
    decl = cfg.decode_lane()
    assert decl.layer_windows == [W, W, W, None, W]
    assert [r.name for r in decl.cache_rows(None)] == ["k", "v"]
    assert decl.cache_rows(None)[0].width == 2 * 16
    with pytest.raises(ValueError, match="no int8 form"):
        decl.cache_rows("int8")
    assert len(decl.device_counters) == 2 * 4    # 4 expert layers x 2
    with pytest.raises(ValueError, match="layer_windows names 2 layers"):
        lane.DecodeLane(num_layers=3, max_position=8, cache_rows=None,
                        build_decode_step=None, build_prefill_chunk=None,
                        layer_windows=[None, 4])


# ---------------------------------------------------------------------------
# engine: prefill chunks, then decode steps, through both kinds of cache,
# against the reference's full forward; contexts several windows long
# ---------------------------------------------------------------------------

PROMPTS = (30, 5, 45, 17)       # 0.6 to 5.6 windows; 5 fits inside one


def _generate(weights, force=None, n_new=12, **engine):
    cfg = _cfg()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in PROMPTS]
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=3, page_size=PAGE,
        max_len=64, attn_force=force, auto_start=False,
        name=f"trinity-{force}-{len(engine)}", **engine)
    try:
        eng.warmup()
        eng.start()
        outs = eng.generate(prompts, max_new_tokens=n_new, timeout=600)
        eng.book_device_counters()
        return eng, prompts, outs, eng.stats()
    finally:
        eng.close()


@pytest.mark.parametrize("force", [None, "pallas"])
def test_engine_matches_the_reference_and_frees_window_pages(weights, force):
    eng, prompts, outs, stats = _generate(weights, force)
    assert all(len(o) == 12 for o in outs)
    assert max(_served_gaps(weights, prompts, outs)) < 1e-3
    assert stats["evictions"] == 0
    kinds = stats["kv_pool"]["kinds"]
    assert set(kinds) == {"full", "window8"}
    # the pool's tensors: every slot at full length, or a window's worth
    per_seq = lane.window_pages_per_seq(W, CHUNK, PAGE)
    assert per_seq == 5
    assert eng.pool.pages_by_kind() == {"full": 3 * 16 + 1,
                                        "window8": 3 * per_seq + 1}
    assert np.shape(eng.scope.get(eng.pool.var_names[3][0])) == (49, 4, 32)
    assert np.shape(eng.scope.get(eng.pool.var_names[0][0])) == (16, 4, 32)
    # both kinds handed out the same logical pages; the window kind gave
    # most of them back while their requests lived
    full, window = kinds["full"], kinds["window8"]
    assert full["alloc_total"] == window["alloc_total"] == sum(
        -(-(n + 11) // PAGE) for n in PROMPTS)
    assert full["freed"] == {"window": 0, "end": full["alloc_total"],
                             "evict": 0}
    assert window["freed"]["window"] > window["freed"]["end"] > 0
    assert sum(window["freed"].values()) == window["alloc_total"]
    assert full["pages_in_use"] == window["pages_in_use"] == 0
    # ... and the counters say so
    snap = obs.snapshot()
    freed = snap["pt_kv_pages_freed_total"]["samples"]
    alloc = snap["pt_kv_pages_alloc_total"]["samples"]
    assert freed[(eng.name, "window8", "window")] == window["freed"]["window"]
    assert freed[(eng.name, "full", "end")] == full["alloc_total"]
    assert alloc[(eng.name, "window8")] == window["alloc_total"]
    assert snap["pt_kv_pages_in_use"]["samples"][(eng.name, "full")] == 0
    picks = snap["pt_moe_picks_total"]["samples"]
    assert picks[(eng.name, "any")] == (picks[(eng.name, "held")]
                                        + picks[(eng.name, "absent")]) > 0


def test_eviction_and_replay_cover_both_kinds(weights):
    """A pool too small for three sequences: the youngest is evicted, its
    pages of BOTH kinds go back, and its replay serves the same tokens."""
    _, prompts, want, _ = _generate(weights)
    eng, _, outs, stats = _generate(weights, num_pages=20)
    assert outs == want
    assert stats["evictions"] > 0
    kinds = stats["kv_pool"]["kinds"]
    assert kinds["full"]["freed"]["evict"] > 0
    assert kinds["window8"]["freed"]["evict"] > 0
    assert kinds["window8"]["freed"]["window"] > 0
    for k in kinds.values():
        assert k["pages_in_use"] == 0
        assert sum(k["freed"].values()) == k["alloc_total"]


def test_the_kernel_names_say_which_kind_they_serve():
    from paddle_tpu.kernels.primitives import paged

    assert paged.kernel_name(1, None) == "paged_attention"
    assert paged.kernel_name(6, None) == "paged_attention_grouped"
    assert paged.kernel_name(6, 4096) == "paged_attention_grouped_window"
    assert paged.kernel_name(1, 8) == "paged_attention_window"


# ---------------------------------------------------------------------------
# the pool's kinds
# ---------------------------------------------------------------------------


def _pool(**kw):
    rows = lane.kv_rows(2, 16)
    return KVPool(5, rows, 33, PAGE, 8, layer_windows=[W, W, W, None, W],
                  window_pages={"window8": 11}, **kw)


def test_a_freed_window_page_is_trash_in_the_table_and_reusable_at_once():
    pool = _pool()
    assert pool.kinds == ["full", "window8"]
    pool.open_seq("a")
    pool.ensure_capacity("a", 20)                    # 5 pages of each kind
    before = pool.table("a", "window8")
    assert pool.release("a", 11) == 0                # 11 - 8 = 3: no whole page
    assert pool.release("a", 12) == 1                # page 0 = keys 0..3
    assert pool.release("a", 12) == 0                # idempotent
    assert pool.release("a", 20) == 2                # pages 1, 2
    table = pool.table("a", "window8")
    assert table[:3] == [TRASH_PAGE] * 3 and table[3:] == before[3:]
    assert list(pool.padded_table("a", "window8")[:5]) == table
    assert pool.table("a", "full") == pool.table("a")    # untouched
    assert TRASH_PAGE not in pool.table("a", "full")
    assert pool.pages_in_use("window8") == 2
    assert pool.pages_in_use("full") == 5 and pool.pages_in_use() == 7
    # the next allocation of that kind comes from the batch freed last
    # (LIFO; a batch goes back so that its first page is handed out first)
    pool.open_seq("b")
    pool.ensure_capacity("b", 4)
    assert pool.table("b", "window8") == [before[1]]
    # growth allocates nothing below what was released
    pool.ensure_capacity("a", 32)
    grown = pool.table("a", "window8")
    assert grown[:3] == [TRASH_PAGE] * 3 and TRASH_PAGE not in grown[3:]


def test_no_kind_leaks_after_free_seq_and_exhaustion_names_the_kind():
    pool = _pool()
    for s in "abc":
        pool.open_seq(s)
    pool.ensure_capacity("a", 16)
    pool.ensure_capacity("b", 16)
    with pytest.raises(PoolExhaustedError, match="kind 'window8'"):
        pool.ensure_capacity("c", 16)                # 10 window pages in all
    pool.release("a", 16)
    assert pool.free_seq("a") == 4 + 2               # full + what was left
    pool.ensure_capacity("c", 16)                    # now it fits
    for s in "bc":
        pool.free_seq(s, why="evict")
    assert pool.pages_in_use() == 0 and pool.live_seqs() == []
    st = pool.kind_stats()
    assert st["window8"]["freed"] == {"window": 2, "end": 2, "evict": 8}
    assert st["full"]["freed"] == {"window": 0, "end": 4, "evict": 8}
    assert pool.stats()["free_total"] == pool.stats()["alloc_total"] == 24


def test_a_one_kind_lane_allocates_what_it_always_did():
    """The same calls against a pool that declares nothing and against
    the allocator of the parent commit's rule (LIFO free list from page
    1 up): page for page."""
    rows = lane.kv_rows(2, 16)
    pool = KVPool(3, rows, 9, PAGE, 4)
    assert pool.kinds == ["full"] and pool.pages_by_kind() == {"full": 9}
    free = list(range(1, 9))
    want = {}
    script = [("a", 5), ("b", 9), ("a", 12), ("-", "b"), ("c", 16),
              ("-", "a"), ("b", 3)]
    for seq, n in script:
        if seq == "-":
            for p in reversed(want.pop(n)):
                free.append(p)
            pool.free_seq(n)
            continue
        if seq not in want:
            want[seq] = []
            pool.open_seq(seq)
        while len(want[seq]) < -(-n // PAGE):
            want[seq].append(free.pop())
        assert pool.ensure_capacity(seq, n) == want[seq]
        assert pool.release(seq, n) == 0
        assert list(pool.padded_table(seq)[:len(want[seq])]) == want[seq]
    assert pool.pages_in_use() == 8 - len(free)
    assert pool.row_bytes(rows[0]) == 9 * 4 * 32 * 4 * 3
    assert pool.row_bytes(rows[0], pages=2) == 2 * 4 * 32 * 4 * 3
    assert set(pool.stats()) >= {"pages_total", "pages_in_use", "page_size",
                                 "live_seqs", "alloc_total", "free_total",
                                 "reused_allocs"}


def test_the_pool_is_sized_a_kind_and_its_bytes_are_counted_a_kind():
    pool = _pool()
    k = lane.kv_rows(2, 16)[0]
    # 4 window layers of 11 pages, 1 full layer of 33
    assert pool.row_bytes(k) == (4 * 11 + 33) * PAGE * 32 * 4
    assert pool.row_bytes(k, {"full": 2, "window8": 1}) == 6 * PAGE * 32 * 4
    assert pool.modeled_bytes() == 2 * pool.row_bytes(k)
    scope = fluid.Scope()
    pool.install(scope)
    shapes = [np.shape(scope.get(names[0])) for names in pool.var_names]
    assert shapes == [(11, 4, 32)] * 3 + [(33, 4, 32), (11, 4, 32)]


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------


def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Eight chips of an EP8 deployment at the tiny size: the routed
    parts that the eight shares give, with the shared expert — which
    every chip computes alike — counted once, equal the reference's
    uncut expert layer; through the program's op for each share."""
    experts, held, d, f = 16, 2, 64, 32
    whole = dict(CONFIG, num_experts=experts, num_experts_total=experts,
                 deployment={"first_expert": 0})
    z = dict(ref.sizes(whole), scaling=2.448, route_norm=True)
    rng = np.random.RandomState(11)
    p = {"moe_router.w_0": rng.randn(d, experts) * 0.5,
         "moe_router.b_0": rng.randn(experts) * 0.1,
         **{f"moe_experts_{k}.w_0": rng.randn(*s) * 0.2
            for k, s in (("gate", (experts, d, f)), ("up", (experts, d, f)),
                         ("down", (experts, f, d)))},
         **{f"shared_{k}.w_0": rng.randn(*s) * 0.2
            for k, s in (("gate", (d, f)), ("up", (d, f)),
                         ("down", (f, d)))}}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    x = jnp.asarray(rng.randn(24, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = (ref.routed_experts(x, p, z, jnp.matmul)
                 + ref.shared_expert(x, p, jnp.matmul))
        total = ref.shared_expert(x, p, jnp.matmul)          # counted once
        from_program = jnp.zeros_like(total)
        for chip in range(experts // held):
            first = chip * held
            share = dict(p, **{
                f"moe_experts_{k}.w_0": p[f"moe_experts_{k}.w_0"][
                    first:first + held] for k in ("gate", "up", "down")})
            zs = dict(z, held=held, first=first)
            part = ref.routed_experts(x, share, zs, jnp.matmul)
            total = total + part
            from_program = from_program + _program_share(x, share, first,
                                                         held, experts)
            np.testing.assert_allclose(
                np.asarray(_program_share(x, share, first, held, experts)),
                np.asarray(part), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=1e-5)
    assert float(jnp.abs(from_program).max()) > 0.1


def _program_share(x, share, first, held, experts):
    """One share's routed part through ``layers.moe_ffn_held``."""
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        xv = fluid.data("x", [1, x.shape[0], x.shape[1]], False,
                        dtype="float32")
        out = fluid.layers.moe_ffn_held(
            xv, experts, held, 32, 2, first_expert=first,
            routed_scaling_factor=2.448, norm_topk_prob=True, name="m")
    scope = fluid.Scope()
    for k, v in share.items():
        if k.startswith("moe_"):
            scope.set("m_" + k[len("moe_"):], v)
    with fluid.scope_guard(scope):
        (got,) = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": np.asarray(x)[None]}, fetch_list=[out.name])
    return jnp.asarray(got)[0]


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------


def test_rope_half_and_sigmoid_gate_ops_match_the_reference():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 5, 3, 16).astype(np.float32)
    gate = rng.randn(2, 5, 3, 16).astype(np.float32)
    pos = np.array([[0, 1, 2, 3, 40], [7, 8, 9, 10, 11]], np.int64)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        xv = fluid.data("x", list(x.shape), False, dtype="float32")
        gv = fluid.data("g", list(x.shape), False, dtype="float32")
        pv = fluid.data("p", [2, 5], False, dtype="int64")
        turned = fluid.layers.rope_half(xv, pv, theta=10000.0)
        gated = fluid.layers.sigmoid_gate(xv, gv)
    got_t, got_g = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x, "g": gate, "p": pos},
        fetch_list=[turned.name, gated.name])
    for b in range(2):
        np.testing.assert_allclose(
            got_t[b], np.asarray(ref.rope(jnp.asarray(x[b]),
                                          jnp.asarray(pos[b]), 10000.0)),
            atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_g, x / (1.0 + np.exp(-gate)), atol=1e-6,
                               rtol=1e-5)
    # position 0 turns nothing; a turn keeps each pair's length
    np.testing.assert_allclose(got_t[0, 0], x[0, 0], atol=1e-6)
    np.testing.assert_allclose(
        got_t[..., :8] ** 2 + got_t[..., 8:] ** 2,
        x[..., :8] ** 2 + x[..., 8:] ** 2, atol=1e-4, rtol=1e-4)
