"""MiMo-V2 through the decode lane (models/mimo.py, serving/lane.py rows
BY KIND, kernels/primitives/paged.py asymmetric and sink forms): window
layers with a learned sink beside full layers, two K/V head counts, K
heads wider than V heads, rotary positions on part of a head, held
experts and no shared one — against the plain reference
(benchmark/reference/mimo.py, which imports nothing of the program) at a
tiny size with seeded float32 weights: hidden 64, 8 query heads on 2
(full) / 4 (window) K/V heads, K 24 / V 16 wide, 8 rotated entries, W 6
(shorter than the 8-token chunk), page 4, layers f,w,w,w,w,f,w, 16
experts top-2 with 4 held."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import fluid, serving
from paddle_tpu import observability as obs
from paddle_tpu.kernels.primitives import paged
from paddle_tpu.models import mimo
from paddle_tpu.serving import lane
from paddle_tpu.serving.kv_pool import KVPool

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "mimo-tiny.json")) as _f:
    CONFIG = json.load(_f)
ref = harness.load_module("reference", "mimo.py")
W, PAGE, CHUNK = 6, 4, 8
SEQ = 40    # whole-sequence program: 6.7 windows, not a multiple of W


def _cfg(**over):
    return mimo.MiMoConfig(**dict(CONFIG["builder"]["config_args"], **over))


def _scope_with(weights):
    scope = fluid.Scope()
    for name, w in weights.items():
        scope.set(name, w)
    return scope


@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(CONFIG, 20261001)


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
    with open(os.path.join(ROOT, "benchmark", "reference", "mimo.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+(paddle_tpu|benchmark)", src,
                         re.M)


def _lm_params(cfg):
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        mimo.build_mimo_lm(cfg)
    return {p.name: tuple(p.shape)
            for p in main.global_block().all_parameters()}


def test_program_parameters_are_the_references():
    have = {n: tuple(s) for n, (s, _, _) in ref.param_shapes(CONFIG).items()}
    assert have == _lm_params(_cfg())
    # sinks in the window layers only, one logit a query head
    sinks = sorted(n for n in have if n.endswith("_sink.b_0"))
    assert sinks == [f"mimo_layer_{n}_sink.b_0" for n in (1, 2, 3, 4, 6)]
    assert {have[n] for n in sinks} == {(8,)}
    # a V projection read at K's width is another model
    assert _lm_params(_cfg(v_head_dim=24)) != have
    assert have["mimo_layer_0_v.w_0"] == (64, 2 * 16)
    assert have["mimo_layer_1_v.w_0"] == (64, 4 * 16)
    assert have["mimo_layer_1_k.w_0"] == (64, 4 * 24)
    assert have["mimo_layer_1_o.w_0"] == (8 * 16, 64)
    # no shared expert, no q/k norm, two norms a block
    assert not [n for n in have if "shared" in n or "q_norm" in n]


# ---------------------------------------------------------------------------
# the whole-sequence program against the reference, in logits; and each
# mechanism seen: a program that drops or mistakes it leaves the tolerance
# ---------------------------------------------------------------------------

TOKENS = np.random.RandomState(5).randint(1, 96, SEQ)


def _lm_logprobs(weights, cfg=None, attn_force=None):
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        logp = mimo.build_mimo_lm(cfg or _cfg(), seq_len=SEQ, page_size=PAGE,
                                  attn_force=attn_force)
    with fluid.scope_guard(_scope_with(weights)):
        (got,) = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"pf_tok": TOKENS[None].astype(np.int64),
                        "pf_pos": np.arange(SEQ)[None].astype(np.int64)},
            fetch_list=[logp.name])
    return np.asarray(got)


@pytest.fixture(scope="module")
def want_logprobs(weights):
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(weights, CONFIG, TOKENS, np.arange(SEQ))
    assert float(jnp.std(logits)) > 0.5      # the comparison has something
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def test_the_whole_sequence_program_matches_the_reference(weights,
                                                          want_logprobs):
    """Float32 on both sides, sums in another order: 2e-4 on
    log-probabilities whose spread is over 0.5."""
    got = _lm_logprobs(weights)
    np.testing.assert_allclose(got, want_logprobs, atol=2e-4, rtol=0)


def _broken(monkeypatch, what):
    real_rope = mimo.layers.rope_half
    real_attn = mimo.layers.paged_attention
    if what == "no_sink":
        monkeypatch.setattr(
            mimo.layers, "paged_attention",
            lambda *a, sinks=None, **kw: real_attn(*a, sinks=None, **kw))
    elif what == "thetas_swapped":
        monkeypatch.setattr(
            mimo.layers, "rope_half",
            lambda x, pos, theta, **kw: real_rope(
                x, pos, 1e7 if theta == 1e4 else 1e4, **kw))
    elif what == "whole_head_rotated":
        monkeypatch.setattr(
            mimo.layers, "rope_half",
            lambda x, pos, theta, rotary_dim=None, **kw: real_rope(
                x, pos, theta, **kw))
    elif what == "value_unscaled":
        monkeypatch.setattr(mimo.layers, "scale",
                            lambda x, scale=1.0, **kw: x)
    elif what == "window_ignored":
        monkeypatch.setattr(
            mimo.layers, "paged_attention",
            lambda *a, window=None, **kw: real_attn(*a, window=None, **kw))


@pytest.mark.parametrize("what", ["no_sink", "thetas_swapped",
                                  "whole_head_rotated", "value_unscaled",
                                  "window_ignored"])
def test_a_program_that_mistakes_a_mechanism_leaves_the_tolerance(
        weights, want_logprobs, monkeypatch, what):
    _broken(monkeypatch, what)
    got = _lm_logprobs(weights)
    assert float(np.max(np.abs(got - want_logprobs))) > 0.05, what


def test_the_logits_move_with_the_sinks(weights, want_logprobs):
    moved = dict(weights)
    for name in weights:
        if name.endswith("_sink.b_0"):
            moved[name] = weights[name] + 1.5
    got = _lm_logprobs(moved)
    assert float(np.max(np.abs(got - want_logprobs))) > 0.05
    with jax.default_matmul_precision("highest"):
        want = jax.nn.log_softmax(ref.forward(moved, CONFIG, TOKENS,
                                              np.arange(SEQ)), axis=-1)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4, rtol=0)


# ---------------------------------------------------------------------------
# engine: prefill chunks, then decode steps, through both kinds of cache
# (rows by kind), against the reference's full forward
# ---------------------------------------------------------------------------

PROMPTS = (30, 5, 45, 17)       # 0.8 to 7.5 windows; 5 fits inside one


def _generate(weights, force=None, n_new=12, **engine):
    cfg = _cfg()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in PROMPTS]
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=3, page_size=PAGE,
        max_len=64, attn_force=force, auto_start=False,
        name=f"mimo-{force}-{len(engine)}", **engine)
    try:
        eng.warmup()
        eng.start()
        outs = eng.generate(prompts, max_new_tokens=n_new, timeout=600)
        eng.book_device_counters()
        return eng, prompts, outs, eng.stats()
    finally:
        eng.close()


def test_engine_matches_the_reference_and_frees_window_pages(weights):
    eng, prompts, outs, stats = _generate(weights)
    assert all(len(o) == 12 for o in outs)
    assert max(_served_gaps(weights, prompts, outs)) < 1e-3
    assert stats["evictions"] == 0
    kinds = stats["kv_pool"]["kinds"]
    assert set(kinds) == {"full", "window6"}
    per_seq = lane.window_pages_per_seq(W, CHUNK, PAGE)
    assert per_seq == 5
    assert eng.pool.pages_by_kind() == {"full": 3 * 16 + 1,
                                        "window6": 3 * per_seq + 1}
    # each layer's tensors at its kind's pages AND its kind's widths
    shapes = [tuple(np.shape(eng.scope.get(name)) for name in names)
              for names in eng.pool.var_names]
    full, window = ((49, 4, 2 * 24), (49, 4, 2 * 16)), \
        ((16, 4, 4 * 24), (16, 4, 4 * 16))
    assert shapes == [full, window, window, window, window, full, window]
    # the bytes go by kind too
    assert eng.pool.kind_bytes("full") == 2 * 49 * 4 * (48 + 32) * 4
    assert eng.pool.kind_bytes("window6") == 5 * 16 * 4 * (96 + 64) * 4
    assert eng.pool.modeled_bytes() == (eng.pool.kind_bytes("full")
                                        + eng.pool.kind_bytes("window6"))
    # both kinds handed out the same logical pages; the window kind gave
    # most of them back while their requests lived
    full, window = kinds["full"], kinds["window6"]
    assert full["alloc_total"] == window["alloc_total"] == sum(
        -(-(n + 11) // PAGE) for n in PROMPTS)
    assert full["freed"] == {"window": 0, "end": full["alloc_total"],
                             "evict": 0}
    assert window["freed"]["window"] > window["freed"]["end"] > 0
    assert sum(window["freed"].values()) == window["alloc_total"]
    snap = obs.snapshot()
    freed = snap["pt_kv_pages_freed_total"]["samples"]
    alloc = snap["pt_kv_pages_alloc_total"]["samples"]
    assert freed[(eng.name, "window6", "window")] == window["freed"]["window"]
    assert alloc[(eng.name, "window6")] == window["alloc_total"]
    assert snap["pt_kv_bytes_in_use"]["samples"][(eng.name, "window6")] == 0
    picks = snap["pt_moe_picks_total"]["samples"]
    assert picks[(eng.name, "any")] == (picks[(eng.name, "held")]
                                        + picks[(eng.name, "absent")]) > 0


def test_the_gauges_count_a_kinds_pages_at_its_own_bytes(weights):
    """Mid-request: the K row's gauge adds up both kinds' K rows, and a
    kind's gauge is its pages in use at its own widths."""
    cfg = _cfg()
    eng = serving.DecodeEngine(cfg, scope=_scope_with(weights), pool_slots=3,
                               page_size=PAGE, max_len=64, auto_start=False,
                               name="mimo-gauges")
    try:
        pool = eng.pool
        pool.open_seq("a")
        pool.ensure_capacity("a", 20)                # 5 pages of each kind
        pool.release("a", 20)                        # window: 3 go back
        assert pool.pages_in_use("full") == 5
        assert pool.pages_in_use("window6") == 2
        eng._book_pool()
        snap = obs.snapshot()
        by_kind = snap["pt_kv_bytes_in_use"]["samples"]
        assert by_kind[("mimo-gauges", "full")] == 2 * 5 * 4 * 80 * 4
        assert by_kind[("mimo-gauges", "window6")] == 5 * 2 * 4 * 160 * 4
        rows = snap["pt_decode_cache_bytes"]["samples"]
        assert rows[("mimo-gauges", "k")] == (2 * 5 * 4 * 48 * 4
                                              + 5 * 2 * 4 * 96 * 4)
        assert rows[("mimo-gauges", "v")] == (2 * 5 * 4 * 32 * 4
                                              + 5 * 2 * 4 * 64 * 4)
        pool.free_seq("a")
    finally:
        eng.close()


def test_eviction_and_replay_cover_both_kinds(weights):
    _, prompts, want, _ = _generate(weights)
    eng, _, outs, stats = _generate(weights, num_pages=20)
    assert outs == want
    assert stats["evictions"] > 0
    for k in stats["kv_pool"]["kinds"].values():
        assert k["freed"]["evict"] > 0 and k["pages_in_use"] == 0
        assert sum(k["freed"].values()) == k["alloc_total"]


# ---------------------------------------------------------------------------
# the lane's declaration and the pool: rows by kind
# ---------------------------------------------------------------------------


def test_the_lane_declares_its_rows_by_kind_and_refuses_an_int8_pool():
    decl = _cfg().decode_lane()
    assert decl.layer_windows == [None, W, W, W, W, None, W]
    rows = decl.cache_rows(None)
    assert {k: [(r.name, r.width) for r in v] for k, v in rows.items()} == {
        "full": [("k", 2 * 24), ("v", 2 * 16)],
        "window6": [("k", 4 * 24), ("v", 4 * 16)]}
    with pytest.raises(ValueError, match="no int8 form"):
        decl.cache_rows("int8")
    assert len(decl.device_counters) == 2 * 6    # 6 expert layers x 2


def test_a_pool_with_rows_by_kind():
    rows = {"full": lane.kv_rows(2, 16),
            "window8": [lane.CacheRow("k", 96, "float32"),
                        lane.CacheRow("v", 64, "float32")]}
    pool = KVPool(3, rows, 33, PAGE, 8, layer_windows=[8, None, 8],
                  window_pages={"window8": 11})
    assert pool.var_names == lane.pool_var_names(
        lane.kv_rows(2, 16), 3)                      # the names a layer
    scope = fluid.Scope()
    pool.install(scope)
    assert [tuple(np.shape(scope.get(n)) for n in names)
            for names in pool.var_names] == [
        ((11, 4, 96), (11, 4, 64)), ((33, 4, 32), (33, 4, 32)),
        ((11, 4, 96), (11, 4, 64))]
    k_full, v_full = rows["full"]
    k_win, v_win = rows["window8"]
    assert pool.rows == [k_win, v_win, k_full, v_full]   # by layer, once
    assert pool.row_bytes(k_full) == 33 * 4 * 32 * 4
    assert pool.row_bytes(k_win) == 2 * 11 * 4 * 96 * 4
    assert pool.row_bytes(v_win, {"full": 7, "window8": 3}) == (
        2 * 3 * 4 * 64 * 4)
    assert pool.kind_bytes("full") == 2 * 33 * 4 * 32 * 4
    assert pool.kind_bytes("window8", 1) == 2 * 4 * (96 + 64) * 4
    assert pool.modeled_bytes() == (pool.kind_bytes("full")
                                    + pool.kind_bytes("window8"))
    with pytest.raises(ValueError, match="layers are of kinds"):
        KVPool(3, rows, 33, PAGE, 8, layer_windows=[4, None, 4])
    with pytest.raises(ValueError, match="layers are of kinds"):
        KVPool(3, rows, 33, PAGE, 8)


def test_a_lane_of_one_list_declares_the_vars_it_always_did():
    rows = lane.kv_rows(2, 16)
    assert lane.rows_of_layers(rows, 2) == [rows, rows]
    assert lane.pool_var_names(rows, 2) == [
        ("@KVPOOL@k_l0", "@KVPOOL@v_l0"), ("@KVPOOL@k_l1", "@KVPOOL@v_l1")]
    pool = KVPool(2, rows, 9, PAGE, 4)
    assert pool.rows == rows and pool.layer_rows == [rows, rows]
    assert pool.kind_bytes("full") == pool.modeled_bytes()


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------


def test_sixteen_shares_are_the_uncut_layer():
    """Sixteen chips of an EP16 deployment at the tiny size, one expert
    each: the routed parts the sixteen shares give add up to the
    reference's uncut expert layer (no shared expert to count once);
    through the program's op for each share."""
    experts, held, d, f = 16, 1, 64, 32
    z = dict(ref.sizes(dict(CONFIG, n_routed_experts=experts,
                            deployment={"first_expert": 0})),
             **ref.routing(CONFIG))
    rng = np.random.RandomState(11)
    p = {"moe_router.w_0": rng.randn(d, experts) * 0.5,
         "moe_router.b_0": rng.randn(experts) * 0.1,
         **{f"moe_experts_{k}.w_0": rng.randn(*s) * 0.2
            for k, s in (("gate", (experts, d, f)), ("up", (experts, d, f)),
                         ("down", (experts, f, d)))}}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    x = jnp.asarray(rng.randn(24, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed_experts(x, p, z, jnp.matmul)
        total = jnp.zeros_like(uncut)
        from_program = jnp.zeros_like(uncut)
        for chip in range(experts // held):
            first = chip * held
            share = dict(p, **{
                f"moe_experts_{k}.w_0": p[f"moe_experts_{k}.w_0"][
                    first:first + held] for k in ("gate", "up", "down")})
            total = total + ref.routed_experts(
                x, share, dict(z, held=held, first=first), jnp.matmul)
            from_program = from_program + _program_share(x, share, first,
                                                         held, experts)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(from_program), np.asarray(uncut),
                               atol=5e-5, rtol=1e-5)
    assert float(jnp.abs(uncut).max()) > 0.1


def _program_share(x, share, first, held, experts):
    """One share's routed part through ``layers.moe_ffn_held``, at this
    model's routing (gates normalised, times 1)."""
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        xv = fluid.data("x", [1, x.shape[0], x.shape[1]], False,
                        dtype="float32")
        out = fluid.layers.moe_ffn_held(
            xv, experts, held, 32, 2, first_expert=first,
            routed_scaling_factor=1.0, norm_topk_prob=True, name="m")
    scope = fluid.Scope()
    for k, v in share.items():
        scope.set("m_" + k[len("moe_"):], v)
    with fluid.scope_guard(scope):
        (got,) = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": np.asarray(x)[None]}, fetch_list=[out.name])
    return jnp.asarray(got)[0]


# ---------------------------------------------------------------------------
# ops and kernels
# ---------------------------------------------------------------------------


def test_rope_half_over_part_of_a_head_matches_the_reference():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 5, 3, 24).astype(np.float32)
    pos = np.array([[0, 1, 2, 3, 40], [7, 8, 9, 10, 11]], np.int64)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        xv = fluid.data("x", list(x.shape), False, dtype="float32")
        pv = fluid.data("p", [2, 5], False, dtype="int64")
        part = fluid.layers.rope_half(xv, pv, theta=1e7, rotary_dim=8)
        whole = fluid.layers.rope_half(xv, pv, theta=1e7)
    got, got_whole = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x, "p": pos}, fetch_list=[part.name, whole.name])
    for b in range(2):
        np.testing.assert_allclose(
            got[b], np.asarray(ref.rope(jnp.asarray(x[b]),
                                        jnp.asarray(pos[b]), 1e7, 8)),
            atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])  # the rest pass
    assert np.abs(got_whole[..., 8:] - x[..., 8:]).max() > 0.1
    # a turn keeps each pair's length
    np.testing.assert_allclose(got[..., :4] ** 2 + got[..., 4:8] ** 2,
                               x[..., :4] ** 2 + x[..., 4:8] ** 2,
                               atol=1e-4, rtol=1e-4)


def test_the_kernel_names_tell_the_forms_apart():
    assert paged.kernel_name(16, None, asym=True) == \
        "paged_attention_grouped_asym"
    assert paged.kernel_name(8, 128, asym=True, sink=True) == \
        "paged_attention_grouped_window_asym_sink"
    assert paged.kernel_name(6, 4096) == "paged_attention_grouped_window"
    assert paged.kernel_name(1, None, sink=True) == "paged_attention_sink"


def _paged_case(b, n, n_kv, t, d, d_v, page, max_pages, starts, seed=0):
    rng = np.random.RandomState(seed)
    pages = b * max_pages + 1
    return (jnp.asarray(rng.randn(b, n, t, d), jnp.float32),
            jnp.asarray(rng.randn(pages, page, n_kv * d), jnp.float32),
            jnp.asarray(rng.randn(pages, page, n_kv * d_v), jnp.float32),
            jnp.asarray(1 + rng.permutation(b * max_pages).reshape(
                b, max_pages), jnp.int32),
            jnp.asarray(starts, jnp.int32),
            jnp.asarray(rng.randn(n), jnp.float32))


@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("group", [16, 8])
@pytest.mark.parametrize("t", [1, 512])
def test_the_pallas_forms_match_the_reference_at_the_published_head_widths(
        t, group, window, sink):
    """K heads of 192 beside V heads of 128, as a decode step (rows at
    contexts from inside one page to five pages) and as a 512-token chunk
    past 300 cached tokens, in the interpreter; ``window`` = one page."""
    b, starts = (3, [0, 130, 600]) if t == 1 else (1, [300])
    *args, sinks = _paged_case(b, 2 * group, 2, t, 192, 128, 128, 7, starts)
    kw = {"window": window, "sinks": sinks if sink else None}
    want = paged.paged_attention(*args, force="reference", **kw)
    got = paged.paged_attention(*args, force="pallas", **kw)
    assert got.shape == (b, 2 * group, t, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=1e-4)
    name = paged.kernel_name(group, window, True, sink)
    forms = obs.snapshot()["pt_paged_attention_form_total"]["samples"]
    assert any(k[0] == name and k[1].startswith(
        "heads_batched" if t == 1 else "kv_head_tq") for k in forms)


# (queries a tile, pages a step) of the lane-block body's grid step (PR
# 43): one page; tq < t under several pages; a step longer than the
# window and as long as the table
@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("window", [None, 16, 64])
@pytest.mark.parametrize("group", [8, 16])
@pytest.mark.parametrize("geometry", [(8, 1), (16, 2), (32, 4), (64, 12)])
def test_the_lane_block_chunk_at_every_geometry(monkeypatch, geometry, group,
                                                window, sink):
    """A 64-token chunk over pages of 16 from 0 (its first query sees
    one key: with a sink, two columns) and from 70 cached tokens (the
    last step partly dead), two K heads of 192 a lane block.  A tile is
    at most half a window long."""
    tq, pages = geometry
    monkeypatch.setattr(paged, "_CHUNK_ROWS_PER_STEP", group * tq)
    monkeypatch.setattr(paged, "_CHUNK_KEYS_PER_STEP", pages * 16)
    while window is not None and tq > 8 and 2 * tq > window:
        tq //= 2
    usable = 12 if window is None else min(12, -(-(window + tq) // 16))
    assert paged._chunk_geometry(64, group, 2, 16, 12, window) == (
        tq, min(pages, usable))
    *args, sinks = _paged_case(2, 2 * group, 2, 64, 192, 128, 16, 12,
                               [0, 70], seed=tq)
    kw = {"window": window, "sinks": sinks if sink else None}
    want = paged.paged_attention(*args, force="reference", **kw)
    got = paged.paged_attention(*args, force="pallas", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=1e-4)
    name = paged.kernel_name(group, window, True, sink)
    forms = obs.snapshot()["pt_paged_attention_form_total"]["samples"]
    assert forms[(name, f"kv_head_tq{tq}", str(min(pages, usable)))] >= 1
    # the pools as the configuration stores them: bfloat16 operands,
    # float32 scores and sums
    low = [x.astype(jnp.bfloat16) if i in (1, 2) else x
           for i, x in enumerate(args)]
    got = paged.paged_attention(*low, force="pallas", **kw)
    want = paged.paged_attention(*low, force="reference", **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2,
                               rtol=3e-2)


@pytest.mark.parametrize("force", ["reference", "pallas"])
@pytest.mark.parametrize("t", [1, 16])
def test_a_sink_of_minus_infinity_is_the_plain_softmax(t, force):
    b, starts = (2, [5, 40]) if t == 1 else (1, [19])
    *args, sinks = _paged_case(b, 8, 2, t, 192, 128, 16, 5, starts, seed=1)
    plain = paged.paged_attention(*args, force=force, window=16)
    none = paged.paged_attention(*args, force=force, window=16,
                                 sinks=jnp.full((8,), -jnp.inf))
    some = paged.paged_attention(*args, force=force, window=16, sinks=sinks)
    np.testing.assert_allclose(np.asarray(none), np.asarray(plain),
                               atol=1e-6, rtol=1e-6)
    # a sink takes its share of every probability: outputs shrink
    assert float(jnp.abs(some - plain).max()) > 0.05
    assert float(jnp.abs(some).sum()) < float(jnp.abs(plain).sum())


def test_pools_that_are_not_whole_heads_are_refused():
    q, k, v, table, start, _ = _paged_case(1, 8, 2, 1, 192, 128, 16, 4, [9])
    with pytest.raises(ValueError, match="whole heads"):
        paged.paged_attention(q, k, v[:, :, :255], table, start)
    with pytest.raises(ValueError, match="one logit"):
        paged.paged_attention(q, k, v, table, start, sinks=jnp.zeros((4,)))
    # the Pallas chunk reads V heads in whole 128-lane tiles; the XLA form
    # takes any width
    q16 = jnp.tile(q, (1, 1, 16, 1))
    with pytest.raises(ValueError, match="lane block"):
        paged.paged_attention(q16, k, v[:, :, :128], table, start,
                              force="pallas")
    out = paged.paged_attention(q16, k, v[:, :, :128], table, start,
                                force="reference")
    assert out.shape == (1, 8, 16, 64)
