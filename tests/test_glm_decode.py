"""GLM-5 through the decode lane (models/glm.py, serving/lane.py): latent
and indexer caches under one page table, learned sparse attention, held
experts — against the plain reference (benchmark/reference/glm.py, which
imports nothing of the program) at a tiny size with seeded float32
weights, so that the selected sets must be IDENTICAL, with contexts past
the tiny ``index_topk`` so that selection is live.
"""

import copy
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import fluid, serving
from paddle_tpu import observability as obs
from paddle_tpu.kernels import primitives as prims
from paddle_tpu.kernels.primitives import grouped
from paddle_tpu.models import glm
from paddle_tpu.serving import lane
from paddle_tpu.serving.kv_pool import KVPool
from paddle_tpu.serving.lane import CacheRow, lane_padded

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "glm-tiny.json")) as _f:
    CONFIG = json.load(_f)
ref = harness.load_module("reference", "glm.py")
PAGE, MAX_PAGES, CHUNK = 4, 16, 8


def _cfg(**over):
    args = dict(CONFIG["builder"]["config_args"])
    args.update(over)
    return glm.GLMConfig(**args)


def _scope_with(weights):
    scope = fluid.Scope()
    for name, w in weights.items():
        scope.set(name, w)
    return scope


@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(CONFIG, 20260928)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "glm.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+(paddle_tpu|benchmark)", src,
                         re.M)


def test_decode_py_imports_no_model_module():
    with open(os.path.join(ROOT, "paddle_tpu", "serving", "decode.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+paddle_tpu\.models", src, re.M)
    assert "models import" not in src


def test_program_parameters_are_the_references():
    cfg = _cfg()
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        glm.build_glm_lm(cfg)
    want = {p.name: tuple(p.shape)
            for p in main.global_block().all_parameters()}
    have = {n: tuple(s) for n, (s, _, _) in ref.param_shapes(CONFIG).items()}
    assert have == want


# ---------------------------------------------------------------------------
# prefill chunks, then decode steps, through both caches, against the
# reference's full forward: logits and selected sets
# ---------------------------------------------------------------------------


def _selected_names(program):
    return [op.output("Out")[0] for op in program.global_block().ops
            if op.type == "dsa_topk_select"]


@pytest.mark.parametrize("force", [None, "pallas"])
def test_prefill_then_decode_matches_the_reference(weights, force):
    cfg = _cfg()
    n_layers = cfg.num_hidden_layers
    num_pages = MAX_PAGES + 1
    rng = np.random.RandomState(3)
    tokens = rng.randint(1, cfg.vocab_size, 41)
    n_prompt = 30                       # 4 chunks: 8, 8, 8, 6
    scope = _scope_with(weights)
    KVPool(n_layers, cfg.cache_rows(), num_pages, PAGE,
           MAX_PAGES).install(scope)
    for counter in cfg.decode_lane().device_counters:
        scope.set(counter.name, jnp.zeros((counter.length,), jnp.int32))
    progs = {}
    decl = cfg.decode_lane()
    for kind, build in (
            ("pf", lambda: decl.build_prefill_chunk(
                CHUNK, num_pages, PAGE, MAX_PAGES, attn_force=force)),
            ("dec", lambda: decl.build_decode_step(
                1, num_pages, PAGE, MAX_PAGES, attn_force=force))):
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start), fluid.unique_name.guard():
            _, _, logp = build()
        progs[kind] = (main, [logp.name] + _selected_names(main))
    table = np.zeros((1, MAX_PAGES), np.int32)
    table[0, :] = np.arange(1, 1 + MAX_PAGES)
    got_logp, got_sel = {}, {}
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        for pos0 in range(0, n_prompt, CHUNK):
            valid = min(CHUNK, n_prompt - pos0)
            tok = np.zeros((1, CHUNK), np.int64)
            tok[0, :valid] = tokens[pos0:pos0 + valid]
            main, fetch = progs["pf"]
            out = exe.run(main, feed=lane.prefill_feed(
                tok, (pos0 + np.arange(CHUNK, dtype=np.int64))[None],
                {lane.FULL: table},
                {lane.FULL: table[0, pos0 // PAGE:(pos0 + CHUNK) // PAGE]},
                np.asarray([pos0], np.int32),
                np.asarray([valid - 1], np.int64),
                np.asarray([1], np.int32)), fetch_list=fetch)
            got_logp[pos0 + valid - 1] = np.asarray(out[0])[0]
            for t in range(valid):
                got_sel[pos0 + t] = [np.asarray(m)[0, t] for m in out[1:]]
        for p in range(n_prompt, len(tokens)):
            main, fetch = progs["dec"]
            out = exe.run(main, feed=lane.decode_feed(
                np.asarray([[tokens[p]]], np.int64),
                np.asarray([[p]], np.int64), {lane.FULL: table},
                {lane.FULL: table[0, p // PAGE:p // PAGE + 1]},
                np.asarray([p % PAGE], np.int32)), fetch_list=fetch)
            got_logp[p] = np.asarray(out[0])[0]
            got_sel[p] = [np.asarray(m)[0, 0] for m in out[1:]]
    rows = sorted(got_logp)
    selections = []
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(weights, CONFIG, tokens, rows,
                             selections=selections)
    want = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    for i, p in enumerate(rows):
        np.testing.assert_allclose(got_logp[p], want[i], atol=2e-4,
                                   err_msg=f"position {p}")
    live = 0
    for p, per_layer in got_sel.items():
        for layer, mask in enumerate(per_layer):
            mine = (mask[:len(tokens)] == 0.0) & (
                np.arange(len(tokens)) <= p)
            theirs = np.asarray(selections[layer])[p]
            assert (mine == theirs).all(), (p, layer)
            if p + 1 > cfg.index_topk:
                assert mine.sum() == cfg.index_topk
                live += 1
    assert live >= 20 * n_layers  # selection was live: contexts past top-k


def test_the_reference_scores_through_the_latent_as_by_expanding_it(weights):
    """benchmark/reference/glm.py scores and sums through the compressed
    row; expanding every position's k_nope and v per head, as the source
    writes the layer, gives the same attention."""
    z = ref.sizes(CONFIG)
    p = {k[len("glm_layer_1_"):]: v.astype(jnp.float32)
         for k, v in weights.items() if k.startswith("glm_layer_1_")}
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(20, z["d"]), jnp.float32)
    zt = tuple(sorted(dict(z, scaling=2.5).items()))
    with jax.default_matmul_precision("highest"):
        xa, c_q, c_kv, k_rope, k_idx = ref.layer_keys(
            x, {k: p[k] for k in ref.KEY_PARAMS}, z=zt, eps=1e-5, theta=1e6,
            matmul=jnp.matmul)
        got, chosen = ref.attend_block(
            0, xa, c_q, c_kv, k_rope, k_idx,
            {k: p[k] for k in ref.QUERY_PARAMS}, z=zt, theta=1e6,
            matmul=jnp.matmul)
        t, h = 20, z["heads"]
        q = (c_q @ p["q_b.w_0"]).reshape(t, h, z["nope"] + z["rope"])
        q_rope = ref.rope(q[..., z["nope"]:], jnp.arange(t), 1e6, z["rope"])
        k_nope = jnp.einsum("sc,hnc->shn", c_kv, p["kv_b_k.w_0"])
        v = jnp.einsum("sc,hcv->shv", c_kv, p["kv_b_v.w_0"])
        s = (jnp.einsum("thn,shn->hts", q[..., :z["nope"]], k_nope)
             + jnp.einsum("thr,sr->hts", q_rope, k_rope)) * (
                 z["nope"] + z["rope"]) ** -0.5
        probs = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), -1)
        want = jnp.einsum("hts,shv->thv", probs, v).reshape(
            t, -1) @ p["o.w_0"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# the expert layer: 16 shares of one layer add up to the uncut layer
# ---------------------------------------------------------------------------


def _routed_share(x, p, first, held, total, top_k, force=None):
    """moe_ffn_held over x [1, T, D] holding experts first .. first+held."""
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        xv = fluid.data("x", list(x.shape), False, dtype="float32")
        out = fluid.layers.moe_ffn_held(
            xv, total, held, p["moe_experts_gate.w_0"].shape[2], top_k,
            first_expert=first, routed_scaling_factor=2.5, force=force,
            name="m")
    scope = fluid.Scope()
    scope.set("m_router.w_0", p["moe_router.w_0"])
    scope.set("m_router.b_0", p["moe_router.b_0"])
    for k in ("gate", "up", "down"):
        scope.set(f"m_experts_{k}.w_0",
                  p[f"moe_experts_{k}.w_0"][first:first + held])
    with fluid.scope_guard(scope):
        return np.asarray(fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": x}, fetch_list=[out.name])[0])


@pytest.mark.parametrize("force", [None, "pallas"])
def test_sixteen_shares_of_an_expert_layer_sum_to_the_uncut_layer(force):
    whole = copy.deepcopy(CONFIG)
    whole["n_routed_experts"] = whole["n_routed_experts_total"]   # all 16
    whole["deployment"]["first_expert"] = 0
    full = ref.init_weights(whole, 77)
    p = {k[len("glm_layer_1_"):]: v for k, v in full.items()
         if k.startswith("glm_layer_1_")}
    z = dict(ref.sizes(whole), scaling=2.5)
    rng = np.random.RandomState(1)
    x = rng.randn(1, 24, z["d"]).astype(np.float32)
    rest = {k: v for k, v in p.items()
            if k not in ref.KEY_PARAMS + ref.QUERY_PARAMS}
    with jax.default_matmul_precision("highest"):
        # the uncut layer, by the reference: shared expert + all 16
        # experts (attention output and o.w_0 set aside: attn = 0)
        f = ref.rms_norm(jnp.asarray(x[0]), 1.0, 1e-5)
        rest["ffn_norm.scale"] = jnp.ones_like(rest["ffn_norm.scale"])
        uncut = np.asarray(ref.finish_rows(
            jnp.asarray(x[0]), jnp.zeros_like(x[0]), rest,
            z=tuple(sorted(z.items())), eps=1e-5, dense=False,
            matmul=jnp.matmul)) - x[0]
        shared = np.asarray(ref.swiglu(
            f, rest["shared_gate.w_0"], rest["shared_up.w_0"],
            rest["shared_down.w_0"], jnp.matmul))
    xf = np.asarray(f)[None]
    shares = sum(_routed_share(xf, p, e, 1, 16, z["picks"], force)
                 for e in range(16))
    np.testing.assert_allclose(shares[0] + shared, uncut, atol=2e-5)
    # and four shares of four experts are the same sum
    fours = sum(_routed_share(xf, p, e, 4, 16, z["picks"], force)
                for e in (0, 4, 8, 12))
    np.testing.assert_allclose(fours, shares, atol=2e-5)


def test_padding_rows_pick_nothing_and_picks_are_counted():
    """RowValid drops padding rows from the product and from the
    in-place counters; the counters hold held picks by expert, absent
    picks, and the held experts each call touched."""
    rng = np.random.RandomState(5)
    d, f, total, held, top_k, n = 16, 8, 8, 3, 2, 6
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        xv = fluid.data("x", [1, n, d], False, dtype="float32")
        valid = fluid.data("valid", [n], False, dtype="int32")
        stats = main.global_block().create_var(
            name="stats", shape=[held + 2], dtype="int32", persistable=True)
        out = fluid.layers.moe_ffn_held(xv, total, held, f, top_k,
                                        first_expert=2, row_valid=valid,
                                        stats=stats, name="m")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(start)
        scope.set("stats", jnp.zeros((held + 2,), jnp.int32))
        x = rng.randn(1, n, d).astype(np.float32)
        flags = np.asarray([1, 1, 0, 1, 0, 0], np.int32)
        for _ in range(2):  # the counters accumulate in place
            y = np.asarray(exe.run(main, feed={"x": x, "valid": flags},
                                   fetch_list=[out.name])[0])
        counts = np.asarray(scope.get("stats"))
        from paddle_tpu.ops.mla_ops import route_sigmoid_topk
        picks, _ = route_sigmoid_topk(
            jnp.asarray(x[0]), scope.get("m_router.w_0"),
            scope.get("m_router.b_0"), top_k, 1.0, True)
    picks = np.asarray(picks)[flags > 0]
    want = [int((picks == 2 + e).sum()) for e in range(held)]
    want.append(picks.size - sum(want))
    want.append(sum(c > 0 for c in want[:held]))
    assert list(counts) == [2 * c for c in want]
    assert (y[0, flags == 0] == 0).all()


# ---------------------------------------------------------------------------
# the small ops of ops/mla_ops.py, each against the reference's own function
# ---------------------------------------------------------------------------


def _run_layer(build, feeds, params=None):
    """Build a one-layer program over float32 feeds, run it, return the
    output and the scope (parameters come from the startup program unless
    given)."""
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        out = build({k: fluid.data(k, list(v.shape), False, dtype=str(v.dtype))
                     for k, v in feeds.items()})
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(start)
        for name, value in (params or {}).items():
            scope.set(name, value)
        got = np.asarray(exe.run(main, feed=feeds, fetch_list=[out.name])[0])
    return got, scope


@pytest.mark.parametrize("op", ["weight_matmul", "headwise_matmul",
                                "rms_norm", "swiglu", "rope_interleaved"])
def test_small_ops_against_the_references_functions(op):
    rng = np.random.RandomState(4)
    L = fluid.layers
    x = rng.randn(2, 3, 4, 8).astype(np.float32)
    if op == "weight_matmul":
        w = rng.randn(8, 5).astype(np.float32)
        got, _ = _run_layer(
            lambda v: L.weight_matmul(v["x"], 5, param_attr="w"),
            {"x": x}, {"w": w})
        want = x @ w
    elif op == "headwise_matmul":
        w = rng.randn(4, 8, 6).astype(np.float32)
        got, _ = _run_layer(
            lambda v: L.headwise_matmul(v["x"], 6, param_attr="w"),
            {"x": x}, {"w": w})
        want = np.einsum("bthx,hxy->bthy", x, w)
    elif op == "rms_norm":
        scale = rng.rand(8).astype(np.float32) + 0.5
        got, _ = _run_layer(
            lambda v: L.rms_norm(v["x"], epsilon=1e-5, param_attr="g"),
            {"x": x}, {"g": scale})
        want = np.asarray(ref.rms_norm(jnp.asarray(x), scale, 1e-5))
    elif op == "swiglu":
        up = rng.randn(*x.shape).astype(np.float32)
        got, _ = _run_layer(lambda v: L.swiglu(v["x"], v["up"]),
                            {"x": x, "up": up})
        want = np.asarray(ref.silu(jnp.asarray(x))) * up
    else:
        # rotary dims 4 of 8, positions to 30k: both the per-head [B, T, H,
        # d] and the shared-key [B, T, d] forms against the reference's
        pos = np.asarray([[0, 7, 29999], [5, 1000, 31]], np.int64)
        got, _ = _run_layer(
            lambda v: L.rope_interleaved(v["x"], v["pos"], 1e6, 4),
            {"x": x, "pos": pos})
        want = np.stack([np.asarray(ref.rope(jnp.asarray(x[b]),
                                             jnp.asarray(pos[b]), 1e6, 4))
                         for b in range(2)])
        flat, _ = _run_layer(
            lambda v: L.rope_interleaved(v["x"], v["pos"], 1e6, 4),
            {"x": x[:, :, 0], "pos": pos})
        np.testing.assert_allclose(flat, want[:, :, 0], atol=1e-5)
        assert (got[..., 4:] == x[..., 4:]).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def _product_layouts(since=None):
    """{(rows, pinned): products booked} of ``weight_matmul``, less those
    of an earlier reading."""
    fam = obs.snapshot().get("pt_weight_matmul_layout_total") or {}
    return {k: v - (since or {}).get(k, 0)
            for k, v in fam.get("samples", {}).items()
            if v != (since or {}).get(k, 0)}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("lead,head_dim,pinned", [
    ((16, 1), None, "false"),       # a step
    ((16, 1), 192, "true"),         # a step split into heads of 192
    ((1, 512), 192, "true")])       # a chunk split into heads of 192
def test_weight_matmul_pins_its_product_and_stays_bit_equal(
        lead, head_dim, pinned, dtype):
    """A product split into heads that are no whole lane tiles carries
    the layout constraint, at a step's and at a chunk's rows, and none
    computes anything else than the plain dot in the weight's dtype with
    float32 accumulation: equal to the last bit."""
    rng = np.random.RandomState(7)
    x = rng.randn(*lead, 1024).astype(np.float32)
    w = jnp.asarray(rng.randn(1024, 2 * 192).astype(np.float32) * 0.02,
                    dtype=dtype)
    before = _product_layouts()
    got, _ = _run_layer(
        lambda v: fluid.layers.weight_matmul(
            v["x"], 2 * 192, param_attr="w", dtype=dtype, head_dim=head_dim),
        {"x": x}, {"w": w})
    # booked once a trace of the op, and the executor traces more than once
    assert set(_product_layouts(before)) == {
        (str(lead[0] * lead[1]), pinned)}
    want = jnp.dot(jnp.asarray(x).astype(w.dtype), w,
                   preferred_element_type=jnp.float32)
    assert got.dtype == np.float32
    assert (got == np.asarray(want)).all()


# the served widths (benchmark/configs/*.json): lead, K, N, the heads the
# caller splits the product into, pinned
_SERVED_PRODUCTS = {
    "mimo_q_step": ((16, 1), 4096, 12288, 192, True),
    "mimo_q_chunk": ((1, 512), 4096, 12288, 192, True),
    "mimo_k_chunk": ((1, 512), 4096, 768, 192, True),
    "mimo_v_chunk": ((1, 512), 4096, 512, None, False),
    "mimo_o_step": ((16, 1), 8192, 4096, None, False),
    "kimi_q_step": ((16, 1), 2048, 3072, 192, True),
    "kimi_q_chunk": ((1, 512), 2048, 3072, 192, True),
    # heads of 72, but 4096 patches: the product is seven times the weight
    "kimi_tower_qkv": ((4096,), 1152, 3456, 72, False),
    # whole tiles, were they stated
    "trinity_q_chunk": ((1, 512), 3072, 6144, 128, False),
    "trinity_o_step": ((16, 1), 6144, 3072, None, False),
    "glm_q_up_step": ((16, 1), 2048, 16384, None, False),
    "glm_q_up_chunk": ((1, 512), 2048, 16384, 256, False),
    "olmo_values_step": ((16, 1), 3840, 5760, None, False),
    "olmo_values_chunk": ((1, 512), 3840, 5760, None, False),
}


@pytest.mark.parametrize("case", list(_SERVED_PRODUCTS))
def test_weight_matmul_books_what_the_shapes_choose(case):
    """The rule reads nothing but shapes: heads that are no whole lane
    tiles, stated by the caller, under a weight as large as the product."""
    from paddle_tpu.ops.mla_ops import _weight_matmul

    lead, k, n, head_dim, pinned = _SERVED_PRODUCTS[case]
    before = _product_layouts()
    out = jax.eval_shape(
        lambda x, w: _weight_matmul(
            None, x, w, {"head_dim": head_dim} if head_dim else {}),
        jax.ShapeDtypeStruct(lead + (k,), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.bfloat16))
    assert out.shape == lead + (n,) and out.dtype == jnp.float32
    assert _product_layouts(before) == {
        (str(np.prod(lead)), str(pinned).lower()): 1}


# ---------------------------------------------------------------------------
# the pool: two kinds of row tensor under one page table
# ---------------------------------------------------------------------------


def test_pool_holds_two_row_tensors_under_one_page_table():
    rows = [CacheRow("latent", lane_padded(576), "bfloat16"),
            CacheRow("index", 128, "bfloat16")]
    assert rows[0].width == 640 and lane_padded(128) == 128
    pool = KVPool(num_layers=2, rows=rows, num_pages=9, page_size=4,
                  max_pages_per_seq=4)
    assert pool.var_names == [("@KVPOOL@latent_l0", "@KVPOOL@index_l0"),
                              ("@KVPOOL@latent_l1", "@KVPOOL@index_l1")]
    scope = fluid.Scope()
    pool.install(scope)
    for names in pool.var_names:
        for name, row in zip(names, rows):
            arr = scope.get(name)
            assert arr.shape == (9, 4, row.width)
            assert str(arr.dtype) == "bfloat16"
    assert pool.row_bytes(rows[0]) == 9 * 4 * 640 * 2 * 2
    assert pool.row_bytes(rows[1], pages=3) == 3 * 4 * 128 * 2 * 2
    assert pool.modeled_bytes() == 9 * 4 * (640 + 128) * 2 * 2
    # one page table for both: a sequence's pages index every row tensor
    pool.open_seq("s")
    assert len(pool.ensure_capacity("s", 10)) == 3
    assert pool.pages_in_use() == 3
    # idempotent on shape and dtype; re-installed on a dtype change
    scope.set(pool.var_names[0][1], scope.get(pool.var_names[0][1]) + 1)
    pool.install(scope)
    assert float(jnp.max(scope.get(pool.var_names[0][1]))) == 1.0
    KVPool(2, [r._replace(dtype="float32") for r in rows], 9, 4,
           4).install(scope)
    assert str(scope.get(pool.var_names[0][1]).dtype) == "float32"


def test_glm_declares_its_lane_and_refuses_an_int8_pool():
    cfg = _cfg()
    lane = cfg.decode_lane()
    assert [(r.name, r.width) for r in lane.cache_rows("float32")] == [
        ("latent", 128), ("index", 8)]       # 16 + 4 padded to 128
    assert lane.num_layers == 3 and lane.prefill_chunk == 8
    # per expert layer and program: 4 held experts + absent + touched
    assert [(c.name.split("@")[-2:], c.length)
            for c in lane.device_counters] == [
        (["l1", "decode"], 6), (["l1", "prefill"], 6),
        (["l2", "decode"], 6), (["l2", "prefill"], 6)]
    with pytest.raises(ValueError, match="no int8 form"):
        lane.cache_rows("int8")


def _engine(weights, name, **kw):
    args = dict(pool_slots=3, page_size=PAGE, max_len=64, auto_start=False,
                place=fluid.CPUPlace())
    args.update(kw)
    eng = serving.DecodeEngine(_cfg(), scope=_scope_with(weights),
                               name=name, **args)
    eng.warmup()
    return eng.start()


def test_eviction_and_replay_with_two_row_tensors(weights):
    """A pool too small for three long sequences evicts and replays; the
    streams are those of a roomy pool, and of the reference's argmax."""
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, 96, n).tolist() for n in (33, 28, 37, 14)]
    roomy = _engine(weights, "glm-roomy")
    try:
        want = roomy.generate(prompts, max_new_tokens=8, timeout=600)
    finally:
        roomy.close()
    tight = _engine(weights, "glm-tight", num_pages=MAX_PAGES + 6)
    try:
        got = tight.generate(prompts, max_new_tokens=8, timeout=600)
        assert tight.stats()["evictions"] > 0
        assert tight.stats()["kv_pool"]["pages_in_use"] == 0
    finally:
        tight.close()
    assert got == want
    with jax.default_matmul_precision("highest"):
        for prompt, out in zip(prompts, want):
            logits = ref.served_logits(weights, CONFIG, prompt, out)
            assert np.asarray(jnp.argmax(logits, axis=1)).tolist() == out


def test_engine_books_the_new_counters(weights):
    eng = _engine(weights, "glm-count")
    try:
        prompts = [[5] * 20, [7] * 9]
        outs = eng.generate(prompts, max_new_tokens=4, timeout=600)
        eng.stats()                       # reads nothing off the device
        assert "pt_moe_picks_total" not in obs.snapshot() or not any(
            k[0] == "glm-count" for k in
            obs.snapshot()["pt_moe_picks_total"]["samples"])
        eng.book_device_counters()        # the one call that does
        snap = obs.snapshot()
    finally:
        eng.close()
    cfg = _cfg()
    picks = snap["pt_moe_picks_total"]["samples"]
    # every token but each request's last generated one went through the
    # model: prompt + 3, in every expert layer, 4 picks each
    tokens = sum(len(p) + len(o) - 1 for p, o in zip(prompts, outs))
    layers = len(cfg.moe_layers)
    assert picks[("glm-count", "any")] == tokens * 4 * layers
    assert (picks[("glm-count", "held")] + picks[("glm-count", "absent")]
            == picks[("glm-count", "any")])
    by_expert = snap["pt_moe_expert_tokens_total"]["samples"]
    mine = {k: v for k, v in by_expert.items() if k[0] == "glm-count"}
    assert sum(mine.values()) == picks[("glm-count", "held")]
    assert {int(k[2]) for k in mine} <= {4, 5, 6, 7}      # the held ids
    # held experts touched: at most min(held picks, 4 experts) a layer a
    # run, at least one where any pick was held; 3 decode steps of 2 rows
    touched = snap["pt_moe_experts_touched_total"]["samples"]
    assert 0 < touched[("glm-count", "decode")] <= 3 * layers * 4
    assert 0 < touched[("glm-count", "prefill")] <= (
        picks[("glm-count", "held")])
    rows = {k[1] for k in snap["pt_decode_cache_bytes"]["samples"]
            if k[0] == "glm-count"}
    assert rows == {"latent", "index"}


# ---------------------------------------------------------------------------
# the primitives: Pallas (interpreted) against the XLA forms
# ---------------------------------------------------------------------------


def _dsa_case(t, seed=0, b=2, hi=2, di=8, h=4, c=16, r=4, page=4, maxp=6):
    rng = np.random.RandomState(seed)
    n_pages = b * maxp + 1
    table = (1 + rng.permutation(b * maxp)).reshape(b, maxp).astype(np.int32)
    return dict(
        q_idx=jnp.asarray(rng.randn(b, t, hi, di), jnp.float32),
        w_idx=jnp.asarray(rng.randn(b, t, hi), jnp.float32),
        index_pages=jnp.asarray(rng.randn(n_pages, page, di), jnp.float32),
        latent_pages=jnp.asarray(rng.randn(n_pages, page, 128), jnp.float32),
        q_lat=jnp.asarray(rng.randn(b, t, h, c), jnp.float32),
        q_rope=jnp.asarray(rng.randn(b, t, h, r), jnp.float32),
        table=jnp.asarray(table),
        q_start=jnp.asarray([maxp * page - t, 3], jnp.int32)), maxp * page


@pytest.mark.parametrize("t", [1, 8])
def test_indexer_scores_pallas_against_reference(t):
    a, length = _dsa_case(t)
    args = (a["q_idx"], a["w_idx"], a["index_pages"], a["table"],
            a["q_start"])
    want = np.asarray(prims.dsa_indexer_scores(*args, force="reference"))
    got = np.asarray(prims.dsa_indexer_scores(*args, force="pallas"))
    assert got.shape[-1] >= length and got.shape[-1] % (4 * 8) == 0
    assert np.isneginf(got[..., length:]).all()
    assert (np.isneginf(want) == np.isneginf(got[..., :length])).all()
    seen = np.isfinite(want)
    np.testing.assert_allclose(got[..., :length][seen], want[seen],
                               atol=1e-5)
    # what a query may see: positions up to its own
    assert seen[0, 0].sum() == int(a["q_start"][0]) + 1


def _attention_forms(since=None):
    """{(form, pages a step): launches booked} of the attention, less
    those of an earlier reading."""
    fam = obs.snapshot().get("pt_paged_attention_form_total") or {}
    now = {k[1:]: v for k, v in fam.get("samples", {}).items()
           if k[0] == "sparse_mla_attention"}
    return {k: v - (since or {}).get(k, 0) for k, v in now.items()
            if v != (since or {}).get(k, 0)}


# t, pages a row, the two rows' q_start, pages a step (None: what the
# shapes give, the whole table at this size; else the step's keys are
# held to that many pages), the pool's dtype, the tile
_ATTENTION_CASES = {
    "t1": (1, 6, (23, 3), None, jnp.float32, "row_tq1"),
    "t8": (8, 6, (16, 3), None, jnp.float32, "chunk_tq8"),
    # a tile of more than 8 queries; steps of 8 keys, so that several
    # accumulate, and row 1's last live step (keys 16-23 for queries
    # 3-18) is partly dead
    "t16_several_steps": (16, 12, (32, 3), 2, jnp.float32, "chunk_tq16"),
    # row 1's first query sees a single key
    "t32_single_key": (32, 12, (16, 0), 4, jnp.float32, "chunk_tq32"),
    "t8_single_key": (8, 6, (0, 0), 2, jnp.float32, "chunk_tq8"),
    # the last live step of both rows ends past the last query
    "t8_partly_dead_step": (8, 12, (21, 2), 2, jnp.float32, "chunk_tq8"),
    "t1_several_steps": (1, 12, (41, 9), 2, jnp.float32, "row_tq1"),
    "t1_bf16": (1, 12, (47, 3), 2, jnp.bfloat16, "row_tq1"),
    "t16_bf16": (16, 12, (32, 3), 2, jnp.bfloat16, "chunk_tq16"),
}


@pytest.mark.parametrize("case", list(_ATTENTION_CASES))
def test_sparse_attention_pallas_against_reference(case, monkeypatch):
    t, maxp, starts, g, dtype, form = _ATTENTION_CASES[case]
    a, length = _dsa_case(t, seed=1, maxp=maxp)
    a["q_start"] = jnp.asarray(starts, jnp.int32)
    a["latent_pages"] = a["latent_pages"].astype(dtype)
    # a bfloat16 pool rounds where the XLA form rounds (test_mla_kernels)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    if g is not None:
        from paddle_tpu.kernels.primitives import dsa

        monkeypatch.setattr(dsa, "_ATTN_KEYS_PER_STEP", g * 4)
    scores = prims.dsa_indexer_scores(
        a["q_idx"], a["w_idx"], a["index_pages"], a["table"], a["q_start"],
        force="pallas")
    selected = prims.dsa_topk_select(scores, 5)
    tail = (a["latent_pages"], a["table"])
    want = prims.sparse_mla_attention(
        a["q_lat"], a["q_rope"], *tail, selected[..., :length],
        a["q_start"], sm_scale=0.3, force="reference")
    before = _attention_forms()
    got = prims.sparse_mla_attention(
        a["q_lat"], a["q_rope"], *tail, selected, a["q_start"],
        sm_scale=0.3, force="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)
    steps = selected.shape[-1] // 4 if g is None else g
    assert _attention_forms(since=before) == {(form, str(steps)): 1}
    # only the selected rows count: overwrite every other row of the cache
    keep = np.zeros(a["latent_pages"].shape[:2], bool)
    sel = np.asarray(selected)[..., :length] == 0.0
    table = np.asarray(a["table"])
    for b in range(sel.shape[0]):
        for pos in np.nonzero(sel[b].any(axis=0))[0]:
            keep[table[b, pos // 4], pos % 4] = True
    zeroed = jnp.where(jnp.asarray(keep)[..., None], a["latent_pages"], 7.0)
    again = prims.sparse_mla_attention(
        a["q_lat"], a["q_rope"], zeroed, a["table"], selected, a["q_start"],
        sm_scale=0.3, force="pallas")
    np.testing.assert_allclose(np.asarray(again), np.asarray(got), atol=tol)


@pytest.mark.parametrize("b,t,form,pages", [(16, 1, "row_tq1", 8),
                                            (1, 512, "chunk_tq16", 4)])
def test_attention_books_the_tile_it_chose_at_glm5_widths(b, t, form,
                                                          pages):
    """The decode step's and the prefill chunk's launch at the
    benchmark's shapes (64 heads, rows stored 640 wide in bfloat16, page
    128, a selection 264 pages wide): the query tile and the pages a step
    come from the shapes alone and are booked at trace time."""
    shapes = [((b, t, 64, 512), jnp.float32), ((b, t, 64, 64), jnp.float32),
              ((4129, 128, 640), jnp.bfloat16), ((b, 258), jnp.int32),
              ((b, t, 264 * 128), jnp.float32), ((b,), jnp.int32)]
    before = _attention_forms()
    out = jax.eval_shape(
        lambda *a: prims.sparse_mla_attention(*a, sm_scale=1.0 / 16,
                                              force="pallas"),
        *[jax.ShapeDtypeStruct(s, d) for s, d in shapes])
    assert out.shape == (b, t, 64, 512) and out.dtype == jnp.float32
    assert _attention_forms(since=before) == {(form, str(pages)): 1}


def _topk_oracle(scores, k):
    order = np.argsort(-scores, axis=-1, kind="stable")[..., :k]
    mask = np.full(scores.shape, -1e9, np.float32)
    np.put_along_axis(mask, order, 0.0, axis=-1)
    return mask


@pytest.mark.parametrize("force", ["pallas", "reference"])
@pytest.mark.parametrize("case", ["random", "ties", "masked", "all",
                                  "wide"])
def test_topk_select_is_exact(case, force):
    rng = np.random.RandomState(11)
    scores = rng.randn(3, 2, 96).astype(np.float32)
    k = 17
    if case == "ties":          # many equal values around the k-th
        scores = np.round(scores)
    elif case == "masked":      # fewer visible positions than k
        scores[..., 9:] = -np.inf
    elif case == "all":
        k = 200
    elif case == "wide":        # whole 8-row tiles of several lane tiles,
        # one row with ties at the k-th value and one all but masked
        scores = rng.randn(16, 1, 384).astype(np.float32)
        scores[3] = np.round(scores[3])
        scores[5, :, 7:] = -np.inf
        scores[6, :, 40:] = 0.0
    got = np.asarray(prims.dsa_topk_select(jnp.asarray(scores), k,
                                           force=force))
    if case == "all":
        assert (got == 0.0).all()
        return
    assert (got == _topk_oracle(scores, k)).all()
    assert ((got == 0.0).sum(axis=-1) == k).all()
    if case == "masked":        # every visible position is taken
        assert (got[..., :9] == 0.0).all()


# one block a side (k = 16, n = 24), then three 128-tiles a side under two
# row tiles, where a grid step no group owns would move a block index:
# no row at all, empty groups at the front, in the middle and at the end,
# a group across the row tiles' edge, and both row tiles full; then a width
# with no divisor but 128 (11 x 128) on either side, which the block holds
# whole
@pytest.mark.parametrize("sizes,k,n", [
    ([3, 0, 10, 5, 2], 16, 24), ([0, 0, 0, 0, 0], 16, 24),
    ([40, 0, 0, 0, 0], 16, 24), ([1, 1, 1, 1, 130], 16, 24),
    ([0, 0, 0, 0, 0], 384, 384), ([0, 0, 40, 30, 0], 384, 384),
    ([50, 0, 0, 60, 0], 384, 384), ([100, 0, 56, 0, 90], 384, 384),
    ([0, 120, 16, 0, 1], 384, 384), ([1, 0, 0, 0, 0], 384, 384),
    ([0, 0, 0, 0, 256], 384, 384), ([128, 0, 0, 128, 0], 384, 384),
    ([70, 0, 130, 56], 1408, 256), ([70, 0, 130, 56], 256, 1408)])
def test_grouped_matmul_pallas_against_oracle(monkeypatch, sizes, k, n):
    if k == 384:
        # a matrix this small is one block: hold it to 128 a side
        monkeypatch.setattr(grouped, "_weight_block",
                            lambda k, n, itemsize: (128, 128))
    rng = np.random.RandomState(2)
    m = max(40, sum(sizes) + 7) if k == 16 else 256
    lhs = rng.randn(m, k).astype(np.float32)
    rhs = rng.randn(len(sizes), k, n).astype(np.float32)
    want = np.zeros((m, n), np.float32)
    off = 0
    for g, size in enumerate(sizes):
        want[off:off + size] = lhs[off:off + size] @ rhs[g]
        off += size
    for force in ("pallas", "reference"):
        got = prims.grouped_matmul(jnp.asarray(lhs), jnp.asarray(rhs),
                                   jnp.asarray(sizes, jnp.int32),
                                   force=force)
        # float32 sums in another order: the room grows with their length
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=1e-4 * max(1, k // 256))
        assert (np.asarray(got)[sum(sizes):] == 0.0).all()
