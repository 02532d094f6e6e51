"""Qwen3-Next through the decode lane (models/qwen3_next.py,
kernels/primitives/gdn.py's grouped-head bodies, ops/mla_ops.py's softmax
router, serving/lane.py ``SeqState`` beside K/V rows in one pool):
gated-delta-rule layers with TWO VALUE HEADS A KEY HEAD, whose state a
SEQUENCE owns, beside gated grouped-query attention with a partial
rotation, softmax-routed held experts and a gated shared expert, every
norm's gain ``1 + w`` -- against the plain reference
(benchmark/reference/qwen3_next.py, the recurrence token by token, which
imports nothing of the program) at a tiny size with seeded float32
weights: hidden 64, 4 value heads on 2 key heads of 8 x 8, 4 query heads
on 2 K/V heads of 16 (4 entries rotated), layers l, l, f, l, 4 of 16
experts held under 3 picks, page 4, chunk 8.

Tolerances, each with its reason.  Kernels against the recurrence:
1e-4 (2e-4 on the state, whose entries are sums over a chunk), the
float32 rounding of sums taken in another order; a bfloat16 in-chunk
solve or state reads 1e-2 (``test_a_bfloat16_state_or_solve...``).
Engine and whole-sequence program against the reference: 1e-3 on the
served logit's gap, 2e-4 on log-probabilities: float32 throughout, the
chunked form against the token-by-token one."""

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
from paddle_tpu.kernels.primitives import gdn
from paddle_tpu.models import decode_blocks, olmo_hybrid, qwen3_next
from paddle_tpu.ops import mla_ops
from paddle_tpu.serving import lane
from paddle_tpu.serving.kv_pool import KVPool, TRASH_PAGE

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "qwen3-next-tiny.json")) as _f:
    CONFIG = json.load(_f)
ref = harness.load_module("reference", "qwen3_next.py")
PAGE, CHUNK = 4, 8


def _cfg(**over):
    return qwen3_next.Qwen3NextConfig(
        **dict(CONFIG["builder"]["config_args"], **over))


def _scope_with(weights):
    scope = fluid.Scope()
    for name, w in weights.items():
        scope.set(name, w)
    return scope


@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(CONFIG, 20261004)


def _served_gaps(weights, prompts, outs):
    gaps = []
    with jax.default_matmul_precision("highest"):
        for p, o in zip(prompts, outs):
            logits = ref.served_logits(weights, CONFIG, p, o)
            got = jnp.take_along_axis(
                logits, jnp.asarray(o, jnp.int32)[:, None], axis=1)[:, 0]
            gaps.append(float(jnp.max(jnp.max(logits, axis=1) - got)))
    return gaps


def _run(build, feed, weights):
    """The fetched value of a little program ``build()`` makes."""
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        out = build()
    (got,) = fluid.Executor(fluid.CPUPlace()).run(
        main, feed=feed, fetch_list=[out], scope=_scope_with(weights))
    return np.asarray(got)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "qwen3_next.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+(paddle_tpu|benchmark)", src,
                         re.M)
    assert "lax.scan" in src            # the recurrence, token by token


def test_program_parameters_are_the_references():
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        qwen3_next.build_qwen3_next_lm(_cfg())
    want = {p.name: tuple(p.shape)
            for p in main.global_block().all_parameters()}
    have = {n: tuple(s) for n, (s, _, _) in ref.param_shapes(CONFIG).items()}
    assert have == want
    # a softmax router has no selection bias
    assert not any(n.endswith("router.b_0") for n in want)


def test_the_lane_declares_kv_rows_and_a_state_of_the_value_heads():
    cfg = _cfg()
    assert cfg.layer_types == ["linear_attention", "linear_attention",
                               "full_attention", "linear_attention"]
    decl = cfg.decode_lane()
    assert decl.num_layers == 1 and decl.state_layers == [0, 1, 3]
    assert [(r.name, r.width) for r in decl.cache_rows(None)] == [
        ("k", 2 * 16), ("v", 2 * 16)]
    # the state's columns are the VALUE heads'; the convolution carries
    # 2 H_k d_k + H_v d_v channels
    assert [(s.name, tuple(s.shape), s.dtype) for s in decl.seq_state] == [
        ("s", (8, 4 * 8), "float32"),
        ("conv", (3 * (2 * 2 * 8 + 4 * 8),), "float32")]
    assert len(decl.device_counters) == 2 * 4            # 4 expert layers
    with pytest.raises(ValueError, match="no int8 form"):
        decl.cache_rows("int8")
    with pytest.raises(ValueError, match="whole groups"):
        _cfg(linear_num_key_heads=3)
    with pytest.raises(ValueError, match="experts behind every layer"):
        _cfg(mlp_only_layers=[0])
    # the published layout: every fourth layer full, 2 MiB of state
    full = qwen3_next.Qwen3NextConfig()
    assert full.full_layers == list(range(3, 48, 4))
    assert [tuple(s.shape) for s in full.seq_state()] == [
        (128, 4096), (3 * 8192,)]
    assert full.rotary_dim == 64
    # ... and the model with as many key heads as value heads says where
    # the other one lives
    with pytest.raises(ValueError, match="models/qwen3_next.py"):
        olmo_hybrid.OlmoHybridConfig.tiny(linear_num_key_heads=1)


# ---------------------------------------------------------------------------
# the kernels with 2 value heads a key head, against the recurrence
# ---------------------------------------------------------------------------


def _operands(rng, n, heads_k, heads, dk, dv, decay="mid"):
    q = rng.standard_normal((n, heads_k, dk)).astype(np.float32)
    # keys that lean one way, as a SiLU's outputs do: k_t . k_j well over 0
    k = rng.standard_normal((n, heads_k, dk)).astype(np.float32) + 0.5
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((n, heads, dv)).astype(np.float32)
    b = rng.random((n, heads))
    shape = (n, heads)
    g = {"mid": -0.5 * rng.random(shape),
         "near1": -1e-4 * rng.random(shape),
         # the published initialisation's strong end (A near 16, dt near
         # 0.1) beside heads that hardly decay
         "strong": -np.where(rng.random(shape) < 0.7,
                             1.0 + 2.0 * rng.random(shape),
                             1e-3 * rng.random(shape))}[decay]
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, b)]


def _recurrence(q, k, v, g, b, s0):
    """The float32 recurrence in numpy, value head h on key head h // r:
    s0 [H, d_k, d_v] -> (o [T, H, d_v], s)."""
    q, k, v, g, b = (np.asarray(x, np.float64) for x in (q, k, v, g, b))
    r = v.shape[1] // q.shape[1]
    s = np.asarray(s0, np.float64).copy()
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        for h in range(v.shape[1]):
            kh, qh = k[t, h // r], q[t, h // r]
            sh = np.exp(g[t, h]) * s[h]
            u = b[t, h] * (v[t, h] - sh.T @ kh)
            s[h] = sh + np.outer(kh, u)
            out[t, h] = s[h].T @ qh
    return out, s


def _stored(s):
    return np.asarray(s).transpose(1, 0, 2).reshape(s.shape[1], -1)


@pytest.mark.parametrize("decay", ["mid", "near1", "strong"])
@pytest.mark.parametrize("n,heads_k,heads,dk,dv", [
    (8, 2, 4, 8, 8), (24, 1, 3, 8, 16), (64, 1, 2, 128, 128),
    (200, 2, 4, 16, 32)])
@pytest.mark.parametrize("force", ["reference", "pallas"])
def test_the_grouped_chunk_forms_are_the_recurrence(force, n, heads_k, heads,
                                                    dk, dv, decay):
    rng = np.random.default_rng(n + heads)
    ops = _operands(rng, n, heads_k, heads, dk, dv, decay)
    state = jnp.asarray(rng.standard_normal((5, dk, heads * dv)), jnp.float32)
    for fresh in (False, True):
        s0 = np.zeros((heads, dk, dv)) if fresh else np.asarray(
            state[2]).reshape(dk, heads, dv).transpose(1, 0, 2)
        want_o, want_s = _recurrence(*ops, s0)
        got_o, got_s = prims.gated_delta_chunk(
            *ops, state, jnp.int32(2), jnp.bool_(fresh), force=force)
        np.testing.assert_allclose(got_o, want_o, atol=1e-4)
        np.testing.assert_allclose(got_s[2], _stored(want_s), atol=2e-4)
        # the other blocks are not touched
        np.testing.assert_array_equal(np.delete(got_s, 2, 0),
                                      np.delete(state, 2, 0))


@pytest.mark.parametrize("slots,heads_k,heads,dk,dv", [(4, 2, 4, 8, 16),
                                                      (3, 16, 32, 128, 128)])
@pytest.mark.parametrize("force", ["reference", "pallas"])
def test_the_grouped_step_forms_are_the_recurrence_in_place(
        force, slots, heads_k, heads, dk, dv):
    rng = np.random.default_rng(slots)
    ops = _operands(rng, slots, heads_k, heads, dk, dv, "strong")
    state = jnp.asarray(rng.standard_normal((6, dk, heads * dv)), jnp.float32)
    blocks = jnp.asarray([3, 0, 5, 0][:slots], jnp.int32)   # two inactive
    got_o, got_s = prims.gated_delta_step(*ops, state, blocks, force=force)
    for slot, blk in enumerate(np.asarray(blocks)):
        if blk == TRASH_PAGE:
            continue
        s0 = np.asarray(state[blk]).reshape(dk, heads, dv).transpose(1, 0, 2)
        want_o, want_s = _recurrence(*(x[slot:slot + 1] for x in ops), s0)
        np.testing.assert_allclose(got_o[slot], want_o[0], atol=1e-5)
        np.testing.assert_allclose(got_s[blk], _stored(want_s), atol=1e-5)
    for blk in (1, 2, 4):        # blocks no slot names are as they were
        np.testing.assert_array_equal(got_s[blk], state[blk])
    if heads == 32:     # the published sizes: sixteen value heads a lane
        # tile, in whole groups, so eight rows of q and k a product
        assert gdn._heads_per_tile(32, 128, 128, 2) == 16
        assert gdn._heads_per_tile(30, 96, 192) == 10       # as it was


@pytest.mark.parametrize("force", ["reference", "pallas"])
def test_key_heads_repeated_in_memory_give_the_grouped_forms_numbers(force):
    """q and k handed over at H_k heads give, bit for bit, what the forms
    of as many key heads as value heads (today's) give for q and k
    repeated: the grouped bodies index, they do not compute otherwise."""
    rng = np.random.default_rng(21)
    q, k, v, g, b = _operands(rng, 72, 2, 4, 8, 16, "mid")
    qr, kr = (jnp.repeat(x, 2, axis=1) for x in (q, k))
    state = jnp.asarray(rng.standard_normal((4, 8, 64)), jnp.float32)
    args = (state, jnp.int32(2), jnp.bool_(False))
    want_o, want_s = prims.gated_delta_chunk(qr, kr, v, g, b, *args,
                                             force=force)
    got_o, got_s = prims.gated_delta_chunk(q, k, v, g, b, *args, force=force)
    np.testing.assert_array_equal(got_o, want_o)
    np.testing.assert_array_equal(got_s, want_s)
    blocks = jnp.asarray([1, 0, 3], jnp.int32)
    want_o, want_s = prims.gated_delta_step(
        qr[:3], kr[:3], v[:3], g[:3], b[:3], state, blocks, force=force)
    got_o, got_s = prims.gated_delta_step(
        q[:3], k[:3], v[:3], g[:3], b[:3], state, blocks, force=force)
    np.testing.assert_array_equal(got_o[::2], want_o[::2])
    np.testing.assert_array_equal(got_s[1:], want_s[1:])
    # groups are whole, and q and k agree
    with pytest.raises(ValueError, match="whole groups"):
        prims.gated_delta_chunk(q, k, v[:, :3], g[:, :3], b[:, :3],
                                state[:, :, :48], jnp.int32(2),
                                jnp.bool_(False))


@pytest.mark.parametrize("force", ["reference", "pallas"])
def test_a_padded_tail_leaves_the_grouped_state_as_it_was(force):
    """beta = 0 and g = 0 past the last real position: the state after 19
    real positions of 24 is the state after a chunk of 19."""
    rng = np.random.default_rng(7)
    q, k, v, g, b = _operands(rng, 24, 2, 4, 8, 16, "strong")
    live = (jnp.arange(24) < 19)[:, None]
    state = jnp.asarray(rng.standard_normal((3, 8, 64)), jnp.float32)
    _, padded = prims.gated_delta_chunk(
        q, k, v, g * live, b * live, state, jnp.int32(1), jnp.bool_(False),
        force=force)
    _, short = prims.gated_delta_chunk(
        q[:19], k[:19], v[:19], g[:19], b[:19], state, jnp.int32(1),
        jnp.bool_(False), force="reference")
    np.testing.assert_allclose(padded, short, atol=1e-5)


def test_grouped_chunks_then_steps_are_one_recurrence():
    """The state handed from chunk to chunk and into the steps: 40 tokens
    as a chunk of 16, a chunk of 16 (the last 5 padded), and 13 steps,
    against one pass over all 40."""
    rng = np.random.default_rng(40)
    q, k, v, g, b = _operands(rng, 40, 2, 4, 8, 16, "strong")
    state = jnp.asarray(rng.standard_normal((4, 8, 64)), jnp.float32)
    want, _ = _recurrence(q, k, v, g, b, np.zeros((4, 8, 16)))
    o1, state = prims.gated_delta_chunk(
        q[:16], k[:16], v[:16], g[:16], b[:16], state, jnp.int32(3),
        jnp.bool_(True), force="pallas")
    live = (jnp.arange(16) < 11)[:, None]
    o2, state = prims.gated_delta_chunk(
        q[16:32], k[16:32], v[16:32], g[16:32] * live, b[16:32] * live,
        state, jnp.int32(3), jnp.bool_(False), force="pallas")
    outs = [o1, o2[:11]]
    blocks = jnp.asarray([0, 3], jnp.int32)
    for t in range(27, 40):
        row = [jnp.stack([x[t], x[t]]) for x in (q, k, v, g, b)]
        o, state = prims.gated_delta_step(*row, state, blocks,
                                          force="pallas")
        outs.append(o[1:2])
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=1e-4)


def test_a_bfloat16_state_or_solve_fails_the_kernels_tolerance(monkeypatch):
    """What the 1e-4 above separates: the recurrence with its state
    rounded to bfloat16 after every token, and the chunk kernel with its
    in-chunk solve's products in bfloat16, both miss it by far."""
    rng = np.random.default_rng(5)
    ops = _operands(rng, 128, 1, 2, 16, 32, "near1")  # a state that builds up
    want, _ = _recurrence(*ops, np.zeros((2, 16, 32)))
    q, k = (jnp.repeat(x, 2, axis=1) for x in ops[:2])
    with jax.default_matmul_precision("highest"):
        _, rounded = ref.delta_rule(
            jnp.zeros((2, 16, 32), jnp.float32), q, k, *ops[2:],
            jnp.bfloat16)
        _, exact = ref.delta_rule(
            jnp.zeros((2, 16, 32), jnp.float32), q, k, *ops[2:],
            jnp.float32)
    np.testing.assert_allclose(exact, want, atol=1e-4)
    assert float(jnp.max(jnp.abs(rounded - want))) > 1e-3

    def bf16_mm(a, b):
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)

    monkeypatch.setattr(gdn, "_mm", bf16_mm)
    state = jnp.zeros((3, 16, 64), jnp.float32)
    low, _ = prims.gated_delta_chunk(*ops, state, jnp.int32(1),
                                     jnp.bool_(True), force="pallas")
    assert float(jnp.max(jnp.abs(low - want))) > 1e-3


def test_the_form_counter_tells_the_grouped_bodies_apart():
    rng = np.random.default_rng(3)
    ops = _operands(rng, 64, 1, 2, 128, 128)
    state = jnp.zeros((3, 128, 256), jnp.float32)

    def samples():
        return dict(obs.snapshot().get("pt_gated_delta_form_total", {})
                    .get("samples", {}))

    before = samples()
    prims.gated_delta_chunk(*ops, state, jnp.int32(1), jnp.bool_(True),
                            force="pallas")
    prims.gated_delta_step(*(x[:2] for x in ops), state,
                           jnp.asarray([1, 2], jnp.int32), force="pallas")
    after = samples()
    for key in (("gated_delta_chunk", "sub64_vk2"),
                ("gated_delta_step", "heads2_vk2")):
        assert after[key] == before.get(key, 0) + 1
    # as many key heads as value heads: the names they had
    q, k = (jnp.repeat(x, 2, axis=1) for x in ops[:2])
    prims.gated_delta_chunk(q, k, *ops[2:], state, jnp.int32(1),
                            jnp.bool_(True), force="pallas")
    assert samples()[("gated_delta_chunk", "sub64")] == before.get(
        ("gated_delta_chunk", "sub64"), 0) + 1


# ---------------------------------------------------------------------------
# the router, the shared expert's gate, the norms' gains
# ---------------------------------------------------------------------------


def test_the_softmax_router_picks_and_weighs_a_hand_written_case():
    """Three tokens over six experts, two picks: the picks are the two
    largest softmax scores, the gates those two over their sum."""
    logits = np.array([[2.0, 0.0, 1.0, -1.0, 0.5, -3.0],
                       [0.0, 0.0, 0.0, 4.0, 0.0, 3.0],
                       [-1.0, 3.0, -2.0, 0.0, 2.5, 0.0]], np.float32)
    # x = logits, W_r = I: the router's product is the logits
    picks, gates = mla_ops.route_softmax_topk(
        jnp.asarray(logits), jnp.eye(6, dtype=jnp.float32), 2, 1.0, True)
    np.testing.assert_array_equal(picks, [[0, 2], [3, 5], [1, 4]])
    e = np.exp(logits)
    want = np.stack([e[0, [0, 2]] / e[0, [0, 2]].sum(),
                     e[1, [3, 5]] / e[1, [3, 5]].sum(),
                     e[2, [1, 4]] / e[2, [1, 4]].sum()])
    np.testing.assert_allclose(gates, want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(1), 1.0, rtol=1e-6)
    # not normalised: the softmax scores themselves
    _, raw = mla_ops.route_softmax_topk(
        jnp.asarray(logits), jnp.eye(6, dtype=jnp.float32), 2, 1.0, False)
    np.testing.assert_allclose(raw[0], e[0, [0, 2]] / e[0].sum(), rtol=1e-6)
    # a sigmoid router picks the same experts (both are monotone) and
    # weighs them otherwise
    _, sig = mla_ops.route_sigmoid_topk(
        jnp.asarray(logits), jnp.eye(6, dtype=jnp.float32),
        jnp.zeros(6, jnp.float32), 2, 1.0, True)
    assert float(jnp.max(jnp.abs(sig - gates))) > 0.05


def _expert_layer(cfg, weights, x, name="qwen3n_layer_1", **kw):
    kw = dict(dict(score_func="softmax",
                   shared_width=cfg.shared_expert_intermediate_size,
                   shared_gate=True), **kw)

    def build():
        xin = fluid.data("x", list(x.shape), False, dtype="float32")
        return decode_blocks.expert_ffn(xin, 1, None, None, cfg, name, None,
                                        **kw)

    return _run(build, {"x": x}, weights)[0]


def _uncut():
    uncut = dict(CONFIG, num_experts=16,
                 deployment=dict(CONFIG["deployment"], first_expert=0))
    weights = ref.init_weights(uncut, 7)
    layer = "qwen3n_layer_1_"
    p = {k[len(layer):]: v for k, v in weights.items()
         if k.startswith(tuple(layer + part
                               for part in ("ffn_", "moe_", "shared_")))}
    z = ref.sizes(uncut)
    z["normalize"] = True
    return uncut, weights, layer, p, tuple(sorted(z.items()))


def test_four_shares_are_the_uncut_layer_the_gated_shared_expert_once():
    """Four chips of an EP4 deployment at the tiny size, 4 of 16 experts
    each: what the four shares' expert layers add to the stream, the
    gated shared expert (computed on every chip) counted once, is the
    reference's uncut layer; each share through the program's block."""
    uncut, weights, layer, p, zt = _uncut()
    rng = np.random.RandomState(2)
    x = rng.normal(0, 1, (8, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.finish_rows(jnp.asarray(x), jnp.zeros((8, 64)), p, z=zt,
                               eps=1e-6, matmul=jnp.matmul) - x
        f = ref.norm(jnp.asarray(x), p["ffn_norm.scale"], 1e-6)
        shared = ref.swiglu(f, *(p[f"shared_{k}.w_0"]
                                 for k in ("gate", "up", "down")),
                            jnp.matmul) * jax.nn.sigmoid(
            f @ p["shared_expert_gate.w_0"])
    total = np.zeros((8, 64), np.float32)
    for chip in range(4):
        share = dict(weights)
        for k in ("gate", "up", "down"):
            name = f"{layer}moe_experts_{k}.w_0"
            share[name] = weights[name][4 * chip:4 * chip + 4]
        total += _expert_layer(_cfg(held_experts=4, first_expert=4 * chip),
                               share, x[None])
    np.testing.assert_allclose(total - 3 * np.asarray(shared),
                               np.asarray(want), rtol=2e-4, atol=2e-4)
    assert float(jnp.abs(want - shared).max()) > 0.05     # experts matter
    assert float(jnp.abs(shared).max()) > 0.05      # and so does the shared


def test_a_sigmoid_router_or_an_ungated_shared_expert_is_not_this_model():
    uncut, weights, layer, p, zt = _uncut()
    rng = np.random.RandomState(4)
    x = rng.normal(0, 1, (1, 8, 64)).astype(np.float32)
    cfg = _cfg(held_experts=16, first_expert=0)
    sound = _expert_layer(cfg, weights, x)
    with jax.default_matmul_precision("highest"):
        want = ref.finish_rows(jnp.asarray(x[0]), jnp.zeros((8, 64)), p, z=zt,
                               eps=1e-6, matmul=jnp.matmul) - x[0]
    np.testing.assert_allclose(sound, want, rtol=2e-4, atol=2e-4)
    ungated = _expert_layer(cfg, weights, x, shared_gate=False)
    assert np.max(np.abs(ungated - sound)) > 0.01
    bias = {f"{layer}moe_router.b_0": jnp.zeros(16, jnp.float32)}
    sigmoid = _expert_layer(cfg, dict(weights, **bias), x,
                            score_func="sigmoid")
    assert np.max(np.abs(sigmoid - sound)) > 0.01
    with pytest.raises(ValueError, match="score_func"):
        _expert_layer(cfg, weights, x, score_func="tanh")


def test_a_zero_centred_gain_is_one_plus_the_stored_weight():
    rng = np.random.RandomState(1)
    x = rng.normal(0, 2, (2, 3, 16)).astype(np.float32)
    w = rng.normal(0, 0.3, 16).astype(np.float32)

    def normed(offset, scale):
        def build():
            xin = fluid.data("x", [2, 3, 16], False, dtype="float32")
            return fluid.layers.rms_norm(
                xin, epsilon=1e-6, gain_offset=offset,
                param_attr=fluid.ParamAttr(name="g.scale"))
        return _run(build, {"x": x}, {"g.scale": scale})

    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * (1 + w)
    np.testing.assert_allclose(normed(1.0, w), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(normed(1.0, w), normed(0.0, 1 + w))
    assert np.max(np.abs(normed(0.0, w) - want)) > 0.1   # a plain gain: not it
    # the model's norms start at w = 0 (a gain of 1) and read 1 + w
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        xin = fluid.data("x", [2, 3, 16], False, dtype="float32")
        decode_blocks._rms(xin, "n", _cfg())
    (op,) = [o for o in main.global_block().ops if o.type == "rms_norm"]
    assert op.attrs["gain_offset"] == 1.0


# ---------------------------------------------------------------------------
# the full-attention layer: the partial rotation, the gate's place in W_q
# ---------------------------------------------------------------------------


def _gated_gqa_by_hand(x, p, rot, gate_at="head"):
    """The full layer's equations head by head in numpy float64: x [T, D]
    -> [T, D].  ``rot`` entries of a head are rotated; ``gate_at``
    "head" reads a head's 2 d columns as [q | gate], "tail" reads W_q as
    [every head's q | every head's gate]."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    t, heads, kvh, d = x.shape[0], 4, 2, 16

    def norm(y, w):
        return y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-6) * (1 + w)

    def rope(y, pos):
        half = rot // 2
        inv = 1.0 / (1e7 ** (np.arange(0, rot, 2) / rot))
        c, s = np.cos(pos * inv), np.sin(pos * inv)
        a, b = y[:half], y[half:rot]
        return np.concatenate([a * c - b * s, b * c + a * s, y[rot:]])

    u = norm(np.asarray(x, np.float64), p["input_norm.scale"])
    qg, kk, vv = u @ p["q.w_0"], u @ p["k.w_0"], u @ p["v.w_0"]
    out = np.zeros((t, heads * d))
    for j in range(heads):
        if gate_at == "head":
            q, gate = (qg[:, 2 * d * j:2 * d * j + d],
                       qg[:, 2 * d * j + d:2 * d * (j + 1)])
        else:
            q, gate = (qg[:, d * j:d * (j + 1)],
                       qg[:, heads * d + d * j:heads * d + d * (j + 1)])
        kv = j // (heads // kvh)
        k, v = kk[:, d * kv:d * (kv + 1)], vv[:, d * kv:d * (kv + 1)]
        q = np.stack([rope(norm(q[i], p["q_norm.scale"]), i)
                      for i in range(t)])
        k = np.stack([rope(norm(k[i], p["k_norm.scale"]), i)
                      for i in range(t)])
        s = q @ k.T / np.sqrt(d)
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
        pr = np.exp(s - s.max(-1, keepdims=True))
        o = (pr / pr.sum(-1, keepdims=True)) @ v
        out[:, d * j:d * (j + 1)] = o / (1 + np.exp(-gate))
    return out @ p["o.w_0"]


def test_the_full_layer_rotates_a_quarter_and_reads_its_gate_from_w_q(
        weights):
    cfg = _cfg()
    rng = np.random.RandomState(5)
    x = rng.normal(0, 1, (1, 8, 64)).astype(np.float32)

    def build():
        L = fluid.layers
        xin = fluid.data("x", [1, 8, 64], False, dtype="float32")
        pos = L.reshape(L.range(0, 8, 1, "int64"), shape=[1, 8])
        pools = tuple(L.fill_constant(shape=[3, 4, 32], value=0.0,
                                      dtype="float32") for _ in range(2))
        table = L.reshape(L.cast(L.range(1, 3, 1, "int64"), "int32"),
                          shape=[1, 2])
        write = lane._page_writer(8, L.reshape(table, shape=[2]))
        return qwen3_next._full_attention(
            xin, pos, table, L.fill_constant(shape=[1], value=0,
                                             dtype="int32"),
            pools, write, (1, 8), cfg, "qwen3n_layer_2", None)

    got = _run(build, {"x": x}, weights)[0]
    layer = "qwen3n_layer_2_"
    p = {k[len(layer):]: v for k, v in weights.items() if k.startswith(layer)}
    np.testing.assert_allclose(got, _gated_gqa_by_hand(x[0], p, 4),
                               atol=2e-4)
    # the test is not blind: a whole-head rotation, no rotation, or the
    # gate read from W_q's tail each move the output
    for other in (_gated_gqa_by_hand(x[0], p, 16),
                  _gated_gqa_by_hand(x[0], p, 0),
                  _gated_gqa_by_hand(x[0], p, 4, gate_at="tail")):
        assert np.max(np.abs(other - got)) > 1e-2
    # ... and the reference's layer is the same numbers
    z = tuple(sorted(ref.sizes(CONFIG).items()))
    with jax.default_matmul_precision("highest"):
        want = ref.full_layer(
            jnp.pad(jnp.asarray(x[0]), ((0, ref.QUERY_BLOCK - 8), (0, 0))),
            p, 8, z=z, eps=1e-6, theta=1e7, matmul=jnp.matmul)[:8]
    np.testing.assert_allclose(got, want, atol=2e-4)


# ---------------------------------------------------------------------------
# engine: prefill chunks, then decode steps, through pages and state,
# against the reference's full forward
# ---------------------------------------------------------------------------

# 30 ends inside a chunk of 8, as 5, 45, 17 and 9 do; 5 sequences over 3
# slots and 4 state blocks: blocks pass from one sequence to the next
PROMPTS = (30, 5, 45, 17, 9)


def _generate(weights, force=None, n_new=12, prompts=PROMPTS, slots=3,
              **engine):
    cfg = _cfg()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in prompts]
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=slots, page_size=PAGE,
        max_len=64, attn_force=force, auto_start=False,
        name=f"q3n-{force}-{len(prompts)}-{slots}-{sorted(engine.items())}",
        **engine)
    try:
        assert eng.warmup() == 2
        eng.start()
        outs = eng.generate(prompts, max_new_tokens=n_new, timeout=600)
        eng.book_device_counters()
        return eng, prompts, outs, eng.stats()
    finally:
        eng.close()


@pytest.mark.parametrize("force", [None, "pallas"])
def test_engine_matches_the_reference_through_pages_and_state(weights,
                                                              force):
    eng, prompts, outs, stats = _generate(weights, force)
    assert all(len(o) == 12 for o in outs)
    assert max(_served_gaps(weights, prompts, outs)) < 1e-3
    assert stats["evictions"] == 0
    kinds = stats["kv_pool"]["kinds"]
    assert set(kinds) == {"full", "state"}
    state = kinds["state"]
    assert state["pages_total"] == 3 + 1     # a slot each, one prefilling
    assert state["alloc_total"] == len(PROMPTS)
    assert state["freed"] == {"window": 0, "end": len(PROMPTS), "evict": 0}
    assert state["pages_in_use"] == kinds["full"]["pages_in_use"] == 0
    # the tensors: [blocks, *shape] a linear layer, a K and a V row tensor
    # of the full layer
    assert np.shape(eng.scope.get("@KVPOOL@s_l3")) == (5, 8, 32)
    assert np.shape(eng.scope.get("@KVPOOL@conv_l0")) == (5, 3 * 64)
    assert eng.scope.get("@KVPOOL@s_l2") is None
    assert eng.pool.var_names == [("@KVPOOL@k_l0", "@KVPOOL@v_l0")]
    assert np.shape(eng.scope.get("@KVPOOL@k_l0")) == (49, 4, 32)
    snap = obs.snapshot()
    rows = {k[1] for k in snap["pt_decode_cache_bytes"]["samples"]
            if k[0] == eng.name}
    assert rows == {"k", "v", "s", "conv"}
    dispatch = snap["pt_kernel_dispatch_total"]["samples"]
    mode = "reference" if force is None else "interpret"
    for primitive in ("gated_delta_chunk", "gated_delta_step",
                      "paged_attention_grouped", "grouped_matmul"):
        assert dispatch[(primitive, mode)] >= 1
    # the expert layers' device-side counters under both executables
    picks = snap["pt_moe_picks_total"]["samples"]
    assert picks[(eng.name, "any")] == \
        picks[(eng.name, "held")] + picks[(eng.name, "absent")] > 0
    assert 0 < picks[(eng.name, "held")] < picks[(eng.name, "any")]
    touched = snap["pt_moe_experts_touched_total"]["samples"]
    assert touched[(eng.name, "decode")] > 0
    assert touched[(eng.name, "prefill")] > 0
    if force == "pallas":
        forms = snap["pt_gated_delta_form_total"]["samples"]
        assert forms[("gated_delta_chunk", "sub8_vk2")] >= 3
        assert forms[("gated_delta_step", "heads4_vk2")] >= 3


def test_more_than_sixty_four_slots_give_every_block_and_page_back(weights):
    """72 slots (no cell ran more than 32 before this one, and this
    model's runs 64): 90 requests of unequal lengths and outputs, so
    slots turn over while others decode; a sample is what the reference
    serves, and both kinds come back whole."""
    count = 90
    lengths = tuple(5 + (7 * i) % 41 for i in range(count))
    cfg = _cfg()
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in lengths]
    new = [4 + (5 * i) % 11 for i in range(count)]
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=72, page_size=PAGE,
        max_len=64, auto_start=False, name="q3n-72-slots")
    try:
        eng.warmup()
        reqs = [eng.submit_request(p, n) for p, n in zip(prompts, new)]
        eng.start()
        outs = [r.future.result(timeout=900) for r in reqs]
        stats = eng.stats()
        feed = eng._dec_layout.unpack(eng._decode_feed([]))
    finally:
        eng.close()
    assert [len(o) for o in outs] == new
    assert stats["pool_slots"] == 72 and stats["evictions"] == 0
    assert feed["dec_state_block"].shape == (72,)
    assert feed["dec_page_table"].shape == (72, 16)
    sample = list(range(0, count, 9))
    assert max(_served_gaps(weights, [prompts[i] for i in sample],
                            [outs[i] for i in sample])) < 1e-3
    kinds = stats["kv_pool"]["kinds"]
    assert kinds["state"]["pages_total"] == 72 + 1
    assert kinds["state"]["alloc_total"] == count
    assert kinds["state"]["freed"]["end"] == count
    assert kinds["full"]["freed"]["end"] == kinds["full"]["alloc_total"]
    assert kinds["state"]["pages_in_use"] == 0
    assert kinds["full"]["pages_in_use"] == 0
    assert stats["tokens"] == sum(new)


def test_the_whole_sequence_program_is_the_reference(weights):
    cfg = _cfg()
    rng = np.random.RandomState(5)
    tokens = rng.randint(1, cfg.vocab_size, 24)

    def build():
        return qwen3_next.build_qwen3_next_lm(cfg, seq_len=24, page_size=4)

    got = _run(build, {"pf_tok": tokens[None].astype(np.int64),
                       "pf_pos": np.arange(24)[None].astype(np.int64)},
               weights)
    with jax.default_matmul_precision("highest"):
        want = jax.nn.log_softmax(ref.forward(weights, CONFIG, tokens,
                                              np.arange(24)))
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4)
    # the norms' stored w is drawn, not 0: 1 + w is held by the above
    assert float(jnp.abs(weights["qwen3n_final_norm.scale"]).max()) > 0.1


# ---------------------------------------------------------------------------
# the committed configuration's bytes
# ---------------------------------------------------------------------------


def test_the_pools_modeled_bytes_are_the_configurations_products():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-ep4.json")) as f:
        config = json.load(f)
    cfg = qwen3_next.Qwen3NextConfig(**config["builder"]["config_args"])
    e = config["engine"]
    assert e["pool_slots"] == 64
    per_seq = -(-e["max_len"] // e["page_size"])
    pages = e["pool_slots"] * per_seq + 1
    assert (per_seq, pages) == (140, 8961)
    decl = cfg.decode_lane()
    assert decl.num_layers == 2 and decl.state_layers == [0, 1, 2, 4, 5, 6]
    pool = KVPool(decl.num_layers, decl.cache_rows(None), pages,
                  e["page_size"], per_seq, seq_state=decl.seq_state,
                  state_layers=decl.state_layers,
                  state_blocks=e["pool_slots"] + 2)
    kv = 2 * 8961 * 128 * 2048
    state = 6 * 66 * (2097152 + 98304)
    assert pool.kind_bytes("full") == kv
    assert pool.kind_bytes("state") == state
    assert pool.modeled_bytes() == kv + state
    assert round(kv / 1e9, 2) == 4.70 and round(state / 1e9, 2) == 0.87
    n = sum(int(np.prod(s)) for s, _, _ in ref.param_shapes(config).values())
    assert round(n / 1e6, 1) == 3667.3
    # 7.33 + 4.70 + 0.87 GB resident
    assert abs(2 * n + kv + state - 12.90e9) < 0.01e9
    # reduced: the three keys and nothing else; no width among them
    assert set(config["changed"]) == {"num_hidden_layers", "num_experts",
                                      "vocab_size"}
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    assert config["deployment"]["pipeline_stages"] == 6
    assert config["num_experts_total"] == 512
