"""Kimi-Linear through the decode lane (models/kimi_linear.py,
kernels/primitives/kda.py, serving/lane.py ``SeqState`` beside latent
rows in one pool): Kimi-Delta-Attention layers, a delta rule whose decay
is one number a KEY CHANNEL, whose state a SEQUENCE owns, beside
latent-attention layers with no rotary positions and held experts,
against the plain reference (benchmark/reference/kimi_linear.py, the
recurrence token by token, which imports nothing of the program) at a
tiny size with seeded float32 weights: hidden 64, 3 KDA heads of 8 x 8,
4 latent heads over rank 32, layers k, k, l, k, 4 of 8 experts held,
page 4, chunk 8.

Tolerances, each with its reason.  Kernels against the recurrence:
1e-4 (2e-4 on the state, whose entries are sums over a chunk), the
float32 rounding of sums taken in another order; a bfloat16 in-chunk
solve or state would read 1e-2 (``test_a_bfloat16_state_or_solve...``).
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
from paddle_tpu.kernels.primitives import gdn, kda
from paddle_tpu.models import decode_blocks, kimi_linear
from paddle_tpu.serving.kv_pool import KVPool, TRASH_PAGE

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "kimi-linear-tiny.json")) as _f:
    CONFIG = json.load(_f)
ref = harness.load_module("reference", "kimi_linear.py")
PAGE, CHUNK = 4, 8


def _cfg(**over):
    return kimi_linear.KimiLinearConfig(
        **dict(CONFIG["builder"]["config_args"], **over))


def _scope_with(weights):
    scope = fluid.Scope()
    for name, w in weights.items():
        scope.set(name, w)
    return scope


@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(CONFIG, 20261002)


def _served_gaps(weights, prompts, outs):
    gaps = []
    with jax.default_matmul_precision("highest"):
        for p, o in zip(prompts, outs):
            logits = ref.served_logits(weights, CONFIG, p, o)
            got = jnp.take_along_axis(
                logits, jnp.asarray(o, jnp.int32)[:, None], axis=1)[:, 0]
            gaps.append(float(jnp.max(jnp.max(logits, axis=1) - got)))
    return gaps


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "kimi_linear.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+(paddle_tpu|benchmark)", src,
                         re.M)
    assert "lax.scan" in src            # the recurrence, token by token


def test_program_parameters_are_the_references():
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        kimi_linear.build_kimi_linear_lm(_cfg())
    want = {p.name: tuple(p.shape)
            for p in main.global_block().all_parameters()}
    have = {n: tuple(s) for n, (s, _, _) in ref.param_shapes(CONFIG).items()}
    assert have == want


def test_the_lane_declares_latent_rows_and_state_from_the_sources_lists():
    cfg = _cfg()
    assert cfg.layer_kinds == ["kda", "kda", "latent", "kda"]
    decl = cfg.decode_lane()
    assert decl.num_layers == 1 and decl.state_layers == [0, 1, 3]
    (row,) = decl.cache_rows(None)
    assert (row.name, row.width) == ("latent", 128)      # 40 -> a lane tile
    assert [(s.name, tuple(s.shape), s.dtype) for s in decl.seq_state] == [
        ("s", (8, 3 * 8), "float32"), ("conv", (3 * 3 * 3 * 8,), "float32")]
    assert len(decl.device_counters) == 2 * 3            # 3 expert layers
    with pytest.raises(ValueError, match="no int8 form"):
        decl.cache_rows("int8")
    with pytest.raises(ValueError, match="each of the layers 1 .. 4 once"):
        _cfg(linear_attn_config=dict(
            CONFIG["linear_attn_config"], kda_layers=[1, 2]))
    # the published layout: 20 KDA layers beside 7 latent ones
    full = kimi_linear.KimiLinearConfig()
    assert len(full.kda_layers) == 20 and full.latent_layers == [
        3, 7, 11, 15, 19, 23, 26]
    assert [tuple(s.shape) for s in full.seq_state()] == [
        (128, 4096), (3 * 12288,)]


# ---------------------------------------------------------------------------
# (a)-(c) the kernels against the recurrence
# ---------------------------------------------------------------------------


def _operands(rng, n, heads, dk, dv, decay="mid", channel=True):
    q = rng.standard_normal((n, heads, dk)).astype(np.float32)
    # keys that lean one way, as a SiLU's outputs do: k_t . k_j well over 0
    k = rng.standard_normal((n, heads, dk)).astype(np.float32) + 0.5
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((n, heads, dv)).astype(np.float32)
    b = rng.random((n, heads))
    shape = (n, heads, dk) if channel else (n, heads)
    g = {"mid": -0.5 * rng.random(shape),
         "near1": -1e-4 * rng.random(shape),
         # the published initialisation's strong end (A near 16, dt near
         # 0.1): about e^-1.6 a token, some channels stronger, beside
         # channels that hardly decay
         "strong": -np.where(rng.random(shape) < 0.7,
                             1.0 + 2.0 * rng.random(shape),
                             1e-3 * rng.random(shape))}[decay]
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, b)]


@pytest.mark.parametrize("decay", ["mid", "near1", "strong"])
@pytest.mark.parametrize("n,heads,dk,dv", [(8, 3, 8, 8), (24, 3, 8, 16),
                                           (64, 2, 128, 128),
                                           (200, 2, 16, 32)])
def test_the_kda_chunk_kernel_is_the_recurrence(n, heads, dk, dv, decay):
    rng = np.random.default_rng(n + heads)
    ops = _operands(rng, n, heads, dk, dv, decay)
    if decay == "strong" and n >= 64:
        # the case in which a naive e^-Gamma overflows float32
        assert float(jnp.min(jnp.cumsum(ops[3][:64], axis=0))) < -100.0
    state = jnp.asarray(rng.standard_normal((5, dk, heads * dv)), jnp.float32)
    for fresh in (False, True):
        want_o, want_s = prims.kda_chunk(
            *ops, state, jnp.int32(2), jnp.bool_(fresh), force="reference")
        got_o, got_s = prims.kda_chunk(
            *ops, state, jnp.int32(2), jnp.bool_(fresh), force="pallas")
        assert np.all(np.isfinite(np.asarray(got_o)))
        np.testing.assert_allclose(got_o, want_o, atol=1e-4)
        np.testing.assert_allclose(got_s, want_s, atol=2e-4)
        # the other blocks are not touched
        np.testing.assert_array_equal(np.delete(got_s, 2, 0),
                                      np.delete(state, 2, 0))


@pytest.mark.parametrize("force", ["reference", "pallas"])
def test_a_padded_tail_leaves_the_kda_state_as_it_was(force):
    """beta = 0 and g = 0 past the last real position: the state after 19
    real positions of 24 is the state after a chunk of 19."""
    rng = np.random.default_rng(7)
    q, k, v, g, b = _operands(rng, 24, 3, 8, 16, "strong")
    live = (jnp.arange(24) < 19)[:, None]
    state = jnp.asarray(rng.standard_normal((3, 8, 48)), jnp.float32)
    _, padded = prims.kda_chunk(
        q, k, v, g * live[..., None], b * live, state, jnp.int32(1),
        jnp.bool_(False), force=force)
    _, short = prims.kda_chunk(
        q[:19], k[:19], v[:19], g[:19], b[:19], state, jnp.int32(1),
        jnp.bool_(False), force="reference")
    np.testing.assert_allclose(padded, short, atol=1e-5)


@pytest.mark.parametrize("slots,heads,dk,dv", [(4, 3, 8, 16),
                                              (3, 32, 128, 128)])
def test_the_kda_step_kernel_is_the_recurrence_in_place(slots, heads, dk,
                                                        dv):
    rng = np.random.default_rng(slots)
    ops = _operands(rng, slots, heads, dk, dv, "strong")
    state = jnp.asarray(rng.standard_normal((6, dk, heads * dv)), jnp.float32)
    blocks = jnp.asarray([3, 0, 5, 0][:slots], jnp.int32)   # two inactive
    want_o, want_s = prims.kda_step(*ops, state, blocks, force="reference")
    got_o, got_s = prims.kda_step(*ops, state, blocks, force="pallas")
    live = np.asarray(blocks) != TRASH_PAGE
    np.testing.assert_allclose(np.asarray(got_o)[live],
                               np.asarray(want_o)[live], atol=1e-5)
    np.testing.assert_allclose(got_s[1:], want_s[1:], atol=1e-5)
    for blk in (1, 2, 4):        # blocks no slot names are as they were
        np.testing.assert_array_equal(got_s[blk], state[blk])
    if heads == 32:     # the published sizes: sixteen heads a lane tile
        assert gdn._heads_per_tile(32, 128, 128) == 16


@pytest.mark.parametrize("force", ["reference", "pallas"])
def test_with_every_channels_decay_equal_kda_is_the_gated_delta_rule(force):
    """g the same number on every key channel of a head: kda_chunk and
    kda_step ARE gated_delta_chunk and gated_delta_step (beta in (0, 1):
    ``beta_scale`` 1)."""
    rng = np.random.default_rng(11)
    q, k, v, g, b = _operands(rng, 72, 3, 8, 16, "mid", channel=False)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    state = jnp.asarray(rng.standard_normal((4, 8, 48)), jnp.float32)
    args = (state, jnp.int32(2), jnp.bool_(False))
    want_o, want_s = prims.gated_delta_chunk(q, k, v, g, b, *args,
                                             force=force)
    got_o, got_s = prims.kda_chunk(q, k, v, wide, b, *args, force=force)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    blocks = jnp.asarray([1, 0, 3], jnp.int32)
    row = [x[:3] for x in (q, k, v)]
    want_o, want_s = prims.gated_delta_step(*row, g[:3], b[:3], state,
                                            blocks, force=force)
    got_o, got_s = prims.kda_step(*row, wide[:3], b[:3], state, blocks,
                                  force=force)
    np.testing.assert_allclose(got_o[::2], want_o[::2], atol=1e-6)
    np.testing.assert_allclose(got_s[1:], want_s[1:], atol=1e-6)
    # the forms are told apart by name, and a decay a head is refused
    with pytest.raises(ValueError, match="a decay a key channel"):
        prims.kda_chunk(q, k, v, g, b, *args)


def test_kda_chunks_then_steps_are_one_recurrence():
    """The state handed from chunk to chunk and into the steps: 40 tokens
    as a chunk of 16, a chunk of 16 (the last 5 padded), and 13 steps,
    against one pass over all 40."""
    rng = np.random.default_rng(40)
    q, k, v, g, b = _operands(rng, 40, 3, 8, 16, "strong")
    state = jnp.asarray(rng.standard_normal((4, 8, 48)), jnp.float32)
    want, _ = prims.kda_chunk(q, k, v, g, b, state, jnp.int32(3),
                              jnp.bool_(True), force="reference")
    o1, state = prims.kda_chunk(
        q[:16], k[:16], v[:16], g[:16], b[:16], state, jnp.int32(3),
        jnp.bool_(True), force="pallas")
    live = (jnp.arange(16) < 11)[:, None]
    o2, state = prims.kda_chunk(
        q[16:32], k[16:32], v[16:32], g[16:32] * live[..., None],
        b[16:32] * live, state, jnp.int32(3), jnp.bool_(False),
        force="pallas")
    outs = [o1, o2[:11]]
    blocks = jnp.asarray([0, 3], jnp.int32)
    for t in range(27, 40):
        row = [jnp.stack([x[t], x[t]]) for x in (q, k, v, g, b)]
        o, state = prims.kda_step(*row, state, blocks, force="pallas")
        outs.append(o[1:2])
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=1e-4)


def test_a_bfloat16_state_or_solve_fails_the_kernels_tolerance(monkeypatch):
    """What the 1e-4 above separates: the recurrence with its state
    rounded to bfloat16 after every token, and the chunk kernel with its
    in-chunk solve's products in bfloat16, both miss it by far."""
    rng = np.random.default_rng(5)
    ops = _operands(rng, 128, 2, 16, 32, "near1")     # a state that builds up
    state = jnp.zeros((3, 16, 64), jnp.float32)
    args = (state, jnp.int32(1), jnp.bool_(True))
    want, _ = prims.kda_chunk(*ops, *args, force="reference")
    with jax.default_matmul_precision("highest"):
        _, rounded = ref.delta_rule(
            jnp.zeros((2, 16, 32), jnp.float32), *ops, jnp.bfloat16)
    assert float(jnp.max(jnp.abs(rounded - want))) > 1e-3

    def bf16_mm(a, b):
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)

    monkeypatch.setattr(gdn, "_mm", bf16_mm)     # the Neumann product's
    monkeypatch.setattr(kda, "_mm", bf16_mm)     # and the chunk's own
    low, _ = prims.kda_chunk(*ops, *args, force="pallas")
    assert float(jnp.max(jnp.abs(low - want))) > 1e-3


def test_the_form_counter_tells_the_kda_kernels_choices():
    rng = np.random.default_rng(3)
    ops = _operands(rng, 64, 2, 128, 128)
    state = jnp.zeros((3, 128, 256), jnp.float32)
    before = dict(obs.snapshot().get("pt_gated_delta_form_total", {})
                  .get("samples", {}))
    prims.kda_chunk(*ops, state, jnp.int32(1), jnp.bool_(True),
                    force="pallas")
    prims.kda_step(*(x[:2] for x in ops), state,
                   jnp.asarray([1, 2], jnp.int32), force="pallas")
    after = obs.snapshot()["pt_gated_delta_form_total"]["samples"]
    assert after[("kda_chunk", "sub64.block8")] == before.get(
        ("kda_chunk", "sub64.block8"), 0) + 1
    assert after[("kda_step", "heads2")] == before.get(
        ("kda_step", "heads2"), 0) + 1


# ---------------------------------------------------------------------------
# (d), (f) engine: prefill chunks, then decode steps, through both kinds
# of cache, against the reference's full forward
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
        name=f"klin-{force}-{len(prompts)}-{slots}-{sorted(engine.items())}",
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
def test_engine_matches_the_reference_through_latent_rows_and_state(
        weights, force):
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
    assert eng.pool.kinds == ["full"]        # the page kinds; no table for state
    # the tensors: [blocks, *shape] a KDA layer, ONE latent row tensor of
    # the latent layer, stored at a whole lane tile
    assert np.shape(eng.scope.get("@KVPOOL@s_l3")) == (5, 8, 24)
    assert np.shape(eng.scope.get("@KVPOOL@conv_l0")) == (5, 3 * 72)
    assert eng.scope.get("@KVPOOL@s_l2") is None
    assert eng.pool.var_names == [("@KVPOOL@latent_l0",)]
    assert np.shape(eng.scope.get("@KVPOOL@latent_l0")) == (49, 4, 128)
    # ... and the counters say so, both kinds under their names
    snap = obs.snapshot()
    alloc = snap["pt_kv_pages_alloc_total"]["samples"]
    freed = snap["pt_kv_pages_freed_total"]["samples"]
    assert alloc[(eng.name, "state")] == len(PROMPTS)
    assert alloc[(eng.name, "full")] == kinds["full"]["alloc_total"] > 0
    assert freed[(eng.name, "state", "end")] == len(PROMPTS)
    assert freed[(eng.name, "full", "end")] == alloc[(eng.name, "full")]
    rows = {k[1] for k in snap["pt_decode_cache_bytes"]["samples"]
            if k[0] == eng.name}
    assert rows == {"latent", "s", "conv"}
    dispatch = snap["pt_kernel_dispatch_total"]["samples"]
    mode = "reference" if force is None else "interpret"
    for primitive in ("kda_chunk", "kda_step", "paged_mla_attention",
                      "mla_chunk_attention"):
        assert dispatch[(primitive, mode)] >= 1
    picks = snap["pt_moe_picks_total"]["samples"]
    assert picks[(eng.name, "any")] == \
        picks[(eng.name, "held")] + picks[(eng.name, "absent")] > 0
    assert 0 < picks[(eng.name, "held")] < picks[(eng.name, "any")]
    assert snap["pt_moe_experts_touched_total"]["samples"][
        (eng.name, "decode")] > 0
    if force == "pallas":
        forms = snap["pt_gated_delta_form_total"]["samples"]
        assert forms[("kda_chunk", "sub8.block8")] >= 3
        assert forms[("kda_step", "heads3")] >= 3


def test_more_than_sixteen_slots_give_every_block_and_page_back(weights):
    """20 slots (no cell ran more than 16 before this one): 26 requests
    of unequal lengths and outputs, so slots turn over while others
    decode; every one is what the reference serves, and both kinds come
    back whole."""
    lengths = tuple(5 + (7 * i) % 41 for i in range(26))
    cfg = _cfg()
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in lengths]
    new = [4 + (5 * i) % 11 for i in range(26)]
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=20, page_size=PAGE,
        max_len=64, auto_start=False, name="klin-20-slots")
    try:
        eng.warmup()
        reqs = [eng.submit_request(p, n) for p, n in zip(prompts, new)]
        eng.start()
        outs = [r.future.result(timeout=600) for r in reqs]
        stats = eng.stats()
        feed = eng._dec_layout.unpack(eng._decode_feed([]))
    finally:
        eng.close()
    assert [len(o) for o in outs] == new
    assert stats["pool_slots"] == 20 and stats["evictions"] == 0
    assert feed["dec_state_block"].shape == (20,)
    assert feed["dec_page_table"].shape == (20, 16)
    assert max(_served_gaps(weights, prompts, outs)) < 1e-3
    kinds = stats["kv_pool"]["kinds"]
    assert kinds["state"]["pages_total"] == 20 + 1
    assert kinds["state"]["alloc_total"] == 26
    assert kinds["state"]["freed"]["end"] == 26
    assert kinds["full"]["freed"]["end"] == kinds["full"]["alloc_total"]
    assert kinds["state"]["pages_in_use"] == 0
    assert kinds["full"]["pages_in_use"] == 0
    # more than 16 rows decoded at once
    assert stats["tokens"] == sum(new)


def test_the_whole_sequence_program_is_the_reference_and_reads_no_position(
        weights):
    """(h) rotation is absent: with the positions feed permuted the
    whole-sequence program serves the same numbers (the latent layer's
    rows hold no position; order comes from the KDA layers alone)."""
    cfg = _cfg()
    rng = np.random.RandomState(5)
    tokens = rng.randint(1, cfg.vocab_size, 24)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        logp = kimi_linear.build_kimi_linear_lm(cfg, seq_len=24, page_size=4)
    exe = fluid.Executor(fluid.CPUPlace())
    got = {}
    for name, pos in (("in order", np.arange(24)),
                      ("permuted", rng.permutation(24))):
        (got[name],) = exe.run(
            main, feed={"pf_tok": tokens[None].astype(np.int64),
                        "pf_pos": pos[None].astype(np.int64)},
            fetch_list=[logp.name], scope=_scope_with(weights))
    with jax.default_matmul_precision("highest"):
        want = jax.nn.log_softmax(ref.forward(weights, CONFIG, tokens,
                                              np.arange(24)))
    np.testing.assert_allclose(np.asarray(got["in order"]),
                               np.asarray(want), atol=2e-4)
    np.testing.assert_array_equal(np.asarray(got["permuted"]),
                                  np.asarray(got["in order"]))
    assert not any(op.type.startswith("rope")
                   for op in main.global_block().ops)


def test_a_latent_layer_that_rotates_is_not_this_model(weights):
    """The same block with ``rotate`` left on (kimi_vl.py's call) moves
    the logits: the test above is not blind."""
    cfg = _cfg()
    cfg.rope_theta = 10000.0
    rng = np.random.RandomState(5)
    x = rng.normal(0, 1, (1, 8, 64)).astype(np.float32)
    outs = []
    for rotate in (False, True):
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start), fluid.unique_name.guard():
            from paddle_tpu.serving import lane

            xin = fluid.data("x", [1, 8, 64], False, dtype="float32")
            L = fluid.layers
            pos = L.reshape(L.range(0, 8, 1, "int64"), shape=[1, 8])
            pool = (L.fill_constant(shape=[3, 4, 128], value=0.0,
                                    dtype="float32"),)
            table = L.reshape(L.cast(L.range(1, 3, 1, "int64"), "int32"),
                              shape=[1, 2])
            write = lane._page_writer(8, L.reshape(table, shape=[2]))
            out = decode_blocks.latent_attention(
                xin, pos, table, L.fill_constant(shape=[1], value=0,
                                                 dtype="int32"),
                pool, write, (1, 8), cfg, "klin_layer_2", None,
                rotate=rotate)
        (o,) = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": x}, fetch_list=[out],
            scope=_scope_with(weights))
        outs.append(np.asarray(o))
    assert np.max(np.abs(outs[0] - outs[1])) > 1e-3


# ---------------------------------------------------------------------------
# (e) the shares add up
# ---------------------------------------------------------------------------


def test_four_shares_are_the_uncut_layer_the_shared_expert_counted_once():
    """Four chips of an EP4 deployment at the tiny size, 2 of 8 experts
    each: what the four shares' expert layers add to the stream, the
    shared expert (computed on every chip) counted once, is the
    reference's uncut layer; each share through the program's block."""
    uncut = dict(CONFIG, num_experts=8,
                 deployment=dict(CONFIG["deployment"], first_expert=0))
    weights = ref.init_weights(uncut, 7)
    layer = "klin_layer_1_"
    p = {k[len(layer):]: v for k, v in weights.items()
         if k.startswith(tuple(layer + part
                               for part in ("ffn_", "moe_", "shared_")))}
    rng = np.random.RandomState(2)
    x = rng.normal(0, 1, (8, 64)).astype(np.float32)
    z = ref.sizes(uncut)
    z.update(scaling=float(CONFIG["routed_scaling_factor"]),
             renormalize=True)
    with jax.default_matmul_precision("highest"):
        want = ref.finish_rows(
            jnp.asarray(x), jnp.zeros((8, 64)), p,
            z=tuple(sorted(z.items())), eps=1e-5, dense=False,
            matmul=jnp.matmul) - x
        f = ref.rms_norm(jnp.asarray(x), p["ffn_norm.scale"], 1e-5)
        shared = ref.swiglu(f, *(p[f"shared_{k}.w_0"]
                                 for k in ("gate", "up", "down")),
                            jnp.matmul)
    total = np.zeros((8, 64), np.float32)
    for chip in range(4):
        cfg = _cfg(held_experts=2, first_expert=2 * chip)
        share = dict(weights)
        for k in ("gate", "up", "down"):
            name = f"{layer}moe_experts_{k}.w_0"
            share[name] = weights[name][2 * chip:2 * chip + 2]
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start), fluid.unique_name.guard():
            xin = fluid.data("x", [1, 8, 64], False, dtype="float32")
            out = decode_blocks.expert_ffn(xin, 1, None, None, cfg,
                                           "klin_layer_1", None)
        (got,) = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": x[None]}, fetch_list=[out],
            scope=_scope_with(share))
        total += np.asarray(got)[0]
    np.testing.assert_allclose(total - 3 * np.asarray(shared),
                               np.asarray(want), rtol=2e-4, atol=2e-4)
    assert float(jnp.abs(want - shared).max()) > 0.05     # experts matter


# ---------------------------------------------------------------------------
# (g) the committed configuration's bytes
# ---------------------------------------------------------------------------


def test_the_pools_modeled_bytes_are_the_configurations_products():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-ep4.json")) as f:
        config = json.load(f)
    cfg = kimi_linear.KimiLinearConfig(**config["builder"]["config_args"])
    e = config["engine"]
    assert e["pool_slots"] == 32
    per_seq = -(-e["max_len"] // e["page_size"])
    pages = e["pool_slots"] * per_seq + 1
    assert (per_seq, pages) == (272, 8705)
    decl = cfg.decode_lane()
    assert decl.num_layers == 2 and decl.state_layers == [0, 1, 2, 4, 5, 6]
    pool = KVPool(decl.num_layers, decl.cache_rows(None), pages,
                  e["page_size"], per_seq, seq_state=decl.seq_state,
                  state_layers=decl.state_layers,
                  state_blocks=e["pool_slots"] + 2)
    latent = 2 * 8705 * 128 * 640 * 2
    state = 6 * 34 * (2097152 + 147456)
    assert pool.kind_bytes("full") == latent
    assert pool.kind_bytes("state") == state
    assert pool.modeled_bytes() == latent + state
    assert round(latent / 1e9, 2) == 2.85 and round(state / 1e9, 3) == 0.458
    n = sum(int(np.prod(s)) for s, _, _ in ref.param_shapes(config).values())
    assert round(n / 1e6, 1) == 3772.4
    # 7.54 + 2.85 + 0.46 GB resident (10.855: the issue's 10.85)
    assert abs(2 * n + latent + state - 10.85e9) < 0.01e9
    # reduced: the four keys and nothing else; no width among them
    assert set(config["changed"]) == {"num_hidden_layers",
                                      "linear_attn_config", "num_experts",
                                      "vocab_size"}
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    assert config["deployment"]["pipeline_stages"] == 4
