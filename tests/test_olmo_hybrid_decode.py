"""Olmo-Hybrid through the decode lane (models/olmo_hybrid.py,
serving/lane.py ``SeqState``, serving/kv_pool.py's ``state`` kind,
kernels/primitives/gdn.py): gated-delta-rule linear-attention layers
whose state a SEQUENCE owns (one pool block, found by index) beside
full-attention layers over the paged K/V kind — against the plain
reference (benchmark/reference/olmo_hybrid.py, the recurrence token by
token, which imports nothing of the program) at a tiny size with seeded
float32 weights: hidden 48, 3 heads (keys of 8, values of 16; 16 in the
full layer), layers l, l, f, l, page 4, chunk 8."""

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
from paddle_tpu.models import olmo_hybrid
from paddle_tpu.serving import lane
from paddle_tpu.serving.errors import PoolExhaustedError
from paddle_tpu.serving.kv_pool import KVPool, TRASH_PAGE

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "olmo-hybrid-tiny.json")) as _f:
    CONFIG = json.load(_f)
ref = harness.load_module("reference", "olmo_hybrid.py")
PAGE, CHUNK = 4, 8


def _cfg(**over):
    return olmo_hybrid.OlmoHybridConfig(
        **dict(CONFIG["builder"]["config_args"], **over))


def _scope_with(weights):
    scope = fluid.Scope()
    for name, w in weights.items():
        scope.set(name, w)
    return scope


@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(CONFIG, 20260930)


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
                           "olmo_hybrid.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+(paddle_tpu|benchmark)", src,
                         re.M)
    assert "lax.scan" in src            # the recurrence, token by token


def test_program_parameters_are_the_references():
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        olmo_hybrid.build_olmo_hybrid_lm(_cfg())
    want = {p.name: tuple(p.shape)
            for p in main.global_block().all_parameters()}
    have = {n: tuple(s) for n, (s, _, _) in ref.param_shapes(CONFIG).items()}
    assert have == want


def test_the_seeded_gates_decay_neither_to_nothing_nor_not_at_all(weights):
    a_log = np.concatenate([np.asarray(w) for n, w in weights.items()
                            if n.endswith("_A_log")])
    dt = np.concatenate([np.asarray(w) for n, w in weights.items()
                         if n.endswith("_dt_bias")])
    assert np.all(np.exp(a_log) < 16.0) and np.all(np.exp(a_log) > 0.0)
    softplus = np.log1p(np.exp(dt))
    assert np.all(softplus > 0.0009) and np.all(softplus < 0.11)


def test_the_lane_declares_cache_rows_and_state_and_refuses_an_int8_pool():
    cfg = _cfg()
    decl = cfg.decode_lane()
    assert decl.num_layers == 1 and decl.state_layers == [0, 1, 3]
    assert [r.name for r in decl.cache_rows(None)] == ["k", "v"]
    assert decl.cache_rows(None)[0].width == 3 * 16
    assert [(s.name, tuple(s.shape), s.dtype) for s in decl.seq_state] == [
        ("s", (8, 3 * 16), "float32"),
        ("conv", (3 * 3 * (2 * 8 + 16),), "float32")]
    with pytest.raises(ValueError, match="no int8 form"):
        decl.cache_rows("int8")
    with pytest.raises(ValueError, match="go together"):
        lane.DecodeLane(num_layers=1, max_position=8, cache_rows=None,
                        build_decode_step=None, build_prefill_chunk=None,
                        seq_state=decl.seq_state)
    assert lane.state_var_names(decl.seq_state, [0, 3]) == [
        ("@KVPOOL@s_l0", "@KVPOOL@conv_l0"),
        ("@KVPOOL@s_l3", "@KVPOOL@conv_l3")]


# ---------------------------------------------------------------------------
# engine: prefill chunks, then decode steps, through both kinds of cache,
# against the reference's full forward
# ---------------------------------------------------------------------------

# 30 ends inside a chunk of 8, as 5, 45, 17 and 9 do; 5 sequences over 3
# slots and 4 state blocks: blocks pass from one sequence to the next
PROMPTS = (30, 5, 45, 17, 9)


def _generate(weights, force=None, n_new=12, prompts=PROMPTS, **engine):
    cfg = _cfg()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in prompts]
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=3, page_size=PAGE,
        max_len=64, attn_force=force, auto_start=False,
        name=f"olmo-{force}-{len(prompts)}-{sorted(engine.items())}",
        **engine)
    try:
        assert eng.warmup() == 2
        eng.start()
        outs = eng.generate(prompts, max_new_tokens=n_new, timeout=600)
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
    # one block a sequence whatever its length, every one given back
    state = kinds["state"]
    assert state["pages_total"] == 3 + 1     # a slot each, one prefilling
    assert state["alloc_total"] == len(PROMPTS)
    assert state["freed"] == {"window": 0, "end": len(PROMPTS), "evict": 0}
    assert state["pages_in_use"] == kinds["full"]["pages_in_use"] == 0
    assert eng.pool.kinds == ["full"]        # the page kinds; no table for state
    assert eng.pool.state_blocks == 5
    # the tensors: [blocks, *shape] a state layer, K/V a full layer
    assert np.shape(eng.scope.get("@KVPOOL@s_l3")) == (5, 8, 48)
    assert np.shape(eng.scope.get("@KVPOOL@conv_l0")) == (5, 3 * 96)
    assert eng.scope.get("@KVPOOL@s_l0").dtype == jnp.float32
    assert eng.scope.get("@KVPOOL@s_l2") is None
    assert np.shape(eng.scope.get(eng.pool.var_names[0][0])) == (49, 4, 48)
    # ... and the counters say so, the state kind under its name
    snap = obs.snapshot()
    alloc = snap["pt_kv_pages_alloc_total"]["samples"]
    freed = snap["pt_kv_pages_freed_total"]["samples"]
    assert alloc[(eng.name, "state")] == len(PROMPTS)
    assert freed[(eng.name, "state", "end")] == len(PROMPTS)
    assert freed[(eng.name, "state", "evict")] == 0
    assert snap["pt_kv_pages_in_use"]["samples"][(eng.name, "state")] == 0
    rows = {k[1] for k in snap["pt_decode_cache_bytes"]["samples"]
            if k[0] == eng.name}
    assert rows == {"k", "v", "s", "conv"}
    dispatch = snap["pt_kernel_dispatch_total"]["samples"]
    mode = "reference" if force is None else "interpret"
    assert dispatch[("gated_delta_chunk", mode)] >= 3
    assert dispatch[("gated_delta_step", mode)] >= 3
    if force == "pallas":
        forms = snap["pt_gated_delta_form_total"]["samples"]
        assert forms[("gated_delta_chunk", "sub8")] >= 3
        assert forms[("gated_delta_step", "heads3")] >= 3


def test_a_block_another_sequence_left_does_not_leak(weights):
    """The least blocks (two for three slots): every sequence takes over
    a block another left (never cleared on the host; stale state must
    not leak) and serves what it serves with blocks to spare."""
    _, prompts, want, _ = _generate(weights)
    eng, _, outs, stats = _generate(weights, state_blocks=3)
    assert outs == want
    assert stats["kv_pool"]["kinds"]["state"]["pages_total"] == 2
    assert eng.pool._state.reused_allocs >= len(PROMPTS) - 2


def test_eviction_and_replay_cover_the_state_kind(weights):
    """Two state blocks for three slots: a third sequence evicts the
    youngest, whose block goes back, and its replay from token 0 serves
    the same tokens."""
    _, prompts, want, _ = _generate(weights)
    _, _, outs, stats = _generate(weights, state_blocks=3)
    assert outs == want
    assert stats["evictions"] > 0
    state = stats["kv_pool"]["kinds"]["state"]
    assert state["freed"]["evict"] > 0
    assert sum(state["freed"].values()) == state["alloc_total"]
    assert state["pages_in_use"] == 0
    # K/V pages run out too: both kinds evict through one path
    _, _, outs, stats = _generate(weights, num_pages=20)
    assert outs == want and stats["evictions"] > 0
    assert stats["kv_pool"]["kinds"]["state"]["freed"]["evict"] > 0


def _booked(family, engine):
    """What `family` reads for the engine of that name (several tests'
    engines share one: a caller takes the difference around its run)."""
    return obs.snapshot().get(family, {}).get("samples", {}).get(
        (engine,), 0)


_HEAD_FAMILIES = ("pt_decode_prefill_chunks_total",
                  "pt_decode_prefill_head_runs_total")


def test_a_prompt_of_n_chunks_runs_the_head_once(weights):
    """45 tokens in chunks of 8: six chunk executions, ONE of them (the
    last) under `pf_final` = 1, and the tokens the same prompt serves
    through one chunk of 48."""
    served = {}
    for chunk, n_chunks in ((8, 6), (48, 1)):
        name = f"olmo-None-1-{[('prefill_chunk', chunk)]}"
        before = [_booked(f, name) for f in _HEAD_FAMILIES]
        eng, _, served[chunk], _ = _generate(
            weights, n_new=6, prompts=(45,), prefill_chunk=chunk)
        assert eng.name == name
        assert [_booked(f, name) - n for f, n in zip(
            _HEAD_FAMILIES, before)] == [n_chunks, 1]
    assert served[8] == served[48] and len(served[8][0]) == 6


def test_a_replayed_prompt_runs_no_head(weights):
    """An evicted request replays prompt + served tokens through the
    chunk program and reads no token of it: as many head runs as
    prompts, however many replays."""
    name = f"olmo-None-{len(PROMPTS)}-{[('state_blocks', 3)]}"
    before = [_booked(f, name) for f in _HEAD_FAMILIES]
    eng, prompts, _, stats = _generate(weights, state_blocks=3)
    assert eng.name == name and stats["evictions"] > 0
    chunks, heads = [_booked(f, name) - n
                     for f, n in zip(_HEAD_FAMILIES, before)]
    assert heads == len(prompts) < chunks


def test_inactive_slots_write_the_trash_block_only(weights):
    eng, prompts, outs, _ = _generate(weights, prompts=(11,))
    assert max(_served_gaps(weights, prompts, outs)) < 1e-3
    fed = eng._dec_layout.unpack(eng._decode_feed([]))
    assert list(fed["dec_state_block"]) == [TRASH_PAGE] * 3
    assert eng._pf_layout.unpack(eng._prefill_feed(
        **eng._warm_prefill_args()))["pf_state_block"].tolist() == [
            TRASH_PAGE]


def test_a_lane_without_state_feeds_what_it_fed():
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_heads=2,
                        num_layers=1, max_position=32)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        lm, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(lm, start), fluid.unique_name.guard():
            gpt.build_gpt_lm(cfg, is_test=True)
        fluid.Executor(fluid.CPUPlace()).run(start)
        eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                                   page_size=4, max_len=16,
                                   auto_start=False, name="gpt-no-state")
    try:
        assert eng.pool.state_blocks == 0 and eng.pool.seq_state == []
        assert set(eng.pool.kind_stats()) == {"full"}
        assert "dec_state_block" not in eng._dec_layout.pieces
        assert "pf_state_block" not in eng._pf_layout.pieces
        # and its feeds are as long as the pieces it always fed
        assert eng._decode_feed([])["dec_feed"].shape == (
            2 + 2 + 2 * eng.pool.max_pages_per_seq + 2 + 2,)
    finally:
        eng.close()


def test_the_whole_sequence_program_is_the_reference(weights):
    cfg = _cfg()
    rng = np.random.RandomState(5)
    tokens = rng.randint(1, cfg.vocab_size, 24)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        logp = olmo_hybrid.build_olmo_hybrid_lm(cfg, seq_len=24, page_size=4)
    (got,) = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"pf_tok": tokens[None].astype(np.int64),
                    "pf_pos": np.arange(24, dtype=np.int64)[None]},
        fetch_list=[logp.name], scope=_scope_with(weights))
    with jax.default_matmul_precision("highest"):
        want = jax.nn.log_softmax(ref.forward(weights, CONFIG, tokens,
                                              np.arange(24)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


# ---------------------------------------------------------------------------
# the pool's state kind
# ---------------------------------------------------------------------------


def _pool(blocks=4, **kw):
    decl = _cfg().decode_lane()
    return KVPool(1, lane.kv_rows(3, 16), 17, PAGE, 8,
                  seq_state=decl.seq_state, state_layers=decl.state_layers,
                  state_blocks=blocks, **kw)


def test_a_sequence_holds_one_state_block_whatever_its_length():
    pool = _pool()
    pool.open_seq("a")
    block = pool.state_block("a")
    assert block != TRASH_PAGE and pool.pages_in_use("state") == 1
    for n in (1, 9, 32):
        pool.ensure_capacity("a", n)
        assert pool.state_block("a") == block
        assert pool.pages_in_use("state") == 1
    assert pool.pages_in_use("full") == 8
    assert pool.pages_in_use() == 8          # pages, not blocks
    assert pool.state_block(None) == TRASH_PAGE
    assert pool.free_seq("a") == 8 + 1
    assert pool.pages_in_use("state") == 0
    pool.open_seq("b")                       # LIFO: the block just freed
    assert pool.state_block("b") == block
    assert pool.kind_stats()["state"] == {
        "pages_total": 3, "pages_in_use": 1, "alloc_total": 2,
        "freed": {"window": 0, "end": 1, "evict": 0}}


def test_a_pool_out_of_state_blocks_raises_what_one_out_of_pages_raises():
    pool = _pool(blocks=3)
    pool.open_seq("a")
    pool.open_seq("b")
    with pytest.raises(PoolExhaustedError, match="kind 'state'"):
        pool.open_seq("c")
    assert "c" not in pool.live_seqs()       # nothing half-opened
    assert pool.free_seq("b", why="evict") == 1
    pool.open_seq("c")
    assert pool.kind_stats()["state"]["freed"]["evict"] == 1
    with pytest.raises(ValueError, match="at least 2 state blocks"):
        _pool(blocks=1)


def test_the_state_tensors_are_sized_and_counted_beside_the_rows():
    pool = _pool(blocks=5)
    s, conv = pool.seq_state
    assert pool.state_bytes(s) == 3 * 5 * 8 * 48 * 4
    assert pool.state_bytes(conv, blocks=2) == 3 * 2 * 288 * 4
    rows = sum(pool.row_bytes(r) for r in pool.rows)
    assert pool.modeled_bytes() == rows + pool.state_bytes(s) \
        + pool.state_bytes(conv)
    scope = fluid.Scope()
    pool.install(scope)
    assert [np.shape(scope.get(n)) for n in pool.state_var_names[2]] == [
        (5, 8, 48), (5, 288)]
    assert pool.state_var_names[2][0] == "@KVPOOL@s_l3"
    # a pool that declares no state has no such kind
    plain = KVPool(1, lane.kv_rows(3, 16), 17, PAGE, 8)
    assert plain.state_blocks == 0 and set(plain.kind_stats()) == {"full"}
    plain.open_seq("a")
    assert plain.state_block("a") == TRASH_PAGE


# ---------------------------------------------------------------------------
# the kernels against the recurrence
# ---------------------------------------------------------------------------


def _operands(rng, n, heads, dk, dv, beta="any", decay="mid"):
    q = rng.standard_normal((n, heads, dk)).astype(np.float32)
    # keys that lean one way, as a SiLU's outputs do: k_t . k_j well over 0
    k = rng.standard_normal((n, heads, dk)).astype(np.float32) + 0.5
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((n, heads, dv)).astype(np.float32)
    b = {"any": 2 * rng.random((n, heads)),
         "near2": 1.95 + 0.05 * rng.random((n, heads))}[beta]
    g = {"mid": -0.5 * rng.random((n, heads)),
         "near1": -1e-4 * rng.random((n, heads)),     # alpha near 1
         "near0": -5 - 20 * rng.random((n, heads))}[decay]   # alpha near 0
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, b)]


@pytest.mark.parametrize("decay", ["mid", "near1", "near0"])
@pytest.mark.parametrize("beta", ["any", "near2"])
@pytest.mark.parametrize("n,heads,dk,dv", [(8, 3, 8, 16), (24, 3, 8, 16),
                                           (64, 2, 96, 192), (128, 4, 16, 32),
                                           (200, 2, 16, 32)])
def test_the_chunk_kernel_is_the_recurrence(n, heads, dk, dv, beta, decay):
    rng = np.random.default_rng(n + heads)
    ops = _operands(rng, n, heads, dk, dv, beta, decay)
    state = jnp.asarray(rng.standard_normal((5, dk, heads * dv)), jnp.float32)
    for fresh in (False, True):
        want_o, want_s = prims.gated_delta_chunk(
            *ops, state, jnp.int32(2), jnp.bool_(fresh), force="reference")
        got_o, got_s = prims.gated_delta_chunk(
            *ops, state, jnp.int32(2), jnp.bool_(fresh), force="pallas")
        np.testing.assert_allclose(got_o, want_o, atol=1e-4)
        np.testing.assert_allclose(got_s, want_s, atol=2e-4)
        # the other blocks are not touched
        np.testing.assert_array_equal(np.delete(got_s, 2, 0),
                                      np.delete(state, 2, 0))
    if not fresh:
        return
    # a fresh block is read as zeros, whatever it holds
    zeroed = state.at[2].set(0.0)
    again, _ = prims.gated_delta_chunk(*ops, zeroed, jnp.int32(2),
                                       jnp.bool_(False), force="pallas")
    np.testing.assert_allclose(again, got_o, atol=1e-6)


@pytest.mark.parametrize("force", ["reference", "pallas"])
def test_a_padded_tail_leaves_the_state_as_it_was(force):
    """beta = 0 and g = 0 past the last real position: the state after 19
    real positions of 24 is the state after a chunk of 19."""
    rng = np.random.default_rng(7)
    q, k, v, g, b = _operands(rng, 24, 3, 8, 16)
    live = (jnp.arange(24) < 19)[:, None]
    state = jnp.asarray(rng.standard_normal((3, 8, 48)), jnp.float32)
    _, padded = prims.gated_delta_chunk(
        q, k, v, g * live, b * live, state, jnp.int32(1), jnp.bool_(False),
        force=force)
    _, short = prims.gated_delta_chunk(
        q[:19], k[:19], v[:19], g[:19], b[:19], state, jnp.int32(1),
        jnp.bool_(False), force="reference")
    np.testing.assert_allclose(padded, short, atol=1e-5)


@pytest.mark.parametrize("slots,heads,dk,dv", [(4, 3, 8, 16),
                                              (3, 30, 96, 192)])
def test_the_step_kernel_is_the_recurrence_in_place(slots, heads, dk, dv):
    rng = np.random.default_rng(slots)
    ops = _operands(rng, slots, heads, dk, dv)
    state = jnp.asarray(rng.standard_normal((6, dk, heads * dv)), jnp.float32)
    blocks = jnp.asarray([3, 0, 5, 0][:slots], jnp.int32)   # two inactive
    want_o, want_s = prims.gated_delta_step(*ops, state, blocks,
                                            force="reference")
    got_o, got_s = prims.gated_delta_step(*ops, state, blocks,
                                          force="pallas")
    live = np.asarray(blocks) != TRASH_PAGE
    np.testing.assert_allclose(np.asarray(got_o)[live],
                               np.asarray(want_o)[live], atol=1e-5)
    np.testing.assert_allclose(got_s[1:], want_s[1:], atol=1e-5)
    # blocks no slot names are as they were
    for blk in (1, 2, 4):
        np.testing.assert_array_equal(got_s[blk], state[blk])
    if heads == 30:     # the published sizes: ten heads a lane tile
        assert gdn._heads_per_tile(30, 96, 192) == 10


def test_chunks_then_steps_are_one_recurrence():
    """The state handed from chunk to chunk and into the steps: 40 tokens
    as a chunk of 16, a chunk of 16 (the last 5 padded), and 13 steps,
    against one pass over all 40."""
    rng = np.random.default_rng(40)
    q, k, v, g, b = _operands(rng, 40, 3, 8, 16)
    state = jnp.asarray(rng.standard_normal((4, 8, 48)), jnp.float32)
    want, _ = prims.gated_delta_chunk(q, k, v, g, b, state, jnp.int32(3),
                                      jnp.bool_(True), force="reference")
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


def test_the_unit_lower_inverse_is_exact_where_a_power_series_is_not():
    """beta = 2 on identical keys with no decay: (I + 2 L)^-1 has entries
    of size 2, its Neumann series terms of size 2^k C(64, k)."""
    c = 64
    a = jnp.asarray(2.0 * np.tril(np.ones((c, c), np.float32), -1))
    with jax.default_matmul_precision("highest"):
        t = gdn._unit_lower_inverse(a)
        err = jnp.max(jnp.abs(t @ (jnp.eye(c) + a) - jnp.eye(c)))
    assert float(jnp.max(jnp.abs(t))) == pytest.approx(2.0, abs=1e-4)
    assert float(err) < 1e-4


# ---------------------------------------------------------------------------
# the short convolution's carried tail
# ---------------------------------------------------------------------------


def _conv_program(chunk, ch, blocks=3, step=False):
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        n = 2 if step else 1
        x = fluid.data("x", [n, 1 if step else chunk, ch], False,
                       dtype="float32")
        block = fluid.data("block", [n], False, dtype="int32")
        tail = fluid.default_main_program().global_block().create_var(
            name="tail", shape=[blocks, 3 * ch], dtype="float32",
            persistable=True)
        kw = {}
        if not step:
            kw = {"q_start": fluid.data("qs", [1], False, dtype="int32"),
                  "last_idx": fluid.data("last", [1], False, dtype="int64")}
        out = fluid.layers.short_conv(
            x, 4, tail, block, param_attr=fluid.ParamAttr(name="w"), **kw)
    return main, out.name


def test_the_convolutions_tail_is_carried_across_a_chunk_boundary():
    ch, chunk = 6, 8
    rng = np.random.default_rng(1)
    x = rng.standard_normal((21, ch)).astype(np.float32)
    w = rng.standard_normal((4, ch)).astype(np.float32)
    ext = np.concatenate([np.zeros((3, ch), np.float32), x])
    pre = sum(w[j] * ext[j:j + 21] for j in range(4))
    want = pre / (1 + np.exp(-pre))
    scope = fluid.Scope()
    scope.set("w", w)
    # the block holds what another sequence left
    scope.set("tail", rng.standard_normal((3, 3 * ch)).astype(np.float32))
    exe = fluid.Executor(fluid.CPUPlace())
    main, out = _conv_program(chunk, ch)
    got = []
    for first in (0, 8):            # two chunks; the second's last 3 padded
        valid = min(chunk, 13 - first)
        xc = np.zeros((1, chunk, ch), np.float32)
        xc[0, :valid] = x[first:first + valid]
        xc[0, valid:] = 99.0        # padding must not reach the tail
        (o,) = exe.run(main, feed={
            "x": xc, "block": np.asarray([2], np.int32),
            "qs": np.asarray([first], np.int32),
            "last": np.asarray([valid - 1], np.int64)},
            fetch_list=[out], scope=scope)
        got.append(np.asarray(o)[0, :valid])
    np.testing.assert_allclose(np.asarray(scope.get("tail"))[2],
                               x[10:13].reshape(-1), atol=1e-6)
    step, out = _conv_program(chunk, ch, step=True)
    for t in range(13, 21):         # then a token at a time, slot 1 of 2
        (o,) = exe.run(step, feed={
            "x": np.stack([np.zeros((1, ch), np.float32), x[t:t + 1]]),
            "block": np.asarray([0, 2], np.int32)},
            fetch_list=[out], scope=scope)
        got.append(np.asarray(o)[1])
    np.testing.assert_allclose(np.concatenate(got), want, atol=1e-5)
    assert np.all(np.asarray(scope.get("tail"))[1] != 0)   # not its block


# ---------------------------------------------------------------------------
# the rule's operands and the gated norm (ops gdn_inputs, gated_rms_norm;
# the convolution above is ops short_conv_chunk and short_conv_step)
# ---------------------------------------------------------------------------


def test_gdn_inputs_and_gated_rms_norm_against_numpy():
    heads, dk, dv, t = 3, 8, 16, 5
    rng = np.random.default_rng(2)
    qkv = rng.standard_normal((1, t, heads * (2 * dk + dv))).astype("float32")
    a, b = (rng.standard_normal((1, t, heads)).astype("float32")
            for _ in range(2))
    a_log = np.log(rng.uniform(0.1, 16, heads)).astype("float32")
    dt_bias = rng.standard_normal(heads).astype("float32")
    gate = rng.standard_normal((1, t, heads * dv)).astype("float32")
    gain = rng.uniform(0.5, 2, dv).astype("float32")
    valid = np.asarray([1, 1, 1, 0, 0], np.int32)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        L = fluid.layers
        feeds = {"qkv": qkv, "a": a, "b": b, "gate": gate, "valid": valid}
        v = {n: fluid.data(n, list(x.shape), False, dtype=str(x.dtype))
             for n, x in feeds.items()}
        outs = L.gdn_inputs(
            v["qkv"], v["a"], v["b"], heads, dk, dv, beta_scale=2.0,
            epsilon=1e-6, row_valid=v["valid"],
            a_log_attr=fluid.ParamAttr(name="A_log"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"))
        normed = L.gated_rms_norm(outs[2], v["gate"], epsilon=1e-6,
                                  param_attr=fluid.ParamAttr(name="gain"))
    scope = fluid.Scope()
    for n, x in (("A_log", a_log), ("dt_bias", dt_bias), ("gain", gain)):
        scope.set(n, x)
    q, k, val, g, beta, y = (np.asarray(x) for x in fluid.Executor(
        fluid.CPUPlace()).run(main, feed=feeds, fetch_list=[
            o.name for o in outs] + [normed.name], scope=scope))

    def l2(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q0 = qkv[..., :heads * dk].reshape(1, t, heads, dk)
    k0 = qkv[..., heads * dk:2 * heads * dk].reshape(1, t, heads, dk)
    v0 = qkv[..., 2 * heads * dk:].reshape(1, t, heads, dv)
    live = valid[None, :, None]
    np.testing.assert_allclose(q, l2(q0) / np.sqrt(dk), atol=1e-6)
    np.testing.assert_allclose(k, l2(k0), atol=1e-6)
    np.testing.assert_allclose(val, v0)
    np.testing.assert_allclose(
        g, -np.exp(a_log) * np.log1p(np.exp(a + dt_bias)) * live, atol=1e-5)
    np.testing.assert_allclose(beta, 2 / (1 + np.exp(-b)) * live, atol=1e-6)
    assert np.all(beta[:, :3] > 0) and np.all(beta[:, 3:] == 0)
    rms = v0 / np.sqrt((v0 * v0).mean(-1, keepdims=True) + 1e-6) * gain
    want = rms.reshape(1, t, -1) * gate / (1 + np.exp(-gate))
    np.testing.assert_allclose(y, want, atol=1e-5)
