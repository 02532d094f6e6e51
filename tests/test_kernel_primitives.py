"""Kernel primitives layer (paddle_tpu/kernels/primitives, ISSUE 17).

Acceptance contract: every migrated primitive (flash, paged, fused
update/bias-act ride their own suites) passes interpret-mode parity
against its reference math; the uniform block/VMEM contract
(contract.make_spec / primitive_call) launches arbitrary kernels with
single-output normalization and scratch; the autotune hook resolves
pinned (PT_KERNEL_TILE_TABLE) → in-process measured → defaults and
books pt_kernel_autotune_total; ragged attention equals dense attention
on every unpadded row; the dual-int8 KV pool halves modeled bytes and
a 20-step int8-KV decode drifts logprobs only negligibly vs fp32.

Everything runs on CPU: pallas interpret mode for the kernel arms, XLA
reference math for the oracle arms.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import primitives as prims
from paddle_tpu.kernels.primitives import autotune, contract

jax.config.update("jax_platform_name", "cpu")


def _rand(shape, seed=0, dtype=np.float32):
    return np.random.RandomState(seed).normal(size=shape).astype(dtype)


# ---------------------------------------------------------------------------
# contract: spec construction + primitive_call
# ---------------------------------------------------------------------------


def test_contract_single_output_normalization():
    """len(out_specs) == 1 returns the bare array, not a 1-tuple."""
    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    x = _rand((8, 128))
    spec = contract.make_spec(
        "t_double", grid=(1,),
        in_specs=(contract.Block((8, 128), lambda i: (0, 0)),),
        out_specs=(contract.Block((8, 128), lambda i: (0, 0)),),
        out_shape=(((8, 128), jnp.float32),),
        interpret=True)
    out = contract.primitive_call(double, spec, x)
    assert not isinstance(out, (tuple, list))
    np.testing.assert_allclose(np.asarray(out), x * 2.0, rtol=1e-6)


def test_contract_multi_output_and_scratch():
    def twin(x_ref, a_ref, b_ref, acc_ref):
        acc_ref[...] = x_ref[...] + 1.0
        a_ref[...] = acc_ref[...]
        b_ref[...] = x_ref[...] - 1.0

    x = _rand((8, 128), seed=1)
    blk = contract.Block((8, 128), lambda i: (0, 0))
    spec = contract.make_spec(
        "t_twin", grid=(1,), in_specs=(blk,), out_specs=(blk, blk),
        out_shape=(((8, 128), jnp.float32), ((8, 128), jnp.float32)),
        scratch=(contract.Vmem((8, 128), jnp.float32),),
        interpret=True)
    a, b = contract.primitive_call(twin, spec, x)
    np.testing.assert_allclose(np.asarray(a), x + 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(b), x - 1.0, rtol=1e-6)


def test_resolve_mode_cpu_semantics(monkeypatch):
    # CPU default: XLA reference, no interpreter
    assert contract.resolve_mode("t", None) == ("reference", False)
    # forced pallas off-TPU runs the kernel under the interpreter
    assert contract.resolve_mode("t", "pallas") == ("pallas", True)
    assert contract.resolve_mode("t", "reference") == ("reference", False)
    # force_env engages the kernel off-TPU (the CPU parity lane)
    monkeypatch.setenv("PT_TEST_FORCE_PALLAS", "1")
    assert contract.resolve_mode(
        "t", None, force_env="PT_TEST_FORCE_PALLAS") == ("pallas", True)
    monkeypatch.setenv("PT_TEST_FORCE_PALLAS", "0")
    assert contract.resolve_mode(
        "t", None, force_env="PT_TEST_FORCE_PALLAS") == ("reference", False)


def test_resolve_mode_books_what_it_chose_and_follows_lowering_target():
    """Every decision lands on pt_kernel_dispatch_total — what
    chip_smoke.py reads instead of assuming a form — and inside
    lowering_for("tpu") the default is COMPILED Pallas (the chip-free
    AOT lane), not the interpreter."""
    from paddle_tpu import observability as obs
    from paddle_tpu.fluid.platform_utils import lowering_for

    def booked(mode):
        fam = obs.snapshot().get("pt_kernel_dispatch_total") or {}
        return fam.get("samples", {}).get(("t_book", mode), 0)

    before = {m: booked(m) for m in ("reference", "interpret", "pallas")}
    contract.resolve_mode("t_book", None)
    contract.resolve_mode("t_book", "pallas")
    with lowering_for("tpu"):
        assert contract.resolve_mode("t_book", None) == ("pallas", False)
    assert contract.resolve_mode("t_book", None) == ("reference", False)
    after = {m: booked(m) for m in before}
    assert {m: after[m] - before[m] for m in before} == {
        "reference": 2, "interpret": 1, "pallas": 1}


# ---------------------------------------------------------------------------
# autotune: pinned table -> measured cache -> defaults
# ---------------------------------------------------------------------------


def _autotune_counter(source):
    from paddle_tpu import observability as obs

    fam = obs.REGISTRY.get("pt_kernel_autotune_total")
    if fam is None:
        return 0.0
    return fam._snapshot()["samples"].get(("t_prim", source), 0.0)


def test_shape_signature_stable_ordering():
    assert autotune.shape_signature(s=128, b=2) == \
        autotune.shape_signature(b=2, s=128)
    assert "b=2" in autotune.shape_signature(b=2, s=128)


def test_tile_for_defaults_when_untuned():
    autotune.clear_cache()
    tile = autotune.tile_for("t_prim", "b=1", {"block": 64})
    assert tile == {"block": 64}


def test_tile_for_pinned_table(monkeypatch, tmp_path):
    table = {"t_prim": {"b=2,s=128": {"block": 256},
                        "*": {"block": 32}}}
    tf = tmp_path / "tiles.json"
    tf.write_text(json.dumps(table))
    monkeypatch.setenv(autotune.ENV_TABLE, str(tf))
    autotune.clear_cache()
    before = _autotune_counter("pinned")
    assert autotune.tile_for("t_prim", "b=2,s=128",
                             {"block": 64}) == {"block": 256}
    # wildcard signature covers everything else
    assert autotune.tile_for("t_prim", "b=9,s=7",
                             {"block": 64}) == {"block": 32}
    assert _autotune_counter("pinned") == before + 2
    monkeypatch.delenv(autotune.ENV_TABLE)
    autotune.clear_cache()


def test_tile_for_measured_requires_flag(monkeypatch):
    from paddle_tpu.fluid import flags as fl

    autotune.clear_cache()
    calls = []

    def measure(cand):
        calls.append(cand)
        return 0.001 if cand["block"] == 128 else 0.1

    cands = ({"block": 64}, {"block": 128})
    # flag off (the default): no candidate is ever measured
    assert autotune.tile_for("t_prim", "b=4", {"block": 64},
                             candidates=cands,
                             measure=measure) == {"block": 64}
    assert calls == []
    fl.set_flags({"FLAGS_kernel_autotune": True})
    try:
        before = _autotune_counter("measured")
        tile = autotune.tile_for("t_prim", "b=4", {"block": 64},
                                 candidates=cands, measure=measure)
        assert tile == {"block": 128}
        # one warm + one timed call per candidate
        assert len(calls) == 4
        assert _autotune_counter("measured") == before + 1
        # second call resolves from the in-process measured cache —
        # nothing re-measured
        calls.clear()
        assert autotune.tile_for("t_prim", "b=4", {"block": 64},
                                 candidates=cands,
                                 measure=measure) == {"block": 128}
        assert calls == []
    finally:
        fl.set_flags({"FLAGS_kernel_autotune": False})
        autotune.clear_cache()


def test_tile_for_raising_candidate_disqualified(monkeypatch):
    from paddle_tpu.fluid import flags as fl

    autotune.clear_cache()

    def measure(cand):
        if cand["block"] == 64:
            raise RuntimeError("unsupported tile")
        return 0.01

    fl.set_flags({"FLAGS_kernel_autotune": True})
    try:
        tile = autotune.tile_for("t_prim", "b=5", {"block": 32},
                                 candidates=({"block": 64},
                                             {"block": 128}),
                                 measure=measure)
        assert tile == {"block": 128}
    finally:
        fl.set_flags({"FLAGS_kernel_autotune": False})
        autotune.clear_cache()


# ---------------------------------------------------------------------------
# interpret-mode parity: migrated primitives vs their reference math
# ---------------------------------------------------------------------------


def test_flash_interpret_parity():
    # 4-D [B, H, S, D] public form vs the 3-D [BH, S, D] oracle
    q, k, v = (_rand((1, 2, 128, 32), seed=s) for s in (0, 1, 2))
    for causal in (False, True):
        got = prims.flash_attention(q, k, v, causal=causal,
                                    force="pallas")
        want = prims.attention_reference(
            q.reshape(2, 128, 32), k.reshape(2, 128, 32),
            v.reshape(2, 128, 32), causal=causal)
        np.testing.assert_allclose(
            np.asarray(got).reshape(2, 128, 32), np.asarray(want),
            atol=1e-6, rtol=1e-5)


def test_flash_pinned_tile_table_end_to_end(monkeypatch, tmp_path):
    """A PT_KERNEL_TILE_TABLE pin reaches the flash launch and the
    result still matches the reference — tile size is a pure
    performance knob, never a semantics knob."""
    table = {"flash_attention": {"*": {"block": 256}}}
    tf = tmp_path / "tiles.json"
    tf.write_text(json.dumps(table))
    monkeypatch.setenv(autotune.ENV_TABLE, str(tf))
    autotune.clear_cache()
    try:
        q, k, v = (_rand((1, 128, 32), seed=s) for s in (3, 4, 5))
        got = prims.flash_attention(q, k, v, causal=True, force="pallas")
        want = prims.attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-5)
    finally:
        monkeypatch.delenv(autotune.ENV_TABLE)
        autotune.clear_cache()


def _flat(pages):
    """A pool built with the heads apart, [P, page, n, w], in the one
    shape the pool is stored in: [P, page, n*w]."""
    return pages.reshape(pages.shape[0], pages.shape[1], -1)


def _paged_oracle(q, k_pages, v_pages, page_table, q_start):
    """Dense numpy attention over pools held with the HEADS APART
    ([P, page, n, d]): independent of how the primitive splits the flat
    lane dimension into heads."""
    q, k_pages, v_pages = (np.asarray(x, np.float64)
                           for x in (q, k_pages, v_pages))
    b, n, t, d = q.shape
    out = np.zeros(q.shape)
    for bi in range(b):
        k = k_pages[page_table[bi]].reshape(-1, n, d)      # [L, n, d]
        v = v_pages[page_table[bi]].reshape(-1, n, d)
        for i in range(t):
            keys = int(q_start[bi]) + i + 1
            for h in range(n):
                s = k[:keys, h] @ q[bi, h, i] / np.sqrt(d)
                p = np.exp(s - s.max())
                out[bi, h, i] = (p / p.sum()) @ v[:keys, h]
    return out


# (n, d, t): two heads of 32; five heads of 64 (a lane dimension that is
# not a multiple of 128, heads at 64-lane offsets as on the real models);
# a prefill chunk of 8 queries
@pytest.mark.parametrize("n,d,t", [(2, 32, 1), (5, 64, 1), (2, 32, 8)])
def test_paged_interpret_parity(n, d, t):
    b = 2
    page_size, max_pages, num_pages = 8, 4, 9
    q = _rand((b, n, t, d), seed=0)
    k_pages = _rand((num_pages, page_size, n, d), seed=1)
    v_pages = _rand((num_pages, page_size, n, d), seed=2)
    rng = np.random.RandomState(3)
    page_table = np.zeros((b, max_pages), np.int32)
    page_table[0, :4] = rng.choice(np.arange(1, num_pages), 4, False)
    page_table[1, :3] = rng.choice(np.arange(1, num_pages), 3, False)
    q_start = np.array([19, 12], np.int32)
    got = prims.paged_attention(q, _flat(k_pages), _flat(v_pages),
                                page_table, q_start, force="pallas")
    want = prims.paged_attention_reference(q, _flat(k_pages),
                                           _flat(v_pages), page_table,
                                           q_start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-5)
    # and both split the lane dimension into the heads it was built from
    np.testing.assert_allclose(
        np.asarray(want),
        _paged_oracle(q, k_pages, v_pages, page_table, q_start),
        atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the two bodies of the paged kernel (PR 30): a grid step reads G pages;
# a decode row (T = 1) takes all heads in one block-diagonal product,
# a chunk (T > 1) the per-head body.  Page 8 and G = 4 here, so a step
# is 32 keys and the table's 10 pages are padded to 12.
# ---------------------------------------------------------------------------

_PG, _G, _MAXP = 8, 4, 10            # max_len 80; _MAXP % _G != 0
_NPAGES = 5 * _MAXP + 1              # pages for five full rows


@pytest.fixture
def pages_per_step(monkeypatch, tmp_path):
    """Pin G through the tile table, the only way a caller can."""
    def pin(g):
        tf = tmp_path / f"tiles_{g}.json"
        tf.write_text(json.dumps(
            {name: {"*": {"pages_per_step": g}}
             for name in ("paged_attention", "paged_attention_quant")}))
        monkeypatch.setenv(autotune.ENV_TABLE, str(tf))
        autotune.clear_cache()

    yield pin
    monkeypatch.delenv(autotune.ENV_TABLE, raising=False)
    autotune.clear_cache()


def _booked_forms(primitive="paged_attention"):
    from paddle_tpu import observability as obs

    fam = obs.snapshot().get("pt_paged_attention_form_total") or {}
    return {k[1:]: v for k, v in fam.get("samples", {}).items()
            if k[0] == primitive}


def _paged_pool_case(n, d, t, q_starts, seed=0, inactive=()):
    """Pools whose TRASH page (0) holds large values, so a masked key
    that leaked would show; live pages in shuffled physical order, every
    dead entry the shared trash page; ``inactive`` rows get an all-trash
    table."""
    b = len(q_starts)
    rng = np.random.RandomState(seed)
    q = _rand((b, n, t, d), seed=seed + 1)
    k_pages = _rand((_NPAGES, _PG, n * d), seed=seed + 2)
    v_pages = _rand((_NPAGES, _PG, n * d), seed=seed + 3)
    k_pages[0] = 50.0
    v_pages[0] = 1000.0
    free = list(rng.permutation(np.arange(1, _NPAGES)))
    page_table = np.zeros((b, _MAXP), np.int32)
    for i, start in enumerate(q_starts):
        if i not in inactive:
            for j in range((start + t - 1) // _PG + 1):
                page_table[i, j] = free.pop()
    return q, k_pages, v_pages, page_table, np.asarray(q_starts, np.int32)


def _assert_paged_parity(case, form, g):
    before = _booked_forms().get((form, str(g)), 0)
    got = prims.paged_attention(*case, force="pallas")
    want = prims.paged_attention_reference(*case)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    assert _booked_forms().get((form, str(g)), 0) == before + 1


# contexts (keys the decode row sees = q_start + 1) around a page's and
# a grid step's edges, and the longest the table holds
@pytest.mark.parametrize("n", [12, 20])
@pytest.mark.parametrize("context", [
    1, _PG - 1, _PG, _PG + 1, _G * _PG - 1, _G * _PG, _G * _PG + 1,
    _MAXP * _PG])
def test_paged_decode_row_parity(pages_per_step, context, n):
    """T = 1: the heads-batched body, beside a second row of another
    length in the same batch."""
    pages_per_step(_G)
    case = _paged_pool_case(n, 64, 1, [context - 1, 44], seed=context)
    _assert_paged_parity(case, "heads_batched", _G)


@pytest.mark.parametrize("n", [12, 20])
@pytest.mark.parametrize("q_start", [0, 1, _PG, _G * _PG - 1,
                                     _G * _PG, _MAXP * _PG - 32])
def test_paged_chunk_parity(pages_per_step, q_start, n):
    """T = 32 (a prefill chunk of four pages): the per-head body."""
    pages_per_step(_G)
    case = _paged_pool_case(n, 64, 32, [q_start], seed=q_start)
    _assert_paged_parity(case, "per_head", _G)


@pytest.mark.parametrize("t", [1, 32])
@pytest.mark.parametrize("g", [1, 2, 3, _MAXP, None])
def test_paged_pages_per_step(pages_per_step, g, t):
    """Any G gives the same attention: one page a step, a G that does
    not divide the table, the whole table in one step, and the G the
    shapes give (None: 256 keys a step, capped by the table)."""
    if g is not None:
        pages_per_step(g)
    starts = [0, 7, 8, 30, 47] if t == 1 else [0, 9, 16, 40, 48]
    case = _paged_pool_case(5, 64, t, starts, seed=7)
    _assert_paged_parity(case, "heads_batched" if t == 1 else "per_head",
                         _MAXP if g is None else g)


@pytest.mark.parametrize("t", [1, 32])
def test_paged_inactive_slot_and_shared_trash(pages_per_step, t):
    """A slot with no sequence (all-trash table, q_start 0) beside live
    rows: it reads what the reference reads (the trash page's first
    key), and no live row sees the trash page's values."""
    pages_per_step(_G)
    case = _paged_pool_case(12, 64, t, [0, 39, 0, 17], seed=11,
                            inactive=(0, 2))
    assert not case[3][0].any() and not case[3][2].any()
    _assert_paged_parity(case, "heads_batched" if t == 1 else "per_head",
                         _G)
    got = np.asarray(prims.paged_attention(*case, force="pallas"))
    assert np.abs(got[[1, 3]]).max() < 10.0       # V's trash reads 1000
    if t == 1:
        np.testing.assert_allclose(got[[0, 2]], 1000.0)


def test_paged_quant_keeps_the_per_head_body(pages_per_step):
    """The int8 pool's decode row stays on the per-head body (its scales
    are one a head, not one a lane) and reads G pages a step too."""
    pages_per_step(3)
    n, d = 5, 64
    q = _rand((2, n, 1, d), seed=0)
    k_pages = _rand((9, 8, n, d), seed=1)
    v_pages = _rand((9, 8, n, d), seed=2)
    k_hi, k_lo, k_sc = map(_flat, prims.quantize_lastdim(
        jnp.asarray(k_pages)))
    v_hi, v_lo, v_sc = map(_flat, prims.quantize_lastdim(
        jnp.asarray(v_pages)))
    page_table = np.array([[1, 4, 7, 2], [3, 5, 0, 0]], np.int32)
    q_start = np.array([30, 12], np.int32)
    before = _booked_forms("paged_attention_quant").get(("per_head", "3"), 0)
    got = prims.paged_attention_quant(q, k_hi, k_lo, k_sc, v_hi, v_lo,
                                      v_sc, page_table, q_start,
                                      force="pallas")
    want = prims.paged_attention_quant_reference(
        q, k_hi, k_lo, k_sc, v_hi, v_lo, v_sc, page_table, q_start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-5)
    assert _booked_forms("paged_attention_quant").get(
        ("per_head", "3"), 0) == before + 1


@pytest.mark.parametrize("force", ["pallas", "reference"])
def test_paged_attention_refuses_a_pool_with_the_heads_apart(force):
    """ONE pool shape: the primitive does not reshape a 4-D pool (that
    reshape is what every executable paid a whole-pool copy for), it
    names the shape it got and the one it takes."""
    b, n, d = 2, 2, 32
    q = _rand((b, n, 1, d), seed=0)
    pool = _rand((9, 8, n, d), seed=1)
    page_table = np.zeros((b, 4), np.int32)
    q_start = np.array([3, 5], np.int32)
    with pytest.raises(ValueError, match=r"\(9, 8, 2, 32\).*"
                                        r"\[num_pages, page_size, 64\]"):
        prims.paged_attention(q, pool, pool, page_table, q_start,
                              force=force)
    hi, lo, sc = prims.quantize_lastdim(jnp.asarray(pool))
    with pytest.raises(ValueError, match=r"k_scale has shape "
                                        r"\(9, 8, 2, 1\)"):
        prims.paged_attention_quant(q, _flat(hi), _flat(lo), sc, _flat(hi),
                                    _flat(lo), sc, page_table, q_start,
                                    force=force)


@pytest.mark.parametrize("n,d", [(2, 32), (5, 64)])
def test_paged_quant_interpret_parity(n, d):
    b = 2
    page_size, max_pages, num_pages = 8, 4, 9
    q = _rand((b, n, 1, d), seed=0)
    k_pages = _rand((num_pages, page_size, n, d), seed=1)
    v_pages = _rand((num_pages, page_size, n, d), seed=2)
    # one scale per head_dim vector, taken while the heads are apart
    k_hi, k_lo, k_sc = map(_flat, prims.quantize_lastdim(
        jnp.asarray(k_pages)))
    v_hi, v_lo, v_sc = map(_flat, prims.quantize_lastdim(
        jnp.asarray(v_pages)))
    assert k_hi.shape == (num_pages, page_size, n * d)
    assert k_sc.shape == (num_pages, page_size, n)
    page_table = np.zeros((b, max_pages), np.int32)
    page_table[0, :3] = (1, 4, 7)
    page_table[1, :2] = (2, 5)
    q_start = np.array([19, 12], np.int32)
    got = prims.paged_attention_quant(q, k_hi, k_lo, k_sc, v_hi, v_lo,
                                      v_sc, page_table, q_start,
                                      force="pallas")
    want = prims.paged_attention_quant_reference(
        q, k_hi, k_lo, k_sc, v_hi, v_lo, v_sc, page_table, q_start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-5)
    # and the dual-int8 dequant stays CLOSE to the fp pool it encodes
    np.testing.assert_allclose(
        np.asarray(got),
        _paged_oracle(q, k_pages, v_pages, page_table, q_start),
        atol=5e-3, rtol=5e-3)


def test_ragged_interpret_parity():
    # 3-D [BH, S, D] form: per-row lengths, oracle shares the rank
    bh, s, d = 3, 64, 32
    q, k, v = (_rand((bh, s, d), seed=i) for i in (0, 1, 2))
    lengths = np.array([64, 37, 5], np.int32)
    for causal in (False, True):
        got = prims.ragged_attention(q, k, v, lengths, causal=causal,
                                     force="pallas")
        want = prims.ragged_attention_reference(q, k, v, lengths,
                                                causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-5)


def test_ragged_equals_dense_on_unpadded_rows():
    """THE ragged contract: for every sequence, rows [0, len) equal a
    dense attention over the TRUNCATED (never padded) sequence — the
    padded tail contributes nothing."""
    b, n, s, d = 3, 2, 48, 32
    q, k, v = (_rand((b, n, s, d), seed=i) for i in (3, 4, 5))
    lengths = np.array([48, 21, 7], np.int32)
    for force in (None, "pallas"):
        out = np.asarray(prims.ragged_attention(q, k, v, lengths,
                                                causal=True, force=force))
        for i, ln in enumerate(lengths):
            # dense attention over the TRUNCATED sequence i ([n, ln, d]
            # rides the oracle's [BH, S, D] rank directly)
            dense = prims.attention_reference(
                q[i, :, :ln], k[i, :, :ln], v[i, :, :ln], causal=True)
            np.testing.assert_allclose(
                out[i, :, :ln], np.asarray(dense), atol=1e-5,
                rtol=1e-4,
                err_msg=f"row {i} (len {ln}, force={force})")


def test_ragged_batch_lengths_broadcast():
    """4-D input takes per-SEQUENCE lengths [B] and broadcasts across
    heads; rows past a sequence's length carry no contract."""
    b, n, s, d = 2, 2, 32, 32
    q, k, v = (_rand((b, n, s, d), seed=i) for i in (6, 7, 8))
    lengths = np.array([32, 9], np.int32)
    out = prims.ragged_attention(q, k, v, lengths)
    ref = prims.ragged_attention_reference(
        q.reshape(b * n, s, d), k.reshape(b * n, s, d),
        v.reshape(b * n, s, d),
        jnp.asarray(np.repeat(lengths, n)))
    np.testing.assert_allclose(
        np.asarray(out).reshape(b * n, s, d), np.asarray(ref),
        atol=1e-6, rtol=1e-5)


# ---------------------------------------------------------------------------
# shims: the legacy module paths still serve the migrated primitives
# ---------------------------------------------------------------------------


def test_legacy_modules_are_shims():
    # importlib: the kernels package re-exports the FUNCTIONS under the
    # same names, so attribute access would shadow the shim modules
    import importlib

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    pa = importlib.import_module("paddle_tpu.kernels.paged_attention")

    assert fa.flash_attention is prims.flash_attention
    assert fa.attention_reference is prims.attention_reference
    assert pa.paged_attention is prims.paged_attention
    assert pa.paged_attention_reference is prims.paged_attention_reference
    assert pa.paged_attention_quant is prims.paged_attention_quant


def test_primitives_public_surface():
    for name in prims.__all__:
        assert getattr(prims, name) is not None, name


# ---------------------------------------------------------------------------
# int8: quantization math, byte model, counters
# ---------------------------------------------------------------------------


def test_quantize_lastdim_roundtrip():
    x = _rand((4, 8, 2, 32), seed=9)
    hi, lo, sc = prims.quantize_lastdim(jnp.asarray(x))
    assert np.asarray(hi).dtype == np.int8
    assert np.asarray(lo).dtype == np.int8
    assert sc.shape == (4, 8, 2, 1)
    back = np.asarray(prims.dequantize_lastdim(hi, lo, sc))
    err = np.abs(back - x).max() / max(np.abs(x).max(), 1e-9)
    assert err < 1e-3, f"dual-int8 roundtrip rel err {err}"


def test_quantize_weight_roundtrip_with_padding():
    w = _rand((7, 33), seed=10)  # 231 elements: not a block multiple
    hi, lo, sc, pad = prims.quantize_weight(jnp.asarray(w), block_size=64)
    back = np.asarray(prims.dequantize_weight(hi, lo, sc, w.shape,
                                              block_size=64))
    assert back.shape == w.shape
    err = np.abs(back - w).max() / np.abs(w).max()
    assert err < 1e-3


def test_dual_int8_byte_model():
    # 2 int8 bytes/element + one fp32 scale per block
    assert prims.dual_int8_bytes(1024, 32) == 2 * 1024 + 4 * (1024 // 32)
    assert prims.dual_int8_bytes(100, 64) == 200 + 4 * 2  # ceil(100/64)=2
    assert prims.bytes_saved(1024, 32) == 4 * 1024 - prims.dual_int8_bytes(
        1024, 32)
    # the halving claim: for block >= 32 the dual-int8 form is at most
    # 55% of fp32 (2n + 4n/32 = 2.125n vs 4n)
    for block in (32, 64, 128):
        n = 1 << 20
        assert prims.dual_int8_bytes(n, block) <= 0.55 * 4 * n


def test_book_bytes_saved_counter():
    from paddle_tpu import observability as obs

    prims.book_bytes_saved("t_kind", 12345)
    fam = obs.REGISTRY.get("pt_int8_bytes_saved_total")
    assert fam is not None
    assert fam._snapshot()["samples"].get(("t_kind",)) >= 12345


def test_kv_pool_modeled_bytes_halved():
    """KVPool(dtype='int8') models the dual-int8 layout; vs its own fp32
    model the pool is at most 55% (head_dim >= 32) — the counter-proven
    half of the int8-KV acceptance."""
    from paddle_tpu.serving.kv_pool import KVPool
    from paddle_tpu.serving.lane import kv_rows

    pool = KVPool(num_layers=2, rows=kv_rows(2, 32, "int8"), num_pages=17,
                  page_size=8, max_pages_per_seq=8)
    fp32 = 17 * 8 * 2 * 32 * 4 * 2 * 2  # elements x 4 bytes x K,V x layers
    q = pool.modeled_bytes()
    assert q <= 0.55 * fp32
    # and the fp32 pool models exactly its dtype width
    pool_fp = KVPool(num_layers=2, rows=kv_rows(2, 32), num_pages=17,
                     page_size=8, max_pages_per_seq=8)
    assert pool_fp.modeled_bytes() == fp32


# The int8-KV decode acceptance gates (20-step logprob drift vs the
# fp32 pool, token-for-token greedy parity through DecodeEngine) run in
# the decode e2e CHILD process — tests/decode_e2e_checks.py
# check_int8_kv_* , asserted by tests/test_decode.py — because decode
# programs in a warm pytest process trip the jaxlib-0.4.3x XLA:CPU heap
# corruption that file isolates.
