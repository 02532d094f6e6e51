"""The grouped-query and window forms of paged attention
(kernels/primitives/paged.py, PR 31): both Pallas bodies (decode row,
prefill chunk) in the interpreter against the XLA form, and the XLA form
against a dense oracle written here, at window and page edges."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import primitives as prims
from paddle_tpu.kernels.primitives import autotune, paged

PG, MAXP = 4, 12                      # max_len 48
NPAGES = 4 * MAXP + 1


def _rand(shape, seed):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def _case(n_q, n_kv, d, t, q_starts, window, seed=0, dtype=np.float32,
          page=PG, max_pages=MAXP):
    """Pools whose trash page (0) and every page wholly below a row's
    window hold large values (the allocator has given those back: their
    table entries are the trash page), live pages in shuffled order."""
    b = len(q_starts)
    n_pages = b * max_pages + 1
    rng = np.random.RandomState(seed)
    q = _rand((b, n_q, t, d), seed + 1)
    k_pages = _rand((n_pages, page, n_kv * d), seed + 2)
    v_pages = _rand((n_pages, page, n_kv * d), seed + 3)
    k_pages[0], v_pages[0] = 50.0, 1000.0
    free = list(rng.permutation(np.arange(1, n_pages)))
    table = np.zeros((b, max_pages), np.int32)
    for i, start in enumerate(q_starts):
        first = 0 if window is None else max(0, start - window + 1) // page
        for j in range(first, (start + t - 1) // page + 1):
            table[i, j] = free.pop()
    return (q, jnp.asarray(k_pages, dtype), jnp.asarray(v_pages, dtype),
            table, np.asarray(q_starts, np.int32))


def _oracle(q, k_pages, v_pages, table, q_start, window):
    """Dense attention a row and a head at a time, in float64."""
    b, n_q, t, d = q.shape
    k_pages, v_pages = (np.asarray(x, np.float64) for x in (k_pages, v_pages))
    n_kv = k_pages.shape[2] // d
    out = np.zeros(q.shape, np.float64)
    for bi in range(b):
        k = k_pages[table[bi]].reshape(-1, n_kv, d)
        v = v_pages[table[bi]].reshape(-1, n_kv, d)
        for h in range(n_q):
            for i in range(t):
                pos = int(q_start[bi]) + i
                lo = 0 if window is None else max(0, pos - window + 1)
                keys = slice(lo, pos + 1)
                s = k[keys, h // (n_q // n_kv)] @ q[bi, h, i] / np.sqrt(d)
                p = np.exp(s - s.max())
                out[bi, h, i] = (p / p.sum()) @ v[keys, h // (n_q // n_kv)]
    return out


@pytest.fixture
def pages_per_step(monkeypatch, tmp_path):
    def pin(g):
        tf = tmp_path / f"tiles_{g}.json"
        tf.write_text(json.dumps({
            paged.kernel_name(hpk, w): {"*": {"pages_per_step": g}}
            for hpk in (1, 3) for w in (None, 1)}))
        monkeypatch.setenv(autotune.ENV_TABLE, str(tf))
        autotune.clear_cache()

    yield pin
    monkeypatch.delenv(autotune.ENV_TABLE, raising=False)
    autotune.clear_cache()


@pytest.fixture
def chunk_step(monkeypatch):
    """Hold the chunk rule's budgets down (``paged._chunk_geometry``):
    at most ``rows`` rows a score product and ``keys`` keys a step."""
    def hold(keys, rows=1 << 20):
        monkeypatch.setattr(paged, "_CHUNK_KEYS_PER_STEP", keys)
        monkeypatch.setattr(paged, "_CHUNK_ROWS_PER_STEP", rows)

    return hold


def _forms(name):
    from paddle_tpu import observability as obs

    fam = obs.snapshot().get("pt_paged_attention_form_total") or {}
    return {k[1:]: v for k, v in fam.get("samples", {}).items()
            if k[0] == name}


WINDOWS = [None, 1, PG - 1, PG, PG + 1, 2 * PG, 9]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("heads", [(6, 2), (4, 4), (8, 1)])
def test_the_xla_form_is_the_dense_oracle(window, heads):
    n_q, n_kv = heads
    for t, starts in ((1, [0, 3, 4, 17, 47]), (8, [0, 4, 8, 20, 40])):
        case = _case(n_q, n_kv, 8, t, starts, window, seed=3)
        got = prims.paged_attention(*case, force="reference", window=window)
        np.testing.assert_allclose(
            np.asarray(got), _oracle(*case, window), atol=2e-5, rtol=1e-5)


# contexts (q_start + 1) around a page's, a grid step's (2 pages) and a
# window's edges
@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("context", [1, PG, PG + 1, 2 * PG, 2 * PG + 1, 9,
                                     10, 23, MAXP * PG])
def test_decode_row_grouped_and_window(pages_per_step, window, context):
    """T = 1: the heads-batched body's grouped form beside a second row
    of another length."""
    pages_per_step(2)
    case = _case(6, 2, 16, 1, [context - 1, 13], window, seed=context)
    name = paged.kernel_name(3, window)
    before = _forms(name).get(("heads_batched", "2"), 0)
    got = prims.paged_attention(*case, force="pallas", window=window)
    want = prims.paged_attention_reference(*case, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    assert np.abs(np.asarray(got)).max() < 10.0    # V's trash reads 1000
    assert _forms(name).get(("heads_batched", "2"), 0) == before + 1


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("q_start", [0, 1, PG, 2 * PG - 1, 2 * PG, 13,
                                     MAXP * PG - 8])
def test_chunk_grouped_and_window(chunk_step, window, q_start):
    """T = 8 (a chunk of two pages): the body with a grid axis a K/V
    head, two pages a grid step."""
    chunk_step(2 * PG)
    case = _case(6, 2, 16, 8, [q_start, 5], window, seed=q_start)
    name = paged.kernel_name(3, window)
    before = _forms(name).get(("kv_head_tq8", "2"), 0)
    got = prims.paged_attention(*case, force="pallas", window=window)
    want = prims.paged_attention_reference(*case, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    assert np.abs(np.asarray(got)).max() < 10.0
    assert _forms(name).get(("kv_head_tq8", "2"), 0) == before + 1


@pytest.mark.parametrize("window", [None, 5, 16])
@pytest.mark.parametrize("g", [1, 2, 3, MAXP])
def test_chunk_in_query_tiles_at_any_pages_per_step(chunk_step, window, g):
    """A chunk whose group's rows pass the budget is scored a query
    tile at a time, each from its own window's first step; a step takes
    no more pages than a window and a tile span."""
    chunk_step(g * PG, rows=24)                     # tq = 8 of 16
    usable = MAXP if window is None else -(-(window + 8) // PG)
    assert paged._chunk_geometry(16, 3, 1, PG, MAXP, window) == (
        8, min(g, usable))
    case = _case(6, 2, 16, 16, [0, 7, 16, 29], window, seed=5)
    got = prims.paged_attention(*case, force="pallas", window=window)
    want = prims.paged_attention_reference(*case, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("t", [1, 8])
def test_window_alone_and_a_bfloat16_pool(pages_per_step, chunk_step, t):
    """Multi-head attention with a window takes the grouped form at
    g = 1; a bfloat16 pool feeds the products in bfloat16."""
    pages_per_step(2)
    chunk_step(2 * PG)
    case = _case(4, 4, 16, t, [21, 6], 6, seed=2)
    got = prims.paged_attention(*case, force="pallas", window=6)
    np.testing.assert_allclose(
        np.asarray(got), _oracle(*case, 6), atol=2e-5, rtol=1e-5)
    assert _forms("paged_attention_window")
    low = _case(6, 2, 16, t, [21, 6], 6, seed=2, dtype=jnp.bfloat16)
    got = prims.paged_attention(*low, force="pallas", window=6)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), _oracle(*low, 6), atol=0.05, rtol=0.05)


# ---------------------------------------------------------------------------
# the chunk's grid step (PR 43): the query tile and the keys a step come
# from the shapes together; every geometry the rule may give scores what
# the reference scores
# ---------------------------------------------------------------------------

GPG, GMAXP, GT = 8, 16, 32            # pages of 8, max_len 128, a chunk of 32
# (queries a tile, pages a step): one page a step; tq < t under several
# pages; a step as long as the table
GEOMETRIES = [(8, 1), (8, 4), (16, 2), (16, 8), (32, 16)]
# rows whose first query sees ONE key; whose last step is partly dead
# (context 69 under steps of 16, 32, 64 keys) and holds the causal edge
# and a window's lower edge at once; that start several steps in
GSTARTS = [0, 37, 96]


def _tile_under(tq, window):
    """The tile the rule gives where ``tq`` fits the rows: no longer
    than half the window, and no shorter than the sublanes."""
    while window is not None and tq > 8 and 2 * tq > window:
        tq //= 2
    return tq


@pytest.mark.parametrize("window", [None, 5, 24, 64, 200])
@pytest.mark.parametrize("heads", [(6, 1, 16), (2, 2, 128)])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_chunk_at_every_geometry(chunk_step, monkeypatch, geometry, heads,
                                 window):
    """g = 6 and g = 1 (plain heads whose whole-chunk blocks pass the
    per-head body's budget: Olmo-Hybrid's route) at each geometry, under
    no window, one shorter than a page, two that end inside a step (a
    tile is at most half a window long) and one longer than the
    table."""
    tq, pages = geometry
    n_q, n_kv, d = heads
    g = n_q // n_kv
    chunk_step(pages * GPG, rows=g * tq)
    monkeypatch.setattr(paged, "_PER_HEAD_VMEM_BYTES", 0)
    tq = _tile_under(tq, window)
    usable = GMAXP if window is None else min(
        GMAXP, -(-(window + tq) // GPG))
    assert paged._chunk_geometry(GT, g, 1, GPG, GMAXP, window) == (
        tq, min(pages, usable))
    case = _case(n_q, n_kv, d, GT, GSTARTS, window, seed=tq + pages,
                 page=GPG, max_pages=GMAXP)
    name = paged.kernel_name(g, window)
    form = (f"kv_head_tq{tq}", str(min(pages, usable)))
    before = _forms(name).get(form, 0)
    got = prims.paged_attention(*case, force="pallas", window=window)
    want = prims.paged_attention_reference(*case, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=1e-5)
    assert np.abs(np.asarray(got)).max() < 10.0    # V's trash reads 1000
    assert _forms(name).get(form, 0) == before + 1


@pytest.mark.parametrize("window", [None, 5, 24])
@pytest.mark.parametrize("geometry", [(8, 4), (16, 8), (32, 16)])
def test_chunk_geometries_over_a_bfloat16_pool(chunk_step, geometry, window):
    """A bfloat16 pool: the products run in bfloat16 with float32
    scores, state and sums."""
    tq, pages = geometry
    chunk_step(pages * GPG, rows=6 * tq)
    case = _case(6, 1, 16, GT, GSTARTS, window, seed=9, dtype=jnp.bfloat16,
                 page=GPG, max_pages=GMAXP)
    got = prims.paged_attention(*case, force="pallas", window=window)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), _oracle(*case, window),
                               atol=3e-2, rtol=3e-2)


# the chunk launches of the three served models that take these bodies
# (benchmark/configs: a chunk of 512 tokens over pages of 128):
# (query heads, K/V heads, d, d_v, table pages, window, sink) -> (queries
# a tile, pages a step).  Trinity's 6 heads x 256 queries fill the 1536
# rows of one product; Olmo-Hybrid's single head takes the whole chunk;
# MiMo's 16 heads take 64 queries, its two heads a lane block two score
# tiles of 4 MB; its window of 128 keeps the two pages a tile can use
SERVED = {
    "trinity_full": ((48, 8, 128, 128, 262, None, False), (256, 8)),
    "trinity_window": ((48, 8, 128, 128, 262, 4096, False), (256, 8)),
    "olmo_hybrid": ((30, 30, 128, 128, 98, None, False), (512, 8)),
    "mimo_full": ((64, 4, 192, 128, 272, None, False), (64, 8)),
    "mimo_window": ((64, 8, 192, 128, 272, 128, True), (64, 2)),
}


@pytest.mark.parametrize("layer", sorted(SERVED))
def test_the_rule_at_the_served_widths(layer):
    """The geometry comes from the shapes alone, and a trace books it as
    ``form="kv_head_tq<tq>"`` with the pages a step."""
    import jax

    (n, n_kv, d, d_v, max_pages, window, sink), (tq, pages) = SERVED[layer]
    lane_block = 2 if d_v != d else 1    # two heads of 192 a lane block
    assert paged._chunk_geometry(512, n // n_kv, lane_block, 128, max_pages,
                                 window) == (tq, pages)
    name = paged.kernel_name(n // n_kv, window, d_v != d, sink)
    form = (f"kv_head_tq{tq}", str(pages))
    before = _forms(name).get(form, 0)
    f32, bf16 = jnp.float32, jnp.bfloat16
    args = [jax.ShapeDtypeStruct((1, n, 512, d), f32),
            jax.ShapeDtypeStruct((9, 128, n_kv * d), bf16),
            jax.ShapeDtypeStruct((9, 128, n_kv * d_v), bf16),
            jax.ShapeDtypeStruct((1, max_pages), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32)]
    sinks = jax.ShapeDtypeStruct((n,), f32) if sink else None
    out = jax.eval_shape(
        lambda *a: prims.paged_attention(
            *a[:5], force="pallas", window=window,
            sinks=a[5] if sink else None), *args, *([sinks] if sink else []))
    assert out.shape == (1, n, 512, d_v)
    assert _forms(name).get(form, 0) == before + 1


def test_pools_of_unequal_heads_are_refused():
    q = _rand((1, 6, 1, 8), 0)
    k, v = _rand((5, PG, 16), 1), _rand((5, PG, 24), 2)
    table, start = np.zeros((1, 2), np.int32), np.zeros(1, np.int32)
    # since PR 41 as many V heads of ANOTHER width are a pool (two heads
    # of 12 beside two K heads of 8: the output is 12 wide) ...
    assert prims.paged_attention(q, k, v, table, start).shape == (1, 6, 1, 12)
    # ... but not V lanes that are no whole heads, another page count, or
    # another dtype
    with pytest.raises(ValueError, match="whole heads"):
        prims.paged_attention(q, k, v[:, :, :23], table, start)
    with pytest.raises(ValueError, match="whole heads"):
        prims.paged_attention(q, k, v[:4], table, start)
    with pytest.raises(ValueError, match="one dtype and one shape"):
        prims.paged_attention(q, k, _rand((5, PG, 16), 2).astype(
            jnp.bfloat16), table, start)
    with pytest.raises(ValueError, match=r"has shape \(5, 4, 20\)"):
        bad = _rand((5, PG, 20), 3)     # 20 lanes: no whole heads of 8
        prims.paged_attention(q, bad, bad, np.zeros((1, 2), np.int32),
                              np.zeros(1, np.int32))
