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


def _case(n_q, n_kv, d, t, q_starts, window, seed=0, dtype=np.float32):
    """Pools whose trash page (0) and every page wholly below a row's
    window hold large values (the allocator has given those back: their
    table entries are the trash page), live pages in shuffled order."""
    b = len(q_starts)
    rng = np.random.RandomState(seed)
    q = _rand((b, n_q, t, d), seed + 1)
    k_pages = _rand((NPAGES, PG, n_kv * d), seed + 2)
    v_pages = _rand((NPAGES, PG, n_kv * d), seed + 3)
    k_pages[0], v_pages[0] = 50.0, 1000.0
    free = list(rng.permutation(np.arange(1, NPAGES)))
    table = np.zeros((b, MAXP), np.int32)
    for i, start in enumerate(q_starts):
        first = 0 if window is None else max(0, start - window + 1) // PG
        for j in range(first, (start + t - 1) // PG + 1):
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
def test_chunk_grouped_and_window(pages_per_step, window, q_start):
    """T = 8 (a chunk of two pages): the body with a grid axis a K/V
    head."""
    pages_per_step(2)
    case = _case(6, 2, 16, 8, [q_start, 5], window, seed=q_start)
    name = paged.kernel_name(3, window)
    before = _forms(name).get(("kv_head", "2"), 0)
    got = prims.paged_attention(*case, force="pallas", window=window)
    want = prims.paged_attention_reference(*case, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)
    assert np.abs(np.asarray(got)).max() < 10.0
    assert _forms(name).get(("kv_head", "2"), 0) == before + 1


@pytest.mark.parametrize("window", [None, 5, 16])
@pytest.mark.parametrize("g", [1, 2, 3, MAXP])
def test_chunk_in_query_tiles_at_any_pages_per_step(pages_per_step,
                                                    monkeypatch, window, g):
    """A chunk whose group's rows pass the budget is scored a query
    tile at a time, each from its own window's first step."""
    pages_per_step(g)
    monkeypatch.setattr(paged, "_QUERY_ROWS_PER_STEP", 24)  # tq = 8 of 16
    assert paged._query_tile(16, 3) == 8
    case = _case(6, 2, 16, 16, [0, 7, 16, 29], window, seed=5)
    got = prims.paged_attention(*case, force="pallas", window=window)
    want = prims.paged_attention_reference(*case, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("t", [1, 8])
def test_window_alone_and_a_bfloat16_pool(pages_per_step, t):
    """Multi-head attention with a window takes the grouped form at
    g = 1; a bfloat16 pool feeds the products in bfloat16."""
    pages_per_step(2)
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
