"""Dense multi-head latent attention over a paged latent cache: every
visible row, no selection operand (dsa.py is the learned-sparse sibling
and keeps its ``selected`` mask).  A token's cache row is
``[c_kv (C) | k_rope (R) | zeros]``, shared by all heads and stored at
whole lane tiles (serving/lane.py ``lane_padded``).  The same numbers in
two forms, one a side of the roofline:

  paged_mla_attention   LATENT space, for the decode step (T = 1 over B
                        slots): the query's nope half is already
                        multiplied into the compressed-KV space
                        (``q_lat = q_nope W_uk``), scores are
                        ``(q_lat . c + q_rope . k_r) * sm_scale`` and
                        the result ``P c`` stays in the latent space
                        (the caller applies ``W_uv``).  A step reads a
                        sequence's rows once for all heads: bound by the
                        cache's bytes, 1152 B a token a layer.
  mla_chunk_attention   HEAD space, for the prefill chunk (B = 1, T = the
                        chunk): each block of cached rows is
                        up-projected inside the kernel,
                        ``k_h = [c W_uk,h | k_r]``, ``v_h = c W_uv,h``,
                        and scored by ``q_h = [q_nope,h | q_rope,h]``.
                        A (query, key) pair costs (192 + 128) x 2 FLOP a
                        head against (576 + 512) x 2 in latent space and
                        the accumulator is v wide (128), not C (512);
                        the up-projection, 2 x C x (nope + v) FLOP a key
                        a head, is paid once a key block a query tile,
                        and a chunk is one query tile.

Both walk a sequence's pages through the scalar-prefetched page table as
paged.py and dsa.py do, G pages a grid step, concatenated on the key axis
before ONE score / online-softmax / value update; a step whose first key
lies past the tile's last query is skipped and fetches nothing new (its
table entries read the trash page, whose block index does not move).
Neither takes a ``[B, T, Lp]`` operand.

Shapes:
  q_lat        [B, T, H, C]; q_nope [B, T, H, nope]; q_rope [B, T, H, R]
  latent_pages [num_pages, page_size, W], W >= C + R
  page_table   [B, max_pages] int32; q_start [B] int32: query t of row b
               sees positions s <= q_start[b] + t
  w_uk         [H, nope, C]; w_uv [H, C, v]  (the two halves of the KV
               up-projection, a matrix a head)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import contract
from .contract import Block, Vmem
from .dsa import _padded_queries, _padded_table, _page_map, _row_map, \
    _visible
from .paged import NEG_INF, _init_state, _mxu, _online_softmax_step, \
    _p_dtype, _step_pages

__all__ = ["paged_mla_attention", "paged_mla_attention_reference",
           "mla_chunk_attention", "mla_chunk_attention_reference"]

# keys a grid step scores at once (whole pages): a step costs ~0.35 us
# whatever it moves, and 1024 rows of 640 bf16 are 1.3 MB
KEYS_PER_STEP = 1024
# queries of one head a chunk step scores: the whole 512-token chunk, so
# a key block is up-projected once a head
CHUNK_QUERY_TILE = 512


def _pages_per_step(page, max_pages):
    return max(1, min(KEYS_PER_STEP // page, max_pages))


def _check_latent_pool(op, latent_pages, least):
    if latent_pages.ndim != 3 or latent_pages.shape[2] < least:
        raise ValueError(
            f"{op}: the latent cache has shape {tuple(latent_pages.shape)}, "
            f"wanted [num_pages, page_size, >= {least}] (a row is "
            f"[c_kv | k_rope | pad])")


def _mxu_operand(x, latent_pages):
    """``x`` as the MXU multiplies it with the pool's rows: in the pool's
    bfloat16, or in float32."""
    return x.astype(jnp.bfloat16 if latent_pages.dtype == jnp.bfloat16
                    else jnp.float32)


def _gathered_rows(latent_pages, page_table):
    b = page_table.shape[0]
    return latent_pages[page_table].reshape(
        b, page_table.shape[1] * latent_pages.shape[1], -1)


# ---------------------------------------------------------------------------
# latent space: the decode step
# ---------------------------------------------------------------------------


def paged_mla_attention_reference(q_lat, q_rope, latent_pages, page_table,
                                  q_start, sm_scale):
    """Materialising XLA form: CPU fallback and numerics oracle."""
    t, c = q_lat.shape[1], q_lat.shape[3]
    rows = _gathered_rows(latent_pages, page_table)
    q = _padded_queries(q_lat, q_rope, rows.shape[-1]).astype(rows.dtype)
    s = jnp.einsum("bthc,blc->bthl", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(_visible(q_start, t, rows.shape[1])[:, :, None, :], s,
                  NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bthl,blc->bthc", p.astype(rows.dtype),
                      rows[..., :c], preferred_element_type=jnp.float32)


def _masked(s, first_q, base, tq, heads):
    """Scores [tq * heads, K] of queries first_q .. against keys base ..:
    -1e9 where a query may not see the key (its own value is lost in the
    rounding, as in paged.py)."""
    keys = s.shape[-1]
    kpos = base + jax.lax.broadcasted_iota(jnp.int32, (tq, keys), 1)
    qpos = first_q + jax.lax.broadcasted_iota(jnp.int32, (tq, keys), 0)
    if heads == 1:
        return jnp.where(kpos <= qpos, s, NEG_INF)
    return jnp.where((kpos <= qpos)[:, None, :],
                     s.reshape(tq, heads, keys), NEG_INF).reshape(
                         tq * heads, keys)


def _normalised(acc_ref, l_ref):
    l = l_ref[...]
    return acc_ref[...] / jnp.where(l == 0.0, 1.0, l)[:, :1]


def _latent_kernel(pt_ref, qs_ref, q_ref, *refs, page, tq, heads, c, n_sub,
                   n_steps, sm_scale):
    from jax.experimental import pallas as pl

    k_refs, o_ref = refs[:n_sub], refs[n_sub]
    acc_ref, m_ref, l_ref = refs[n_sub + 1:]
    bi, qi, pi = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    first_q = qs_ref[bi] + qi * tq
    base = pi * n_sub * page

    # live iff the tile's last query sees the step's first key
    @pl.when(base <= first_q + tq - 1)
    def _live():
        rows = _mxu(_step_pages(k_refs))                   # [K, W]
        q2 = q_ref[0].reshape(tq * heads, q_ref.shape[-1])
        s = jax.lax.dot_general(
            q2, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        _online_softmax_step(_masked(s, first_q, base, tq, heads),
                             rows[:, :c], acc_ref, m_ref, l_ref,
                             p_dtype=_p_dtype(rows.dtype))

    @pl.when(pi == n_steps - 1)
    def _done():
        o_ref[0] = _normalised(acc_ref, l_ref).reshape(tq, heads, c)


def _pallas_latent(q_lat, q_rope, latent_pages, page_table, q_start,
                   sm_scale, interpret):
    b, t, heads, c = q_lat.shape
    page, width = latent_pages.shape[1], latent_pages.shape[2]
    n_sub = _pages_per_step(page, page_table.shape[1])
    steps = -(-page_table.shape[1] // n_sub)
    page_table = _padded_table(page_table, steps * n_sub)
    tq = 8 if t % 8 == 0 else t
    q = _mxu_operand(_padded_queries(q_lat, q_rope, width), latent_pages)
    spec = contract.make_spec(
        "paged_mla_attention",
        grid=(b, t // tq, steps),
        in_specs=[Block((1, tq, heads, width), _row_map)]
        + [Block((1, page, width), _page_map(n_sub, j))
           for j in range(n_sub)],
        out_specs=[Block((1, tq, heads, c), _row_map)],
        out_shape=[((b, t, heads, c), jnp.float32)],
        scratch=[Vmem((tq * heads, c), jnp.float32),
                 Vmem((tq * heads, 128), jnp.float32),
                 Vmem((tq * heads, 128), jnp.float32)],
        num_scalar_prefetch=2,
        interpret=interpret,
    )
    return contract.primitive_call(
        functools.partial(_latent_kernel, page=page, tq=tq, heads=heads,
                          c=c, n_sub=n_sub, n_steps=steps,
                          sm_scale=sm_scale),
        spec, page_table, q_start.astype(jnp.int32), q,
        *([latent_pages] * n_sub))


def paged_mla_attention(q_lat, q_rope, latent_pages, page_table, q_start, *,
                        sm_scale, force=None):
    """Latent-space attention over every visible row of the paged latent
    cache: q_lat [B, T, H, C] (q_nope absorbed into the compressed-KV
    space), q_rope [B, T, H, R] -> [B, T, H, C] float32, still in the
    latent space.  The decode step's form (T = 1); any T is computed.

    force: None -> Pallas on TPU, XLA reference elsewhere; "pallas" ->
    Pallas (interpret mode off-TPU); "reference" -> XLA."""
    _check_latent_pool("paged_mla_attention", latent_pages,
                       q_lat.shape[3] + q_rope.shape[3])
    mode, interpret = contract.resolve_mode("paged_mla_attention", force)
    if mode == "pallas":
        return _pallas_latent(q_lat, q_rope, latent_pages, page_table,
                              q_start, float(sm_scale), interpret)
    return paged_mla_attention_reference(
        q_lat, q_rope, latent_pages, page_table, q_start, float(sm_scale))


# ---------------------------------------------------------------------------
# head space: the prefill chunk
# ---------------------------------------------------------------------------


def mla_chunk_attention_reference(q_nope, q_rope, latent_pages, page_table,
                                  q_start, w_uk, w_uv, sm_scale):
    """Materialising XLA form: CPU fallback and numerics oracle.  The
    cached rows are up-projected to every head's keys and values, which
    are rounded to the cache's dtype as the kernel rounds them."""
    t, c, r = q_nope.shape[1], w_uk.shape[2], q_rope.shape[3]
    rows = _gathered_rows(latent_pages, page_table)
    dt = rows.dtype

    def rounded(x):
        """Rounded to the cache's dtype, multiplied in float32: the
        products of a bfloat16 MXU pass, in a form every backend has."""
        return x.astype(dt).astype(jnp.float32)

    lat, k_rope = rounded(rows[..., :c]), rounded(rows[..., c:c + r])
    k_nope = rounded(jnp.einsum("blc,hnc->bhln", lat, rounded(w_uk)))
    v = rounded(jnp.einsum("blc,hcv->bhlv", lat, rounded(w_uv)))
    s = (jnp.einsum("bthn,bhln->bthl", rounded(q_nope), k_nope)
         + jnp.einsum("bthr,blr->bthl", rounded(q_rope), k_rope)) * sm_scale
    s = jnp.where(_visible(q_start, t, rows.shape[1])[:, :, None, :], s,
                  NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bthl,bhlv->bthv", rounded(p), v)


def _chunk_kernel(pt_ref, qs_ref, q_ref, uk_ref, uv_ref, *refs, page, tq,
                  nope, c, n_sub, n_steps, sm_scale):
    from jax.experimental import pallas as pl

    k_refs, o_ref = refs[:n_sub], refs[n_sub]
    acc_ref, m_ref, l_ref = refs[n_sub + 1:]
    bi, qi, pi = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(pi == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    first_q = qs_ref[bi] + qi * tq
    base = pi * n_sub * page

    @pl.when(base <= first_q + tq - 1)
    def _live():
        rows = _mxu(_step_pages(k_refs))                   # [K, W]
        dt = rows.dtype
        lat = rows[:, :c]
        # this head's keys and values of the step's rows
        k_nope = jax.lax.dot_general(
            lat, uk_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dt)     # [K, nope]
        v = jnp.dot(lat, uv_ref[0],
                    preferred_element_type=jnp.float32).astype(dt)
        q = q_ref[0, 0]                                    # [tq, nope + W-c]
        s = (jax.lax.dot_general(
            q[:, :nope], k_nope, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                q[:, nope:], rows[:, c:], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) * sm_scale
        _online_softmax_step(_masked(s, first_q, base, tq, 1), v, acc_ref,
                             m_ref, l_ref, p_dtype=_p_dtype(dt))

    @pl.when(pi == n_steps - 1)
    def _done():
        o_ref[0, 0] = _normalised(acc_ref, l_ref)


def _pallas_chunk(q_nope, q_rope, latent_pages, page_table, q_start, w_uk,
                  w_uv, sm_scale, interpret):
    b, t, heads, nope = q_nope.shape
    c, v = w_uk.shape[2], w_uv.shape[2]
    page, width = latent_pages.shape[1], latent_pages.shape[2]
    n_sub = _pages_per_step(page, page_table.shape[1])
    steps = -(-page_table.shape[1] // n_sub)
    page_table = _padded_table(page_table, steps * n_sub)
    # a head's query beside the row it scores: [q_nope | q_rope | zeros],
    # the rope half as wide as the row's tail (its pad lanes hold zeros)
    tail = width - c - q_rope.shape[-1]
    q = _mxu_operand(jnp.concatenate(
        [q_nope, jnp.pad(q_rope, ((0, 0),) * 3 + ((0, tail),))], axis=-1),
        latent_pages).transpose(0, 2, 1, 3)                # [B, H, T, .]
    tp = -(-t // 8) * 8        # rows past T see later keys; dropped below
    tq = CHUNK_QUERY_TILE if tp % CHUNK_QUERY_TILE == 0 else tp
    if tp != t:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    spec = contract.make_spec(
        "mla_chunk_attention",
        grid=(b, heads, tp // tq, steps),
        in_specs=[Block((1, 1, tq, q.shape[-1]),
                        lambda bi, hi, qi, pi, pt, qs: (bi, hi, qi, 0)),
                  Block((1, nope, c),
                        lambda bi, hi, qi, pi, pt, qs: (hi, 0, 0)),
                  Block((1, c, v),
                        lambda bi, hi, qi, pi, pt, qs: (hi, 0, 0))]
        + [Block((1, page, width),
                 (lambda j: lambda bi, hi, qi, pi, pt, qs:
                  (pt[bi, pi * n_sub + j], 0, 0))(j))
           for j in range(n_sub)],
        out_specs=[Block((1, 1, tq, v),
                         lambda bi, hi, qi, pi, pt, qs: (bi, hi, qi, 0))],
        out_shape=[((b, heads, tp, v), jnp.float32)],
        scratch=[Vmem((tq, v), jnp.float32),
                 Vmem((tq, 128), jnp.float32),
                 Vmem((tq, 128), jnp.float32)],
        num_scalar_prefetch=2,
        interpret=interpret,
    )
    out = contract.primitive_call(
        functools.partial(_chunk_kernel, page=page, tq=tq, nope=nope, c=c,
                          n_sub=n_sub, n_steps=steps, sm_scale=sm_scale),
        spec, page_table, q_start.astype(jnp.int32), q,
        _mxu_operand(w_uk, latent_pages), _mxu_operand(w_uv, latent_pages),
        *([latent_pages] * n_sub))
    return out[:, :, :t].transpose(0, 2, 1, 3)


def mla_chunk_attention(q_nope, q_rope, latent_pages, page_table, q_start,
                        w_uk, w_uv, *, sm_scale, force=None):
    """Head-space attention of a chunk's queries over every visible row
    of the paged latent cache, the rows up-projected inside the kernel:
    q_nope [B, T, H, nope], q_rope [B, T, H, R], w_uk [H, nope, C], w_uv
    [H, C, v] -> [B, T, H, v] float32 (the caller applies W_o).  The
    prefill chunk's form; any T is computed.

    force: None -> Pallas on TPU, XLA reference elsewhere; "pallas" ->
    Pallas (interpret mode off-TPU); "reference" -> XLA."""
    _check_latent_pool("mla_chunk_attention", latent_pages,
                       w_uk.shape[2] + q_rope.shape[3])
    mode, interpret = contract.resolve_mode("mla_chunk_attention", force)
    if mode == "pallas":
        return _pallas_chunk(q_nope, q_rope, latent_pages, page_table,
                             q_start, w_uk, w_uv, float(sm_scale),
                             interpret)
    return mla_chunk_attention_reference(
        q_nope, q_rope, latent_pages, page_table, q_start, w_uk, w_uv,
        float(sm_scale))
