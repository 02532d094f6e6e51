"""The delta rule with a decay a KEY CHANNEL (Kimi Delta Attention) over
the per-sequence state gdn.py lays out: per head, ``S`` in R^(d_k x
d_v), ``alpha_t = exp(g_t)`` in (0, 1)^d_k,

  S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t

which is ``S' = Diag(alpha_t) S_{t-1}; u = beta_t (v_t - S'^T k_t);
S_t = S' + k_t u^T``.  With every channel's ``g`` equal it IS gdn.py's
rule; the stored layout of the state (``[blocks, d_k, H * d_v]``
float32), the exact inverse of the unit lower-triangular system and the
token-by-token XLA reference forms are gdn.py's, which take ``g`` of
either shape.  Everything is float32 at full precision.

  kda_chunk   one sequence's C tokens, all heads, the sequence's state
              block in and out, over sub-chunks of ``SUB`` tokens.  With
              Gamma_t = sum_{i<=t} g_i (a vector of d_k a head) inside a
              sub-chunk the decay no longer factors out of the products:

                (I + Diag(beta) A) U = Diag(beta) (V - (K * e^Gamma) S_0)
                A_tj = sum_c k_tc k_jc e^(Gamma_tc - Gamma_jc),   j < t
                O = (Q * e^Gamma) S_0 + B U
                B_tj = sum_c q_tc k_jc e^(Gamma_tc - Gamma_jc),   j <= t
                S_C = Diag(e^Gamma_C) S_0 + (K * e^(Gamma_C - Gamma))^T U

              ``e^-Gamma_j`` alone overflows float32 inside one
              sub-chunk (a decay of e^-1.6 a token is e^-102 over 64),
              so **every exponent the kernel evaluates is <= 0**: a pair
              (t, j) is referred to a point between j and t.  The
              sub-chunk is cut into blocks of ``BLOCK`` rows.  A pair in
              two blocks is referred to the FIRST ROW r of t's block,
              e^(Gamma_t - Gamma_r) e^(Gamma_r - Gamma_j): one product a
              row block, [2 BLOCK, d_k] x [d_k, SUB] for A and B
              together.  A pair inside one block takes the pairwise
              difference itself, e^(Gamma_t - Gamma_(t-d)) for the
              ``BLOCK`` offsets d = 0 .. BLOCK - 1, each over the whole
              sub-chunk at once (the rows rolled by d), summed over the
              channels and laid on the d-th diagonal.  A far pair
              underflows to 0, which is what it is.  Then gdn.py's
              finite Neumann product solves the system exactly.  One
              grid step a (head, sub-chunk), the state carried in VMEM.
  kda_step    one token a slot: gdn.py's step kernel with ``alpha``
              [d_k, heads of the tile] spread over each head's d_v lanes
              of the stored block by the 0/1 pick matrix, inside the
              kernel (an ``alpha`` as large as the state never exists in
              HBM).  The state is rewritten in place
              (``input_output_aliases``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import contract
from .contract import Block, Vmem
from .gdn import (_check, _f32, _launch_step, _mm, _mm_t, _run_chunk,
                  _step_update, _unit_lower_inverse,
                  gated_delta_step_reference)

__all__ = ["kda_chunk", "kda_step"]

BLOCK = 8          # rows of a block: pairs inside it take their own difference


def _check_decay(op, q, g):
    if g.shape != q.shape:
        raise ValueError(f"{op}: g {tuple(g.shape)} is not a decay a key "
                         f"channel of q {tuple(q.shape)}")


# ---------------------------------------------------------------------------
# the chunk kernel
# ---------------------------------------------------------------------------


def _pair_scores(q, k, kb, gam, roll):
    """The two decayed score matrices of one sub-chunk of one head: q, k,
    kb = beta k, gam = Gamma [c, d_k] -> (A with beta_t on its rows,
    strictly lower; B, lower with its diagonal), both [c, c].  No
    exponent is above 0."""
    c = q.shape[0]
    bits = BLOCK.bit_length() - 1
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = (row >> bits) == (col >> bits)
    # pairs of one block, by their offset d = t - j
    a = jnp.zeros((c, c), jnp.float32)
    b = jnp.zeros((c, c), jnp.float32)
    for d in range(min(BLOCK, c)):
        ks, gs = (k, gam) if d == 0 else (roll(k, d), roll(gam, d))
        e = ks * jnp.exp(jnp.minimum(gam - gs, 0.0))   # row t: k, Gamma of t - d
        on = jnp.where(same & (row - col == d), 1.0, 0.0)
        b = b + jnp.sum(q * e, axis=-1, keepdims=True) * on
        if d:
            a = a + jnp.sum(kb * e, axis=-1, keepdims=True) * on
    if c <= BLOCK:
        return a, b
    # pairs of two blocks, referred to the first row of t's block
    none = jnp.zeros((BLOCK, c), jnp.float32)
    off_a, off_b = [none], [none]
    for lo in range(BLOCK, c, BLOCK):
        at = gam[lo:lo + 1]
        left = jnp.exp(gam[lo:lo + BLOCK] - at)
        right = k * jnp.exp(jnp.minimum(at - gam, 0.0))
        both = _mm_t(jnp.concatenate([q[lo:lo + BLOCK] * left,
                                      kb[lo:lo + BLOCK] * left]), right)
        off_b.append(both[:BLOCK])
        off_a.append(both[BLOCK:])
    before = (col >> bits) < (row >> bits)
    return (a + jnp.where(before, jnp.concatenate(off_a), 0.0),
            b + jnp.where(before, jnp.concatenate(off_b), 0.0))


def _chunk_math(s, q, k, kb, kdt, vb, gam, elast, roll):
    """One sub-chunk of one head: s [d_k, d_v] the state before it; q, k,
    kb = beta k, gam = Gamma [c, d_k]; kdt = (e^(Gamma_c - Gamma) k)^T
    [d_k, c]; vb = beta v [c, d_v]; elast = e^Gamma_c [d_k, 1].  -> (o
    [c, d_v], the state after it)."""
    a, b = _pair_scores(q, k, kb, gam, roll)
    eg = jnp.exp(gam)
    u = _mm(_unit_lower_inverse(a), vb - _mm(kb * eg, s))
    o = _mm(q * eg, s) + _mm(b, u)
    return o, elast * s + _mm(kdt, u)


def _rolled(interpret):
    """``roll(x, d)``: row t of the result is row t - d of x (the first d
    rows wrap, and nothing reads them)."""
    if interpret:
        return lambda x, d: jnp.roll(x, d, axis=0)
    from jax.experimental.pallas import tpu as pltpu

    return lambda x, d: pltpu.roll(x, d, 0)


def _chunk_kernel(q_ref, k_ref, kb_ref, kdt_ref, vb_ref, gam_ref, el_ref,
                  s0_ref, o_ref, so_ref, s_ref, *, n_sub, roll):
    from jax.experimental import pallas as pl

    n = pl.program_id(1)

    @pl.when(n == 0)
    def _load():
        s_ref[...] = s0_ref[0]

    o, s = _chunk_math(s_ref[...], q_ref[0], k_ref[0], kb_ref[0],
                       kdt_ref[0, 0], vb_ref[0], gam_ref[0], el_ref[0, 0],
                       roll)
    o_ref[0] = o
    s_ref[...] = s

    @pl.when(n == n_sub - 1)
    def _store():
        so_ref[0] = s


def _chunk_operands(q, k, v, g, beta, sub):
    """The kernel's operands from the chunk's inputs (q, k, g [C, H, d_k],
    v [C, H, d_v], beta [C, H]), heads first and by sub-chunk; every
    exponent is <= 0."""
    c, heads, dk = q.shape
    n = c // sub
    q, k, v, g = (x.transpose(1, 0, 2) for x in (q, k, v, g))   # [H, C, .]
    beta = beta.T[..., None]                                    # [H, C, 1]
    gamma = jnp.cumsum(g.reshape(heads, n, sub, dk), axis=2)
    last = gamma[:, :, -1:]                                     # [H, n, 1, dk]
    kdt = (k.reshape(heads, n, sub, dk)
           * jnp.exp(last - gamma)).transpose(0, 1, 3, 2)       # [H, n, dk, sub]
    elast = jnp.exp(last).transpose(0, 1, 3, 2)                 # [H, n, dk, 1]
    return (q, k, k * beta, kdt, v * beta, gamma.reshape(heads, c, dk),
            elast)


def _pallas_chunk(q, k, v, g, beta, s0, sub, interpret):
    """s0 [H, d_k, d_v] -> (o [C, H, d_v], s [H, d_k, d_v])."""
    c, heads, dk = q.shape
    dv = v.shape[-1]
    n = c // sub
    ops = _chunk_operands(q, k, v, g, beta, sub)

    def rows(width):
        return Block((1, sub, width), lambda h, i: (h, i, 0))

    def tile(a, b):
        return Block((1, 1, a, b), lambda h, i: (h, i, 0, 0))

    whole = Block((1, dk, dv), lambda h, i: (h, 0, 0))
    spec = contract.make_spec(
        "kda_chunk",
        grid=(heads, n),
        in_specs=[rows(dk), rows(dk), rows(dk), tile(dk, sub), rows(dv),
                  rows(dk), tile(dk, 1), whole],
        out_specs=[rows(dv), whole],
        out_shape=[((heads, c, dv), jnp.float32),
                   ((heads, dk, dv), jnp.float32)],
        scratch=[Vmem((dk, dv), jnp.float32)],
        interpret=interpret,
    )
    o, s = contract.primitive_call(
        functools.partial(_chunk_kernel, n_sub=n, roll=_rolled(interpret)),
        spec, *ops, s0)
    return o.transpose(1, 0, 2), s


def kda_chunk(q, k, v, g, beta, state, block, fresh, *, force=None):
    """The delta rule with a decay a key channel over one sequence's C
    tokens, all heads: q, k, g [C, H, d_k], v [C, H, d_v], beta [C, H] ->
    (o [C, H, d_v] float32, the state tensor [blocks, d_k, H * d_v] with
    block ``block`` (a scalar) written).  ``fresh`` (a scalar bool): the
    block is read as zeros.  Positions with ``beta = 0`` and ``g = 0``
    leave the state as it was.

    force: None -> Pallas on TPU, XLA reference elsewhere; "pallas" ->
    Pallas (interpret mode off-TPU); "reference" -> XLA."""
    _check_decay("kda_chunk", q, g)
    return _run_chunk("kda_chunk", _pallas_chunk,
                      lambda sub: f"sub{sub}.block{min(BLOCK, sub)}", q, k, v,
                      g, beta, state, block, fresh, force)


# ---------------------------------------------------------------------------
# the step kernel
# ---------------------------------------------------------------------------


def _step_kernel(blk_ref, q_ref, k_ref, kt_ref, v_ref, at_ref, b_ref, e_ref,
                 s_ref, o_ref, so_ref):
    del blk_ref  # read by the index maps
    pick = e_ref[...]                                  # [hp, L] 0 / 1
    # alpha [d_k, hp] spread over each head's lanes: one 1 a column, exact
    _step_update(_mm(at_ref[0, 0], pick) * s_ref[0], pick, q_ref, k_ref,
                 kt_ref, v_ref, b_ref, o_ref, so_ref)


def _pallas_step(q, k, v, g, beta, state, blocks, interpret):
    dk = q.shape[-1]

    def decay(by_tile, lanes_of, head_rows, lane_row):
        alpha = by_tile(jnp.exp(g))                    # [B, tiles, hp, d_k]
        return (alpha.transpose(0, 1, 3, 2),
                head_rows(dk, alpha.shape[2]))

    return _launch_step("kda_step", _step_kernel, decay, q, k, v, beta,
                        state, blocks, interpret)


def kda_step(q, k, v, g, beta, state, blocks, *, force=None):
    """The delta rule with a decay a key channel for one token a slot: q,
    k, g [B, H, d_k], v [B, H, d_v], beta [B, H], ``blocks`` [B] int32
    each slot's state block (inactive slots name the trash block 0) ->
    (o [B, H, d_v] float32, the state tensor with those blocks rewritten
    in place).

    force: None -> Pallas on TPU, XLA reference elsewhere; "pallas" ->
    Pallas (interpret mode off-TPU); "reference" -> XLA."""
    _check("kda_step", q, k, v, state)
    _check_decay("kda_step", q, g)
    mode, interpret = contract.resolve_mode("kda_step", force)
    if mode != "pallas":
        return gated_delta_step_reference(q, k, v, g, beta, state, blocks)
    return _pallas_step(*_f32(q, k, v, g, beta), state, blocks, interpret)
