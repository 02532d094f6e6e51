"""The gated delta rule (a linear-attention layer's recurrence) over a
per-sequence state: what a SEQUENCE owns in a layer and every token
overwrites, not rows a token leaves (serving/lane.py ``SeqState``).

Per head, with ``S`` in R^(d_k x d_v), ``alpha = exp(g)``:

  S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t

which is ``S' = alpha S; u = beta (v - S'^T k); S_t = S' + k u^T``.  The
callers hand over q and k already L2-normalised (q also scaled), ``g``
the log of the decay (<= 0) and ``beta`` in (0, 2); a position with
``beta = 0`` and ``g = 0`` leaves the state as it was (a chunk's padded
tail).  Everything here is float32: the state is stored and updated in
float32 and every product is taken at full precision.

Keys may have FEWER heads than values (``q``, ``k`` [.., H_k, d_k] beside
``v``, ``g``, ``beta`` of H = r H_k value heads): value head h reads the q
and k of key head ``h // r``, with a decay, a beta and a state of its own
(Qwen3-Next: 32 value heads on 16 key heads).  Neither kernel repeats q
or k in memory: the chunk kernel's q and k blocks are found at ``h // r``
by their index maps, and the step kernel's 0/1 pick matrix gives a key
head the lanes of its r value heads, so its products have 1 / r of the
rows.  With H_k = H every operand and every traced operation is what it
was.

``g`` has one of two shapes.  One number a HEAD, ``[.., H]``: the rule
above, and the two kernels of this file.  One number a KEY CHANNEL,
``[.., H, d_k]`` (Kimi Delta Attention): ``alpha`` is then
``Diag(alpha_t)`` on the state's d_k rows, ``S' = Diag(alpha_t)
S_{t-1}``, and the kernels are kda.py's (``kda_chunk``, ``kda_step``),
which share this file's layout, triangular inverse and reference forms:
the XLA reference forms below take either shape.

Two forms of the same numbers:

  gated_delta_chunk   one sequence's C tokens, all heads (a prefill
                      chunk): the sequence's state block in, out.  The
                      chunked form of the rule over sub-chunks of
                      ``SUB`` tokens: inside a sub-chunk the pseudo-values
                      ``u`` solve the unit lower-triangular system
                      (I + diag(beta) (K K^T . D)) U = diag(beta) (V -
                      diag(e^gamma) K S_0), D_tj = e^(gamma_t - gamma_j)
                      (gamma the running sum of g), solved by the exact
                      inverse of 8 x 8 diagonal blocks (a finite Neumann
                      product) merged block by block; then O = e^gamma .
                      (Q S_0) + ((Q K^T) . D) U and S_C = e^gamma_C S_0 +
                      (e^(gamma_C - gamma) . K)^T U.  One grid step a
                      (head, sub-chunk), the state carried in VMEM.
  gated_delta_step    one token a slot (a decode step): each slot's
                      state block is read by its index and written back
                      IN PLACE (``input_output_aliases``: no executable
                      copies the state tensor).  One grid step a (slot,
                      tile of heads); the heads of a tile are picked out
                      of whole-tile products by a 0/1 mask, so nothing is
                      sliced inside a lane tile.

The XLA reference forms are the recurrence token by token.

Stored layout of the state tensor: ``[blocks, d_k, H * d_v]`` float32,
the heads' value columns side by side in the lane dimension (30 x 192 =
5760 = 45 lane tiles, 96 = 12 sublane tiles at the published sizes: the
default row-major tiling with no pad).  Block 0 is the trash block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import contract
from .contract import Block, Vmem

__all__ = ["gated_delta_chunk", "gated_delta_chunk_reference",
           "gated_delta_step", "gated_delta_step_reference"]

SUB = 64           # tokens of a sub-chunk (the triangular system's size)
BASE = 8           # diagonal blocks inverted by the finite Neumann product
STEP_TILE_BYTES = 1 << 20   # most bytes of one (slot, head tile) state block

_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.dot(a, b, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _mm_t(a, b):
    """a [m, k] x b [n, k]^T -> [m, n]."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _heads_first(block, heads):
    """A stored state block [d_k, H * d_v] as [H, d_k, d_v]."""
    dk = block.shape[0]
    return block.reshape(dk, heads, -1).transpose(1, 0, 2)


def _stored(s):
    """[H, d_k, d_v] as a stored state block [d_k, H * d_v]."""
    h, dk, dv = s.shape
    return s.transpose(1, 0, 2).reshape(dk, h * dv)


def _check(op, q, k, v, state):
    heads, dk, dv = v.shape[-2], q.shape[-1], v.shape[-1]
    if (state.ndim != 3 or state.shape[1] != dk
            or state.shape[2] != heads * dv or state.dtype != jnp.float32):
        raise ValueError(
            f"{op}: the state tensor is {state.dtype}"
            f"{tuple(state.shape)}, wanted float32 [blocks, {dk}, "
            f"{heads * dv}] (d_k rows, the heads' d_v columns side by "
            f"side)")
    if (k.shape != q.shape or v.shape[:-2] != q.shape[:-2]
            or heads % q.shape[-2]):
        raise ValueError(
            f"{op}: q {q.shape}, k {k.shape}, v {v.shape} (value heads in "
            f"whole groups a key head)")


def _per_value_head(x, heads, axis=-2):
    """q or k [.., H_k, d_k] (its heads on ``axis``) as [.., H, d_k]:
    value head h reads key head h // (H / H_k).  As it was where H_k =
    H."""
    r = heads // x.shape[axis]
    return x if r == 1 else jnp.repeat(x, r, axis=axis)


def _group_suffix(q, v):
    """"" or ``_vk<r>``: r value heads read one key head."""
    r = v.shape[-2] // q.shape[-2]
    return "" if r == 1 else f"_vk{r}"


def _book_form(primitive, form):
    """Count one trace-time choice of kernel body on
    ``pt_gated_delta_form_total{primitive, form}``."""
    from paddle_tpu.observability import metrics as obs

    obs.counter(
        "pt_gated_delta_form_total",
        "Trace-time choices of the delta-rule kernels' bodies: the chunk "
        "kernel's sub-chunk length (sub<N>), the step kernel's heads a "
        "lane tile (heads<N>); _vk<r> behind either where r value heads "
        "read one key head", labels=("primitive", "form"),
    ).labels(primitive=primitive, form=form).inc()


# ---------------------------------------------------------------------------
# the recurrence, token by token: XLA reference forms
# ---------------------------------------------------------------------------


def _rule(s, q, k, v, g, beta):
    """One token of every head: s [H, d_k, d_v], q, k [H, d_k], v
    [H, d_v], beta [H], g [H] (a decay a head) or [H, d_k] (a decay a
    key channel, on the state's rows) -> (new s, o [H, d_v])."""
    alpha = jnp.exp(g)
    s = (alpha[:, None, None] if g.ndim == 1 else alpha[:, :, None]) * s
    r = jnp.einsum("hkv,hk->hv", s, k, precision=_HIGHEST)
    u = beta[:, None] * (v - r)
    s = s + k[:, :, None] * u[:, None, :]
    return s, jnp.einsum("hkv,hk->hv", s, q, precision=_HIGHEST)


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


def gated_delta_chunk_reference(q, k, v, g, beta, state, block, fresh):
    """The recurrence over one sequence's tokens: q, k [C, H_k, d_k], v
    [C, H, d_v], beta [C, H], g [C, H] or [C, H, d_k]; ``state``
    [blocks, d_k, H * d_v],
    ``block`` the sequence's block (a scalar), ``fresh`` (a scalar bool)
    reads the block as zeros.  -> (o [C, H, d_v], the state tensor with
    the block written)."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    heads = v.shape[1]
    q, k = _per_value_head(q, heads), _per_value_head(k, heads)
    s0 = jnp.where(fresh, 0.0, _heads_first(state[block], heads))
    s, out = jax.lax.scan(lambda s, x: _rule(s, *x), s0,
                          (q, k, v, g, beta))
    return out, state.at[block].set(_stored(s))


def gated_delta_step_reference(q, k, v, g, beta, state, blocks):
    """One token a slot: q, k [B, H_k, d_k], v [B, H, d_v], beta [B, H], g
    [B, H] or [B, H, d_k], ``blocks`` [B] each slot's state block.  -> (o
    [B, H, d_v], the state tensor with the slots' blocks written; slots
    that share the trash block write it in turn)."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    heads = v.shape[1]
    q, k = _per_value_head(q, heads), _per_value_head(k, heads)
    s0 = jax.vmap(lambda b: _heads_first(b, heads))(state[blocks])
    s, out = jax.vmap(_rule)(s0, q, k, v, g, beta)
    return out, state.at[blocks].set(jax.vmap(_stored)(s))


# ---------------------------------------------------------------------------
# the chunk kernel
# ---------------------------------------------------------------------------


def _unit_lower_inverse(a):
    """(I + a)^-1 of a strictly lower-triangular a [c, c], c = BASE * 2^n:
    the BASE x BASE diagonal blocks by the finite product (I + N)(I +
    N^2)(I + N^4), N = -a's block (N^8 = 0, so the product IS the
    inverse and no power outgrows 2^4 x 70 times its entries' bound),
    then blocks merged pairwise, [[T1, 0], [-T2 A21 T1, T2]]: two
    products a level."""
    c = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def same(size):
        bits = size.bit_length() - 1
        return (row >> bits) == (col >> bits)

    n = jnp.where(same(BASE), -a, 0.0)
    t = jnp.where(row == col, 1.0, 0.0) + n
    for _ in range(2):                       # (I + N)(I + N^2)(I + N^4)
        n = _mm(n, n)
        t = t + _mm(t, n)
    size = BASE
    while size < c:
        off = jnp.where(same(2 * size) & ~same(size), a, 0.0)
        t = t - _mm(_mm(t, off), t)
        size *= 2
    return t


def _chunk_math(s, q, k, kb, kdt, vb, eg, elast, d):
    """One sub-chunk of one head: s [d_k, d_v] the state before it; q, k,
    kb = beta k [c, d_k]; kdt = (e^(gamma_c - gamma) k)^T [d_k, c]; vb =
    beta v [c, d_v]; eg = e^gamma [c, 1]; elast = e^gamma_c [1, 1]; d
    [c, c] = e^(gamma_t - gamma_j) for j <= t, else 0.  -> (o [c, d_v],
    the state after it)."""
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    a = jnp.where(row > col, _mm_t(kb, k) * d, 0.0)
    u = _mm(_unit_lower_inverse(a), vb - eg * _mm(kb, s))
    o = eg * _mm(q, s) + _mm(_mm_t(q, k) * d, u)
    return o, elast * s + _mm(kdt, u)


def _chunk_kernel(q_ref, k_ref, kb_ref, kdt_ref, vb_ref, eg_ref, el_ref,
                  d_ref, s0_ref, o_ref, so_ref, s_ref, *, n_sub):
    from jax.experimental import pallas as pl

    n = pl.program_id(1)

    @pl.when(n == 0)
    def _load():
        s_ref[...] = s0_ref[0]

    o, s = _chunk_math(s_ref[...], q_ref[0], k_ref[0], kb_ref[0],
                       kdt_ref[0, 0], vb_ref[0], eg_ref[0], el_ref[0, 0],
                       d_ref[0, 0])
    o_ref[0] = o
    s_ref[...] = s

    @pl.when(n == n_sub - 1)
    def _store():
        so_ref[0] = s


def _chunk_operands(q, k, v, g, beta, sub):
    """The kernel's operands from the chunk's [C, H, .] inputs, heads
    first and by sub-chunk; every exponent is <= 0.  q and k stay at
    their own H_k heads (the launch finds a value head's at h // r); the
    products with beta and the decay are a value head's."""
    c, heads, _ = v.shape
    n = c // sub
    q, k, v = (x.transpose(1, 0, 2) for x in (q, k, v))     # [H, C, .]
    g, beta = g.T, beta.T                                   # [H, C]
    kv = _per_value_head(k, heads, axis=0)            # a value head's keys
    gamma = jnp.cumsum(g.reshape(heads, n, sub), axis=-1)   # [H, n, sub]
    diff = gamma[..., :, None] - gamma[..., None, :]        # t, j
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    d = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    eg = jnp.exp(gamma).reshape(heads, c, 1)
    elast = jnp.exp(gamma[..., -1])[..., None, None]        # [H, n, 1, 1]
    to_end = jnp.exp(gamma[..., -1:] - gamma).reshape(heads, c, 1)
    kdt = (kv * to_end).reshape(heads, n, sub, -1).transpose(0, 1, 3, 2)
    return (q, k, kv * beta[..., None], kdt, v * beta[..., None], eg, elast,
            d)


def _pallas_chunk(q, k, v, g, beta, s0, sub, interpret):
    """s0 [H, d_k, d_v] -> (o [C, H, d_v], s [H, d_k, d_v])."""
    c, heads_k, dk = q.shape
    heads, dv = v.shape[1:]
    r = heads // heads_k
    n = c // sub
    ops = _chunk_operands(q, k, v, g, beta, sub)

    def rows(width):
        return Block((1, sub, width), lambda h, i: (h, i, 0))

    # q and k of value head h: key head h // r's rows
    qk_rows = rows(dk) if r == 1 else Block(
        (1, sub, dk), lambda h, i: (h // r, i, 0))

    def tile(a, b):
        return Block((1, 1, a, b), lambda h, i: (h, i, 0, 0))

    whole = Block((1, dk, dv), lambda h, i: (h, 0, 0))
    spec = contract.make_spec(
        "gated_delta_chunk",
        grid=(heads, n),
        in_specs=[qk_rows, qk_rows, rows(dk), tile(dk, sub), rows(dv),
                  rows(1), tile(1, 1), tile(sub, sub), whole],
        out_specs=[rows(dv), whole],
        out_shape=[((heads, c, dv), jnp.float32),
                   ((heads, dk, dv), jnp.float32)],
        scratch=[Vmem((dk, dv), jnp.float32)],
        interpret=interpret,
    )
    o, s = contract.primitive_call(
        functools.partial(_chunk_kernel, n_sub=n), spec, *ops, s0)
    return o.transpose(1, 0, 2), s


def _run_chunk(op, pallas_chunk, form, q, k, v, g, beta, state, block, fresh,
               force):
    """What the chunk forms of the rule share (this file's and kda.py's):
    the dispatch, the tail padded to whole sub-chunks, the sequence's
    block read (as zeros where ``fresh``) and written back.
    ``pallas_chunk(q, k, v, g, beta, s0, sub, interpret)`` -> (o, s);
    ``form(sub)`` names the choice on ``pt_gated_delta_form_total``."""
    _check(op, q, k, v, state)
    mode, interpret = contract.resolve_mode(op, force)
    if mode != "pallas":
        return gated_delta_chunk_reference(q, k, v, g, beta, state, block,
                                           fresh)
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    c, heads = v.shape[0], v.shape[1]
    sub = SUB if c >= SUB else BASE
    pad = -c % sub
    if pad:  # beta = 0, g = 0: the tail leaves the state alone
        q, k, v, g, beta = (jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
                            for x in (q, k, v, g, beta))
    _book_form(op, form(sub) + _group_suffix(q, v))
    s0 = jnp.where(fresh, 0.0, _heads_first(state[block], heads))
    o, s = pallas_chunk(q, k, v, g, beta, s0, sub, interpret)
    return o[:c], state.at[block].set(_stored(s))


def gated_delta_chunk(q, k, v, g, beta, state, block, fresh, *, force=None):
    """The gated delta rule over one sequence's C tokens, all heads: q, k
    [C, H_k, d_k] (H_k = H, or H a multiple of it: value head h reads key
    head h // (H / H_k)), v [C, H, d_v], g, beta [C, H] -> (o [C, H, d_v]
    float32,
    the state tensor [blocks, d_k, H * d_v] with block ``block`` (a
    scalar) written).  ``fresh`` (a scalar bool): the block is read as
    zeros (a sequence's first chunk; nothing clears a block on the
    host).  Positions with ``beta = 0`` and ``g = 0`` leave the state as
    it was.

    force: None -> Pallas on TPU, XLA reference elsewhere; "pallas" ->
    Pallas (interpret mode off-TPU); "reference" -> XLA."""
    return _run_chunk("gated_delta_chunk", _pallas_chunk,
                      lambda sub: f"sub{sub}", q, k, v, g, beta, state, block,
                      fresh, force)


# ---------------------------------------------------------------------------
# the step kernel
# ---------------------------------------------------------------------------


def _heads_per_tile(heads, dk, dv, group=1):
    """Heads of one lane tile of the step kernel: the most whose state
    block stays under STEP_TILE_BYTES and whose d_v columns fill whole
    lane tiles (else every head: a block as wide as the tensor), in whole
    groups of the ``group`` value heads that read one key head."""
    fits = [n for n in range(group, heads + 1, group)
            if heads % n == 0 and (n * dv) % 128 == 0
            and 4 * dk * n * dv <= STEP_TILE_BYTES]
    return max(fits) if fits else heads


def _step_update(s, pick, q_ref, k_ref, kt_ref, v_ref, b_ref, o_ref, so_ref):
    """The token's update of one (slot, tile of heads) from its DECAYED
    state s [d_k, L]; ``pick`` [hp, L] 0 / 1 says which lanes read which
    row of q and k (a value head's d_v lanes; with r value heads a key
    head, the r d_v lanes of a key head's group)."""
    r = jnp.sum(_mm(k_ref[0, 0], s) * pick, axis=0, keepdims=True)
    u = b_ref[0] * (v_ref[0] - r)                      # [1, L]
    s = s + _mm(kt_ref[0, 0], pick) * u
    o_ref[0] = jnp.sum(_mm(q_ref[0, 0], s) * pick, axis=0, keepdims=True)
    so_ref[0] = s


def _step_kernel(blk_ref, q_ref, k_ref, kt_ref, v_ref, a_ref, b_ref, e_ref,
                 s_ref, o_ref, so_ref):
    del blk_ref  # read by the index maps
    pick = e_ref[...]
    _step_update(a_ref[0] * s_ref[0], pick, q_ref, k_ref, kt_ref, v_ref,
                 b_ref, o_ref, so_ref)


def _launch_step(op, kernel, decay, q, k, v, beta, state, blocks, interpret):
    """What the step forms of the rule share (this file's and kda.py's):
    heads by lane tile, the 0/1 pick matrix, the slot's block found
    through the scalar-prefetched index and rewritten where it lies.
    ``decay(by_tile, lanes_of, head_rows, lane_row)`` -> (the decay's
    operand, its block): the one operand the two kernels take in
    different shapes, the sixth of ``kernel``."""
    b, heads_k, dk = q.shape
    heads, dv = v.shape[1:]
    r = heads // heads_k
    hg = _heads_per_tile(heads, dk, dv, r)
    _book_form(op, f"heads{hg}" + _group_suffix(q, v))
    tiles, lanes = heads // hg, hg * dv
    hk = hg // r                             # rows of q and k a tile
    hp = -(-hk // 8) * 8
    pad = ((0, 0), (0, 0), (0, hp - hk), (0, 0))

    def by_tile(x):                        # [B, H_k, dk] -> [B, tiles, hp, dk]
        return jnp.pad(x.reshape(b, tiles, hk, dk), pad)

    qx = by_tile(q)
    kx = by_tile(k)

    def lanes_of(x):                                   # [B, H] -> [B, 1, H dv]
        return jnp.repeat(x, dv, axis=-1)[:, None, :]

    pick = (jnp.arange(lanes)[None, :] // (r * dv)
            == jnp.arange(hp)[:, None]).astype(jnp.float32)

    def head_rows(a, c):
        return Block((1, 1, a, c), lambda i, t, blk: (i, t, 0, 0))

    lane_row = Block((1, 1, lanes), lambda i, t, blk: (i, 0, t))
    block = Block((1, dk, lanes), lambda i, t, blk: (blk[i], 0, t))
    operands = [blocks.astype(jnp.int32), qx, kx, kx.transpose(0, 1, 3, 2),
                v.reshape(b, 1, heads * dv)]
    decay_operand, decay_block = decay(by_tile, lanes_of, head_rows, lane_row)
    operands += [decay_operand, lanes_of(beta), pick, state]
    spec = contract.make_spec(
        op,
        grid=(b, tiles),
        in_specs=[head_rows(hp, dk), head_rows(hp, dk), head_rows(dk, hp),
                  lane_row, decay_block, lane_row,
                  Block((hp, lanes), lambda i, t, blk: (0, 0)), block],
        out_specs=[lane_row, block],
        out_shape=[((b, 1, heads * dv), jnp.float32),
                   (tuple(state.shape), jnp.float32)],
        num_scalar_prefetch=1,
        # the state tensor (operand 8, the block index counted) IS the
        # second output: blocks are rewritten where they lie
        input_output_aliases={8: 1},
        interpret=interpret,
    )
    o, state = contract.primitive_call(kernel, spec, *operands)
    return o.reshape(b, heads, dv), state


def _pallas_step(q, k, v, g, beta, state, blocks, interpret):
    def decay(by_tile, lanes_of, head_rows, lane_row):
        return lanes_of(jnp.exp(g)), lane_row          # alpha a head's lanes

    return _launch_step("gated_delta_step", _step_kernel, decay, q, k, v,
                        beta, state, blocks, interpret)


def gated_delta_step(q, k, v, g, beta, state, blocks, *, force=None):
    """The gated delta rule for one token a slot: q, k [B, H_k, d_k] (H_k
    = H, or H a multiple of it), v [B, H, d_v], g, beta [B, H],
    ``blocks`` [B] int32 each slot's state
    block (inactive slots name the trash block 0) -> (o [B, H, d_v]
    float32, the state tensor with those blocks rewritten in place).

    force: None -> Pallas on TPU, XLA reference elsewhere; "pallas" ->
    Pallas (interpret mode off-TPU); "reference" -> XLA."""
    _check("gated_delta_step", q, k, v, state)
    mode, interpret = contract.resolve_mode("gated_delta_step", force)
    if mode != "pallas":
        return gated_delta_step_reference(q, k, v, g, beta, state, blocks)
    return _pallas_step(*_f32(q, k, v, g, beta), state, blocks, interpret)
