"""Bidirectional attention inside one image: every patch attends every
patch of its own image (a vision tower's block; models/kimi_vl.py).

flash.py is the training kernel: square 128 blocks and float32 operands,
so an image of 6144 patches is 36 864 grid steps a layer of two small
float32 products each.  This one is forward-only and takes the operands
as the serving lane stores them (bfloat16 on the MXU, float32 sums),
512 queries against 1024 keys a step, no bias operand and no causal
mask; keys past the image's length (the padding to whole blocks) are
masked by position.

  q, k, v  [H, N, d]  ->  [H, N, d] float32

On the chip d must be a multiple of 128 (a head's lanes are one block):
the caller pads a narrower head with zeros and passes its own
``sm_scale`` (models/kimi_vl.py: heads of 72).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import contract
from .contract import Block, Vmem
from .paged import NEG_INF, _init_state, _mxu, _online_softmax_step, \
    _p_dtype

__all__ = ["vit_attention", "vit_attention_reference"]

QUERY_BLOCK, KEY_BLOCK = 512, 1024


def vit_attention_reference(q, k, v, sm_scale):
    """Materialising XLA form: CPU fallback and numerics oracle."""
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, bk, n,
            n_k, sm_scale):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale     # [bq, bk]
    if n % bk:  # the last key block runs past the image
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < n, s, NEG_INF)
    _online_softmax_step(s, v_ref[0], acc_ref, m_ref, l_ref,
                         p_dtype=_p_dtype(v_ref.dtype))

    @pl.when(ki == n_k - 1)
    def _done():
        l = l_ref[...]
        o_ref[0] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)[:, :1]


def _blocks(n):
    """(query block, key block, padded length) for an image of n patches."""
    if n <= QUERY_BLOCK:
        n8 = -(-n // 8) * 8
        return n8, n8, n8
    bk = KEY_BLOCK if n >= KEY_BLOCK else QUERY_BLOCK
    return QUERY_BLOCK, bk, -(-n // bk) * bk


def _pallas(q, k, v, sm_scale, interpret):
    heads, n, d = q.shape
    bq, bk, n_pad = _blocks(n)
    q, k, v = (jnp.pad(_mxu(x), ((0, 0), (0, n_pad - n), (0, 0)))
               for x in (q, k, v))
    spec = contract.make_spec(
        "vit_attention",
        grid=(heads, n_pad // bq, n_pad // bk),
        in_specs=[Block((1, bq, d), lambda h, qi, ki: (h, qi, 0)),
                  Block((1, bk, d), lambda h, qi, ki: (h, ki, 0)),
                  Block((1, bk, d), lambda h, qi, ki: (h, ki, 0))],
        out_specs=[Block((1, bq, d), lambda h, qi, ki: (h, qi, 0))],
        out_shape=[((heads, n_pad, d), jnp.float32)],
        scratch=[Vmem((bq, d), jnp.float32), Vmem((bq, 128), jnp.float32),
                 Vmem((bq, 128), jnp.float32)],
        interpret=interpret,
    )
    out = contract.primitive_call(
        functools.partial(_kernel, bk=bk, n=n, n_k=n_pad // bk,
                          sm_scale=sm_scale),
        spec, q, k, v)
    return out[:, :n]


def vit_attention(q, k, v, *, sm_scale, force=None):
    """softmax(q k^T * sm_scale) v over [H, N, d], every key visible to
    every query -> [H, N, d] float32.

    force: None -> Pallas on TPU, XLA reference elsewhere; "pallas" ->
    Pallas (interpret mode off-TPU); "reference" -> XLA."""
    mode, interpret = contract.resolve_mode("vit_attention", force)
    if mode == "pallas":
        return _pallas(q, k, v, float(sm_scale), interpret)
    return vit_attention_reference(q, k, v, float(sm_scale))
