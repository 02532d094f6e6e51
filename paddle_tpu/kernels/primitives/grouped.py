"""Grouped matrix product — rows sorted by group, one weight matrix a
group: ``out[rows of group g] = lhs[rows of group g] @ rhs[g]``.

The expert layer's product (ops/mla_ops.py ``moe_ffn_held``): the picks
that land on the experts held here are sorted by expert, ``group_sizes``
says how many each expert got, and only the row tiles a group touches
are computed — an expert nobody picked is neither computed nor read,
and no expert runs on a token that did not pick it.

Shapes:
  lhs          [M, K]    rows sorted by group; rows past
                         sum(group_sizes) belong to no group
  rhs          [G, K, N]
  group_sizes  [G] int32
  out          [M, N]    rows of no group read 0

Two implementations (the shared resolve_mode dispatch):

- **XLA reference** (CPU fallback and oracle): ``lax.ragged_dot``.
- **Pallas kernel**: the megablox schedule (``make_group_metadata`` of
  ``jax.experimental.pallas.ops.tpu.megablox``): one grid visit per
  (row tile, group) pair that shares rows, at most ``M/tm + G - 1`` of
  them, and the grid's visit axis ends at the visits this call has (a
  traced extent, as megablox's own): a call streams the weights of the
  experts it touched, each once a row tile, and nothing else.  The
  group's id rides as scalar prefetch so the weight block's index_map
  resolves the expert, and rows of a tile that belong to another group
  are masked on the store.  Launched through the contract under its own
  name, so a device trace shows ``grouped_matmul``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import contract
from .contract import Block, Vmem

__all__ = ["grouped_matmul", "grouped_matmul_reference"]

# A decode step gives an expert a row or two, yet a smaller row tile buys
# nothing: 128 x 1024 x 1024 is 1.4 us on a v5e's MXU, under the 2.6 us
# its 2 MiB weight block takes to arrive, so a step is bound by the
# block's bytes and the padding rows ride for free
ROW_TILE = 128
# (tk, tn), from the shape and the weights' item size.  A grid step costs
# ~0.3 us before it moves a byte, which is what a 256-KiB block takes to
# arrive at 819 GB/s: under a megabyte a step streams at about half of
# the bandwidth (Kimi-VL's 1408 = 11 x 128 had no divisor here but 128:
# 54% of its bytes' roofline), and blocks of 1 to 4.5 MiB read within 5%
# of each other at ~750 GB/s (3072 x 3072; 6144 x 2048 and 2048 x 6144:
# PERF.md, PR 32).  So a side's first divisor below stands wherever the
# block it gives is a megabyte, and elsewhere the block is the largest
# that fits: each side a multiple of 128 that divides it or the side
# whole, which a block's last dimensions may always be
_K_TILES = (1024, 512, 256, 128)
_N_TILES = (1024, 512, 256, 128)
_MIN_BLOCK_BYTES = 1 << 20
# Mosaic's default scoped limit is 16 MiB and the launch passes none of
# its own: the rest is room for what the compiler keeps beside the blocks
_VMEM_BUDGET = 12 << 20


def _tile(size, choices):
    for c in choices:
        if size % c == 0:
            return c
    return size


def _sides(size):
    return [t for t in range(128, size, 128) if size % t == 0] + [size]


def _vmem_bytes(tk, tn, itemsize):
    """Two buffers each of the weight block, the [tm, tk] row block and
    the [tm, tn] float32 output block, and the accumulator."""
    return (2 * tk * tn * itemsize + 2 * ROW_TILE * tk * itemsize
            + 3 * ROW_TILE * tn * 4)


def _weight_block(k, n, itemsize):
    tk, tn = _tile(k, _K_TILES), _tile(n, _N_TILES)
    if tk * tn * itemsize >= _MIN_BLOCK_BYTES:
        return tk, tn
    fits = [(a, b) for a in _sides(k) for b in _sides(n)
            if _vmem_bytes(a, b, itemsize) <= _VMEM_BUDGET]
    # of two blocks as large, the one with fewer passes over the
    # accumulator; a matrix no block of which fits fails in Mosaic as it
    # did
    return max(fits, key=lambda s: (s[0] * s[1], s[0]), default=(tk, tn))


def _zero_rows_of_no_group(out, group_sizes):
    rows = jnp.arange(out.shape[0])[:, None]
    return jnp.where(rows < jnp.sum(group_sizes), out, 0.0)


def grouped_matmul_reference(lhs, rhs, group_sizes):
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return _zero_rows_of_no_group(out, group_sizes)


def _gmm_kernel(offsets_ref, group_ids_ref, tile_ids_ref, visits_ref,
                lhs_ref, rhs_ref, out_ref, acc_ref, *, tm, tiles_k):
    from jax.experimental import pallas as pl

    visit, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(visit < visits_ref[0])
    def _visit():
        @pl.when(ki == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(ki == tiles_k - 1)
        def _store():
            group = group_ids_ref[visit]
            rows = tile_ids_ref[visit] * tm + jax.lax.broadcasted_iota(
                jnp.int32, acc_ref.shape, 0)
            mine = (rows >= offsets_ref[group]) & (
                rows < offsets_ref[group + 1])
            # the tile stays resident across the visits that share it:
            # each writes its own group's rows
            out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...])


def _pallas_gmm(lhs, rhs, group_sizes, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    m, k = lhs.shape
    groups, _, n = rhs.shape
    tm = ROW_TILE
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tk, tn = _weight_block(k, n, jnp.dtype(rhs.dtype).itemsize)
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m + pad, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=groups,
        visit_empty_groups=False)

    def lhs_map(ni, vi, ki, off, gid, tid, nv):
        return (tid[vi], ki)

    def rhs_map(ni, vi, ki, off, gid, tid, nv):
        return (gid[vi], ki, ni)

    def out_map(ni, vi, ki, off, gid, tid, nv):
        return (tid[vi], ni)

    # the schedule holds M/tm + G - 1 visits and this call has `visits`
    # of them.  A block's copy is issued outside the body, whenever its
    # index moves, so a step the body skips still streams a weight block:
    # the grid ends at the live visits (a traced extent, as megablox
    # sizes its own).  A call with no row keeps one visit, which the body
    # skips, so that no axis is ever empty
    spec = contract.make_spec(
        "grouped_matmul",
        grid=(n // tn, jnp.maximum(visits, 1), k // tk),
        in_specs=[Block((tm, tk), lhs_map), Block((1, tk, tn), rhs_map)],
        out_specs=[Block((tm, tn), out_map)],
        out_shape=[((m + pad, n), jnp.float32)],
        scratch=[Vmem((tm, tn), jnp.float32)],
        num_scalar_prefetch=4,
        interpret=interpret,
    )
    out = contract.primitive_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=k // tk),
        spec, offsets, group_ids, tile_ids,
        jnp.reshape(visits, (1,)).astype(jnp.int32),
        lhs.astype(rhs.dtype), rhs)
    # a tile no group touched was never written
    return _zero_rows_of_no_group(out, group_sizes)[:m]


def grouped_matmul(lhs, rhs, group_sizes, *, force=None):
    """``lhs`` [M, K] (rows sorted by group) times ``rhs`` [G, K, N] by
    group → [M, N] float32; rows past ``sum(group_sizes)`` read 0.

    force: None → Pallas on TPU, XLA reference elsewhere; "pallas" →
    Pallas (interpret mode off-TPU); "reference" → XLA."""
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(
            f"grouped_matmul: lhs {tuple(lhs.shape)} and rhs "
            f"{tuple(rhs.shape)} are not [M, K] and [G, K, N]")
    mode, interpret = contract.resolve_mode("grouped_matmul", force)
    if mode == "pallas":
        return _pallas_gmm(lhs, rhs, group_sizes, interpret)
    return grouped_matmul_reference(lhs.astype(rhs.dtype), rhs,
                                    group_sizes)
