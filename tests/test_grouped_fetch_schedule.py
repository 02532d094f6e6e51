"""What the grouped product's launch fetches, read off the launch itself.

A BlockSpec's copy is issued outside the kernel's body, whenever an
operand's block index differs from the step before: a grid step whose
body `pl.when` skips still streams a `[tk, tn]` weight block if its index
map moves.  So the launch of `grouped_matmul` is captured as data (the
contract's `KernelSpec` and the scalar-prefetch operands, at the two
expert cells' real shapes: no weight is allocated and nothing runs), its
index maps are walked over the whole grid in plain Python, and the steps
at which each operand's index changes are counted.  A visit no group owns
must fetch nothing: the grid's visit extent is the live visits, and the
weight blocks fetched are the live visits' blocks and no other.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import primitives as prims
from paddle_tpu.kernels.primitives import grouped

# (rows, K, N, held experts): a decode step's and a chunk's pick rows of
# trinity-large-ep8 (64 picks pad to one row tile), glm-5-ep16 and
# kimi-vl-a3b-ep1 (up and down projections), benchmark/configs/
SHAPES = {
    "trinity-decode": (64, 3072, 3072, 32),
    "trinity-chunk": (2048, 3072, 3072, 32),
    "glm-decode-up": (128, 6144, 2048, 16),
    "glm-decode-down": (128, 2048, 6144, 16),
    "glm-chunk-up": (4096, 6144, 2048, 16),
    "glm-chunk-down": (4096, 2048, 6144, 16),
    "kimi-decode-up": (96, 2048, 1408, 64),
    "kimi-decode-down": (96, 1408, 2048, 64),
    "kimi-chunk-up": (3072, 2048, 1408, 64),
    "kimi-chunk-down": (3072, 1408, 2048, 64),
}
# the widths whose first divisor is 1024 on both sides: their launches,
# and so their compiled kernels, are what they were before the block was
# sized from the shape
DIVIDE_BY_1024 = [s for s, (_, k, n, _) in SHAPES.items()
                  if k % 1024 == 0 and n % 1024 == 0]


def _sizes(kind, rows, groups):
    sizes = np.zeros(groups, np.int32)
    if kind == "sparse":            # a decode step's: a row or two each
        sizes[[1, groups // 3, groups // 2, groups - 2]] = [1, 2, 1, 2]
    elif kind == "dense":           # a chunk's: every expert, 1/8 of the picks
        sizes[:] = max(rows // 8 // groups, 1)
        sizes[groups // 2] += 3     # off the row tiles' edges
    elif kind == "one-expert":      # every row on one expert
        sizes[groups // 2] = rows
    return sizes


def _launch(monkeypatch, rows, k, n, groups, sizes):
    """The KernelSpec and scalar-prefetch operands of one call."""
    seen = {}

    def capture(kernel, spec, *operands):
        seen["spec"] = spec
        seen["scalars"] = [np.asarray(o)
                           for o in operands[:spec.num_scalar_prefetch]]
        (shape, dtype), = spec.out_shape
        return jnp.zeros(shape, dtype)

    monkeypatch.setattr(grouped.contract, "primitive_call", capture)
    # the launch reads the weights' shape and dtype only
    prims.grouped_matmul(
        jnp.zeros((rows, k), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16),
        jnp.asarray(sizes), force="pallas")
    return seen["spec"], seen["scalars"]


def _walk(spec, scalars):
    """(grid, {operand: steps at which its block index changed})."""
    grid = [int(g) for g in spec.grid]
    blocks = dict(zip(("lhs", "rhs", "out"), spec.in_specs + spec.out_specs))
    fetches, last = dict.fromkeys(blocks, 0), {}
    for ni in range(grid[0]):
        for vi in range(grid[1]):
            for ki in range(grid[2]):
                for name, block in blocks.items():
                    index = tuple(int(i) for i in block.index_map(
                        ni, vi, ki, *scalars))
                    fetches[name] += index != last.get(name)
                    last[name] = index
    return grid, fetches


@pytest.mark.parametrize("kind", ["sparse", "dense", "one-expert",
                                  "all-empty"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_visit_no_group_owns_fetches_nothing(monkeypatch, shape, kind):
    rows, k, n, groups = SHAPES[shape]
    sizes = _sizes(kind, rows, groups)
    spec, scalars = _launch(monkeypatch, rows, k, n, groups, sizes)
    (tiles_n, visits, tiles_k), fetches = _walk(spec, scalars)
    tm = spec.in_specs[0].shape[0]
    live = int(scalars[3][0])
    # what the schedule needs, counted here from the sizes: one visit a
    # (row tile, group) pair that shares rows
    ends = np.cumsum(sizes)
    want = sum(int(-(-e // tm) - (e - s) // tm)
               for e, s in zip(ends, sizes) if s)
    assert live == want
    # an all-empty call keeps one (dead) visit: a grid has no empty axis
    assert visits == max(live, 1)
    _, tk, tn = spec.in_specs[1].shape
    assert tk * tn * 2 >= 1 << 20
    # what the launch holds in VMEM, under Mosaic's default scoped limit
    # (the contract passes none): two buffers of every block (bfloat16 in,
    # float32 out) and the scratch
    blocks = [(b.shape, 2) for b in spec.in_specs] + [
        (b.shape, 4) for b in spec.out_specs]
    held = sum(2 * np.prod(shape) * size for shape, size in blocks) + sum(
        np.prod(v.shape) * np.dtype(v.dtype).itemsize for v in spec.scratch)
    assert held < 16 << 20
    if shape in DIVIDE_BY_1024:
        assert (tk, tn) == (1024, 1024)
        assert tiles_k > 1 and tiles_n > 1
    if tiles_k > 1:
        assert fetches["rhs"] == max(live, 1) * tiles_k * tiles_n
        assert fetches["lhs"] <= fetches["rhs"]
    else:
        # the block holds K whole: its index does not move between two
        # visits of one group, so an expert whose rows straddle a row
        # tile is read once a tile of N, not once a visit
        runs = max(int(np.count_nonzero(sizes)), 1)
        assert fetches["rhs"] == runs * tiles_n
        if kind == "dense" and rows > tm:
            assert live > runs
    assert fetches["out"] <= max(live, 1) * tiles_n
