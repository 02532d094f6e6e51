"""The uniform block/tile/VMEM contract every Pallas primitive rides.

Tensor Processing Primitives (arXiv:2104.05755) argues for a SMALL set
of composable primitives behind one audited dispatch surface instead of
per-op hand-rolled kernels; this module is that surface for the
paddle_tpu kernel layer.  Every primitive in ``kernels/primitives/``
describes its launch as plain data — a :class:`KernelSpec` of grid,
block specs, VMEM scratch and output shapes — and hands it to
:func:`primitive_call`, the ONE place in the library that touches
``pl.pallas_call`` / ``pltpu`` (tools/lint_kernels.py enforces the
boundary; a deliberate site elsewhere carries ``# kernel: allow``).

What the contract buys:

- **One launch idiom.**  Block specs are ``Block(shape, index_map)``
  tuples and scratch is ``Vmem(shape, dtype)`` (``Smem`` and ``DmaSem``
  for a kernel that issues its own copies) — pure data, no pallas
  import needed to BUILD a spec, so specs can be constructed (and
  tested) without a kernel backend present at all.
- **Interpret mode.**  ``interpret=True`` runs the same kernel
  through the Pallas interpreter on CPU — the numerics parity lane.
  It checks kernel logic, not Mosaic: tests/test_mosaic_aot.py compiles
  every primitive for a v5e topology without a chip.
- **Scalar prefetch.**  ``num_scalar_prefetch > 0`` lowers through
  ``pltpu.PrefetchScalarGridSpec`` so index maps can read small int32
  operands (page tables, per-row lengths) — the mechanism behind the
  paged and ragged attention forms.
- **Tile-size autotune.**  Primitives resolve their block sizes through
  ``autotune.tile_for`` (measured-or-pinned table keyed by shape
  signature) instead of baking constants — see autotune.py.

Mosaic tiling facts the specs must respect (the guide's table): the
minor-most block dim wants multiples of 128 (lanes), the second-minor 8
for fp32 (sublanes; 32 for int8); rank-2 operands ride as rank-3 with a
literal leading 1.  Running-state scratch is kept 2-D ``(rows, 128)``
with all lanes equal — the layout Mosaic accepts for reduction state.
"""

from __future__ import annotations

import os
from collections import namedtuple

# A block spec as data: `shape` is the per-step block shape, `index_map`
# maps grid indices (plus one ref per scalar-prefetch operand) to block
# coordinates.  `shape=None` means "whole operand in VMEM".
Block = namedtuple("Block", ("shape", "index_map"))

# A VMEM scratch allocation as data.
Vmem = namedtuple("Vmem", ("shape", "dtype"))

# What a kernel that copies from an unblocked operand itself needs
# beside VMEM (paged.py's decode row): scalars that outlive a grid step,
# in SMEM, and an array of DMA semaphores, one per copy stream in flight.
Smem = namedtuple("Smem", ("shape", "dtype"))
DmaSem = namedtuple("DmaSem", ("shape",))

# One primitive launch as data.  `out_shape` entries are (shape, dtype)
# pairs; `in_specs`/`out_specs` are Block tuples (one out entry per
# out_shape entry).  A single-element out list returns a single array.
# ``input_output_aliases``: {operand index (scalar-prefetch operands
# counted): output index} — the output IS that operand's buffer, written
# where it lies (a state tensor a kernel updates in place).
KernelSpec = namedtuple(
    "KernelSpec",
    ("name", "grid", "in_specs", "out_specs", "out_shape", "scratch",
     "num_scalar_prefetch", "interpret", "input_output_aliases"),
    defaults=((),),
)


def make_spec(name, grid, in_specs, out_specs, out_shape, scratch=(),
              num_scalar_prefetch=0, interpret=False,
              input_output_aliases=None):
    """Build a :class:`KernelSpec` (keyword-friendly constructor)."""
    return KernelSpec(name, tuple(grid), tuple(in_specs),
                      tuple(out_specs), tuple(out_shape), tuple(scratch),
                      int(num_scalar_prefetch), bool(interpret),
                      tuple(sorted((input_output_aliases or {}).items())))


def primitive_call(kernel, spec, *operands):
    """Launch ``kernel`` under ``spec`` — the library's one raw
    ``pl.pallas_call`` site.

    Scalar-prefetch operands (the first ``spec.num_scalar_prefetch``
    of ``operands``) are passed positionally before the tensor
    operands, exactly as ``PrefetchScalarGridSpec`` expects."""
    import jax
    from jax.experimental import pallas as pl          # kernel: allow
    from jax.experimental.pallas import tpu as pltpu   # kernel: allow

    def block(b):
        if b.shape is None:
            return pl.BlockSpec(memory_space=pl.ANY)
        return pl.BlockSpec(tuple(b.shape), b.index_map)

    in_specs = [block(b) for b in spec.in_specs]
    out_specs = [block(b) for b in spec.out_specs]
    out_shape = [jax.ShapeDtypeStruct(tuple(s), d)
                 for s, d in spec.out_shape]
    def scratch_shape(v):
        if isinstance(v, DmaSem):
            return pltpu.SemaphoreType.DMA(tuple(v.shape))
        space = pltpu.SMEM if isinstance(v, Smem) else pltpu.VMEM
        return space(tuple(v.shape), v.dtype)

    scratch = [scratch_shape(v) for v in spec.scratch]
    single = len(out_specs) == 1
    aliases = dict(spec.input_output_aliases)

    if spec.num_scalar_prefetch:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=spec.num_scalar_prefetch,
            grid=spec.grid,
            in_specs=in_specs,
            out_specs=out_specs[0] if single else out_specs,
            scratch_shapes=scratch,
        )
        return pl.pallas_call(                         # kernel: allow
            kernel, grid_spec=grid_spec,
            out_shape=out_shape[0] if single else out_shape,
            interpret=spec.interpret,
            name=spec.name,
            input_output_aliases=aliases,
        )(*operands)
    return pl.pallas_call(                             # kernel: allow
        kernel,
        grid=spec.grid,
        in_specs=in_specs,
        out_specs=out_specs[0] if single else out_specs,
        out_shape=out_shape[0] if single else out_shape,
        scratch_shapes=scratch,
        interpret=spec.interpret,
        name=spec.name,
        input_output_aliases=aliases,
    )(*operands)


# ---------------------------------------------------------------------------
# shared dispatch-mode resolution: every primitive decides pallas vs
# reference here, and every decision is booked so a run can REPORT which
# form each primitive took instead of assuming it (chip_smoke.py)
# ---------------------------------------------------------------------------


def is_tpu_platform():
    """Whether traces lower for a TPU (where compiled Mosaic engages):
    the default backend, or the ``platform_utils.lowering_for`` target
    of a chip-free AOT compile."""
    from paddle_tpu.fluid.platform_utils import is_tpu

    return is_tpu()


def book_dispatch(primitive, mode):
    """Count one trace-time dispatch decision on
    ``pt_kernel_dispatch_total{primitive, mode}`` (mode: ``pallas`` |
    ``interpret`` | ``reference``)."""
    from paddle_tpu.observability import metrics as obs

    obs.counter(
        "pt_kernel_dispatch_total",
        "Trace-time kernel dispatch decisions by primitive and the "
        "implementation it resolved to (pallas = compiled Mosaic, "
        "interpret = Pallas interpreter, reference = XLA)",
        labels=("primitive", "mode"),
    ).labels(primitive=primitive, mode=mode).inc()


def resolve_mode(primitive, force=None, *, force_env=None):
    """The shared dispatch decision: returns ``(mode, interpret)`` where
    mode is "pallas" or "reference".

    force: None → Pallas on TPU, XLA reference elsewhere; "pallas" →
    Pallas (interpret mode off-TPU, the CPU parity lane); "reference"
    → XLA.  ``force_env`` names an env var that engages the kernel
    off-TPU too (the blockwise structure survives the interpreter —
    what lets pass-layer cost attribution measure kernel-boundary
    bytes on CPU)."""
    on_tpu = is_tpu_platform()
    mode = force
    if mode is None:
        if on_tpu:
            mode = "pallas"
        elif force_env and os.environ.get(force_env, "") not in ("", "0"):
            mode = "pallas"
        else:
            mode = "reference"
    interpret = mode == "pallas" and not on_tpu
    book_dispatch(primitive, "interpret" if interpret else mode)
    return mode, interpret
