"""The platform a trace lowers for.

Op lowerings and kernel dispatch ask one question — "is this going to a
TPU?" — and the answer is the default JAX backend, except inside
:func:`lowering_for`, where a host without a chip AOT-compiles against a
TPU topology (tests/test_mosaic_aot.py, README "Chip-free AOT").
(Reference analog: platform/device_context.cc knows its place from the
Place argument; here the platform is ambient jax state.)
"""

from __future__ import annotations

import contextlib
import contextvars

import jax

_lowering_target = contextvars.ContextVar("pt_lowering_target", default=None)


def default_platform():
    """Platform name lowerings decide by: the :func:`lowering_for`
    target when one is active, else ``jax.default_backend()``."""
    return _lowering_target.get() or jax.default_backend()


def is_tpu():
    return default_platform() == "tpu"


@contextlib.contextmanager
def lowering_for(platform):
    """Trace as if the default backend were ``platform``: kernel
    dispatch picks compiled (non-interpret) Pallas and the RNG picks the
    TPU implementation, so ``jit(...).lower(...).compile()`` against
    ``jax.experimental.topologies`` devices builds exactly what the chip
    would run."""
    token = _lowering_target.set(platform)
    try:
        yield
    finally:
        _lowering_target.reset(token)
