"""Paged attention — compat shim over kernels/primitives/paged.py.

The kernel moved onto the primitives contract (docs/KERNELS.md), which
also added the int8-pool form (``paged_attention_quant``) and frames
``q_start`` as the decode lane's ragged length vector.  This module
keeps the historical import surface — ``from paddle_tpu.kernels import
paged_attention`` and its internals — pointing at the migrated
implementation; new code should import ``paddle_tpu.kernels.primitives``
directly.
"""

from __future__ import annotations

from .primitives.paged import (  # noqa: F401
    NEG_INF, _paged_kernel, _pallas_paged, paged_attention,
    paged_attention_quant, paged_attention_quant_reference,
    paged_attention_reference,
)

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_quant", "paged_attention_quant_reference"]

