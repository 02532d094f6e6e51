"""Flash attention — compat shim over kernels/primitives/flash.py.

The kernel moved onto the primitives contract (docs/KERNELS.md): one
audited pallas_call site, specs as data, tile sizes through the
autotune table.  This module keeps the historical import surface —
``from paddle_tpu.kernels import flash_attention`` and its internals —
pointing at the migrated implementation; new code should import
``paddle_tpu.kernels.primitives`` directly.
"""

from __future__ import annotations

from .primitives.flash import (  # noqa: F401
    BLOCK_CANDIDATES, DEFAULT_BLOCK, NEG_INF, _bwd_dkv_kernel,
    _bwd_dq_kernel, _causal_mask, _ceil_to, _flash, _fwd_kernel,
    _pallas_bwd, _pallas_fwd, attention_reference, flash_attention,
)

__all__ = ["flash_attention", "attention_reference", "DEFAULT_BLOCK",
           "NEG_INF"]

