"""The audited kernel-primitives layer (docs/KERNELS.md).

One uniform block/tile/VMEM contract (contract.py), one tile-table
autotune hook (autotune.py), and the primitives every fused op and
serving lane lowers through:

  flash      dense attention, custom VJP (training + serving)
  ragged     variable-length dense attention (serving prefill form)
  paged      page-table attention, fp32 and int8 pools (decode form)
  int8       dual-int8 storage quantization (weights + KV cache)
  dsa        learned sparse attention over a paged latent cache:
             indexer scores, exact top-k selection, latent attention
  grouped    grouped matrix product over rows sorted by group (experts)
  mla        dense latent attention over a paged latent cache: latent
             space for the decode step, head space for the prefill chunk
  vit        bidirectional attention inside one image (a vision tower)
  gdn        the gated delta rule over a per-sequence state: a chunked
             form for the prefill chunk, an in-place step for decode
  kda        the same rule with a decay a key channel (Kimi Delta
             Attention), over gdn's state layout: its chunk and step

Raw ``pl.pallas_call`` / ``pltpu`` outside this package is a lint
error (tools/lint_kernels.py) unless marked ``# kernel: allow``.
The legacy modules ``kernels/flash_attention.py`` and
``kernels/paged_attention.py`` re-export from here; the fused-update
and fused-bias-act kernels launch through the contract in place.
"""

from . import autotune, contract  # noqa: F401
from .contract import (  # noqa: F401
    Block, KernelSpec, Vmem, is_tpu_platform, make_spec, primitive_call,
    resolve_mode,
)
from .autotune import (  # noqa: F401
    clear_cache, measure_candidates, shape_signature, tile_for,
)
from .flash import (  # noqa: F401
    DEFAULT_BLOCK, attention_reference, flash_attention,
)
from .int8 import (  # noqa: F401
    book_bytes_saved, bytes_saved, dequantize_lastdim, dequantize_weight,
    dual_int8_bytes, quantize_lastdim, quantize_weight,
)
from .dsa import (  # noqa: F401
    dsa_indexer_scores, dsa_indexer_scores_reference, dsa_topk_select,
    dsa_topk_select_reference, sparse_mla_attention,
    sparse_mla_attention_reference,
)
from .gdn import (  # noqa: F401
    gated_delta_chunk, gated_delta_chunk_reference, gated_delta_step,
    gated_delta_step_reference,
)
from .kda import kda_chunk, kda_step  # noqa: F401
from .grouped import (  # noqa: F401
    grouped_matmul, grouped_matmul_reference,
)
from .mla import (  # noqa: F401
    mla_chunk_attention, mla_chunk_attention_reference,
    paged_mla_attention, paged_mla_attention_reference,
)
from .paged import (  # noqa: F401
    paged_attention, paged_attention_quant,
    paged_attention_quant_reference, paged_attention_reference,
)
from .ragged import (  # noqa: F401
    ragged_attention, ragged_attention_reference,
)
from .vit import vit_attention, vit_attention_reference  # noqa: F401

__all__ = [
    "Block", "KernelSpec", "Vmem", "make_spec", "primitive_call",
    "resolve_mode", "is_tpu_platform",
    "shape_signature", "tile_for", "clear_cache", "measure_candidates",
    "DEFAULT_BLOCK", "flash_attention", "attention_reference",
    "ragged_attention", "ragged_attention_reference",
    "paged_attention", "paged_attention_reference",
    "paged_attention_quant", "paged_attention_quant_reference",
    "quantize_lastdim", "dequantize_lastdim", "quantize_weight",
    "dequantize_weight", "dual_int8_bytes", "bytes_saved",
    "book_bytes_saved",
    "dsa_indexer_scores", "dsa_indexer_scores_reference",
    "dsa_topk_select", "dsa_topk_select_reference",
    "sparse_mla_attention", "sparse_mla_attention_reference",
    "grouped_matmul", "grouped_matmul_reference",
    "paged_mla_attention", "paged_mla_attention_reference",
    "mla_chunk_attention", "mla_chunk_attention_reference",
    "vit_attention", "vit_attention_reference",
    "gated_delta_chunk", "gated_delta_chunk_reference",
    "gated_delta_step", "gated_delta_step_reference",
    "kda_chunk", "kda_step",
]
