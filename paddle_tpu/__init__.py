"""paddle_tpu: a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid (~1.5), re-designed around JAX/XLA/Pallas.

Layer map (mirrors SURVEY.md §1, TPU-first):
  fluid/      Fluid-compatible Python front end (Program/Block/Operator,
              layers, optimizers, backward) — graphs, not eager tensors
  ops/        op lowerings: op type → pure JAX function (whole-block XLA
              compilation replaces per-op kernel dispatch)
  parallel/   device meshes, collective transpilers, fleet API (XLA
              collectives over ICI/DCN replace NCCL rings)
  models/     flagship model zoo (MLP, ResNet, BERT/Transformer)
  kernels/    Pallas TPU kernels for ops XLA fuses poorly
  observability/  unified telemetry: metrics registry, /metricsz
              exposition, JSONL events, cross-process tracing
  serving/    production serving lane: continuous batching engine,
              multi-model warm executable cache, /servez SLO surfaces
"""

__version__ = "0.1.0"

from . import fluid  # noqa: F401
from . import reader  # noqa: F401
from . import dataset  # noqa: F401
from . import inference  # noqa: F401
from . import compat  # noqa: F401
from . import distributed  # noqa: F401
from . import observability  # noqa: F401
from . import serving  # noqa: F401
from . import proto  # noqa: F401
from . import utils  # noqa: F401
from .reader import batch  # noqa: F401

# paddle.* top-level conveniences (subset; the reference re-exports fluid too)
from .fluid import (  # noqa: F401
    CPUPlace, CUDAPlace, TPUPlace, Executor, Program, program_guard,
)
