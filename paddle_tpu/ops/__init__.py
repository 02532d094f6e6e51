"""Op lowering library — importing this package registers every op.

The registry (paddle_tpu.fluid.registry) is the TPU-native analog of the
reference's OpInfoMap (paddle/fluid/framework/op_registry.h): instead of
per-device kernels, each op carries a JAX lowering traced into whole-block
XLA computations.
"""

from . import common  # noqa: F401
from . import math_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import tensor_array_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import structured_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import metric_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import amp_ops  # noqa: F401
from . import health_ops  # noqa: F401
from . import dist_ops  # noqa: F401
from . import tensor_extra_ops  # noqa: F401
from . import nn_extra_ops  # noqa: F401
from . import detection_extra_ops  # noqa: F401
from . import fused_ops  # noqa: F401
from . import decode_ops  # noqa: F401
from . import mla_ops  # noqa: F401
from . import gqa_ops  # noqa: F401
from . import vision_ops  # noqa: F401
from . import gdn_ops  # noqa: F401
from . import compat_ops  # noqa: F401
from . import interop_tail_ops  # noqa: F401
