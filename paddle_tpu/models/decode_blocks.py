"""The parts the decode-lane decoders share (models/glm.py, trinity.py,
kimi_vl.py, olmo_hybrid.py, mimo.py): a matrix stored in ``cfg.dtype``,
an RMSNorm, a SwiGLU, the latent-attention RoPE and the head.  ``cfg`` is
the model's config: ``dtype``, ``initializer_range``, ``rms_norm_eps``,
``hidden_size`` and what each part names below.  The programs around a
decoder (feeds, pools, page writers) are serving/lane.py's."""

from __future__ import annotations

from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Constant, Normal
from paddle_tpu.fluid.param_attr import ParamAttr


def _attr(name, cfg, init=None):
    return ParamAttr(name=name, initializer=init or Normal(
        0.0, cfg.initializer_range))


def _linear(x, size, name, cfg, head_dim=None):
    return layers.weight_matmul(x, size, param_attr=_attr(name + ".w_0", cfg),
                                dtype=cfg.dtype, head_dim=head_dim)


def _rms(x, name, cfg):
    return layers.rms_norm(
        x, epsilon=cfg.rms_norm_eps,
        param_attr=ParamAttr(name=name + ".scale",
                             initializer=Constant(1.0)))


def _swiglu_ffn(x, width, name, cfg):
    hidden = layers.swiglu(_linear(x, width, name + "_gate", cfg),
                           _linear(x, width, name + "_up", cfg))
    return _linear(hidden, cfg.hidden_size, name + "_down", cfg)


def _rope(x, pos, cfg):
    """Interleaved-pair RoPE on the first ``qk_rope_head_dim`` entries."""
    return layers.rope_interleaved(x, pos, theta=cfg.rope_theta,
                                   rotary_dim=cfg.qk_rope_head_dim)


def _next_token(h, cfg, prefix):
    """h [N, 1, D] -> (greedy next token [N] int64, logprobs [N, V]):
    the final RMSNorm and the untied head, ``<prefix>_final_norm`` /
    ``<prefix>_head``."""
    L = layers
    logits = L.reshape(_linear(_rms(h, prefix + "_final_norm", cfg),
                               cfg.vocab_size, prefix + "_head", cfg),
                       shape=[-1, cfg.vocab_size])
    logp = L.log_softmax(logits)
    return L.argmax(logp, axis=-1), logp
