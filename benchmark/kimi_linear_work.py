"""Bytes and operations the mathematics of Kimi-Linear's work needs, from
the configuration's sizes alone (the numerators of its roofline shares;
see ``flops.py`` for the rule: what the algorithm requires, never what
the program happens to move or compute).  Configuration keys are the
source's own; ``linear_attn_config`` numbers its layers from 1.

A decode step's latent attention, its delta-rule step and its expert
product are bound by memory (one token a sequence): bytes.  A prefill
chunk's latent attention is counted in head space (``kimi_work.py``'s
rule) and its delta rule in operations of the RULE (the recurrence token
by token), whatever form computes them: the chunked form with a decay a
key channel does more, and a later kernel is read by the same count.
"""

from __future__ import annotations

# an expert's bytes (14.16 MB at the published sizes: what a decode step
# must read once for every held expert a pick lands on) and the causal
# pairs of a span are Kimi-VL's, the same keys and the same counts
from benchmark.kimi_work import (_itemsize, expert_bytes,  # noqa: F401
                                 visible_pairs)
from benchmark.trinity_work import decode_contexts  # noqa: F401 (the runner's)


def kda_layers(config):
    return len(config["linear_attn_config"]["kda_layers"])


def latent_layers(config):
    return len(config["linear_attn_config"]["full_attn_layers"])


def latent_bytes_per_context_token(config):
    """Bytes of latent rows ([c_kv | k_r], as wide as the model makes
    them: 576 values, 1152 B; the stored row's pad lanes are the
    program's) that one decode step reads for one token of one
    sequence's context, over the latent layers."""
    return (latent_layers(config)
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * _itemsize(config, "cache"))


def latent_bytes(config, contexts):
    """Latent-cache bytes decode steps at ``contexts`` (one entry a
    sequence a step) must read."""
    return sum(contexts) * latent_bytes_per_context_token(config)


def chunk_attention_flop(config, spans):
    """Latent attention's FLOP of prefilling positions ``spans`` =
    [(first, last)] in head space, over the latent layers: a visible pair
    costs a score over nope + rope dims and a value update over v dims,
    2 FLOP each, a head (32 x (192 + 128) x 2 = 20 480 at the published
    sizes)."""
    per_pair = 2 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])
    return (sum(visible_pairs(a, b) for a, b in spans) * per_pair
            * latent_layers(config))


def state_values(config):
    """Values of one sequence's recurrent state in one KDA layer: d x d a
    head (32 x 128 x 128 = 524 288 at the published sizes)."""
    la = config["linear_attn_config"]
    return la["num_heads"] * la["head_dim"] ** 2


def state_bytes_per_row(config):
    """Bytes one decode step must move for one active sequence, over the
    KDA layers: its state read once and written once (2 x 2 097 152 B a
    layer at the published sizes).  The decay (a vector of 128 a head),
    the convolution's carried inputs (0.15 MB) and the 0/1 head mask are
    other operands and not counted."""
    return (kda_layers(config) * 2 * state_values(config)
            * _itemsize(config, "state"))


def rule_flop_per_token(config):
    """FLOP of the delta rule for one token, over the KDA layers and
    every head: the decay of S, S^T k, the rank-one update and S^T q, 2
    FLOP an entry of S each: 8 x d x d a head (4.19 MFLOP a layer at the
    published sizes), as ``olmo_work.rule_flop_per_token`` counts the
    scalar-decay rule: a decay a channel multiplies the same entries."""
    return kda_layers(config) * 8 * state_values(config)
