"""Bytes and operations the mathematics of Qwen3-Next's work needs, from
the configuration's sizes alone (the numerators of its roofline shares;
see ``flops.py`` for the rule: what the algorithm requires, never what
the program happens to move or compute).  Configuration keys are the
source's own; layer i (from 0) is a full-attention layer where (i + 1) %
``full_attention_interval`` == 0, else a linear-attention layer.

A decode step's full attention, its delta-rule step and its expert
product are bound by memory (one token a sequence): bytes.  A prefill
chunk's delta rule is counted in operations of the RULE (the recurrence
token by token), whatever form computes them: a chunked form does more,
and a later kernel is read by the same count.
"""

from __future__ import annotations

from benchmark.trinity_work import decode_contexts  # noqa: F401 (the runner's)


def _itemsize(config, what):
    return {"bfloat16": 2, "float32": 4}[config["precision"][what]]


def full_layers(config):
    return config["num_hidden_layers"] // config["full_attention_interval"]


def linear_layers(config):
    return config["num_hidden_layers"] - full_layers(config)


def expert_layers(config):
    """Every layer is an expert layer (``decoder_sparse_step`` 1,
    ``mlp_only_layers`` empty)."""
    return config["num_hidden_layers"]


def kv_bytes_per_context_token(config):
    """Bytes of K and V that one decode step reads for one token of one
    sequence's context, over the full-attention layers: a K and a V row
    of the K/V heads side by side (2 x 2 x 256 values = 2048 B a layer
    at the published sizes)."""
    width = config["num_key_value_heads"] * config["head_dim"]
    return full_layers(config) * 2 * width * _itemsize(config, "cache")


def full_attn_bytes(config, contexts):
    """K and V bytes decode steps at ``contexts`` (one entry a sequence a
    step) must read."""
    return sum(contexts) * kv_bytes_per_context_token(config)


def expert_bytes(config):
    """Bytes of one routed expert's three matrices (3 x 2048 x 512 x 2 B
    = 6 291 456 B at the published sizes): what a decode step must read
    once for every held expert at least one of its picks lands on (the
    program counts those on the device)."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _itemsize(config, "weights"))


def grouped_calls_per_decode_step(config):
    """Calls of the grouped product a decode step: gate, up and down of
    every expert layer (24 at 8 layers)."""
    return 3 * expert_layers(config)


def held_expert_reads_possible(config, steps):
    """Held experts x expert layers x ``steps``: what decode steps would
    touch if every held expert got a pick in every layer of every
    step."""
    return config["num_experts"] * expert_layers(config) * steps


def state_values(config):
    """Values of one sequence's recurrent state in one layer: d_k x d_v a
    VALUE head (32 x 128 x 128 = 524 288 at the published sizes)."""
    return (config["linear_num_value_heads"] * config["linear_key_head_dim"]
            * config["linear_value_head_dim"])


def state_bytes_per_row(config):
    """Bytes one decode step must move for one active sequence, over the
    linear-attention layers: its state read once and written once (2 x
    2 097 152 B a layer at the published sizes).  The convolution's
    carried inputs (0.1 MB) are another kernel's and not counted."""
    return (linear_layers(config) * 2 * state_values(config)
            * _itemsize(config, "state"))


def rule_flop_per_token(config):
    """FLOP of the gated delta rule for one token, over the
    linear-attention layers and every VALUE head: the decay of S, S^T k,
    the rank-one update and S^T q, 2 FLOP an entry of S each: 8 x d_k x
    d_v a head (8 x 128 x 128 x 32 = 4.19 MFLOP a layer at the published
    sizes)."""
    return linear_layers(config) * 8 * state_values(config)
