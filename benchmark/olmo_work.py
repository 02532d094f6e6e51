"""Bytes and operations the mathematics of Olmo-Hybrid's work needs, from
the configuration's sizes alone (the numerators of its roofline shares;
see ``flops.py`` for the rule: what the algorithm requires, never what
the program happens to move or compute).  Configuration keys are the
source's own.

A decode step's full attention and its delta-rule step are bound by
memory (one token a sequence): bytes.  A prefill chunk's delta rule is
counted in operations of the RULE (the recurrence token by token),
whatever form computes them: a chunked form does more, and a later
kernel is read by the same count.
"""

from __future__ import annotations

from benchmark.trinity_work import decode_contexts  # noqa: F401 (the runner's)

LINEAR, FULL = "linear_attention", "full_attention"


def _itemsize(config, what):
    return {"bfloat16": 2, "float32": 4}[config["precision"][what]]


def layers_of(config, kind):
    return sum(t == kind for t in config["layer_types"])


def kv_bytes_per_context_token(config):
    """Bytes of K and V that one decode step reads for one token of one
    sequence's context, over the full-attention layers: a K and a V row
    of every head (30 x 128 values each at the published sizes)."""
    width = config["num_attention_heads"] * config["assumed"]["head_dim"]
    return layers_of(config, FULL) * 2 * width * _itemsize(config, "cache")


def kv_bytes(config, contexts):
    """K and V bytes decode steps at ``contexts`` (one entry a sequence a
    step) must read."""
    return sum(contexts) * kv_bytes_per_context_token(config)


def state_values(config):
    """Values of one sequence's recurrent state in one layer: d_k x d_v a
    head (30 x 96 x 192 = 552 960 at the published sizes)."""
    return (config["linear_num_value_heads"] * config["linear_key_head_dim"]
            * config["linear_value_head_dim"])


def state_bytes_per_row(config):
    """Bytes one decode step must move for one active sequence, over the
    linear-attention layers: its state read once and written once (2 x
    2 211 840 B a layer at the published sizes).  The convolution's
    carried inputs (0.14 MB) are another kernel's and not counted."""
    return (layers_of(config, LINEAR) * 2 * state_values(config)
            * _itemsize(config, "state"))


def rule_flop_per_token(config):
    """FLOP of the gated delta rule for one token, over the
    linear-attention layers and every head: the decay of S, S^T k, the
    rank-one update and S^T q, 2 FLOP an entry of S each: 8 x d_k x d_v a
    head (4.42 MFLOP a layer at the published sizes)."""
    return layers_of(config, LINEAR) * 8 * state_values(config)
