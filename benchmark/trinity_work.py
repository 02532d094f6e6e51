"""Bytes the mathematics of Trinity's decode step needs, from the
configuration's sizes alone (the numerators of its roofline shares; see
``flops.py`` for the rule: what the algorithm requires, never what the
program happens to move).  Configuration keys are the source's own.

Grouped-query attention over the paged K/V caches and the expert layer's
grouped product are bound by memory in a decode step (one query a
sequence), so each function counts bytes.  A ``full_attention`` layer
must read the K and V rows of the whole context, a ``sliding_attention``
layer those of the last ``sliding_window`` tokens.
"""

from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"


def _itemsize(config, what):
    return {"bfloat16": 2, "float32": 4}[config["precision"][what]]


def kv_bytes_per_token_per_layer(config):
    """A token's K row and V row in one layer: the K/V heads side by
    side."""
    return (2 * config["num_key_value_heads"] * config["head_dim"]
            * _itemsize(config, "cache"))


def layers_of(config, kind):
    return sum(t == kind for t in config["layer_types"])


def kv_bytes_per_context_token(config):
    """Bytes of K and V that one decode step reads for one token of one
    sequence's context in the FULL layers (what grows with the context
    without bound)."""
    return layers_of(config, FULL) * kv_bytes_per_token_per_layer(config)


def decode_contexts(prompt_len, first, last):
    """The contexts of the decode steps that produced a request's
    generated tokens number ``first`` .. ``last - 1`` (0-based; token 0
    comes from the prefill): the step that produces token k attends to
    the prompt and the k tokens before it."""
    return [prompt_len + k for k in range(max(first, 1), last)]


def full_attn_bytes(config, contexts):
    """K and V bytes the full layers must read for decode steps at
    ``contexts`` (one entry a sequence a step)."""
    return sum(contexts) * kv_bytes_per_context_token(config)


def window_attn_bytes(config, contexts):
    """K and V bytes the sliding layers must read for the same steps: the
    last ``sliding_window`` tokens of each context, a layer."""
    w = config["sliding_window"]
    return (sum(min(c, w) for c in contexts) * layers_of(config, SLIDING)
            * kv_bytes_per_token_per_layer(config))


def expert_bytes(config):
    """Bytes of one routed expert's three matrices: what a decode step
    must read once for every held expert at least one of its picks lands
    on (the program counts those on the device)."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _itemsize(config, "weights"))
