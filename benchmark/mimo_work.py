"""Bytes and operations the mathematics of MiMo-V2's decode step and
prefill chunk need, from the configuration's sizes alone (the numerators
of its roofline shares; see ``flops.py`` for the rule: what the algorithm
requires, never what the program happens to move).  Configuration keys
are the source's own.

Grouped-query attention over the paged K/V caches and the expert layer's
grouped product are bound by memory in a decode step (one query a
sequence), so those functions count bytes: a FULL layer
(``hybrid_layer_pattern`` 0) must read the K and V rows of the whole
context, a WINDOW layer (1) those of the last ``sliding_window`` tokens.
A K row holds the kind's K/V heads x ``head_dim``, a V row as many x
``v_head_dim``.  A prefill chunk's attention is bound by the MXU, so
``chunk_attention_flop`` counts operations.
"""

from __future__ import annotations

FULL, WINDOW = 0, 1


def _itemsize(config, what):
    return {"bfloat16": 2, "float32": 4}[config["precision"][what]]


def kv_heads(config, kind):
    return (config["swa_num_key_value_heads"] if kind == WINDOW
            else config["num_key_value_heads"])


def kv_bytes_per_token_per_layer(config, kind):
    """A token's K row and V row in one layer of ``kind``."""
    return (kv_heads(config, kind)
            * (config["head_dim"] + config["v_head_dim"])
            * _itemsize(config, "cache"))


def layers_of(config, kind):
    return sum(k == kind for k in config["hybrid_layer_pattern"])


def kv_bytes_per_context_token(config):
    """Bytes of K and V that one decode step reads for one token of one
    sequence's context in the FULL layers (what grows with the context
    without bound)."""
    return (layers_of(config, FULL)
            * kv_bytes_per_token_per_layer(config, FULL))


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
    """K and V bytes the window layers must read for the same steps: the
    last ``sliding_window`` tokens of each context, a layer."""
    w = config["sliding_window"]
    return (sum(min(c, w) for c in contexts) * layers_of(config, WINDOW)
            * kv_bytes_per_token_per_layer(config, WINDOW))


def visible_pairs(first, last, window=None):
    """(query, key) pairs of the causal queries at positions ``first`` ..
    ``last - 1``: query q sees keys 0 .. q, or its last ``window``."""
    if window is None:
        return (last * (last + 1) - first * (first + 1)) // 2
    return sum(min(q + 1, window) for q in range(first, last))


def chunk_attention_flop(config, spans):
    """Attention FLOP of prefilling positions ``spans`` = [(first, last)]
    over every layer of both kinds: a visible pair costs a score over
    ``head_dim`` and a value update over ``v_head_dim``, 2 FLOP each, a
    query head (64 x (192 + 128) x 2 = 40 960 at the published sizes)."""
    per_pair = 2 * config["num_attention_heads"] * (
        config["head_dim"] + config["v_head_dim"])
    w = config["sliding_window"]
    return per_pair * sum(
        layers_of(config, FULL) * visible_pairs(a, b)
        + layers_of(config, WINDOW) * visible_pairs(a, b, w)
        for a, b in spans)


def expert_bytes(config):
    """Bytes of one routed expert's three matrices: what a decode step
    must read once for every held expert at least one of its picks lands
    on (the program counts those on the device)."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _itemsize(config, "weights"))
