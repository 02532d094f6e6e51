"""Bytes the mathematics of GLM-5's new operations needs, from the
configuration's sizes alone (the numerators of their roofline shares; see
``flops.py`` for the rule: what the algorithm requires, never what the
program happens to move).  Configuration keys are the source's own.

The three operations of learned sparse attention over the paged caches
and the expert layer's grouped product are all bound by memory in a
decode step (one query a sequence), so each function counts bytes.
"""

from __future__ import annotations


def _itemsize(config):
    return {"bfloat16": 2, "float32": 4}[config["precision"]["cache"]]


def _weight_itemsize(config):
    return {"bfloat16": 2, "float32": 4}[config["precision"]["weights"]]


def index_bytes_per_context_token(config):
    """Bytes of indexer keys one decode step reads for one token of one
    sequence's context, over every layer: the score operation looks at
    every visible position (that is what it is for)."""
    return (config["num_hidden_layers"] * config["index_head_dim"]
            * _itemsize(config))


def selected_latent_bytes_per_query(config, context=None):
    """Bytes of latent rows ([c_kv | k_rope]) sparse attention must read
    for one query, over every layer: min(context, index_topk) selected
    rows.  ``context`` None means a context past index_topk."""
    rows = config["index_topk"] if context is None else min(
        int(context), config["index_topk"])
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return config["num_hidden_layers"] * rows * width * _itemsize(config)


def expert_bytes(config):
    """Bytes of one routed expert's three matrices: what a decode step
    must read once for every held expert at least one of its picks lands
    on (the program counts those on the device)."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _weight_itemsize(config))
