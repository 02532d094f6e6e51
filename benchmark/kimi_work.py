"""Bytes and operations the mathematics of Kimi-VL's new work needs, from
the configuration's sizes alone (the numerators of its roofline shares;
see ``flops.py`` for the rule: what the algorithm requires, never what
the program happens to move or compute).  Configuration keys are the
source's own; the tower's are ``vision_config``'s.

A decode step's latent attention and its expert product are bound by
memory (one query a sequence): bytes.  A prefill chunk's attention and
the tower are bound by the MXU: operations, counted in HEAD space for
the chunk (the cheaper of the two forms of the same numbers), whatever
form computes them, and without the up-projection of the cached rows,
which is the program's way of getting keys and values and no part of
attention's own count.
"""

from __future__ import annotations

from benchmark.trinity_work import decode_contexts  # noqa: F401 (the runner's)


def _itemsize(config, what):
    return {"bfloat16": 2, "float32": 4}[config["precision"][what]]


def latent_bytes_per_context_token(config):
    """Bytes of latent rows ([c_kv | k_rope], as wide as the model makes
    them: the stored row's pad lanes are the program's) that one decode
    step reads for one token of one sequence's context, over every
    layer: dense latent attention looks at every visible row."""
    return (config["num_hidden_layers"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * _itemsize(config, "cache"))


def latent_bytes(config, contexts):
    """Latent-cache bytes decode steps at ``contexts`` (one entry a
    sequence a step) must read."""
    return sum(contexts) * latent_bytes_per_context_token(config)


def visible_pairs(first, last):
    """(query, key) pairs of the causal queries at positions ``first`` ..
    ``last - 1``: query q sees keys 0 .. q."""
    return (last * (last + 1) - first * (first + 1)) // 2


def chunk_attention_flop(config, spans):
    """Attention FLOP of prefilling positions ``spans`` = [(first, last)]
    in head space, over every layer: a visible pair costs a score over
    nope + rope dims and a value update over v dims, 2 FLOP each, a
    head (16 x (192 + 128) x 2 = 10 240 at the published sizes)."""
    per_pair = 2 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])
    return (sum(visible_pairs(a, b) for a, b in spans) * per_pair
            * config["num_hidden_layers"])


def expert_bytes(config):
    """Bytes of one routed expert's three matrices: what a decode step
    must read once for every expert at least one of its picks lands on
    (the program counts those on the device)."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _itemsize(config, "weights"))


def image_rows(grid):
    return (grid[0] // 2) * (grid[1] // 2)


def tower_attention_flop(config, grid):
    """FLOP of the tower's attention for one image of ``grid`` patches,
    over its layers: every patch scores every patch and sums its values,
    2 FLOP each over the head's width, every head."""
    vc = config["vision_config"]
    n = grid[0] * grid[1]
    return 4 * n * n * vc["hidden_size"] * vc["num_hidden_layers"]


def tower_flop(config, grid):
    """FLOP of the whole tower and projector for one image: the patch
    embedding, every block's four square and two wide linears and its
    attention, and the projector's two linears an image row."""
    vc = config["vision_config"]
    w, i, n = vc["hidden_size"], vc["intermediate_size"], grid[0] * grid[1]
    patch = vc.get("num_channels", 3) * vc["patch_size"] ** 2
    block = 2 * n * (4 * w * w + 2 * w * i)
    projector = 2 * image_rows(grid) * (4 * w * 4 * w
                                        + 4 * w * config["hidden_size"])
    return (2 * n * patch * w + vc["num_hidden_layers"] * block
            + tower_attention_flop(config, grid) + projector)
