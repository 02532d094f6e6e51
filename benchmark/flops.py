"""The operations and bytes an algorithm needs, computed from its shapes.

These are the numerators of the roofline shares.  They count what the
mathematics requires (recomputed operations never count), from the
configuration's sizes alone, so no PR that changes the program can move
them.  Configuration keys are the source's own (Hugging Face ``config.json``
names).  A configuration file names the functions it needs under ``work``
(``module`` and one function per quantity); a model with other keys brings a
module of its own.
"""

from __future__ import annotations


def bert_train_flops_per_step(cfg, job):
    """Matmul FLOPs of one BERT pretraining step of the job (``batch``,
    ``seq_len``, ``masked_per_seq``), forward plus backward
    (backward = 2 x forward; a multiply-add is 2 FLOPs).

    Per layer, forward: QKV and output projections 8*b*s*h^2, FFN
    4*b*s*h*i, attention scores and context 4*b*s^2*h.  Heads: the
    masked-LM transform 2*M*h^2 and the tied vocabulary projection 2*M*h*V
    over the M = b*n_masked gathered positions, the pooler 2*b*h^2.
    Embedding gathers, layer norms, softmax, GeLU, dropout and the
    optimizer count 0: the share is of the matmul peak."""
    b, s = int(job["batch"]), int(job["seq_len"])
    n_masked = int(job["masked_per_seq"])
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    per_layer = 8 * b * s * h * h + 4 * b * s * h * i + 4 * b * s * s * h
    m = b * n_masked
    head = 2 * m * h * h + 2 * m * h * vocab + 2 * b * h * h
    return 3.0 * (layers * per_layer + head)


def gpt2_kv_bytes_per_context_token(cfg):
    """Bytes of K and V that one decode step reads for one token of one
    sequence's context, over every layer, at the pool's item size."""
    return (2 * cfg["n_layer"] * cfg["n_embd"]
            * cfg["engine"]["pool_itemsize"])


def decode_context_tokens(prompt_len, first, last):
    """Context tokens read by the decode steps that produced a request's
    generated tokens number ``first`` .. ``last - 1`` (0-based; token 0
    comes from the prefill, so it is never a decode step).  The step that
    produces token k attends to the prompt and the k tokens before it."""
    first = max(first, 1)
    if last <= first:
        return 0
    n = last - first
    return n * prompt_len + (first + last - 1) * n // 2
