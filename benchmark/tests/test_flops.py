"""FLOP and byte counts against hand-worked numbers."""

import json
import os

from benchmark import flops, harness


def _config(name):
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_base_b128_s128_step():
    cfg = _config("bert-base-nodropout")
    # per layer: 8*16384*768^2 + 4*16384*768*3072 + 4*128*128^2*768
    per_layer = 77309411328 + 154618822656 + 6442450944
    m = 128 * 16
    head = 2 * m * 768 * 768 + 2 * m * 768 * 30528 + 2 * 128 * 768 * 768
    want = 3.0 * (12 * per_layer + head)
    got = flops.bert_train_flops_per_step(
        cfg, {"batch": 128, "seq_len": 128, "masked_per_seq": 16})
    assert got == want
    assert abs(got - 8.88e12) / 8.88e12 < 0.005


def test_kv_bytes_and_context_tokens():
    cfg = _config("gpt2-large")
    # K and V, 36 layers, 1280 wide, 4 bytes: 368640 B a context token
    assert flops.gpt2_kv_bytes_per_context_token(cfg) == 368640
    # tokens 1..3 of a 10-token prompt read 11 + 12 + 13 context tokens
    assert flops.decode_context_tokens(10, 0, 4) == 36
    assert flops.decode_context_tokens(10, 1, 4) == 36
    assert flops.decode_context_tokens(10, 2, 4) == 25
    assert flops.decode_context_tokens(10, 4, 4) == 0
