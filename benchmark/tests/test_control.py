"""The control: the reference computed in the precision below the stated
one has to read far above what the stated precision reads, on the numbers
`correct` compares.  At a size a test run can hold; PERF.md has the
readings at the cells' own sizes on the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import generator, harness

TINY = os.path.join(harness.HERE, "tests", "tiny")
lowprec = harness.load_module("reference", "lowprec.py")


def _load(name):
    with open(os.path.join(TINY, name)) as f:
        return json.load(f)


def test_fp8_control_fails_the_first_gradient_bf16_does_not():
    train = harness.load_module("runners", "train.py")
    ref = harness.load_module("reference", "bert.py")
    cfg = dict(_load("bert-tiny.json"), num_hidden_layers=2)
    job = dict(_load("tiny-train.json"), batch=32, seq_len=32,
               masked_per_seq=4)
    sound, control = [], []
    for seed in (2**31 + 1, 7, 99):
        batch = generator.bert_batch(job, seed, cfg)
        with jax.default_matmul_precision("highest"):
            base = ref.train_readings(cfg, batch, seed, 3, 1e-4)
            bf16 = ref.train_readings(cfg, batch, seed, 3, 1e-4,
                                      matmul=lowprec.bf16_matmul)
            fp8 = ref.train_readings(cfg, batch, seed, 3, 1e-4,
                                     matmul=lowprec.fp8_matmul)
        bf16["ref_module"] = fp8["ref_module"] = ref
        sound.append(train.compare(bf16, base)["grad_diff_all"])
        control.append(train.compare(fp8, base)["grad_diff_all"])
    assert min(control) > 3 * max(sound), (sound, control)


def test_fp8_control_fails_the_served_logit_gap_bf16_does_not():
    ref = harness.load_module("reference", "gpt2.py")
    cfg = dict(_load("gpt-tiny.json"), max_served=24)
    rng = np.random.default_rng(5)
    sound, control = [], []
    for seed in (2**31 + 1, 7, 99):
        with jax.default_matmul_precision("highest"):
            params = ref.init_weights(cfg, seed)
            # make the logits depend on the context: larger weights than
            # the 0.02 the cells serve, at this tiny width
            params = {k: v * 8.0 if k.endswith(".w_0") else v
                      for k, v in params.items()}
            prompt = rng.integers(1, cfg["vocab_size"], 40).tolist()
            served = rng.integers(1, cfg["vocab_size"], 24).tolist()
            base = ref.served_logits(params, cfg, prompt, served)
            best = jnp.max(base, axis=1)
            for matmul, out in ((lowprec.bf16_matmul, sound),
                                (lowprec.fp8_matmul, control)):
                low = ref.served_logits(params, cfg, prompt, served, matmul)
                pick = jnp.argmax(low, axis=1)
                got = jnp.take_along_axis(base, pick[:, None], axis=1)[:, 0]
                out.append(float(jnp.max(best - got)))
    assert min(control) > 3 * max(max(sound), 1e-6), (sound, control)
