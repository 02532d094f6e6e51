"""chip_smoke.py's phases at tiny width on the CPU mesh, and the script's
refusal of anything but a TPU.

The phases are the same functions the chip run calls; only the sizes,
the place and the expected kernel form (``reference`` — no Mosaic on a
CPU) differ.  Positions stay within the tiny models' ``max_position``.
"""

import os
import subprocess
import sys

import pytest

import chip_smoke
from paddle_tpu import fluid
from paddle_tpu.models import bert, gpt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU = dict(place=fluid.CPUPlace(), platform="cpu", expect_mode="reference")


def test_trainer_phase():
    r = chip_smoke.run_trainer(bert.BertConfig.tiny(), batch=8, seq_len=32,
                               steps=6, **CPU)
    assert len(r["losses"]) == 7 and r["losses"][-1] < r["losses"][0]
    assert r["graph_passes"]["fuse_bias_act_dropout"] == {
        "sites": 3, "kernel": "xla"}
    # startup + one train executable, every later step a cache hit
    # (xla_persistent/* depends on what .jax_cache already holds)
    cache = r["pt_compile_cache_total"]
    assert (cache["single/hit"], cache["single/miss"]) == (6, 2)


def test_server_phase():
    r = chip_smoke.run_server(gpt.GPTConfig.tiny(), slots=4, page=8,
                              max_len=64, prompt_lens=(5, 17, 30, 9),
                              gen_len=6, **CPU)
    assert r["token_exact_prompts"] + len(r["reference_ties"]) == 4
    assert r["kernels"] == {"flash_attention": "reference",
                            "paged_attention": "reference"}


def test_dp_phase_matches_one_device():
    r = chip_smoke.run_dp(
        bert.BertConfig.tiny(hidden_dropout=0.0, attn_dropout=0.0),
        seq_len=32, parity_batch=8, batch=16, steps=3,
        places=[fluid.CPUPlace()] * 4, **CPU)
    assert r["devices"] == 4 and r["all_reduce_ops"] >= 1
    assert r["parity"]["data_parallel"] == pytest.approx(
        r["parity"]["one_device"], rel=1e-3)


def test_a_kernel_in_another_form_fails_the_phase():
    """The report names what the counters saw; a phase that expected
    compiled Pallas and got the XLA reference is a failure, not a
    footnote."""
    with pytest.raises(chip_smoke.SmokeFailure, match="another form"):
        chip_smoke.run_server(gpt.GPTConfig.tiny(), slots=2, page=8,
                              max_len=32, prompt_lens=(5,), gen_len=2,
                              **dict(CPU, expect_mode="pallas"))


def test_script_refuses_a_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout and "platform=cpu" in out.stderr
    assert '"ok"' not in out.stdout
