"""Chip-free Mosaic: every Pallas primitive, and the steps that carry
them, AOT-compiled for a TPU v5e from this CPU host.

libtpu compiles for a topology it does not have
(``jax.experimental.topologies``), so ``jit(...).lower(...).compile()``
against those devices runs the real Mosaic and XLA:TPU pipelines.  The
interpreter (``interpret=True``) checks kernel logic, never lowering: a
block shape Mosaic cannot tile or a primitive it does not implement
fails HERE, in tier 1, not on the chip.  Shapes are the ones
chip_smoke.py runs (BERT-base / GPT-base heads and widths; whole
programs are cut in depth only, to keep the compile in seconds).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, SingleDeviceSharding

from paddle_tpu import fluid, serving
from paddle_tpu.fluid.platform_utils import lowering_for
from paddle_tpu.kernels import fused_update
from paddle_tpu.kernels import primitives as prims
from paddle_tpu.kernels.quantized_collectives import quantize_block_scaled
from paddle_tpu.models import bert, gpt
from paddle_tpu.parallel.data_parallel import DataParallelRunner

HEADS, HEAD_DIM = 12, 64  # BERT-base and GPT-base


@pytest.fixture(scope="module")
def topo():
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, chip, *shapes):
    """Compile ``fn`` for the topology's first chip; returns the HLO."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    with lowering_for("tpu"):
        return jax.jit(fn).lower(*args).compile().as_text()


def _mosaic_calls(hlo):
    return hlo.count("tpu_custom_call")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,causal", [(128, 128, False), (4, 512, False),
                                        (1, 1024, True)])
def test_flash_forward_and_backward(chip, dtype, b, s, causal):
    qkv = ((b, HEADS, s, HEAD_DIM), dtype)

    def loss(q, k, v, bias):
        return prims.flash_attention(q, k, v, bias=bias,
                                     causal=causal).astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), chip, qkv, qkv, qkv,
                   ((b, s), jnp.float32))
    assert _mosaic_calls(hlo) >= 3  # fwd, dq, dk/dv


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_attention(chip, dtype):
    qkv = ((8, HEADS, 512, HEAD_DIM), dtype)
    hlo = _compile(
        lambda q, k, v, n: prims.ragged_attention(q, k, v, n, causal=True),
        chip, qkv, qkv, qkv, ((8,), jnp.int32))
    assert _mosaic_calls(hlo) == 1


def _paged_shapes(t, page, dtype):
    b = 8 if t == 1 else 1          # decode step : prefill chunk
    max_pages = 512 // page
    pool = ((8 * max_pages + 1, page, HEADS, HEAD_DIM), dtype)
    return (((b, HEADS, t, HEAD_DIM), jnp.float32 if dtype == jnp.int8
             else dtype), pool,
            ((b, max_pages), jnp.int32), ((b,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,page", [(1, 32), (32, 32), (1, 16), (128, 128)])
def test_paged_attention(chip, dtype, t, page):
    q, pool, table, start = _paged_shapes(t, page, dtype)
    hlo = _compile(prims.paged_attention, chip, q, pool, pool, table, start)
    assert _mosaic_calls(hlo) == 1


@pytest.mark.parametrize("t,page", [(1, 32), (32, 32), (1, 16), (128, 128)])
def test_paged_attention_int8_pool(chip, t, page):
    q, pool, table, start = _paged_shapes(t, page, jnp.int8)
    scale = (pool[0][:3] + (1,), jnp.float32)
    hlo = _compile(prims.paged_attention_quant, chip, q, pool, pool, scale,
                   pool, pool, scale, table, start)
    assert _mosaic_calls(hlo) == 1


def test_fused_adam_update_on_a_quantized_gradient(chip):
    """The one chain the fused-update kernel serves: a wire-format
    (dual-int8 + scales) gradient straight into Adam."""
    shape, block = (768, 3072), 256

    def step(p, g, m1, m2, lr, b1p, b2p):
        q_hi, q_lo, scales = quantize_block_scaled(jnp.ravel(g),
                                                   block_size=block)
        return fused_update.fused_adam_update(
            p, (q_hi, q_lo, scales, 0, g.size), m1, m2, lr, b1p, b2p,
            block_size=block)

    w = (shape, jnp.float32)
    one = ((1,), jnp.float32)
    hlo = _compile(step, chip, w, w, w, w, one, one, one)
    assert _mosaic_calls(hlo) == 1


# ---------------------------------------------------------------------------
# whole steps, through the entry points chip_smoke.py drives: full width,
# depth 2
# ---------------------------------------------------------------------------


def _bert_train(**cfg_kw):
    from chip_smoke import _build_bert_train

    cfg = bert.BertConfig.base(vocab_size=30528, num_layers=2, **cfg_kw)
    return (cfg,) + _build_bert_train(cfg)


def test_bert_train_step_default_flags(chip):
    """The trainer's headline step: attention dropout keeps attention
    composed, and the fused bias+GeLU sites take the XLA form (the pass
    report says so) — no Mosaic call, and it compiles."""
    cfg, main, startup, loss = _bert_train()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with lowering_for("tpu"):
            hlo = exe.lower(main, bert.make_fake_batch(cfg, 128, 128),
                            [loss.name], sharding=chip).compile().as_text()
    assert _mosaic_calls(hlo) == 0
    report = {e["pass"]: e for e in main._pass_report if e["changed"]}
    assert report["fuse_bias_act_dropout"]["sites"] == 3
    assert report["fuse_bias_act_dropout"]["kernel"] == "xla"


def test_bert_data_parallel_step_over_four_chips(topo):
    """The four-chip step chip_smoke.py runs, compiled over a Mesh of the
    four topology devices: Mosaic flash kernels (dropout off) inside the
    shard_map, and the gradient all-reduce in the compiled HLO."""
    cfg, main, startup, loss = _bert_train(hidden_dropout=0.0,
                                           attn_dropout=0.0)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        runner = DataParallelRunner(main, loss.name,
                                    places=[fluid.CPUPlace()] * 4)
        with lowering_for("tpu"):
            hlo = runner.lower(
                exe, bert.make_fake_batch(cfg, 128, 128, shards=4),
                [loss.name], scope=scope,
                mesh=Mesh(np.array(topo.devices), ("dp",)),
            ).compile().as_text()
    assert _mosaic_calls(hlo) >= 3 * cfg.num_layers
    assert "all-reduce" in hlo and "replica_groups={{0,1,2,3}}" in hlo


@pytest.mark.parametrize("pool_dtype", ["float32", "int8"])
def test_decode_engine_executables(chip, pool_dtype):
    """GPT-base as chip_smoke.py serves it (page 32, max_len 512, 8
    slots): the prefill chunk and the decode step, one paged-attention
    Mosaic call per layer each."""
    cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=768, num_heads=12,
                        num_layers=2, max_position=512)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        lm, lm_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(lm, lm_start), fluid.unique_name.guard():
            gpt.build_gpt_lm(cfg, is_test=True)
        fluid.Executor(fluid.CPUPlace()).run(lm_start)
        engine = serving.DecodeEngine(
            cfg, scope=scope, place=fluid.CPUPlace(), pool_slots=8,
            page_size=32, max_len=512, pool_dtype=pool_dtype,
            name=f"aot-{pool_dtype}", auto_start=False)
        try:
            with lowering_for("tpu"):
                for lowered in engine.lower(sharding=chip):
                    hlo = lowered.compile().as_text()
                    assert _mosaic_calls(hlo) == cfg.num_layers
        finally:
            engine.close()
