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

import functools
import json
import os
import re
import types

import ml_dtypes
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
from paddle_tpu.models import bert, glm, gpt
from paddle_tpu.parallel.data_parallel import DataParallelRunner

HEADS, HEAD_DIM = 12, 64  # BERT-base and GPT-base


@functools.lru_cache(maxsize=None)
def _topo():
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def topo():
    return _topo()


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


def _paged_shapes(t, page, dtype, heads=HEADS, slots=8, max_len=512):
    b = slots if t == 1 else 1      # decode step : prefill chunk
    max_pages = max_len // page
    pool = ((slots * max_pages + 1, page, heads * HEAD_DIM), dtype)
    return (((b, heads, t, HEAD_DIM), jnp.float32 if dtype == jnp.int8
             else dtype), pool,
            ((b, max_pages), jnp.int32), ((b,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,page", [(1, 32), (32, 32), (1, 16), (128, 128)])
def test_paged_attention(chip, dtype, t, page):
    q, pool, table, start = _paged_shapes(t, page, dtype)
    hlo = _compile(prims.paged_attention, chip, q, pool, pool, table, start)
    assert _mosaic_calls(hlo) == 1


@pytest.mark.parametrize("t", [1, 32])
def test_paged_attention_at_the_benchmark_cell_shapes(chip, t):
    """gpt2-large.closed16-mixed's own call: 16 slots (a decode step) or
    one 32-token chunk, 20 heads of 64, page 32, a 32-page table, the
    pool f32[513,32,1280] — read eight pages at a time, by the kernel's
    own copies (the decode row) or eight BlockSpecs a pool (the chunk)."""
    q, pool, table, start = _paged_shapes(t, 32, jnp.float32, heads=20,
                                          slots=16, max_len=1024)
    assert pool[0] == (513, 32, 1280) and table[0] == (q[0][0], 32)
    hlo = _compile(prims.paged_attention, chip, q, pool, pool, table, start)
    assert _mosaic_calls(hlo) == 1 and "%paged_attention" in hlo
    assert _pool_copies(hlo, 513, 32) == []


@pytest.mark.parametrize("t,page", [(1, 32), (32, 32), (1, 16), (128, 128)])
def test_paged_attention_int8_pool(chip, t, page):
    q, pool, table, start = _paged_shapes(t, page, jnp.int8)
    scale = (pool[0][:2] + (HEADS,), jnp.float32)
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


def _pool_copies(hlo, num_pages, page):
    """Shapes of the pool tensors the compiled module copies whole:
    every ``copy`` or ``transpose`` whose result carries the pool's
    leading dimensions (at either rank: ``513,32,1280`` and
    ``513,32,20,64`` alike), and every ``copy-start`` that changes the
    layout (one that keeps it is the compiler's own prefetch into
    another memory space, ``S(1)``, of a buffer that fits there)."""
    pool = re.compile(rf"\[({num_pages},{page}(?:,\d+)+)\](\{{[\d,]*)")
    found = []
    for line in hlo.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%\S+ = (.+?) (copy|copy-start|transpose)\(", line)
        arrays = pool.findall(m.group(1)) if m else []
        if arrays and not (m.group(2) == "copy-start"
                           and arrays[0][1] == arrays[1][1]):
            found.append(arrays[0][0])
    return found


def _pool_parameters(hlo, shape):
    """(parameter number, layout) of every entry parameter of ``shape``."""
    entry = hlo[hlo.index("ENTRY "):]
    return [(int(num), layout) for layout, num in re.findall(
        rf"= \w+\[{shape}\](\{{[^}}]*\}}) parameter\((\d+)\)", entry)]


def _aliased_parameters(hlo):
    """Parameter numbers the module's ``input_output_alias`` donates."""
    header = hlo[:hlo.index("\n")]
    return {int(n) for n in re.findall(r"\((\d+), \{\}", header)}


def _long_hlo(compiled):
    """The compiled module with every operand's shape printed, which is
    how a device trace names an operation (``%x = f32[..] custom-call(
    s32[16,32]{..} %table, ..)``) and so what the benchmark's trace
    patterns are written against; ``as_text()`` leaves them out."""
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_operand_shape = True
    options.print_metadata = False
    return compiled.runtime_executable().hlo_modules()[0].to_string(options)


def _roofline_pattern():
    """The regular expression ``paged_attn_roofline.serve`` finds the
    decode step's paged-attention calls by, read from the benchmark."""
    return re.compile(
        harness_json(_ROOT, "paged_attn_roofline.serve")["pattern"])


# (hidden, heads, layers, slots, max_len): GPT-base as chip_smoke.py
# serves it, and GPT-2-large as benchmark/configs/gpt2-large.json serves
# it (the benchmark's widths, pool and slots; two of its 36 layers)
_ENGINES = {"gpt-base": (768, 12, 2, 8, 512),
            "gpt2-large": (1280, 20, 2, 16, 1024)}


@pytest.mark.parametrize("pool_dtype", ["float32", "int8"])
@pytest.mark.parametrize("size", list(_ENGINES))
def test_decode_engine_executables(chip, size, pool_dtype):
    """The prefill chunk and the decode step at page 32: one
    paged-attention Mosaic call per layer each, and the KV pool goes
    through both UNCOPIED — every pool parameter arrives row-major (the
    layout the kernel's blocks address), is donated, and no copy or
    transpose of a whole pool tensor is left in the compiled HLO.  With
    the heads apart, [pages, page, 20, 64], the float32 gpt2-large case
    compiled to 12 such copies in the decode step and 4 in the chunk
    (PERF.md finding 4): four fifths of the device's time."""
    hidden, heads, layers, slots, max_len = _ENGINES[size]
    page = 32
    cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=hidden,
                        num_heads=heads, num_layers=layers,
                        max_position=max_len)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        lm, lm_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(lm, lm_start), fluid.unique_name.guard():
            gpt.build_gpt_lm(cfg, is_test=True)
        fluid.Executor(fluid.CPUPlace()).run(lm_start)
        engine = serving.DecodeEngine(
            cfg, scope=scope, place=fluid.CPUPlace(), pool_slots=slots,
            page_size=page, max_len=max_len, pool_dtype=pool_dtype,
            name=f"aot-{size}-{pool_dtype}", auto_start=False)
        # K and V of every layer; the int8 pool holds each as hi and lo
        payload = f"{engine.pool.num_pages},{page},{hidden}"
        n_payload = 2 * layers * (2 if pool_dtype == "int8" else 1)
        scales = f"{engine.pool.num_pages},{page},{heads}"
        n_scales = 2 * layers if pool_dtype == "int8" else 0
        relaid = {scales} if n_scales else set()
        # the yardstick's view (benchmark/layer_metrics/
        # paged_attn_roofline.serve.json): its pattern names the decode
        # step's paged calls, one a layer, and none of the chunk's — a
        # kernel change that moved the page table from the call's first
        # operand, or split the call, would turn the metric null
        seen_by_roofline = []
        try:
            with lowering_for("tpu"):
                for lowered in engine.lower(sharding=chip):
                    compiled = lowered.compile()
                    hlo = compiled.as_text()
                    seen_by_roofline.append(sum(
                        1 for line in _long_hlo(compiled).splitlines()
                        if _roofline_pattern().search(line)))
                    assert _mosaic_calls(hlo) == cfg.num_layers
                    assert set(_pool_copies(hlo, engine.pool.num_pages,
                                            page)) <= relaid
                    params = _pool_parameters(hlo, payload)
                    assert len(params) == n_payload
                    assert [lay for _, lay in params
                            if not lay.startswith("{2,1,0")] == []
                    # the int8 pool's scales are donated like the rest,
                    # but [pages, page, heads] is NOT held to row-major:
                    # 20 lanes of 128 would pad it sixfold, so XLA keeps
                    # the page index minor-most and relays these (1/64 of
                    # the fp32 pool's bytes) around the kernel: the one
                    # shape `relaid` lets through (PERF.md section 7)
                    scale_params = _pool_parameters(hlo, scales)
                    assert len(scale_params) == n_scales
                    assert {num for num, _ in params + scale_params} <= \
                        _aliased_parameters(hlo)
            assert seen_by_roofline == [0, cfg.num_layers]
        finally:
            engine.close()


# ---------------------------------------------------------------------------
# The benchmark's five bf16 serve configurations through the decode lane:
# each one's executables are compiled ONCE a module, at the benchmark's
# widths, pool and slots and a cut in depth, for that configuration's own
# test below and for the audit of copies at the end of this file
# ---------------------------------------------------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config_args(config):
    return config["builder"]["config_args"]


def _glm_cut(config):
    # two of its five layers: the dense one and an expert one
    cfg = glm.GLMConfig(**dict(_config_args(config),
                               num_hidden_layers=2))
    return cfg, [lambda: glm.build_glm_lm(cfg)]


def _trinity_cut(config):
    from paddle_tpu.models import trinity

    # three of its five layers: the dense sliding one, a full and a
    # sliding expert layer
    cfg = trinity.TrinityConfig(**dict(
        _config_args(config), num_hidden_layers=3,
        layer_types=["sliding_attention", "full_attention",
                     "sliding_attention"]))
    return cfg, [lambda: trinity.build_trinity_lm(cfg)]


def _kimi_cut(config):
    from paddle_tpu.models import kimi_vl

    # two of its six decoder layers, the dense one and an expert one, and
    # two of the tower's six blocks
    cfg = kimi_vl.KimiVLConfig(**dict(
        _config_args(config), num_hidden_layers=2, vt_num_hidden_layers=2))
    return cfg, [lambda: kimi_vl.build_kimi_vl_lm(cfg),
                 lambda: kimi_vl.build_kimi_vl_vision_encoder(
                     cfg, 4, 4, 8)[1]]


def _olmo_cut(config):
    from paddle_tpu.models import olmo_hybrid

    # one period of its two: linear, linear, linear, full
    cfg = olmo_hybrid.OlmoHybridConfig(**dict(
        _config_args(config), num_hidden_layers=4,
        layer_types=config["layer_types"][:4]))
    return cfg, [lambda: olmo_hybrid.build_olmo_hybrid_lm(cfg)]


def _mimo_cut(config):
    from paddle_tpu.models import mimo

    # three of its seven layers: the dense full layer, a window expert
    # layer and the full expert layer
    cfg = mimo.MiMoConfig(**dict(
        _config_args(config), num_hidden_layers=3,
        hybrid_layer_pattern=[0, 1, 0], moe_layer_freq=[0, 1, 1]))
    return cfg, [lambda: mimo.build_mimo_lm(cfg)]


def _kimi_linear_cut(config):
    from paddle_tpu.models import kimi_linear

    # one period of its two: KDA (the dense layer), KDA, KDA, latent
    cfg = kimi_linear.KimiLinearConfig(**dict(
        _config_args(config), num_hidden_layers=4,
        linear_attn_config=dict(config["linear_attn_config"],
                                kda_layers=[1, 2, 3], full_attn_layers=[4])))
    return cfg, [lambda: kimi_linear.build_kimi_linear_lm(cfg)]


def _qwen3_next_cut(config):
    from paddle_tpu.models import qwen3_next

    # one period of its two: linear, linear, linear, full; four expert
    # layers
    cfg = qwen3_next.Qwen3NextConfig(**dict(_config_args(config),
                                            num_hidden_layers=4))
    return cfg, [lambda: qwen3_next.build_qwen3_next_lm(cfg)]


_CUTS = {"glm-5-ep16": _glm_cut, "trinity-large-ep8": _trinity_cut,
         "kimi-vl-a3b-ep1": _kimi_cut, "olmo-hybrid-7b-pp4": _olmo_cut,
         "mimo-v2.5-ep16": _mimo_cut,
         "kimi-linear-48b-ep4": _kimi_linear_cut,
         "qwen3-next-80b-ep4": _qwen3_next_cut}


@functools.lru_cache(maxsize=None)
def _served(name):
    """``benchmark/configs/<name>.json`` at its cut, compiled for one v5e
    chip: what the engine said of itself (``prefill_chunk``, ``pool``,
    ``image_rows``; it is closed again) and ``exes``, {label: (the
    compiled HLO, the lines of its long form)} under the labels chunk,
    step and, an image shape each, tower0.."""
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    cfg, builds = _CUTS[name](config)
    programs = []
    for build in builds:
        prog, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, start), fluid.unique_name.guard():
            built = build()
        programs.append(prog)
        if isinstance(built, fluid.Program):    # an encoder's second program
            programs.append(built)
    scope = fluid.Scope()
    for prog in programs:
        for p in prog.global_block().all_parameters():
            dtype = (ml_dtypes.bfloat16 if p.dtype == "bfloat16"
                     else np.dtype(p.dtype))
            # shapes are all a lowering reads: no 1.5 B parameters on the
            # host
            scope.set(p.name, np.broadcast_to(np.zeros((), dtype),
                                              tuple(p.shape)))
    e = config["engine"]
    engine = serving.DecodeEngine(
        cfg, scope=scope, place=fluid.CPUPlace(), pool_slots=e["pool_slots"],
        page_size=e["page_size"], max_len=e["max_len"], name="aot-" + name,
        auto_start=False)
    exes = {}
    try:
        with lowering_for("tpu"):
            lowered = engine.lower(
                sharding=SingleDeviceSharding(_topo().devices[0]))
            labels = ["chunk", "step"] + [
                f"tower{i}" for i in range(len(lowered) - 2)]
            for label, low in zip(labels, lowered):
                compiled = low.compile()
                exes[label] = (compiled.as_text(), [
                    line.strip()
                    for line in _long_hlo(compiled).splitlines()])
        return types.SimpleNamespace(
            prefill_chunk=engine.prefill_chunk, pool=engine.pool,
            image_rows=engine.stats().get("image_rows"), exes=exes)
    finally:
        engine.close()


# ---------------------------------------------------------------------------
# GLM-5 through the decode lane (benchmark/configs/glm-5-ep16.json): the
# three sparse-attention operations and the grouped product at the
# published widths, and the engine's two executables
# ---------------------------------------------------------------------------

_GLM_PAGES, _GLM_PAGE, _GLM_MAX_PAGES = 4129, 128, 258


@pytest.mark.parametrize("b,t", [(16, 1), (1, 512)])
def test_dsa_kernels_at_glm5_widths(chip, b, t):
    """Indexer scores (32 heads of 128 over the paged indexer cache),
    the exact top-2048 selection and latent attention (64 heads, rows
    stored 640 wide) as a decode step and as a 512-token chunk see
    them, at a 33k-token page table."""
    bf = jnp.bfloat16
    table, starts = ((b, _GLM_MAX_PAGES), jnp.int32), ((b,), jnp.int32)
    padded = 264 * _GLM_PAGE
    hlo = _compile(
        lambda q, w, pages, pt, qs: prims.dsa_indexer_scores(q, w, pages,
                                                             pt, qs),
        chip, ((b, t, 32, 128), bf), ((b, t, 32), jnp.float32),
        ((_GLM_PAGES, _GLM_PAGE, 128), bf), table, starts)
    assert _mosaic_calls(hlo) == 1 and "%dsa_indexer_scores" in hlo
    assert f"f32[{b},{t},{padded}]" in hlo
    hlo = _compile(lambda s: prims.dsa_topk_select(s, 2048), chip,
                   ((b, t, padded), jnp.float32))
    assert _mosaic_calls(hlo) == 1 and "%dsa_topk_select" in hlo
    assert "sort(" not in hlo and "approx" not in hlo.lower()
    hlo = _compile(
        lambda ql, qr, pages, pt, sel, qs: prims.sparse_mla_attention(
            ql, qr, pages, pt, sel, qs, sm_scale=1.0 / 16),
        chip, ((b, t, 64, 512), jnp.float32), ((b, t, 64, 64), jnp.float32),
        ((_GLM_PAGES, _GLM_PAGE, 640), bf), table,
        ((b, t, padded), jnp.float32), starts)
    assert _mosaic_calls(hlo) == 1 and "%sparse_mla_attention" in hlo
    assert _pool_copies(hlo, _GLM_PAGES, _GLM_PAGE) == []


@pytest.mark.parametrize("groups,rows,k,n", [
    (16, 128, 6144, 2048), (16, 4096, 6144, 2048), (16, 128, 2048, 6144),
    (16, 4096, 2048, 6144), (32, 128, 3072, 3072), (32, 2048, 3072, 3072),
    (64, 128, 2048, 1408), (64, 3072, 2048, 1408), (64, 128, 1408, 2048),
    (64, 3072, 1408, 2048)])
def test_grouped_matmul_at_glm5_widths(chip, groups, rows, k, n):
    """The expert layer's product over GLM-5's 16 held experts (a decode
    step's 128 pick rows and a chunk's 4096), Trinity's 32 (128 and
    2048) and Kimi-VL's 64 (128 and 3072; 1408 wide, so a block holds
    that side whole: 2.75 MiB and ~8 MiB of VMEM), the grid's visit
    extent a traced number."""
    hlo = _compile(lambda x, w, g: prims.grouped_matmul(x, w, g), chip,
                   ((rows, k), jnp.bfloat16), ((groups, k, n), jnp.bfloat16),
                   ((groups,), jnp.int32))
    assert _mosaic_calls(hlo) == 1 and "%grouped_matmul" in hlo


def test_glm_decode_engine_executables():
    """The prefill chunk and the decode step of GLM-5 at the benchmark's
    widths, pool and slots (two of its five layers: the dense one and an
    expert one): per layer one indexer, one selection and one attention
    Mosaic call,
    three grouped products an expert layer, and BOTH kinds of cache
    state go through both executables UNCOPIED — every latent and
    indexer pool parameter arrives row-major, is donated, and no copy
    or transpose of a whole pool tensor is left.  With the latent row
    stored 576 wide the decode step compiled to ten whole-pool copies
    and 6.1 GB of temporaries (serving/lane.py lane_padded)."""
    served = _served("glm-5-ep16")
    assert served.prefill_chunk == 512
    assert served.pool.num_pages == _GLM_PAGES
    for hlo, _ in served.exes.values():
        assert hlo.count("%dsa_indexer_scores") >= 2
        assert hlo.count("%sparse_mla_attention") >= 2
        assert hlo.count("%dsa_topk_select") >= 2
        assert _mosaic_calls(hlo) == 2 * 3 + 3
        assert _pool_copies(hlo, _GLM_PAGES, _GLM_PAGE) == []
        pools = []
        for width in (640, 128):
            params = _pool_parameters(
                hlo, f"{_GLM_PAGES},{_GLM_PAGE},{width}")
            assert len(params) == 2        # one a layer
            assert [lay for _, lay in params
                    if not lay.startswith("{2,1,0")] == []
            pools += params
        assert {num for num, _ in pools} <= _aliased_parameters(hlo)


# ---------------------------------------------------------------------------
# Trinity through the decode lane (benchmark/configs/
# trinity-large-ep8.json): the grouped-query and window forms of the
# paged kernel at the published widths, and the engine's two executables
# over a pool with a size a cache kind
# ---------------------------------------------------------------------------

_TRI_PAGES = {"full": 16 * 262 + 1, "window4096": 16 * 37 + 1}


@pytest.mark.parametrize("window", [None, 4096])
@pytest.mark.parametrize("b,t", [(16, 1), (1, 512)])
def test_paged_attention_grouped_and_window_at_trinity_widths(chip, b, t,
                                                              window):
    """48 query heads on 8 K/V heads of 128 over a bf16 pool
    [pages, 128, 1024] at a 33k-token page table, as a decode step and
    as a 512-token chunk see them; the kernel's name says which kind of
    layer it serves."""
    import functools

    pages = _TRI_PAGES["full" if window is None else "window4096"]
    pool = ((pages, 128, 1024), jnp.bfloat16)
    hlo = _compile(
        functools.partial(prims.paged_attention, window=window), chip,
        ((b, 48, t, 128), jnp.float32), pool, pool, ((b, 262), jnp.int32),
        ((b,), jnp.int32))
    name = "paged_attention_grouped" + ("" if window is None else "_window")
    assert _mosaic_calls(hlo) == 1
    assert len(re.findall(rf"%{name}[.\d]* = ", hlo)) == 1
    assert _pool_copies(hlo, pages, 128) == []


def test_trinity_decode_engine_executables():
    """The prefill chunk and the decode step of Trinity at the
    benchmark's widths, pool and slots (three of its five layers: the
    dense sliding one, a full and a sliding expert layer): a paged call
    a layer, named by its kind — which is what the benchmark's two
    roofline patterns read —, three grouped products an expert layer,
    each kind's pool tensors at that kind's size, donated, row-major and
    UNCOPIED."""
    served = _served("trinity-large-ep8")
    assert served.prefill_chunk == 512
    assert served.pool.pages_by_kind() == _TRI_PAGES
    patterns = {
        kind: re.compile(harness_json(_ROOT, f"{kind}_attn_roofline.serve")
                         ["pattern"])
        for kind in ("full", "window")}
    for hlo, lines in served.exes.values():
        assert sum(bool(patterns["full"].search(x)) for x in lines) == 1
        assert sum(bool(patterns["window"].search(x)) for x in lines) == 2
        assert len(re.findall(r"%grouped_matmul[.\d]* = ", hlo)) == 6
        assert _mosaic_calls(hlo) == 3 + 6
        pools = []
        for kind, layers in (("full", 1), ("window4096", 2)):
            pages = _TRI_PAGES[kind]
            assert _pool_copies(hlo, pages, 128) == []
            params = _pool_parameters(hlo, f"{pages},128,1024")
            assert len(params) == 2 * layers       # K and V
            assert [lay for _, lay in params
                    if not lay.startswith("{2,1,0")] == []
            pools += params
        assert {num for num, _ in pools} <= _aliased_parameters(hlo)


def harness_json(root, metric):
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Kimi-VL through the decode lane (benchmark/configs/kimi-vl-a3b-ep1.json):
# dense latent attention in its two forms and the tower's bidirectional
# attention at the published widths, and the engine's executables: the
# decode step, the prefill chunk, one encoder an image shape
# ---------------------------------------------------------------------------

_KIMI_PAGES, _KIMI_PAGE = 16 * 262 + 1, 128


def test_mla_kernels_at_kimi_widths(chip):
    bf = jnp.bfloat16
    pool = ((_KIMI_PAGES, _KIMI_PAGE, 640), bf)
    hlo = _compile(
        lambda ql, qr, pages, pt, qs: prims.paged_mla_attention(
            ql, qr, pages, pt, qs, sm_scale=192 ** -0.5),
        chip, ((16, 1, 16, 512), jnp.float32), ((16, 1, 16, 64), jnp.float32),
        pool, ((16, 262), jnp.int32), ((16,), jnp.int32))
    assert _mosaic_calls(hlo) == 1 and "%paged_mla_attention" in hlo
    hlo = _compile(
        lambda qn, qr, pages, pt, qs, uk, uv: prims.mla_chunk_attention(
            qn, qr, pages, pt, qs, uk, uv, sm_scale=192 ** -0.5),
        chip, ((1, 512, 16, 128), jnp.float32),
        ((1, 512, 16, 64), jnp.float32), pool, ((1, 262), jnp.int32),
        ((1,), jnp.int32), ((16, 128, 512), bf), ((16, 512, 128), bf))
    assert _mosaic_calls(hlo) == 1 and "%mla_chunk_attention" in hlo
    assert _pool_copies(hlo, _KIMI_PAGES, _KIMI_PAGE) == []


@pytest.mark.parametrize("patches", [1024, 4096, 6144])
def test_vit_attention_at_the_tower_shapes(chip, patches):
    qkv = ((16, patches, 128), jnp.bfloat16)
    hlo = _compile(lambda q, k, v: prims.vit_attention(
        q, k, v, sm_scale=72 ** -0.5), chip, qkv, qkv, qkv)
    assert _mosaic_calls(hlo) == 1 and "%vit_attention" in hlo


def test_kimi_vl_decode_engine_executables():
    """The prefill chunk, the decode step and the three encoders of
    Kimi-VL at the benchmark's widths, pool, slots and image shapes (two
    of its six decoder layers, the dense one and an expert one, and two
    of the tower's six blocks): a layer one latent-attention Mosaic call
    (latent space in the step, head space in the chunk), three grouped
    products an expert layer, one attention call a tower block; the
    latent pool and the row staging go through every executable that
    writes them UNCOPIED and donated."""
    served = _served("kimi-vl-a3b-ep1")
    assert served.prefill_chunk == 512
    assert served.pool.num_pages == _KIMI_PAGES
    assert served.image_rows["staging_rows"] == 1536 + 512
    staging = "2048,1,2048"
    chunk, step, *encoders = [hlo for hlo, _ in served.exes.values()]
    assert len(encoders) == 3
    assert chunk.count("%mla_chunk_attention") >= 2
    assert "%paged_mla_attention" not in chunk
    assert step.count("%paged_mla_attention") >= 2
    assert "%mla_chunk_attention" not in step
    for hlo in (chunk, step):
        assert _mosaic_calls(hlo) == 2 + 3
        assert _pool_copies(hlo, _KIMI_PAGES, _KIMI_PAGE) == []
        params = _pool_parameters(
            hlo, f"{_KIMI_PAGES},{_KIMI_PAGE},640")
        assert len(params) == 2                # one a layer
        assert [lay for _, lay in params
                if not lay.startswith("{2,1,0")] == []
        assert {num for num, _ in params} <= _aliased_parameters(hlo)
    # the chunk reads the staged rows, an encoder writes them in place
    assert len(_pool_parameters(chunk, staging)) == 1
    assert _pool_parameters(step, staging) == []
    for hlo in encoders:
        assert _mosaic_calls(hlo) == 2 and "%vit_attention" in hlo
        (row,) = _pool_parameters(hlo, staging)
        assert row[0] in _aliased_parameters(hlo)


# ---------------------------------------------------------------------------
# Olmo-Hybrid through the decode lane (benchmark/configs/
# olmo-hybrid-7b-pp4.json): the two delta-rule kernels at the published
# widths, and the engine's two executables over K/V pages AND per-sequence
# state blocks
# ---------------------------------------------------------------------------

_OLMO_PAGES, _OLMO_BLOCKS = 16 * 98 + 1, 18
_OLMO_STATE = ((_OLMO_BLOCKS, 96, 30 * 192), jnp.float32)


def _state_copies(hlo, shape):
    """Lines of the compiled module that copy or transpose a whole state
    tensor of ``shape`` (``18,96,5760``)."""
    return [line.strip()[:160] for line in hlo.splitlines()
            if re.match(rf"\s*(?:ROOT )?%\S+ = \w+\[{shape}\]\S* "
                        r"(copy|copy-start|transpose)\(", line)]


@pytest.mark.parametrize("form", ["step", "chunk"])
def test_gated_delta_kernels_at_olmo_widths(chip, form):
    """30 heads of 96 keys x 192 values over the float32 state tensor
    [18, 96, 5760]: the step over 16 slots (the tensor aliased, rewritten
    where it lies) and the chunk over 512 tokens (one block sliced out,
    solved in sub-chunks of 64, written back).  Neither copies the
    tensor."""
    f32 = jnp.float32
    n = 16 if form == "step" else 512
    rows = [((n, 30, 96), f32), ((n, 30, 96), f32), ((n, 30, 192), f32),
            ((n, 30), f32), ((n, 30), f32), _OLMO_STATE]
    if form == "step":
        fn = prims.gated_delta_step
        rows.append(((16,), jnp.int32))
    else:
        def fn(q, k, v, g, beta, state, block, fresh):
            return prims.gated_delta_chunk(q, k, v, g, beta, state,
                                           block[0], fresh[0])
        rows += [((1,), jnp.int32), ((1,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in rows]
    with lowering_for("tpu"):
        hlo = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile() \
            .as_text()
    assert _mosaic_calls(hlo) == 1
    assert len(re.findall(rf"%gated_delta_{form}[.\d]* = ", hlo)) == 1
    assert _state_copies(hlo, "18,96,5760") == []
    assert 5 in _aliased_parameters(hlo)


def test_olmo_hybrid_decode_engine_executables():
    """The prefill chunk and the decode step of Olmo-Hybrid at the
    benchmark's widths, pool and slots (one period of its two: linear,
    linear, linear, full): a delta-rule call a linear layer under the
    names the benchmark's two patterns read, ONE plain paged call whose
    page table is its first operand (what ``paged_attn_roofline.serve``
    finds, in the decode step and not in the chunk, and finds nothing
    else by), and both kinds of cache — K/V pages and per-sequence state
    blocks — donated, row-major and UNCOPIED."""
    served = _served("olmo-hybrid-7b-pp4")
    assert served.prefill_chunk == 512
    assert served.pool.num_pages == _OLMO_PAGES
    assert served.pool.state_blocks == _OLMO_BLOCKS
    gdn = {form: re.compile(harness_json(_ROOT, f"gdn_{metric}.serve")
                            ["pattern"])
           for form, metric in (("chunk", "chunk_mxu_share"),
                                ("step", "step_roofline"))}
    seen_by_roofline = []
    for form in ("chunk", "step"):
        hlo, lines = served.exes[form]
        seen_by_roofline.append(sum(
            bool(_roofline_pattern().search(x)) for x in lines))
        assert sum(bool(gdn[form].search(x)) for x in lines) == 3
        other = "step" if form == "chunk" else "chunk"
        assert sum(bool(gdn[other].search(x)) for x in lines) == 0
        assert _mosaic_calls(hlo) == 3 + 1
        assert _pool_copies(hlo, _OLMO_PAGES, 128) == []
        kv = _pool_parameters(hlo, f"{_OLMO_PAGES},128,3840")
        assert len(kv) == 2                        # K and V
        state = _pool_parameters(hlo, "18,96,5760")
        tails = _pool_parameters(hlo, "18,34560")
        assert len(state) == len(tails) == 3       # a linear layer
        assert _state_copies(hlo, "18,96,5760") == []
        assert _state_copies(hlo, "18,34560") == []
        assert [lay for _, lay in kv + state
                if not lay.startswith("{2,1,0")] == []
        assert {num for num, _ in kv + state + tails} <= \
            _aliased_parameters(hlo)
    assert seen_by_roofline == [0, 1]


# ---------------------------------------------------------------------------
# Kimi-Linear through the decode lane (benchmark/configs/
# kimi-linear-48b-ep4.json): the two KDA kernels and both latent forms at
# the published widths and 32 SLOTS, and the engine's two executables over
# latent pages AND per-sequence state blocks
# ---------------------------------------------------------------------------

_KLIN_PAGES, _KLIN_BLOCKS = 32 * 272 + 1, 34
_KLIN_STATE = ((_KLIN_BLOCKS, 128, 32 * 128), jnp.float32)


@pytest.mark.parametrize("form", ["step", "chunk"])
def test_kda_kernels_at_kimi_linear_widths(chip, form):
    """32 heads of 128 keys x 128 values, a decay a key channel, over the
    float32 state tensor [34, 128, 4096]: the step over 32 slots (the
    tensor aliased, rewritten where it lies) and the chunk over 512
    tokens (one block sliced out, solved in sub-chunks of 64 with blocks
    of 8, written back).  Neither copies the tensor."""
    f32 = jnp.float32
    n = 32 if form == "step" else 512
    rows = [((n, 32, 128), f32)] * 4 + [((n, 32), f32), _KLIN_STATE]
    if form == "step":
        fn = prims.kda_step
        rows.append(((32,), jnp.int32))
    else:
        def fn(q, k, v, g, beta, state, block, fresh):
            return prims.kda_chunk(q, k, v, g, beta, state, block[0],
                                   fresh[0])
        rows += [((1,), jnp.int32), ((1,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in rows]
    with lowering_for("tpu"):
        hlo = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile() \
            .as_text()
    assert _mosaic_calls(hlo) == 1
    assert len(re.findall(rf"%kda_{form}[.\d]* = ", hlo)) == 1
    assert "%gated_delta" not in hlo
    assert _state_copies(hlo, "34,128,4096") == []
    assert 5 in _aliased_parameters(hlo)


def test_mla_kernels_at_kimi_linear_widths(chip):
    """Both latent forms at 32 heads: the decode body over 32 slots x 32
    query rows, the chunk's head-space form over 32 heads of 512
    queries; the pool of 8705 pages is not copied."""
    bf = jnp.bfloat16
    pool = ((_KLIN_PAGES, 128, 640), bf)
    hlo = _compile(
        lambda ql, qr, pages, pt, qs: prims.paged_mla_attention(
            ql, qr, pages, pt, qs, sm_scale=192 ** -0.5),
        chip, ((32, 1, 32, 512), jnp.float32), ((32, 1, 32, 64), jnp.float32),
        pool, ((32, 272), jnp.int32), ((32,), jnp.int32))
    assert _mosaic_calls(hlo) == 1 and "%paged_mla_attention" in hlo
    assert _pool_copies(hlo, _KLIN_PAGES, 128) == []
    hlo = _compile(
        lambda qn, qr, pages, pt, qs, uk, uv: prims.mla_chunk_attention(
            qn, qr, pages, pt, qs, uk, uv, sm_scale=192 ** -0.5),
        chip, ((1, 512, 32, 128), jnp.float32),
        ((1, 512, 32, 64), jnp.float32), pool, ((1, 272), jnp.int32),
        ((1,), jnp.int32), ((32, 128, 512), bf), ((32, 512, 128), bf))
    assert _mosaic_calls(hlo) == 1 and "%mla_chunk_attention" in hlo
    assert _pool_copies(hlo, _KLIN_PAGES, 128) == []


def test_kimi_linear_decode_engine_executables():
    """The prefill chunk and the decode step of Kimi-Linear at the
    benchmark's widths, pool and 32 slots (one period of its two: KDA,
    KDA, KDA, latent; the first layer dense, three expert layers): a KDA
    call a KDA layer under the names the benchmark's two new patterns
    read, one latent call (latent space in the step, head space in the
    chunk) under the names ``mla_decode_roofline.serve`` and
    ``mla_chunk_mxu_share.serve`` read, three grouped products an expert
    layer, and both kinds of cache (latent pages and per-sequence state
    blocks) donated, row-major and UNCOPIED."""
    served = _served("kimi-linear-48b-ep4")
    assert served.prefill_chunk == 512
    assert served.pool.num_pages == _KLIN_PAGES
    assert served.pool.state_blocks == _KLIN_BLOCKS
    reads = {form: [re.compile(harness_json(_ROOT, metric)["pattern"])
                    for metric in metrics]
             for form, metrics in (
                 ("chunk", ("kda_chunk_mxu_share.serve",
                            "mla_chunk_mxu_share.serve")),
                 ("step", ("kda_step_roofline.serve",
                           "mla_decode_roofline.serve")))}
    grouped = re.compile(harness_json(_ROOT, "moe_ffn_roofline.serve")
                         ["pattern"])
    for form in ("chunk", "step"):
        hlo, lines = served.exes[form]
        other = "step" if form == "chunk" else "chunk"
        kda_mine, mla_mine = reads[form]
        assert sum(bool(kda_mine.search(x)) for x in lines) == 3
        assert sum(bool(mla_mine.search(x)) for x in lines) == 1
        for pattern in reads[other]:
            assert sum(bool(pattern.search(x)) for x in lines) == 0
        assert sum(bool(grouped.search(x)) for x in lines) == 3 * 3
        assert "%gated_delta" not in hlo
        assert _mosaic_calls(hlo) == 3 + 1 + 9
        assert _pool_copies(hlo, _KLIN_PAGES, 128) == []
        latent = _pool_parameters(hlo, f"{_KLIN_PAGES},128,640")
        state = _pool_parameters(hlo, "34,128,4096")
        tails = _pool_parameters(hlo, "34,36864")
        assert len(latent) == 1                    # ONE row tensor a layer
        assert len(state) == len(tails) == 3       # a KDA layer
        assert _state_copies(hlo, "34,128,4096") == []
        assert _state_copies(hlo, "34,36864") == []
        assert [lay for _, lay in latent + state
                if not lay.startswith("{2,1,0")] == []
        assert {num for num, _ in latent + state + tails} <= \
            _aliased_parameters(hlo)


# ---------------------------------------------------------------------------
# MiMo-V2.5 through the decode lane (benchmark/configs/mimo-v2.5-ep16.json):
# the asymmetric paged kernels (K heads of 192 beside V heads of 128, the
# window layers' sink) at the published widths, and the engine's two
# executables over a pool whose ROWS go by cache kind
# ---------------------------------------------------------------------------

_MIMO_PAGES = {"full": 16 * 272 + 1, "window128": 16 * 6 + 1}


@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("b,t", [(16, 1), (1, 512)])
def test_paged_attention_asym_and_sink_at_mimo_widths(chip, b, t, window):
    """64 query heads of 192 on 4 (full) or 8 (window) K/V heads, V heads
    of 128, over bf16 pools [pages, 128, 768 | 1536] and [.., 512 | 1024]
    as stored, at a 34k-token page table, as a decode step and as a
    512-token chunk see them; the window layers carry a sink; the
    kernel's name says which form it is."""
    n_kv = 4 if window is None else 8
    pages = _MIMO_PAGES["full" if window is None else "window128"]
    shapes = [((b, 64, t, 192), jnp.float32),
              ((pages, 128, n_kv * 192), jnp.bfloat16),
              ((pages, 128, n_kv * 128), jnp.bfloat16),
              ((b, 272), jnp.int32), ((b,), jnp.int32)]
    if window is None:
        def fn(q, k, v, pt, qs):
            return prims.paged_attention(q, k, v, pt, qs)
        name = "paged_attention_grouped_asym"
    else:
        shapes.append(((64,), jnp.float32))

        def fn(q, k, v, pt, qs, sinks):
            return prims.paged_attention(q, k, v, pt, qs, window=window,
                                         sinks=sinks)
        name = "paged_attention_grouped_window_asym_sink"
    hlo = _compile(fn, chip, *shapes)
    assert _mosaic_calls(hlo) == 1
    assert len(re.findall(rf"%{name}[.\d]* = ", hlo)) == 1
    assert _pool_copies(hlo, pages, 128) == []


def test_mimo_decode_engine_executables():
    """The prefill chunk and the decode step of MiMo-V2.5 at the
    benchmark's widths, pool and slots (three of its seven layers: the
    dense full one, a window and a full expert layer... cut to the dense
    full layer, a window expert layer and the full expert layer): a
    paged call a layer, named by its form — which is what the benchmark's
    three attention patterns read —, three grouped products an expert
    layer, each kind's pool tensors at that kind's pages AND widths,
    donated, row-major and UNCOPIED."""
    served = _served("mimo-v2.5-ep16")
    assert served.prefill_chunk == 512
    assert served.pool.pages_by_kind() == _MIMO_PAGES
    # the issue's bytes, at the configuration's seven layers
    per_page = 128 * 2
    assert served.pool.kind_bytes("full") == 2 * 4353 * per_page * 1280
    assert served.pool.kind_bytes("window128") == 97 * per_page * 2560
    patterns = {
        name: re.compile(harness_json(_ROOT, name + ".serve")["pattern"])
        for name in ("sink_window_attn_roofline", "asym_full_attn_roofline",
                     "asym_attn_chunk_mxu_share", "full_attn_roofline",
                     "window_attn_roofline")}
    for which, (hlo, lines) in served.exes.items():

        def seen(name):
            return sum(bool(patterns[name].search(x)) for x in lines)

        assert seen("asym_full_attn_roofline") == 2
        assert seen("sink_window_attn_roofline") == 1
        assert seen("asym_attn_chunk_mxu_share") == 3
        # Trinity's patterns find none of this model's calls
        assert seen("full_attn_roofline") == 0
        assert seen("window_attn_roofline") == 0
        assert len(re.findall(r"%grouped_matmul[.\d]* = ", hlo)) == 6
        assert _mosaic_calls(hlo) == 3 + 6, which
        pools = []
        for kind, layers, heads in (("full", 2, 4), ("window128", 1, 8)):
            pages = _MIMO_PAGES[kind]
            assert _pool_copies(hlo, pages, 128) == []
            for width in (heads * 192, heads * 128):    # K, V
                params = _pool_parameters(hlo, f"{pages},128,{width}")
                assert len(params) == layers
                assert [lay for _, lay in params
                        if not lay.startswith("{2,1,0")] == []
                pools += params
        assert {num for num, _ in pools} <= _aliased_parameters(hlo)


# ---------------------------------------------------------------------------
# Qwen3-Next through the decode lane (benchmark/configs/
# qwen3-next-80b-ep4.json): the delta-rule kernels' grouped-head bodies
# (32 value heads on 16 key heads), the grouped paged kernel at heads of
# 256 and 8 queries a K/V head, the grouped product at 128 experts 512
# wide, all at 64 SLOTS, and the engine's two executables
# ---------------------------------------------------------------------------

_Q3N_PAGES, _Q3N_BLOCKS = 64 * 140 + 1, 66
_Q3N_STATE = ((_Q3N_BLOCKS, 128, 32 * 128), jnp.float32)


@pytest.mark.parametrize("form", ["step", "chunk"])
def test_gated_delta_kernels_at_qwen3_next_widths(chip, form):
    """32 value heads on 16 key heads of 128 x 128 over the float32 state
    tensor [66, 128, 4096]: the step over 64 slots (the tensor aliased,
    rewritten where it lies; eight rows of q and k a tile of sixteen
    value heads) and the chunk over 512 tokens (q and k blocks found at
    h // 2).  Neither copies the tensor, and q and k are not repeated in
    memory: no [.., 32, 128] tensor of theirs is made."""
    f32 = jnp.float32
    n = 64 if form == "step" else 512
    rows = [((n, 16, 128), f32), ((n, 16, 128), f32), ((n, 32, 128), f32),
            ((n, 32), f32), ((n, 32), f32), _Q3N_STATE]
    if form == "step":
        fn = prims.gated_delta_step
        rows.append(((64,), jnp.int32))
    else:
        def fn(q, k, v, g, beta, state, block, fresh):
            return prims.gated_delta_chunk(q, k, v, g, beta, state,
                                           block[0], fresh[0])
        rows += [((1,), jnp.int32), ((1,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in rows]
    with lowering_for("tpu"):
        hlo = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile() \
            .as_text()
    assert _mosaic_calls(hlo) == 1
    assert len(re.findall(rf"%gated_delta_{form}[.\d]* = ", hlo)) == 1
    assert _state_copies(hlo, "66,128,4096") == []
    assert 5 in _aliased_parameters(hlo)


@pytest.mark.parametrize("b,t", [(64, 1), (1, 512)])
def test_paged_attention_grouped_at_qwen3_next_widths(chip, b, t):
    """16 query heads on 2 K/V heads of 256 (8 queries a K/V head, the
    widest head and the largest group the grouped bodies have run) over a
    bf16 pool [8961, 128, 512] at a 17 920-token page table, as a decode
    step of 64 slots and as a 512-token chunk see them."""
    pool = ((_Q3N_PAGES, 128, 512), jnp.bfloat16)
    hlo = _compile(
        lambda q, kp, vp, pt, qs: prims.paged_attention(
            q, kp, vp, pt, qs, sm_scale=256 ** -0.5), chip,
        ((b, 16, t, 256), jnp.float32), pool, pool, ((b, 140), jnp.int32),
        ((b,), jnp.int32))
    assert _mosaic_calls(hlo) == 1
    assert len(re.findall(r"%paged_attention_grouped[.\d]* = ", hlo)) == 1
    assert _pool_copies(hlo, _Q3N_PAGES, 128) == []


@pytest.mark.parametrize("rows,k,n", [(640, 2048, 512), (640, 512, 2048),
                                      (5120, 2048, 512), (5120, 512, 2048)])
def test_grouped_matmul_at_qwen3_next_widths(chip, rows, k, n):
    """The expert layer's product over 128 held experts 512 wide: a
    64-row decode step's 640 pick rows and a chunk's 5120 (10 picks a
    token), blocks of [2048, 512] and [512, 2048], 2 MiB each."""
    hlo = _compile(lambda x, w, g: prims.grouped_matmul(x, w, g), chip,
                   ((rows, k), jnp.bfloat16), ((128, k, n), jnp.bfloat16),
                   ((128,), jnp.int32))
    assert _mosaic_calls(hlo) == 1 and "%grouped_matmul" in hlo


def test_qwen3_next_decode_engine_executables():
    """The prefill chunk and the decode step of Qwen3-Next at the
    benchmark's widths, pool and 64 slots (one period of its two: linear,
    linear, linear, full; four expert layers): a delta-rule call a linear
    layer under the names ``gdn_chunk_mxu_share.serve`` and
    ``gdn_step_roofline.serve`` read, one grouped paged call under the
    name ``full_attn_roofline.serve`` reads, three grouped products an
    expert layer, and both kinds of cache (K/V pages and per-sequence
    state blocks) donated, row-major and UNCOPIED."""
    served = _served("qwen3-next-80b-ep4")
    assert served.prefill_chunk == 512
    assert served.pool.num_pages == _Q3N_PAGES
    assert served.pool.state_blocks == _Q3N_BLOCKS
    gdn = {form: re.compile(harness_json(_ROOT, f"gdn_{metric}.serve")
                            ["pattern"])
           for form, metric in (("chunk", "chunk_mxu_share"),
                                ("step", "step_roofline"))}
    full = re.compile(harness_json(_ROOT, "full_attn_roofline.serve")
                      ["pattern"])
    grouped = re.compile(harness_json(_ROOT, "moe_ffn_roofline.serve")
                         ["pattern"])
    for form in ("chunk", "step"):
        hlo, lines = served.exes[form]
        other = "step" if form == "chunk" else "chunk"
        assert sum(bool(gdn[form].search(x)) for x in lines) == 3
        assert sum(bool(gdn[other].search(x)) for x in lines) == 0
        assert sum(bool(full.search(x)) for x in lines) == 1
        assert sum(bool(grouped.search(x)) for x in lines) == 3 * 4
        assert _mosaic_calls(hlo) == 3 + 1 + 12
        assert _pool_copies(hlo, _Q3N_PAGES, 128) == []
        kv = _pool_parameters(hlo, f"{_Q3N_PAGES},128,512")
        state = _pool_parameters(hlo, "66,128,4096")
        tails = _pool_parameters(hlo, "66,24576")
        assert len(kv) == 2                        # K and V
        assert len(state) == len(tails) == 3       # a linear layer
        assert _state_copies(hlo, "66,128,4096") == []
        assert _state_copies(hlo, "66,24576") == []
        assert [lay for _, lay in kv + state
                if not lay.startswith("{2,1,0")] == []
        assert {num for num, _ in kv + state + tails} <= \
            _aliased_parameters(hlo)



# ---------------------------------------------------------------------------
# What the compiled executables COPY (PR 42).  Where a product is reshaped
# into heads that are no whole lane tiles, XLA:TPU's layout assignment
# pays with a copy of the read-only WEIGHT through HBM in every run
# (MiMo's 100.7-MB W_q in every layer of a chunk and in one of a step);
# `weight_matmul` pins its product's layout where the caller states such
# heads under a weight as large (ops/mla_ops.py _pin_product).  A copy
# whose result lies in fast memory (`S(1)` in its layout) is NOT such a
# pass: it is XLA's fetch of the weight, its one read from HBM, and
# taking those away cost Olmo-Hybrid's step 3.4% on the chip (PERF.md
# section 6, PR 42): every executable the rule does not pin compiles
# what it compiled
# ---------------------------------------------------------------------------


def _copies(hlo):
    """(dims, bytes, whether into fast memory) of every ``copy`` of the
    compiled module, those inside fusions too."""
    from paddle_tpu.observability.profiling import _shape_bytes

    return [(tuple(int(d) for d in dims.split(",") if d),
             _shape_bytes(f"{dtype}[{dims}]"), "S(1)" in layout)
            for dtype, dims, layout in re.findall(
                r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\](\S*) copy\(", hlo,
                re.M)]


def _true_branch(hlo):
    """The text of the true computation of the module's one
    ``conditional`` ('' where it holds none): in a prefill chunk, what
    only a prompt's last chunk runs (PR 48)."""
    m = re.search(r" conditional\(.*branch_computations=\{%\S+, (%[^\s}]+)\}",
                  hlo)
    if m is None:
        return ""
    start = hlo.index(f"\n{m.group(1)} (")
    return hlo[start:hlo.index("\n}\n", start)]


def _matrix_parameters(hlo):
    """Shapes of the entry's 2-D parameters (the weights), and their
    transposes."""
    entry = hlo[hlo.index("ENTRY "):]
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"= \w+\[(\d+,\d+)\]\S* parameter\(",
                                     entry)}
    return shapes | {s[::-1] for s in shapes}


# {(config, executable): (MB of copies at the parent 564dea6, MB now)}, at
# this file's cuts, fetches into fast memory included (of MiMo's step
# 227.8 MB were such fetches and 101.4 one W_q through HBM; of its chunk
# 136.6 and 321.7)
_COPY_MB = {
    ("mimo-v2.5-ep16", "chunk"): (458.2, 131.1),
    ("mimo-v2.5-ep16", "step"): (329.2, 0.8),
    ("trinity-large-ep8", "chunk"): (289.4, 289.4),
    ("trinity-large-ep8", "step"): (134.7, 134.7),
    ("glm-5-ep16", "chunk"): (359.9, 359.9),
    ("glm-5-ep16", "step"): (179.1, 179.1),
    ("olmo-hybrid-7b-pp4", "chunk"): (213.1, 213.1),
    ("olmo-hybrid-7b-pp4", "step"): (8.5, 8.5),
    ("kimi-vl-a3b-ep1", "chunk"): (54.1, 33.1),
    ("kimi-vl-a3b-ep1", "step"): (26.7, 1.7),
    ("kimi-vl-a3b-ep1", "tower0"): (177.2, 177.2),
    ("kimi-vl-a3b-ep1", "tower1"): (257.4, 257.4),
    ("kimi-vl-a3b-ep1", "tower2"): (85.2, 85.2),
    # new in PR 46 (no parent): what the cut's executables copy today,
    # activations all (the chunk's [512, 32, 128] operands laid heads
    # first for the KDA kernel)
    ("kimi-linear-48b-ep4", "chunk"): (424.4, 424.4),
    ("kimi-linear-48b-ep4", "step"): (23.2, 23.2),
    # new in PR 49 (no parent): what the cut's executables copy today; of
    # the step's 62.1 MB 33.6 are XLA's fetch of the full layer's W_q
    # into fast memory and 18.9 the convolution's carried inputs laid out
    # for its update
    ("qwen3-next-80b-ep4", "chunk"): (288.4, 288.4),
    ("qwen3-next-80b-ep4", "step"): (62.2, 62.2),
}
# {(config, executable): shapes of weights of 1 MiB or more that a decode
# step still relays through HBM}.  Qwen3-Next's full layer: XLA:TPU
# transposes W_k [2048, 512] (2 MiB of the ~6 GB a step reads) on its way
# into the product; its head of 256 is whole lane tiles, so
# `_pin_product`'s rule does not apply, and the copy is left to XLA
# (PERF.md section 7)
_WEIGHT_RELAYS_LEFT = {
    ("qwen3-next-80b-ep4", "step"): [(512, 2048)],
}
_PINNED_CHUNKS = {("mimo-v2.5-ep16", "chunk"), ("kimi-vl-a3b-ep1", "chunk"),
                  ("kimi-linear-48b-ep4", "chunk")}
# {(config, executable): MB copied inside the chunk's conditional}, which
# a prompt's LAST chunk runs and no other (PR 48).  Two held vocabularies
# are no whole lane tiles (25024 and 19360 columns): their head matrices
# arrive with the hidden dimension minor-most, the product inside the
# branch wants them row-major, and XLA:TPU copies them there, once a
# prompt, where the parent streamed them once a chunk; the four others'
# head matrices go in uncopied.  Qwen3-Next's 37984 columns (PR 49) are
# no whole lane tiles either
_COPY_MB_FINAL_CHUNK = {
    ("trinity-large-ep8", "chunk"): 153.7,    # bf16[3072,25024]
    ("glm-5-ep16", "chunk"): 237.9,           # bf16[6144,19360]
    ("qwen3-next-80b-ep4", "chunk"): 155.6,   # bf16[2048,37984]
}


@pytest.mark.parametrize("name,exe", list(_COPY_MB))
def test_served_executables_copy_no_more_than_they_did(name, exe):
    """(a) No decode step, and no chunk whose products the rule pins,
    copies a tensor of a weight's shape (or its transpose) of 1 MiB or
    more THROUGH HBM; (b) no executable, the tower's three included,
    copies more bytes a run than it did at the parent (half a MB of
    room), nor more than this table says it copies now; (c) what a chunk
    copies besides, inside the conditional that only a prompt's last
    chunk runs, is what ``_COPY_MB_FINAL_CHUNK`` says."""
    parent, now = _COPY_MB[(name, exe)]
    assert now <= parent
    hlo, _ = _served(name).exes[exe]
    last_chunk = _true_branch(hlo)
    assert bool(last_chunk) == (exe == "chunk")
    copies = _copies(hlo.replace(last_chunk, ""))
    assert sum(n for _, n, _ in copies) / 1e6 <= now + 0.5
    assert sum(n for _, n, _ in _copies(last_chunk)) / 1e6 <= (
        _COPY_MB_FINAL_CHUNK.get((name, exe), 0.0) + 0.5)
    if exe == "step" or (name, exe) in _PINNED_CHUNKS:
        weights = _matrix_parameters(hlo)
        assert len(weights) >= 8
        assert [dims for dims, n, fast in copies
                if dims in weights and n >= 2 ** 20 and not fast] == \
            _WEIGHT_RELAYS_LEFT.get((name, exe), [])


@pytest.mark.parametrize("name", list(_CUTS))
def test_a_served_chunk_reads_its_head_weight_under_the_conditional(name):
    """PR 48: compiled for the chip, the chunk keeps ONE `conditional`
    (XLA neither turned it into a select nor hoisted the product out),
    and the head's weight, an entry parameter, goes nowhere but into it:
    no fusion of the entry computation reads it and nothing copies it on
    the way in, so a chunk fed `pf_final` = 0 streams none of it.  The
    decode step holds no conditional."""
    exes = _served(name).exes
    assert " conditional(" not in exes["step"][0]
    hlo = exes["chunk"][0]
    entry = hlo[hlo.index("\nENTRY "):]
    (weight,) = set(re.findall(r"%readonly__\w+_head_w_0__[.\d]*", entry))
    uses = [line.split(" = ", 1)[1] for line in entry.splitlines()
            if weight in line.split(" = ", 1)[-1]
            and not line.lstrip().startswith("ENTRY")]
    kinds = [re.match(r"(?:\(.*?\)|\S+) ([\w-]+)\(", use).group(1)
             for use in uses]
    assert kinds and set(kinds) <= {"tuple", "conditional"}
    assert len(re.findall(r" conditional\(", hlo)) == 1

