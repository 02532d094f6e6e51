"""Kimi-VL through the decode lane (models/kimi_vl.py, serving/lane.py
``ImageEncoder``, kernels/primitives/mla.py): dense latent attention in
its two forms, every expert held, a vision tower whose rows stand at the
prompt's placeholder positions, staged on the device between the encoder
and the chunks that read them — against the plain reference
(benchmark/reference/kimi_vl.py, which imports nothing of the program) at
a tiny size with seeded float32 weights: hidden 64, 4 heads of 16 + 8 /
16, latent 32, 8 experts top-2 + 1 shared, tower width 48 with 4 heads of
12 and a 4 x 4 table, page 4, chunk 8."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import fluid, serving
from paddle_tpu import observability as obs
from paddle_tpu.models import kimi_vl
from paddle_tpu.ops import vision_ops
from paddle_tpu.serving import lane

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                       "kimi-vl-tiny.json")) as _f:
    CONFIG = json.load(_f)
ref = harness.load_module("reference", "kimi_vl.py")
PAGE, CHUNK, HOLD = 4, 8, CONFIG["media_placeholder_token_id"]


def _cfg(**over):
    return kimi_vl.KimiVLConfig(**dict(CONFIG["builder"]["config_args"],
                                       **over))


def _scope_with(weights):
    scope = fluid.Scope()
    for name, w in weights.items():
        scope.set(name, w)
    return scope


@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(CONFIG, 20260929)


def _image(rng, grid):
    """Pixels of an image of ``grid`` patches (patch 2)."""
    return rng.normal(0, 1, (2 * grid[0], 2 * grid[1], 3)).astype(np.float32)


def _prompt(rng, cfg, parts):
    """Token ids: an int is that many text tokens, a grid that image's
    placeholder run."""
    out = []
    for part in parts:
        if isinstance(part, int):
            out += rng.randint(1, HOLD, part).tolist()
        else:
            out += [HOLD] * cfg.image_rows(part)
    return out


def _served_gaps(weights, prompts, images, outs):
    gaps = []
    with jax.default_matmul_precision("highest"):
        for p, ims, o in zip(prompts, images, outs):
            logits = ref.served_logits(weights, CONFIG, p, o, images=ims)
            got = jnp.take_along_axis(
                logits, jnp.asarray(o, jnp.int32)[:, None], axis=1)[:, 0]
            gaps.append(float(jnp.max(jnp.max(logits, axis=1) - got)))
    return gaps


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "kimi_vl.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+(paddle_tpu|benchmark)", src,
                         re.M)


def _programs(cfg):
    """The whole-sequence program and one encoder (with its prepare
    program): between them every parameter."""
    out = []
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        kimi_vl.build_kimi_vl_lm(cfg)
    out.append(main)
    enc, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(enc, start), fluid.unique_name.guard():
        _, prepare = kimi_vl.build_kimi_vl_vision_encoder(cfg, 4, 4, 16)
    return out + [enc, prepare]


def test_program_parameters_are_the_references():
    want = {p.name: tuple(p.shape) for prog in _programs(_cfg())
            for p in prog.global_block().all_parameters()}
    have = {n: tuple(s) for n, (s, _, _) in ref.param_shapes(CONFIG).items()}
    assert have == want


def test_the_lane_declares_an_encoder_and_refuses_an_int8_pool():
    cfg = _cfg()
    decl = cfg.decode_lane()
    assert [r.name for r in decl.cache_rows(None)] == ["latent"]
    assert decl.cache_rows(None)[0].width == 128          # 40, lane-padded
    with pytest.raises(ValueError, match="no int8 form"):
        decl.cache_rows("int8")
    assert len(decl.device_counters) == 2 * 2      # 2 expert layers x 2
    enc = decl.encoder
    assert enc.shapes == [(4, 4), (2, 6)] and enc.row_width == 64
    assert [enc.rows_of(s) for s in enc.shapes] == [4, 3]
    image = enc.prepare(np.zeros((8, 8, 3), np.float32))
    assert image.shape == (4, 4) and image.rows == 4
    assert image.feeds["enc_patches"].shape == (16, 12)
    with pytest.raises(ValueError, match="multiples of 4"):
        enc.prepare(np.zeros((6, 8, 3), np.float32))


# ---------------------------------------------------------------------------
# the tower against the reference, a square and a non-square grid
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("force", [None, "pallas"])
@pytest.mark.parametrize("grid", [(4, 4), (2, 6)])
def test_the_tower_matches_the_reference(weights, grid, force):
    cfg = _cfg()
    rng = np.random.RandomState(grid[1])
    pixels = _image(rng, grid)
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=2, page_size=PAGE,
        max_len=32, attn_force=force, auto_start=False,
        name=f"tower-{grid[1]}-{force}")
    try:
        image = cfg.decode_lane().encoder.prepare(pixels)
        eng._run_encoder_feed(image, 3)
        got = np.asarray(eng.scope.get(lane.ROW_STAGING))[:, 0]
    finally:
        eng.close()
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.encode_image(weights, CONFIG, pixels))
    assert want.shape == (cfg.image_rows(grid), 64)
    np.testing.assert_allclose(got[3:3 + len(want)], want, rtol=2e-4,
                               atol=2e-4)
    assert not got[:3].any() and not got[3 + len(want):].any()


def test_the_table_is_resized_when_the_encoder_is_built_not_in_its_run(
        weights):
    cfg = _cfg()
    eng = serving.DecodeEngine(cfg, scope=_scope_with(weights), pool_slots=2,
                               page_size=PAGE, max_len=32, auto_start=False,
                               name="table")
    try:
        prog, _ = eng._encoder_for((2, 6))
        assert "bicubic_resize_table" not in [
            op.type for op in prog.global_block().ops]
        got = np.asarray(eng.scope.get(kimi_vl.pos_table_var_name((2, 6))))
    finally:
        eng.close()
    table = np.asarray(weights["kimi_vit_pos.w_0"])
    want = np.einsum("ih,hwd,jw->ijd", vision_ops.bicubic_matrix(4, 2),
                     table, vision_ops.bicubic_matrix(4, 6)).reshape(12, 48)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the program's kernel is the reference's, and a table of its own
    # size comes back as it is
    np.testing.assert_allclose(vision_ops.bicubic_matrix(4, 6),
                               np.asarray(ref.bicubic_weights(4, 6)),
                               atol=1e-6)
    np.testing.assert_allclose(vision_ops.bicubic_matrix(4, 4), np.eye(4),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# engine: images of two shapes, prefill chunks that straddle them, decode
# steps through the latent cache, against the reference's full forward
# ---------------------------------------------------------------------------

# text and images; the second prompt's chunks hold pieces of three images
LAYOUTS = (
    (5, (4, 4), 3, (2, 6), 6),
    (2, (2, 6), 1, (4, 4), 2, (2, 6), 1, (4, 4), 9),
    (11,),
    (1, (4, 4), 14),
)


def _requests(cfg):
    rng = np.random.RandomState(5)
    prompts = [_prompt(rng, cfg, parts) for parts in LAYOUTS]
    images = [[_image(rng, part) for part in parts
               if not isinstance(part, int)] for parts in LAYOUTS]
    return prompts, images


def _generate(weights, force=None, n_new=10, **engine):
    cfg = _cfg()
    prompts, images = _requests(cfg)
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=3, page_size=PAGE,
        max_len=64, attn_force=force, auto_start=False,
        name=f"kimi-{force}-{len(engine)}", **engine)
    try:
        assert eng.warmup() == 4      # chunk, step, two image shapes
        eng.start()
        outs = eng.generate(prompts, max_new_tokens=n_new, timeout=600,
                            images=images)
        eng.book_device_counters()
        return eng, prompts, images, outs, eng.stats()
    finally:
        eng.close()


@pytest.mark.parametrize("force", [None, "pallas"])
def test_generate_with_images_matches_the_reference(weights, force):
    before = harness.counters()
    eng, prompts, images, outs, stats = _generate(weights, force)
    gaps = _served_gaps(weights, prompts, images, outs)
    assert max(gaps) < 1e-3, gaps
    d = harness.delta(harness.counters(), before)
    name = eng.name
    n_images = sum(len(ims) for ims in images)
    runs = {k: v for k, v in d.items()
            if k.startswith("pt_decode_encoder_runs_total{" + name) and v}
    assert sum(runs.values()) == n_images
    assert set(runs) == {f"pt_decode_encoder_runs_total{{{name},4x4}}",
                         f"pt_decode_encoder_runs_total{{{name},2x6}}"}
    rows = sum(n for p in prompts for n in [p.count(HOLD)])
    assert d[f"pt_decode_prompt_tokens_total{{{name},image}}"] == rows
    assert d[f"pt_decode_prompt_tokens_total{{{name},text}}"] == sum(
        map(len, prompts)) - rows
    assert d[f"pt_decode_phase_seconds_total{{{name},encode}}"] > 0
    # nothing compiled after warm-up: one executable an image shape
    staging = stats["image_rows"]
    assert staging["shapes_built"] == [(2, 6), (4, 4)]
    assert staging["staging_rows"] == 4 + CHUNK
    assert 0 < staging["live_max"] <= staging["staging_rows"]
    # every pick lands on a held expert
    assert d[f"pt_moe_picks_total{{{name},absent}}"] == 0
    assert d[f"pt_moe_picks_total{{{name},held}}"] > 0


def test_ignoring_the_images_is_not_what_the_reference_computes(weights):
    """The rows matter: the same prompts served without their images'
    rows (the placeholder's own embedding at every image position) are
    far from the reference."""
    cfg = _cfg()
    prompts, images = _requests(cfg)
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=3, page_size=PAGE,
        max_len=64, auto_start=False, name="kimi-blind")
    try:
        eng._stage_image_rows = lambda req, ctx_len, valid: np.full(
            (1, CHUNK), -1, np.int32)
        eng.start()
        outs = eng.generate(prompts[:2], max_new_tokens=6, timeout=600,
                            images=images[:2])
    finally:
        eng.close()
    assert max(_served_gaps(weights, prompts[:2], images[:2], outs)) > 0.05


def test_an_eviction_replays_from_token_0_and_encodes_again(weights):
    before = harness.counters()
    # pages for two of the four sequences at most: the youngest goes
    eng, prompts, images, outs, stats = _generate(
        weights, n_new=12, num_pages=1 + 2 * 16 // 2 + 6)
    assert stats["evictions"] > 0
    assert max(_served_gaps(weights, prompts, images, outs)) < 1e-3
    d = harness.delta(harness.counters(), before)
    runs = sum(v for k, v in d.items() if k.startswith(
        "pt_decode_encoder_runs_total{" + eng.name))
    assert runs > sum(len(ims) for ims in images)


def test_admission_checks_placeholders_against_the_images(weights):
    cfg = _cfg()
    eng = serving.DecodeEngine(
        cfg, scope=_scope_with(weights), pool_slots=2, page_size=PAGE,
        max_len=64, auto_start=False, name="kimi-admit")
    rng = np.random.RandomState(1)
    try:
        with pytest.raises(ValueError, match="holds 4 placeholder ids"):
            eng.submit([1, 2] + [HOLD] * 4, 3)
        with pytest.raises(ValueError, match="give 3 rows"):
            eng.submit([1] + [HOLD] * 4, 3, images=[_image(rng, (2, 6))])
        with pytest.raises(ValueError, match="not one unbroken run"):
            eng.submit([HOLD, HOLD, 1, HOLD, HOLD], 3,
                       images=[_image(rng, (4, 4))])
        with pytest.raises(ValueError, match="the largest declared shape"):
            eng.submit([HOLD] * 6, 3, images=[_image(rng, (4, 6))])
    finally:
        eng.close()
    gpt_like = serving.DecodeEngine  # a lane without an encoder
    from paddle_tpu.models import glm
    gcfg = glm.GLMConfig.tiny()
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        glm.build_glm_lm(gcfg)
    scope = fluid.Scope()
    for p in main.global_block().all_parameters():
        scope.set(p.name, np.zeros(tuple(p.shape), np.float32))
    eng = gpt_like(gcfg, scope=scope, pool_slots=2, page_size=4, max_len=32,
                   auto_start=False, name="no-encoder")
    try:
        assert eng.stats()["image_rows"] is None
        with pytest.raises(ValueError, match="declares no image encoder"):
            eng.submit([1, 2, 3], 2, images=[np.zeros((8, 8, 3))])
    finally:
        eng.close()


def test_every_expert_held_is_the_uncut_layer(weights):
    """8 held of 8: the expert layer is the reference's whole layer, no
    pick lost (the share that is the whole)."""
    from paddle_tpu.fluid import layers
    from paddle_tpu.models import decode_blocks

    cfg = _cfg()
    rng = np.random.RandomState(2)
    x = rng.normal(0, 1, (1, 8, 64)).astype(np.float32)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        xin = fluid.data("x", [1, 8, 64], False, dtype="float32")
        out = layers.elementwise_add(xin, decode_blocks.expert_ffn(
            xin, 1, None, None, cfg, "kimi_layer_1", None))
    (got,) = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x}, fetch_list=[out], scope=_scope_with(weights))
    z = ref.sizes(CONFIG)
    z["scaling"] = float(CONFIG["routed_scaling_factor"])
    p = {k[len("kimi_layer_1_"):]: v for k, v in weights.items()
         if k.startswith("kimi_layer_1_")}
    with jax.default_matmul_precision("highest"):
        want = ref.finish_rows(
            jnp.asarray(x[0]), jnp.zeros((8, 64)),
            {k: v for k, v in p.items()
             if k not in ref.KEY_PARAMS + ref.QUERY_PARAMS},
            z=tuple(sorted(z.items())), eps=1e-5, dense=False,
            matmul=jnp.matmul)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _run_op(build, feed):
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        out = build()
    (got,) = fluid.Executor(fluid.CPUPlace()).run(
        main, feed=feed, fetch_list=[out], scope=fluid.Scope())
    return np.asarray(got)


def test_rope_2d_interleaved_turns_pairs_by_column_and_row():
    from paddle_tpu.fluid import layers

    rng = np.random.RandomState(0)
    gh, gw, heads, d = 2, 3, 2, 8
    x = rng.normal(0, 1, (gh * gw, heads, d)).astype(np.float32)
    got = _run_op(lambda: layers.rope_2d_interleaved(
        fluid.data("x", list(x.shape), False, dtype="float32"), gh, gw,
        100.0), {"x": x})
    want = x.copy()
    for n in range(gh * gw):
        row, col = divmod(n, gw)
        for j in range(d // 2):        # pair 2k by column, 2k + 1 by row
            ang = (col, row)[j % 2] * 100.0 ** (-4 * (j // 2) / d)
            a, b = x[n, :, 2 * j], x[n, :, 2 * j + 1]
            want[n, :, 2 * j] = a * np.cos(ang) - b * np.sin(ang)
            want[n, :, 2 * j + 1] = a * np.sin(ang) + b * np.cos(ang)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the reference's own spelling of the same angles
    ang = vision_ops.rope_2d_angles(gh, gw, d, 100.0)
    np.testing.assert_allclose(
        np.asarray(ref.turn_pairs(jnp.asarray(x), ang[:, None, :])), want,
        rtol=1e-5, atol=1e-5)


def test_select_embedding_rows_takes_staged_rows_where_indexed():
    from paddle_tpu.fluid import layers

    rng = np.random.RandomState(1)
    emb = rng.normal(0, 1, (1, 6, 4)).astype(np.float32)
    rows = rng.normal(0, 1, (5, 1, 4)).astype(np.float32)
    idx = np.asarray([[-1, 3, 4, -1, 0, -1]], np.int32)
    got = _run_op(lambda: layers.select_embedding_rows(
        fluid.data("emb", [1, 6, 4], False, dtype="float32"),
        fluid.data("rows", [5, 1, 4], False, dtype="float32"),
        fluid.data("idx", [1, 6], False, dtype="int32")),
        {"emb": emb, "rows": rows, "idx": idx})
    want = emb.copy()
    want[0, [1, 2, 4]] = rows[[3, 4, 0], 0]
    np.testing.assert_array_equal(got, want)
