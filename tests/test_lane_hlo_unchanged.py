"""The decode lane's executables of all eight models lower to the HLO
they lowered, compared BY TEXT: the two served programs of
`models/gpt.py` (float32 and int8 pools), `models/glm.py`,
`models/trinity.py`, `models/kimi_vl.py` (and its image encoder),
`models/olmo_hybrid.py`, `models/mimo.py`, `models/kimi_linear.py` and
`models/qwen3_next.py`,
both in the XLA form of their attention and with the Pallas kernels
interpreted (`attn_force="pallas"`), where the text holds the kernels'
own bodies.  A PR that means to change no executable proves it here; a
PR that changes one re-makes the digests it moved and says why.

The digests' history.  The gpt and glm twelve were made at commit
67a584c (PR 30's tree), before the pool had kinds (PR 31), the
`trinity.*` four at 3ff1d2d (PR 32's tree), before a lane could have an
encoder (PR 33), the `kimi_vl.*` six, `olmo_hybrid.*` four and `mimo.*`
four at e98f100 (PR 43's tree), before PR 44 moved the lanes' program
scaffold out of the model files into `serving/lane.py`, and the
`kimi_linear.*` four with its lane (PR 46); on the way `glm.pallas.*`
moved by design in PR 32 and PR 37 (the interpreted grouped product's
traced grid extent; one score / softmax / value update a grid step of
`sparse_mla_attention`) and `trinity.pallas.prefill` in PR 43 (the
grouped chunk body's geometry from the shapes).  **PR 47 re-made every
`*.prefill` and `*.decode` digest, all 32**: a served program takes ONE
packed int32 feed (`dec_feed` / `pf_feed`) in place of its five to
eight, so each text's parameter list is shorter and a slice, a reshape
and, for the three int64 pieces, a convert stand behind the one
parameter; nothing else of a program was touched (`models/`,
`kernels/` and `ops/` are the parent's), which the two
`kimi_vl.*.encoder` digests, whose program keeps its own feeds, hold:
they are PR 43's still.  What the packed feed carries and how it is laid
out is held by `tests/test_lane_feed.py`.  **PR 48 re-made the sixteen
`*.prefill` digests and no other**: the chunk's feed is one int32
longer (`pf_final`) and the chunk's head (the gather of the last valid
row, the final norm, the `[1, D] x [D, V]` product, log-softmax and
argmax) stands inside the true region of ONE `stablehlo.case` on that
piece, whose false region returns the token 0 and zeros
(`test_a_chunks_head_lies_in_the_true_region_only` reads the text for
it); the sixteen `*.decode` and two `*.encoder` digests are PR 47's and
PR 43's still, which is the proof that the decode step and the tower
were left alone.  **PR 49 added the `qwen3_next.*` four with its lane and
moved no other**: `gdn_inputs` (key heads beside value heads), both
delta-rule kernels (q and k found at `h // r`), `moe_ffn_held` (a
softmax router), `expert_ffn` (a gate on the shared expert) and
`rms_norm` (an offset on the gain) take their new behaviour from
arguments whose defaults trace what they traced, which the other 34
digests hold (`olmo_hybrid.pallas.*` and `kimi_linear.pallas.*` the
interpreted kernels' bodies among them).  After a deliberate change to
what these models compile, run `python tests/test_lane_hlo_unchanged.py`
and paste its output over GOLDEN, saying in the commit why they moved.
"""

import ast
import functools
import hashlib
import json
import pathlib
import re

import ml_dtypes
import numpy as np
import pytest

from paddle_tpu import fluid, serving
from paddle_tpu.models import (glm, gpt, kimi_linear, kimi_vl, mimo,
                               olmo_hybrid, qwen3_next, trinity)

GOLDEN = {
    "gpt.float32.None.prefill": "558a7809cf01862028c6dea6430fa34ac0de8a4a4913b2bfb96bde8855b3e5ec",
    "gpt.float32.None.decode": "c4315b4216b00cc6d20f7075317007a2d62957ddcfe28626552bcc5ec722d567",
    "gpt.int8.None.prefill": "0a585d5e4b4a29e58ee09c174fb9c1ebc8bcbeb7e4e6b61db0e32a10e9f31ac5",
    "gpt.int8.None.decode": "fcc118898d68aee07cf5dc7c957d99123b04b864bb4e8258bfc50dfd1afafff8",
    "glm.None.prefill": "bbc886e9b81f4423733191e527a77ca30801f2c999c2ef49812a0336ef0b49c6",
    "glm.None.decode": "e717ec4e4af8f2e2182ea0b7f864d17463ccc61c0b1eaf94be4b432d048bc191",
    "gpt.float32.pallas.prefill": "5d11e38d32806cbb5223686c3253dbf188447c46bffcd5756b8a074b3d0a564d",
    "gpt.float32.pallas.decode": "b8b9b9cf1f332b41c3fdcad335c8e5bc051bdfa6ad4bed3c2b7e2f51e16235bc",
    "gpt.int8.pallas.prefill": "33b00be68a19cd1c166d1b5973a26ff282d4654446a3990aa845bdf9b2cc733c",
    "gpt.int8.pallas.decode": "b6392bbacd705f4f6f30dad2d1d967f229bdfe8187c72d8d92534dc430f91e77",
    "glm.pallas.prefill": "ab5265b36cff15dc4296fd2133af4625ec773080acf0e39a6e13c38ead2b3167",
    "glm.pallas.decode": "a905524dbcb69117e35504829da2a46c4202f53112c10a10cd9719ac0de36693",
    "trinity.None.prefill": "d164041e74316e06bb80246019e7c7e43a53c2889a5ff04031388d0e82ae9997",
    "trinity.None.decode": "cc8d6fd21f5065d7415394414831a96d22e792dcf2fe55080fc45a52cadb4df9",
    "trinity.pallas.prefill": "afdff4f1c430b6311ac7068980f65cbdca634a9d57a8af66ffc3ba464676c254",
    "trinity.pallas.decode": "a202c1c5b5ab89aa1e4e25ce4111e91d77ab9141c59b12ed180e77048a8ac203",
    "kimi_vl.None.prefill": "0d1f98d236faa636b625381e9845b131251ad1cf764aa156b3ee0081410287f0",
    "kimi_vl.None.decode": "6994807fd51d4e1eb51814692a08014bc0664dc8cfc17e4c30c5667630b04e42",
    "kimi_vl.None.encoder": "6b20bcce5d7cebedc5d6ae00b8dd48f61001971a6e3ba52c451e2397133014b4",
    "kimi_vl.pallas.prefill": "5764d7b16274a73b652e8cfbf466c14094387e711c8754ae31f389e1aef5ec58",
    "kimi_vl.pallas.decode": "a83e021cbfe4a63173eb7903cf49fcc366808a292c2118ae3bbc38a10fa3b85e",
    "kimi_vl.pallas.encoder": "45645d79bbe5ee2fb023ea669853f92f3f037814b83388a0422c786d8f168d92",
    "olmo_hybrid.None.prefill": "1f251e786c696c45f33be955a143ae553cb1d4304a3ab770cd0ae0e52aefd242",
    "olmo_hybrid.None.decode": "7b60445602a67a9990cf19862ac8e2e3149c6b87e3f163fc2a5b3f0720cf5bc6",
    "olmo_hybrid.pallas.prefill": "85840af3796b2e1f3a99eeb08369b2eeaf55db49bb3e9af7cdaf19c64fc7d6d9",
    "olmo_hybrid.pallas.decode": "22fa1302f64eb2e96c9eb09fae00a75a97f5b50303bb65ecb056f187ca47ad12",
    "mimo.None.prefill": "fd123cc8f22b9547556249bf9d2b3cb558668f2dda17aa25acfa791e64a8c373",
    "mimo.None.decode": "fb8d239f74ce651284c231af36d52cb160b0b115192c1d280db1fdae5a6af4f8",
    "mimo.pallas.prefill": "bff28455be5e737260e73ba489aeb6ceb3ceb30903c61e8c93c3bc11545cb569",
    "mimo.pallas.decode": "c42947622e1a395273405b1da73c4ff6ef662e232571cfdb6193969776326fb4",
    "kimi_linear.None.prefill": "349a66b613bdc8973252960687c6ff123f59fde285f3bbddc9fe571a640ad5e0",
    "kimi_linear.None.decode": "d622ff070875cae920e4471d00899bedd0ccff3eee02ed7547c09bcb0663e881",
    "kimi_linear.pallas.prefill": "ee332d3c173c5a25ffa2ee57597047d180efbae6ae8630b4f93d190c0a0e0061",
    "kimi_linear.pallas.decode": "8512086f341e77354003c507d3b9520087affad4073f0affdeb47dbce80fa476",
    "qwen3_next.None.prefill": "36764fc3c5b75b895cbd780327e9feca92ab5c5934619e5abf1d9823284490db",
    "qwen3_next.None.decode": "25438bc7d7c325227de456244947876122ea040bb719d5411f85110d74b8cfe0",
    "qwen3_next.pallas.prefill": "a9566c62edfa817ac480aecd28b4dd540347d65af9f8221298d9e97b454be13d",
    "qwen3_next.pallas.decode": "f92217445a9a6b2777e738d650afd2b67c26369ff270153b293f5ffc97b8e670"
}


MODELS = ("gpt", "glm", "trinity", "kimi_vl", "olmo_hybrid", "mimo",
          "kimi_linear", "qwen3_next")


def _zero_scope(*builds, rng=None):
    """Zeros (with ``rng``: normal values of deviation 0.3) under every
    parameter of the programs ``builds`` build (a builder that returns
    ``(feeds, prepare program)``, the image encoder's, gives that
    program's too)."""
    scope = fluid.Scope()
    for build in builds:
        lm, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(lm, start), fluid.unique_name.guard():
            built = build()
        programs = [lm] + [b for b in (built if isinstance(built, tuple)
                                       else ()) if isinstance(b, fluid.Program)]
        for p in (p for prog in programs
                  for p in prog.global_block().all_parameters()):
            dtype = (ml_dtypes.bfloat16 if p.dtype == "bfloat16"
                     else np.dtype(p.dtype))
            value = (np.zeros(tuple(p.shape)) if rng is None
                     else rng.normal(0.0, 0.3, tuple(p.shape)))
            scope.set(p.name, value.astype(dtype))
    return scope


def _lowered(cfg, scope, force, **kw):
    eng = serving.DecodeEngine(
        cfg, scope=scope, place=fluid.CPUPlace(), pool_slots=3, page_size=4,
        max_len=32, attn_force=force, auto_start=False, name="hlo", **kw)
    try:
        return dict(zip(("prefill", "decode", "encoder"),
                        (low.as_text() for low in eng.lower())))
    finally:
        eng.close()


def _later_model(model):
    """(tiny config, the builders that name every parameter) of a model
    with one whole-sequence builder; kimi_vl's one declared image shape
    makes the engine's third executable."""
    if model == "glm":
        cfg = glm.GLMConfig.tiny()
        return cfg, [lambda: glm.build_glm_lm(cfg)]
    if model == "trinity":
        cfg = trinity.TrinityConfig.tiny()
        return cfg, [lambda: trinity.build_trinity_lm(cfg)]
    if model == "kimi_vl":
        cfg = kimi_vl.KimiVLConfig.tiny(image_grids=((4, 4),))
        return cfg, [lambda: kimi_vl.build_kimi_vl_lm(cfg),
                     lambda: kimi_vl.build_kimi_vl_vision_encoder(
                         cfg, 4, 4, 16)]
    if model == "olmo_hybrid":
        cfg = olmo_hybrid.OlmoHybridConfig.tiny()
        return cfg, [lambda: olmo_hybrid.build_olmo_hybrid_lm(cfg)]
    if model == "kimi_linear":
        cfg = kimi_linear.KimiLinearConfig.tiny(held_experts=4,
                                                first_expert=2)
        return cfg, [lambda: kimi_linear.build_kimi_linear_lm(cfg)]
    if model == "qwen3_next":
        cfg = qwen3_next.Qwen3NextConfig.tiny(held_experts=4, first_expert=4)
        return cfg, [lambda: qwen3_next.build_qwen3_next_lm(cfg)]
    # K heads of 192 beside V heads of 128, the published widths: the
    # asymmetric Pallas forms read whole 128-lane tiles and take no other
    cfg = mimo.MiMoConfig.tiny(
        head_dim=192, v_head_dim=128, num_hidden_layers=3,
        hybrid_layer_pattern=[0, 1, 1], moe_layer_freq=[0, 1, 1])
    return cfg, [lambda: mimo.build_mimo_lm(cfg)]


@functools.lru_cache(maxsize=None)
def texts(model, force):
    """{case: HLO text} of one model's executables."""
    force = None if force == "None" else force
    if model != "gpt":
        cfg, builds = _later_model(model)
        low = _lowered(cfg, _zero_scope(*builds), force, prefill_chunk=8)
        return {f"{model}.{force}.{which}": t for which, t in low.items()}
    cfg = gpt.GPTConfig.tiny()
    out = {}
    for pool_dtype in ("float32", "int8"):
        low = _lowered(
            cfg, _zero_scope(lambda: gpt.build_gpt_lm(cfg, is_test=True)),
            force, prefill_chunk=8, pool_dtype=pool_dtype)
        out.update({f"gpt.{pool_dtype}.{force}.{which}": t
                    for which, t in low.items()})
    return out


def digests():
    return {case: hashlib.sha256(text.encode()).hexdigest()
            for model in MODELS for force in ("None", "pallas")
            for case, text in texts(model, force).items()}


@pytest.mark.parametrize("force", ["None", "pallas"])
@pytest.mark.parametrize("model", MODELS)
def test_one_kind_lanes_lower_the_hlo_they_lowered(model, force):
    got = {case: hashlib.sha256(text.encode()).hexdigest()
           for case, text in texts(model, force).items()}
    assert got and got == {case: GOLDEN[case] for case in got}


@pytest.mark.parametrize("model", MODELS)
def test_a_chunks_head_lies_in_the_true_region_only(model):
    """The lowered chunk holds ONE `stablehlo.case`; its false region
    returns what it was handed, its true region holds the program's only
    product of one row by the vocabulary; the decode step holds no
    case."""
    for case, text in texts(model, "None").items():
        if not case.endswith(".prefill"):
            assert '"stablehlo.case"' not in text
            continue
        before, cased = text.split('"stablehlo.case"')
        skipped, ran = cased.split("\n    }, {\n", 1)
        ran, after = ran.split("\n    }) :", 1)
        assert [ln.split()[0] for ln in skipped.splitlines()[1:]] == [
            "stablehlo.return"]
        one_row = re.compile(
            r"stablehlo\.dot_general .*-> tensor<(?:1x)+(\d+)xf32>$", re.M)
        assert len(one_row.findall(ran)) == 1
        assert "@log_softmax" in ran and "@argmax" in ran
        assert not one_row.findall(before) and not one_row.findall(
            after.split("func.func", 1)[0])


def test_the_feed_contract_has_one_owner_and_model_files_no_private_siblings():
    """The engine's feed names are spelled in serving/lane.py and nowhere
    else in the package, and a model file imports no underscore name
    from another model file (models/decode_blocks.py holds the parts the
    decoders share)."""
    package = pathlib.Path(fluid.__file__).parent.parent
    spelled = [str(path.relative_to(package))
               for path in sorted(package.rglob("*.py"))
               if re.search(r'"(dec|pf)_[a-z_]+"', path.read_text())]
    assert spelled == ["serving/lane.py"]
    borrowed = [
        (path.name, node.module, alias.name)
        for path in sorted((package / "models").glob("*.py"))
        for node in ast.walk(ast.parse(path.read_text()))
        if isinstance(node, ast.ImportFrom) and node.level == 1
        and node.module != "decode_blocks"
        for alias in node.names if alias.name.startswith("_")]
    assert not borrowed


if __name__ == "__main__":
    print(json.dumps(digests(), indent=4))
