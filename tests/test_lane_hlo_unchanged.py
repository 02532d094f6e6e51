"""The decode lane's executables of all seven models lower to the HLO
they lowered, compared BY TEXT: the two served programs of
`models/gpt.py` (float32 and int8 pools), `models/glm.py`,
`models/trinity.py`, `models/kimi_vl.py` (and its image encoder),
`models/olmo_hybrid.py`, `models/mimo.py` and `models/kimi_linear.py`,
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
out is held by `tests/test_lane_feed.py`.  After a deliberate change to
what these models compile, run `python tests/test_lane_hlo_unchanged.py`
and paste its output over GOLDEN, saying in the commit why they moved.
"""

import ast
import hashlib
import json
import pathlib
import re

import ml_dtypes
import numpy as np
import pytest

from paddle_tpu import fluid, serving
from paddle_tpu.models import (glm, gpt, kimi_linear, kimi_vl, mimo,
                               olmo_hybrid, trinity)

GOLDEN = {
    "gpt.float32.None.prefill": "144ad6592526cc91fdeafd56f5f9c391f27f83e4dcbd53136bd8d030c33febd8",
    "gpt.float32.None.decode": "c4315b4216b00cc6d20f7075317007a2d62957ddcfe28626552bcc5ec722d567",
    "gpt.int8.None.prefill": "509cc9052ce6e1af543c3893c4779941f16416cdb11cb594f38bdc1cf72d40ff",
    "gpt.int8.None.decode": "fcc118898d68aee07cf5dc7c957d99123b04b864bb4e8258bfc50dfd1afafff8",
    "glm.None.prefill": "16f574ed11ebf0b5ed5d0adce9041960a84bf467699c724bf8f368c02782f550",
    "glm.None.decode": "e717ec4e4af8f2e2182ea0b7f864d17463ccc61c0b1eaf94be4b432d048bc191",
    "gpt.float32.pallas.prefill": "e52f48bbfdc9da2fc670d8d6da64d99c75795212bc39c3f413f9b429acb40534",
    "gpt.float32.pallas.decode": "b8b9b9cf1f332b41c3fdcad335c8e5bc051bdfa6ad4bed3c2b7e2f51e16235bc",
    "gpt.int8.pallas.prefill": "fbc44627b30fe2a2d02652d157ae817177de62cc4eac0d85d1366d7acb4e62fb",
    "gpt.int8.pallas.decode": "b6392bbacd705f4f6f30dad2d1d967f229bdfe8187c72d8d92534dc430f91e77",
    "glm.pallas.prefill": "91b9c1065e4437de77894ff596fdefc95cd090c81bf1b5ec982875bf1cc9d55e",
    "glm.pallas.decode": "a905524dbcb69117e35504829da2a46c4202f53112c10a10cd9719ac0de36693",
    "trinity.None.prefill": "9e13a9c7c32e1e20e47e85fe9c2cec15d1de88b4d0e9d45163fa03c718f9d54e",
    "trinity.None.decode": "cc8d6fd21f5065d7415394414831a96d22e792dcf2fe55080fc45a52cadb4df9",
    "trinity.pallas.prefill": "7a044d31eaee0f83ef79dc1dfb379e6e385605748a604a62c3f8b1ef36edb97e",
    "trinity.pallas.decode": "a202c1c5b5ab89aa1e4e25ce4111e91d77ab9141c59b12ed180e77048a8ac203",
    "kimi_vl.None.prefill": "7dbacc3e480ba6dda9a119f8840ec9bd1c64d9f38d02aefad4e2b8275075c423",
    "kimi_vl.None.decode": "6994807fd51d4e1eb51814692a08014bc0664dc8cfc17e4c30c5667630b04e42",
    "kimi_vl.None.encoder": "6b20bcce5d7cebedc5d6ae00b8dd48f61001971a6e3ba52c451e2397133014b4",
    "kimi_vl.pallas.prefill": "6bd8dc2344287e9651964fc5e8eff8863299982613833ca3cbff26d9a0d25866",
    "kimi_vl.pallas.decode": "a83e021cbfe4a63173eb7903cf49fcc366808a292c2118ae3bbc38a10fa3b85e",
    "kimi_vl.pallas.encoder": "45645d79bbe5ee2fb023ea669853f92f3f037814b83388a0422c786d8f168d92",
    "olmo_hybrid.None.prefill": "d172ad775fbd5add4a370fbe5eebce9803963bb47990391c80c0922e6775df5a",
    "olmo_hybrid.None.decode": "7b60445602a67a9990cf19862ac8e2e3149c6b87e3f163fc2a5b3f0720cf5bc6",
    "olmo_hybrid.pallas.prefill": "8e78a12ad239d34a51d0d617a0027342349173be12278ed76b4f72ae0111e81e",
    "olmo_hybrid.pallas.decode": "22fa1302f64eb2e96c9eb09fae00a75a97f5b50303bb65ecb056f187ca47ad12",
    "mimo.None.prefill": "9de4dfb3b9da96dee4c85d4c36b004d2ffaa30c8f3ed78996245def5e2efa571",
    "mimo.None.decode": "fb8d239f74ce651284c231af36d52cb160b0b115192c1d280db1fdae5a6af4f8",
    "mimo.pallas.prefill": "2a03645e50f971e6e430b981632d186462b4baf3e54b57715845e5a9e654ceff",
    "mimo.pallas.decode": "c42947622e1a395273405b1da73c4ff6ef662e232571cfdb6193969776326fb4",
    "kimi_linear.None.prefill": "98b4dba1650993eb8f487c07c2f33828fdfcbf29868f7b6a64cc6f97fd24572f",
    "kimi_linear.None.decode": "d622ff070875cae920e4471d00899bedd0ccff3eee02ed7547c09bcb0663e881",
    "kimi_linear.pallas.prefill": "d149dfa1eb811df4f3feeb2e94d479d530df8b415d007a71213b825b44d32b24",
    "kimi_linear.pallas.decode": "8512086f341e77354003c507d3b9520087affad4073f0affdeb47dbce80fa476"
}


MODELS = ("gpt", "glm", "trinity", "kimi_vl", "olmo_hybrid", "mimo",
          "kimi_linear")


def _zero_scope(*builds):
    """Zeros under every parameter of the programs ``builds`` build (a
    builder that returns ``(feeds, prepare program)``, the image
    encoder's, gives that program's too)."""
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
            scope.set(p.name, np.zeros(tuple(p.shape), dtype))
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
    # K heads of 192 beside V heads of 128, the published widths: the
    # asymmetric Pallas forms read whole 128-lane tiles and take no other
    cfg = mimo.MiMoConfig.tiny(
        head_dim=192, v_head_dim=128, num_hidden_layers=3,
        hybrid_layer_pattern=[0, 1, 1], moe_layer_freq=[0, 1, 1])
    return cfg, [lambda: mimo.build_mimo_lm(cfg)]


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
