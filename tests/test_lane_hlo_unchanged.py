"""A model with one cache kind builds the programs and feeds the names it
did before the pool had kinds (PR 31): the decode lane's two executables
of `models/gpt.py` (float32 and int8 pools) and `models/glm.py` lower to
the HLO they lowered at the parent commit, compared BY TEXT — both in the
XLA form of their attention and with the Pallas kernels interpreted
(`attn_force="pallas"`), where the text holds the paged kernel's own
body: plain multi-head attention over the whole context traces what it
traced.

The digests below are of `lowered.as_text()` at commit 67a584c (PR 30's
tree), made by this file's `digests()` there, but for `glm.pallas.*`,
which PR 32 and PR 37 moved by design: their text holds the interpreted
grouped product, whose grid ends at the live visits (a traced extent,
PR 32), and the interpreted `sparse_mla_attention`, which makes one
score / softmax / value update a grid step over all the step's pages
(PR 37); the ten others are the proof that nothing else moved.  The `trinity.*` four
are of commit 3ff1d2d (PR 32's tree), made before PR 33 gave a lane its
optional encoder: a lane that declares none builds what it built; but
for `trinity.pallas.prefill`, which PR 43 moved by design: its text holds
the interpreted grouped chunk body, whose query tile and keys a step now
come from the shapes together and whose step axis ends at the last step
the chunk reaches (`trinity.pallas.decode` stands: the decode row did
not move).  The `kimi_vl.*` six (its third executable is the image
encoder of its one declared shape), the `olmo_hybrid.*` four and the
`mimo.*` four are of commit e98f100 (PR 43's tree), made before PR 44
moved the lanes' program scaffold out of the model files into
`serving/lane.py`: all thirty are the proof that the move changed no
executable.  PR 46 moved kimi_vl.py's latent attention and expert
layer to `models/decode_blocks.py` under public names (`kimi_vl.*`
stand) and widened the delta-rule ops (`olmo_hybrid.*` stand); the
`kimi_linear.*` four are PR 46's own lane (KDA layers beside a latent
layer, 4 of 8 experts held), pinned as it was added.  After a deliberate change
to what these models compile, run `python tests/test_lane_hlo_unchanged.py`
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
    "gpt.float32.None.prefill": "e4de139bba244c34c30e3378cea1230b90f392d45e9ccf67a0d043c67755d999",
    "gpt.float32.None.decode": "8535d14ebf7212bdae543d6edac2532ed15a3d0596735d6f6c4335bc33cda3f8",
    "gpt.int8.None.prefill": "12933a5e197e4aa7654c2e35518d1f01aecd462ade5f36f12413b0007bcd8c5e",
    "gpt.int8.None.decode": "0892ca85181c45072ab0d457155baa5a1a467e22d81c67888cac767bf54f69b6",
    "glm.None.prefill": "a42a84a33db2b0b0b740b907c65e7f89d9ac9e7dc67a53b8abb99e50fa945b22",
    "glm.None.decode": "246eecbf9f8d71f6aa3c18bdf96f4272caad508300e3604a8d278bc211a19dc5",
    "gpt.float32.pallas.prefill": "4dc7dc62aab88691a43f8bd2292b9e7eafe0f0c3d1ad1ddd3bf911c7f9887706",
    "gpt.float32.pallas.decode": "931f17d5b326722c8c62cc0222397248c73d072475d72e437d830427d102cfd8",
    "gpt.int8.pallas.prefill": "6c60c7216eb5c3bdf1ddf181932dce89b726895a1a580d4007cfcf4796f59142",
    "gpt.int8.pallas.decode": "5b1236c43d90c101518a07f823c0b878bcd2344b3f6cc813df068e8a3a84ae6c",
    "glm.pallas.prefill": "d2a0f7d7b30df9bdf2511c7b4f2244290a4776f329925830d162d7a55b64c807",
    "glm.pallas.decode": "578e2a3223d4468c4e64b81fbf6adce62130beace5189ff432f7ae0a23dbd310",
    "trinity.None.prefill": "6a0acaf7ee2c08877896d839f89c39e243d3bc10d4e001c30b769978814375b6",
    "trinity.None.decode": "ce29f09c2160a3c759dab784c8df9ca47db24d0ccc773a70bb2dfbefbf9175fb",
    "trinity.pallas.prefill": "f4a43df07a6f72f3aee87bad50ed93c0158aeb46c08ee96774dfe571d37cf65e",
    "trinity.pallas.decode": "901a6a9d530abca4c35470742261f788f866b9a119c3b2f2da2f720e07e03861",
    "kimi_vl.None.prefill": "3ab5440d0790ab5dbd49c3ce241c53186efc8adcd1a8b45b9db7f450c54a1a9b",
    "kimi_vl.None.decode": "6bd8524d832d625f9037c5c262c620436d68837a5cb31cebf32aa58982da8042",
    "kimi_vl.None.encoder": "6b20bcce5d7cebedc5d6ae00b8dd48f61001971a6e3ba52c451e2397133014b4",
    "kimi_vl.pallas.prefill": "b00e232159c740b15f90228784930c5925f0c4c1cff80abe9e5e4c2edf21f589",
    "kimi_vl.pallas.decode": "e7a624f22e7d2c22b49ac339a5451104796cf8e436ce423d597e811b4f336a03",
    "kimi_vl.pallas.encoder": "45645d79bbe5ee2fb023ea669853f92f3f037814b83388a0422c786d8f168d92",
    "olmo_hybrid.None.prefill": "00de766abf0286e0f1a461d822489f2f3a3f370fb2e08b8e61660724f77a7dca",
    "olmo_hybrid.None.decode": "e2110da57faecdbf733e9abe407fe5031f6eaff67363957ebe44be4bd6e2c3d2",
    "olmo_hybrid.pallas.prefill": "6b6526dda50c081d66b7c5d86d551a603aef9a9c005d020d8940da97948e47df",
    "olmo_hybrid.pallas.decode": "50690e7dce8990a9e437fd7c4c7e322c8e28e5f106a29f16cbc760bb1b6d2f42",
    "mimo.None.prefill": "3c2ece044c67fda4e36e33b3ae2b728bb1251c295704a6f781a555a290e90e71",
    "mimo.None.decode": "aa38eae686b3abd65a3cbbcc60fef6e4b5ce9004cefb869562b8a65f09bb7a4a",
    "mimo.pallas.prefill": "948323c8f183e13c9742b70dd73a21b84939d27192a01347bb70d8776ed30d46",
    "mimo.pallas.decode": "de58ea6e91449e77cdc780da162099b5ebee6d66b73e5ff677966152b2d1c245",
    "kimi_linear.None.prefill": "1da69d78a406e226867b65aea9171145f7de7d372153abce0de31929fcefd685",
    "kimi_linear.None.decode": "5267acacf672a7a037aae8b01cbed62f5b65afd7e4b72088f5b11c28cc74ed0d",
    "kimi_linear.pallas.prefill": "bbcde65d990121b1f031f763f59a7cbf494b43021dafb5893d71c3ec67525e2b",
    "kimi_linear.pallas.decode": "c32d82a3bd91aea4551e5c6496dffd1d9a61c90ddce978b94c401203afefe0da"
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


def test_one_kind_lanes_feed_the_names_they_fed():
    cfg = gpt.GPTConfig.tiny()
    eng = serving.DecodeEngine(
        cfg, scope=_zero_scope(lambda: gpt.build_gpt_lm(cfg, is_test=True)),
        place=fluid.CPUPlace(), pool_slots=3, page_size=4, max_len=32,
        auto_start=False, name="feeds")
    try:
        assert eng.pool.kinds == ["full"]
        assert list(eng._decode_feed([])) == [
            "dec_tok", "dec_pos", "dec_page_table", "dec_write_page",
            "dec_write_off"]
        assert list(eng._prefill_feed(**eng._warm_prefill_args())) == [
            "pf_tok", "pf_pos", "pf_page_table", "pf_write_pages",
            "pf_qstart", "pf_last_idx"]
    finally:
        eng.close()


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
