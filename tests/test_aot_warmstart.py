"""AOT-serialized executables across a restart (ISSUE 13):
FLAGS_aot_cache_dir makes a restarted process DESERIALIZE its compiled
executables — `pt_compile_cache_total{result="aot_hit"}` books the hit,
no miss, no `phase="aot_compile"` seconds — so a decode replica's first
request after warmup() performs zero compiles (the fleet-restart
acceptance)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, os
import numpy as np
from paddle_tpu import fluid, serving
from paddle_tpu import observability as obs
from paddle_tpu.models import gpt

def cache_counts():
    fam = obs.REGISTRY.get("pt_compile_cache_total")
    samples = fam._snapshot()["samples"] if fam else {}
    out = {"miss": 0, "hit": 0, "aot_hit": 0}
    for k, v in samples.items():
        if k[0] == "single" and k[1] in out:
            out[k[1]] += v
    return out

def aot_compile_seconds():
    fam = obs.REGISTRY.get("pt_compile_seconds_total")
    samples = fam._snapshot()["samples"] if fam else {}
    return sum(v for k, v in samples.items() if k[1] == "aot_compile")

cfg = gpt.GPTConfig.tiny(num_layers=1, hidden_dropout=0.0,
                         use_flash_attention=False, vocab_size=64,
                         hidden_size=32, intermediate_size=64,
                         max_position=16)
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup), fluid.unique_name.guard():
    gpt.build_gpt_lm(cfg)  # declares the params the decode lane shares
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)  # deterministic init: both processes agree
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2,
                               page_size=4, prefill_chunk=4, max_len=8,
                               name="aot", auto_start=False)
    eng.warmup()
    after_warmup = dict(cache_counts())
    eng.start()
    toks = eng.generate([[3, 5, 7]], max_new_tokens=3, timeout=120)[0]
    after_traffic = dict(cache_counts())
    eng.close()
print("AOT " + json.dumps({
    "warmup": after_warmup, "traffic": after_traffic,
    "aot_compile_s": aot_compile_seconds(), "tokens": toks}))
"""


def _run_child(cache_dir, compile_cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               FLAGS_aot_cache_dir=cache_dir,
               FLAGS_compile_cache_dir=compile_cache)
    # single-device child (a serving replica's shape) — the conftest's
    # 8-device virtual mesh is for sharding tests and widens the surface
    # of jaxlib 0.4.3x's nondeterministic XLA:CPU heap corruption
    # (tests/cpu_mesh.py gspmd_cpu_heap_broken), which can SIGSEGV the
    # child.  Signal deaths retry: the zero-compile assertions need one
    # CLEAN completion, and a crash never books a false aot_hit.
    env["XLA_FLAGS"] = "--xla_cpu_use_thunk_runtime=false"
    for _ in range(3):
        r = subprocess.run([sys.executable, "-c", _CHILD],
                           capture_output=True, text=True, timeout=600,
                           cwd=REPO, env=env)
        if r.returncode >= 0:
            break
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("AOT ")]
    assert r.returncode == 0 and lines, \
        f"AOT child failed rc={r.returncode}\n{r.stderr[-3000:]}"
    return json.loads(lines[-1][len("AOT "):])


@pytest.mark.slow
def test_decode_engine_zero_compiles_after_restart(tmp_path):
    aot_dir = str(tmp_path / "aot")
    cc_dir = str(tmp_path / "xla")
    run1 = _run_child(aot_dir, cc_dir)
    # first boot: everything misses (and saves), nothing AOT-loads
    assert run1["warmup"]["miss"] >= 2
    assert run1["warmup"]["aot_hit"] == 0
    files = [f for f in os.listdir(aot_dir) if f.endswith(".aotx")]
    assert len(files) >= 2  # startup + prefill + decode executables

    run2 = _run_child(aot_dir, cc_dir)
    # restart: every executable deserializes — zero misses, zero AOT
    # compiles, and the first request adds NOTHING beyond warmup
    assert run2["warmup"]["miss"] == 0, run2
    assert run2["warmup"]["aot_hit"] >= 2
    assert run2["aot_compile_s"] == 0.0
    assert run2["traffic"]["miss"] == 0
    assert run2["traffic"]["aot_hit"] == run2["warmup"]["aot_hit"] + \
        run2["traffic"]["hit"] * 0  # no new aot loads mid-traffic
    # deterministic init → the restarted replica serves identical tokens
    assert run2["tokens"] == run1["tokens"]


def test_aot_cache_key_stability_and_fallback(tmp_path):
    """Unit coverage for fluid/aot_cache.py: the key is stable across
    program rebuilds, sensitive to spec changes, and a corrupt cache
    entry falls back to compile (warn once, heal the file)."""
    import numpy as np

    from paddle_tpu import fluid
    from paddle_tpu.fluid import aot_cache

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            x = fluid.data("x", [2, 4], False, dtype="float32")
            fluid.layers.fc(x, size=3)
        return main

    import jax

    spec = {"x": jax.ShapeDtypeStruct((2, 4), np.float32)}
    k1 = aot_cache.executable_key(build(), spec, ["out"])
    k2 = aot_cache.executable_key(build(), spec, ["out"])
    assert k1 == k2  # restart-stable: no id()/address leakage
    spec2 = {"x": jax.ShapeDtypeStruct((4, 4), np.float32)}
    assert aot_cache.executable_key(build(), spec2, ["out"]) != k1
    assert aot_cache.executable_key(build(), spec, ["other"]) != k1

    # the fingerprint covers op WIRING, not just types/attrs/var specs:
    # swapped operands of a non-commutative op (identical op sequence,
    # attrs, var names and shapes) must not share an executable — a
    # collision would aot_hit the wrong compiled program and return
    # silently wrong numerics
    def build_sub(swap):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            a = fluid.data("fpa", [2, 3], False, dtype="float32")
            b = fluid.data("fpb", [2, 3], False, dtype="float32")
            fluid.layers.elementwise_sub(*((b, a) if swap else (a, b)))
        return main

    assert (aot_cache.program_fingerprint(build_sub(False))
            == aot_cache.program_fingerprint(build_sub(False)))
    assert (aot_cache.program_fingerprint(build_sub(False))
            != aot_cache.program_fingerprint(build_sub(True)))

    # kernel-impl override envs select WHAT lowers for the same
    # program, so they are part of the key — a Pallas-path executable
    # must never be served to a PT_FUSED_UPDATE_IMPL=xla debug run
    prev = os.environ.get("PT_FUSED_UPDATE_IMPL")
    os.environ["PT_FUSED_UPDATE_IMPL"] = "xla"
    try:
        assert aot_cache.executable_key(build(), spec, ["out"]) != k1
    finally:
        if prev is None:
            os.environ.pop("PT_FUSED_UPDATE_IMPL", None)
        else:
            os.environ["PT_FUSED_UPDATE_IMPL"] = prev

    assert aot_cache.available()
    fluid.set_flags({"FLAGS_aot_cache_dir": str(tmp_path)})
    try:
        assert aot_cache.enabled()
        path = os.path.join(str(tmp_path), k1 + ".aotx")
        with open(path, "wb") as f:
            f.write(b"not a pickle")
        import warnings as _w

        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            assert aot_cache.load(k1) is None
        assert any("failed to load" in str(w.message) for w in rec)
        assert not os.path.exists(path)  # healed: deleted for re-save
    finally:
        fluid.set_flags({"FLAGS_aot_cache_dir": ""})
