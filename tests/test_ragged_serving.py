"""Ragged serving lane (ISSUE 17 satellite): mixed-length traffic on a
ragged-attention model batches TOGETHER under one shape key.

Acceptance contract: a ragged lane warms ONE executable per batch
bucket (the seq-bucket cross product collapses — the warmup-truncation
wart disappears), mixed-length traffic runs zero-cold-compile after
warmup with ZERO padding rows for full batches, over-length requests
reject with a typed FeedValidationError (they cannot fall through to a
cold unpadded shape the way the bucketed path allows), ragged mode
without sequence buckets is a construction-time error, and
``load_model(ragged=None)`` resolves from FLAGS_ragged_attention.

The model masks its own padded tail via the per-row ``lens`` feed
(layers.ragged_attention) — serving just stops minting padding rows.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.fluid import layers as L
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.serving import FeedValidationError

VOCAB, HIDDEN, HEADS = 64, 32, 2
SEQ_BUCKETS = [4, 8, 16]


@pytest.fixture(scope="module")
def ragged_model(tmp_path_factory):
    """One-layer ragged-attention scorer: ids [-1, -1] int64 + per-row
    lens [-1] int32, one layer."""
    d = str(tmp_path_factory.mktemp("ragged_model"))
    head_dim = HIDDEN // HEADS
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.data("ids", [-1, -1], False, dtype="int64")
        lens = fluid.data("lens", [-1], False, dtype="int32")
        x = L.embedding(ids, size=[VOCAB, HIDDEN])
        qkv = [L.reshape(L.fc(x, size=HIDDEN, num_flatten_dims=2),
                         shape=[0, 0, HEADS, head_dim])
               for _ in range(3)]
        q, k, v = [L.transpose(t, perm=[0, 2, 1, 3]) for t in qkv]
        ctx = L.ragged_attention(q, k, v, lens, causal=True)
        ctx = L.reshape(L.transpose(ctx, perm=[0, 2, 1, 3]),
                        shape=[0, 0, HIDDEN])
        x = L.elementwise_add(x, L.fc(ctx, size=HIDDEN,
                                      num_flatten_dims=2))
        score = L.reshape(L.reduce_mean(x, dim=[1, 2]), shape=[-1, 1])
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["ids", "lens"], [score], exe,
                                      main_program=main)
    return d


def _feed(rng, ln):
    return {"ids": rng.randint(1, VOCAB, (1, ln)).astype(np.int64),
            "lens": np.full((1,), ln, np.int32)}


def _rows(model, kind):
    fam = obs.REGISTRY.get("pt_serve_rows_total")
    samples = fam._snapshot()["samples"] if fam else {}
    return samples.get((model, kind), 0.0)


def test_warmup_one_executable_per_batch_bucket(ragged_model):
    """The warmup-collapse half of the tentpole: the bucketed lane warms
    the batch x seq cross product; the ragged lane warms exactly one
    shape per batch bucket."""
    eng = serving.Engine(batch_buckets=[2, 4], seq_buckets=SEQ_BUCKETS,
                        max_wait_ms=5, auto_start=False, name="rg_warm")
    try:
        eng.load_model("bucketed", ragged_model, ragged=False)
        eng.load_model("ragged", ragged_model, ragged=True)
        warmed = eng.warmup()
    finally:
        eng.close()
    assert warmed["bucketed"] == 2 * len(SEQ_BUCKETS)
    assert warmed["ragged"] == 2


def test_mixed_length_wave_zero_padding_zero_cold(ragged_model):
    """THE regression test: after warmup, a full wave of mixed-length
    requests forms ONE batch — every row real, zero padding rows, zero
    cold compiles (the zero-cold-compile contract extends from 'per
    bucket combination' to 'per batch bucket')."""
    rng = np.random.RandomState(0)
    eng = serving.Engine(batch_buckets=[4], seq_buckets=SEQ_BUCKETS,
                        max_wait_ms=20, auto_start=False, name="rg_wave")
    try:
        eng.load_model("m", ragged_model, ragged=True)
        eng.warmup()
        eng.start()
        lane = eng._lanes["m"]
        cold0 = lane._cache_counts["cold"]
        pad0, real0 = _rows("m", "padding"), _rows("m", "real")
        for _ in range(3):  # three full mixed-length waves
            futs = [eng.submit("m", _feed(rng, ln))
                    for ln in (3, 5, 7, 2)]
            outs = [f.result(timeout=120) for f in futs]
            for o in outs:
                assert next(iter(o.values())).shape[0] == 1
        assert lane._cache_counts["cold"] - cold0 == 0, \
            "ragged mixed-length traffic cold-compiled after warmup"
        assert _rows("m", "real") - real0 == 12
        assert _rows("m", "padding") - pad0 == 0, \
            "ragged full waves must not mint padding rows"
    finally:
        eng.close()


def test_bucketed_lane_pays_padding_on_same_traffic(ragged_model):
    """The A/B counterpart: the SAME wave on a bucketed lane shatters
    across shape keys and mints padding rows — what the ragged mode
    deletes."""
    rng = np.random.RandomState(0)
    eng = serving.Engine(batch_buckets=[4], seq_buckets=SEQ_BUCKETS,
                        max_wait_ms=5, auto_start=False, name="rg_pad")
    try:
        eng.load_model("mb", ragged_model, ragged=False)
        eng.warmup()
        eng.start()
        pad0 = _rows("mb", "padding")
        futs = [eng.submit("mb", _feed(rng, ln)) for ln in (3, 5, 7, 2)]
        for f in futs:
            f.result(timeout=120)
        assert _rows("mb", "padding") - pad0 > 0
    finally:
        eng.close()


def test_over_length_rejected_typed(ragged_model):
    """Length above the single ragged pad target cannot fall through to
    an unpadded cold shape — typed rejection instead."""
    rng = np.random.RandomState(1)
    eng = serving.Engine(batch_buckets=[4], seq_buckets=SEQ_BUCKETS,
                        max_wait_ms=5, auto_start=False, name="rg_over")
    try:
        eng.load_model("mo", ragged_model, ragged=True)
        with pytest.raises(FeedValidationError,
                           match="above the ragged lane's single padded "
                                 "length 16"):
            eng.submit("mo", _feed(rng, 20))
    finally:
        eng.close()


def test_ragged_requires_seq_buckets(ragged_model):
    """No sequence buckets -> nothing names the single padded length:
    construction-time error, not a runtime surprise."""
    eng = serving.Engine(batch_buckets=[4], max_wait_ms=5,
                        auto_start=False, name="rg_nosb")
    try:
        assert not eng.policy.seq_buckets
        with pytest.raises(ValueError, match="needs sequence buckets"):
            eng.load_model("mn", ragged_model, ragged=True)
    finally:
        eng.close()


def test_load_model_ragged_defaults_to_flag(ragged_model):
    """load_model(ragged=None) resolves FLAGS_ragged_attention — the
    fleet-wide opt-in path."""
    eng = serving.Engine(batch_buckets=[2], seq_buckets=SEQ_BUCKETS,
                        max_wait_ms=5, auto_start=False, name="rg_flag")
    try:
        eng.load_model("off", ragged_model)
        assert eng._lanes["off"]._ragged is False
        fluid.set_flags({"FLAGS_ragged_attention": True})
        try:
            eng.load_model("on", ragged_model)
            assert eng._lanes["on"]._ragged is True
            assert eng._lanes["on"]._ragged_len == max(SEQ_BUCKETS)
        finally:
            fluid.set_flags({"FLAGS_ragged_attention": False})
    finally:
        eng.close()
