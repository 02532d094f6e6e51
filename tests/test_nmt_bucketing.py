"""NMT variable-length bucketing discipline on CPU (r4 verdict item 7 —
de-risks the on-chip `nmt_varlen` leg; SURVEY §7 hard part 1, the
dynamic-shape stress):

1. K buckets → exactly K XLA compiles, and the count STAYS K across
   epochs of fresh ragged lengths (cache hits, no per-length recompile).
2. Padded-bucket loss parity: a batch padded out to its bucket produces
   the SAME loss as the minimally-padded program — the _pad_bias
   attention mask + label_weight discipline makes padding numerically
   invisible, so bucket choice is a pure perf knob."""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.models import transformer as tfm

BUCKETS = [16, 32]


def _ragged(cfg, rng, bucket, lo, batch=4):
    """Batch padded to `bucket`; true source lengths uniform in
    (lo, bucket], target lengths = source - 1, label_weight zeroes the
    padding."""
    data = tfm.make_fake_batch(cfg, batch=batch, src_len=bucket,
                               trg_len=bucket - 1,
                               seed=int(rng.randint(1 << 30)))
    lens = rng.randint(lo + 1, bucket + 1, batch)
    w = np.zeros_like(data["label_weight"])
    for i, ln in enumerate(lens):
        data["src_ids"][i, ln:] = 0  # pad_id
        w[i, :ln - 1] = 1.0
    data["label_weight"] = w
    return data


def test_k_buckets_exactly_k_compiles_across_epochs():
    cfg = tfm.TransformerConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, cost, acc = tfm.build_transformer_nmt(cfg)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
    rng = np.random.RandomState(0)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for epoch in range(3):
            for bucket, lo in zip(BUCKETS, [0] + BUCKETS[:-1]):
                # fresh ragged lengths every epoch — same bucket signature
                for _ in range(2):
                    data = _ragged(cfg, rng, bucket, lo)
                    (lv,) = exe.run(main, feed=data, fetch_list=[cost.name])
                    assert np.isfinite(float(np.asarray(lv)))
            n = len(exe.compiled_for(main))
            assert n == len(BUCKETS), (
                f"epoch {epoch}: {n} executables for {len(BUCKETS)} "
                "buckets — per-length recompile leak")


def test_padded_bucket_loss_parity():
    """Same sentences, padded to bucket 16 vs minimally padded to the
    batch max length: identical loss/accuracy within fp32 reduction
    noise.  is_test=True (dropout off — random masks are shape-keyed)."""
    cfg = tfm.TransformerConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, cost, acc = tfm.build_transformer_nmt(cfg, is_test=True)
    rng = np.random.RandomState(3)
    bucket, maxlen = 16, 12
    data = tfm.make_fake_batch(cfg, batch=6, src_len=bucket,
                               trg_len=bucket - 1, seed=5)
    lens = rng.randint(8, maxlen + 1, 6)  # ragged, all <= 12
    w = np.zeros_like(data["label_weight"])
    for i, ln in enumerate(lens):
        data["src_ids"][i, ln:] = 0
        w[i, :ln - 1] = 1.0
    data["label_weight"] = w

    tight = {
        "src_ids": data["src_ids"][:, :maxlen],
        "trg_ids": data["trg_ids"][:, :maxlen - 1],
        "labels": data["labels"][:, :maxlen - 1],
        "label_weight": w[:, :maxlen - 1],
    }
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        cost_pad, acc_pad = [float(np.asarray(v)) for v in exe.run(
            main, feed=data, fetch_list=[cost.name, acc.name])]
        cost_tight, acc_tight = [float(np.asarray(v)) for v in exe.run(
            main, feed=tight, fetch_list=[cost.name, acc.name])]
        assert len(exe.compiled_for(main)) == 2  # two shapes, two compiles
    assert abs(cost_pad - cost_tight) < 1e-4 * max(1.0, abs(cost_tight)), (
        cost_pad, cost_tight)
    assert abs(acc_pad - acc_tight) < 1e-5, (acc_pad, acc_tight)
