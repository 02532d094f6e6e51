"""slim Compressor core + NAS (VERDICT r2 missing#3).

Reference analogs: contrib/slim/core/compressor.py (config-driven epoch
loop with strategy plugins), searcher/controller.py (SAController),
nas/light_nas_strategy.py.
"""

import numpy as np

from paddle_tpu import fluid
from paddle_tpu.fluid.contrib import slim
from paddle_tpu.fluid.executor import Scope, scope_guard

RNG = np.random.RandomState(0)


def _build_net():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [-1, 8], False, dtype="float32")
        y = fluid.data("y", [-1, 1], False, dtype="int64")
        h = fluid.layers.fc(x, size=16, act="relu", name="slimfc1")
        prob = fluid.layers.fc(h, size=2, act="softmax", name="slimfc2")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(prob, y))
        acc = fluid.layers.accuracy(prob, y)
        test_prog = main.clone(for_test=True)
        fluid.optimizer.Adam(learning_rate=0.05).minimize(loss)
    return main, startup, test_prog, loss, acc


def _reader(n=256, batch=32, seed=1):
    rng = np.random.RandomState(seed)
    xs = rng.randn(n, 8).astype("float32")
    ys = (xs[:, :3].sum(1) > 0).astype("int64")[:, None]

    def it():
        for i in range(0, n, batch):
            yield {"x": xs[i:i + batch], "y": ys[i:i + batch]}

    return it


def test_config_driven_prune_pipeline(tmp_path):
    cfg = tmp_path / "compress.yaml"
    cfg.write_text("""
version: 1.0
strategies:
  prune_s:
    class: PruneStrategy
    start_epoch: 0
    ratio: 0.5
compressor:
  epoch: 4
  strategies: [prune_s]
""")
    main, startup, test_prog, loss, acc = _build_net()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    comp = slim.Compressor(
        fluid.CPUPlace(), scope, main, startup_program=startup,
        train_reader=_reader(), train_fetch_list=[loss.name],
        eval_program=test_prog, eval_reader=_reader(seed=2),
        eval_fetch_list=[acc.name]).config(str(cfg))
    ctx = comp.run()

    # sparsity held through fine-tuning (the strategy's whole point)
    w = np.asarray(scope.get("slimfc1.w_0"))
    sparsity = float((w == 0).mean())
    assert sparsity >= 0.45, sparsity
    # and the model still learned
    assert ctx.eval_results[acc.name][-1] > 0.7, ctx.eval_results


def test_compressor_checkpoint_resume(tmp_path):
    cfg_text = """
version: 1.0
strategies:
  prune_s:
    class: PruneStrategy
    start_epoch: 0
    ratio: 0.3
compressor:
  epoch: 2
  checkpoint_path: {ckpt}
  strategies: [prune_s]
"""
    ckpt = str(tmp_path / "ckpt")
    cfg = tmp_path / "c.yaml"
    cfg.write_text(cfg_text.format(ckpt=ckpt))

    main, startup, test_prog, loss, acc = _build_net()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    comp = slim.Compressor(
        fluid.CPUPlace(), scope, main, startup_program=startup,
        train_reader=_reader(), train_fetch_list=[loss.name]).config(str(cfg))
    comp.run()
    import os

    assert sorted(os.listdir(ckpt)) == ["0", "1"]

    # fresh scope + program resumes from epoch 1's checkpoint and KEEPS
    # FINE-TUNING (epochs 2..3) — masks must be recreated in the fresh
    # program and pinned so sparsity survives the resumed training
    cfg2 = tmp_path / "c2.yaml"
    cfg2.write_text(cfg_text.format(ckpt=ckpt).replace("epoch: 2",
                                                       "epoch: 4"))
    main2, startup2, test2, loss2, acc2 = _build_net()
    scope2 = Scope()
    with scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup2)
    comp2 = slim.Compressor(
        fluid.CPUPlace(), scope2, main2, startup_program=startup2,
        train_reader=_reader(),
        train_fetch_list=[loss2.name]).config(str(cfg2))
    ctx2 = comp2.run()  # resumes at epoch 2, trains epochs 2 and 3
    assert ctx2.epoch_id == 3
    w = np.asarray(scope2.get("slimfc1.w_0"))
    # sparsity survived two epochs of post-resume optimization
    assert float((w == 0).mean()) >= 0.25, float((w == 0).mean())
    assert sorted(os.listdir(ckpt)) == ["0", "1", "2", "3"]


def test_quantization_strategy_pipeline(tmp_path):
    cfg = tmp_path / "quant.yaml"
    cfg.write_text("""
version: 1.0
strategies:
  quant_s:
    class: QuantizationStrategy
    start_epoch: 1
compressor:
  epoch: 2
  strategies: [quant_s]
""")
    main, startup, test_prog, loss, acc = _build_net()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    comp = slim.Compressor(
        fluid.CPUPlace(), scope, main, startup_program=startup,
        train_reader=_reader(), train_fetch_list=[loss.name]).config(str(cfg))
    comp.run()
    types = [op.type for op in main.global_block().ops]
    assert any("quantize" in t for t in types), types


def test_sa_controller_converges_on_quadratic():
    """SAController must walk token space toward the optimum of a simple
    concave reward."""
    ctrl = slim.SAController(seed=3, init_temperature=1.0, reduce_rate=0.9)
    target = [7, 2, 9]
    ctrl.reset([10, 10, 10], [0, 0, 0])

    def reward(tokens):
        return -sum((t - g) ** 2 for t, g in zip(tokens, target))

    ctrl.update([0, 0, 0], reward([0, 0, 0]))
    for _ in range(300):
        tokens = ctrl.next_tokens()
        ctrl.update(tokens, reward(tokens))
    assert ctrl.max_reward >= -2, (ctrl.best_tokens, ctrl.max_reward)


def test_light_nas_finds_better_architecture():
    """NAS over MLP width: reward = val acc - size penalty; the search must
    beat the initial (tiny) architecture."""

    class WidthSpace(slim.SearchSpace):
        WIDTHS = [2, 4, 8, 16, 32]

        def init_tokens(self):
            return [0]  # width 2: too small for the task

        def range_table(self):
            return [len(self.WIDTHS)]

        def create_eval_func(self, tokens):
            width = self.WIDTHS[tokens[0]]

            def evaluate():
                rng = np.random.RandomState(0)
                xs = rng.randn(256, 8).astype("float32")
                ys = ((xs[:, 0] * xs[:, 1] > 0)).astype("int64")[:, None]
                main, startup = fluid.Program(), fluid.Program()
                with fluid.program_guard(main, startup), \
                        fluid.unique_name.guard():
                    x = fluid.data("x", [-1, 8], False, dtype="float32")
                    y = fluid.data("y", [-1, 1], False, dtype="int64")
                    h = fluid.layers.fc(x, size=width, act="tanh")
                    p = fluid.layers.fc(h, size=2, act="softmax")
                    loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
                    acc = fluid.layers.accuracy(p, y)
                    fluid.optimizer.Adam(0.05).minimize(loss)
                scope = Scope()
                with scope_guard(scope):
                    exe = fluid.Executor(fluid.CPUPlace())
                    exe.run(startup)
                    for _ in range(30):
                        exe.run(main, feed={"x": xs, "y": ys},
                                fetch_list=[loss])
                    a, = exe.run(main, feed={"x": xs, "y": ys},
                                 fetch_list=[acc])
                return float(a) - 0.001 * width

            return evaluate

    strat = slim.LightNASStrategy(search_steps=6, seed=5,
                                  search_space=WidthSpace())
    ctx = slim.Context(fluid.CPUPlace(), Scope(), None, None)
    strat.on_compression_begin(ctx)
    result = ctx.nas_result
    assert not isinstance(ctx.search_space, dict)  # input slot untouched
    assert result["best_reward"] > result["history"][0][1] + 0.1, result
    assert WidthSpace.WIDTHS[result["best_tokens"][0]] >= 8, result


def test_sa_controller_handles_fixed_dims():
    ctrl = slim.SAController(seed=1)
    ctrl.reset([1, 5, 1], [0, 2, 0])
    for _ in range(20):
        toks = ctrl.next_tokens()
        assert toks[0] == 0 and toks[2] == 0  # fixed dims never mutate
        assert 0 <= toks[1] < 5
        ctrl.update(toks, 0.0)
    # all dims fixed: tokens just come back unchanged
    ctrl2 = slim.SAController(seed=1)
    ctrl2.reset([1, 1], [0, 0])
    assert ctrl2.next_tokens() == [0, 0]


def test_quantization_resume_keeps_scale_state(tmp_path):
    """Checkpoint resume of a QAT run must re-apply the transform BEFORE
    loading, so saved scale statistics land in matching vars."""
    cfg = tmp_path / "q.yaml"
    ckpt = str(tmp_path / "ck")
    cfg.write_text(f"""
version: 1.0
strategies:
  quant_s:
    class: QuantizationStrategy
    start_epoch: 0
compressor:
  epoch: 1
  checkpoint_path: {ckpt}
  strategies: [quant_s]
""")
    main, startup, test_prog, loss, acc = _build_net()
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    slim.Compressor(fluid.CPUPlace(), scope, main, startup_program=startup,
                    train_reader=_reader(),
                    train_fetch_list=[loss.name]).config(str(cfg)).run()
    scale_names = [n for n in main.global_block().vars if "scale" in n
                   and main.global_block().var(n).persistable]
    assert scale_names, "QAT created no scale vars?"
    saved = {n: np.asarray(scope.get(n)).copy() for n in scale_names
             if scope.get(n) is not None}
    assert saved

    # resume with epoch: 2 — fresh program, transform must be re-applied
    cfg2 = tmp_path / "q2.yaml"
    cfg2.write_text(cfg.read_text().replace("epoch: 1", "epoch: 2"))
    main2, startup2, *_rest = _build_net()
    loss2 = _rest[2]
    scope2 = Scope()
    with scope_guard(scope2):
        fluid.Executor(fluid.CPUPlace()).run(startup2)
    slim.Compressor(fluid.CPUPlace(), scope2, main2,
                    startup_program=startup2, train_reader=_reader(),
                    train_fetch_list=[loss2.name]).config(str(cfg2)).run()
    types = [op.type for op in main2.global_block().ops]
    assert any("quantize" in t for t in types)
    # at least one saved scale value visible in the resumed scope pre-drift
    # (epoch-0 checkpoint loaded into the re-transformed program)
    present = [n for n in saved if scope2.get(n) is not None]
    assert present, "scale vars did not load on resume"


def test_prefetcher_iterate_after_close_raises_stopiteration():
    from paddle_tpu.fluid.prefetch import DatasetPrefetcher

    def gen():
        while True:
            yield {"x": np.zeros(1, "float32")}

    pf = DatasetPrefetcher(gen(), depth=2)
    next(iter(pf))
    pf.close()
    assert list(pf) == []  # StopIteration, not a hang
