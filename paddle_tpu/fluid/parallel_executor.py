"""ParallelExecutor (reference python/paddle/fluid/parallel_executor.py +
paddle/fluid/framework/parallel_executor.cc:45).

Reference: clones scopes per GPU, builds an op-handle SSA graph with NCCL
allreduce per grad, schedules with a threaded dep-count executor.
TPU-native redesign: all of that collapses into one SPMD XLA compilation —
ParallelExecutor is a thin convenience wrapper over
`CompiledProgram.with_data_parallel` + `Executor` (the reference's newer API
deprecates it the same way, compiler.py:48).
"""

from __future__ import annotations

from . import framework
from .compiler import BuildStrategy, CompiledProgram, ExecutionStrategy
from .executor import Executor, global_scope

__all__ = ["ParallelExecutor"]


class ParallelExecutor:
    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None, build_strategy=None,
                 num_trainers=1, trainer_id=0, scope=None):
        self._program = main_program or framework.default_main_program()
        self._scope = scope if scope is not None else global_scope()
        build_strategy = build_strategy or BuildStrategy()
        build_strategy.num_trainers = num_trainers
        build_strategy.trainer_id = trainer_id
        self._compiled = CompiledProgram(self._program).with_data_parallel(
            loss_name=loss_name,
            build_strategy=build_strategy,
            exec_strategy=exec_strategy or ExecutionStrategy(),
            share_vars_from=getattr(share_vars_from, "_compiled",
                                    share_vars_from))
        # use_cuda = "the accelerator if this host has one"
        self._exe = Executor(None if use_cuda else framework.CPUPlace())

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        feed = feed if feed is not None else feed_dict
        return self._exe.run(self._compiled, feed=feed, fetch_list=fetch_list,
                             scope=self._scope, return_numpy=return_numpy)

    def drop_local_exe_scopes(self):
        """Reference frees per-device local scopes between iterations; our
        per-device state is XLA-managed device buffers — drop the cached DP
        runner so the next run re-shards from the global scope."""
        self._compiled._dp_runner = None
