"""Inference engine: AnalysisPredictor-style serving API.

Reference: paddle/fluid/inference/ — `AnalysisPredictor`
(api/analysis_predictor.h:46) loads a saved ProgramDesc + params, runs the
Analyzer fusion-pass pipeline, then serves through a NaiveExecutor with
ZeroCopyTensor inputs/outputs (:68); TensorRT/Anakin/nGraph subgraphs offload
pieces of the graph (analysis/ir_pass_manager.cc).

TPU-native redesign: the "engine subgraph offload" side-path of the
reference IS this framework's main path — the whole pruned inference program
compiles to one XLA executable, cached per input-shape signature, with
parameters resident on device across calls (the ZeroCopyRun property: no
per-call weight transfer; only inputs/outputs cross the host boundary).
Fusion passes are XLA's job.  `config.switch_ir_optim` etc. are accepted for
API parity but have no separate pass pipeline to toggle.
"""

from __future__ import annotations

import os

import numpy as np

__all__ = ["AnalysisConfig", "AnalysisPredictor", "PaddleTensor",
           "PaddleDType", "create_paddle_predictor", "ZeroCopyTensor",
           "check_feed_against_var"]


def _resolve_np_dtype(dtype):
    """np.dtype for a framework dtype (string or proto enum int),
    resolving ml_dtypes extension floats (bfloat16) via the shared
    ops.common helper — None when unresolvable."""
    try:
        from paddle_tpu.ops.common import np_dtype

        return np.dtype(np_dtype(dtype))
    except Exception:
        return None


def _dtype_kind(dt):
    """numpy kind char, with ml_dtypes extension floats (bfloat16,
    float8* — numpy kind 'V') reported as 'f'.  A true void/structured
    dtype stays 'V' (np.finfo rejects it)."""
    if dt.kind == "V":
        try:
            import ml_dtypes

            ml_dtypes.finfo(dt)
            return "f"
        except Exception:
            pass
    return dt.kind


def check_feed_against_var(name, arr, var, error_cls=ValueError):
    """Cheap edge validation of a feed array against the program's static
    var: rank and every fixed dim must match, and the dtype KIND must
    match (width differences — float64→float32, int64→int32 — are safe,
    the executor coerces them like the reference feed path).  `var=None`
    (no static info) passes.

    The serving lane multiplexes many callers onto one compiled
    executable, so a bad feed must fail HERE with the caller's name on
    it, not inside XLA attributed to whoever shares the batch."""
    if var is None:
        return
    arr = np.asarray(arr)
    # shape None = no static info; shape () is a GENUINE scalar var and
    # still gets the rank check (a (4, 8) feed against it must fail
    # here, not deep in XLA)
    if var.shape is not None:
        want = tuple(var.shape)
        if arr.ndim != len(want):
            raise error_cls(
                f"feed {name!r}: rank {arr.ndim} array {tuple(arr.shape)} "
                f"does not match the program's static shape {list(want)}")
        for axis, (got, exp) in enumerate(zip(arr.shape, want)):
            if exp >= 0 and int(got) != int(exp):
                raise error_cls(
                    f"feed {name!r}: shape {tuple(arr.shape)} does not "
                    f"match the program's static shape {list(want)} "
                    f"(dim {axis}: got {got}, expected {exp})")
    # "is not None"/"!= ''" rather than truthiness: the proto enum for
    # bool is 0, and `if var.dtype:` would silently skip validating it
    if var.dtype is not None and var.dtype != "":
        want_dtype = _resolve_np_dtype(var.dtype)
        if want_dtype is None:
            return  # unresolvable dtype: executor coerces
        got_kind, want_kind = _dtype_kind(arr.dtype), _dtype_kind(want_dtype)
        if got_kind != want_kind:
            raise error_cls(
                f"feed {name!r}: dtype {arr.dtype} is not "
                f"{var.dtype}-compatible (kind {got_kind!r} vs "
                f"{want_kind!r}) — cast at the caller")


class PaddleDType:
    FLOAT32 = "float32"
    INT64 = "int64"
    INT32 = "int32"


class PaddleTensor:
    """Input/output container for the non-zero-copy `run` API
    (reference api/paddle_api.h PaddleTensor)."""

    def __init__(self, data=None, name="", lod=None):
        arr = np.asarray(data) if data is not None else None
        self.name = name
        self.data = arr
        self.dtype = str(arr.dtype) if arr is not None else None
        self.shape = list(arr.shape) if arr is not None else []
        self.lod = lod or []

    def as_ndarray(self):
        return self.data


class AnalysisConfig:
    """Reference api/paddle_analysis_config.h.  Device toggles map to
    Places; pass/engine switches are parity no-ops (XLA compiles and fuses
    the whole graph unconditionally)."""

    def __init__(self, model_dir=None, prog_file=None, params_file=None):
        self._model_dir = model_dir
        self._prog_file = prog_file
        self._params_file = params_file
        self._use_tpu = True
        self._ir_optim = True
        self._enable_memory_optim = False
        self._quantizer_enabled = False
        self._quantizer_config = None

    def set_model(self, model_dir, params_file=None):
        if params_file is None:
            self._model_dir = model_dir
        else:
            self._prog_file = model_dir
            self._params_file = params_file

    def model_dir(self):
        return self._model_dir

    def disable_gpu(self):
        self._use_tpu = False

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        # GPU knob accepted for source compatibility; device is the TPU
        self._use_tpu = True

    def switch_ir_optim(self, x=True):
        self._ir_optim = bool(x)

    def enable_memory_optim(self):
        self._enable_memory_optim = True

    def switch_use_feed_fetch_ops(self, x=True):
        pass

    def switch_specify_input_names(self, x=True):
        pass

    # -- post-training int8 quantization (reference EnableMkldnnQuantizer,
    #    inference/api/mkldnn_quantizer.cc) ------------------------------
    def enable_quantizer(self):
        """Calibrate on warmup data at predictor build, then run the
        int8-QDQ rewritten program (fluid/contrib/ptq.py)."""
        from paddle_tpu.fluid.contrib.ptq import PTQConfig

        self._quantizer_enabled = True
        if self._quantizer_config is None:
            self._quantizer_config = PTQConfig()
        return self._quantizer_config

    # reference spelling
    enable_mkldnn_quantizer = enable_quantizer

    def quantizer_enabled(self):
        return self._quantizer_enabled

    mkldnn_quantizer_enabled = quantizer_enabled

    def quantizer_config(self):
        """Pure accessor (the reference's mkldnn_quantizer_config never
        enables quantization as a side effect)."""
        from paddle_tpu.fluid.contrib.ptq import PTQConfig

        if self._quantizer_config is None:
            self._quantizer_config = PTQConfig()
        return self._quantizer_config

    mkldnn_quantizer_config = quantizer_config


class ZeroCopyTensor:
    """Named handle onto a predictor slot (reference ZeroCopyTensor):
    copy_from_cpu stages the next run's input; copy_to_cpu reads the last
    run's output without an extra staging buffer on the Python side."""

    def __init__(self, predictor, name, is_input):
        self._pred = predictor
        self.name = name
        self._is_input = is_input

    def copy_from_cpu(self, arr):
        if not self._is_input:
            raise ValueError(f"{self.name} is an output tensor")
        arr = np.ascontiguousarray(arr)
        # fail bad feeds at the edge (dtype kind / rank / fixed dims)
        # instead of inside XLA — serving multiplexes many callers
        var = self._pred._program.global_block()._find_var_recursive(
            self.name)
        check_feed_against_var(self.name, arr, var)
        self._pred._staged[self.name] = arr

    def copy_to_cpu(self):
        store = self._pred._staged if self._is_input else self._pred._outputs
        if self.name not in store:
            raise RuntimeError(
                f"tensor {self.name!r} has no value yet — "
                + ("copy_from_cpu() first" if self._is_input
                   else "call zero_copy_run() first"))
        return np.asarray(store[self.name])

    def shape(self):
        store = self._pred._staged if self._is_input else self._pred._outputs
        if self.name in store:
            return list(np.shape(store[self.name]))
        # not materialized yet: report the static shape from the program
        var = self._pred._program.global_block()._find_var_recursive(self.name)
        if var is not None and var.shape is not None:
            return list(var.shape)
        raise RuntimeError(f"tensor {self.name!r} has no value or static "
                           f"shape yet")


class AnalysisPredictor:
    def __init__(self, config: AnalysisConfig):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid.executor import Scope, scope_guard

        self._config = config
        self._scope = Scope()
        # _use_tpu = "the accelerator if this host has one" (Executor()'s
        # default place); disable_gpu() pins the CPU
        self._exe = fluid.Executor(
            None if config._use_tpu else fluid.CPUPlace())
        with scope_guard(self._scope):
            if config._model_dir:
                prog, feeds, fetches = fluid.io.load_inference_model(
                    config._model_dir, self._exe)
            else:
                dirname = os.path.dirname(config._prog_file) or "."
                prog, feeds, fetches = fluid.io.load_inference_model(
                    dirname, self._exe,
                    model_filename=os.path.basename(config._prog_file),
                    params_filename=(os.path.basename(config._params_file)
                                     if config._params_file else None))
        fetch_names = [v.name if hasattr(v, "name") else v
                       for v in fetches]
        # graph-optimization passes (FLAGS_graph_passes) on the LOADED
        # program — the serving path motivation: an exported inference
        # program built from the plain layers API gets the fused
        # attention/FFN kernels without a model-level opt-in.  The known
        # fetch list pins keep_vars, so applying here (rather than at
        # first Executor.run) can never fuse a fetch target away.
        from paddle_tpu import passes as _graph_passes

        _graph_passes.apply_graph_passes(prog, lane="serving",
                                         keep_vars=fetch_names)
        if getattr(config, "_ir_optim", True):
            # kernel fusion is XLA's job, but program-level rewrites that
            # still pay (smaller op graphs to trace) run here, mirroring
            # the reference's analysis pass pipeline.  Fetch targets are a
            # name list outside the program, invisible to the pass's
            # use-count — pin them explicitly
            from paddle_tpu.fluid import ir

            ir.apply_pass(prog, "fc_fuse_pass", keep_vars=fetch_names)
        if config._quantizer_enabled:
            from paddle_tpu.fluid.contrib.ptq import quantize_post_training

            with scope_guard(self._scope):
                self._ptq_scales, self._ptq_rewired = \
                    quantize_post_training(self._exe, prog,
                                           config._quantizer_config)
        self._program = prog
        self._feed_names = list(feeds)
        self._fetch_vars = fetches
        self._fetch_names = fetch_names
        self._staged = {}
        self._outputs = {}

    # -- ZeroCopy API ---------------------------------------------------
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return list(self._fetch_names)

    def get_input_tensor(self, name):
        if name not in self._feed_names:
            raise KeyError(f"unknown input {name!r}; have {self._feed_names}")
        return ZeroCopyTensor(self, name, is_input=True)

    def get_output_tensor(self, name):
        if name not in self._fetch_names:
            raise KeyError(f"unknown output {name!r}")
        return ZeroCopyTensor(self, name, is_input=False)

    def zero_copy_run(self):
        from paddle_tpu.fluid.executor import scope_guard

        missing = [n for n in self._feed_names if n not in self._staged]
        if missing:
            raise ValueError(f"inputs not set: {missing}")
        with scope_guard(self._scope):
            outs = self._exe.run(self._program, feed=dict(self._staged),
                                 fetch_list=self._fetch_names)
        self._outputs = dict(zip(self._fetch_names, outs))
        return True

    # -- PaddleTensor API -----------------------------------------------
    def run(self, inputs):
        """inputs: list of PaddleTensor in get_input_names() order (or
        named).  Returns list of PaddleTensor."""
        if any(not t.name for t in inputs) and \
                len(inputs) != len(self._feed_names):
            # positional feeding only works when the count matches — a
            # longer list used to fall off self._feed_names[i] with a
            # bare IndexError
            raise ValueError(
                f"run() got {len(inputs)} positional inputs but the "
                f"model expects {len(self._feed_names)}: "
                f"{self._feed_names}")
        feed = {}
        for i, t in enumerate(inputs):
            name = t.name or self._feed_names[i]
            if name not in self._feed_names:
                raise ValueError(
                    f"run() got unknown input {name!r}; expected "
                    f"{self._feed_names}")
            if name in feed:
                # two tensors resolving to one input — duplicate names,
                # or a named tensor colliding with a positional slot —
                # must fail typed instead of silently overwriting
                raise ValueError(
                    f"run() fed input {name!r} twice; expected exactly "
                    f"one tensor per input in {self._feed_names}")
            feed[name] = t.data
        missing = [n for n in self._feed_names if n not in feed]
        if missing:
            raise ValueError(
                f"run() is missing inputs {missing}; expected "
                f"{self._feed_names}")
        from paddle_tpu.fluid.executor import scope_guard

        with scope_guard(self._scope):
            outs = self._exe.run(self._program, feed=feed,
                                 fetch_list=self._fetch_names)
        return [PaddleTensor(o, name=n)
                for n, o in zip(self._fetch_names, outs)]

    # -- dict-in/dict-out serving entry ----------------------------------
    def run_feed_dict(self, feed, validate=True):
        """Serving-path entry (paddle_tpu.serving): run the compiled
        program on a complete ``{input_name: array}`` feed and return
        ``{output_name: array}``.  Same executable cache as
        zero_copy_run/run — one compiled XLA executable per feed-shape
        signature, parameters device-resident across calls.

        validate=False skips the edge checks for callers that already
        validated (the serving engine checks every request at submit;
        re-checking each assembled batch would be pure duplicated
        work in the hot path)."""
        missing = [n for n in self._feed_names if n not in feed]
        extra = [n for n in feed if n not in self._feed_names]
        if missing or extra:
            raise ValueError(
                f"run_feed_dict expects exactly {self._feed_names}; "
                f"missing {missing}, unexpected {extra}")
        if validate:
            blk = self._program.global_block()
            for n in self._feed_names:
                # same fail-at-the-edge contract as copy_from_cpu: a bad
                # feed errors HERE with the name on it, not inside XLA
                check_feed_against_var(n, feed[n],
                                       blk._find_var_recursive(n))
        from paddle_tpu.fluid.executor import scope_guard

        with scope_guard(self._scope):
            outs = self._exe.run(self._program, feed=dict(feed),
                                 fetch_list=self._fetch_names)
        return dict(zip(self._fetch_names, outs))

    def program(self):
        return self._program


def create_paddle_predictor(config: AnalysisConfig) -> AnalysisPredictor:
    """Reference api factory CreatePaddlePredictor<AnalysisConfig>."""
    return AnalysisPredictor(config)
