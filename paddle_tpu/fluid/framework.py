"""Graph-building core: Program / Block / Operator / Variable.

Re-designs the reference's declarative "Fluid" programming model
(reference: python/paddle/fluid/framework.py — Variable:379, Operator:988,
Block:1439, Program:2778) for a TPU-native stack: the program is still a
sequence of op descs grouped in blocks, but instead of being serialized to a
protobuf and interpreted op-by-op by a C++ executor, the whole block is lowered
to a single XLA computation by :mod:`paddle_tpu.fluid.executor` (traced once
with JAX, compiled once, cached).  Python-side metadata stays authoritative:
transpilers (data-parallel rewrite, AMP, distillation) mutate the op list the
same way the reference's transpilers do.
"""

from __future__ import annotations

import collections
import contextlib
import copy
import re

import numpy as np

__all__ = [
    "Variable",
    "Parameter",
    "Operator",
    "Block",
    "Program",
    "default_main_program",
    "default_startup_program",
    "program_guard",
    "name_scope",
    "unique_name",
    "grad_var_name",
    "cpu_places",
    "cuda_places",
    "tpu_places",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "in_dygraph_mode",
    "_dygraph_tracer",
    "_dygraph_guard",
    "convert_np_dtype_to_dtype_",
]

GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


# ---------------------------------------------------------------------------
# dtypes.  The reference uses VarDesc.VarType proto enums (framework.proto:105);
# we canonicalize on numpy dtype strings, with a small shim for the enum-style
# spellings users may pass.
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "fp16": "float16",
    "fp32": "float32",
    "fp64": "float64",
    "bf16": "bfloat16",
    "float": "float32",
    "double": "float64",
    "int": "int32",
    "long": "int64",
    "bool_": "bool",
}

_FLOAT_DTYPES = ("float16", "bfloat16", "float32", "float64")


def convert_np_dtype_to_dtype_(dtype) -> str:
    """Normalize any dtype spelling to a canonical string."""
    if isinstance(dtype, str):
        d = _DTYPE_ALIASES.get(dtype, dtype)
        return d
    try:
        import jax.numpy as jnp

        if dtype == jnp.bfloat16:
            return "bfloat16"
    except Exception:  # pragma: no cover
        pass
    return np.dtype(dtype).name


def is_float_dtype(dtype) -> bool:
    return convert_np_dtype_to_dtype_(dtype) in _FLOAT_DTYPES


# ---------------------------------------------------------------------------
# Places.  Reference: paddle/fluid/platform/place.h:26-79 (boost::variant of
# CUDAPlace/CPUPlace/CUDAPinnedPlace).  Here a Place selects a JAX backend +
# device ordinal; TPUPlace is the first-class citizen.  CUDAPlace is accepted
# for script compatibility and means the TPU.  A place never stands for
# another device than it names: TPUPlace on a host whose default backend
# is not the TPU raises, and so does an ordinal the host does not have.
# ---------------------------------------------------------------------------


class Place:
    _platform = "cpu"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def jax_device(self):
        import jax

        if self._platform == "cpu":
            return jax.devices("cpu")[self.device_id]
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                f"{self!r}: the default JAX backend is "
                f"{jax.default_backend()!r}, not 'tpu' — use CPUPlace() "
                f"(or Executor() with no place) on a host without a chip")
        devs = jax.devices()
        if self.device_id >= len(devs):
            raise RuntimeError(
                f"{self!r}: this host has {len(devs)} TPU device(s)")
        return devs[self.device_id]


class CPUPlace(Place):
    _platform = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    _platform = "tpu"


class CUDAPlace(TPUPlace):
    """Compatibility alias: scripts written for the reference's CUDAPlace run
    unmodified, landing on the TPU."""


class CUDAPinnedPlace(CPUPlace):
    pass


def cpu_places(device_count=None):
    return [CPUPlace()]


def tpu_places(device_ids=None):
    import jax

    if device_ids is None:
        device_ids = range(len(jax.devices()))
    return [TPUPlace(i) for i in device_ids]


def cuda_places(device_ids=None):
    return tpu_places(device_ids)


def cuda_pinned_places(device_count=None):
    """Host staging buffers (reference CUDAPinnedPlace list); on TPU the
    host side is plain CPU memory — PJRT pins transfer buffers internally."""
    return [CUDAPinnedPlace() for _ in range(device_count or 1)]


_global_place = None


def _current_expected_place():
    global _global_place
    if _global_place is None:
        import jax

        _global_place = (CPUPlace() if jax.default_backend() == "cpu"
                         else TPUPlace(0))
    return _global_place


# ---------------------------------------------------------------------------
# unique names (reference: python/paddle/fluid/unique_name.py)
# ---------------------------------------------------------------------------


class _UniqueNameGenerator:
    def __init__(self):
        self.ids = collections.defaultdict(int)
        self.prefix = ""

    def __call__(self, key):
        tmp = self.ids[key]
        self.ids[key] += 1
        return self.prefix + "_".join([key, str(tmp)])


_name_generator = _UniqueNameGenerator()


class unique_name:
    """Namespace mirroring fluid.unique_name."""

    @staticmethod
    def generate(key):
        return _name_generator(key)

    @staticmethod
    @contextlib.contextmanager
    def guard(new_generator=None):
        global _name_generator
        old = _name_generator
        _name_generator = _UniqueNameGenerator()
        if isinstance(new_generator, str):
            _name_generator.prefix = new_generator
        try:
            yield
        finally:
            _name_generator = old

    @staticmethod
    def switch(new_generator=None):
        """Swap the active generator, returning the old one (reference
        unique_name.switch)."""
        global _name_generator
        old = _name_generator
        _name_generator = new_generator or _UniqueNameGenerator()
        return old


_name_scope_stack = []


@contextlib.contextmanager
def name_scope(prefix=None):
    _name_scope_stack.append(prefix or "")
    try:
        yield
    finally:
        _name_scope_stack.pop()


# ---------------------------------------------------------------------------
# Variable
# ---------------------------------------------------------------------------


class Variable:
    """A named tensor slot in a Block (reference framework.py:379).

    Shape may contain -1 (unknown/batch) dims; concrete shapes are bound at
    executor trace time from the fed arrays.  ``lod_level`` is kept for API
    parity with the reference's LoDTensor (ragged sequences); the TPU lowering
    represents ragged data as padded dense tensors + explicit length tensors.
    """

    def __init__(
        self,
        block,
        name=None,
        shape=None,
        dtype="float32",
        lod_level=0,
        persistable=False,
        stop_gradient=False,
        is_data=False,
        need_check_feed=False,
        initializer=None,
        trainable=True,
        type=None,
    ):
        self.block = block
        self.name = name if name is not None else unique_name.generate("_generated_var")
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = convert_np_dtype_to_dtype_(dtype)
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.initializer = initializer
        self.trainable = trainable
        self.type = type  # parity slot: LOD_TENSOR / LOD_TENSOR_ARRAY / ...
        # op that produced this var last (for introspection)
        self.op = None

    def __repr__(self):
        return (
            f"Variable(name={self.name}, shape={self.shape}, dtype={self.dtype}, "
            f"persistable={self.persistable}, stop_gradient={self.stop_gradient})"
        )

    __str__ = __repr__

    # -- numpy-ish sugar (subset of reference math_op_patch.py) --------------
    def _binary(self, other, op):
        from .layers import nn as _nn  # lazy, avoids import cycle

        return _nn._elementwise_binary_var(self, other, op)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __rsub__(self, other):
        from .layers import nn as _nn

        return _nn._elementwise_binary_var(other, self, "elementwise_sub")

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __matmul__(self, other):
        from .layers import nn as _nn

        return _nn.matmul(self, other)

    def __neg__(self):
        from .layers import nn as _nn

        return _nn.scale(self, scale=-1.0)

    def astype(self, dtype):
        from .layers import tensor as _t

        return _t.cast(self, dtype)


class Parameter(Variable):
    """Persistable, trainable variable (reference framework.py Parameter)."""

    def __init__(self, block, *, regularizer=None, **kw):
        kw.setdefault("persistable", True)
        super().__init__(block, **kw)
        self.regularizer = regularizer
        self.optimize_attr = {"learning_rate": 1.0}
        self.do_model_average = None


# ---------------------------------------------------------------------------
# Operator
# ---------------------------------------------------------------------------


class Operator:
    """An op desc: type + named input/output var lists + attrs
    (reference framework.py:988; proto framework.proto:43)."""

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None,
                 skip_validate=False):
        from . import registry

        self.block = block
        self.type = type
        # canonical: slot name -> list[str] of variable names
        self.inputs = {}
        self.outputs = {}
        self.attrs = dict(attrs or {})
        for slot, vars_ in (inputs or {}).items():
            self.inputs[slot] = [v.name if isinstance(v, Variable) else v for v in _as_list(vars_)]
        for slot, vars_ in (outputs or {}).items():
            self.outputs[slot] = [v.name if isinstance(v, Variable) else v for v in _as_list(vars_)]
        # skip_validate: proto import of reference-signature control-flow
        # ops (while X/Condition, conditional_block Input/Cond) — their
        # slots are rewritten to ours post-parse, once sub-blocks exist
        # (proto_compat._normalize_reference_control_flow)
        if not skip_validate and type is not None and registry.has_op(type):
            registry.get_op(type).validate(self)

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_arg_names(self):
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name):
        return self.attrs.get(name)

    def _set_attr(self, name, val):
        self.attrs[name] = val
        if self.block is not None:
            self.block.program._bump_version()

    def has_attr(self, name):
        return name in self.attrs

    def __repr__(self):
        ins = ", ".join(f"{k}={v}" for k, v in self.inputs.items())
        outs = ", ".join(f"{k}={v}" for k, v in self.outputs.items())
        return f"{{{self.type}: ({ins}) -> ({outs}) attrs={self.attrs}}}"


def _as_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------


class Block:
    """A straight-line list of ops + a var symbol table
    (reference framework.py:1439; proto BlockDesc framework.proto:171)."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: dict[str, Variable] = collections.OrderedDict()
        self.ops: list[Operator] = []

    # -- vars ----------------------------------------------------------------
    def var(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError(f"Variable {name!r} not found in block {self.idx}")
        return v

    def has_var(self, name):
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name):
        if name in self.vars:
            return self.vars[name]
        if self.parent_idx >= 0:
            return self.program.block(self.parent_idx)._find_var_recursive(name)
        return None

    def create_var(self, **kw):
        name = kw.get("name")
        if name is not None and name in self.vars:
            return self.vars[name]
        v = Variable(self, **kw)
        self.vars[v.name] = v
        return v

    def create_parameter(self, **kw):
        p = Parameter(self, **kw)
        # parameters always live in the top (global) block, like the reference
        gb = self.program.global_block()
        gb.vars[p.name] = v = p
        return v

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- ops -----------------------------------------------------------------
    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        from . import registry

        op = Operator(self, type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.append(op)
        needs_shapes = False
        for slot, names in op.outputs.items():
            for n in names:
                v = self._find_var_recursive(n)
                if v is not None:
                    v.op = op
                    if v.shape is None:
                        needs_shapes = True
        if needs_shapes:
            registry.infer_op_outputs(op, self)
        self.program._bump_version()
        return op

    def _insert_op(self, index, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.insert(index, op)
        self.program._bump_version()
        return op

    def _remove_op(self, index):
        del self.ops[index]
        self.program._bump_version()

    def __repr__(self):
        lines = [f"Block[{self.idx}] parent={self.parent_idx}"]
        for v in self.vars.values():
            lines.append("  " + repr(v))
        for op in self.ops:
            lines.append("  " + repr(op))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------


class Program:
    """A list of blocks; block 0 is global (reference framework.py:2778;
    proto ProgramDesc framework.proto:184).

    ``_version`` increments on every mutation — the executor's XLA compile
    cache keys on it, so transpiler rewrites automatically invalidate caches.
    """

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self._version = 0
        self._seed = None
        self.random_seed = 0
        self._is_test = False
        # what the executor compiles this program as (``jit_<name>`` in a
        # device trace); None lets it derive train_step/startup/program
        self.name = None
        # parity knobs referenced by user scripts
        self._fleet_opt = None
        self.op_role_var = []
        # raw (param, grad) names recorded by Optimizer.apply_gradients;
        # consumed by the data-parallel transpiler
        self._params_grads = []

    # -- blocks --------------------------------------------------------------
    def global_block(self):
        return self.blocks[0]

    def block(self, idx):
        return self.blocks[idx]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None):
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def _bump_version(self):
        self._version += 1

    # -- params --------------------------------------------------------------
    def all_parameters(self):
        return self.global_block().all_parameters()

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    # -- clone ---------------------------------------------------------------
    def clone(self, for_test=False):
        """Deep-copy the program.  for_test=True flips `is_test` attrs so
        dropout/batch_norm switch to inference behavior (reference
        framework.py:2429)."""
        p = Program.__new__(Program)
        p.__dict__.update(
            _version=0,
            current_block_idx=0,
            _seed=self._seed,
            random_seed=self.random_seed,
            _is_test=for_test,
            name=self.name,
            _fleet_opt=None,
            op_role_var=[],
            _params_grads=list(self._params_grads),
        )
        p.blocks = []
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            for name, v in b.vars.items():
                nv = copy.copy(v)
                nv.block = nb
                nb.vars[name] = nv
            p.blocks.append(nb)
        for b, nb in zip(self.blocks, p.blocks):
            for op in b.ops:
                nop = Operator(nb, None)
                nop.type = op.type
                nop.inputs = {k: list(v) for k, v in op.inputs.items()}
                nop.outputs = {k: list(v) for k, v in op.outputs.items()}
                nop.attrs = copy.deepcopy(op.attrs)
                if for_test and "is_test" in nop.attrs:
                    nop.attrs["is_test"] = True
                nb.ops.append(nop)
        if for_test:
            p = p._prune_backward()
        return p

    def _prune_backward(self):
        """Drop ops marked as backward/optimize (set by append_backward /
        optimizers) — used by clone(for_test=True)."""
        for b in self.blocks:
            b.ops = [
                op
                for op in b.ops
                if op.attrs.get("op_role", "forward") in ("forward", "loss")
            ]
        self._bump_version()
        return self

    def verify(self, mesh=None, policy=None, **kw):
        """Statically verify this program (paddle_tpu/analysis/,
        docs/ANALYSIS.md): dataflow, shape/dtype propagation, and —
        given a (mesh, policy) — sharding/collective legality.  Returns
        a ``paddle_tpu.analysis.Report``; never raises on findings
        (callers inspect ``report.errors`` or use the
        FLAGS_program_verify preflight for enforcement)."""
        from paddle_tpu import analysis  # deferred: analysis imports fluid

        return analysis.verify(self, mesh=mesh, policy=policy, **kw)

    def to_string(self, throw_on_error=True, with_details=False):
        """Serialized form (reference Program.to_string renders the proto;
        ours is the io.py JSON program schema)."""
        import json

        from . import io as _io

        return json.dumps(_io.program_to_dict(self), indent=2)

    @staticmethod
    def parse_from_string(s):
        import json

        from . import io as _io

        return _io.program_from_dict(json.loads(s))

    def __repr__(self):
        return "\n".join(repr(b) for b in self.blocks)


# ---------------------------------------------------------------------------
# default programs / guards (reference framework.py default_main_program etc.)
# ---------------------------------------------------------------------------

_main_program_ = Program()
_startup_program_ = Program()


def default_main_program() -> Program:
    return _main_program_


def default_startup_program() -> Program:
    return _startup_program_


def switch_main_program(p):
    global _main_program_
    old, _main_program_ = _main_program_, p
    return old


def switch_startup_program(p):
    global _startup_program_
    old, _startup_program_ = _startup_program_, p
    return old


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    old_main = switch_main_program(main_program)
    old_start = None
    if startup_program is not None:
        old_start = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_start is not None:
            switch_startup_program(old_start)


# ---------------------------------------------------------------------------
# dygraph hooks (filled in by paddle_tpu.fluid.dygraph)
# ---------------------------------------------------------------------------

_dygraph_tracer_ = None


def in_dygraph_mode():
    return _dygraph_tracer_ is not None


def _dygraph_tracer():
    return _dygraph_tracer_


@contextlib.contextmanager
def _dygraph_guard(tracer):
    global _dygraph_tracer_
    old = _dygraph_tracer_
    _dygraph_tracer_ = tracer
    try:
        yield
    finally:
        _dygraph_tracer_ = old
