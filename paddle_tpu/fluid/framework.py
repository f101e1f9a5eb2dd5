"""Static-graph IR: Program / Block / Variable / Operator / Parameter.

Parity surface: python/paddle/fluid/framework.py in the reference
(Program:3857, Block:2395, Operator:1821, Variable:834, Parameter:4970).

TPU-native design notes (vs the reference):
- The reference mirrors a C++ protobuf ProgramDesc and interprets it op-by-op.
  Here the Program IS the source of truth in Python; the Executor lowers a
  whole block to a single jitted JAX function (StableHLO via XLA), so there is
  no per-op kernel dispatch at runtime.
- Output shape/dtype inference is done by abstractly evaluating each op's JAX
  emitter (jax.eval_shape) instead of hand-written InferShape functions; a
  dual-probe substitution propagates -1 (batch) dims through the trace.
"""
from __future__ import annotations

import contextlib
import itertools
import copy
import sys
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from . import unique_name
from .dtypes import convert_dtype, dtype_name, is_floating
from .flags import flag

GRAD_VAR_SUFFIX = "@GRAD"
_dummy_batch_probes = (3, 5)

# op attr holding the build-time Python call stack (reference OpDesc attr
# "op_callstack", operator.cc exception enrichment). Double-underscored so
# the registry's attr signatures (registry._attrs_sig) and the generic
# grad path ignore it — pure diagnostics, never semantics.
OP_CALLSTACK_ATTR = "__op_callstack__"

# an op's role in the training step (the reference's OpRole, as far as the
# compiled step is split by it); also the names of the scopes in the step
ROLE_FORWARD = "forward"
ROLE_BACKWARD = "backward"
ROLE_OPTIMIZER = "optimizer"
ROLES = (ROLE_FORWARD, ROLE_BACKWARD, ROLE_OPTIMIZER)


def _capture_callstack(skip: int = 2, limit: int = 32):
    """Cheap (file, line, fn) stack walk for op attribution — no source
    lines are read (unlike traceback.extract_stack), so this costs a few
    microseconds per op. FLAGS_op_callstack=0 disables capture for
    build-speed-critical jobs."""
    if not flag("FLAGS_op_callstack"):
        return None
    try:
        f = sys._getframe(skip)
    except ValueError:
        return None
    out = []
    while f is not None and len(out) < limit:
        code = f.f_code
        out.append((code.co_filename, f.f_lineno, code.co_name))
        f = f.f_back
    return tuple(out)


class Variable:
    """A named tensor slot in a Block.

    Reference: framework.py:834. LoD (ragged-sequence metadata) is represented
    as `lod_level` for API parity, but the TPU build lowers ragged sequences
    to dense padded tensors (see ops/sequence.py), so no runtime LoD exists.
    """

    def __init__(
        self,
        block: "Block",
        name: str,
        shape: Optional[Sequence[int]] = None,
        dtype: Any = "float32",
        lod_level: int = 0,
        persistable: bool = False,
        stop_gradient: bool = False,
        is_data: bool = False,
        trainable: bool = True,
        **kwargs,
    ):
        self.block = block
        self.name = name
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = convert_dtype(dtype)
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.trainable = trainable
        # op that produces this var (last writer), for pruning/backward
        self.op: Optional["Operator"] = None

    # -- paddle-compatible sugar -------------------------------------------
    @property
    def ndim(self):
        return len(self.shape) if self.shape is not None else None

    def astype(self, dtype):
        from . import layers

        return layers.cast(self, dtype)

    @property
    def grad_name(self) -> str:
        return self.name + GRAD_VAR_SUFFIX

    def __repr__(self):
        return (
            f"Variable(name={self.name}, shape={self.shape}, "
            f"dtype={dtype_name(self.dtype)}, persistable={self.persistable}, "
            f"stop_gradient={self.stop_gradient})"
        )

    __str__ = __repr__

    # arithmetic sugar (static graph) — defined via layers to emit ops
    def _binary(self, other, fn_name, reverse=False):
        from . import layers

        fn = getattr(layers, fn_name)
        if not isinstance(other, Variable):
            value = float(other)
            dtype = self.dtype
            if not is_floating(dtype) and not value.is_integer():
                # int/bool var against a fractional scalar: a same-dtype
                # constant would silently truncate (x * 0.5 -> x * 0, the
                # bug proglint's fill-truncation check flags). Promote the
                # scalar; the op's jnp promotion yields the float result.
                dtype = "float32"
            other = layers.fill_constant(
                shape=[1], dtype=dtype, value=value
            )
        return fn(other, self) if reverse else fn(self, other)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __rsub__(self, other):
        return self._binary(other, "elementwise_sub", reverse=True)

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __rtruediv__(self, other):
        return self._binary(other, "elementwise_div", reverse=True)

    def __matmul__(self, other):
        from . import layers

        return layers.matmul(self, other)

    def __neg__(self):
        from . import layers

        return layers.scale(self, scale=-1.0)

    # comparison sugar (reference layers/math_op_patch.py monkey-patch):
    # emits compare ops, which is what lets AST-converted `if x > 0:`
    # build a cond predicate during a to_static trace
    def __gt__(self, other):
        return self._binary(other, "greater_than")

    def __ge__(self, other):
        return self._binary(other, "greater_equal")

    def __lt__(self, other):
        return self._binary(other, "less_than")

    def __le__(self, other):
        return self._binary(other, "less_equal")


class Parameter(Variable):
    """Trainable persistable variable. Reference: framework.py:4970."""

    def __init__(self, block, name, shape, dtype, **kwargs):
        kwargs.setdefault("persistable", True)
        kwargs.setdefault("stop_gradient", False)
        super().__init__(block, name, shape=shape, dtype=dtype, **kwargs)
        self.trainable = kwargs.get("trainable", True)
        self.regularizer = kwargs.get("regularizer", None)
        self.need_clip = kwargs.get("need_clip", True)
        self.is_distributed = kwargs.get("is_distributed", False)
        self.optimize_attr = kwargs.get("optimize_attr", {"learning_rate": 1.0})


class Operator:
    """One op in a block: type + named input/output var lists + attrs.

    Reference: framework.py:1821 (wrapping C++ OpDesc,
    paddle/fluid/framework/op_desc.h). Inputs/outputs map slot name ->
    list of variable names (strings).

    `role` is the reference's `op_role` (forward / backward / optimizer):
    which part of the training step the op belongs to. It is stamped from
    the program's current role when the op is made, lives beside `attrs`
    and not in them (attribute comparisons, saved programs and the
    program's hash see nothing of it), and names the `jax.named_scope`
    that `ops/registry.emit_ops` lowers the op under.

    `scope` is the reference's `op_namescope`: the names of the
    `name_scope`s the op was made inside, outermost first (none by
    default). Like the role it lives beside `attrs`, is stamped when the op
    is made, is handed by `append_backward` to the op's gradient ops, and
    names further `jax.named_scope`s inside the role's: metadata of the
    compiled step and nothing else.
    """

    def __init__(
        self,
        block: "Block",
        type: str,
        inputs: Optional[Dict[str, List[str]]] = None,
        outputs: Optional[Dict[str, List[str]]] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ):
        self.block = block
        self.type = type
        self.role = block.program._op_role
        self.scope = block.program._op_scope
        self.inputs = {k: list(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: list(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})

    def input_names(self) -> List[str]:
        return [n for vs in self.inputs.values() for n in vs]

    def output_names(self) -> List[str]:
        return [n for vs in self.outputs.values() for n in vs]

    def input(self, slot: str) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot: str) -> List[str]:
        return self.outputs.get(slot, [])

    def attr(self, name: str, default=None):
        return self.attrs.get(name, default)

    def _set_attr(self, name: str, val):
        self.attrs[name] = val
        self.block.program._bump_version()

    def __repr__(self):
        ins = {k: v for k, v in self.inputs.items()}
        outs = {k: v for k, v in self.outputs.items()}
        return f"Op(type={self.type}, inputs={ins}, outputs={outs})"


class Block:
    """Ordered op list + var map. Reference: framework.py:2395."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    # -- variables ----------------------------------------------------------
    def create_var(self, name=None, **kwargs) -> Variable:
        if name is None:
            name = unique_name.generate("_generated_var")
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, name, **kwargs)
        self.vars[name] = v
        self.program._bump_version()
        return v

    def create_parameter(self, name, shape, dtype, **kwargs) -> Parameter:
        # parameters live in the global (root) block, like the reference
        global_block = self.program.global_block()
        p = Parameter(global_block, name, shape, dtype, **kwargs)
        global_block.vars[name] = p
        self.program._bump_version()
        return p

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError(f"Variable {name!r} not found in block {self.idx}")
        return v

    def has_var(self, name: str) -> bool:
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        blk: Optional[Block] = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- ops ----------------------------------------------------------------
    def append_op(
        self,
        type: str,
        inputs: Optional[Dict[str, Any]] = None,
        outputs: Optional[Dict[str, Any]] = None,
        attrs: Optional[Dict[str, Any]] = None,
        infer: bool = True,
    ) -> Operator:
        op = Operator(
            self,
            type,
            inputs=_normalize_io(inputs),
            outputs=_normalize_io(outputs),
            attrs=attrs,
        )
        dev = _current_op_device()
        if dev is not None and "op_device" not in op.attrs:
            op.attrs["op_device"] = dev
        if OP_CALLSTACK_ATTR not in op.attrs:
            cs = _capture_callstack()
            if cs is not None:
                op.attrs[OP_CALLSTACK_ATTR] = cs
        self.ops.append(op)
        self._post_insert(op, infer)
        return op

    def _insert_op(self, index: int, **kwargs) -> Operator:
        infer = kwargs.pop("infer", True)
        op = Operator(
            self,
            kwargs["type"],
            inputs=_normalize_io(kwargs.get("inputs")),
            outputs=_normalize_io(kwargs.get("outputs")),
            attrs=kwargs.get("attrs"),
        )
        if OP_CALLSTACK_ATTR not in op.attrs:
            cs = _capture_callstack()
            if cs is not None:
                op.attrs[OP_CALLSTACK_ATTR] = cs
        self.ops.insert(index, op)
        self._post_insert(op, infer)
        return op

    def _remove_op(self, index: int):
        del self.ops[index]
        self.program._bump_version()

    def _post_insert(self, op: Operator, infer: bool):
        # ensure output vars exist; infer their shapes/dtypes from the emitter
        for slot, names in op.outputs.items():
            for n in names:
                if self._find_var_recursive(n) is None:
                    self.create_var(name=n)
        if infer:
            try:
                infer_op_outputs(self, op)
            except Exception as e:  # noqa: BLE001 — surface op context
                raise RuntimeError(
                    f"shape inference failed for op {op.type}: {e}"
                ) from e
        for n in op.output_names():
            self._find_var_recursive(n).op = op
        self.program._bump_version()

    def __repr__(self):
        lines = [f"Block(idx={self.idx}, parent={self.parent_idx}) {{"]
        for v in self.vars.values():
            lines.append(f"  {v}")
        for op in self.ops:
            lines.append(f"  {op}")
        lines.append("}")
        return "\n".join(lines)


_program_serial_counter = itertools.count()


class Program:
    """A list of blocks; block 0 is global. Reference: framework.py:3857."""

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._version = 0
        # monotonic identity for the executor compile cache: id() can be
        # REUSED by CPython after a Program is GC'd, aliasing a stale
        # cache entry when feed/fetch signatures happen to match
        self._serial = next(_program_serial_counter)
        # set by AMP / fleet passes; consumed by the Executor
        self._amp_enabled = False
        self._mesh = None  # paddle_tpu.parallel mesh attached by fleet
        # the role every op made from here on is stamped with
        self._op_role = ROLE_FORWARD
        # and the name scopes it is made inside (`name_scope`)
        self._op_scope = ()

    def _bump_version(self):
        self._version += 1

    @contextlib.contextmanager
    def _role_guard(self, role: str):
        prev, self._op_role = self._op_role, role
        try:
            yield
        finally:
            self._op_role = prev

    def _backward_role_guard(self):
        """Ops appended inside are gradient ops (reference framework.py
        `_backward_role_guard`): the loss-gradient fill, the `*_grad`
        ops, the sums that accumulate partial gradients."""
        return self._role_guard(ROLE_BACKWARD)

    def _optimized_guard(self):
        """Ops appended inside run between the gradients and the
        parameter write (reference framework.py `_optimized_guard`):
        clipping, regularisation, AMP's unscale and loss-scale update,
        gradient synchronisation, the update itself."""
        return self._role_guard(ROLE_OPTIMIZER)

    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def block(self, idx: int) -> Block:
        return self.blocks[idx]

    @property
    def num_blocks(self) -> int:
        return len(self.blocks)

    def _create_block(self, parent_idx: Optional[int] = None) -> Block:
        if parent_idx is None:
            parent_idx = self.current_block_idx
        b = Block(self, len(self.blocks), parent_idx)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        self._bump_version()
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx
        if self.current_block_idx < 0:
            self.current_block_idx = 0

    def all_parameters(self) -> List[Parameter]:
        return self.global_block().all_parameters()

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def clone(self, for_test: bool = False) -> "Program":
        """Deep-copy the program. for_test=True marks test mode: ops like
        dropout/batch_norm read attr is_test (rewritten here)."""
        p = Program.__new__(Program)
        p.blocks = []
        p.current_block_idx = 0
        p.random_seed = self.random_seed
        p._version = 0
        p._serial = next(_program_serial_counter)  # own compile-cache identity
        p._amp_enabled = self._amp_enabled
        p._mesh = self._mesh
        p._op_role = ROLE_FORWARD
        p._op_scope = ()
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            p.blocks.append(nb)
        for b, nb in zip(self.blocks, p.blocks):
            for name, v in b.vars.items():
                cls = Parameter if isinstance(v, Parameter) else Variable
                nv = cls.__new__(cls)
                nv.__dict__.update({k: w for k, w in v.__dict__.items() if k not in ("block", "op")})
                nv.block = nb
                nv.op = None
                nb.vars[name] = nv
            for op in b.ops:
                attrs = {
                    k: (v if not isinstance(v, Block) else p.blocks[v.idx])
                    for k, v in op.attrs.items()
                }
                # fused recompute segments carry live sub-Operator lists:
                # copy them (no aliasing with the source program) and apply
                # the is_test rewrite inside the segment too (dropout etc.)
                if "recompute_sub_ops" in attrs:
                    subs = []
                    for sop in attrs["recompute_sub_ops"]:
                        nsop = Operator(
                            nb,
                            sop.type,
                            inputs=copy.deepcopy(sop.inputs),
                            outputs=copy.deepcopy(sop.outputs),
                            attrs=dict(sop.attrs),
                        )
                        nsop.role, nsop.scope = sop.role, sop.scope
                        if for_test and "is_test" in nsop.attrs:
                            nsop.attrs["is_test"] = True
                        subs.append(nsop)
                    attrs["recompute_sub_ops"] = subs
                nop = Operator(
                    nb,
                    op.type,
                    inputs=copy.deepcopy(op.inputs),
                    outputs=copy.deepcopy(op.outputs),
                    attrs=attrs,
                )
                nop.role, nop.scope = op.role, op.scope
                if for_test and "is_test" in nop.attrs:
                    nop.attrs["is_test"] = True
                nb.ops.append(nop)
                for n in nop.output_names():
                    fv = nb._find_var_recursive(n)
                    if fv is not None:
                        fv.op = nop
        p._bump_version()
        return p

    def __repr__(self):
        return "\n".join(repr(b) for b in self.blocks)


# ---------------------------------------------------------------------------
# shape/dtype inference by abstract evaluation of the op emitter
# ---------------------------------------------------------------------------


def compute_op_output_metas(block: Block, op: Operator):
    """Pure output-meta inference: {slot: [(shape, dtype)]} from the
    registered emitter (jax.eval_shape dual-probe for -1 dims) or the
    explicit infer_shape override. Returns None for no_infer ops. Never
    mutates the program — the static verifier (fluid/analysis) re-runs
    this to cross-check recorded metadata after graph rewrites."""
    from ..ops import registry

    spec = registry.get(op.type)
    if spec is None:
        raise KeyError(f"op {op.type!r} is not registered")
    in_metas = {
        slot: [_var_meta(block, n) for n in names]
        for slot, names in op.inputs.items()
    }
    if spec.infer_shape is not None:
        # explicit override (control flow, data-dependent shapes)
        return spec.infer_shape(in_metas, op.attrs)
    if spec.no_infer:
        return None

    has_dynamic = any(
        (m[0] is not None and -1 in m[0]) for ms in in_metas.values() for m in ms
    )
    probes = _dummy_batch_probes if has_dynamic else (_dummy_batch_probes[0],)
    results = [registry.abstract_eval(op.type, in_metas, op.attrs, probe) for probe in probes]
    out0 = results[0]
    metas = {}
    for slot in out0:
        metas[slot] = []
        for i, (shape0, dt) in enumerate(out0[slot]):
            if len(results) > 1:
                shape1 = results[1][slot][i][0]
                shape = tuple(
                    -1 if a != b else a for a, b in zip(shape0, shape1)
                )
            else:
                shape = shape0
            metas[slot].append((shape, dt))
    return metas


def infer_op_outputs(block: Block, op: Operator):
    """Set shapes/dtypes of op's output vars by abstractly tracing the
    registered JAX emitter (twice, with different probe values standing in
    for -1 dims, to detect batch-dim propagation)."""
    metas = compute_op_output_metas(block, op)
    if metas is not None:
        _apply_metas(block, op, metas)


def _apply_metas(block, op, metas):
    for slot, names in op.outputs.items():
        ms = metas.get(slot)
        if ms is None:
            continue
        for n, (shape, dt) in zip(names, ms):
            v = block._find_var_recursive(n)
            v.shape = tuple(shape) if shape is not None else None
            if dt is not None:
                v.dtype = convert_dtype(dt)


def _var_meta(block, name):
    v = block.var(name)
    return (v.shape, v.dtype)


def _normalize_io(io: Optional[Dict[str, Any]]) -> Dict[str, List[str]]:
    out: Dict[str, List[str]] = {}
    for slot, val in (io or {}).items():
        if val is None:
            continue
        if isinstance(val, (Variable, str)):
            val = [val]
        out[slot] = [v.name if isinstance(v, Variable) else str(v) for v in val]
    return out


# ---------------------------------------------------------------------------
# default programs & guards (reference: framework.py program_guard etc.)
# ---------------------------------------------------------------------------

_main_program_ = Program()
_startup_program_ = Program()


def default_main_program() -> Program:
    return _main_program_


def default_startup_program() -> Program:
    return _startup_program_


def switch_main_program(program: Program) -> Program:
    global _main_program_
    old = _main_program_
    _main_program_ = program
    return old


def switch_startup_program(program: Program) -> Program:
    global _startup_program_
    old = _startup_program_
    _startup_program_ = program
    return old


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


@contextlib.contextmanager
def name_scope(prefix: str):
    """Ops made inside carry `prefix` in their `scope` (reference
    fluid.name_scope, which writes the attribute `op_namescope` for its
    graph viewer). Here the compiled step shows it: every such op, and the
    gradient ops `append_backward` makes for it, lowers inside
    `jax.named_scope(prefix)` beneath its role, so a device trace can be
    split by the parts of a model its builder names (`mtp`, `lm_head`).
    Scopes nest. A name is one word of letters, digits and `_`."""
    if not prefix or not prefix.replace("_", "").isalnum():
        raise ValueError(f"name_scope: {prefix!r} is not one word")
    program = default_main_program()
    before = program._op_scope
    program._op_scope = before + (prefix,)
    try:
        yield
    finally:
        program._op_scope = before


# ---------------------------------------------------------------------------
# device_guard: pipeline-stage annotation (reference fluid.device_guard;
# ops get attr "op_device" like the reference's OpDesc attribute consumed by
# PipelineOptimizer, optimizer.py:3627)
# ---------------------------------------------------------------------------

_op_device_stack: List[str] = []


@contextlib.contextmanager
def device_guard(device: Optional[str] = None):
    """Annotate ops appended in this scope with a device/stage tag, e.g.
    "gpu:0". On TPU the tag names a pipeline stage, not a physical device —
    placement is the mesh's job."""
    _op_device_stack.append(device)
    try:
        yield
    finally:
        _op_device_stack.pop()


def _current_op_device() -> Optional[str]:
    return _op_device_stack[-1] if _op_device_stack else None


# dygraph mode switch (filled in by paddle_tpu.fluid.dygraph)
_dygraph_tracer_ = None


def in_dygraph_mode() -> bool:
    return _dygraph_tracer_ is not None


def _dygraph_tracer():
    return _dygraph_tracer_


def grad_var_name(name: str) -> str:
    return name + GRAD_VAR_SUFFIX
