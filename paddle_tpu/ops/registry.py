"""Op registry: op type -> JAX emitter (+ optional overrides).

TPU-native replacement for the reference's operator registry
(/root/reference/paddle/fluid/framework/op_registry.h:223,
 operator.cc:908 RunImpl kernel dispatch). Instead of per-(place,dtype)
kernels, each op registers ONE `emit` function mapping JAX values -> JAX
values. The Executor traces a whole block of emitters into a single jitted
function, so XLA sees the full graph and fuses across op boundaries — there
is no per-op dispatch at runtime.

Three services are derived from the same emitter:
  * execution  — emitters called under jax.jit trace
  * shape/dtype inference — jax.eval_shape over the emitter (framework.py)
  * autodiff   — a synthesized `<op>_grad` op whose emitter is jax.vjp of
                 the forward emitter (see grad_emit below); ops with
                 randomness or data-dependent residuals register explicit
                 grad ops instead (e.g. dropout_grad uses the saved Mask).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

Ins = Dict[str, List[Any]]  # slot -> list of jax values
Attrs = Dict[str, Any]


class EmitContext:
    """Per-trace context handed to emitters (rng threading, mesh info)."""

    def __init__(self, rng_key=None, mesh=None, axis_env=None,
                 manual_axes=None, op_scopes=False):
        self._key = rng_key
        self._base_key = rng_key  # frozen per-step key for salted_rng
        self.mesh = mesh
        # FLAGS_op_profile: emit_ops wraps each op's lowering in
        # jax.named_scope("op<idx>:<type>") so device profiles attribute
        # back to Program IR ops (telemetry/cost.py). Trace-time only.
        self.op_scopes = bool(op_scopes)
        # mapping of logical ring_id -> mesh axis name, for collective ops
        self.axis_env = axis_env or {}
        # mesh axes the surrounding shard_map runs MANUALLY over (the
        # executor's multi-slice dcn mode); emitters needing collectives
        # use lax.p* with these names directly
        self.manual_axes = tuple(manual_axes) if manual_axes else ()
        # (type, fwd input names) -> LIFO of (outs, vjp_fn, fwd_ins):
        # captured at forward emission, consumed by the generic grad op —
        # the primal forward is computed ONCE (emitting the backward by
        # re-tracing would duplicate it; XLA cannot CSE two while loops
        # whose bodies differ, so a scanned encoder would run twice)
        self.vjp_cache: Dict[tuple, list] = {}

    def rng(self):
        """Split and return a fresh PRNG key (functional rng threading)."""
        import jax

        if self._key is None:
            self._key = jax.random.PRNGKey(0)
        self._key, sub = jax.random.split(self._key)
        return sub

    def salted_rng(self, salt: int):
        """Deterministic per-op key: fold a graph-build-time salt into the
        per-step base key. Unlike rng(), the result does not depend on trace
        order, so an op with internal randomness (fused attention dropout)
        gets the SAME mask when its forward emitter is re-traced under
        jax.vjp by the generic grad path — no saved mask needed."""
        import jax

        base = self._base_key
        if base is None:
            base = jax.random.PRNGKey(0)
        return jax.random.fold_in(base, salt)

    @property
    def rng_state(self):
        return self._key


@dataclasses.dataclass
class OpSpec:
    type: str
    emit: Callable[[EmitContext, Ins, Attrs], Dict[str, List[Any]]]
    # explicit shape inference override (rarely needed; control flow etc.)
    infer_shape: Optional[Callable] = None
    no_infer: bool = False
    # custom grad-op builder: fn(op, out_grads: {slot: [names]|None})
    #   -> (list_of_op_descs, {fwd_in_slot: [grad_names]})
    grad_maker: Optional[Callable] = None
    # ops that must NOT take the generic vjp grad path (randomness /
    # non-differentiable): they either register grad_maker or are leaves
    no_vjp_grad: bool = False
    # stateless ops whose outputs are never differentiable (compare etc.)
    stop_gradient: bool = False
    # True for lazily synthesized "<base>_grad" specs (generic vjp)
    generic_vjp: bool = False


_REGISTRY: Dict[str, OpSpec] = {}


def register(
    type: str,
    *,
    infer_shape=None,
    no_infer=False,
    grad_maker=None,
    no_vjp_grad=False,
    stop_gradient=False,
):
    """Decorator: register `emit` for op `type`."""

    def deco(emit_fn):
        _REGISTRY[type] = OpSpec(
            type=type,
            emit=emit_fn,
            infer_shape=infer_shape,
            no_infer=no_infer,
            grad_maker=grad_maker,
            no_vjp_grad=no_vjp_grad,
            stop_gradient=stop_gradient,
        )
        return emit_fn

    return deco


def set_grad_maker(type: str, grad_maker):
    _REGISTRY[type].grad_maker = grad_maker


def get(type: str) -> Optional[OpSpec]:
    spec = _REGISTRY.get(type)
    if spec is not None:
        return spec
    # lazily synthesize generic vjp-based grad ops: "<base>_grad"
    if type.endswith("_grad"):
        base = _REGISTRY.get(type[: -len("_grad")])
        if base is not None and not base.no_vjp_grad:
            spec = OpSpec(
                type=type, emit=_make_generic_grad_emit(base), generic_vjp=True
            )
            _REGISTRY[type] = spec
            return spec
    return None


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# generic vjp grad
# ---------------------------------------------------------------------------

GRAD = "@GRAD"


def _apply_vjp(ins: Ins, outs, vjp_fn, fwd_ins):
    """Build cotangents from the grad op's "<slot>@GRAD" inputs, run the
    vjp, and clean the input gradients (zeros for float0/None)."""
    import jax
    import jax.numpy as jnp

    cot = {}
    for slot, vals in outs.items():
        gs = ins.get(slot + GRAD)
        cs = []
        for i, v in enumerate(vals):
            g = gs[i] if gs is not None and i < len(gs) and gs[i] is not None else None
            if not jnp.issubdtype(v.dtype, jnp.floating) and not jnp.issubdtype(
                v.dtype, jnp.complexfloating
            ):
                cs.append(np.zeros(v.shape, jax.dtypes.float0))
            elif g is None:
                cs.append(jnp.zeros(v.shape, v.dtype))
            else:
                cs.append(jnp.asarray(g, v.dtype))
        cot[slot] = cs
    (d_ins,) = vjp_fn(cot)
    result = {}
    for slot in fwd_ins:
        gvals = d_ins.get(slot)
        if gvals is None:
            continue
        cleaned = []
        for g, v in zip(gvals, fwd_ins[slot]):
            if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
                cleaned.append(jnp.zeros(jnp.shape(v), jnp.result_type(v)) if v is not None else None)
            else:
                cleaned.append(g)
        result[slot + GRAD] = cleaned
    return result


def _make_generic_grad_emit(base: OpSpec):
    """Build the FALLBACK emitter for `<base>_grad` (used when the primal
    vjp was not captured — e.g. gradients() called on a block whose
    forward was emitted in a different trace).

    Grad-op convention (established by backward.append_backward):
      inputs : forward inputs under their original slots, plus available
               output grads under "<out_slot>@GRAD"
      outputs: input grads under "<in_slot>@GRAD"
      attrs  : forward attrs + "__fwd_in_slots__" (list of fwd input slots)

    The fast path lives in emit_ops: when the forward op of this grad op
    was emitted in the same trace, its captured (outs, vjp_fn) pair is
    reused and the forward is NOT re-traced.
    """
    import jax

    def grad_emit(ctx: EmitContext, ins: Ins, attrs: Attrs):
        fwd_attrs = {k: v for k, v in attrs.items() if not k.startswith("__")}
        in_slots = list(attrs["__fwd_in_slots__"])
        fwd_ins = {s: list(ins[s]) for s in in_slots if s in ins}

        def fn(fi):
            return base.emit(ctx, fi, fwd_attrs)

        outs, vjp_fn = jax.vjp(fn, fwd_ins)
        return _apply_vjp(ins, outs, vjp_fn, fwd_ins)

    return grad_emit


# ---------------------------------------------------------------------------
# block emission: shared by the Executor's whole-block trace and by
# control-flow op emitters (cond/while) that recursively evaluate sub-blocks
# ---------------------------------------------------------------------------


def _attrs_sig(attrs):
    """Stable signature of forward attrs. The grad desc carries a shallow
    COPY of the forward attrs (backward.py: dict(op.attrs)), so contained
    objects (Blocks, callables) are identical and repr() is consistent
    between the pair."""
    return tuple(sorted(
        (k, repr(v)) for k, v in attrs.items() if not k.startswith("__")
    ))


def _fwd_key_from_fwd(op):
    # attrs are part of the key: two same-type ops over the same inputs
    # but different attrs (e.g. scale by 2 vs 3) must not share a vjp
    return (op.type, tuple(sorted(
        (s, tuple(ns)) for s, ns in op.inputs.items() if ns
    )), _attrs_sig(op.attrs))


def _fwd_key_from_grad(op):
    slots = op.attrs.get("__fwd_in_slots__", ())
    return (op.type[: -len("_grad")], tuple(sorted(
        (s, tuple(op.inputs.get(s, ()))) for s in slots if op.inputs.get(s)
    )), _attrs_sig(op.attrs))


def role_scope(role: str):
    """The scope an op of this role is lowered under. A function of its
    own so that a test can put a null context in its place and compare
    the two executables."""
    import jax

    return jax.named_scope(role)


def emit_ops(ctx: EmitContext, ops, env: Dict[str, Any],
             on_op=None) -> Dict[str, Any]:
    """Trace a list of framework Operators into JAX values. `env` maps var
    name -> value and is mutated in place (op outputs land there).

    on_op: optional per-op probe called as on_op(op_idx, op, outs) AFTER
    the op's outputs land in env — the numerics doctor's instrumented
    eager replay hangs its finiteness checks here (telemetry/numerics.
    bisect_first_nonfinite). None (the default) costs nothing.

    Primal reuse: forward ops whose generic grad op appears later in the
    list are emitted under jax.vjp ONCE; the grad op consumes the stored
    vjp instead of re-tracing the forward (a re-traced scanned encoder
    would otherwise run twice — XLA cannot CSE differing while loops).

    Op-scope tagging (ctx.op_scopes, FLAGS_op_profile): every op's
    emission is wrapped in jax.named_scope("op<idx>:<type>") so each HLO
    instruction's op_name metadata carries the Program IR position of
    the op that lowered it — the join key telemetry/cost.py aggregates
    xplane device events by. Grad-op backward compute (the cached vjp_fn
    call) is tagged at the GRAD op's index; sub-block emitters recursing
    through emit_ops nest their scopes under the parent op's.

    Role scopes (always on): every op is lowered under
    jax.named_scope(op.role) — "forward", "backward" or "optimizer" — so
    the first component of an instruction's op_name after the jit(...)
    wrappers says which part of the step it belongs to; a grad op's
    cached vjp_fn call runs under the grad op's scope, so transposes and
    rematerialised computation read "backward". A named scope writes
    op_name metadata and nothing else: the executable is the same with
    and without it (tests/test_op_role.py), which is why there is no flag
    here and nothing in the executor's cache key. The op<idx>:<type>
    scope nests inside; a sub-block's ops nest a second role, and only
    the outermost counts. Between the two lie the op's own name scopes
    (`fluid.name_scope`, `Operator.scope`), metadata in the same way."""
    import contextlib

    import jax

    def _scope(idx, op):
        role = role_scope(op.role)
        if not ctx.op_scopes and not op.scope:
            return role
        stack = contextlib.ExitStack()
        stack.enter_context(role)
        for name in op.scope:
            stack.enter_context(jax.named_scope(name))
        if ctx.op_scopes:
            stack.enter_context(jax.named_scope(f"op{idx}:{op.type}"))
        return stack

    wanted: Dict[tuple, int] = {}
    for op in ops:
        if op.type.endswith("_grad"):
            spec = get(op.type)
            if spec is not None and spec.generic_vjp:
                k = _fwd_key_from_grad(op)
                wanted[k] = wanted.get(k, 0) + 1

    for op_idx, op in enumerate(ops):
        spec = get(op.type)
        if spec is None:
            raise KeyError(f"op {op.type!r} has no registered emitter")
        ins = {}
        for slot, names in op.inputs.items():
            vals = []
            for n in names:
                if n not in env:
                    raise RuntimeError(
                        f"op {op.type}: input var {n!r} not produced, fed, "
                        f"captured, nor in scope"
                    )
                vals.append(env[n])
            if vals:
                ins[slot] = vals

        with _scope(op_idx, op):
            outs = None
            if spec.generic_vjp:
                cached = ctx.vjp_cache.get(_fwd_key_from_grad(op))
                if cached:
                    f_outs, vjp_fn, fwd_ins = cached.pop()
                    outs = _apply_vjp(ins, f_outs, vjp_fn, fwd_ins)
            elif (
                not spec.no_vjp_grad
                and not spec.stop_gradient
                and spec.grad_maker is None
                and wanted.get(_fwd_key_from_fwd(op), 0) > 0
            ):
                key = _fwd_key_from_fwd(op)
                attrs = op.attrs

                def fn(fi, _spec=spec, _attrs=attrs):
                    return _spec.emit(ctx, fi, _attrs)

                outs, vjp_fn = jax.vjp(fn, ins)
                ctx.vjp_cache.setdefault(key, []).append((outs, vjp_fn, ins))
                wanted[key] -= 1
            if outs is None:
                outs = spec.emit(ctx, ins, op.attrs)
        for slot, names in op.outputs.items():
            vals = outs.get(slot)
            if vals is None:
                continue
            for n, v in zip(names, vals):
                env[n] = v
        if on_op is not None:
            on_op(op_idx, op, outs)
    return env


# ---------------------------------------------------------------------------
# abstract evaluation (shape/dtype inference service for framework.py)
# ---------------------------------------------------------------------------


def abstract_eval(op_type: str, in_metas, attrs, dyn_probe: int):
    """Run the emitter under jax.eval_shape.

    in_metas: {slot: [(shape|None, np.dtype)]}; -1 dims replaced by
    dyn_probe. Returns {slot: [(shape, dtype)]}.
    """
    import jax

    spec = get(op_type)
    structs = {}
    for slot, metas in in_metas.items():
        structs[slot] = [
            jax.ShapeDtypeStruct(
                tuple(dyn_probe if d == -1 else d for d in (shape or ())), dtype
            )
            for shape, dtype in metas
        ]

    def fn(ins):
        ctx = EmitContext(rng_key=jax.random.PRNGKey(0))
        return spec.emit(ctx, ins, dict(attrs))

    out = jax.eval_shape(fn, structs)
    return {
        slot: [(tuple(int(d) for d in v.shape), np.dtype(v.dtype)) for v in vals]
        for slot, vals in out.items()
    }
