"""Control-flow ops: cond (lax.cond) and while (lax.while_loop).

Parity: /root/reference/paddle/fluid/operators/controlflow/
(conditional_block_op.cc, while_op.cc). The reference interprets
sub-blocks with per-step Scopes; here sub-blocks are SSA-ified —
captured outer vars become explicit operands, block-carried state becomes
lax loop carries — so the whole construct compiles into HLO
Conditional/While (SURVEY.md §7 "hard parts": per-step scopes -> SSA).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import registry
from .registry import register


def _scalar_pred(p):
    p = jnp.asarray(p)
    if p.ndim > 0:
        p = p.reshape(())
    return p.astype(jnp.bool_)


def _cond_infer(in_metas, attrs):
    blk = attrs["true_block"]
    metas = []
    for n in attrs["true_out_names"]:
        v = blk._find_var_recursive(n)
        metas.append((v.shape, v.dtype))
    return {"Out": metas}


@register("cond", infer_shape=_cond_infer)
def cond_op(ctx, ins, attrs):
    captured = list(attrs["captured_names"])
    cap_vals = list(ins.get("Input", []))
    t_blk, f_blk = attrs["true_block"], attrs["false_block"]
    t_outs, f_outs = attrs["true_out_names"], attrs["false_out_names"]

    def make_branch(blk, out_names):
        def f(cap):
            env = dict(zip(captured, cap))
            registry.emit_ops(ctx, blk.ops, env)
            return tuple(env[n] for n in out_names)

        return f

    outs = jax.lax.cond(
        _scalar_pred(ins["Cond"][0]),
        make_branch(t_blk, t_outs),
        make_branch(f_blk, f_outs),
        tuple(cap_vals),
    )
    return {"Out": list(outs)}


def _while_infer(in_metas, attrs):
    return {"Out": list(in_metas.get("LoopVars", []))}


@register("while_loop", infer_shape=_while_infer, no_vjp_grad=True)
def while_loop_op(ctx, ins, attrs):
    """inputs: LoopVars (carried state), Input (captured constants).
    attrs: cond_block/body_block, loop_var_names (names the blocks use for
    the carries), cond_out_name, body_out_names, captured_names."""
    captured = dict(zip(attrs["captured_names"], ins.get("Input", [])))
    loop_names = list(attrs["loop_var_names"])
    cond_blk, body_blk = attrs["cond_block"], attrs["body_block"]

    def cond_fn(carry):
        env = dict(captured)
        env.update(zip(loop_names, carry))
        registry.emit_ops(ctx, cond_blk.ops, env)
        return _scalar_pred(env[attrs["cond_out_name"]])

    def body_fn(carry):
        env = dict(captured)
        env.update(zip(loop_names, carry))
        registry.emit_ops(ctx, body_blk.ops, env)
        out = []
        for init, name in zip(carry, attrs["body_out_names"]):
            v = env[name]
            out.append(jnp.asarray(v, jnp.asarray(init).dtype))
        return tuple(out)

    final = jax.lax.while_loop(cond_fn, body_fn, tuple(ins["LoopVars"]))
    return {"Out": list(final)}


@register("select_input", infer_shape=lambda m, a: {"Out": [m["X"][0]]})
def select_input(ctx, ins, attrs):
    """Out = X[Mask] — reference controlflow/select_input_op."""
    mask = _scalar_pred(ins["Mask"][0]).astype(jnp.int32)
    xs = ins["X"]
    out = jax.lax.switch(jnp.clip(mask, 0, len(xs) - 1), [lambda x=x: x for x in xs])
    return {"Out": [out]}


def _recurrent_infer(in_metas, attrs):
    blk = attrs["step_block"]
    t = attrs["__seq_len__"]
    outs = []
    for n in attrs["step_output_names"]:
        v = blk._find_var_recursive(n)
        outs.append(((v.shape[0], t) + tuple(v.shape[1:]), v.dtype))
    states = []
    for n in attrs["memory_out_names"]:
        v = blk._find_var_recursive(n)
        states.append((v.shape, v.dtype))
    return {"Out": outs, "FinalStates": states}


@register("recurrent", infer_shape=_recurrent_infer)
def recurrent_op(ctx, ins, attrs):
    """Block-based RNN (reference recurrent_op.cc / StaticRNN): scan the
    step sub-block over the time axis. The reference runs the block in a
    per-step Scope; here the block is SSA-ified into a lax.scan body —
    memories are the carries, step inputs are the scanned xs — so the
    whole recurrence compiles to one HLO While and reverse-mode AD works
    through the generic vjp path (no per-step scopes to differentiate).

    inputs: StepInputs [B,T,...] (sliced per step), Memories (initial
    carry values), Captured (loop constants).
    attrs: step_block, step_input_names, memory_in_names,
    memory_out_names, step_output_names, captured_names, is_reverse."""
    step_blk = attrs["step_block"]
    step_in_names = list(attrs["step_input_names"])
    mem_in = list(attrs["memory_in_names"])
    mem_out = list(attrs["memory_out_names"])
    out_names = list(attrs["step_output_names"])
    captured = dict(zip(attrs["captured_names"], ins.get("Captured", [])))
    reverse = bool(attrs.get("is_reverse", False))

    xs = [jnp.swapaxes(x, 0, 1) for x in ins.get("StepInputs", [])]  # [T,B,..]
    if reverse:
        xs = [jnp.flip(x, 0) for x in xs]
    mems = tuple(ins.get("Memories", []))

    def body(carry, x_t):
        env = dict(captured)
        env.update(zip(mem_in, carry))
        env.update(zip(step_in_names, x_t))
        registry.emit_ops(ctx, step_blk.ops, env)
        new_carry = tuple(env[n] for n in mem_out)
        outs = tuple(env[n] for n in out_names)
        return new_carry, outs

    final, stacked = jax.lax.scan(body, mems, tuple(xs))
    outs = [jnp.swapaxes(o, 0, 1) for o in stacked]  # [B,T,...]
    if reverse:
        outs = [jnp.flip(o, 1) for o in outs]
    return {"Out": outs, "FinalStates": list(final)}


@register("py_func")
def py_func_op(ctx, ins, attrs):
    """Python-callback op (reference controlflow/py_func_op.cc): run a
    host Python callable inside the compiled program via
    jax.pure_callback. The callable is stored in the op attrs (the same
    way sub-Blocks are). backward_func, when given, defines the vjp —
    also as a host callback."""
    import numpy as np

    xs = ins["X"]
    fwd = attrs["pyfunc_fwd"]
    bwd = attrs.get("pyfunc_bwd")
    skip_idx = set(attrs.get("pyfunc_skip_idx", []))
    out_specs = [
        jax.ShapeDtypeStruct(tuple(s), jnp.dtype(str(np.dtype(d))))
        for s, d in attrs["pyfunc_out_meta"]
    ]

    def host_fwd(*arrs):
        res = fwd(*arrs)
        res = res if isinstance(res, (list, tuple)) else [res]
        if len(res) != len(out_specs):
            raise ValueError(
                f"py_func forward returned {len(res)} arrays; the op "
                f"declares {len(out_specs)} outputs"
            )
        return tuple(np.asarray(r, dtype=spec.dtype) for r, spec in zip(res, out_specs))

    if bwd is None:
        outs = jax.pure_callback(host_fwd, tuple(out_specs), *xs)
        return {"Out": list(outs)}

    @jax.custom_vjp
    def call(*xs_):
        return jax.pure_callback(host_fwd, tuple(out_specs), *xs_)

    def call_fwd(*xs_):
        return call(*xs_), xs_

    def call_bwd(res_xs, gs):
        # cotangents are produced only for ACTIVE inputs: float dtype and
        # not listed in skip_vars_in_backward_input; everything else gets
        # None (ints cannot carry gradients)
        active = [
            i for i, x in enumerate(res_xs)
            if i not in skip_idx and jnp.issubdtype(x.dtype, jnp.floating)
        ]

        def host_bwd(*arrs):
            n = len(res_xs)
            # reference py_func contract: inputs listed in
            # skip_vars_in_backward_input are omitted from the bwd args
            fwd_args = [a for i, a in enumerate(arrs[:n]) if i not in skip_idx]
            grads = bwd(*fwd_args, *arrs[n:])
            grads = grads if isinstance(grads, (list, tuple)) else [grads]
            if len(grads) != len(active):
                raise ValueError(
                    f"py_func backward returned {len(grads)} gradients; "
                    f"expected {len(active)} (one per float non-skipped input)"
                )
            return tuple(
                np.asarray(g, dtype=np.dtype(str(res_xs[i].dtype)))
                for g, i in zip(grads, active)
            )

        in_specs = tuple(
            jax.ShapeDtypeStruct(res_xs[i].shape, res_xs[i].dtype)
            for i in active
        )
        dact = jax.pure_callback(host_bwd, in_specs, *res_xs, *gs)
        out = [None] * len(res_xs)
        for g, i in zip(dact, active):
            out[i] = g
        return tuple(out)

    call.defvjp(call_fwd, call_bwd)
    outs = call(*xs)
    return {"Out": list(outs)}


@register("print", no_vjp_grad=True,
          infer_shape=lambda m, a: {"Out": [m["In"][0]]})
def print_op(ctx, ins, attrs):
    """Runtime tensor printing (reference print_op.cc) via a host
    callback; honors first_n (stop after N prints) and summarize
    (np.array2string threshold). Out aliases In so the print stays
    ordered relative to consumers."""
    x = ins["In"][0]
    msg = str(attrs.get("message") or "")
    name = str(attrs.get("var_name", ""))
    first_n = int(attrs.get("first_n", -1))
    summarize = int(attrs.get("summarize", 20))
    state = {"n": 0}  # one closure per compiled program (trace-time)

    def _emit(val):
        import numpy as np

        if 0 <= first_n <= state["n"]:
            return
        state["n"] += 1
        body = np.array2string(
            np.asarray(val), threshold=summarize if summarize > 0 else 1000)
        print(f"{msg}{name} = {body}", flush=True)

    jax.debug.callback(_emit, x, ordered=False)
    return {"Out": [x]}


@register("assert", no_vjp_grad=True, stop_gradient=True,
          infer_shape=lambda m, a: {"Out": [((1,), "bool")]})
def assert_op(ctx, ins, attrs):
    """Runtime assertion (reference assert_op.cc): host callback raises
    when the condition is false, aborting the step."""
    cond = _scalar_pred(ins["Cond"][0])
    data = [jnp.asarray(d) for d in ins.get("Data", [])]

    def _check(c, *vals):
        import numpy as np

        if not bool(np.asarray(c)):
            raise AssertionError(
                "layers.Assert failed"
                + ("; data: " + ", ".join(repr(np.asarray(v)) for v in vals)
                   if vals else "")
            )

    jax.debug.callback(_check, cond, *data, ordered=False)
    return {"Out": [cond.reshape(1)]}
