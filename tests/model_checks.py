"""What the op and model tests share: a relative error, one op or one layer
run through Program -> Executor on seeded weights, and a float32
reference's value and gradients at the highest matmul precision."""
import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.executor import Scope

INIT = fluid.initializer.TruncatedNormalInitializer(scale=0.3)


def rel(a, b):
    """Relative L2 error of a against b, in float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def run_op(build, feed, amp=False, lowered=None, traced=None):
    """Build `out = build(x)` over a fed x, loss = sum(out * w) for a fixed
    random w, minimize with SGD(0) so that nothing moves, and return (out,
    {parameter or 'x': gradient}, parameters, w, extras). A list given as
    `lowered` receives the lowered step's text, one given as `traced` the
    traced step (`Executor._trace_step`)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=list(feed["x"].shape), dtype="float32",
                        append_batch_size=False)
        # a zero parameter added to x: its gradient is x's
        shift = layers.create_parameter(
            shape=list(feed["x"].shape), dtype="float32", name="x_shift",
            default_initializer=fluid.initializer.ConstantInitializer(0.0))
        out, extras = build(layers.elementwise_add(x, shift))
        w = layers.data("w", shape=list(out.shape), dtype="float32",
                        append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(
            layers.cast(out, "float32"), w))
        opt = fluid.optimizer.SGD(learning_rate=0.0)
        if amp:
            opt = mixed_precision.decorate(opt, use_bf16=True)
        _, pgs = opt.minimize(loss, startup_program=startup)
    exe, scope = fluid.Executor(), Scope()
    exe.run(startup, scope=scope)
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in main.all_parameters() if p.name != "x_shift"}
    rng = np.random.RandomState(5)
    wv = rng.uniform(0.5, 1.5, out.shape).astype(np.float32)
    grads = {p.name: g.name for p, g in pgs if g is not None}
    names = sorted(grads)
    fetch = [out.name] + [grads[n] for n in names] + [e.name for e in extras]
    step = dict(feed={"x": feed["x"], "w": wv}, fetch_list=fetch,
                scope=scope)
    if lowered is not None:
        lowered.append(exe._lower_step(main, **step).as_text(
            debug_info=True))
    if traced is not None:
        traced.append(exe._trace_step(main, **step))
    got = exe.run(main, **step)
    g = dict(zip(names, got[1:1 + len(names)]))
    g["x"] = g.pop("x_shift")
    return got[0], g, params, wv, got[1 + len(names):]


def ref_grads(fn, x, params, w):
    """Gradients of sum(fn(x, params) * w) in float32 at highest precision."""
    with jax.default_matmul_precision("highest"):
        out = fn(jnp.asarray(x), params)
        gx, gp = jax.grad(
            lambda x, p: jnp.sum(fn(x, p) * w), argnums=(0, 1))(
                jnp.asarray(x), {k: jnp.asarray(v) for k, v in params.items()})
    return out, {"x": gx, **gp}


def assert_close(got_out, got_grads, want_out, want_grads, tol):
    assert rel(got_out, want_out) < tol
    assert set(got_grads) == set(want_grads)
    for name, g in got_grads.items():
        assert rel(g, want_grads[name]) < tol, name


def run_layer(build, x, weights):
    """out = build(x) with the named parameters set to `weights`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        v = layers.data("x", shape=list(x.shape), dtype="float32",
                        append_batch_size=False)
        out = build(v)
    exe, scope = fluid.Executor(), Scope()
    exe.run(startup, scope=scope)
    names = {p.name for p in main.all_parameters()}
    assert names == set(weights), names ^ set(weights)
    for name, w in weights.items():
        assert scope.find_var(name).shape == w.shape, name
        scope.set_var(name, jnp.asarray(w))
    return exe.run(main, feed={"x": x}, fetch_list=[out], scope=scope)[0]


def reference_value_and_grad(loss_fn, params, has_aux=False):
    """`jax.value_and_grad(loss_fn)(params)` as one compiled program, in
    float32 at the highest matmul precision: a reference run eagerly
    compiles each of its operations on its own."""
    params = {k: jnp.asarray(v) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss_fn, has_aux=has_aux))(params)
