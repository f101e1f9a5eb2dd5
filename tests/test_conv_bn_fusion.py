"""conv2d -> batch_norm [-> relu] fusion: the fused_conv_bn op
(ops/pallas/conv_bn.py mega-kernel + jnp fallback, identical math) and
the graph pass (fluid/fusion_pass.py).

Covers: kernel-vs-oracle fwd+bwd in interpret mode (strides 1/2,
SAME/VALID, odd channel counts, kernel 1/3/7), op_test numeric gradient
exactness through the real Program path, bf16 tolerance vs the unfused
emitters, pass-level matching rules (grouped/dilated/shared-intermediate
left untouched, is_test folded), FLAGS_conv_bn_fusion=0 no-op, and
fused-vs-unfused training parity (plain and under AMP)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import flags, layers
from paddle_tpu.fluid.fusion_pass import apply_conv_bn_fusion
from paddle_tpu.ops import nn_ops
from paddle_tpu.ops.pallas import conv_bn as cb

from op_test import OpTest


# ---------------------------------------------------------------------------
# Pallas kernel vs the jnp oracle (interpret mode on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("xs,ws,strides,pads,with_relu", [
    ((2, 8, 8, 8), (16, 8, 3, 3), (1, 1), "SAME", True),   # ResNet 3x3 class
    ((2, 8, 8, 8), (16, 8, 1, 1), (1, 1), "VALID", False), # bottleneck 1x1
    ((2, 8, 8, 8), (16, 8, 1, 1), (2, 2), "VALID", True),  # strided projection
    ((2, 9, 9, 5), (7, 5, 3, 3), (1, 1), "VALID", False),  # odd channels/size
    ((1, 6, 6, 4), (8, 4, 7, 7), (1, 1), "SAME", True),    # stem-class kernel
])
def test_kernel_matches_oracle(pinned, xs, ws, strides, pads, with_relu):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*xs).astype(np.float32))
    w = jnp.asarray(rng.randn(*ws).astype(np.float32) * 0.1)
    o = ws[0]
    scale = jnp.asarray(rng.rand(o).astype(np.float32) + 0.5)
    bias = jnp.asarray(rng.randn(o).astype(np.float32))
    pr = cb._resolve_pads(pads, xs[1], xs[2], ws[2], ws[3], strides)
    ref = cb.conv_bn_reference(x, w, scale, bias, strides=strides, pads=pr,
                               with_relu=with_relu)
    assert cb.conv_bn_dispatch_ok(x.shape, w.shape, tuple(strides), pr)
    out = cb.fused_conv_bn(x, w, scale, bias, strides=strides, pads=pads,
                           with_relu=with_relu)
    for got, exp, nm in zip(out, ref, ("y", "mean", "var")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=2e-5, atol=2e-5, err_msg=nm)

    def make_loss(fn, pad_arg):
        def f(x_, w_, s_, b_):
            y, _, _ = fn(x_, w_, s_, b_, strides=strides, pads=pad_arg,
                         with_relu=with_relu)
            return jnp.sum(y * jnp.cos(y))
        return f

    g_pallas = jax.grad(make_loss(cb.fused_conv_bn, pads),
                        argnums=(0, 1, 2, 3))(x, w, scale, bias)
    g_ref = jax.grad(make_loss(cb.conv_bn_reference, pr),
                     argnums=(0, 1, 2, 3))(x, w, scale, bias)
    for got, exp, nm in zip(g_pallas, g_ref, ("dx", "dw", "dscale", "dbias")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=5e-4, atol=5e-4, err_msg=nm)


def test_shape_gate():
    p0 = ((0, 0), (0, 0))
    p1 = ((1, 1), (1, 1))
    ok = cb.conv_bn_shapes_ok
    assert ok((2, 8, 8, 8), (16, 8, 3, 3), (1, 1), p1)
    assert ok((2, 8, 8, 8), (16, 8, 1, 1), (2, 2), p0)
    assert not ok((2, 8, 8, 8), (16, 4, 3, 3), (1, 1), p1, groups=2)
    assert not ok((2, 8, 8, 8), (16, 8, 3, 3), (1, 1), p1, dilations=(2, 2))
    assert not ok((2, 8, 8, 8), (16, 8, 3, 3), (2, 2), p1)  # k>1 strided
    assert not ok((2, 8, 8, 8), (16, 8, 1, 1), (1, 1), p1)  # padded 1x1


# ---------------------------------------------------------------------------
# op-level: numeric gradients through the real Program path
# ---------------------------------------------------------------------------


def _oracle_factory(with_relu):
    def oracle(ins, attrs):
        x = jnp.asarray(ins["Input"][0])
        w = jnp.asarray(ins["Filter"][0])
        strides = tuple(attrs.get("strides", [1, 1]))
        pads = nn_ops._conv_padding(
            attrs.get("paddings", [0, 0]),
            attrs.get("padding_algorithm", "EXPLICIT"), 2)
        pads = cb._resolve_pads(pads, x.shape[1], x.shape[2],
                                w.shape[2], w.shape[3], strides)
        y, _, _ = cb.conv_bn_reference(
            x, w, jnp.asarray(ins["Scale"][0]), jnp.asarray(ins["Bias"][0]),
            strides=strides, pads=pads,
            eps=attrs.get("epsilon", 1e-5), with_relu=with_relu)
        return {"Y": [np.asarray(y)]}
    return oracle


@pytest.mark.parametrize("stride,algo,ksize,cin,cout,with_relu", [
    (1, "SAME", 3, 6, 10, False),
    (1, "VALID", 3, 5, 7, False),   # odd channel counts
    (2, "VALID", 1, 6, 8, False),   # strided projection shortcut
])
def test_op_numeric_gradients(stride, algo, ksize, cin, cout, with_relu):
    rng = np.random.RandomState(3)
    x = rng.randn(2, 6, 6, cin).astype(np.float32)
    w = (rng.randn(cout, cin, ksize, ksize) * 0.2).astype(np.float32)
    OpTest(
        "fused_conv_bn",
        inputs={
            "Input": x,
            "Filter": w,
            "Scale": (rng.rand(cout) + 0.5).astype(np.float32),
            "Bias": rng.randn(cout).astype(np.float32),
            "Mean": np.zeros(cout, np.float32),
            "Variance": np.ones(cout, np.float32),
        },
        attrs={
            "strides": [stride, stride],
            "padding_algorithm": algo,
            "data_format": "NHWC",
            "data_layout": "NHWC",
            "with_relu": with_relu,
        },
        outputs={"Y": 1},
        oracle=_oracle_factory(with_relu),
        grad=("Input", "Filter", "Scale", "Bias"),
        grad_eps=1e-2,
        grad_tol=2e-2,
    ).run()


def test_bf16_matches_unfused_emitters():
    """Fused emitter vs the unfused conv2d+batch_norm+relu emitter chain
    on bf16 activations (the AMP configuration), bf16 tolerance."""
    from paddle_tpu.ops.registry import EmitContext, get

    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 8, 8, 8).astype(np.float32)).astype(jnp.bfloat16)
    w = jnp.asarray((rng.randn(12, 8, 3, 3) * 0.2).astype(np.float32)).astype(jnp.bfloat16)
    scale = jnp.asarray((rng.rand(12) + 0.5).astype(np.float32))
    bias = jnp.asarray(rng.randn(12).astype(np.float32))
    mean = jnp.zeros(12, jnp.float32)
    var = jnp.ones(12, jnp.float32)
    conv_attrs = {"strides": [1, 1], "paddings": [1, 1],
                  "data_format": "NHWC"}
    ctx = EmitContext()
    z = get("conv2d").emit(ctx, {"Input": [x], "Filter": [w]}, conv_attrs)
    bn = get("batch_norm").emit(ctx, {
        "X": z["Output"], "Scale": [scale], "Bias": [bias],
        "Mean": [mean], "Variance": [var],
    }, {"data_layout": "NHWC"})
    y_unfused = jnp.maximum(bn["Y"][0].astype(jnp.float32), 0.0)
    fused = get("fused_conv_bn").emit(ctx, {
        "Input": [x], "Filter": [w], "Scale": [scale], "Bias": [bias],
        "Mean": [mean], "Variance": [var],
    }, dict(conv_attrs, data_layout="NHWC", with_relu=True))
    np.testing.assert_allclose(
        np.asarray(fused["Y"][0], np.float32), np.asarray(y_unfused),
        rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(fused["MeanOut"][0]),
                               np.asarray(bn["MeanOut"][0]),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# pass-level
# ---------------------------------------------------------------------------


def _conv_bn_program(groups=1, dilation=1, act="relu", layout="NHWC"):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.data("img", [2, 3, 8, 8], "float32")
        x = layers.transpose(img, [0, 2, 3, 1]) if layout == "NHWC" else img
        c = layers.conv2d(x, 4, 3, padding=dilation, groups=1,
                          bias_attr=False, data_format=layout)
        c = layers.conv2d(c, 8, 3, padding=dilation, dilation=dilation,
                          groups=groups, bias_attr=False, data_format=layout)
        bn = layers.batch_norm(c, act=act, data_layout=layout)
        out = layers.reduce_mean(bn)
    return main, startup, out


def _types(program):
    return [op.type for op in program.global_block().ops]


def test_pass_fuses_plain_pattern():
    main, _, _ = _conv_bn_program()
    n = apply_conv_bn_fusion(main)
    assert n == 1
    t = _types(main)
    assert "fused_conv_bn" in t and "batch_norm" not in t
    fused = [op for op in main.global_block().ops
             if op.type == "fused_conv_bn"][0]
    assert fused.attr("with_relu") is True
    assert t.count("relu") == 0


def test_pass_skips_grouped_and_dilated():
    for kwargs in ({"groups": 2}, {"dilation": 2}):
        main, _, _ = _conv_bn_program(**kwargs)
        assert apply_conv_bn_fusion(main) == 0
        assert "batch_norm" in _types(main)


def test_pass_keeps_shared_bn_output_unfused_relu():
    """BN output consumed twice: conv+BN still fuse, but the relu stays a
    separate op (folding it would hide the pre-activation value)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.data("img", [2, 4, 8, 8], "float32")
        x = layers.transpose(img, [0, 2, 3, 1])
        c = layers.conv2d(x, 8, 3, padding=1, bias_attr=False,
                          data_format="NHWC")
        bn = layers.batch_norm(c, data_layout="NHWC")
        r = layers.relu(bn)
        extra = layers.reduce_sum(bn)  # second consumer of BN's Y
    n = apply_conv_bn_fusion(main)
    assert n == 1
    t = _types(main)
    assert "fused_conv_bn" in t and "relu" in t
    fused = [op for op in main.global_block().ops
             if op.type == "fused_conv_bn"][0]
    assert fused.attr("with_relu") is False


def test_pass_skips_conv_with_two_consumers():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.data("img", [2, 4, 8, 8], "float32")
        x = layers.transpose(img, [0, 2, 3, 1])
        c = layers.conv2d(x, 8, 3, padding=1, bias_attr=False,
                          data_format="NHWC")
        bn = layers.batch_norm(c, data_layout="NHWC")
        other = layers.reduce_sum(c)  # second consumer of the conv output
    assert apply_conv_bn_fusion(main) == 0
    assert "batch_norm" in _types(main)


def test_pass_folds_is_test():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.data("img", [2, 3, 8, 8], "float32")
        x = layers.transpose(img, [0, 2, 3, 1])
        c = layers.conv2d(x, 6, 3, padding=1, bias_attr=False,
                          data_format="NHWC")
        out = layers.batch_norm(c, act="relu", data_layout="NHWC")
    test_p = main.clone(for_test=True)
    assert apply_conv_bn_fusion(test_p) == 1
    fused = [op for op in test_p.global_block().ops
             if op.type == "fused_conv_bn"][0]
    assert fused.attr("is_test") is True
    rng = np.random.RandomState(2)
    feed = {"img": rng.randn(2, 3, 8, 8).astype("f4")}
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.executor.Scope()):
        exe.run(startup)
        (a,) = exe.run(main.clone(for_test=True), feed=feed,
                       fetch_list=[out.name])
        (b,) = exe.run(test_p, feed=feed, fetch_list=[out.name])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_flag_off_is_noop():
    """FLAGS_conv_bn_fusion=0 (the default): minimize leaves the program
    op-for-op identical to the unfused baseline."""
    assert flags.get_flags(["FLAGS_conv_bn_fusion"])["FLAGS_conv_bn_fusion"] is False

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = fluid.data("img", [2, 3, 8, 8], "float32")
            y = fluid.data("y", [2, 1], "int64")
            x = layers.transpose(img, [0, 2, 3, 1])
            c = layers.conv2d(x, 6, 3, padding=1, bias_attr=False,
                              data_format="NHWC")
            c = layers.batch_norm(c, act="relu", data_layout="NHWC")
            logits = layers.fc(c, 4)
            loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
        return main

    assert _types(build()) == _types(build())
    assert "fused_conv_bn" not in _types(build())
    assert "batch_norm" in _types(build())


def _train(fuse, amp=False, steps=5, seed=7):
    flags.set_flags({"FLAGS_conv_bn_fusion": fuse})
    try:
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            img = fluid.data("img", [4, 3, 16, 16], "float32")
            y = fluid.data("y", [4, 1], "int64")
            x = layers.transpose(img, [0, 2, 3, 1])
            c = layers.conv2d(x, 8, 3, padding=1, bias_attr=False,
                              data_format="NHWC")
            c = layers.batch_norm(c, act="relu", data_layout="NHWC")
            c = layers.conv2d(c, 8, 1, bias_attr=False, data_format="NHWC")
            c = layers.batch_norm(c, data_layout="NHWC")
            logits = layers.fc(c, 5)
            loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
            opt = fluid.optimizer.MomentumOptimizer(0.05, momentum=0.9)
            if amp:
                from paddle_tpu.contrib import mixed_precision as mp

                opt = mp.decorate(opt, use_bf16=True)
            opt.minimize(loss)
        types = _types(main)
        exe = fluid.Executor()
        rng = np.random.RandomState(1)
        feed = {"img": rng.randn(4, 3, 16, 16).astype("f4"),
                "y": rng.randint(0, 5, (4, 1)).astype("i8")}
        with fluid.scope_guard(fluid.executor.Scope()):
            exe.run(startup)
            losses = [
                float(np.asarray(
                    exe.run(main, feed=feed, fetch_list=[loss])[0]
                ).reshape(()))
                for _ in range(steps)
            ]
        return types, losses
    finally:
        flags.set_flags({"FLAGS_conv_bn_fusion": False})


def test_training_parity_fused_vs_unfused():
    tf, lf = _train(True)
    tu, lu = _train(False)
    assert tf.count("fused_conv_bn") == 2
    assert "batch_norm" not in tf
    assert tf.count("fused_conv_bn_grad") == 2
    assert "fused_conv_bn" not in tu
    np.testing.assert_allclose(lf, lu, rtol=1e-5, atol=1e-6)
    assert lf[-1] < lf[0]


def test_training_under_amp():
    tf, lf = _train(True, amp=True)
    assert "fused_conv_bn" in tf and "batch_norm" not in tf
    assert all(np.isfinite(l) for l in lf)
    assert lf[-1] < lf[0]
