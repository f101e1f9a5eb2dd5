"""Neural-net ops: conv/pool/norm/dropout/embedding/losses.

Parity surface: reference conv_op.cc + conv_cudnn_op.cu.cc, pool_op.cc,
batch_norm_op.cc, layer_norm_op.cc, group_norm_op.cc, instance_norm_op.cc,
dropout_op.cc, lookup_table_v2_op.cc, one_hot_v2_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, sigmoid_cross_entropy_with_logits_op.cc,
squared_error / huber / log_loss ops, metrics/accuracy_op.cc.

TPU notes: convs lower to lax.conv_general_dilated (XLA tiles them onto the
MXU); embedding grad becomes a fused scatter-add via the generic vjp path —
the TPU-native replacement for the reference's SelectedRows sparse grad
(framework/selected_rows.h:32). dropout registers an explicit grad op that
reuses the saved Mask so backward sees the same randomness as forward.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..fluid.dtypes import convert_dtype
from .registry import register, set_grad_maker


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------


def _conv_padding(paddings, algo, ndim_spatial):
    if algo == "SAME":
        return "SAME"
    if algo == "VALID":
        return "VALID"
    p = list(paddings)
    if len(p) == ndim_spatial:
        return [(pi, pi) for pi in p]
    if len(p) == 2 * ndim_spatial:
        return [(p[2 * i], p[2 * i + 1]) for i in range(ndim_spatial)]
    raise ValueError(f"bad paddings {paddings}")


def _conv2d_impl(x, w, attrs):
    strides = tuple(attrs.get("strides", [1, 1]))
    dil = tuple(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1))
    algo = attrs.get("padding_algorithm", "EXPLICIT")
    pad = _conv_padding(attrs.get("paddings", [0, 0]), algo, 2)
    df = attrs.get("data_format", "NCHW")
    if df in ("NCHW", "AnyLayout"):
        dn = ("NCHW", "OIHW", "NCHW")
    else:
        # weights are ALWAYS stored OIHW in paddle programs — tell lax so
        # directly instead of transposing (shape-sniffing for HWIO
        # misfired whenever k == C_in/groups)
        dn = ("NHWC", "OIHW", "NHWC")
    return jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad,
        rhs_dilation=dil, dimension_numbers=dn, feature_group_count=groups,
    )


def _conv2d_key(attrs):
    return (
        tuple(attrs.get("strides", [1, 1])),
        tuple(attrs.get("dilations", [1, 1])),
        int(attrs.get("groups", 1)),
        attrs.get("padding_algorithm", "EXPLICIT"),
        tuple(attrs.get("paddings", [0, 0])),
        attrs.get("data_format", "NCHW"),
    )


import functools as _ft  # noqa: E402 — local to the conv vjp cache


@_ft.lru_cache(maxsize=64)
def _conv2d_im2col_dw_fn(key):
    """conv2d with an im2col-matmul dW formulation (custom vjp).

    The reference answers dW-conv slowness with cudnn's exhaustive algo
    search (conv_cudnn_op.cu.cc); XLA has one dW lowering and no search
    knob. This path reformulates ONLY the weight gradient: extract the
    kernel-window patches of x (conv_general_dilated_patches) and
    contract them against dy in a single [C*kh*kw, NHoWo]x[NHoWo, O]
    einsum — the MXU sees one big matmul instead of XLA's dW-conv
    schedule. dX keeps the standard transposed-conv lowering (it was
    never the bottleneck). NHWC, groups=1. Costs kh*kw x activation
    traffic for the patches, so it wins only where the dW conv is far
    off roofline — gate via FLAGS_conv_dw_im2col and measure.
    """
    strides, dil, groups, algo, paddings, df = key
    attrs = {"strides": list(strides), "dilations": list(dil),
             "groups": groups, "padding_algorithm": algo,
             "paddings": list(paddings), "data_format": df}

    @jax.custom_vjp
    def conv(x, w):
        return _conv2d_impl(x, w, attrs)

    def fwd(x, w):
        return conv(x, w), (x, w)

    def bwd(res, dy):
        x, w = res
        # dX: XLA's transposed-conv lowering via the standard vjp
        _, vjp_x = jax.vjp(lambda x_: _conv2d_impl(x_, w, attrs), x)
        (dx,) = vjp_x(dy)
        # dW: im2col patches -> one matmul
        o, cg, kh, kw = w.shape
        pad = _conv_padding(paddings, algo, 2)
        patches = jax.lax.conv_general_dilated_patches(
            x, filter_shape=(kh, kw), window_strides=strides,
            padding=pad if isinstance(pad, str) else tuple(pad),
            rhs_dilation=dil,
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
        )  # [N, Ho, Wo, C*kh*kw], feature index = c*kh*kw + ki*kw + kj
        dw_flat = jnp.einsum(
            "nhwp,nhwo->op", patches, dy,
            preferred_element_type=jnp.float32,
        )
        dw = dw_flat.reshape(o, cg, kh, kw).astype(w.dtype)
        return dx, dw

    conv.defvjp(fwd, bwd)
    return conv


def _use_im2col_dw(attrs, w_shape):
    from ..fluid import flags as _flags

    if not _flags.get_flags(
            ["FLAGS_conv_dw_im2col"])["FLAGS_conv_dw_im2col"]:
        return False
    df = attrs.get("data_format", "NCHW")
    groups = int(attrs.get("groups", 1))
    kh, kw = int(w_shape[2]), int(w_shape[3])
    # NHWC only (the patches layout above), grouped convs excluded, and
    # 1x1 kernels gain nothing (dW already IS one matmul there)
    return df == "NHWC" and groups == 1 and (kh, kw) != (1, 1)


@register("conv2d")
def conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    if _use_im2col_dw(attrs, w.shape):
        fn = _conv2d_im2col_dw_fn(_conv2d_key(attrs))
        return {"Output": [fn(x, w)]}
    return {"Output": [_conv2d_impl(x, w, attrs)]}


@register("depthwise_conv2d")
def depthwise_conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    a = dict(attrs)
    a["groups"] = x.shape[1] if a.get("data_format", "NCHW") == "NCHW" else x.shape[-1]
    return {"Output": [_conv2d_impl(x, w, a)]}


@register("conv2d_transpose")
def conv2d_transpose(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1]))
    dil = tuple(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1))
    pad = _conv_padding(attrs.get("paddings", [0, 0]),
                        attrs.get("padding_algorithm", "EXPLICIT"), 2)
    # emulate gradient-of-conv semantics: lhs dilation
    if isinstance(pad, str):
        padding = pad
    else:
        kh = (w.shape[2] - 1) * dil[0] + 1
        kw = (w.shape[3] - 1) * dil[1] + 1
        padding = [
            (kh - 1 - pad[0][0], kh - 1 - pad[0][1]),
            (kw - 1 - pad[1][0], kw - 1 - pad[1][1]),
        ]
    w = jnp.flip(w, axis=(2, 3))  # (Cin, Cout/g, kh, kw)
    w = jnp.swapaxes(w, 0, 1) if groups == 1 else w.reshape(
        (groups, w.shape[0] // groups) + w.shape[1:]
    ).swapaxes(1, 2).reshape((w.shape[1] * groups, w.shape[0] // groups) + w.shape[2:])
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=padding,
        lhs_dilation=strides, rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"), feature_group_count=groups,
    )
    if attrs.get("output_padding"):
        op_ = attrs["output_padding"]
        if any(op_):
            out = jnp.pad(out, [(0, 0), (0, 0), (0, op_[0]), (0, op_[1])])
    return {"Output": [out]}


@register("conv3d")
def conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1, 1]))
    dil = tuple(attrs.get("dilations", [1, 1, 1]))
    groups = int(attrs.get("groups", 1))
    pad = _conv_padding(attrs.get("paddings", [0, 0, 0]),
                        attrs.get("padding_algorithm", "EXPLICIT"), 3)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad, rhs_dilation=dil,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"), feature_group_count=groups,
    )
    return {"Output": [out]}


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------


def adaptive_pool_nd(x, out_sizes, red):
    """Adaptive pooling for NON-divisible output sizes (reference
    pool_op.h AdaptStartIndex/AdaptEndIndex): spatial bin i of dimension
    `in_size -> out` spans [floor(i*in/out), ceil((i+1)*in/out)). The
    bin extents are static Python ints, so each bin is a static slice
    reduced and stacked — fixed shapes, XLA-fusable, no gathers."""
    spatial = x.shape[2:]
    assert len(spatial) == len(out_sizes)

    def pool_axis(arr, axis, in_size, out):
        bins = [
            (int(np.floor(i * in_size / out)),
             int(np.ceil((i + 1) * in_size / out)))
            for i in range(out)
        ]
        parts = [
            red(jax.lax.slice_in_dim(arr, s, e, axis=axis), axis=axis,
                keepdims=True)
            for s, e in bins
        ]
        return jnp.concatenate(parts, axis=axis)

    out = x
    for d, (in_size, o) in enumerate(zip(spatial, out_sizes)):
        out = pool_axis(out, 2 + d, in_size, o)
    return out


@register("pool2d")
def pool2d(ctx, ins, attrs):
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    ksize = list(attrs.get("ksize", [1, 1]))
    strides = list(attrs.get("strides", ksize))
    paddings = list(attrs.get("paddings", [0, 0]))
    gp = attrs.get("global_pooling", False)
    adaptive = attrs.get("adaptive", False)
    exclusive = attrs.get("exclusive", True)
    algo = attrs.get("padding_algorithm", "EXPLICIT")
    df = attrs.get("data_format", "NCHW")
    hax, wax = (2, 3) if df == "NCHW" else (1, 2)
    H, W = x.shape[hax], x.shape[wax]

    if gp or (adaptive and ksize == [1, 1]):
        red = jnp.max if ptype == "max" else jnp.mean
        return {"Out": [red(x, axis=(hax, wax), keepdims=True)]}
    if adaptive:
        oh, ow = ksize
        red = jnp.max if ptype == "max" else jnp.mean
        if H % oh == 0 and W % ow == 0:
            if df == "NCHW":
                xr = x.reshape(x.shape[0], x.shape[1], oh, H // oh, ow, W // ow)
                return {"Out": [red(xr, axis=(3, 5))]}
            xr = x.reshape(x.shape[0], oh, H // oh, ow, W // ow, x.shape[3])
            return {"Out": [red(xr, axis=(2, 4))]}
        if df != "NCHW":
            raise NotImplementedError(
                "adaptive pool with non-divisible bins supports NCHW only")
        return {"Out": [adaptive_pool_nd(x, (oh, ow), red)]}

    if algo == "SAME":
        pad = "SAME"
    elif algo == "VALID":
        pad = [(0, 0), (0, 0)]
    else:
        if len(paddings) == 2:
            pad = [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
        else:
            pad = [(paddings[0], paddings[1]), (paddings[2], paddings[3])]
    if attrs.get("ceil_mode", False) and pad != "SAME":
        # extend right/bottom padding so the window count rounds up
        def extra(dim, k, s, p):
            import math

            out = math.ceil((dim + p[0] + p[1] - k) / s) + 1
            need = (out - 1) * s + k - dim - p[0]
            return max(need - p[1], 0)

        pad = [
            (pad[0][0], pad[0][1] + extra(H, ksize[0], strides[0], pad[0])),
            (pad[1][0], pad[1][1] + extra(W, ksize[1], strides[1], pad[1])),
        ]
    if df == "NCHW":
        window = (1, 1, ksize[0], ksize[1])
        strid = (1, 1, strides[0], strides[1])
        full_pad = "SAME" if pad == "SAME" else [(0, 0), (0, 0)] + pad
    else:
        window = (1, ksize[0], ksize[1], 1)
        strid = (1, strides[0], strides[1], 1)
        full_pad = "SAME" if pad == "SAME" else [(0, 0)] + pad + [(0, 0)]
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strid, full_pad)
    else:
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strid, full_pad)
        if exclusive:
            ones = jnp.ones_like(x)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strid, full_pad)
            out = s / cnt
        else:
            out = s / (ksize[0] * ksize[1])
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


@register("batch_norm")
def batch_norm(ctx, ins, attrs):
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    use_global = attrs.get("use_global_stats", False) or is_test
    layout = attrs.get("data_layout", "NCHW")
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = tuple(x.shape[ch_axis] if i == ch_axis else 1 for i in range(x.ndim))

    # statistics ALWAYS in f32 (the layer_norm convention): the op sits
    # on AMP's low-precision list, so bf16 in/out halves the activation
    # bandwidth of the conv stack while the mean/variance math stays
    # exact. (Blacklisting BN instead made AMP materialize f32 copies of
    # every bf16 activation — profiled as the dominant ResNet-50 cost.)
    xf = x.astype(jnp.float32)
    if use_global:
        m, v = mean, var
        mean_out, var_out = mean, var
        saved_mean = jnp.zeros_like(mean)
        saved_var = jnp.zeros_like(var)
    else:
        # one-pass moments: E[x] and E[x^2] reduce in a single fusion
        # over one read of the activation (jnp.var's subtract-then-square
        # form costs a second full read); f32 accumulation keeps the
        # cancellation benign at BN's normalized ranges (cuDNN does the
        # same)
        m = jnp.mean(xf, axis=axes)
        v = jnp.maximum(jnp.mean(xf * xf, axis=axes) - m * m, 0.0)
        mean_out = momentum * mean + (1 - momentum) * m
        var_out = momentum * var + (1 - momentum) * v
        saved_mean = m
        saved_var = 1.0 / jnp.sqrt(v + eps)
    inv = 1.0 / jnp.sqrt(v + eps)
    y = (
        (xf - m.reshape(bshape)) * inv.reshape(bshape)
        * scale.astype(jnp.float32).reshape(bshape)
        + bias.astype(jnp.float32).reshape(bshape)
    ).astype(x.dtype)
    return {
        "Y": [y],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register("fused_conv_bn")
def fused_conv_bn(ctx, ins, attrs):
    """conv2d -> batch_norm [-> relu] as ONE op (fluid/fusion_pass.py).

    Training mode routes through the Pallas mega-kernels
    (ops/pallas/conv_bn.py) — conv tiles + batch statistics in one pass,
    normalize+relu in a second, with a custom VJP fusing the relu/BN
    backward chain — falling back to the identical-math jnp composition
    for shapes the kernel doesn't cover. Inference (is_test /
    use_global_stats) folds the BN into the conv weights instead: one
    conv + one bias add, no normalization pass at all.

    Output contract matches batch_norm's (Y + the four stat outputs) so
    the fusion pass can rewire the BN's consumers verbatim.
    """
    from .pallas import conv_bn as _cb

    x, w = ins["Input"][0], ins["Filter"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    with_relu = bool(attrs.get("with_relu", False))
    strides = tuple(attrs.get("strides", [1, 1]))
    algo = attrs.get("padding_algorithm", "EXPLICIT")
    pads = _conv_padding(attrs.get("paddings", [0, 0]), algo, 2)
    is_test = attrs.get("is_test", False)
    use_global = attrs.get("use_global_stats", False) or is_test
    nhwc = attrs.get("data_format", "NCHW") == "NHWC"

    if use_global:
        # weight folding: y = conv(x, w*(s*inv)) + (b - m*s*inv)
        sf = scale.astype(jnp.float32)
        inv = 1.0 / jnp.sqrt(var.astype(jnp.float32) + eps)
        gain = sf * inv
        wf = (w.astype(jnp.float32) * gain.reshape(-1, 1, 1, 1)).astype(w.dtype)
        shift = bias.astype(jnp.float32) - mean.astype(jnp.float32) * gain
        z = _conv2d_impl(x, wf, attrs)
        bshape = (1, 1, 1, -1) if nhwc else (1, -1, 1, 1)
        y = z.astype(jnp.float32) + shift.reshape(bshape)
        if with_relu:
            y = jnp.maximum(y, 0.0)
        return {
            "Y": [y.astype(x.dtype)],
            "MeanOut": [mean],
            "VarianceOut": [var],
            "SavedMean": [jnp.zeros_like(mean)],
            "SavedVariance": [jnp.zeros_like(var)],
        }

    if nhwc:
        y, m, v = _cb.fused_conv_bn(
            x, w, scale, bias, strides=strides, pads=pads, eps=eps,
            with_relu=with_relu,
        )
    else:
        # NCHW never reaches the Pallas path; compose via channel-last
        xt = jnp.transpose(x, (0, 2, 3, 1))
        pads_r = _cb._resolve_pads(pads, xt.shape[1], xt.shape[2],
                                   int(w.shape[2]), int(w.shape[3]), strides)
        y, m, v = _cb.conv_bn_reference(
            xt, w, scale, bias, strides=strides, pads=pads_r, eps=eps,
            with_relu=with_relu,
        )
        y = jnp.transpose(y, (0, 3, 1, 2))
    mean_out = momentum * mean + (1 - momentum) * m.astype(mean.dtype)
    var_out = momentum * var + (1 - momentum) * v.astype(var.dtype)
    return {
        "Y": [y],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [m.astype(mean.dtype)],
        "SavedVariance": [(1.0 / jnp.sqrt(v + eps)).astype(var.dtype)],
    }


@register("layer_norm")
def layer_norm(ctx, ins, attrs):
    # statistics ALWAYS in f32 (the fused-stack ln() convention): the op
    # can then sit on AMP's low-precision list — bf16 in/out keeps the
    # residual stream at half bandwidth while the mean/variance math
    # stays exact
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    axis = attrs.get("begin_norm_axis", 1)
    lead = tuple(x.shape[:axis])
    if axis == x.ndim - 1 and ins.get("Scale") and ins.get("Bias"):
        # last-axis affine LN rides the fused Pallas kernel (one row
        # pass with f32 stats in VMEM, custom VJP) where the gate
        # passes — the wiring FLAGS_use_fused_ln always documented.
        # Mean/Variance keep the op contract via plain reductions that
        # XLA dead-code-eliminates when (as in real programs) unused.
        from .pallas.add_ln import fused_add_ln, fused_ln_dispatch_ok

        if fused_ln_dispatch_ok(x.shape):
            y = fused_add_ln(x, None, ins["Scale"][0], ins["Bias"][0],
                             eps=eps, mesh=ctx.mesh)
            xf = x.astype(jnp.float32)
            m = jnp.mean(xf, axis=-1, keepdims=True)
            v = jnp.var(xf, axis=-1, keepdims=True)
            return {
                "Y": [y],
                "Mean": [m.reshape(lead)],
                "Variance": [v.reshape(lead)],
            }
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=tuple(range(axis, x.ndim)), keepdims=True)
    v = jnp.var(xf, axis=tuple(range(axis, x.ndim)), keepdims=True)
    y = (xf - m) * jax.lax.rsqrt(v + eps)
    tail_shape = (1,) * axis + tuple(x.shape[axis:])
    if ins.get("Scale"):
        y = y * ins["Scale"][0].astype(jnp.float32).reshape(tail_shape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].astype(jnp.float32).reshape(tail_shape)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [m.reshape(lead)],
        "Variance": [v.reshape(lead)],
    }


@register("group_norm")
def group_norm(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    groups = attrs["groups"]
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    rest = x.shape[2:]
    xg = x.reshape((n, groups, c // groups) + rest)
    axes = tuple(range(2, xg.ndim))
    m = jnp.mean(xg, axis=axes, keepdims=True)
    v = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - m) / jnp.sqrt(v + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * len(rest)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(bshape)
    return {
        "Y": [y],
        "Mean": [m.reshape(n, groups)],
        "Variance": [v.reshape(n, groups)],
    }


@register("instance_norm")
def instance_norm(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    m = jnp.mean(x, axis=axes, keepdims=True)
    v = jnp.var(x, axis=axes, keepdims=True)
    y = (x - m) / jnp.sqrt(v + eps)
    c = x.shape[1]
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(bshape)
    n = x.shape[0]
    return {
        "Y": [y],
        "SavedMean": [m.reshape(n * c)],
        "SavedVariance": [(1.0 / jnp.sqrt(v + eps)).reshape(n * c)],
    }


@register("norm")
def norm(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    nrm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / nrm], "Norm": [nrm]}


# ---------------------------------------------------------------------------
# dropout (explicit grad op reusing the saved mask)
# ---------------------------------------------------------------------------


@register("dropout", no_vjp_grad=True)
def dropout(ctx, ins, attrs):
    x = ins["X"][0]
    p = float(attrs.get("dropout_prob", 0.5))
    is_test = attrs.get("is_test", False)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        out = x * (1.0 - p) if impl == "downgrade_in_infer" else x
        return {"Out": [out], "Mask": [jnp.ones_like(x, dtype=jnp.uint8)]}
    keep = jax.random.bernoulli(ctx.rng(), 1.0 - p, x.shape)
    mask = keep.astype(jnp.uint8)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / max(1.0 - p, 1e-12), 0.0).astype(x.dtype)
    else:
        out = jnp.where(keep, x, 0.0).astype(x.dtype)
    return {"Out": [out], "Mask": [mask]}


@register("dropout_grad", no_vjp_grad=True)
def dropout_grad(ctx, ins, attrs):
    dout = ins["Out@GRAD"][0]
    mask = ins["Mask"][0]
    p = float(attrs.get("dropout_prob", 0.5))
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        # forward was out = x*(1-p) (downgrade) or out = x (upscale)
        dx = dout * (1.0 - p) if impl == "downgrade_in_infer" else dout
        return {"X@GRAD": [dx]}
    dx = dout * mask.astype(dout.dtype)
    if impl == "upscale_in_train":
        dx = dx / max(1.0 - p, 1e-12)
    return {"X@GRAD": [dx]}


def _dropout_grad_maker(op, out_grads, block):
    og = out_grads.get("Out")
    if og is None:
        return [], {}
    xname = op.input("X")[0]
    gname = xname + "@GRAD"
    desc = {
        "type": "dropout_grad",
        "inputs": {"Mask": [op.output("Mask")[0]], "Out@GRAD": [og[0]]},
        "outputs": {"X@GRAD": [gname]},
        "attrs": {k: v for k, v in op.attrs.items()},
    }
    return [desc], {xname: gname}


set_grad_maker("dropout", _dropout_grad_maker)


# ---------------------------------------------------------------------------
# embedding / one-hot
# ---------------------------------------------------------------------------


def _lookup(w, ids, padding_idx):
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        padmask = (ids == padding_idx)[..., None]
        out = jnp.where(padmask, 0.0, out)
    return out


@register("lookup_table")
def lookup_table(ctx, ins, attrs):
    # v1 ids carry a trailing [,1] dim (LoD heritage); result keeps it dense
    w, ids = ins["W"][0], ins["Ids"][0]
    ids2 = ids.reshape(ids.shape[:-1])
    out = _lookup(w, ids2, attrs.get("padding_idx", -1))
    return {"Out": [out]}


@register("lookup_table_v2")
def lookup_table_v2(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    return {"Out": [_lookup(w, ids, attrs.get("padding_idx", -1))]}


@register("one_hot_v2", stop_gradient=True, no_vjp_grad=True)
def one_hot_v2(ctx, ins, attrs):
    x = ins["X"][0]
    depth = attrs["depth"]
    return {"Out": [jax.nn.one_hot(x, depth, dtype=jnp.float32)]}


@register("one_hot", stop_gradient=True, no_vjp_grad=True)
def one_hot(ctx, ins, attrs):
    x = ins["X"][0]
    x = x.reshape(x.shape[:-1])  # trailing 1 dim
    depth = attrs["depth"]
    return {"Out": [jax.nn.one_hot(x, depth, dtype=jnp.float32)]}


@register("embedding_with_scaled_gradient")
def embedding_with_scaled_gradient(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    return {"Out": [_lookup(w, ids, attrs.get("padding_idx", -1))]}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


@register("softmax_with_cross_entropy")
def softmax_with_cross_entropy(ctx, ins, attrs):
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = attrs.get("axis", -1) % logits.ndim
    soft_label = attrs.get("soft_label", False)
    ignore_index = attrs.get("ignore_index", -100)
    lse = jax.scipy.special.logsumexp(logits, axis=axis, keepdims=True)
    logp = logits - lse
    softmax = jnp.exp(logp)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        # hard labels: label has shape of logits with the class axis = 1
        lbl = label
        if lbl.ndim == logits.ndim and lbl.shape[axis] == 1:
            idx = lbl.astype(jnp.int32)
        else:
            idx = jnp.expand_dims(lbl.astype(jnp.int32), axis)
        n_cls = logp.shape[axis]
        safe_idx = jnp.clip(idx, 0, n_cls - 1)
        picked = jnp.take_along_axis(logp, safe_idx, axis=axis)
        # kIgnoreIndex (-100) is itself a valid ignore value — mask always
        loss = jnp.where(idx == ignore_index, 0.0, -picked)
    return {"Softmax": [softmax], "Loss": [loss]}


@register("cross_entropy")
def cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    soft_label = attrs.get("soft_label", False)
    ignore_index = attrs.get("ignore_index", -100)
    eps = 1e-12
    if soft_label:
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == x.ndim and lbl.shape[-1] == 1:
            lbl = jnp.squeeze(lbl, axis=-1)
        p = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(jnp.maximum(p, eps))
        loss = jnp.where(lbl[..., None] == ignore_index, 0.0, loss)
    return {"Y": [loss]}


@register("cross_entropy2")
def cross_entropy2(ctx, ins, attrs):
    out = cross_entropy(ctx, ins, attrs)
    x = ins["X"][0]
    from .manipulation import _xshape

    return {
        "Y": out["Y"],
        "XShape": [_xshape(x)],
        "MatchX": [jnp.exp(-out["Y"][0])],
    }


@register("sigmoid_cross_entropy_with_logits")
def sigmoid_cross_entropy_with_logits(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    ignore_index = attrs.get("ignore_index", -100)
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    mask = label != ignore_index
    loss = jnp.where(mask, loss, 0.0)
    if attrs.get("normalize", False):
        loss = loss / jnp.maximum(jnp.sum(mask.astype(loss.dtype)), 1.0)
    return {"Out": [loss]}


@register("bce_loss")
def bce_loss(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    eps = 1e-12
    loss = -(label * jnp.log(jnp.maximum(x, eps)) + (1 - label) * jnp.log(jnp.maximum(1 - x, eps)))
    return {"Out": [loss]}


@register("square_error_cost")
def square_error_cost(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.square(x - y)]}


@register("smooth_l1_loss")
def smooth_l1_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    if ins.get("InsideWeight"):
        diff = diff * ins["InsideWeight"][0]
    ad = jnp.abs(diff)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    if ins.get("OutsideWeight"):
        loss = loss * ins["OutsideWeight"][0]
    out = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [out], "Diff": [diff]}


@register("huber_loss")
def huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@register("log_loss")
def log_loss(ctx, ins, attrs):
    p, label = ins["Predicted"][0], ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    return {"Loss": [loss]}


@register("kldiv_loss")
def kldiv_loss(ctx, ins, attrs):
    x, tgt = ins["X"][0], ins["Target"][0]
    red = attrs.get("reduction", "mean")
    loss = jnp.where(tgt > 0, tgt * (jnp.log(tgt) - x), 0.0)
    if red == "mean":
        loss = jnp.mean(loss).reshape((1,))
    elif red == "sum":
        loss = jnp.sum(loss).reshape((1,))
    elif red == "batchmean":
        loss = (jnp.sum(loss) / x.shape[0]).reshape((1,))
    return {"Loss": [loss]}


@register("label_smooth")
def label_smooth(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.0)
    if ins.get("PriorDist"):
        prior = ins["PriorDist"][0]
        out = (1 - eps) * x + eps * prior
    else:
        out = (1 - eps) * x + eps / x.shape[-1]
    return {"Out": [out]}


@register("mse_loss")
def mse_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.mean(jnp.square(x - y)).reshape((1,))]}


@register("margin_rank_loss")
def margin_rank_loss(ctx, ins, attrs):
    x1, x2, label = ins["X1"][0], ins["X2"][0], ins["Label"][0]
    margin = attrs.get("margin", 0.0)
    act = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [act], "Activated": [(act > 0).astype(x1.dtype)]}


# ---------------------------------------------------------------------------
# metrics (reference operators/metrics/)
# ---------------------------------------------------------------------------


@register("accuracy", stop_gradient=True, no_vjp_grad=True)
def accuracy(ctx, ins, attrs):
    idx = ins["Indices"][0]
    label = ins["Label"][0]
    correct = jnp.any(idx == label.reshape(-1, 1), axis=1)
    total = jnp.asarray(idx.shape[0], jnp.int32)
    num_correct = jnp.sum(correct).astype(jnp.int32)
    acc = num_correct.astype(jnp.float32) / jnp.maximum(total, 1)
    return {
        "Accuracy": [acc.reshape((1,))],
        "Correct": [num_correct.reshape((1,))],
        "Total": [total.reshape((1,))],
    }


@register("auc", stop_gradient=True, no_vjp_grad=True)
def auc(ctx, ins, attrs):
    """Streaming ROC-AUC (reference operators/metrics/auc_op.cc): bucket
    positive-class scores into num_thresholds bins, accumulate pos/neg
    counts into the stat buffers, integrate by trapezoid."""
    pred = ins["Predict"][0]
    label = ins["Label"][0].reshape(-1)
    stat_pos = ins["StatPos"][0].reshape(-1)
    stat_neg = ins["StatNeg"][0].reshape(-1)
    num_t = int(attrs.get("num_thresholds", 4095))
    score = pred[:, -1] if pred.ndim == 2 else pred.reshape(-1)
    idx = jnp.clip((score * num_t).astype(jnp.int32), 0, num_t)
    is_pos = (label > 0).astype(stat_pos.dtype)
    stat_pos = stat_pos.at[idx].add(is_pos)
    stat_neg = stat_neg.at[idx].add(1 - is_pos)
    # integrate high->low threshold
    pos_rev = jnp.cumsum(stat_pos[::-1])
    neg_rev = jnp.cumsum(stat_neg[::-1])
    tot_pos = pos_rev[-1]
    tot_neg = neg_rev[-1]
    if str(attrs.get("curve", "ROC")) == "PR":
        # precision/recall points from the same buckets: TP = cum pos
        # from the high-threshold end, FP = cum neg; start at the
        # conventional (recall 0, precision 1) anchor
        tp = pos_rev.astype(jnp.float32)
        fp = neg_rev.astype(jnp.float32)
        # vacuous precision (no predictions above threshold) counts as 1
        prec = jnp.where(tp + fp > 0, tp / jnp.maximum(tp + fp, 1.0), 1.0)
        rec = tp / jnp.maximum(tot_pos.astype(jnp.float32), 1.0)
        p_pts = jnp.concatenate([jnp.ones(1, jnp.float32), prec])
        r_pts = jnp.concatenate([jnp.zeros(1, jnp.float32), rec])
        area = jnp.sum(
            (r_pts[1:] - r_pts[:-1]) * (p_pts[1:] + p_pts[:-1]) / 2.0
        )
        out = jnp.where(tot_pos > 0, area, 0.0)
        return {
            "AUC": [out.reshape(1)],
            "StatPosOut": [stat_pos.reshape(ins["StatPos"][0].shape)],
            "StatNegOut": [stat_neg.reshape(ins["StatNeg"][0].shape)],
        }
    x = jnp.concatenate([jnp.zeros(1, neg_rev.dtype), neg_rev])
    y = jnp.concatenate([jnp.zeros(1, pos_rev.dtype), pos_rev])
    area = jnp.sum(
        (x[1:] - x[:-1]).astype(jnp.float32) * (y[1:] + y[:-1]).astype(jnp.float32)
    ) / 2.0
    denom = jnp.maximum(tot_pos * tot_neg, 1).astype(jnp.float32)
    out = jnp.where(tot_pos * tot_neg > 0, area / denom, 0.0)
    return {
        "AUC": [out.reshape(1)],
        "StatPosOut": [stat_pos.reshape(ins["StatPos"][0].shape)],
        "StatNegOut": [stat_neg.reshape(ins["StatNeg"][0].shape)],
    }
