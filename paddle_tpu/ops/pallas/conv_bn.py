"""Fused NHWC conv -> batch_norm -> (optional) ReLU Pallas TPU kernels.

The ResNet-50 ceiling analysis (bench_artifacts/resnet50_ceiling.md) pins
the conv path at 0.30-0.31 MFU: every conv is separated by a batch-norm
whose statistics force a full HBM read-modify-write of the activation, so
~100 conv+BN tuple fusions each run within ~2x of their bandwidth bound.
XLA declines the producer-consumer fusion across the reduction boundary
(the arXiv:2301.13062 fusion gap); these kernels take it by hand:

  forward  pass 1: conv output tiles computed on the MXU with per-channel
           (sum, sum-of-squares) accumulated across the grid in the SAME
           kernel — the separate stats pass over the activation is gone.
  forward  pass 2: normalize + scale + shift (+ relu) in one elementwise
           sweep (the stats finalize [C]-sized math sits between the two
           pallas calls and is noise).
  backward pass 1: relu-mask + dgamma/dbeta partials in one read of
           (conv_out, grad) — the relu mask is recomputed from saved
           per-channel stats, no mask tensor is ever materialized.
  backward pass 2: the BN input cotangent dz in one elementwise sweep.
  backward conv:   dX / dW stay on XLA's native conv schedules — the
           round-5 experiments (FLAGS_conv_dw_im2col) measured them as
           the best available; only the normalization chain around them
           is replaced.

Coverage (conv_bn_shapes_ok): NHWC, groups=1, dilation=1; kh=kw=1 with
any stride (the 1x1 conv is lowered to one row-blocked matmul, strided
cases pre-subsample x — exact for 1x1), or any kernel size with stride 1
(per-image grid, halo rows come in with the padded block). Everything
else falls back to `conv_bn_reference` — the jnp composition with
IDENTICAL math (one-pass f32 moments, the batch_norm emitter convention),
so the fused_conv_bn op is always semantically one op regardless of
which engine runs it.

Stats outputs (batch mean/var) are state, not data: their cotangents are
structurally zero in real programs (MeanOut/VarianceOut feed non-trainable
moving-average params, SavedMean/SavedVariance are stop_gradient — the
same contract as the unfused batch_norm op), and the custom VJP ignores
them. Do not differentiate through the returned batch stats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from . import feasible as _feas

# per-grid-step VMEM budget: in/out blocks double-buffered + the f32
# accumulator; leaves headroom of the ~16MB/core for Mosaic's own use.
_CONV_BN_VMEM_BUDGET = _feas.CONV_BN_VMEM_BUDGET
_ROW_UNIT = _feas.CONV_BN_ROW_UNIT

_ROW_CANDIDATES = (2048, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


def default_conv_bn_rows(r, width, bytes_per_row_unit):
    """THE row-block chooser of the row-blocked passes (the 1x1 matmul,
    the normalize and backward sweeps): largest row block dividing r
    whose working set fits the budget. None when nothing tiles."""
    for cand in _ROW_CANDIDATES:
        if _feas.conv_bn_rows_ok(r, width, cand, bytes_per_row_unit)[0]:
            return cand
    return None


def _resolve_pads(pad, h, w, kh, kw, strides):
    """Normalize a lax-style padding spec to explicit ((lo,hi),(lo,hi))."""
    if pad == "VALID":
        return ((0, 0), (0, 0))
    if pad == "SAME":
        out = []
        for size, k, s in ((h, kh, strides[0]), (w, kw, strides[1])):
            total = max((-(-size // s) - 1) * s + k - size, 0)
            out.append((total // 2, total - total // 2))
        return tuple(out)
    return tuple((int(lo), int(hi)) for lo, hi in pad)


def conv_bn_shapes_ok(x_shape, w_shape, strides, pads, dilations=(1, 1),
                      groups=1) -> bool:
    """Structural + VMEM gate for the Pallas path (pads already explicit)."""
    n, h, w, c = x_shape
    o, cg, kh, kw = w_shape
    if groups != 1 or tuple(dilations) != (1, 1) or cg != c:
        return False
    if (kh, kw) == (1, 1):
        if any(p != (0, 0) for p in pads):
            return False
        ho = -(-h // strides[0])
        wo = -(-w // strides[1])
        r = n * ho * wo
        return default_conv_bn_rows(r, c + o, _ROW_UNIT["mm"]) is not None
    if tuple(strides) != (1, 1):
        return False
    hp = h + pads[0][0] + pads[0][1]
    wp = w + pads[1][0] + pads[1][1]
    ho, wo = hp - kh + 1, wp - kw + 1
    if ho <= 0 or wo <= 0:
        return False
    per_img = (
        2 * 2 * hp * wp * c          # x block, double-buffered, <=2B elts
        + 2 * 2 * ho * wo * o        # y block
        + 4 * ho * wo * o            # f32 accumulator
        + 2 * kh * kw * c * o        # weights (resident)
    )
    return per_img <= _CONV_BN_VMEM_BUDGET


def conv_bn_dispatch_ok(x_shape, w_shape, strides, pads, dilations=(1, 1),
                        groups=1) -> bool:
    """Backend + shape gate for dispatch sites (mirrors
    fused_ln_dispatch_ok): CPU/interpret runs take the jnp reference path
    unless `backend.FORCE_PALLAS` pins the kernel (tests)."""
    return (conv_bn_shapes_ok(x_shape, w_shape, strides, pads, dilations,
                              groups) and backend.on_kernels())


# ---------------------------------------------------------------------------
# reference composition (fallback path + test oracle) — the exact math of
# the unfused conv2d + batch_norm(+relu) emitters (ops/nn_ops.py)
# ---------------------------------------------------------------------------


def conv_bn_reference(x, w, scale, bias, *, strides, pads, eps=1e-5,
                      with_relu=False):
    """Returns (y, batch_mean, batch_var); f32 one-pass moments."""
    z = jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(strides), padding=tuple(pads),
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
    )
    zf = z.astype(jnp.float32)
    m = jnp.mean(zf, axis=(0, 1, 2))
    v = jnp.maximum(jnp.mean(zf * zf, axis=(0, 1, 2)) - m * m, 0.0)
    inv = jax.lax.rsqrt(v + eps)
    y = (zf - m) * inv * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    if with_relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype), m, v


# ---------------------------------------------------------------------------
# forward kernels
# ---------------------------------------------------------------------------


def _accumulate_stats(y, s_ref, ss_ref):
    """Per-channel (sum, sumsq) accumulated across the sequential grid.
    Stats are taken on the STORED (dtype-rounded) conv output so the fused
    moments match what the unfused batch_norm computes from the conv op's
    written activation."""
    yf = y.astype(jnp.float32)
    ps = jnp.sum(yf, axis=0, keepdims=True)
    pss = jnp.sum(yf * yf, axis=0, keepdims=True)
    first = pl.program_id(0) == 0

    @pl.when(first)
    def _():
        s_ref[...] = ps
        ss_ref[...] = pss

    @pl.when(jnp.logical_not(first))
    def _():
        s_ref[...] += ps
        ss_ref[...] += pss


def _mm_stats_kernel(x_ref, w_ref, y_ref, s_ref, ss_ref):
    """1x1 conv as matmul + fused stats: x [br, C] @ w [C, O]."""
    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = acc.astype(y_ref.dtype)
    y_ref[...] = y
    _accumulate_stats(y, s_ref, ss_ref)


def _conv_stats_kernel(x_ref, w_ref, y_ref, s_ref, ss_ref, *, kh, kw, ho, wo):
    """kxk stride-1 conv per image as kh*kw shifted matmuls + fused stats.

    x_ref [1, Hp, Wp, C] carries the halo (input pre-padded); w_ref is
    [kh*kw*C, O] with rows ordered (ki, kj, c)."""
    x = x_ref[0]
    c = x.shape[-1]
    o = w_ref.shape[-1]
    acc = jnp.zeros((ho * wo, o), jnp.float32)
    for ki in range(kh):
        for kj in range(kw):
            xs = x[ki:ki + ho, kj:kj + wo, :].reshape(ho * wo, c)
            wk = w_ref[(ki * kw + kj) * c:(ki * kw + kj + 1) * c, :]
            acc = acc + jax.lax.dot_general(
                xs, wk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    y = acc.astype(y_ref.dtype)
    y_ref[0] = y.reshape(ho, wo, o)
    _accumulate_stats(y, s_ref, ss_ref)


def _apply_kernel(y_ref, stat_ref, out_ref, *, with_relu):
    """normalize+affine(+relu): stat rows = (mean, rstd, scale, shift)."""
    y = y_ref[...].astype(jnp.float32)
    out = (y - stat_ref[0:1, :]) * stat_ref[1:2, :] * stat_ref[2:3, :] \
        + stat_ref[3:4, :]
    if with_relu:
        out = jnp.maximum(out, 0.0)
    out_ref[...] = out.astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# backward kernels (relu-mask + BN chain; conv grads stay on XLA)
# ---------------------------------------------------------------------------


def _masked_grad(y, g, stat_ref, with_relu):
    xhat = (y - stat_ref[0:1, :]) * stat_ref[1:2, :]
    if with_relu:
        keep = xhat * stat_ref[2:3, :] + stat_ref[3:4, :] > 0.0
        g = jnp.where(keep, g, 0.0)
    return xhat, g


def _bwd_reduce_kernel(y_ref, g_ref, stat_ref, dg_ref, db_ref, *, with_relu):
    """Per-block (dgamma, dbeta) partials in [NB, 1, O] (summed by the
    caller — the add_ln partials convention)."""
    y = y_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    xhat, g = _masked_grad(y, g, stat_ref, with_relu)
    dg_ref[...] = jnp.sum(g * xhat, axis=0, keepdims=True)[None]
    db_ref[...] = jnp.sum(g, axis=0, keepdims=True)[None]


def _bwd_dz_kernel(y_ref, g_ref, stat_ref, tot_ref, dz_ref, *, with_relu,
                   rcount):
    """BN input cotangent: dz = gamma*rstd*(g - dbeta/R - xhat*dgamma/R).
    tot rows = (dgamma_total, dbeta_total)."""
    y = y_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    xhat, g = _masked_grad(y, g, stat_ref, with_relu)
    dz = stat_ref[1:2, :] * stat_ref[2:3, :] * (
        g - tot_ref[1:2, :] * rcount - xhat * tot_ref[0:1, :] * rcount
    )
    dz_ref[...] = dz.astype(dz_ref.dtype)


# ---------------------------------------------------------------------------
# host-side orchestration
# ---------------------------------------------------------------------------


def _row_specs(br, width):
    return pl.BlockSpec((br, width), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _const_spec(rows, width):
    return pl.BlockSpec((rows, width), lambda i: (0, 0),
                        memory_space=pltpu.VMEM)


def _conv_fwd(x, w2d, out_dtype, kh, kw, pads):
    """k>1 stride-1 path: per-image grid, padded input carries the halo."""
    n, h, w_sp, c = x.shape
    o = w2d.shape[-1]
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]
    ho, wo = hp - kh + 1, wp - kw + 1
    y, s, ss = pl.pallas_call(
        functools.partial(_conv_stats_kernel, kh=kh, kw=kw, ho=ho, wo=wo),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            _const_spec(kh * kw * c, o),
        ],
        out_specs=[
            pl.BlockSpec((1, ho, wo, o), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            _const_spec(1, o),
            _const_spec(1, o),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ho, wo, o), out_dtype),
            jax.ShapeDtypeStruct((1, o), jnp.float32),
            jax.ShapeDtypeStruct((1, o), jnp.float32),
        ],
        name="conv_bn_conv_stats",
        interpret=backend.interpret(),
    )(xp, w2d)
    return y.reshape(n * ho * wo, o), (n, ho, wo, o), s, ss


def _mm_fwd(x, w2d, out_dtype, strides):
    """1x1 path: (strided-subsampled) x flattened to rows x one matmul."""
    if strides != (1, 1):
        x = jax.lax.slice(x, (0, 0, 0, 0), x.shape,
                          (1, strides[0], strides[1], 1))
    n, ho, wo, c = x.shape
    o = w2d.shape[-1]
    r = n * ho * wo
    br = default_conv_bn_rows(r, c + o, _ROW_UNIT["mm"])
    y, s, ss = pl.pallas_call(
        _mm_stats_kernel,
        grid=(r // br,),
        in_specs=[_row_specs(br, c), _const_spec(c, o)],
        out_specs=[_row_specs(br, o), _const_spec(1, o), _const_spec(1, o)],
        out_shape=[
            jax.ShapeDtypeStruct((r, o), out_dtype),
            jax.ShapeDtypeStruct((1, o), jnp.float32),
            jax.ShapeDtypeStruct((1, o), jnp.float32),
        ],
        name="conv_bn_mm_stats",
        interpret=backend.interpret(),
    )(x.reshape(r, c), w2d)
    return y, (n, ho, wo, o), s, ss


def _elementwise_rows(r, o):
    br = default_conv_bn_rows(r, o, _ROW_UNIT["apply"])
    if br is None:
        raise _feas.NoFeasibleConfig(
            "conv_bn", {"kind": "apply", "r": r, "w": o},
            [({"block_rows": c},
              _feas.conv_bn_rows_ok(r, o, c, _ROW_UNIT["apply"])[1])
             for c in _ROW_CANDIDATES])
    return br


def _pallas_fwd(x, w, scale, bias, *, strides, pads, eps, with_relu):
    o, c, kh, kw = w.shape
    w2d = jnp.transpose(w, (2, 3, 1, 0)).reshape(kh * kw * c, o)
    if (kh, kw) == (1, 1):
        z2d, oshape, s, ss = _mm_fwd(x, w2d, x.dtype, strides)
    else:
        z2d, oshape, s, ss = _conv_fwd(x, w2d, x.dtype, kh, kw, pads)
    r = z2d.shape[0]
    m = s[0] / r
    v = jnp.maximum(ss[0] / r - m * m, 0.0)
    inv = jax.lax.rsqrt(v + eps)
    stat = jnp.stack(
        [m, inv, scale.astype(jnp.float32), bias.astype(jnp.float32)]
    )
    br = _elementwise_rows(r, o)
    y2d = pl.pallas_call(
        functools.partial(_apply_kernel, with_relu=with_relu),
        grid=(r // br,),
        in_specs=[_row_specs(br, o), _const_spec(4, o)],
        out_specs=_row_specs(br, o),
        out_shape=jax.ShapeDtypeStruct((r, o), x.dtype),
        name="conv_bn_apply",
        interpret=backend.interpret(),
    )(z2d, stat)
    return y2d.reshape(oshape), z2d, stat, m, v


def _pallas_bwd(x, w, z2d, stat, g, *, strides, pads, with_relu):
    r, o = z2d.shape
    br = _elementwise_rows(r, o)
    nb = r // br
    g2d = g.reshape(r, o)
    part_spec = pl.BlockSpec((1, 1, o), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    dg, db = pl.pallas_call(
        functools.partial(_bwd_reduce_kernel, with_relu=with_relu),
        grid=(nb,),
        in_specs=[_row_specs(br, o), _row_specs(br, o), _const_spec(4, o)],
        out_specs=[part_spec, part_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, o), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, o), jnp.float32),
        ],
        name="conv_bn_bwd_reduce",
        interpret=backend.interpret(),
    )(z2d, g2d, stat)
    dgamma = dg.sum(axis=(0, 1))
    dbeta = db.sum(axis=(0, 1))
    tot = jnp.stack([dgamma, dbeta])
    dz2d = pl.pallas_call(
        functools.partial(_bwd_dz_kernel, with_relu=with_relu,
                          rcount=1.0 / r),
        grid=(nb,),
        in_specs=[_row_specs(br, o), _row_specs(br, o), _const_spec(4, o),
                  _const_spec(2, o)],
        out_specs=_row_specs(br, o),
        out_shape=jax.ShapeDtypeStruct((r, o), x.dtype),
        name="conv_bn_bwd_dz",
        interpret=backend.interpret(),
    )(z2d, g2d, stat, tot)
    # dX / dW on XLA's native conv schedules (the measured best — see the
    # round-5 im2col experiment); the primal conv is dead code under jit
    n, h, w_sp, c = x.shape
    kh, kw = w.shape[2], w.shape[3]
    ho = (h + pads[0][0] + pads[0][1] - kh) // strides[0] + 1
    wo = (w_sp + pads[1][0] + pads[1][1] - kw) // strides[1] + 1
    _, vjp_fn = jax.vjp(
        lambda x_, w_: jax.lax.conv_general_dilated(
            x_, w_, window_strides=tuple(strides), padding=tuple(pads),
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
        ),
        x, w,
    )
    dx, dw = vjp_fn(dz2d.reshape(n, ho, wo, -1))
    return dx, dw, dgamma, dbeta


@functools.lru_cache(maxsize=64)
def _make_core(kh, kw, strides, pads, eps, with_relu):
    @jax.custom_vjp
    def core(x, w, scale, bias):
        y, _, _, m, v = _pallas_fwd(
            x, w, scale, bias, strides=strides, pads=pads, eps=eps,
            with_relu=with_relu,
        )
        return y, m, v

    def core_fwd(x, w, scale, bias):
        y, z2d, stat, m, v = _pallas_fwd(
            x, w, scale, bias, strides=strides, pads=pads, eps=eps,
            with_relu=with_relu,
        )
        return (y, m, v), (x, w, scale, z2d, stat)

    def core_bwd(res, cots):
        x, w, scale, z2d, stat = res
        g, _dm, _dv = cots  # batch-stat cotangents are state: zero by contract
        dx, dw, dgamma, dbeta = _pallas_bwd(
            x, w, z2d, stat, g, strides=strides, pads=pads,
            with_relu=with_relu,
        )
        return dx, dw, dgamma.astype(scale.dtype), dbeta.astype(scale.dtype)

    core.defvjp(core_fwd, core_bwd)
    return core


def fused_conv_bn(x, w, scale, bias, *, strides=(1, 1), pads="SAME",
                  eps=1e-5, with_relu=False):
    """Fused training-mode conv+BN(+ReLU) over NHWC x / OIHW w.

    Returns (y, batch_mean, batch_var) — batch moments in f32 for the
    caller's running-average update. Dispatches to the Pallas kernels
    when `conv_bn_dispatch_ok` passes, else to the jnp reference
    composition (identical math)."""
    strides = tuple(int(s) for s in strides)
    kh, kw = int(w.shape[2]), int(w.shape[3])
    pads = _resolve_pads(pads, x.shape[1], x.shape[2], kh, kw, strides)
    if conv_bn_dispatch_ok(x.shape, w.shape, strides, pads):
        core = _make_core(kh, kw, strides, pads, float(eps), bool(with_relu))
        return core(x, w, scale, bias)
    return conv_bn_reference(
        x, w, scale, bias, strides=strides, pads=pads, eps=eps,
        with_relu=with_relu,
    )
