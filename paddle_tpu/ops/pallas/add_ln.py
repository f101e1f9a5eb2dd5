"""Fused residual-add + LayerNorm Pallas TPU kernel with custom VJP.

TPU-native counterpart of the reference's fused residual/LayerNorm ops
(/root/reference/paddle/fluid/operators/fused/fused_layernorm_residual_dropout_bias.h
and layer_norm_op.cu — one CUDA kernel per row with welford stats).
Motivation measured on v5e (round-4 profile, BERT-base s512/b48): XLA's
convert+reduce LayerNorm fusions cost ~28 ms/step inside the encoder
scans — ~30x the bandwidth roofline for 4 row-stat passes over
[B,S,768] bf16 — while every matmul around them runs near peak. One
pass per row block with f32 stats in VMEM removes almost all of it.

Semantics (matching ops/encoder_stack._ln_f32 exactly):
    out = ((x + y) - mean) * rsqrt(var + eps) * scale + shift
computed in f32 regardless of input dtype, cast back to the input dtype.
y is the residual branch; pass y=None for plain LayerNorm. The backward
saves only the per-row (mean, rstd) f32 stats — x and y are values the
surrounding program already holds (or recomputes under remat policies),
and dx == dy (the add distributes the cotangent), so the bwd kernel
writes one tensor read twice by the caller.

Stats ride as [1, R] lane-major rows written through the same MXU
identity-transpose trick as the flash kernel's lse (a (R, 1)
sublane-major store costs a vreg-walking relayout). Their (1, br)
blocks are only legal on the TPU when br is a multiple of 128, so row
blocks come in multiples of 128 and a row count that is not one (the
MLM head's batch x 76 masked positions) is zero-padded up to the next.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from . import feasible as _feas

_ROW_ALIGN = _feas.LN_ROW_ALIGN
_ROW_CANDIDATES = (1024, 512, 256, 128)


def _padded_rows(r):
    return -(-r // _ROW_ALIGN) * _ROW_ALIGN


def default_ln_rows(r, h):
    """THE row-block chooser, for the forward and the backward alike
    (the saved [1, R] stats re-block as they were written): largest row
    block that tiles r (a multiple of 128 — see _padded_rows) under the
    VMEM budget (x, y, out blocks double-buffered bf16 + ~4 f32
    temporaries per row block). None when nothing tiles."""
    for cand in _ROW_CANDIDATES:
        if _feas.ln_rows_ok(r, h, cand)[0]:
            return cand
    return None


def ln_shapes_ok(r, h) -> bool:
    return h % 128 == 0 and default_ln_rows(_padded_rows(r), h) is not None


def _fwd_kernel(*refs, eps, has_y, br):
    it = iter(refs)
    x_ref = next(it)
    y_ref = next(it) if has_y else None
    scale_ref = next(it)
    shift_ref = next(it)
    out_ref = next(it)
    mean_ref = next(it)
    rstd_ref = next(it)
    s = x_ref[...].astype(jnp.float32)
    if has_y:
        s = s + y_ref[...].astype(jnp.float32)
    mu = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (s - mu) * rstd
    out_ref[...] = (
        xhat * scale_ref[...].astype(jnp.float32)
        + shift_ref[...].astype(jnp.float32)
    ).astype(out_ref.dtype)
    ident = backend.identity(br)
    mean_ref[...] = backend.to_lanes(mu, ident)
    rstd_ref[...] = backend.to_lanes(rstd, ident)


def _bwd_kernel(*refs, has_y, br):
    it = iter(refs)
    x_ref = next(it)
    y_ref = next(it) if has_y else None
    scale_ref = next(it)
    mean_ref = next(it)
    rstd_ref = next(it)
    g_ref = next(it)
    dx_ref = next(it)
    dsc_ref = next(it)
    dsh_ref = next(it)
    ident = backend.identity(br)
    s = x_ref[...].astype(jnp.float32)
    if has_y:
        s = s + y_ref[...].astype(jnp.float32)
    mu = backend.to_sublanes(mean_ref[...], ident)
    rstd = backend.to_sublanes(rstd_ref[...], ident)
    xhat = (s - mu) * rstd
    g = g_ref[...].astype(jnp.float32)
    # per-block partials land in [NB, 1, H] (the 3-D shape keeps the
    # trailing block dims (1, H) legal for any NB); summed by the caller
    dsc_ref[...] = jnp.sum(g * xhat, axis=0, keepdims=True)[None]
    dsh_ref[...] = jnp.sum(g, axis=0, keepdims=True)[None]
    gs = g * scale_ref[...].astype(jnp.float32)
    m1 = jnp.mean(gs, axis=-1, keepdims=True)
    m2 = jnp.mean(gs * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (gs - m1 - xhat * m2)).astype(dx_ref.dtype)


def _ln_fwd(x, y, scale, shift, *, eps):
    r, h = x.shape
    br = default_ln_rows(r, h)
    has_y = y is not None
    row_spec = pl.BlockSpec((br, h), lambda i: (i, 0), memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((1, br), lambda i: (0, i), memory_space=pltpu.VMEM)
    args = [x] + ([y] if has_y else []) + [scale.reshape(1, h), shift.reshape(1, h)]
    in_specs = [row_spec] * (2 if has_y else 1) + [vec_spec, vec_spec]
    out, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, has_y=has_y, br=br),
        grid=(r // br,),
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((r, h), x.dtype),
            jax.ShapeDtypeStruct((1, r), jnp.float32),
            jax.ShapeDtypeStruct((1, r), jnp.float32),
        ],
        name="add_ln_fwd",
        interpret=backend.interpret(),
    )(*args)
    return out, mean, rstd


def _ln_bwd(x, y, scale, mean, rstd, g, *, eps):
    r, h = x.shape
    br = default_ln_rows(r, h)
    has_y = y is not None
    row_spec = pl.BlockSpec((br, h), lambda i: (i, 0), memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((1, br), lambda i: (0, i), memory_space=pltpu.VMEM)
    part_spec = pl.BlockSpec((1, 1, h), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    nb = r // br
    args = [x] + ([y] if has_y else []) + [scale.reshape(1, h), mean, rstd, g]
    in_specs = (
        [row_spec] * (2 if has_y else 1)
        + [vec_spec, stat_spec, stat_spec, row_spec]
    )
    dx, dsc, dsh = pl.pallas_call(
        functools.partial(_bwd_kernel, has_y=has_y, br=br),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[row_spec, part_spec, part_spec],
        out_shape=[
            jax.ShapeDtypeStruct((r, h), x.dtype),
            jax.ShapeDtypeStruct((nb, 1, h), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, h), jnp.float32),
        ],
        name="add_ln_bwd",
        interpret=backend.interpret(),
    )(*args)
    return dx, dsc.sum(axis=(0, 1)), dsh.sum(axis=(0, 1))


@functools.lru_cache(maxsize=32)
def _make_core(eps, has_y):
    @jax.custom_vjp
    def core(x, y, scale, shift):
        out, _, _ = _ln_fwd(x, y, scale, shift, eps=eps)
        return out

    def core_fwd(x, y, scale, shift):
        out, mean, rstd = _ln_fwd(x, y, scale, shift, eps=eps)
        return out, (x, y, scale, mean, rstd)

    def core_bwd(res, g):
        x, y, scale, mean, rstd = res
        dx, dsc, dsh = _ln_bwd(x, y, scale, mean, rstd, g, eps=eps)
        return (
            dx,
            dx if has_y else None,
            dsc.astype(scale.dtype),
            dsh.astype(scale.dtype),
        )

    core.defvjp(core_fwd, core_bwd)
    return core


def fused_ln_dispatch_ok(shape) -> bool:
    """Backend/flag/shape gate for every fused-LN dispatch site (mirrors
    flash_shapes_ok)."""
    from ...fluid.flags import flag

    if not flag("FLAGS_use_fused_ln"):
        return False
    h = shape[-1]
    r = 1
    for d in shape[:-1]:
        r *= d
    return ln_shapes_ok(r, h) and backend.on_kernels()


def _row_spec(mesh, shape):
    """PartitionSpec over the mesh axes that shard LN's rows: the batch
    (dim 0) on "dp" and, for [B, S, H], the sequence on "sp" — where the
    axis is populated and divides the dim; replicated otherwise."""
    from jax.sharding import PartitionSpec as P

    def axis(name, dim):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and dim % n == 0 else None

    dims = [None] * len(shape)
    dims[0] = axis("dp", shape[0])
    if len(shape) == 3:
        dims[1] = axis("sp", shape[1])
    return P(*dims)


def fused_add_ln(x, y, scale, shift, eps=1e-5, mesh=None):
    """LayerNorm(x + y) over the last axis with f32 stats; y may be None.

    x/y: [..., H]; scale/shift: [H]. Dispatch gate: `ln_shapes_ok` on the
    flattened row count and H — callers fall back to the jnp composition
    otherwise (identical math).

    mesh: the GSPMD mesh of the surrounding jit (EmitContext.mesh; None
    inside a manual region). XLA cannot partition a Mosaic call, so over
    more than one device the kernel runs per shard inside a shard_map
    over the axes that shard its rows (`_row_spec`), as
    flash_attention_bsh does.
    """
    if mesh is None or mesh.size == 1:
        return _add_ln_local(x, y, scale, shift, eps)
    from jax.sharding import PartitionSpec as P

    spec = _row_spec(mesh, x.shape)
    return jax.shard_map(
        lambda xl, yl, sc, sh: _add_ln_local(xl, yl, sc, sh, eps),
        mesh=mesh,
        in_specs=(spec, None if y is None else spec, P(), P()),
        out_specs=spec, check_vma=False,
    )(x, y, scale, shift)


def _add_ln_local(x, y, scale, shift, eps):
    shape = x.shape
    h = shape[-1]
    r = 1
    for d in shape[:-1]:
        r *= d
    rp = _padded_rows(r)
    if not ln_shapes_ok(r, h):
        raise _feas.NoFeasibleConfig(
            "add_ln", {"r": rp, "h": h},
            [({"block_rows": c}, _feas.ln_rows_ok(rp, h, c)[1])
             for c in _ROW_CANDIDATES],
            detail=("hidden dim must be a multiple of 128"
                    if h % 128 else "gate with fused_ln_dispatch_ok"))
    pad = rp - r

    def rows(a):
        a = a.reshape(r, h)
        return jnp.pad(a, ((0, pad), (0, 0))) if pad else a

    core = _make_core(float(eps), y is not None)
    out = core(
        rows(x),
        None if y is None else rows(y),
        scale.reshape(h),
        shift.reshape(h),
    )
    return (out[:r] if pad else out).reshape(shape)
