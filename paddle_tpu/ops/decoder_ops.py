"""Decoder-LM building blocks: RMSNorm, rotary position embedding, the gated
short convolution and the dense SwiGLU feed-forward.

New TPU-era capability (the 2020 reference predates all four). Each lowers
as a plain `jnp` composition that XLA fuses; each computes its statistics,
rotations and gates in float32 whatever the compute dtype, so all of them
can take bf16 in and out under AMP. Every emitter runs inside
`jax.named_scope(<op type>)`: beneath the role scope of `emit_ops`, an HLO
instruction's `op_name` then reads `.../forward/jvp(short_conv)/../dot_general`,
which is what the benchmark's per-layer readers sum device time by.

Layout is the projection layout of the attention op, [B, S, H] with heads
interleaved on the last axis (head i owns columns i*d .. (i+1)*d).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .registry import register


@register("rms_norm")
def rms_norm(ctx, ins, attrs):
    """y = x * rsqrt(mean(x^2) + eps) * scale over groups of len(scale)
    consecutive columns of the last axis: the whole axis where Scale is
    [x.shape[-1]], every head by itself where it is [head_dim]."""
    x, scale = ins["X"][0], ins["Scale"][0]
    eps = float(attrs.get("epsilon", 1e-5))
    d = scale.shape[0]
    if x.shape[-1] % d:
        raise ValueError(
            f"rms_norm: last axis {x.shape[-1]} is no multiple of Scale's {d}")
    with jax.named_scope("rms_norm"):
        xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // d, d))
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
        return {"Y": [y.reshape(x.shape).astype(x.dtype)]}


def rope_tables(seq_len: int, head_dim: int, theta: float, inv_freq=None):
    """cos and sin of position * theta^(-2i/d), [S, d/2], float32 from
    float64: the table is exact to float32 rounding at any position.
    `inv_freq`, d/2 frequencies, stands in for theta^(-2i/d) where the
    model brings its own (YaRN's blend)."""
    half = head_dim // 2
    if inv_freq is None:
        inv_freq = float(theta) ** (-np.arange(half, dtype=np.float64) * 2.0
                                    / head_dim)
    inv_freq = np.asarray(inv_freq, np.float64)
    if inv_freq.shape != (half,):
        raise ValueError(
            f"rope: {inv_freq.shape} frequencies for a head of {head_dim}")
    angle = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq
    return (np.cos(angle).astype(np.float32),
            np.sin(angle).astype(np.float32))


@register("rope")
def rope(ctx, ins, attrs):
    """Rotary embedding with the rotate-half pairing (i, i + d/2) inside
    every head of a [B, S, nh*d] tensor; position = index on axis 1. The
    attribute `inv_freq` (d/2 numbers) replaces theta^(-2i/d)."""
    x = ins["X"][0]
    d = int(attrs["head_dim"])
    theta = float(attrs.get("theta", 10000.0))
    h = x.shape[2]
    if h % d or d % 2:
        raise ValueError(f"rope: hidden {h} against an even head_dim {d}")
    cos, sin = rope_tables(x.shape[1], d, theta, attrs.get("inv_freq"))
    with jax.named_scope("rope"):
        return {"Out": [rotate_half(x, d, cos, sin)]}


def rotate_half(x, d, cos, sin):
    """x [B, S, nh*d] rotated by the [S, d/2] tables, in float32."""
    b, s, h = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, h // d, 2, d // 2)
    x1, x2 = xf[..., 0, :], xf[..., 1, :]
    c, sn = cos[None, :, None, :], sin[None, :, None, :]
    y = jnp.stack([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-2)
    return y.reshape(b, s, h).astype(x.dtype)


def causal_depthwise_conv(x, taps):
    """c[:, t] = sum_j taps[j] * x[:, t - (L-1) + j], zeros before t = 0:
    x [B, S, C], taps [L, C]; the last tap weighs the current position."""
    n_taps, s = taps.shape[0], x.shape[1]
    out = taps[n_taps - 1] * x
    for back in range(1, n_taps):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
        out = out + taps[n_taps - 1 - back] * shifted
    return out


@register("short_conv")
def short_conv(ctx, ins, attrs):
    """The gated short-convolution operator: [Bg, Cg, u] = split3(x W_in),
    c = causal depthwise conv of (Bg * u) with L taps a channel,
    out = (Cg * c) W_out. No bias, no activation. The two products run in
    the input dtype; the gates and the taps in float32."""
    x = ins["X"][0]
    w_in, taps, w_out = ins["InW"][0], ins["Filter"][0], ins["OutW"][0]
    with jax.named_scope("short_conv"):
        proj = jnp.einsum("bsh,hk->bsk", x, w_in.astype(x.dtype))
        bg, cg, u = jnp.split(proj.astype(jnp.float32), 3, axis=-1)
        c = causal_depthwise_conv(bg * u, taps.astype(jnp.float32))
        y = (cg * c).astype(x.dtype)
        return {"Out": [jnp.einsum("bsh,hk->bsk", y, w_out.astype(x.dtype))]}


def swiglu(x, w1, w3, w2):
    """W2 (silu(W1 x) * W3 x) on the last axis; products in x's dtype, the
    activation in float32."""
    a = jnp.einsum("...h,hf->...f", x, w1.astype(x.dtype))
    g = jnp.einsum("...h,hf->...f", x, w3.astype(x.dtype))
    inter = (jax.nn.silu(a.astype(jnp.float32))
             * g.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("...f,fh->...h", inter, w2.astype(x.dtype))


def relu2_ffn(x, w1, w2):
    """W2 relu(W1 x)^2 on the last axis; products in x's dtype, the
    activation in float32."""
    a = jnp.einsum("...h,hf->...f", x, w1.astype(x.dtype))
    inter = jnp.square(jax.nn.relu(a.astype(jnp.float32))).astype(x.dtype)
    return jnp.einsum("...f,fh->...h", inter, w2.astype(x.dtype))


def _swiglu_op(scope):
    """Emitter of a SwiGLU feed-forward under the part scope `scope`, or,
    built without W3, of the two-matrix squared-ReLU one. `remat` keeps
    only the input for the backward pass and computes the [.., F]
    intermediates again there (what `remat_ffn` does for the encoder
    stack)."""
    def emit(ctx, ins, attrs):
        weights = [ins[k][0] for k in ("W1", "W3", "W2") if k in ins]
        fn = swiglu if len(weights) == 3 else relu2_ffn
        if attrs.get("remat", False):
            fn = jax.checkpoint(fn)
        with jax.named_scope(scope):
            return {"Out": [fn(ins["X"][0], *weights)]}
    return emit


# the dense SwiGLU feed-forward, and the same arithmetic as the expert that
# every token passes beside the routed ones, under a part scope of its own
# so that a trace tells the shared expert from a dense layer's MLP
register("swiglu_ffn")(_swiglu_op("swiglu_ffn"))
register("shared_expert")(_swiglu_op("shared_expert"))
