"""State-space mixers: `mamba2`, the Mamba-2 layer of "Transformers are
SSMs" (Dao and Gu, arXiv:2405.21060), and the scan inside it, `ssd_scan`.

The recurrence, for head h with its group g = h // (H / G), a state S in
R^{P x N} that is zero in front of position 0, x_t in R^P, B_t and C_t in
R^N (one pair a group), dt_t > 0 and A_h < 0:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

`ssd_scan` computes it in chunks of Q positions, the paper's state-space
dual form, as matrix products and no loop over positions:

- inside a chunk, y_i = sum_{j <= i} exp(cum_i - cum_j) dt_j (C_i . B_j)
  x_j with cum the running sum of dt A over the chunk: one masked [Q, Q]
  matrix a head and chunk, times the chunk's x;
- a chunk leaves the state sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T behind,
  [P, N] a head;
- the state that enters chunk z is the chunks' states in front of it, each
  decayed by what lies between: a [S/Q, S/Q] lower-triangular product over
  the chunks, float32 at `highest` precision (it carries the whole past);
- it adds exp(cum_i) C_i . S_entering to position i.

Decays, running sums and dt stay float32 whatever x's dtype; the products
take x's dtype in and accumulate in float32. The scan keeps only its
inputs for the backward pass (`jax.checkpoint`): the chunk states and the
[Q, Q] matrices are computed again there, and nothing quadratic in S
exists in either pass. Scopes: `mamba2`, and inside it `ssd_scan`.

On the TPU, where `ops/pallas/ssd_scan.py`'s gate admits the shapes, the
same mathematics runs as its two kernels (`ssd_scan_fwd`, `ssd_scan_bwd`),
with the [Q, Q] matrices and the states in VMEM; this composition is the
path everywhere else and the kernels' reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .decoder_ops import causal_depthwise_conv
from .pallas import ssd_scan as ssd_pallas
from .registry import register

_F32 = jnp.float32


def _masked_exp(diff, keep):
    """exp(diff) where `keep`, else 0. The mask goes in front of exp: a
    masked difference is a positive sum of decays' logarithms and would
    overflow, and inf * 0 in the backward pass is NaN."""
    return jnp.exp(jnp.where(keep, diff, -jnp.inf))


def _running_sum(t, axis):
    """Inclusive running sum of float32 `t` along `axis` as a product with
    a lower-triangular matrix of ones at `highest` precision: on the TPU
    `jnp.cumsum` lowers to a reduce-window that took 1.9 ms for the 0.5 M
    decays of a layer (PERF.md, PR 34), forward and again transposed."""
    n = t.shape[axis]
    ones = jnp.tril(jnp.ones((n, n), _F32))
    summed = jnp.tensordot(ones, t, axes=((1,), (axis,)),
                           precision=jax.lax.Precision.HIGHEST)
    return jnp.moveaxis(summed, 0, axis)


def _ssd_chunked(x, dt, a, b, c, d, *, chunk):
    """x [B, S, H, P], dt [B, S, H], a [H], b / c [B, S, G, N], d [H] ->
    y [B, S, H, P]; S a multiple of `chunk`. Axis letters below: z chunk,
    i / j position in a chunk, g group, r head in its group."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r, nc = h // g, s // chunk
    xg = x.reshape(bsz, nc, chunk, g, r, p)
    bg = b.reshape(bsz, nc, chunk, g, n)
    cg = c.reshape(bsz, nc, chunk, g, n)
    dtg = dt.astype(_F32).reshape(bsz, nc, chunk, g, r)
    cum = _running_sum(dtg * a.astype(_F32).reshape(g, r), axis=2)

    # inside a chunk
    cb = jnp.einsum("bzign,bzjgn->bzgij", cg, bg,
                    preferred_element_type=_F32)
    cum_t = jnp.moveaxis(cum, 2, -1)  # [B, nc, G, R, Q]
    pos = jnp.arange(chunk)
    decay = _masked_exp(cum_t[..., :, None] - cum_t[..., None, :],
                        pos[:, None] >= pos[None, :])
    m = (cb[:, :, :, None] * decay
         * jnp.moveaxis(dtg, 2, -1)[..., None, :]).astype(x.dtype)
    y = jnp.einsum("bzgrij,bzjgrp->bzigrp", m, xg,
                   preferred_element_type=_F32)

    # what a chunk leaves behind
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtg
    xw = (xg.astype(_F32) * to_end[..., None]).astype(x.dtype)
    left = jnp.einsum("bzjgn,bzjgrp->bzgrpn", bg, xw,
                      preferred_element_type=_F32)

    # what enters a chunk: chunk w's state decayed over chunks w+1 .. z-1
    through = _running_sum(cum[:, :, -1], axis=1)  # [B, nc, G, R]
    before = through - cum[:, :, -1]
    order = jnp.arange(nc)
    carry = _masked_exp(
        before[:, :, None] - through[:, None, :],
        (order[:, None] > order[None, :])[:, :, None, None])
    entering = jnp.einsum("bzwgr,bwgrpn->bzgrpn", carry, left,
                          precision=jax.lax.Precision.HIGHEST)
    y_in = jnp.einsum("bzign,bzgrpn->bzigrp", cg, entering.astype(x.dtype),
                      preferred_element_type=_F32)

    y = (y + y_in * jnp.exp(cum)[..., None]
         + d.astype(_F32).reshape(g, r)[:, :, None] * xg.astype(_F32))
    return y.reshape(bsz, s, h, p).astype(x.dtype)


def scan_kernels(x_shape, b_shape, chunk: int, dtype) -> bool:
    """Whether `ssd_scan` at these shapes (x [B, S, H, P] and b [B, S, G,
    N] after the row's padding) runs `ops/pallas/ssd_scan.py`'s kernels:
    its gate, from the shapes, the dtype and the platform alone."""
    _, s, h, p = x_shape
    g, n = b_shape[2:]
    return ssd_pallas.use_kernels(s, h, p, g, n, chunk, dtype)


def ssd_scan(x, dt, a, b, c, d, chunk: int):
    """The recurrence of the module docstring in chunks of `chunk`
    positions (the whole row where it is shorter). A row that is no
    multiple of the chunk is continued with dt = 0, which neither decays
    the state nor adds to it. Lowered under the scope `ssd_scan`, as the
    kernels where `scan_kernels` admits the shapes; counted by
    `ssd_scan_lowerings_total{impl}` (one a `mamba2` op traced), where the
    kernels split a group's heads into blocks by
    `ssd_scan_head_blocks_total{blocks}`, and, where the kernels would run
    but their gate refuses the shapes, by
    `ssd_scan_gate_refusals_total{reason}`."""
    from ..fluid.monitor import (record_ssd_scan_gate_refusal,
                                 record_ssd_scan_head_blocks,
                                 record_ssd_scan_lowering)

    s = x.shape[1]
    chunk = min(int(chunk), s)
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    kernels = scan_kernels(x.shape, b.shape, chunk, x.dtype)
    record_ssd_scan_lowering("pallas" if kernels else "jnp")
    _, rows, h, p = x.shape
    if kernels:
        blocks = ssd_pallas.head_blocks(rows, h, p, *b.shape[2:], chunk,
                                        x.dtype)
        if blocks > 1:
            record_ssd_scan_head_blocks(blocks)
    elif ssd_pallas.on_kernels():
        record_ssd_scan_gate_refusal(ssd_pallas.kernel_fits_reason(
            rows, h, p, *b.shape[2:], chunk, x.dtype))
    with jax.named_scope("ssd_scan"):
        if kernels:
            y = ssd_pallas.ssd_scan(x, dt, a, b, c, d, chunk)
        else:
            y = jax.checkpoint(functools.partial(_ssd_chunked, chunk=chunk))(
                x, dt, a, b, c, d)
    return y[:, :s] if pad else y


def gated_group_norm(y, z, weight, group_size: int, eps: float):
    """RMSNorm over every `group_size` consecutive columns of y * silu(z),
    times a learned weight over all columns; float32 inside."""
    gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    grouped = gated.reshape(gated.shape[:-1] + (-1, group_size))
    ms = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(ms + eps)).reshape(gated.shape)
    return (normed * weight.astype(_F32)).astype(y.dtype)


def _mamba2(x, w_in, taps, conv_bias, dt_bias, a_log, d, norm_w, w_out, *,
            heads, head_dim, groups, state, chunk, eps):
    bsz, s, _ = x.shape
    d_in = heads * head_dim
    proj = jnp.einsum("bsh,hk->bsk", x, w_in.astype(x.dtype))
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * groups * state],
                           axis=-1)
    xbc = jax.nn.silu(
        causal_depthwise_conv(xbc.astype(_F32), taps.astype(_F32))
        + conv_bias.astype(_F32)).astype(x.dtype)
    xs, b, c = jnp.split(xbc, [d_in, d_in + groups * state], axis=-1)
    dt = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    a = -jnp.exp(a_log.astype(_F32))
    # A < 0: a head's smallest decay is at its largest dt
    min_decay = jax.lax.stop_gradient(jnp.exp(jnp.max(dt, axis=(0, 1)) * a))
    y = ssd_scan(xs.reshape(bsz, s, heads, head_dim), dt, a,
                 b.reshape(bsz, s, groups, state),
                 c.reshape(bsz, s, groups, state), d, chunk)
    y = gated_group_norm(y.reshape(bsz, s, d_in), z, norm_w, d_in // groups,
                         eps)
    return jnp.einsum("bsk,kh->bsh", y, w_out.astype(x.dtype)), min_decay


@register("mamba2")
def mamba2(ctx, ins, attrs):
    """The Mamba-2 mixer, x [B, S, C] -> Out [B, S, C] and MinDecay [H]:

        [z, xBC, dt] = x InW          d_in, d_in + 2 G N, H columns
        xBC = silu(conv(xBC) + ConvB) causal, depthwise, ConvW [taps, .]
        [x_h, B_g, C_g] = xBC         H heads of P; G groups of N
        dt = softplus(dt + DtBias),  A = -exp(ALog)        float32
        y = ssd_scan(x, dt, A, B, C, D)
        Out = RMSNorm_{d_in / G}(y * silu(z); NormW) OutW

    MinDecay is each head's smallest exp(dt A) over the batch: a head
    near 0 forgets its state inside a step, a head near 1 never does."""
    names = ("X", "InW", "ConvW", "ConvB", "DtBias", "ALog", "D", "NormW",
             "OutW")
    fn = functools.partial(
        _mamba2, heads=int(attrs["num_heads"]), head_dim=int(attrs["head_dim"]),
        groups=int(attrs["n_groups"]), state=int(attrs["state_size"]),
        chunk=int(attrs.get("chunk_size", 128)),
        eps=float(attrs.get("epsilon", 1e-5)))
    with jax.named_scope("mamba2"):
        out, min_decay = fn(*(ins[n][0] for n in names))
    return {"Out": [out], "MinDecay": [min_decay]}
