"""Latent attention (MLA) and manifold-constrained hyper-connections (mHC).

`mla` is the whole attention sublayer of the DeepSeek-V2/V3 line: low-rank
query and key/value projections with an RMSNorm on each latent, a rotary
part that is 64 of a head's query / key columns (of 192 in Xing4.0, of 256
in GLM-4.7-Flash; one rotated key part serves every head), values of a
width of their own (128, 256), a softmax scale the model gives, and the
output projection. It is told how many heads it holds: its W_qb and W_kvb
have those heads' columns, its W_o their rows, and its result is those
heads' part of the sum over heads. `ops/attention.py:latent_attention` runs
the heads through the flash kernels, padded to a kernel width where they
are narrower and in groups where all of them are more than a kernel holds.

`mhc_map`, `mhc_pre` and `mhc_post` are the residual path of "mHC:
Manifold-Constrained Hyper-Connections" (arXiv:2512.24880) on n residual
streams, kept side by side on the last axis: X is [B, S, n*C], stream j
the columns j*C .. (j+1)*C, so that vec(X) is a row and no array has a
minor dimension of n. A sublayer F becomes

    H_pre, H_post, H_res = mhc_map(X)
    X' = mhc_post(X, F(norm(mhc_pre(X, H_pre))), H_res, H_post)

The mappings, Sinkhorn included, and the mixing run in float32 whatever
the streams' dtype; each op keeps only its inputs for the backward pass
(`jax.checkpoint`): a float32 copy of the streams is never a residual.
Scopes: `mla`, `mhc_map`, `mhc_mix`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import latent_attention
from .decoder_ops import rope_tables, rotate_half
from .registry import register


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _proj(x, w):
    return jnp.einsum("bsh,hk->bsk", x, w.astype(x.dtype))


@register("mla")
def mla(ctx, ins, attrs):
    """x [B, S, C] -> the held heads' part of the attention output.
    Weights: QA [C, q_lora], QANorm [q_lora], QB [q_lora, nh * (nope +
    rope)], KVA [C, kv_lora + rope], KVANorm [kv_lora], KVB [kv_lora, nh *
    (nope + v)], O [nh * v, C]; a head's columns are [nope, rope] in QB
    and [k_nope, v] in KVB. Products in x's dtype, norms and the rotation
    in float32."""
    x = ins["X"][0]
    nh = int(attrs["num_heads"])
    nope, rot, dv = (int(attrs[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    eps = float(attrs.get("epsilon", 1e-6))
    b, s, _ = x.shape
    kv_lora = ins["KVANorm"][0].shape[0]
    cos, sin = rope_tables(s, rot, float(attrs.get("theta", 10000.0)),
                           attrs.get("inv_freq"))
    with jax.named_scope("mla"):
        c_q = _rms(_proj(x, ins["QA"][0]), ins["QANorm"][0], eps)
        q = _proj(c_q, ins["QB"][0]).reshape(b, s, nh, nope + rot)
        kv_a = _proj(x, ins["KVA"][0])
        c_kv = _rms(kv_a[..., :kv_lora], ins["KVANorm"][0], eps)
        k_rope = rotate_half(kv_a[..., kv_lora:], rot, cos, sin)
        kv = _proj(c_kv, ins["KVB"][0]).reshape(b, s, nh, nope + dv)
        q_rope = rotate_half(
            q[..., nope:].reshape(b, s, nh * rot), rot, cos, sin)
        q = jnp.concatenate(
            [q[..., :nope], q_rope.reshape(b, s, nh, rot)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None, :], (b, s, nh, rot))],
            axis=-1)
        ctx_heads = latent_attention(
            q.reshape(b, s, nh * (nope + rot)),
            k.reshape(b, s, nh * (nope + rot)),
            kv[..., nope:].reshape(b, s, nh * dv), nh,
            sm_scale=float(attrs["softmax_scale"]), causal=True,
            mesh=ctx.mesh)
        return {"Out": [_proj(ctx_heads, ins["O"][0])]}


def sinkhorn(m, iters: int):
    """`iters` rounds of "divide each row by its sum, then each column by
    its sum" on positive m [n, n, T] (row i, column j, token t)."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=1, keepdims=True)
        m = m / jnp.sum(m, axis=0, keepdims=True)
    return m


def _mhc_map(x, phi, bias, alpha, *, n, eps, iters, clamp_min, clamp_max):
    b, s, nc = x.shape
    xf = x.astype(jnp.float32).reshape(b * s, nc)
    # xbar phi = (x phi) / rms(x): the normalised row is never written
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1) + eps)
    proj = jnp.einsum("km,tk->mt", phi.astype(jnp.float32), xf,
                      precision=jax.lax.Precision.HIGHEST) * inv[None, :]
    bias = bias.astype(jnp.float32)[:, None]
    alpha = alpha.astype(jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * proj[:n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[n:2 * n] + bias[n:2 * n])
    res = alpha[2] * proj[2 * n:] + bias[2 * n:]
    res = sinkhorn(jnp.exp(jnp.clip(res, clamp_min, clamp_max)
                           ).reshape(n, n, b * s), iters)
    gap = jnp.maximum(
        jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0), axis=-1),
        jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0), axis=-1))
    return (pre.T.reshape(b, s, n), post.T.reshape(b, s, n),
            res.reshape(n * n, b * s).T.reshape(b, s, n * n),
            jax.lax.stop_gradient(gap))


@register("mhc_map")
def mhc_map(ctx, ins, attrs):
    """The three mappings of one sublayer, per token, float32:
    xbar = vec(X) / sqrt(mean(vec(X)^2) + eps); with Phi [n*C, 2n + n*n],
    Bias [2n + n*n] and Alpha [3] in the order pre, post, res:
    HPre = sigmoid(a_pre xbar Phi_pre + b_pre) [B, S, n],
    HPost = 2 sigmoid(..) [B, S, n], HRes = Sinkhorn(exp(clamp(a_res
    mat(xbar Phi_res) + b_res))) [B, S, n*n] row-major, doubly stochastic.
    SinkhornGap [n]: for each i the worst |sum - 1| of row i or column i
    over the tokens, to see whether the rounds sufficed. On the TPU, at
    four streams in whole lane tiles and 128 rows that tile S, the forward
    pass is the one-pass kernel of ops/pallas/mhc.py (`mhc.map_rows` is
    the gate; the backward pass is `_mhc_map`'s, see `mhc._map_core_bwd`);
    `_mhc_map` everywhere else. Either way only the four inputs are kept
    for the backward pass."""
    from .pallas import mhc

    args = ins["X"][0], ins["Phi"][0], ins["Bias"][0], ins["Alpha"][0]
    settings = dict(
        n=int(attrs["streams"]), eps=float(attrs["epsilon"]),
        iters=int(attrs["sinkhorn_iters"]),
        clamp_min=float(attrs["clamp_min"]),
        clamp_max=float(attrs["clamp_max"]))
    # XLA cannot partition a Mosaic call: over a mesh, the composition
    alone = ctx.mesh is None or ctx.mesh.size == 1
    rows = (mhc.map_rows(args[0], args[1], settings["n"], settings["iters"])
            if alone else None)
    with jax.named_scope("mhc_map"):
        if rows is None:
            pre, post, res, gap = jax.checkpoint(
                functools.partial(_mhc_map, **settings))(*args)
        else:
            pre, post, res, gap = mhc.mhc_map(*args, rows, **settings)
    return {"HPre": [pre], "HPost": [post], "HRes": [res],
            "SinkhornGap": [gap]}


def _streams(x, n):
    c = x.shape[-1] // n
    return [x[..., j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]


def _mhc_pre(x, h_pre):
    n = h_pre.shape[-1]
    h = h_pre.astype(jnp.float32)
    return sum(h[..., j:j + 1] * xj
               for j, xj in enumerate(_streams(x, n))).astype(x.dtype)


def _mhc_post(x, y, h_res, h_post):
    n = h_post.shape[-1]
    xs, yf = _streams(x, n), y.astype(jnp.float32)
    h_res, h_post = h_res.astype(jnp.float32), h_post.astype(jnp.float32)
    return jnp.concatenate(
        [sum(h_res[..., i * n + j:i * n + j + 1] * xs[j] for j in range(n))
         + h_post[..., i:i + 1] * yf for i in range(n)],
        axis=-1).astype(x.dtype)


@register("mhc_pre")
def mhc_pre(ctx, ins, attrs):
    """u = H_pre X: the sublayer's input [B, S, C] out of the n streams.
    Without HPre, the plain sum of the streams (the model's readout)."""
    x = ins["X"][0]
    with jax.named_scope("mhc_mix"):
        if "HPre" in ins:
            return {"Out": [jax.checkpoint(_mhc_pre)(x, ins["HPre"][0])]}
        n = int(attrs["streams"])
        return {"Out": [sum(_streams(x, n)).astype(x.dtype)]}


@register("mhc_post")
def mhc_post(ctx, ins, attrs):
    """X' = H_res X + H_post^T y: stream i of the result is
    sum_j H_res[i, j] X_j + H_post[i] y. On the TPU, at a width in whole
    lane tiles and rows a block tiles, the two one-pass kernels of
    ops/pallas/mhc.py (`mhc.mhc_rows` is the gate); `_mhc_post` everywhere
    else. Either way only the four inputs are kept for the backward pass."""
    from .pallas import mhc

    args = ins["X"][0], ins["Y"][0], ins["HRes"][0], ins["HPost"][0]
    # XLA cannot partition a Mosaic call: over a mesh, the composition
    alone = ctx.mesh is None or ctx.mesh.size == 1
    rows = mhc.mhc_rows(*args) if alone else None
    with jax.named_scope("mhc_mix"):
        if rows is None:
            return {"Out": [jax.checkpoint(_mhc_post)(*args)]}
        return {"Out": [mhc.mhc_post(*args, rows)]}
