"""Fused multi-head attention op.

Replaces the reference's BERT attention fusion machinery
(/root/reference/paddle/fluid/framework/ir/multihead_matmul_fuse_pass.cc and
 /root/reference/paddle/fluid/operators/math/bert_encoder_functor.cu):
there, a graph pass pattern-matches the decomposed attention subgraph and
swaps in a hand-written CUDA kernel. Here attention is a first-class op;
on TPU it lowers to a Pallas flash-attention kernel (online softmax, O(S)
memory), elsewhere to a jnp composition that XLA fuses.

Semantics: Q,K,V are [B, S, H] (head-interleaved, pre-split); BiasQK is an
additive mask broadcastable to [B, nh, S, S]. Output is [B, S, H].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .registry import register


def _split_heads(x, num_heads):
    b, s, h = x.shape
    return x.reshape(b, s, num_heads, h // num_heads).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, nh, s, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, nh * dh)


def _reference_attention(q, k, v, bias, dropout_prob, deterministic, rng_key,
                         sm_scale=None):
    """jnp composition: [B,nh,S,dh] in, [B,nh,S,dv] out; scores scaled by
    1/sqrt(dh) unless `sm_scale` says otherwise."""
    dh = q.shape[-1]
    scores = jnp.einsum(
        "bnqd,bnkd->bnqk", q, k, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(dh) if sm_scale is None else sm_scale)
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if not deterministic and dropout_prob > 0.0:
        keep = jax.random.bernoulli(rng_key, 1.0 - dropout_prob, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_prob), 0.0)
    return jnp.einsum("bnqk,bnkd->bnqd", probs, v)


# test hook: force the pallas path (interpret mode) on CPU
FORCE_PALLAS = False


def _use_pallas(q):
    from .pallas.flash_attention import flash_shapes_ok

    return flash_shapes_ok(q.shape[2], q.shape[-1])


def _causal_bias(s):
    import numpy as _np

    return jnp.where(
        _np.tril(_np.ones((s, s), bool)), 0.0, -1e30)[None, None, :, :]


def _pad_heads(x3, num_heads, width):
    """[B, S, nh * d] -> [B, S, nh * width], zeros behind every head."""
    b, s, h = x3.shape
    d = h // num_heads
    if d == width:
        return x3
    x4 = x3.reshape(b, s, num_heads, d)
    x4 = jnp.pad(x4, ((0, 0), (0, 0), (0, 0), (0, width - d)))
    return x4.reshape(b, s, num_heads * width)


def latent_head_groups(sq, skv, num_heads, width, batch=None, causal=True):
    """Into how many equal groups of heads latent attention splits a call
    so that the BSH kernels hold a group's slab: the fewest that divide
    `num_heads` and whose [.., num_heads / groups * width] operands pass
    `bsh_dispatch_ok` (the stream kernels keep a batch row's K and V of
    all the heads they are given in VMEM, `pallas/feasible.py`); None
    where not even one head passes. A function of the shapes and of the
    kernels' own model: 1 for 4 heads of 256 at S 4096, 2 for 20."""
    from .pallas.flash_attention import bsh_dispatch_ok

    for groups in range(1, num_heads + 1):
        held = num_heads // groups
        if num_heads % groups == 0 and bsh_dispatch_ok(
                sq, skv, held * width, held, batch=batch, causal=causal):
            return groups
    return None


def latent_attention(q3, k3, v3, num_heads, sm_scale=None, causal=True,
                     mesh=None):
    """Attention whose value heads need not be as wide as its query / key
    heads and whose softmax scale is the caller's: the form latent
    attention (MLA) takes, 192-wide q / k against 128-wide v under a scale
    that YaRN multiplies, or 256-wide q / k (192 + 64) against 256-wide v.
    q3, k3 [B, S, nh * dqk], v3 [B, S, nh * dv] -> [B, S, nh * dv]; no
    bias, no dropout.

    Where the flash gates pass, every head is zero-padded to the next width
    the BSH kernels run (192 and 128 -> 256; heads of 256 and 256 stand as
    they are) and the result sliced: a zero column adds nothing to a score
    and a zero value column gives a zero output column, so the result is
    that of the unpadded heads. Where the slab of all heads is more than
    the kernels hold, the heads go in the fewest equal groups that pass,
    one call a group (`latent_head_groups`: twenty heads of 256 at S 4096
    in two calls of ten). The calls carry names of their own:
    `flash_mla_causal_fwd` / `_bwd` on padded heads, whose useful work is
    the unpadded widths', and `flash_mla_wide_causal_fwd` / `_bwd` where
    nothing is padded and the call's shapes are its work. Elsewhere the jnp
    composition."""
    from ..fluid.monitor import record_attention_lowering
    from .pallas.flash_attention import HEAD_WIDTHS, flash_attention_bsh

    b, sq, hq = q3.shape
    skv = k3.shape[1]
    dqk, dv = hq // num_heads, v3.shape[2] // num_heads
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(dqk)
    width = next((w for w in HEAD_WIDTHS if w >= max(dqk, dv)), None)
    groups = None if width is None else latent_head_groups(
        sq, skv, num_heads, width, batch=b, causal=causal)
    if groups is not None:
        form = "mla_wide" if dqk == dv == width else "mla"
        record_attention_lowering("pallas", form)
        held = num_heads // groups
        padded = [_pad_heads(x3, num_heads, width) for x3 in (q3, k3, v3)]
        outs = []
        for g in range(groups):
            q, k, v = padded if groups == 1 else (
                x[..., g * held * width:(g + 1) * held * width]
                for x in padded)
            outs.append(flash_attention_bsh(
                q, k, v, None, num_heads=held, sm_scale=sm_scale,
                causal=causal, mesh=mesh, form=form))
        out = outs[0] if groups == 1 else jnp.concatenate(outs, axis=-1)
        out = out.reshape(b, sq, num_heads, width)[..., :dv]
        return out.reshape(b, sq, num_heads * dv)
    record_attention_lowering("jnp", "mla")
    out = _reference_attention(
        _split_heads(q3, num_heads), _split_heads(k3, num_heads),
        _split_heads(v3, num_heads), _causal_bias(sq) if causal else None,
        0.0, True, None, sm_scale=sm_scale)
    return _merge_heads(out)


@register("fused_multihead_attention")
def fused_multihead_attention(ctx, ins, attrs):
    from ..fluid.monitor import record_attention_lowering
    from ..parallel.ring_attention import (
        key_bias_from_attn_bias,
        ring_attention_global,
        use_ring,
    )

    q3, k3, v3 = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins.get("BiasQK", [None])[0]
    nh = int(attrs["num_heads"])
    dropout_prob = float(attrs.get("dropout_prob", 0.0))
    is_test = bool(attrs.get("is_test", False))
    causal = bool(attrs.get("causal", False))
    sm_scale = float(attrs.get("softmax_scale", 0.0)) or None

    if sm_scale is not None or v3.shape[2] != q3.shape[2]:
        # a given scale or value heads of another width: the latent form
        if bias is not None or (not is_test and dropout_prob > 0.0):
            raise ValueError(
                "fused_multihead_attention: a softmax_scale or a V of "
                "another width than Q takes neither BiasQK nor dropout")
        return {"Out": [latent_attention(q3, k3, v3, nh, sm_scale, causal,
                                         mesh=ctx.mesh)]}

    if use_ring(ctx, attrs):
        # sequence-parallel ring attention over the "sp" mesh axis; probs
        # dropout is applied inside the ring (numerator-only masking)
        b, s, h = q3.shape
        key_bias = key_bias_from_attn_bias(bias, b)
        dkey = None
        if not is_test and dropout_prob > 0.0:
            dkey = ctx.salted_rng(int(attrs.get("rng_salt", 0)))
        out = ring_attention_global(
            _split_heads(q3, nh), _split_heads(k3, nh), _split_heads(v3, nh),
            ctx.mesh, axis="sp", bias=key_bias, causal=causal,
            dropout_prob=0.0 if is_test else dropout_prob, dropout_key=dkey,
        )
        return {"Out": [_merge_heads(out)]}

    # BSH fast path: no head transposes, rectangular (cross-attention)
    # q/kv lengths included — per-key ([B,1,1,S]) or absent bias only.
    # BiasQK gets a ZERO cotangent on every kernel path of this op (the
    # BHSD call below also defaults bias_requires_grad=False): the op's
    # bias contract is an additive mask derived from data, not a
    # trainable parameter.
    from .pallas.flash_attention import bsh_dispatch_ok

    sq, skv, h = q3.shape[1], k3.shape[1], q3.shape[2]
    if bsh_dispatch_ok(sq, skv, h, nh, bias=bias, batch=q3.shape[0],
                       causal=causal):
        from .pallas.flash_attention import flash_attention_bsh

        record_attention_lowering("pallas", "mha")
        dkey = None
        if not is_test and dropout_prob > 0.0:
            dkey = ctx.salted_rng(int(attrs.get("rng_salt", 0)))
        out = flash_attention_bsh(
            q3, k3, v3, bias, num_heads=nh, causal=causal,
            dropout_prob=0.0 if is_test else dropout_prob,
            dropout_key=dkey, mesh=ctx.mesh,
        )
        return {"Out": [out]}

    q = _split_heads(q3, nh)
    k = _split_heads(k3, nh)
    v = _split_heads(v3, nh)

    # full [.., S, S] biases on square q/kv lengths ride the BHSD kernel;
    # everything else falls through to the jnp composition
    if _use_pallas(q) and q.shape[2] == k.shape[2]:
        from .pallas.flash_attention import flash_attention

        record_attention_lowering("pallas", "mha")
        dkey = None
        if not is_test and dropout_prob > 0.0:
            dkey = ctx.salted_rng(int(attrs.get("rng_salt", 0)))
        out = flash_attention(
            q, k, v, bias, causal=causal,
            dropout_prob=0.0 if is_test else dropout_prob,
            dropout_key=dkey, mesh=ctx.mesh,
        )
    else:
        record_attention_lowering("jnp", "mha")
        if causal:
            cmask = _causal_bias(q.shape[2])
            bias = cmask if bias is None else bias + cmask
        rng = None
        if not is_test and dropout_prob > 0.0:
            rng = ctx.salted_rng(int(attrs.get("rng_salt", 0)))
        # zero-cotangent BiasQK contract: the kernel paths above never
        # produce a dbias, so the fallback must not either — a shape or
        # backend change would otherwise flip gradient semantics
        if bias is not None:
            bias = jax.lax.stop_gradient(bias)
        out = _reference_attention(q, k, v, bias, dropout_prob, is_test, rng)
    return {"Out": [_merge_heads(out)]}
