"""Ring attention: sequence/context parallelism over a mesh axis.

A NEW capability relative to the reference (2020-era aiqingma/Paddle has no
sequence parallelism — SURVEY.md §5 "Long-context"): long sequences are
sharded over the "sp" mesh axis; each device holds a contiguous sequence
block of Q, K, V and rotates its K/V block around the ring with
`lax.ppermute` (ICI neighbor exchange) while accumulating flash-attention
style online-softmax partial results. Peak memory per chip is
O(S_local * D) and the K/V transfer overlaps with the matmul of the
previous block (XLA pipelines the ppermute against the einsum).

The loop is a `lax.scan`, so reverse-mode AD works end-to-end: the
backward pass rotates cotangents with the transposed permutation that JAX
derives for ppermute — no custom VJP needed.

When shapes permit (S_local % 128 == 0, D in {64,128,256}), each local
block runs the Pallas flash kernel via `flash_block_with_lse` — an
(o, lse)-returning custom-VJP core — and the ring merges partials by
log-sum-exp. Causal masking rides the kernel's (q_offset, k_offset)
global-position pair and dropout its in-kernel PRNG, so the training
configurations stay on the kernel path; the jnp online-softmax block
math below remains the fallback for non-kernel shapes.
"""
from __future__ import annotations

import math
from typing import Optional

NEG_INF = -1e30


def ring_attention(q, k, v, axis_name: str, bias=None, sm_scale=None,
                   causal: bool = False, dropout_prob: float = 0.0,
                   dropout_key=None):
    """Per-shard attention body (call inside shard_map / pjit manual axes).

    q, k, v: [B, nh, S_local, D] — the local sequence block.
    bias: optional per-key additive bias [B, S_local] (padding mask block),
        sharded like K; rotated around the ring together with K/V.
    dropout_prob/dropout_key: attention-probs dropout. Masking only the
        numerator accumulation (acc), never the normalizer (l), is exactly
        post-softmax dropout: out = sum(mask*p/(1-pr) * v) / sum(p).
    Returns [B, nh, S_local, D].
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, nh, s_loc, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    use_dropout = dropout_prob > 0.0 and dropout_key is not None

    from ..ops.pallas.flash_attention import flash_block_ok

    if flash_block_ok(s_loc, d):
        return _ring_flash(
            q, k, v, axis_name, bias, sm_scale, n,
            causal=causal,
            dropout_prob=dropout_prob if use_dropout else 0.0,
            dropout_key=dropout_key if use_dropout else None,
        )

    qf = q.astype(jnp.float32) * sm_scale
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        kb, vb, bb, m, l, acc = carry
        src = (idx - t) % n  # which rank's block we currently hold
        s = jnp.einsum(
            "bnqd,bnkd->bnqk", qf, kb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        if bb is not None:
            s = s + bb.astype(jnp.float32)[:, None, None, :]
        if causal:
            qpos = idx * s_loc + jnp.arange(s_loc)
            kpos = src * s_loc + jnp.arange(s_loc)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # explicit re-mask: for a fully-masked block m_new stays NEG_INF and
        # exp(s - m_new) would be exp(0)=1; the where() zeroes those rows
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p_num = p
        if use_dropout:
            # independent mask per (my shard, source block) pair
            kdrop = jax.random.fold_in(jax.random.fold_in(dropout_key, idx), src)
            keep = jax.random.bernoulli(kdrop, 1.0 - dropout_prob, p.shape)
            p_num = jnp.where(keep, p / (1.0 - dropout_prob), 0.0)
        acc = acc * alpha + jnp.einsum(
            "bnqk,bnkd->bnqd", p_num, vb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        if bb is not None:
            bb = lax.ppermute(bb, axis_name, perm)
        return (kb, vb, bb, m_new, l, acc), None

    m0 = jnp.full((b, nh, s_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nh, s_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b, nh, s_loc, d), jnp.float32)
    (kb, vb, bb, m, l, acc), _ = jax.lax.scan(
        step, (k, v, bias, m0, l0, acc0), jnp.arange(n)
    )
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def _ring_flash(q, k, v, axis_name, bias, sm_scale, n, causal=False,
                dropout_prob=0.0, dropout_key=None):
    """Ring schedule where each block is the Pallas flash kernel: merge
    per-block (o, lse) partials by log-sum-exp. AD flows through the
    kernel's custom VJP (the lse cotangent folds into delta).

    causal: the kernel masks each visiting block by its GLOBAL positions
    (q_offset = my shard start, k_offset = source shard start); blocks
    entirely in the future produce lse=-inf partials that merge to zero
    weight. dropout: regenerated in-kernel from a per-(shard, source)
    seed (interpret mode precomputes the mask host-side — same math)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops.pallas.flash_attention import _interpret, flash_block_with_lse

    b, nh, s_loc, d = q.shape
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    use_dropout = dropout_prob > 0.0 and dropout_key is not None
    seed_base = None
    if use_dropout and not _interpret():
        seed_base = jax.random.randint(
            dropout_key, (), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
        )

    def step(carry, t):
        kb, vb, bb, m, l, acc = carry
        src = (idx - t) % n  # which rank's block we currently hold
        kw = {}
        if causal:
            kw = dict(causal=True, q_offset=idx * s_loc,
                      k_offset=src * s_loc)
        if use_dropout:
            kw["dropout_prob"] = dropout_prob
            if seed_base is not None:
                kw["dropout_seed"] = (
                    seed_base + idx * jnp.int32(0x632BE59B)
                    + src * jnp.int32(0x1B873593)
                )
            else:
                # interpret (CPU) mode: the TPU in-kernel PRNG is
                # unavailable — draw the same numerator-only mask host-side
                kdrop = jax.random.fold_in(
                    jax.random.fold_in(dropout_key, idx), src
                )
                kw["dropout_mask"] = jax.random.bernoulli(
                    kdrop, 1.0 - dropout_prob, (b, nh, s_loc, s_loc)
                ).astype(jnp.uint8)
        o_b, lse_b = flash_block_with_lse(q, kb, vb, bb, sm_scale, **kw)
        lse_b = lse_b[..., None]  # [B, nh, S, 1]
        m_new = jnp.maximum(m, lse_b)
        scale_old = jnp.exp(m - m_new)
        scale_new = jnp.exp(lse_b - m_new)
        acc = acc * scale_old + o_b.astype(jnp.float32) * scale_new
        l = l * scale_old + scale_new
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        if bb is not None:
            bb = lax.ppermute(bb, axis_name, perm)
        return (kb, vb, bb, m_new, l, acc), None

    m0 = jnp.full((b, nh, s_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nh, s_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b, nh, s_loc, d), jnp.float32)
    (k, v, bias, m, l, acc), _ = jax.lax.scan(
        step, (k, v, bias, m0, l0, acc0), jnp.arange(n)
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention_global(q, k, v, mesh, axis: str = "sp", bias=None,
                          sm_scale=None, causal: bool = False,
                          batch_axis: Optional[str] = "dp",
                          dropout_prob: float = 0.0, dropout_key=None):
    """Global-array entry: shard [B, nh, S, D] over `axis` on the sequence
    dim (and `batch_axis` on batch if present in the mesh), run the ring
    body per shard. Usable under jit — GSPMD handles everything outside,
    the ring handles attention's cross-shard dependency inside."""
    import jax
    from jax.sharding import PartitionSpec as P

    ba = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    qkv_spec = P(ba, None, axis, None)
    bias_spec = P(ba, axis)

    if bias is None:
        def body(ql, kl, vl):
            return ring_attention(ql, kl, vl, axis, None, sm_scale, causal,
                                  dropout_prob, dropout_key)

        return jax.shard_map(
            body, mesh=mesh, in_specs=(qkv_spec,) * 3, out_specs=qkv_spec,
            check_vma=False,
        )(q, k, v)

    def body_b(ql, kl, vl, bl):
        return ring_attention(ql, kl, vl, axis, bl, sm_scale, causal,
                              dropout_prob, dropout_key)

    return jax.shard_map(
        body_b, mesh=mesh, in_specs=(qkv_spec,) * 3 + (bias_spec,),
        out_specs=qkv_spec, check_vma=False,
    )(q, k, v, bias)


def use_ring(ctx, attrs) -> bool:
    """Shared enablement predicate: the op asked for sequence parallelism
    AND the emit mesh actually has a populated "sp" axis."""
    return (
        bool(attrs.get("sequence_parallel", False))
        and ctx.mesh is not None
        and "sp" in ctx.mesh.axis_names
        and ctx.mesh.shape["sp"] > 1
    )


def key_bias_from_attn_bias(bias, batch):
    """Validate/convert an additive attention bias to the per-key [B, S]
    form the ring kernel rotates. Only [B,1,1,S] (padding mask) qualifies."""
    if bias is None:
        return None
    if bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1:
        return bias.reshape(batch, bias.shape[-1])
    raise ValueError(
        "sequence-parallel ring attention supports per-key bias [B,1,1,S] "
        f"(padding mask); got bias shape {bias.shape}"
    )
