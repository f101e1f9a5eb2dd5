"""Flash attention (online-softmax) Pallas TPU kernel with custom VJP.

TPU-native replacement for the reference's fused BERT attention CUDA kernel
(/root/reference/paddle/fluid/operators/math/bert_encoder_functor.cu —
softmax over scores in shared memory) — here the whole attention is one
kernel: scores never materialize in HBM (O(S) memory instead of O(S^2)),
and the backward pass recomputes probabilities blockwise from the saved
log-sum-exp, the standard flash-attention-2 scheme.

Round-3 kernel layout (profiled on v5e):
  - head-group batching: each grid cell owns G (bh) rows and loops over
    them in-kernel, so DMA blocks are G x bigger and the lse/delta
    tensors tile cleanly;
  - lse and delta ride as [BH, S] f32 with (G, bq) blocks — the round-2
    [BH, NQ, 1, BQ] layout forced T(1,128) sub-tile writes that cost
    ~0.37 ms/layer (70% of the bare kernel!) in the fwd alone, and the
    same penalty again on the bwd reads;
  - the per-key additive bias (BERT padding mask) is pre-broadcast to
    [BH, S] outside the kernel — JAX autodiff turns the broadcast into
    the head/batch sum for dbias, so the kernels lose all bias
    row-mapping arithmetic ([B,nh,S,S]-style full bias keeps the row-map
    path at G=1; it is the rare configuration).

Capabilities:
  - additive bias: per-key [B,1,1,S] (BERT padding mask, cheap correct
    dbias) or full [B,nh,S,S] / [B,1,S,S] / [1,1,S,S]
  - causal masking with block-level skipping (lower-triangular work
    only), including a runtime (q_offset, k_offset) pair so ring
    attention can causal-mask blocks whose global positions are shifted
    relative to the local shard
  - attention-probs dropout folded into the kernel: on TPU the mask is
    regenerated in both forward and backward — zero HBM traffic for
    masks. The BHSD kernels draw it from the hardware PRNG (pltpu.prng_*)
    per (bh, q-block, k-block), and so do the BSH whole-tile kernels
    (S < 1024); the BSH stream kernels draw it from a counter-based
    hash of the absolute (bh, key, query) position, so their two passes
    may tile differently (_dropout_keep_t).
    Masking only the numerator accumulator and never the normalizer is
    exactly post-softmax dropout (same scheme as parallel/ring_attention).
    In interpret mode (CPU tests) the TPU PRNG is unavailable, so the
    mask is precomputed host-side and passed as an input — the dropout
    MATH (fwd + custom VJP) is identical and fully testable on CPU.
  - SPMD: `mesh=` wraps the kernel in shard_map over (dp, tp) — batch on
    dp, heads on tp (megatron split); dropout seeds are decorrelated per
    shard and per-key dbias is psum'd over tp.

S must be a multiple of 128. The BHSD kernels' blocks, which are grid
tiles and compute tiles at once, cap at 512 to match VMEM; the BSH
kernels' DMA tiles go to 1024 and hold compute tiles of their own.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from . import feasible as _feas

MIN_BLOCK = 128
NEG_INF = -1e30
# Scoped-VMEM headroom for the group-size estimate. Calibrated on v5e:
# the dq kernel at (G=3, s=4096, bq=512) — estimate 9.9MB — actually
# allocates 16.98M scoped and OOMs the 16M limit, while (G=2, s=4096,
# estimate 7.7MB) fits; 9.5MB rejects the former and keeps the latter.
_VMEM_BUDGET = 9 * 1024 * 1024 + 512 * 1024


def _pick_block(s):
    """Largest block that tiles s, capped at 512: the whole score tile
    fits VMEM and bigger dots keep the MXU busy (as GRID tiles of the
    BHSD kernels, whose body is one block, 128-blocks are latency-bound:
    profiled 4x slower at S=512; the BSH kernels take this as a DMA tile
    only and compute in tiles of their own)."""
    for cand in (512, 256, 128):
        if s % cand == 0:
            return cand
    raise _feas.NoFeasibleConfig(
        "flash", {"s": s},
        [({"block": c}, f"{s} % {c} != 0") for c in (512, 256, 128)],
        detail=f"seq must be a multiple of {MIN_BLOCK}")


def _scan_groups(bh, fits):
    """Shared group-size scan: the largest divisor of bh whose footprint
    estimate fits."""
    for g in (8, 6, 4, 3, 2, 1):
        if bh % g == 0 and fits(g):
            return g
    return 1


def _pick_group(bh, s, bq, d, full_bias):
    """Head-group size G: how many bh rows one grid cell owns. Bounded by
    a VMEM estimate (k/v resident per cell, double-buffered) and by
    divisibility of bh. full-bias mode pins G=1 (its row-map indexing is
    per-bh)."""
    if full_bias:
        return 1

    def fits(g):
        kv = 2 * g * s * d * 2 * 2       # k+v, bf16, double-buffered
        qo = 2 * g * bq * d * 2 * 2      # q+o blocks
        sc = 3 * bq * min(s, 512) * 4    # per-head f32 score temporaries
        return kv + qo + sc <= _VMEM_BUDGET

    return _scan_groups(bh, fits)


# lse, delta, the pre-broadcast key bias and its gradient all ride as
# [BH, 1, S] with (G, 1, block) blocks: the trailing (1, block) dims
# satisfy Mosaic's tiling rule for ANY head-group size G (a plain
# (G, block) block would need G % 8 == 0), and the rows are written/read
# lane-major, which pairs with the MXU transpose trick below.


# mixing constants for the per-(bh, qi, ki) dropout seed (fwd and bwd must
# regenerate the exact same mask for a block pair); wrapped to signed i32
_SEED_BH = 0x9E3779B9 - (1 << 32)
_SEED_QI = 0x85EBCA6B - (1 << 32)
_SEED_KI = 0xC2B2AE35 - (1 << 32)


def _dropout_quantized_thresh(keep_prob):
    """THE single source of the 8-bit dropout quantization: keep a byte
    iff byte < t, with t in [1, 256]. t == 256 keeps everything exactly
    (bytes are <= 255), so near-1.0 keep probabilities round to a true
    no-op instead of silently dropping 1/256. The numerator rescale must
    divide by t/256 — derive BOTH from this function or the mask and the
    rescale go out of sync (a systematic training bias)."""
    return max(1, min(256, round(keep_prob * 256)))


def _dropout_quantized_keep(keep_prob):
    """Effective keep probability of the quantized in-kernel mask."""
    return _dropout_quantized_thresh(keep_prob) / 256.0


def _dropout_keep(seed_ref, bh, qi, ki, keep_prob, bq, bk):
    """[bq, bk] keep mask from the TPU hardware PRNG.

    One generated u32 word feeds up to FOUR mask bytes (column blocks of
    bk // pack, pack = min(4, bk // 128) to keep 128-lane alignment):
    the PRNG was ~12% of the forward kernel at one word per element.
    Compare in int32 throughout — Mosaic's u32 lowerings are signed;
    bytes are masked to [0, 255] so the arithmetic stays well-defined."""
    pltpu.prng_seed(
        seed_ref[0]
        + bh * jnp.int32(_SEED_BH)
        + qi * jnp.int32(_SEED_QI)
        + ki * jnp.int32(_SEED_KI)
    )
    thresh = jnp.int32(_dropout_quantized_thresh(keep_prob))
    pack = min(4, bk // 128)
    if pack > 1:
        words = pltpu.bitcast(
            pltpu.prng_random_bits((bq, bk // pack)), jnp.int32
        )
        parts = [
            ((words >> jnp.int32(8 * c)) & jnp.int32(0xFF)) < thresh
            for c in range(pack)
        ]
        return jnp.concatenate(parts, axis=1)
    bits = pltpu.bitcast(pltpu.prng_random_bits((bq, bk)), jnp.int32)
    return (bits & jnp.int32(0xFF)) < thresh


def _causal_mask(s, qglob, kglob, bq, bk):
    qpos = qglob + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kglob + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _hi_blocks(causal, qi, bq, bk, nk, q_off, k_off):
    """Number of k blocks a causal q block must visit. q_off/k_off are
    global offsets (ring attention); both 0 locally."""
    if not causal:
        return nk
    # last visible kpos = q_off + (qi+1)*bq - 1 - k_off
    last = q_off + (qi + 1) * bq - k_off
    return jnp.clip((last + bk - 1) // bk, 0, nk)


def _lo_blocks(causal, ki, bq, bk, nq, q_off, k_off):
    """First q block that sees causal k block ki (dkv loop lower bound)."""
    if not causal:
        return 0
    first = k_off + ki * bk - q_off  # lowest qpos that can see this block
    return jnp.clip(first // bq, 0, nq)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _make_fwd_kernel(*, sm_scale, causal, dropout_prob, bias_mode, use_prng,
                     has_mask, has_offsets, G, bq, bk, num_heads, bias_dims):
    """bias_mode: None | 'key' ([BH,S] pre-broadcast) | 'full' ([R,S,S])."""

    def kernel(*refs):
        it = iter(refs)
        q_ref = next(it)          # [G, BQ, D]
        k_ref = next(it)          # [G, S, D]
        v_ref = next(it)          # [G, S, D]
        bias_ref = next(it) if bias_mode else None
        mask_ref = next(it) if has_mask else None     # [G, BQ, S] uint8
        seed_ref = next(it) if use_prng else None     # [1] int32 (SMEM)
        off_ref = next(it) if has_offsets else None   # [2] int32 (SMEM)
        o_ref = next(it)          # [G, BQ, D]
        lse_ref = next(it)        # [G, 1, BQ]

        gi = pl.program_id(0)
        qi = pl.program_id(1)
        seq_len = k_ref.shape[1]
        nk = seq_len // bk
        d = q_ref.shape[-1]
        keep_prob = 1.0 - dropout_prob
        # PRNG path draws quantized 8-bit uniforms; the rescale must
        # match its EFFECTIVE keep probability (mask path keeps exact)
        keep_div = (
            _dropout_quantized_keep(keep_prob) if use_prng else keep_prob
        )
        q_off = off_ref[0] if has_offsets else 0
        k_off = off_ref[1] if has_offsets else 0
        ident = backend.identity(bq)

        def head(g, _):
            bh = gi * G + g
            # keep the input dtype (bf16 under AMP) for the MXU dots — f32
            # inputs would force multi-pass f32 matmuls; accumulate in f32
            q = q_ref[g]

            def body(i, carry):
                m, l, acc = carry
                k = k_ref[g, pl.ds(i * bk, bk), :]
                v = v_ref[g, pl.ds(i * bk, bk), :]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * sm_scale  # [BQ, BK]
                if bias_mode == "key":
                    s = s + bias_ref[g, 0, pl.ds(i * bk, bk)][None, :]
                elif bias_mode == "full":
                    s = s + bias_ref[0, :, pl.ds(i * bk, bk)].astype(jnp.float32)
                if causal:
                    s = _causal_mask(
                        s, q_off + qi * bq, k_off + i * bk, bq, bk
                    )
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
                # numerator-only dropout: l accumulates undropped p, acc
                # the masked p/keep_prob — exactly post-softmax dropout
                p_num = p
                if dropout_prob > 0.0:
                    if use_prng:
                        keep = _dropout_keep(
                            seed_ref, bh, qi, i, keep_prob, bq, bk
                        )
                    else:
                        keep = mask_ref[g, :, pl.ds(i * bk, bk)] != 0
                    p_num = jnp.where(keep, p / keep_div, 0.0)
                acc = acc * alpha + jax.lax.dot_general(
                    p_num.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                return m_new, l, acc

            m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((bq, 1), jnp.float32)
            acc0 = jnp.zeros((bq, d), jnp.float32)
            hi = _hi_blocks(causal, qi, bq, bk, nk, q_off, k_off)
            m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
            l_safe = jnp.maximum(l, 1e-30)
            o_ref[g] = (acc / l_safe).astype(o_ref.dtype)
            lse_ref[g, 0] = backend.to_lanes(m + jnp.log(l_safe), ident)[0]
            return 0

        jax.lax.fori_loop(0, G, head, 0)

    return kernel


def _fwd_specs(bh, s, d, G, bq, bias_mode, bias_dims, num_heads, has_mask,
               use_prng, has_offsets):
    in_specs = [
        pl.BlockSpec((G, bq, d), lambda g, i: (g, i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((G, s, d), lambda g, i: (g, 0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((G, s, d), lambda g, i: (g, 0, 0), memory_space=pltpu.VMEM),
    ]
    if bias_mode == "key":
        in_specs.append(
            pl.BlockSpec((G, 1, s), lambda g, i: (g, 0, 0),
                         memory_space=pltpu.VMEM)
        )
    elif bias_mode == "full":
        dv_, md_ = _bias_row_map(bias_dims, num_heads)
        in_specs.append(
            pl.BlockSpec(
                (1, bq, s),
                lambda g, i, dv=dv_, md=md_: ((g // dv) % md, i, 0),
                memory_space=pltpu.VMEM,
            )
        )
    if has_mask:
        in_specs.append(
            pl.BlockSpec((G, bq, s), lambda g, i: (g, i, 0),
                         memory_space=pltpu.VMEM)
        )
    if use_prng:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_offsets:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    return in_specs


def _flash_fwd(q, k, v, bias, mask, seed, offsets, *, sm_scale, num_heads,
               causal, dropout_prob, bias_mode, bias_dims):
    bh, s, d = q.shape
    bq = bk = _pick_block(s)
    nq = s // bq
    G = _pick_group(bh, s, bq, d, bias_mode == "full")
    use_prng = dropout_prob > 0.0 and mask is None
    has_mask = mask is not None and dropout_prob > 0.0
    has_offsets = offsets is not None
    in_specs = _fwd_specs(bh, s, d, G, bq, bias_mode, bias_dims, num_heads,
                          has_mask, use_prng, has_offsets)
    args = [q, k, v]
    if bias_mode:
        args.append(bias)
    if has_mask:
        args.append(mask)
    if use_prng:
        args.append(seed)
    if has_offsets:
        args.append(offsets)
    kernel = _make_fwd_kernel(
        sm_scale=sm_scale, causal=causal, dropout_prob=dropout_prob,
        bias_mode=bias_mode, use_prng=use_prng, has_mask=has_mask,
        has_offsets=has_offsets, G=G, bq=bq, bk=bk, num_heads=num_heads,
        bias_dims=bias_dims,
    )
    lse_spec = pl.BlockSpec(
        (G, 1, bq), lambda g, i: (g, 0, i), memory_space=pltpu.VMEM
    )
    lse_shape = jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh // G, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((G, bq, d), lambda g, i: (g, i, 0), memory_space=pltpu.VMEM),
            lse_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            lse_shape,
        ],
        name="flash_fwd",
        interpret=backend.interpret(),
    )(*args)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _make_bwd_dq_kernel(*, sm_scale, causal, dropout_prob, bias_mode,
                        use_prng, has_mask, has_offsets, G, bq, bk,
                        num_heads, bias_dims):
    def kernel(*refs):
        it = iter(refs)
        q_ref = next(it)          # [G, BQ, D]
        k_ref = next(it)          # [G, S, D]
        v_ref = next(it)          # [G, S, D]
        bias_ref = next(it) if bias_mode else None
        mask_ref = next(it) if has_mask else None
        seed_ref = next(it) if use_prng else None
        off_ref = next(it) if has_offsets else None
        do_ref = next(it)         # [G, BQ, D]
        lse_ref = next(it)        # [G, 1, BQ]
        delta_ref = next(it)      # [G, 1, BQ]
        dq_ref = next(it)         # [G, BQ, D]

        gi = pl.program_id(0)
        qi = pl.program_id(1)
        seq_len = k_ref.shape[1]
        nk = seq_len // bk
        d = q_ref.shape[-1]
        keep_prob = 1.0 - dropout_prob
        # PRNG path draws quantized 8-bit uniforms; the rescale must
        # match its EFFECTIVE keep probability (mask path keeps exact)
        keep_div = (
            _dropout_quantized_keep(keep_prob) if use_prng else keep_prob
        )
        q_off = off_ref[0] if has_offsets else 0
        k_off = off_ref[1] if has_offsets else 0
        ident = backend.identity(bq)

        def head(g, _):
            bh = gi * G + g
            q = q_ref[g]
            do = do_ref[g]
            lse = backend.to_sublanes(lse_ref[g], ident)
            delta = backend.to_sublanes(delta_ref[g], ident)

            def body(i, dq):
                k = k_ref[g, pl.ds(i * bk, bk), :]
                v = v_ref[g, pl.ds(i * bk, bk), :]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * sm_scale
                if bias_mode:  # split path serves full-bias only
                    s = s + bias_ref[0, :, pl.ds(i * bk, bk)].astype(jnp.float32)
                if causal:
                    s = _causal_mask(
                        s, q_off + qi * bq, k_off + i * bk, bq, bk
                    )
                p = jnp.exp(s - lse)  # normalized probs P [BQ, BK]
                dp = jax.lax.dot_general(
                    do, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if dropout_prob > 0.0:
                    if use_prng:
                        keep = _dropout_keep(
                            seed_ref, bh, qi, i, keep_prob, bq, bk
                        )
                    else:
                        keep = mask_ref[g, :, pl.ds(i * bk, bk)] != 0
                    c = jnp.where(keep, 1.0 / keep_div, 0.0)
                    ds = p * (c * dp - delta) * sm_scale
                else:
                    ds = p * (dp - delta) * sm_scale
                return dq + jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

            hi = _hi_blocks(causal, qi, bq, bk, nk, q_off, k_off)
            dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((bq, d), jnp.float32))
            dq_ref[g] = dq.astype(dq_ref.dtype)
            return 0

        jax.lax.fori_loop(0, G, head, 0)

    return kernel


def _make_bwd_dkv_kernel(*, sm_scale, causal, dropout_prob, bias_mode,
                         use_prng, has_mask, has_offsets, want_dbias, G,
                         bq, bk, num_heads, bias_dims):
    """Split-path dk/dv kernel — serves ONLY the full-bias configuration
    (every other bias mode takes the fused backward). Grid (BH//G, NK);
    loops over q blocks; writes the [S, BK] column of ds (pre-scale) as
    dbias when want_dbias."""

    def kernel(*refs):
        it = iter(refs)
        q_ref = next(it)          # [G, S, D]
        k_ref = next(it)          # [G, BK, D]
        v_ref = next(it)          # [G, BK, D]
        bias_ref = next(it) if bias_mode else None
        mask_ref = next(it) if has_mask else None    # [G, S, BK]
        seed_ref = next(it) if use_prng else None
        off_ref = next(it) if has_offsets else None
        do_ref = next(it)         # [G, S, D]
        lse_ref = next(it)        # [G, 1, S]
        delta_ref = next(it)      # [G, 1, S]
        dk_ref = next(it)         # [G, BK, D]
        dv_ref = next(it)         # [G, BK, D]
        dbias_full_ref = next(it) if want_dbias else None  # [1, S, BK]

        gi = pl.program_id(0)
        ki = pl.program_id(1)
        seq_len = q_ref.shape[1]
        nq = seq_len // bq
        d = k_ref.shape[-1]
        keep_prob = 1.0 - dropout_prob
        # PRNG path draws quantized 8-bit uniforms; the rescale must
        # match its EFFECTIVE keep probability (mask path keeps exact)
        keep_div = (
            _dropout_quantized_keep(keep_prob) if use_prng else keep_prob
        )
        q_off = off_ref[0] if has_offsets else 0
        k_off = off_ref[1] if has_offsets else 0
        ident = backend.identity(bq)
        if dbias_full_ref is not None:
            dbias_full_ref[0] = jnp.zeros_like(dbias_full_ref[0])

        def head(g, _):
            bh = gi * G + g
            k = k_ref[g]  # [BK, D]
            v = v_ref[g]

            def body(i, carry):
                dk, dv, dbsum = carry
                q = q_ref[g, pl.ds(i * bq, bq), :]
                do = do_ref[g, pl.ds(i * bq, bq), :]
                lse = backend.to_sublanes(
                    lse_ref[g, :, pl.ds(i * bq, bq)], ident
                )
                delta = backend.to_sublanes(
                    delta_ref[g, :, pl.ds(i * bq, bq)], ident
                )
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * sm_scale
                if bias_mode:  # split path serves full-bias only
                    s = s + bias_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
                if causal:
                    s = _causal_mask(
                        s, q_off + i * bq, k_off + ki * bk, bq, bk
                    )
                p = jnp.exp(s - lse)  # [BQ, BK]
                dp = jax.lax.dot_general(
                    do, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if dropout_prob > 0.0:
                    if use_prng:
                        keep = _dropout_keep(
                            seed_ref, bh, i, ki, keep_prob, bq, bk
                        )
                    else:
                        keep = mask_ref[g, pl.ds(i * bq, bq), :] != 0
                    c = jnp.where(keep, 1.0 / keep_div, 0.0)
                    p_num = p * c
                else:
                    c = 1.0
                    p_num = p
                dv = dv + jax.lax.dot_general(
                    p_num.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ds_nos = p * (dp * c - delta)
                ds = ds_nos * sm_scale  # [BQ, BK]
                dk = dk + jax.lax.dot_general(
                    ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if dbias_full_ref is not None:
                    dbias_full_ref[0, pl.ds(i * bq, bq), :] = ds_nos.astype(
                        dbias_full_ref.dtype
                    )
                return dk, dv, dbsum

            dk0 = jnp.zeros((bk, d), jnp.float32)
            dv0 = jnp.zeros((bk, d), jnp.float32)
            db0 = jnp.zeros((bk,), jnp.float32)
            lo = _lo_blocks(causal, ki, bq, bk, nq, q_off, k_off)
            dk, dv, _ = jax.lax.fori_loop(lo, nq, body, (dk0, dv0, db0))
            dk_ref[g] = dk.astype(dk_ref.dtype)
            dv_ref[g] = dv.astype(dv_ref.dtype)
            return 0

        jax.lax.fori_loop(0, G, head, 0)

    return kernel


def _make_bwd_fused_kernel(*, sm_scale, causal, dropout_prob, bias_mode,
                           use_prng, has_mask, has_offsets, want_dbias, G,
                           bq, bk, num_heads, bias_dims):
    """Single-pass backward: grid (BH//G, NK) with NK innermost. Computes
    dk/dv for this k block AND accumulates dq across the NK sweep into an
    f32 output block whose index map is constant in ki (Pallas keeps the
    revisited block resident in VMEM; it is zeroed at ki==0 and written
    back once the sweep ends). Versus the two-kernel scheme this shares
    the score/probability recompute (7 matmul passes instead of 9) and
    reads q/do/k/v once instead of twice. key-bias and no-bias only —
    full-bias keeps the split path (its row-map runs at G=1)."""

    def kernel(*refs):
        it = iter(refs)
        q_ref = next(it)          # [G, S, D]
        k_ref = next(it)          # [G, BK, D]
        v_ref = next(it)          # [G, BK, D]
        bias_ref = next(it) if bias_mode else None
        mask_ref = next(it) if has_mask else None    # [G, S, BK]
        seed_ref = next(it) if use_prng else None
        off_ref = next(it) if has_offsets else None
        do_ref = next(it)         # [G, S, D]
        lse_ref = next(it)        # [G, 1, S]
        delta_ref = next(it)      # [G, 1, S]
        dq_ref = next(it)         # [G, S, D] f32, revisited across ki
        dk_ref = next(it)         # [G, BK, D]
        dv_ref = next(it)         # [G, BK, D]
        dbias_key_ref = next(it) if (want_dbias and bias_mode == "key") else None

        gi = pl.program_id(0)
        ki = pl.program_id(1)
        nk = pl.num_programs(1)
        seq_len = q_ref.shape[1]
        nq = seq_len // bq
        d = k_ref.shape[-1]
        keep_prob = 1.0 - dropout_prob
        # PRNG path draws quantized 8-bit uniforms; the rescale must
        # match its EFFECTIVE keep probability (mask path keeps exact)
        keep_div = (
            _dropout_quantized_keep(keep_prob) if use_prng else keep_prob
        )
        q_off = off_ref[0] if has_offsets else 0
        k_off = off_ref[1] if has_offsets else 0
        ident = backend.identity(bq)

        @pl.when(ki == 0)
        def _init():
            dq_ref[...] = jnp.zeros_like(dq_ref)

        def head(g, _):
            bh = gi * G + g
            k = k_ref[g]  # [BK, D]
            v = v_ref[g]
            if bias_mode == "key":
                b_block = bias_ref[g, 0, pl.ds(ki * bk, bk)]

            def body(i, carry):
                dk, dv, dbsum = carry
                q = q_ref[g, pl.ds(i * bq, bq), :]
                do = do_ref[g, pl.ds(i * bq, bq), :]
                lse = backend.to_sublanes(
                    lse_ref[g, :, pl.ds(i * bq, bq)], ident
                )
                delta = backend.to_sublanes(
                    delta_ref[g, :, pl.ds(i * bq, bq)], ident
                )
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * sm_scale
                if bias_mode == "key":
                    s = s + b_block[None, :]
                if causal:
                    s = _causal_mask(
                        s, q_off + i * bq, k_off + ki * bk, bq, bk
                    )
                p = jnp.exp(s - lse)  # [BQ, BK]
                dp = jax.lax.dot_general(
                    do, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if dropout_prob > 0.0:
                    if use_prng:
                        keep = _dropout_keep(
                            seed_ref, bh, i, ki, keep_prob, bq, bk
                        )
                    else:
                        keep = mask_ref[g, pl.ds(i * bq, bq), :] != 0
                    c = jnp.where(keep, 1.0 / keep_div, 0.0)
                    p_num = p * c
                else:
                    c = 1.0
                    p_num = p
                dv = dv + jax.lax.dot_general(
                    p_num.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ds_nos = p * (dp * c - delta)
                ds = (ds_nos * sm_scale).astype(q.dtype)  # [BQ, BK]
                dk = dk + jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dq_ref[g, pl.ds(i * bq, bq), :] += jax.lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if dbias_key_ref is not None:
                    dbsum = dbsum + jnp.sum(ds_nos, axis=0)
                return dk, dv, dbsum

            dk0 = jnp.zeros((bk, d), jnp.float32)
            dv0 = jnp.zeros((bk, d), jnp.float32)
            db0 = jnp.zeros((bk,), jnp.float32)
            lo = _lo_blocks(causal, ki, bq, bk, nq, q_off, k_off)
            dk, dv, dbsum = jax.lax.fori_loop(lo, nq, body, (dk0, dv0, db0))
            dk_ref[g] = dk.astype(dk_ref.dtype)
            dv_ref[g] = dv.astype(dv_ref.dtype)
            if dbias_key_ref is not None:
                dbias_key_ref[g, 0] = dbsum
            return 0

        jax.lax.fori_loop(0, G, head, 0)

    return kernel


def _bwd_fused(q, k, v, bias, mask, seed, offsets, g, lse, delta, *,
               sm_scale, num_heads, causal, dropout_prob, bias_mode,
               bias_dims, want_dbias, G, bq, bk):
    """Launch the single-pass backward. Returns (dq, dk, dv, dbias)."""
    bh, s, d = q.shape
    nk = s // bk
    use_prng = dropout_prob > 0.0 and mask is None
    has_mask = mask is not None and dropout_prob > 0.0
    has_offsets = offsets is not None

    kspec = pl.BlockSpec((G, bk, d), lambda g_, i: (g_, i, 0), memory_space=pltpu.VMEM)
    fullspec = pl.BlockSpec((G, s, d), lambda g_, i: (g_, 0, 0), memory_space=pltpu.VMEM)
    fullrow = pl.BlockSpec((G, 1, s), lambda g_, i: (g_, 0, 0), memory_space=pltpu.VMEM)

    args = [q, k, v]
    in_specs = [fullspec, kspec, kspec]
    if bias_mode == "key":
        in_specs.append(
            pl.BlockSpec((G, 1, s), lambda g_, i: (g_, 0, 0),
                         memory_space=pltpu.VMEM)
        )
        args.append(bias)
    if has_mask:
        in_specs.append(
            pl.BlockSpec((G, s, bk), lambda g_, i: (g_, 0, i), memory_space=pltpu.VMEM)
        )
        args.append(mask)
    if use_prng:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    if has_offsets:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(offsets)
    in_specs += [fullspec, fullrow, fullrow]
    args += [g, lse, delta]

    out_specs = [
        pl.BlockSpec((G, s, d), lambda g_, i: (g_, 0, 0), memory_space=pltpu.VMEM),
        kspec,
        kspec,
    ]
    out_shapes = [
        jax.ShapeDtypeStruct((bh, s, d), jnp.float32),  # dq accumulator
        jax.ShapeDtypeStruct((bh, s, d), k.dtype),
        jax.ShapeDtypeStruct((bh, s, d), v.dtype),
    ]
    if want_dbias and bias_mode == "key":
        out_specs.append(
            pl.BlockSpec((G, 1, bk), lambda g_, i: (g_, 0, i),
                         memory_space=pltpu.VMEM)
        )
        out_shapes.append(jax.ShapeDtypeStruct((bh, 1, s), jnp.float32))

    outs = pl.pallas_call(
        _make_bwd_fused_kernel(
            sm_scale=sm_scale, causal=causal, dropout_prob=dropout_prob,
            bias_mode=bias_mode, use_prng=use_prng, has_mask=has_mask,
            has_offsets=has_offsets,
            want_dbias=want_dbias and bias_mode == "key",
            G=G, bq=bq, bk=bk, num_heads=num_heads, bias_dims=bias_dims,
        ),
        grid=(bh // G, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        name="flash_bwd",
        interpret=backend.interpret(),
    )(*args)
    dq = outs[0].astype(q.dtype)
    dk, dv = outs[1], outs[2]
    dbias = outs[3] if (want_dbias and bias_mode == "key") else None
    return dq, dk, dv, dbias


def _pick_group_bwd(bh, s, bq, d, full_bias):
    """Group size for the fused backward. Footprint model calibrated on
    v5e against Mosaic's scoped-vmem report (G=8/s=512 allocates 16.97M):
    full-length tensors (q, do double-buffered bf16; dq f32 revisited)
    cost ~16 B/elem, the four block tensors (k, v, dk, dv) ~16 B/elem of
    their bk-sized blocks, plus ~7MB of fixed score temporaries and the
    identity; keep the total under 14M of the 16M scoped limit."""
    if full_bias:
        return 1

    def fits(g):
        fulls = 16 * g * s * d
        blocks = 16 * g * min(s, bq) * d
        return fulls + blocks + 7 * 1024 * 1024 <= 14 * 1024 * 1024

    return _scan_groups(bh, fits)


def _flash_bwd(res, g, *, sm_scale, num_heads, causal, dropout_prob,
               bias_mode, bias_dims, want_dbias, g_lse=None):
    q, k, v, bias, mask, seed, offsets, o, lse = res
    bh, s, d = q.shape
    bq = bk = _pick_block(s)
    nq, nk = s // bq, s // bk
    use_prng = dropout_prob > 0.0 and mask is None
    has_mask = mask is not None and dropout_prob > 0.0
    has_offsets = offsets is not None
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)  # [BH,S]
    if g_lse is not None:
        # lse cotangent: d lse_i/d s_ij = P_ij, so ds gains +P*g_lse —
        # algebraically identical to subtracting g_lse from delta
        delta = delta - g_lse.astype(jnp.float32)
    delta = delta.reshape(bh, 1, s)

    if bias_mode != "full":
        Gb = _pick_group_bwd(bh, s, bq, d, False)
        return _bwd_fused(
            q, k, v, bias, mask, seed, offsets, g, lse, delta,
            sm_scale=sm_scale, num_heads=num_heads, causal=causal,
            dropout_prob=dropout_prob, bias_mode=bias_mode,
            bias_dims=bias_dims, want_dbias=want_dbias, G=Gb, bq=bq, bk=bk,
        )

    # ---- full-bias split path (the rare [B|1, nh|1, S, S] bias): its
    # per-bh row-map indexing pins G=1
    G = 1
    qspec = pl.BlockSpec((G, bq, d), lambda g_, i: (g_, i, 0), memory_space=pltpu.VMEM)
    fullspec = pl.BlockSpec((G, s, d), lambda g_, i: (g_, 0, 0), memory_space=pltpu.VMEM)
    rowspec = pl.BlockSpec(
        (G, 1, bq), lambda g_, i: (g_, 0, i), memory_space=pltpu.VMEM
    )
    fullrow = pl.BlockSpec(
        (G, 1, s), lambda g_, i: (g_, 0, 0), memory_space=pltpu.VMEM
    )

    dv_, md_ = _bias_row_map(bias_dims, num_heads)

    def bias_spec(rows_idx):
        return pl.BlockSpec(
            (1, bq, s) if rows_idx else (1, s, bk),
            (lambda g_, i, dv=dv_, md=md_: ((g_ // dv) % md, i, 0))
            if rows_idx
            else (lambda g_, i, dv=dv_, md=md_: ((g_ // dv) % md, 0, i)),
            memory_space=pltpu.VMEM,
        )

    statics = dict(
        sm_scale=sm_scale, causal=causal, dropout_prob=dropout_prob,
        bias_mode=bias_mode, use_prng=use_prng, has_mask=has_mask,
        has_offsets=has_offsets, G=G, bq=bq, bk=bk, num_heads=num_heads,
        bias_dims=bias_dims,
    )

    # ---- dq: grid over q blocks
    args = [q, k, v]
    in_specs = [qspec, fullspec, fullspec]
    if bias_mode:
        in_specs.append(bias_spec(True))
        args.append(bias)
    if has_mask:
        in_specs.append(
            pl.BlockSpec((G, bq, s), lambda g_, i: (g_, i, 0), memory_space=pltpu.VMEM)
        )
        args.append(mask)
    if use_prng:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    if has_offsets:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(offsets)
    in_specs += [qspec, rowspec, rowspec]
    args += [g, lse, delta]
    dq = pl.pallas_call(
        _make_bwd_dq_kernel(**statics),
        grid=(bh // G, nq),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        name="flash_bwd_dq",
        interpret=backend.interpret(),
    )(*args)

    # ---- dk/dv (+dbias): grid over k blocks
    kspec = pl.BlockSpec((G, bk, d), lambda g_, i: (g_, i, 0), memory_space=pltpu.VMEM)
    args2 = [q, k, v]
    in_specs2 = [fullspec, kspec, kspec]
    if bias_mode:
        in_specs2.append(bias_spec(False))
        args2.append(bias)
    if has_mask:
        in_specs2.append(
            pl.BlockSpec((G, s, bk), lambda g_, i: (g_, 0, i), memory_space=pltpu.VMEM)
        )
        args2.append(mask)
    if use_prng:
        in_specs2.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args2.append(seed)
    if has_offsets:
        in_specs2.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args2.append(offsets)
    in_specs2 += [fullspec, fullrow, fullrow]
    args2 += [g, lse, delta]

    out_specs2 = [kspec, kspec]
    out_shapes2 = [
        jax.ShapeDtypeStruct((bh, s, d), k.dtype),
        jax.ShapeDtypeStruct((bh, s, d), v.dtype),
    ]
    if want_dbias:
        out_specs2.append(
            pl.BlockSpec((1, s, bk), lambda g_, i: (g_, 0, i), memory_space=pltpu.VMEM)
        )
        out_shapes2.append(jax.ShapeDtypeStruct((bh, s, s), jnp.float32))

    outs = pl.pallas_call(
        _make_bwd_dkv_kernel(want_dbias=want_dbias, **statics),
        grid=(bh // G, nk),
        in_specs=in_specs2,
        out_specs=out_specs2,
        out_shape=out_shapes2,
        name="flash_bwd_dkv",
        interpret=backend.interpret(),
    )(*args2)
    dk, dv = outs[0], outs[1]

    # reduce dbias grid cells that shared one broadcast row
    dbias = None
    if want_dbias:
        bb, bn = bias_dims
        batch = bh // num_heads
        db = outs[2].reshape(batch, num_heads, s, s)
        if bn == 1 and num_heads > 1:
            db = db.sum(axis=1, keepdims=True)
        if bb == 1 and batch > 1:
            db = db.sum(axis=0, keepdims=True)
        dbias = db.reshape(bb * bn, s, s)
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# core with custom VJP (created per call; closes over static config)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _make_flash_core(*, sm_scale, num_heads, causal, dropout_prob, bias_mode,
                     bias_dims, want_dbias):
    """Cached per static config: eager callers reuse the same custom_vjp
    (and therefore JAX's trace/lowering caches) across calls."""
    statics = dict(
        sm_scale=sm_scale, num_heads=num_heads, causal=causal,
        dropout_prob=dropout_prob, bias_mode=bias_mode, bias_dims=bias_dims,
    )

    @jax.custom_vjp
    def core(q, k, v, bias, mask, seed, offsets):
        o, _ = _flash_fwd(q, k, v, bias, mask, seed, offsets, **statics)
        return o

    def core_fwd(q, k, v, bias, mask, seed, offsets):
        o, lse = _flash_fwd(q, k, v, bias, mask, seed, offsets, **statics)
        # checkpoint_name so a surrounding jax.checkpoint with a
        # save_only_these_names policy can keep (o, lse) and let the
        # recompute pass DCE the forward kernel while q/k/v come back from
        # the (cheap) projection matmuls — the long-context remat story
        o = checkpoint_name(o, "flash_o")
        lse = checkpoint_name(lse, "flash_lse")
        return o, (q, k, v, bias, mask, seed, offsets, o, lse)

    def core_bwd(res, g):
        dq, dk, dv, dbias = _flash_bwd(
            res, g, want_dbias=want_dbias, **statics
        )
        if res[3] is not None and dbias is None:
            # bias_requires_grad=False: zero cotangent (padding masks)
            dbias = jnp.zeros_like(res[3])
        elif dbias is not None:
            dbias = dbias.astype(res[3].dtype)
        return (dq, dk, dv, dbias, None, None, None)

    core.defvjp(core_fwd, core_bwd)
    return core


# ---------------------------------------------------------------------------
# public entry: [B, nh, S, D] with bias/causal/dropout/SPMD
# ---------------------------------------------------------------------------


def _classify_bias(bias, b, nh, s):
    """Returns (bias_kernel, bias_mode, (bb, bn)).

    'key' mode pre-broadcasts the user's [B|1,1,1,S] padding mask to
    [B*nh, S] f32 with plain traced ops — dbias (returned in that shape)
    flows back to the user shape through ordinary autodiff (the
    broadcast transposes to a sum over heads/batch). 'full' mode keeps
    [R, S, S] rows with in-kernel row mapping (R = bb*bn)."""
    if bias is None:
        return None, None, None
    if bias.ndim != 4:
        raise ValueError(f"flash_attention bias must be 4-D, got {bias.shape}")
    bb, bn, bq, bk = bias.shape
    if bb not in (1, b) or bn not in (1, nh):
        raise ValueError(
            f"bias dims {bias.shape} not broadcastable to batch={b}, heads={nh}"
        )
    if bk != s:
        raise ValueError(f"bias key dim {bk} != seq {s}")
    if bn == 1 and bq == 1:
        bkey = jnp.broadcast_to(
            bias.astype(jnp.float32).reshape(bb, 1, s), (b, nh, s)
        ).reshape(b * nh, 1, s)
        return bkey, "key", (bb, 1)
    if bq != s:
        raise ValueError(f"bias query dim {bq} != seq {s}")
    b3 = bias.reshape(bb * bn, s, s)
    return b3, "full", (bb, bn)


def _bias_row_map(bias_dims, num_heads):
    """(div, mod) such that full-bias row = (bh // div) % mod."""
    bb, bn = bias_dims
    return (num_heads if bn == 1 else 1), bb * bn


def _flash_local(q, k, v, bias, mask, seed, *, sm_scale, causal, dropout_prob,
                 bias_requires_grad):
    """[B, nh, S, D] local (per-shard) flash attention."""
    b, nh, s, d = q.shape
    biask, bias_mode, bias_dims = _classify_bias(bias, b, nh, s)
    mask3 = mask.reshape(b * nh, s, s) if mask is not None else None
    qf = q.reshape(b * nh, s, d)
    kf = k.reshape(b * nh, s, d)
    vf = v.reshape(b * nh, s, d)
    core = _make_flash_core(
        sm_scale=float(sm_scale), num_heads=nh, causal=causal,
        dropout_prob=dropout_prob, bias_mode=bias_mode, bias_dims=bias_dims,
        want_dbias=bias_requires_grad and bias_mode is not None,
    )
    o = core(qf, kf, vf, biask, mask3, seed, None)
    return o.reshape(b, nh, s, d)


def flash_attention(q, k, v, bias=None, sm_scale=None, causal=False,
                    dropout_prob=0.0, dropout_key=None, dropout_seed=None,
                    bias_requires_grad=False, mesh=None, batch_axis="dp",
                    head_axis="tp"):
    """Flash attention with optional bias, causal mask, dropout and SPMD.

    q, k, v: [B, nh, S, D]. bias: additive, [B,1,1,S] (per-key padding
    mask) or [B|1, nh|1, S, S]. Returns [B, nh, S, D].

    dropout: `dropout_prob` with either `dropout_key` (a jax PRNG key) or
    `dropout_seed` (int32 scalar). On TPU the mask comes from the in-kernel
    hardware PRNG; in interpret mode (CPU) it is precomputed host-side.

    bias_requires_grad=False returns zero cotangent for the bias (the
    padding-mask case); set True to compute the real dbias.

    mesh: wrap in shard_map over (batch_axis, head_axis) if present —
    batch sharded on dp, heads on tp (megatron attention).
    """
    b, nh, s, d = q.shape
    if s % MIN_BLOCK != 0:
        raise ValueError(f"flash_attention needs seq % {MIN_BLOCK} == 0, got {s}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    seed = None
    mask = None
    if dropout_prob > 0.0:
        if dropout_seed is not None:
            seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
        elif dropout_key is not None:
            seed = jax.random.randint(
                dropout_key, (1,), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
            )
        else:
            raise ValueError("dropout needs dropout_key or dropout_seed")
        if backend.interpret():
            # CPU tests: TPU hardware PRNG is unavailable in interpret
            # mode; draw the mask host-side (same numerator-only math)
            mkey = dropout_key if dropout_key is not None else jax.random.PRNGKey(
                seed[0]
            )
            mask = jax.random.bernoulli(
                jax.random.fold_in(mkey, 7), 1.0 - dropout_prob, (b, nh, s, s)
            ).astype(jnp.uint8)

    kwargs = dict(
        sm_scale=sm_scale, causal=causal, dropout_prob=dropout_prob,
        bias_requires_grad=bias_requires_grad,
    )

    axes = [
        ax for ax in (batch_axis, head_axis)
        if mesh is not None and ax in mesh.axis_names and mesh.shape[ax] > 1
    ]
    if not axes:
        return _flash_local(q, k, v, bias, mask, seed, **kwargs)

    from jax.sharding import PartitionSpec as P

    ba = batch_axis if batch_axis in axes else None
    ha = head_axis if head_axis in axes else None
    qspec = P(ba, ha, None, None)

    def spec_for(x):
        if x is None:
            return None
        return P(
            ba if x.shape[0] != 1 else None,
            ha if x.shape[1] != 1 else None,
            None,
            None,
        )

    bias_spec = spec_for(bias)
    mask_spec = P(ba, ha, None, None) if mask is not None else None

    def body(ql, kl, vl, bl, ml, sl):
        local_seed = sl
        if sl is not None:
            import jax.lax as lax

            salt = jnp.int32(0)
            if ba:
                salt = salt + lax.axis_index(ba) * jnp.int32(0x632BE59B)
            if ha:
                salt = salt + lax.axis_index(ha) * jnp.int32(0x1B873593)
            local_seed = sl + salt
        out = _flash_local(ql, kl, vl, bl, ml, local_seed, **kwargs)
        return out

    in_specs = (qspec, qspec, qspec, bias_spec, mask_spec, P() if seed is not None else None)
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=qspec,
        check_vma=False,
    )(q, k, v, bias, mask, seed)


@functools.lru_cache(maxsize=64)
def _make_flash_core_lse(*, sm_scale, num_heads, causal, dropout_prob,
                         bias_mode, bias_dims, want_dbias=False):
    """Like _make_flash_core but returns (o, lse [BH, S]) with a VJP that
    accepts cotangents for BOTH outputs (g_lse folds into delta). Built
    for ring attention, which merges per-block partials by lse."""
    statics = dict(
        sm_scale=sm_scale, num_heads=num_heads, causal=causal,
        dropout_prob=dropout_prob, bias_mode=bias_mode, bias_dims=bias_dims,
    )

    @jax.custom_vjp
    def core(q, k, v, bias, mask, seed, offsets):
        o, lse = _flash_fwd(q, k, v, bias, mask, seed, offsets, **statics)
        return o, lse.reshape(q.shape[0], q.shape[1])

    def core_fwd(q, k, v, bias, mask, seed, offsets):
        o, lse = _flash_fwd(q, k, v, bias, mask, seed, offsets, **statics)
        o = checkpoint_name(o, "flash_o")
        lse = checkpoint_name(lse, "flash_lse")
        return (o, lse.reshape(q.shape[0], q.shape[1])), (
            q, k, v, bias, mask, seed, offsets, o, lse,
        )

    def core_bwd(res, gs):
        g_o, g_lse = gs
        dq, dk, dv, dbias = _flash_bwd(
            res, g_o, want_dbias=want_dbias and bias_mode is not None,
            g_lse=g_lse, **statics
        )
        if res[3] is not None and dbias is None:
            dbias = jnp.zeros_like(res[3])
        elif dbias is not None:
            dbias = dbias.astype(res[3].dtype)
        return (dq, dk, dv, dbias, None, None, None)

    core.defvjp(core_fwd, core_bwd)
    return core


def flash_block_with_lse(q, k, v, key_bias=None, sm_scale=None,
                         bias_requires_grad=True, causal=False,
                         q_offset=None, k_offset=None,
                         dropout_prob=0.0, dropout_seed=None,
                         dropout_mask=None):
    """One attention block for ring attention: q/k/v [B, nh, S, D] local
    shards, key_bias [B, S] additive per-key bias (rotating with K).
    Returns (out [B, nh, S, D], lse [B, nh, S]) for log-sum-exp merging
    across ring steps.

    causal + (q_offset, k_offset): global positions of this shard's q
    rows / the visiting k block, as int32 scalars (traced values are
    fine — they ride in SMEM), so the ring's shifted blocks mask
    correctly. dropout: `dropout_seed` int32 scalar (the ring caller
    folds its step index in); in interpret mode pass `dropout_mask`
    [B, nh, S, S] uint8 instead. Bias gradients are computed by default,
    matching the jnp ring block math."""
    b, nh, s, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    biask = None
    bias_mode = None
    bias_dims = None
    if key_bias is not None:
        biask = jnp.broadcast_to(
            key_bias.astype(jnp.float32).reshape(b, 1, s), (b, nh, s)
        ).reshape(b * nh, 1, s)
        bias_mode, bias_dims = "key", (b, 1)
    offsets = None
    if causal and (q_offset is not None or k_offset is not None):
        offsets = jnp.stack([
            jnp.asarray(q_offset if q_offset is not None else 0, jnp.int32),
            jnp.asarray(k_offset if k_offset is not None else 0, jnp.int32),
        ])
    seed = None
    mask3 = None
    if dropout_prob > 0.0:
        if dropout_mask is not None:
            mask3 = dropout_mask.reshape(b * nh, s, s)
        elif dropout_seed is not None:
            seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
        else:
            raise ValueError("dropout needs dropout_seed or dropout_mask")
    core = _make_flash_core_lse(
        sm_scale=float(sm_scale), num_heads=nh, causal=causal,
        dropout_prob=dropout_prob, bias_mode=bias_mode, bias_dims=bias_dims,
        want_dbias=bias_requires_grad,
    )
    o, lse = core(
        q.reshape(b * nh, s, d), k.reshape(b * nh, s, d),
        v.reshape(b * nh, s, d), biask, mask3, seed, offsets,
    )
    return o.reshape(b, nh, s, d), lse.reshape(b, nh, s)


# ---------------------------------------------------------------------------
# BSH layout (transpose-free) kernels
# ---------------------------------------------------------------------------
#
# The [B, nh, S, D] layout above needs head-split/merge transposes around
# every kernel call; profiled on v5e (BERT-base s512/b48) those copies +
# their backward/recompute doubles cost ~30-45 ms/step — an order of
# magnitude more than the kernels themselves. These kernels read q/k/v
# exactly as the qkv projection produces them — [B, S, H] with H = nh*D
# — and slice each head's D lanes in-kernel with STATIC offsets (a
# static 64-lane slice lowers to plain vreg selects; measured FASTER
# than the pre-transposed layout even before counting the removed
# copies). Rectangular attention (S_q != S_kv, the NMT cross-attention
# shape) falls out for free because q and k/v carry separate lengths.
#
# Capabilities: per-key additive bias [B, 1, S_kv] (no dbias — padding
# masks), causal with (q_offset, k_offset), in-kernel dropout (the BHSD
# kernels' quantized-byte scheme, keep 230/256 at p = 0.1, bh = b*nh+h,
# reproducible across fwd/bwd: the whole-tile kernels seed the hardware
# PRNG per block and tile both passes alike, the stream kernels hash
# the seed and the absolute position, whatever tiles each pass runs).
# Full [.., S, S] bias and dbias stay on the BHSD path.


def _prescale_ok(sm_scale) -> bool:
    """Fold sm_scale into q BEFORE the qk dot when it is a power of two
    (d = 64/256 -> 1/8, 1/16): a bf16 exponent shift is EXACT, and it
    deletes one [BQ, BK] f32 multiply per (head, k-block) from the
    VPU-bound softmax pipeline. Non-pow2 scales (d=128) keep the
    per-block multiply — prescaling would perturb every logit by the
    bf16 rounding of the scale."""
    import math

    return math.frexp(float(sm_scale))[0] == 0.5


def _bsh_kernel_name(direction: str, causal: bool, form: str = "bsh") -> str:
    """`pallas_call(name=)` of a BSH call: `flash_bsh_fwd` / `flash_bsh_bwd`
    over the full score square, `flash_bsh_causal_fwd` / `_bwd` where the
    kernel skips the blocks above the diagonal. The benchmark finds a call
    by this name and counts its work by it (`benchmark/kernels/<name>.py`):
    the square's count over a causal call's time would read up to twice
    its true share of the roofline. `form` "mla" names the calls that
    latent attention makes on heads padded to a kernel width
    (`flash_mla_causal_fwd`): their useful work is the unpadded heads'.
    "mla_wide" names those it makes on heads that are a kernel width as
    they stand (`flash_mla_wide_causal_fwd`, 256 and 256): a call's
    shapes are then its work, one call a group of heads."""
    return f"flash_{form}_{'causal_' if causal else ''}{direction}"


# Two bodies share these names. Up to S = 512 a head's whole score tile
# is one step of work, and the WHOLE-TILE kernels below do it as one
# straight-line block (DMA tile = compute tile); from S = 1024 the
# STREAM kernels further down run compute tiles inside the DMA tile in
# a rolled, software-pipelined loop. _bsh_streams says which.

def _make_fwd_bsh_tile_kernel(*, sm_scale, causal, dropout_prob, has_bias,
                         use_prng, has_mask, has_offsets, nh, d, bq, bk,
                         prescale=False):
    def kernel(*refs):
        it = iter(refs)
        q_ref = next(it)          # [1, BQ, H]
        k_ref = next(it)          # [1, Skv, H]
        v_ref = next(it)          # [1, Skv, H]
        bias_ref = next(it) if has_bias else None   # [1, 1, Skv]
        mask_ref = next(it) if has_mask else None   # [1, nh, BQ, Skv]
        seed_ref = next(it) if use_prng else None
        off_ref = next(it) if has_offsets else None
        o_ref = next(it)          # [1, BQ, H]
        lse_ref = next(it)        # [1, nh, BQ]

        b = pl.program_id(0)
        qi = pl.program_id(1)
        skv = k_ref.shape[1]
        nk = skv // bk
        keep_prob = 1.0 - dropout_prob
        keep_div = (
            _dropout_quantized_keep(keep_prob) if use_prng else keep_prob
        )
        q_off = off_ref[0] if has_offsets else 0
        k_off = off_ref[1] if has_offsets else 0
        ident = backend.identity(bq)
        hi = _hi_blocks(causal, qi, bq, bk, nk, q_off, k_off)

        for h in range(nh):
            q = q_ref[0, :, h * d:(h + 1) * d]   # [BQ, D] static lanes
            if prescale:
                q = q * jnp.asarray(sm_scale, q.dtype)
            bh = b * nh + h

            def body(i, carry, h=h, q=q, bh=bh):
                m, l, acc = carry
                k = k_ref[0, pl.ds(i * bk, bk), h * d:(h + 1) * d]
                v = v_ref[0, pl.ds(i * bk, bk), h * d:(h + 1) * d]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if not prescale:
                    s = s * sm_scale
                if has_bias:
                    s = s + bias_ref[0, 0, pl.ds(i * bk, bk)][None, :]
                if causal:
                    s = _causal_mask(
                        s, q_off + qi * bq, k_off + i * bk, bq, bk
                    )
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
                p_num = p
                if dropout_prob > 0.0:
                    if use_prng:
                        keep = _dropout_keep(
                            seed_ref, bh, qi, i, keep_prob, bq, bk
                        )
                    else:
                        keep = mask_ref[0, h, :, pl.ds(i * bk, bk)] != 0
                    p_num = jnp.where(keep, p / keep_div, 0.0)
                acc = acc * alpha + jax.lax.dot_general(
                    p_num.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                return m_new, l, acc

            m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((bq, 1), jnp.float32)
            acc0 = jnp.zeros((bq, d), jnp.float32)
            m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
            l_safe = jnp.maximum(l, 1e-30)
            o_ref[0, :, h * d:(h + 1) * d] = (acc / l_safe).astype(o_ref.dtype)
            lse_ref[0, h:h + 1, :] = backend.to_lanes(
                m + jnp.log(l_safe), ident)

    return kernel


def _flash_fwd_bsh_tile(q, k, v, bias, mask, seed, offsets, *, sm_scale, nh,
                   causal, dropout_prob, form="bsh"):
    b, sq, hdim = q.shape
    skv = k.shape[1]
    d = hdim // nh
    use_prng = dropout_prob > 0.0 and mask is None
    bq, bk, vmem_limit = _resolve_bsh_blocks(sq, skv, hdim)
    has_mask = mask is not None and dropout_prob > 0.0
    has_offsets = offsets is not None
    has_bias = bias is not None

    in_specs = [
        pl.BlockSpec((1, bq, hdim), lambda b_, i: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, skv, hdim), lambda b_, i: (b_, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, skv, hdim), lambda b_, i: (b_, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, skv), lambda b_, i: (b_, 0, 0),
                         memory_space=pltpu.VMEM))
        args.append(bias)
    if has_mask:
        in_specs.append(
            pl.BlockSpec((1, nh, bq, skv), lambda b_, i: (b_, 0, i, 0),
                         memory_space=pltpu.VMEM))
        args.append(mask)
    if use_prng:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    if has_offsets:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(offsets)

    kernel = _make_fwd_bsh_tile_kernel(
        sm_scale=sm_scale, causal=causal, dropout_prob=dropout_prob,
        has_bias=has_bias, use_prng=use_prng, has_mask=has_mask,
        has_offsets=has_offsets, nh=nh, d=d, bq=bq, bk=bk,
        prescale=_prescale_ok(sm_scale),
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, sq // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, hdim), lambda b_, i: (b_, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nh, bq), lambda b_, i: (b_, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hdim), q.dtype),
            jax.ShapeDtypeStruct((b, nh, sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit),
        name=_bsh_kernel_name("fwd", causal, form),
        interpret=backend.interpret(),
    )(*args)
    return o, lse


def _make_bwd_bsh_tile_kernel(*, sm_scale, causal, dropout_prob, has_bias,
                         use_prng, has_mask, has_offsets, nh, d, bq, bk,
                         prescale=False):
    """Single-pass BSH backward: grid (B, NKv) with NKv innermost per
    batch row. Computes dk/dv for this k block and accumulates dq into a
    revisited f32 output block (index constant in ki -> stays resident;
    zeroed at ki == 0)."""

    def kernel(*refs):
        it = iter(refs)
        q_ref = next(it)          # [1, Sq, H]
        k_ref = next(it)          # [1, BK, H]
        v_ref = next(it)          # [1, BK, H]
        bias_ref = next(it) if has_bias else None   # [1, 1, Skv]
        mask_ref = next(it) if has_mask else None   # [1, nh, Sq, BK]
        seed_ref = next(it) if use_prng else None
        off_ref = next(it) if has_offsets else None
        do_ref = next(it)         # [1, Sq, H]
        lse_ref = next(it)        # [1, nh, Sq]
        delta_ref = next(it)      # [1, nh, Sq]
        dq_ref = next(it)         # [1, Sq, H] f32, revisited across ki
        dk_ref = next(it)         # [1, BK, H]
        dv_ref = next(it)         # [1, BK, H]

        b = pl.program_id(0)
        ki = pl.program_id(1)
        sq = q_ref.shape[1]
        nq = sq // bq
        keep_prob = 1.0 - dropout_prob
        keep_div = (
            _dropout_quantized_keep(keep_prob) if use_prng else keep_prob
        )
        q_off = off_ref[0] if has_offsets else 0
        k_off = off_ref[1] if has_offsets else 0
        ident = backend.identity(bq)

        @pl.when(ki == 0)
        def _init():
            dq_ref[...] = jnp.zeros_like(dq_ref)

        lo = _lo_blocks(causal, ki, bq, bk, nq, q_off, k_off)
        for h in range(nh):
            k = k_ref[0, :, h * d:(h + 1) * d]   # [BK, D]
            v = v_ref[0, :, h * d:(h + 1) * d]
            bh = b * nh + h
            if has_bias:
                b_block = bias_ref[0, 0, pl.ds(ki * bk, bk)]

            def body(i, carry, h=h, k=k, v=v, bh=bh):
                dk, dv = carry
                q = q_ref[0, pl.ds(i * bq, bq), h * d:(h + 1) * d]
                if prescale:
                    # exact pow2 shift; dk = ds_nos^T @ q_pre is then
                    # ALREADY chain-rule scaled, and dq accumulates
                    # unscaled ds_nos @ k with ONE final scale pass —
                    # both per-block [BQ,BK] sm_scale multiplies gone
                    q = q * jnp.asarray(sm_scale, q.dtype)
                do = do_ref[0, pl.ds(i * bq, bq), h * d:(h + 1) * d]
                lse = backend.to_sublanes(
                    lse_ref[0, h:h + 1, pl.ds(i * bq, bq)], ident
                )
                delta = backend.to_sublanes(
                    delta_ref[0, h:h + 1, pl.ds(i * bq, bq)], ident
                )
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if not prescale:
                    s = s * sm_scale
                if has_bias:
                    s = s + b_block[None, :]
                if causal:
                    s = _causal_mask(
                        s, q_off + i * bq, k_off + ki * bk, bq, bk
                    )
                p = jnp.exp(s - lse)
                dp = jax.lax.dot_general(
                    do, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if dropout_prob > 0.0:
                    if use_prng:
                        keep = _dropout_keep(
                            seed_ref, bh, i, ki, keep_prob, bq, bk
                        )
                    else:
                        keep = mask_ref[0, h, pl.ds(i * bq, bq), :] != 0
                    c = jnp.where(keep, 1.0 / keep_div, 0.0)
                    p_num = p * c
                else:
                    c = 1.0
                    p_num = p
                dv = dv + jax.lax.dot_general(
                    p_num.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if prescale:
                    ds = (p * (dp * c - delta)).astype(q.dtype)
                else:
                    ds = (p * (dp * c - delta) * sm_scale).astype(q.dtype)
                dk = dk + jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dq_ref[0, pl.ds(i * bq, bq), h * d:(h + 1) * d] += (
                    jax.lax.dot_general(
                        ds, k, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )
                return dk, dv

            dk0 = jnp.zeros((bk, d), jnp.float32)
            dv0 = jnp.zeros((bk, d), jnp.float32)
            dk, dv = jax.lax.fori_loop(lo, nq, body, (dk0, dv0))
            dk_ref[0, :, h * d:(h + 1) * d] = dk.astype(dk_ref.dtype)
            dv_ref[0, :, h * d:(h + 1) * d] = dv.astype(dv_ref.dtype)

        if prescale:
            # dq accumulated UNSCALED ds @ k across every ki: apply the
            # chain-rule sm_scale once, on the resident f32 buffer,
            # after the last k block of this batch row
            @pl.when(ki == pl.num_programs(1) - 1)
            def _scale_dq():
                dq_ref[...] = dq_ref[...] * sm_scale

    return kernel


def _flash_bwd_bsh_tile(res, g, *, sm_scale, nh, causal, dropout_prob,
                        form="bsh"):
    q, k, v, bias, mask, seed, offsets, o, lse = res
    b, sq, hdim = q.shape
    skv = k.shape[1]
    d = hdim // nh
    use_prng = dropout_prob > 0.0 and mask is None
    bq, bk, vmem_limit = _resolve_bsh_blocks(sq, skv, hdim, bwd=True)
    has_mask = mask is not None and dropout_prob > 0.0
    has_offsets = offsets is not None
    has_bias = bias is not None

    # delta[b, h, s] = sum_d o*g per head, from the BSH layout
    delta = (
        (o.astype(jnp.float32) * g.astype(jnp.float32))
        .reshape(b, sq, nh, d).sum(axis=-1).transpose(0, 2, 1)
    )

    fullq = pl.BlockSpec((1, sq, hdim), lambda b_, i: (b_, 0, 0),
                        memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, bk, hdim), lambda b_, i: (b_, i, 0),
                         memory_space=pltpu.VMEM)
    statspec = pl.BlockSpec((1, nh, sq), lambda b_, i: (b_, 0, 0),
                            memory_space=pltpu.VMEM)

    args = [q, k, v]
    in_specs = [fullq, kspec, kspec]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, skv), lambda b_, i: (b_, 0, 0),
                         memory_space=pltpu.VMEM))
        args.append(bias)
    if has_mask:
        in_specs.append(
            pl.BlockSpec((1, nh, sq, bk), lambda b_, i: (b_, 0, 0, i),
                         memory_space=pltpu.VMEM))
        args.append(mask)
    if use_prng:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    if has_offsets:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(offsets)
    in_specs += [fullq, statspec, statspec]
    args += [g, lse, delta]

    dq, dk, dv = pl.pallas_call(
        _make_bwd_bsh_tile_kernel(
            sm_scale=sm_scale, causal=causal, dropout_prob=dropout_prob,
            has_bias=has_bias, use_prng=use_prng, has_mask=has_mask,
            has_offsets=has_offsets, nh=nh, d=d, bq=bq, bk=bk,
            prescale=_prescale_ok(sm_scale),
        ),
        grid=(b, skv // bk),
        in_specs=in_specs,
        out_specs=[fullq, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hdim), jnp.float32),
            jax.ShapeDtypeStruct((b, skv, hdim), k.dtype),
            jax.ShapeDtypeStruct((b, skv, hdim), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit),
        name=_bsh_kernel_name("bwd", causal, form),
        interpret=backend.interpret(),
    )(*args)
    return dq.astype(q.dtype), dk, dv



# Two-level tiling (the stream kernels). The DMA tile (bq rows of q / o /
# lse in the forward, bk rows of k / v / dk / dv in the backward) is what
# _resolve_bsh_blocks picks; inside it the arithmetic runs over compute
# tiles in ONE rolled loop a grid cell, so the traced body and Mosaic's
# compile time are those of a trip of that loop whatever the DMA tile is
# (the whole-tile body at bq = bk = 1024 keeps 4 MB intermediates where
# the core has 64 vregs: nine in ten of its vector stores are spills,
# and it takes 27 s to compile).
#
# A compute tile is held TRANSPOSED, ck <= 512 keys on the sublanes and
# cq <= 512 queries on the lanes: s^T = k q^T is [ck, cq]. The per-query
# statistics (m, l, lse, delta) are then [1, cq] lane-major rows, cq / 128
# vregs each and the layout lse / delta have in HBM, instead of [cq, 1]
# columns of cq / 8 vregs; the softmax reductions run down the sublanes,
# elementwise across vregs; acc^T [d, cq] fills its vregs at d = 64. The
# four 128-lane blocks of a 512-query tile are the four weight tiles of
# a product, one an MXU, with no weight pushed twice.
#
# Heads are taken in lane groups of 128 // d (two at d = 64): a group's
# q / k / v / o slab is a 128-aligned lane window, which a rolled loop
# can index, so the traced body is one group's. A head contracts the
# whole slab against an operand zeroed outside its own d rows or lanes —
# the MXU's contraction is 128 deep whatever d is — and no 64-lane
# slice, rotate or masked store is left in the loop.
#
# What the tiles need transposed (V, q, do, k going in; o, dk, dv, dq
# coming out) is transposed a grid cell or a batch row at a time, on the
# MXU (_mxu_t).
_CQ = 512
_CK = 512
# steps of the stream traced into one trip of its rolled loop. Their
# scores / probabilities cross from step to step in two VMEM slots that
# must alternate statically, so two; four measured no faster (17.91
# against 17.57 ms a layer at s4096, my chip run, PR 27)
_STEPS = 2


def _compute_tile(nq, nk, d):
    """(cq, ck) of a compute tile: cq queries on the lanes, ck keys on
    the sublanes, each the largest of 512 / 256 / 128 that tiles the nq
    queries and nk keys a grid cell holds (the forward: its bq block
    and all of Skv; the backward: all of Sq and its bk block)."""
    del d

    def largest(n, cap):
        return next(c for c in (512, 256, 128) if c <= cap and n % c == 0)

    return largest(nq, _CQ), largest(nk, _CK)


def _head_groups(nh, d):
    """(heads a lane group, groups). Groups are 128 lanes wide where
    heads pair up into that; otherwise every head is its own group at
    a static lane offset."""
    hp = MIN_BLOCK // d if d < MIN_BLOCK else 1
    if hp > 1 and nh % hp:
        hp = 1
    return hp, nh // hp


def _own_lanes(x, hh, hp, d):
    """x [n, hp * d] with every lane outside head hh's d lanes zeroed."""
    if hp == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    own = (lane >= hh * d) & (lane < (hh + 1) * d)
    return jnp.where(own, x, jnp.zeros_like(x))


def _eye(n, dtype, hh=0, hp=1):
    """[n, n] identity; with hp > 1 only head hh's n / hp rows of it."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    own = r == c
    if hp > 1:
        d = n // hp
        own = own & (r >= hh * d) & (r < (hh + 1) * d)
    return own.astype(dtype)


def _mxu_t(x, eye=None):
    """x^T of x [m, n], 128 lanes of x at a time, as eye @ x^T on the
    MXU (the transposed weight push q k^T uses): exact for the 16-bit
    operands and results it is used on. The XLU's transpose holds the
    core ~150 cycles a 128 x 128 block, which at S = 512, where a grid
    cell is short, was a third of a kernel; this is a pass of an idle
    MXU. eye: what _eye(min(n, 128)) gives, for a caller that has it."""
    m, n = x.shape
    w = min(n, MIN_BLOCK)
    if eye is None:
        eye = _eye(w, x.dtype)
    blocks = [
        jax.lax.dot_general(
            eye, x[:, j:j + w], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        for j in range(0, n, w)]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=0)


_MIX_A = 0x7FEB352D
_MIX_B = 0x846CA68B - (1 << 32)


def _mix32(x):
    """lowbias32 (Wellons): a bijection of int32 with full avalanche;
    on the scalar core here (two native u32 multiplies)."""
    srl = jax.lax.shift_right_logical
    x = x ^ srl(x, jnp.int32(16))
    x = x * jnp.int32(_MIX_A)
    x = x ^ srl(x, jnp.int32(15))
    x = x * jnp.int32(_MIX_B)
    return x ^ srl(x, jnp.int32(16))


def _dropout_keep_t(seed, bh, k0, q0, keep_prob, ck, cq):
    """[ck, cq] keep mask (keys on sublanes) of the tile whose first key
    and query sit at ABSOLUTE positions k0, q0 of head-row bh.

    A counter-based draw: the mask of (bh, key, query) is a pure function
    of the seed and of that position in the whole [Sq, Skv] matrix, never
    of the DMA partition, of the compute tile or of the order tiles are
    visited in, so forward and backward regenerate the same mask whatever
    tiles each runs. (Seeding the hardware PRNG costs ~150 dependent
    vector operations a seed; this costs 7 a word and keeps no state.)
    One 32-bit word feeds FOUR mask bytes: word (a, query) of the
    128-key group g covers keys g*128 + j*32 + a, j the byte. Byte j is
    moved to the top of its word and compared, signed, with
    t * 2**24 - 2**31: true for exactly t of the 256 byte values, with
    no mask-to-[0, 255] step."""
    t = _dropout_quantized_thresh(keep_prob)
    if t >= 256:
        return jnp.ones((ck, cq), jnp.bool_)
    thresh = jnp.int32(t * 2**24 - 2**31)
    srl = jax.lax.shift_right_logical
    a = jax.lax.broadcasted_iota(jnp.int32, (MIN_BLOCK // 4, cq), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (MIN_BLOCK // 4, cq), 1)
    n = (q0 + lane) * jnp.int32(MIN_BLOCK // 4) + a
    parts = []
    for gi in range(ck // MIN_BLOCK):
        g = k0 // MIN_BLOCK + gi
        base = seed + bh * jnp.int32(_SEED_BH) + g * jnp.int32(_SEED_KI)
        key1 = _mix32(base)
        key2 = _mix32(base ^ jnp.int32(_SEED_QI))
        x = (n ^ key1) * jnp.int32(_MIX_A)
        x = x ^ srl(x, jnp.int32(15))
        x = (x + key2) * jnp.int32(_MIX_B)
        x = x ^ srl(x, jnp.int32(16))
        parts += [
            (x << jnp.int32(24 - 8 * j) if j < 3 else x) < thresh
            for j in range(4)
        ]
    return jnp.concatenate(parts, axis=0)


def _causal_mask_t(s, qglob, kglob):
    """_causal_mask for a transposed [ck, cq] tile."""
    kpos = kglob + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    qpos = qglob + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _bias_columns(bias_ref, bcol_ref, k0, nk):
    """Per-key bias, lane-major in bias_ref[0, 0, k0 + ...], into
    bcol_ref [nk * 128, cq]: key on the sublane, replicated along the
    lanes — the form a transposed score tile adds. An exact XLU
    transpose of the sublane-broadcast row, once a grid cell."""
    n = MIN_BLOCK
    reps = bcol_ref.shape[1] // n

    def body(j, carry):
        row = bias_ref[0, :, pl.ds(pl.multiple_of(k0 + j * n, n), n)]
        col = jnp.broadcast_to(row, (n, n)).T
        for i in range(reps):
            bcol_ref[pl.ds(pl.multiple_of(j * n, n), n),
                     i * n:(i + 1) * n] = col
        return carry

    jax.lax.fori_loop(0, nk, body, 0)


def _window(i, n):
    if isinstance(i, int):
        return slice(i * n, (i + 1) * n)
    return pl.ds(pl.multiple_of(i * n, n), n)


class _LaneGroups:
    """The lane groups of a stream kernel's heads (_head_groups). Groups
    that are 128-aligned lane windows are indexed dynamically, the group
    a value of the loop that walks them; otherwise (an odd head count at
    d = 64) every loop over groups is a static one and `lanes` answers
    for the group being traced."""

    def __init__(self, nh, d):
        self.hp, self.ng = _head_groups(nh, d)
        self.gw = self.hp * d
        self.dynamic = self.gw % MIN_BLOCK == 0
        self._static = None

    def lanes(self, g):
        return _window(g if self.dynamic else self._static, self.gw)

    def each(self, fn, n):
        """fn(g, i) for every group g and i < n."""
        if self.dynamic:
            def body(j, carry):
                fn(j // n, j % n)
                return carry

            jax.lax.fori_loop(0, self.ng * n, body, 0)
            return
        for g in range(self.ng):
            self._static = g

            def body(i, carry, g=g):
                fn(g, i)
                return carry

            jax.lax.fori_loop(0, n, body, 0)

    def streams(self, run):
        """run(first group, groups): one stream over all the groups, or
        one a group at its static lanes."""
        if self.dynamic:
            run(0, self.ng)
            return
        for g in range(self.ng):
            self._static = g
            run(g, 1)


def _tile_stream(g0, ng, hp, n_outer, inner_range, step, finish, init):
    """Run step(j, cur, prv, nxt, flags, carry) -> carry over every
    compute step of lane groups g0 .. g0 + ng - 1 as ONE rolled loop of
    _STEPS steps a trip: groups outermost, then the n_outer windows o of
    the kernel's DMA block, then the hp heads of a group, then the
    windows inner_range(o) = (lo, hi) of the resident side. A step's
    index is (g, o, hh, i); prv / nxt are its neighbours' (of another
    head, window or group at a segment's ends) and j its static place in
    the trip. flags = (live, live_nxt, fresh, fresh_prv): the count is
    rounded up to whole trips and a step past the last is not live and
    must change nothing; fresh says the step opens a segment (g, o, hh).
    finish(j, seg) runs after the trip whose step j opened a new segment
    and so closed seg, the one before. Returns (last step's index,
    whether it opened its segment, carry)."""
    zero = jnp.int32(0)

    def succ(idx):
        g, o, hh, i = idx
        i = i + 1
        wrap = i >= inner_range(o)[1]
        hh = jnp.where(wrap, hh + 1, hh)
        wrap_h = hh >= hp
        hh = jnp.where(wrap_h, 0, hh)
        o = jnp.where(wrap_h, o + 1, o)
        wrap_o = o >= n_outer
        o = jnp.where(wrap_o, 0, o)
        g = jnp.where(wrap_o, g + 1, g)
        return g, o, hh, jnp.where(wrap, inner_range(o)[0], i)

    def count_steps(o, n):
        lo, hi = inner_range(o)
        return n + (hi - lo)

    total = jax.lax.fori_loop(0, n_outer, count_steps, zero) * (hp * ng)
    start = (jnp.int32(g0), zero, zero, inner_range(zero)[0])

    def trip(i, state):
        prv, cur, fresh_prv, carry = state
        closed = []
        for j in range(_STEPS):
            t = i * _STEPS + j
            live = t < total
            live_nxt = t + 1 < total
            # past the end the index stays on the last step
            nxt = tuple(jnp.where(live_nxt, a, b)
                        for a, b in zip(succ(cur), cur))
            fresh = live & (
                (t == 0) | (cur[0] != prv[0]) | (cur[1] != prv[1])
                | (cur[2] != prv[2]))
            carry = step(j, cur, prv, nxt,
                         (live, live_nxt, fresh, fresh_prv), carry)
            closed.append((fresh & (t > 0), prv))
            # a dead step repeats the last live one's index, whose
            # segment it continues (with nothing)
            prv = tuple(jnp.where(live, a, b) for a, b in zip(cur, prv))
            fresh_prv = fresh
            cur = nxt
        for j, (closes, seg) in enumerate(closed):
            @pl.when(closes)
            def _close(j=j, seg=seg):
                finish(j, seg)
        return prv, cur, fresh_prv, carry

    trips = (total + _STEPS - 1) // _STEPS
    last, _, fresh_last, carry = jax.lax.fori_loop(
        0, trips, trip, (start, start, jnp.bool_(True), init))
    return last, fresh_last, carry


def _make_fwd_bsh_stream_kernel(*, sm_scale, causal, dropout_prob, has_bias,
                         use_prng, has_mask, has_offsets, nh, d, bq, cq, ck,
                         prescale=False):
    groups = _LaneGroups(nh, d)
    hp, gw, lanes_of = groups.hp, groups.gw, groups.lanes

    def kernel(*refs):
        it = iter(refs)
        q_ref = next(it)          # [1, BQ, H]
        k_ref = next(it)          # [1, Skv, H]
        v_ref = next(it)          # [1, Skv, H]
        bias_ref = next(it) if has_bias else None   # [1, 1, Skv]
        mask_ref = next(it) if has_mask else None   # [1, nh, Skv, BQ]
        seed_ref = next(it) if use_prng else None
        off_ref = next(it) if has_offsets else None
        o_ref = next(it)          # [1, BQ, H]
        lse_ref = next(it)        # [1, nh, BQ]
        bcol_ref = next(it) if has_bias else None   # [Skv, CQ] f32
        vt_ref = next(it)         # [H, Skv]: V^T
        wq_ref = next(it)         # [nh * GW, BQ]: each head's padded q^T
        s_bufs = (next(it), next(it))   # [CK, CQ] f32 each
        p_bufs = (next(it), next(it))   # [CK, CQ], v's dtype
        acc_ref = next(it)        # [_STEPS, D, CQ] f32
        stat_ref = next(it)       # [_STEPS, 2, CQ] f32: closed m, l
        ot_ref = next(it)         # [GW, CQ] f32: a group's o^T

        b = pl.program_id(0)
        qi = pl.program_id(1)
        nkc = k_ref.shape[1] // ck
        nr = bq // cq
        keep_prob = 1.0 - dropout_prob
        keep_div = (
            _dropout_quantized_keep(keep_prob) if use_prng else keep_prob
        )
        q_off = off_ref[0] if has_offsets else 0
        k_off = off_ref[1] if has_offsets else 0

        def visible(r):
            """Key windows the query window r sees."""
            return _hi_blocks(
                causal, qi * nr + r, cq, ck, nkc, q_off, k_off)

        def keys_of(r):
            # at least one step a segment: a window that sees no key
            # (ring offsets) runs one fully masked step, see finish
            return 0, (jnp.maximum(visible(r), 1) if causal else nkc)

        if has_bias:
            _bias_columns(bias_ref, bcol_ref, 0, k_ref.shape[1] // MIN_BLOCK)

        def transpose_v(g, n):
            vt_ref[_window(g, gw), _window(n, ck)] = _mxu_t(
                v_ref[0, _window(n, ck), lanes_of(g)])

        def transpose_q(g, r):
            """A head's q^T, zero outside its own d rows of the group's
            slab (the MXU contracts the whole 128-lane slab of K), once
            a grid cell: wq_ref[head * GW ..., queries]."""
            q = q_ref[0, _window(r, cq), lanes_of(g)]     # [CQ, GW]
            if prescale:
                q = q * jnp.asarray(sm_scale, q.dtype)
            for hh in range(hp):
                # a head that is its own group transposes whole, 128
                # lanes at a time (d = 256: two blocks)
                wq_ref[_window(g * hp + hh, gw), _window(r, cq)] = _mxu_t(
                    q, _eye(gw, q.dtype, hh, hp) if hp > 1 else None)

        groups.each(transpose_v, nkc)
        groups.each(transpose_q, nr)

        def scores(idx, live=None):
            """s^T [CK, CQ] of head hh of group g, queries r, keys c,
            bias and causal mask on; NEG_INF throughout where not live (a
            step past the end of the stream: p = 0, alpha = 1)."""
            g, r, hh, c = idx
            s = jnp.dot(
                k_ref[0, _window(c, ck), lanes_of(g)],
                wq_ref[_window(g * hp + hh, gw), _window(r, cq)],
                preferred_element_type=jnp.float32,
            )
            if not prescale:
                s = s * sm_scale
            if has_bias:
                s = s + bcol_ref[_window(c, ck), :]
            if causal:
                s = _causal_mask_t(
                    s, q_off + (qi * nr + r) * cq, k_off + c * ck)
            if live is not None:
                # as a [CK, CQ] select here, in the shadow of the softmax
                # before it; done on m_new / alpha in the step itself it
                # cost 0.55 ms a layer at s4096 (my chip run, PR 27)
                s = jnp.where(live, s, NEG_INF)
            return s

        def weighted_values(idx, p):
            """(p v)^T [D, CQ] of head hh of group g, keys c."""
            g, _, hh, c = idx
            return jnp.dot(
                vt_ref[_window(g * hp + hh, d), _window(c, ck)],
                p, preferred_element_type=jnp.float32,
            )

        def write_segment(seg, m, l, acc):
            """Normalize and write a finished (g, r, hh) segment."""
            g, r, hh = seg
            rows = _window(r, cq)
            if has_offsets:
                # a window that sees no key ran one masked step:
                # exp(0) everywhere, which must not reach o
                none = visible(r) <= 0
                l = jnp.where(none, 0.0, l)
                m = jnp.where(none, NEG_INF, m)
                acc = jnp.where(none, 0.0, acc)
            l_safe = jnp.maximum(l, 1e-30)
            # the dropout rescale 1 / keep_div waited for here: one
            # [D, CQ] pass a segment instead of a [CK, CQ] one a step
            denom = l_safe * keep_div if dropout_prob > 0.0 else l_safe
            lse_ref[0, pl.ds(g * hp + hh, 1), rows] = m + jnp.log(l_safe)
            if hp == 1:
                o_ref[0, rows, lanes_of(g)] = _mxu_t(
                    (acc / denom).astype(o_ref.dtype))
                return
            ot_ref[_window(hh, d), :] = acc / denom

            @pl.when(hh == hp - 1)
            def _store_group():
                o_ref[0, rows, lanes_of(g)] = _mxu_t(
                    ot_ref[...].astype(o_ref.dtype))

        # One stream of steps a grid cell, software-pipelined by hand,
        # because the MXU runs in program order: step t asks for the
        # scores of step t + 1 and for the p v product of step t - 1
        # FIRST, and only then does the vector work of its own softmax,
        # which hides both; neither waits at the end of a head. Scores,
        # probabilities and acc cross from step to step through VMEM, in
        # slots that alternate STATICALLY with the step's place in the
        # trip: dynamic slots the compiler cannot tell apart, and it
        # would hold every store behind the other slot's loads.
        def step(j, cur, prv, nxt, flags, carry):
            _, live_nxt, fresh, _ = flags
            g, r, hh, c = cur
            m, l, alpha_prev = carry
            slot, other = j % 2, 1 - j % 2
            p_prev = p_bufs[other][...]
            s_bufs[other][...] = scores(nxt, live_nxt)
            acc_ref[j] = (
                acc_ref[(j - 1) % _STEPS] * alpha_prev
                + weighted_values(prv, p_prev))
            s = s_bufs[slot][...]
            # a segment that closes leaves its m, l for finish; the
            # fresh one's alpha = exp(NEG_INF - m_new) = 0 then drops
            # the closed l and acc by itself
            stat_ref[j, 0:1, :] = m
            stat_ref[j, 1:2, :] = l
            m = jnp.where(fresh, NEG_INF, m)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
            if dropout_prob > 0.0:
                if use_prng:
                    keep = _dropout_keep_t(
                        seed_ref[0], b * nh + g * hp + hh, c * ck,
                        qi * bq + r * cq, keep_prob, ck, cq)
                else:
                    keep = mask_ref[0, g * hp + hh, _window(c, ck),
                                    _window(r, cq)] != 0
                p = jnp.where(keep, p, 0.0)
            p_bufs[slot][...] = p.astype(p_bufs[slot].dtype)
            return m_new, l, alpha

        def finish(j, seg):
            write_segment(seg[:3], stat_ref[j, 0:1, :],
                          stat_ref[j, 1:2, :], acc_ref[j])

        def stream(g0, groups):
            zero = jnp.int32(0)
            s_bufs[0][...] = scores((jnp.int32(g0), zero, zero, zero))
            last_p = p_bufs[(_STEPS - 1) % 2]
            last_p[...] = jnp.zeros((ck, cq), last_p.dtype)
            acc_ref[_STEPS - 1] = jnp.zeros((d, cq), jnp.float32)
            init = (jnp.full((1, cq), NEG_INF, jnp.float32),
                    jnp.zeros((1, cq), jnp.float32),
                    jnp.ones((1, cq), jnp.float32))
            last, _, (m, l, alpha) = _tile_stream(
                g0, groups, hp, nr, keys_of, step, finish, init)
            acc = acc_ref[_STEPS - 1] * alpha + weighted_values(
                last, last_p[...])
            write_segment(last[:3], m, l, acc)

        groups.streams(stream)

    return kernel


def default_bsh_block(s, skv, h, bwd=False):
    """THE BSH DMA-tile chooser: a function of the operands' shapes and
    of nothing else.

    Below _STREAM_FROM the whole-tile kernels run and the tile is
    _pick_block's, for both passes alike (their in-kernel PRNG seeds a
    [bq, bk] block, so the backward must tile as the forward did).
    From it on the tile is the rows of q / o / lse a forward grid cell
    holds (with all of K / V), the rows of k / v / dk / dv a backward
    one does (with q^T / do^T / dq^T of the whole batch row); the
    arithmetic runs over compute tiles inside it (_compute_tile), so the
    tile decides only how often a cell's set-up is paid and how long one
    stream of steps runs: the largest that fits (feasible.py).
    At s4096 / h768, 1024 against 512: 17.62 against 18.17 ms a layer
    (my chip run, PR 27; 0.4266 against 0.4240 MFU before compute
    tiles). The two passes may tile differently there: the dropout mask
    is a function of absolute positions (_dropout_keep_t), lse and
    delta ride as full [B, nh, S] arrays."""
    if not _bsh_streams(s, skv):
        return _pick_block(s)
    need = (_feas.flash_bsh_bwd_vmem_bytes if bwd
            else _feas.flash_bsh_fwd_vmem_bytes)
    for cand in ((1024,) if s >= 4096 else ()) + (512, 256):
        if s % cand == 0 and need(s, skv, h, cand, cand) <= _BSH_VMEM_LIMIT:
            return cand
    return _pick_block(s)


def _resolve_bsh_blocks(sq, skv, h, *, bwd=False):
    """(bq, bk, vmem_limit_bytes) for one BSH kernel launch: the forward
    uses bq, the backward bk."""
    return (
        default_bsh_block(sq, skv, h, bwd=bwd),
        default_bsh_block(skv, skv, h, bwd=bwd),
        _BSH_VMEM_LIMIT,
    )


def _flash_fwd_bsh_stream(q, k, v, bias, mask, seed, offsets, *, sm_scale, nh,
                   causal, dropout_prob, form="bsh"):
    b, sq, hdim = q.shape
    skv = k.shape[1]
    d = hdim // nh
    use_prng = dropout_prob > 0.0 and mask is None
    bq, bk, vmem_limit = _resolve_bsh_blocks(sq, skv, hdim)
    has_mask = mask is not None and dropout_prob > 0.0
    has_offsets = offsets is not None
    has_bias = bias is not None

    # K and V change with the batch row only: where a row has several
    # grid cells, one buffer each (a second would hold the next row's
    # 2 * Skv * H bytes for the whole of this one)
    once_a_row = (
        {"pipeline_mode": pl.Buffered(1)} if sq // bq > 1 else {})
    in_specs = [
        pl.BlockSpec((1, bq, hdim), lambda b_, i: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, skv, hdim), lambda b_, i: (b_, 0, 0),
                     memory_space=pltpu.VMEM, **once_a_row),
        pl.BlockSpec((1, skv, hdim), lambda b_, i: (b_, 0, 0),
                     memory_space=pltpu.VMEM, **once_a_row),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, skv), lambda b_, i: (b_, 0, 0),
                         memory_space=pltpu.VMEM))
        args.append(bias)
    if has_mask:
        in_specs.append(
            pl.BlockSpec((1, nh, skv, bq), lambda b_, i: (b_, 0, 0, i),
                         memory_space=pltpu.VMEM))
        args.append(mask)
    if use_prng:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    if has_offsets:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(offsets)

    cq, ck = _compute_tile(bq, skv, d)
    gw = _head_groups(nh, d)[0] * d
    kernel = _make_fwd_bsh_stream_kernel(
        sm_scale=sm_scale, causal=causal, dropout_prob=dropout_prob,
        has_bias=has_bias, use_prng=use_prng, has_mask=has_mask,
        has_offsets=has_offsets, nh=nh, d=d, bq=bq, cq=cq, ck=ck,
        prescale=_prescale_ok(sm_scale),
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, sq // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, hdim), lambda b_, i: (b_, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nh, bq), lambda b_, i: (b_, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hdim), q.dtype),
            jax.ShapeDtypeStruct((b, nh, sq), jnp.float32),
        ],
        scratch_shapes=(
            [pltpu.VMEM((skv, cq), jnp.float32)] if has_bias else []
        ) + [pltpu.VMEM((hdim, skv), v.dtype),
             pltpu.VMEM((nh * gw, bq), q.dtype),
             pltpu.VMEM((ck, cq), jnp.float32),
             pltpu.VMEM((ck, cq), jnp.float32),
             pltpu.VMEM((ck, cq), v.dtype),
             pltpu.VMEM((ck, cq), v.dtype),
             pltpu.VMEM((_STEPS, d, cq), jnp.float32),
             pltpu.VMEM((_STEPS, 2, cq), jnp.float32),
             pltpu.VMEM((gw, cq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit),
        name=_bsh_kernel_name("fwd", causal, form),
        interpret=backend.interpret(),
    )(*args)
    return o, lse


def _make_bwd_bsh_stream_kernel(*, sm_scale, causal, dropout_prob, has_bias,
                         use_prng, has_mask, has_offsets, nh, d, bk, cq, ck,
                         prescale=False):
    """Single-pass BSH backward: grid (B, NKv) with NKv innermost per
    batch row. Computes dk / dv for this k block and accumulates dq^T
    over the blocks of a batch row in a resident f32 scratch, written
    out (transposed and scaled) with the last block.

    The same stream of transposed [ck, cq] tiles as the forward, over
    (group, key window of the block, head, query window): s^T = k q^T
    and dp^T = v do^T take the block's k / v rows as they lie against
    q^T / do^T (transposed once a batch row); dv^T = do^T p, dk^T = q^T
    ds and dq^T = k^T ds^T come out [d, .] with 64 rows to stream, p and
    ds^T being the weights (pushed transposed for the first two), so no
    product transposes a [ck, cq] matrix on the XLU and the accumulators
    fill their vregs at d = 64."""
    groups = _LaneGroups(nh, d)
    hp, gw, lanes_of = groups.hp, groups.gw, groups.lanes
    keep_prob = 1.0 - dropout_prob
    keep_div = 1.0
    if dropout_prob > 0.0:
        keep_div = (
            _dropout_quantized_keep(keep_prob) if use_prng else keep_prob)
    # ds is formed as keep_div * ds, unscaled by sm_scale: both wait for
    # the end of a segment (dk, dv) or of the batch row (dq). A power of
    # two sm_scale is already in q^T, so dk = ds^T q has it.
    dk_scale = (1.0 if prescale else sm_scale) / keep_div
    dv_scale = 1.0 / keep_div
    dq_scale = sm_scale / keep_div
    nt = (((1,), (1,)), ((), ()))   # a @ b^T

    def kernel(*refs):
        it = iter(refs)
        q_hbm = next(it)          # [B, Sq, H], left in HBM
        k_ref = next(it)          # [1, BK, H]
        v_ref = next(it)          # [1, BK, H]
        bias_ref = next(it) if has_bias else None   # [1, 1, Skv]
        mask_ref = next(it) if has_mask else None   # [1, nh, BK, Sq]
        seed_ref = next(it) if use_prng else None
        off_ref = next(it) if has_offsets else None
        do_hbm = next(it)         # [B, Sq, H], left in HBM
        lse_ref = next(it)        # [1, nh, Sq]
        delta_ref = next(it)      # [1, nh, Sq]
        dq_hbm = next(it)         # [B, Sq, H] f32, left in HBM
        dk_ref = next(it)         # [1, BK, H]
        dv_ref = next(it)         # [1, BK, H]
        bcol_ref = next(it) if has_bias else None   # [BK, CQ] f32
        rows_ref = next(it)       # [2, CQ, H]: q and do rows on their way
        dq_rows_ref = next(it)    # [128, H] f32: dq rows on their way
        sems = next(it)           # 3 DMA semaphores
        qt_ref = next(it)         # [H, Sq]: q^T (prescaled), a batch row
        dot_ref = next(it)        # [H, Sq]: do^T, a batch row
        dqt_ref = next(it)        # [H, Sq] f32: dq^T, a batch row
        kt_ref = next(it)         # [H, BK]: k^T of the block
        kp_ref = next(it) if hp > 1 else None   # [nh, BK, GW]: k, v with
        vp_ref = next(it) if hp > 1 else None   # the other heads' lanes 0
        s_bufs = (next(it), next(it))    # [CK, CQ] f32 each
        dp_bufs = (next(it), next(it))   # [CK, CQ] f32 each
        p_bufs = (next(it), next(it))    # [CK, CQ], q's dtype
        ds_bufs = (next(it), next(it))   # [CK, CQ], q's dtype
        dkt_ref = next(it)        # [_STEPS, D, CK] f32
        dvt_ref = next(it)        # [_STEPS, D, CK] f32
        okt_ref = next(it)        # [GW, CK] f32: a group's dk^T
        ovt_ref = next(it)        # [GW, CK] f32: a group's dv^T

        b = pl.program_id(0)
        ki = pl.program_id(1)
        sq = q_hbm.shape[1]
        nrq = sq // cq
        nck = bk // ck
        q_off = off_ref[0] if has_offsets else 0
        k_off = off_ref[1] if has_offsets else 0

        # q, do and dq are wanted transposed, and only that: they stay in
        # HBM, q / do come in a row window at a time when a batch row
        # starts and dq leaves 128 rows at a time when it ends (a block
        # each would hold 8 B/elem of the row for nothing)
        @pl.when(ki == 0)
        def _new_batch_row():
            def window_in(r, carry):
                copies = [
                    pltpu.make_async_copy(
                        hbm.at[b, _window(r, cq), :], rows_ref.at[i],
                        sems.at[i])
                    for i, hbm in enumerate((q_hbm, do_hbm))]
                for copy in copies:
                    copy.start()
                for copy in copies:
                    copy.wait()

                def transpose(g, _):
                    q = rows_ref[0, :, lanes_of(g)]
                    if prescale:
                        # exact pow2 shift; dk = ds^T q is then
                        # chain-rule scaled already
                        q = q * jnp.asarray(sm_scale, q.dtype)
                    qt_ref[_window(g, gw), _window(r, cq)] = _mxu_t(q)
                    dot_ref[_window(g, gw), _window(r, cq)] = _mxu_t(
                        rows_ref[1, :, lanes_of(g)])

                groups.each(transpose, 1)
                return carry

            jax.lax.fori_loop(0, nrq, window_in, 0)
            dqt_ref[...] = jnp.zeros_like(dqt_ref)

        def prepare_k(g, c):
            k = k_ref[0, _window(c, ck), lanes_of(g)]      # [CK, GW]
            kt_ref[_window(g, gw), _window(c, ck)] = _mxu_t(k)
            if hp > 1:
                v = v_ref[0, _window(c, ck), lanes_of(g)]
                for hh in range(hp):
                    kp_ref[g * hp + hh, _window(c, ck), :] = (
                        _own_lanes(k, hh, hp, d))
                    vp_ref[g * hp + hh, _window(c, ck), :] = (
                        _own_lanes(v, hh, hp, d))

        groups.each(prepare_k, nck)
        if has_bias:
            _bias_columns(bias_ref, bcol_ref, ki * bk, bk // MIN_BLOCK)

        def queries_of(c):
            """Query windows that see the block's key window c: at least
            one (whose causal mask then leaves nothing)."""
            if not causal:
                return 0, nrq
            lo = _lo_blocks(causal, ki * nck + c, cq, ck, nrq, q_off, k_off)
            return jnp.minimum(lo, nrq - 1), nrq

        def kv_rows(ref, pad_ref, idx):
            g, c, hh, _ = idx
            if hp > 1:
                return pad_ref[g * hp + hh, _window(c, ck), :]
            return ref[0, _window(c, ck), lanes_of(g)]

        def t_slab(ref, idx):
            g, _, _, r = idx
            return ref[_window(g, gw), _window(r, cq)]        # [GW, CQ]

        def head_rows(idx):
            return _window(idx[0] * hp + idx[2], d)

        def t_head(ref, idx, cols):
            return ref[head_rows(idx), cols]                  # [D, .]

        def step(j, cur, prv, nxt, flags, carry):
            live, _, _, fresh_prv = flags
            g, c, hh, r = cur
            head = g * hp + hh
            slot, other = j % 2, 1 - j % 2
            # 1. the MXU first, in program order: the two products of
            # the next step, the three of the last
            p_prev = p_bufs[other][...]
            ds_prev = ds_bufs[other][...]
            s_bufs[other][...] = jnp.dot(
                kv_rows(k_ref, kp_ref, nxt), t_slab(qt_ref, nxt),
                preferred_element_type=jnp.float32)
            dp_bufs[other][...] = jnp.dot(
                kv_rows(v_ref, vp_ref, nxt), t_slab(dot_ref, nxt),
                preferred_element_type=jnp.float32)
            rows_prv = _window(prv[3], cq)
            keys_prv = _window(prv[1], ck)
            keep_acc = jnp.where(fresh_prv, 0.0, 1.0)
            dvt_ref[j] = dvt_ref[(j - 1) % _STEPS] * keep_acc + (
                jax.lax.dot_general(
                    t_head(dot_ref, prv, rows_prv), p_prev, nt,
                    preferred_element_type=jnp.float32))
            dkt_ref[j] = dkt_ref[(j - 1) % _STEPS] * keep_acc + (
                jax.lax.dot_general(
                    t_head(qt_ref, prv, rows_prv), ds_prev, nt,
                    preferred_element_type=jnp.float32))
            dqt_ref[head_rows(prv), rows_prv] += jnp.dot(
                t_head(kt_ref, prv, keys_prv), ds_prev,
                preferred_element_type=jnp.float32)
            # 2. the vector work of this step's tile
            rows = _window(r, cq)
            s = s_bufs[slot][...]
            if not prescale:
                s = s * sm_scale
            if has_bias:
                s = s + bcol_ref[_window(c, ck), :]
            if causal:
                s = _causal_mask_t(
                    s, q_off + r * cq, k_off + (ki * nck + c) * ck)
            lse = lse_ref[0, pl.ds(head, 1), rows]
            delta = delta_ref[0, pl.ds(head, 1), rows]
            # a step past the end of the stream leaves p = ds = 0
            p = jnp.exp(s - jnp.where(live, lse, -NEG_INF))
            dp = dp_bufs[slot][...]
            if dropout_prob > 0.0:
                if use_prng:
                    keep = _dropout_keep_t(
                        seed_ref[0], b * nh + head, ki * bk + c * ck,
                        r * cq, keep_prob, ck, cq)
                else:
                    keep = mask_ref[0, head, _window(c, ck), rows] != 0
                dp = jnp.where(keep, dp, 0.0)
                delta = delta * keep_div
                p_bufs[slot][...] = jnp.where(keep, p, 0.0).astype(
                    p_bufs[slot].dtype)
            else:
                p_bufs[slot][...] = p.astype(p_bufs[slot].dtype)
            ds_bufs[slot][...] = (p * (dp - delta)).astype(
                ds_bufs[slot].dtype)
            return carry

        def write_segment(seg, dkt, dvt):
            g, c, hh = seg
            keys = _window(c, ck)
            if hp == 1:
                dk_ref[0, keys, lanes_of(g)] = _mxu_t(
                    (dkt * dk_scale).astype(dk_ref.dtype))
                dv_ref[0, keys, lanes_of(g)] = _mxu_t(
                    (dvt * dv_scale).astype(dv_ref.dtype))
                return
            okt_ref[_window(hh, d), :] = dkt * dk_scale
            ovt_ref[_window(hh, d), :] = dvt * dv_scale

            @pl.when(hh == hp - 1)
            def _store_group():
                dk_ref[0, keys, lanes_of(g)] = _mxu_t(
                    okt_ref[...].astype(dk_ref.dtype))
                dv_ref[0, keys, lanes_of(g)] = _mxu_t(
                    ovt_ref[...].astype(dv_ref.dtype))

        def finish(j, seg):
            write_segment(seg[:3], dkt_ref[j], dvt_ref[j])

        def stream(g0, groups):
            zero = jnp.int32(0)
            first = (jnp.int32(g0), zero, zero, queries_of(zero)[0])
            s_bufs[0][...] = jnp.dot(
                kv_rows(k_ref, kp_ref, first), t_slab(qt_ref, first),
                preferred_element_type=jnp.float32)
            dp_bufs[0][...] = jnp.dot(
                kv_rows(v_ref, vp_ref, first), t_slab(dot_ref, first),
                preferred_element_type=jnp.float32)
            last_slot = (_STEPS - 1) % 2
            p_bufs[last_slot][...] = jnp.zeros(
                (ck, cq), p_bufs[last_slot].dtype)
            ds_bufs[last_slot][...] = jnp.zeros(
                (ck, cq), ds_bufs[last_slot].dtype)
            dkt_ref[_STEPS - 1] = jnp.zeros((d, ck), jnp.float32)
            dvt_ref[_STEPS - 1] = jnp.zeros((d, ck), jnp.float32)
            last, fresh_last, _ = _tile_stream(
                g0, groups, hp, nck, queries_of, step, finish, 0)
            # the last step's three products
            rows_l = _window(last[3], cq)
            p_l = p_bufs[last_slot][...]
            ds_l = ds_bufs[last_slot][...]
            keep_acc = jnp.where(fresh_last, 0.0, 1.0)
            dvt = dvt_ref[_STEPS - 1] * keep_acc + jax.lax.dot_general(
                t_head(dot_ref, last, rows_l), p_l, nt,
                preferred_element_type=jnp.float32)
            dkt = dkt_ref[_STEPS - 1] * keep_acc + jax.lax.dot_general(
                t_head(qt_ref, last, rows_l), ds_l, nt,
                preferred_element_type=jnp.float32)
            dqt_ref[head_rows(last), rows_l] += jnp.dot(
                t_head(kt_ref, last, _window(last[1], ck)), ds_l,
                preferred_element_type=jnp.float32)
            write_segment(last[:3], dkt, dvt)

        groups.streams(stream)

        @pl.when(ki == pl.num_programs(1) - 1)
        def _write_dq():
            n = MIN_BLOCK

            def rows_out(r, carry):
                # dq leaves as f32 and is cast to q's dtype outside:
                # rounded here, once, it transposes exactly
                for lo in range(0, nh * d, min(gw, n)):
                    hi = lo + min(gw, n)
                    dq_rows_ref[:, lo:hi] = _mxu_t(
                        (dqt_ref[lo:hi, _window(r, n)] * dq_scale).astype(
                            kt_ref.dtype)).astype(jnp.float32)
                copy = pltpu.make_async_copy(
                    dq_rows_ref, dq_hbm.at[b, _window(r, n), :], sems.at[2])
                copy.start()
                copy.wait()
                return carry

            jax.lax.fori_loop(0, sq // n, rows_out, 0)

    return kernel


def _flash_bwd_bsh_stream(res, g, *, sm_scale, nh, causal, dropout_prob,
                          form="bsh"):
    q, k, v, bias, mask, seed, offsets, o, lse = res
    b, sq, hdim = q.shape
    skv = k.shape[1]
    d = hdim // nh
    use_prng = dropout_prob > 0.0 and mask is None
    _, bk, vmem_limit = _resolve_bsh_blocks(sq, skv, hdim, bwd=True)
    has_mask = mask is not None and dropout_prob > 0.0
    has_offsets = offsets is not None
    has_bias = bias is not None
    cq, ck = _compute_tile(sq, bk, d)
    hp = _head_groups(nh, d)[0]
    gw = hp * d

    # delta[b, h, s] = sum_d o*g per head, from the BSH layout
    delta = (
        (o.astype(jnp.float32) * g.astype(jnp.float32))
        .reshape(b, sq, nh, d).sum(axis=-1).transpose(0, 2, 1)
    )

    # q, do and dq stay in HBM: the kernel holds them transposed and
    # moves their rows itself. lse and delta change with the batch row
    # only: one buffer each where a row has several grid cells
    fullq = pl.BlockSpec(memory_space=pl.ANY)
    kspec = pl.BlockSpec((1, bk, hdim), lambda b_, i: (b_, i, 0),
                         memory_space=pltpu.VMEM)
    statspec = pl.BlockSpec(
        (1, nh, sq), lambda b_, i: (b_, 0, 0), memory_space=pltpu.VMEM,
        **({"pipeline_mode": pl.Buffered(1)} if skv // bk > 1 else {}))

    args = [q, k, v]
    in_specs = [fullq, kspec, kspec]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, skv), lambda b_, i: (b_, 0, 0),
                         memory_space=pltpu.VMEM))
        args.append(bias)
    if has_mask:
        in_specs.append(
            pl.BlockSpec((1, nh, bk, sq), lambda b_, i: (b_, 0, i, 0),
                         memory_space=pltpu.VMEM))
        args.append(mask)
    if use_prng:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    if has_offsets:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(offsets)
    in_specs += [fullq, statspec, statspec]
    args += [g, lse, delta]

    f32 = jnp.float32
    tile = [pltpu.VMEM((ck, cq), f32)] * 4 + [pltpu.VMEM((ck, cq), q.dtype)] * 4
    dq, dk, dv = pl.pallas_call(
        _make_bwd_bsh_stream_kernel(
            sm_scale=sm_scale, causal=causal, dropout_prob=dropout_prob,
            has_bias=has_bias, use_prng=use_prng, has_mask=has_mask,
            has_offsets=has_offsets, nh=nh, d=d, bk=bk, cq=cq, ck=ck,
            prescale=_prescale_ok(sm_scale),
        ),
        grid=(b, skv // bk),
        in_specs=in_specs,
        out_specs=[fullq, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hdim), jnp.float32),
            jax.ShapeDtypeStruct((b, skv, hdim), k.dtype),
            jax.ShapeDtypeStruct((b, skv, hdim), v.dtype),
        ],
        scratch_shapes=(
            [pltpu.VMEM((bk, cq), f32)] if has_bias else []
        ) + [pltpu.VMEM((2, cq, hdim), q.dtype),
             pltpu.VMEM((MIN_BLOCK, hdim), f32),
             pltpu.SemaphoreType.DMA((3,)),
             pltpu.VMEM((hdim, sq), q.dtype),
             pltpu.VMEM((hdim, sq), q.dtype),
             pltpu.VMEM((hdim, sq), f32),
             pltpu.VMEM((hdim, bk), k.dtype)] + (
            [pltpu.VMEM((nh, bk, gw), k.dtype)] * 2 if hp > 1 else []
        ) + tile + [
            pltpu.VMEM((_STEPS, d, ck), f32),
            pltpu.VMEM((_STEPS, d, ck), f32),
            pltpu.VMEM((gw, ck), f32),
            pltpu.VMEM((gw, ck), f32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit),
        name=_bsh_kernel_name("bwd", causal, form),
        interpret=backend.interpret(),
    )(*args)
    return dq.astype(q.dtype), dk, dv


# the BSH kernels keep whole sequences resident (k / v and V^T in the
# fwd; q^T / do^T / dq^T in the bwd): 34 MB and 56 MB at s=4096/H=768,
# 69 MB at s=8192 (Mosaic's scoped-vmem allocation, bisected on the
# limit). v5e has 128MB of VMEM; the default ~16MB scoped limit is far
# below what the hardware allows, so raise it for these calls. Past the
# estimate below, dispatch falls back to the BHSD kernels (streamed
# blocks, head-transposed layout) — and beyond single-chip HBM, shard
# the sequence (ring attention over "sp") instead.
_BSH_VMEM_LIMIT = _feas.BSH_VMEM_LIMIT


def bsh_shapes_ok(sq, skv, h) -> bool:
    """Will the BSH kernels' whole-sequence VMEM residency fit, at the
    smallest tiles the chooser falls back to? feasible.py's models of
    both passes, calibrated against Mosaic's allocation."""
    return _feas.flash_bsh_ok(sq, skv, h, MIN_BLOCK, MIN_BLOCK)[0]


def bsh_dispatch_ok(sq, skv, h, num_heads, bias=None, batch=None,
                    causal=False) -> bool:
    """THE fitness test for every BSH dispatch site (the attention op and
    both fused stacks): flag/backend/shape gates on both lengths, VMEM
    residency, per-key-only bias actually holdable as [B, 1, S_kv], and
    no rectangular-causal (the kernel's zero-offset causal mask is
    top-left aligned — silently wrong when sq != skv)."""
    d = h // num_heads
    if not (flash_shapes_ok(sq, d) and flash_shapes_ok(skv, d)
            and bsh_shapes_ok(sq, skv, h)):
        return False
    if causal and sq != skv:
        return False
    if bias is None:
        return True
    if bias.ndim == 4:
        bb, bn, bq_, bk_ = bias.shape
    elif bias.ndim == 3:
        bb, bn, bk_ = bias.shape
        bq_ = 1
    else:
        return False
    return (bn == 1 and bq_ == 1 and bk_ == skv
            and (batch is None or bb == batch))


# the S from which the stream kernels run (my chip run, PR 27, BERT-base
# heads, ms a call fwd+bwd, whole-tile -> stream: S 256 2.73 -> 3.85,
# S 512 3.24 -> 4.35, S 1024 6.77 -> 6.40, S 2048 12.05 -> 10.26,
# S 4096 22.37 -> 17.62): a stream pays a grid cell's set-up (K^T / V^T
# / q^T, bias columns) and a pipeline's fill, which S = 512 cannot
# amortize over the 16 tiles a head has there
_STREAM_FROM = _feas.FLASH_BSH_STREAM_FROM


def _bsh_streams(sq, skv) -> bool:
    return max(sq, skv) >= _STREAM_FROM


def _flash_fwd_bsh(q, k, v, *rest, **statics):
    fwd = (_flash_fwd_bsh_stream if _bsh_streams(q.shape[1], k.shape[1])
           else _flash_fwd_bsh_tile)
    return fwd(q, k, v, *rest, **statics)


def _flash_bwd_bsh(res, g, **statics):
    bwd = (_flash_bwd_bsh_stream
           if _bsh_streams(res[0].shape[1], res[1].shape[1])
           else _flash_bwd_bsh_tile)
    return bwd(res, g, **statics)


@functools.lru_cache(maxsize=256)
def _make_flash_core_bsh(*, sm_scale, nh, causal, dropout_prob, form="bsh"):
    statics = dict(sm_scale=sm_scale, nh=nh, causal=causal,
                   dropout_prob=dropout_prob, form=form)

    @jax.custom_vjp
    def core(q, k, v, bias, mask, seed, offsets):
        o, _ = _flash_fwd_bsh(q, k, v, bias, mask, seed, offsets, **statics)
        return o

    def core_fwd(q, k, v, bias, mask, seed, offsets):
        o, lse = _flash_fwd_bsh(q, k, v, bias, mask, seed, offsets, **statics)
        o = checkpoint_name(o, "flash_o")
        lse = checkpoint_name(lse, "flash_lse")
        return o, (q, k, v, bias, mask, seed, offsets, o, lse)

    def core_bwd(res, g):
        dq, dk, dv = _flash_bwd_bsh(res, g, **statics)
        dbias = jnp.zeros_like(res[3]) if res[3] is not None else None
        return (dq, dk, dv, dbias, None, None, None)

    core.defvjp(core_fwd, core_bwd)
    return core


def flash_attention_bsh(q, k, v, bias=None, num_heads=None, sm_scale=None,
                        causal=False, dropout_prob=0.0, dropout_key=None,
                        dropout_seed=None, mesh=None, batch_axis="dp",
                        head_axis="tp", form="bsh"):
    """Transpose-free flash attention on projection-layout tensors.

    q: [B, S_q, H], k/v: [B, S_kv, H] with H = num_heads * D — exactly
    what the qkv/kv projections produce, no head split/merge transposes.
    S_q and S_kv may differ (cross-attention). bias: [B, 1, 1, S_kv] or
    [B, 1, S_kv] per-key additive (padding mask; zero cotangent — use the
    BHSD `flash_attention` for full biases or dbias). Returns [B, S_q, H].

    mesh: shard batch on `batch_axis` and HEADS on `head_axis` (the H
    lane dim splits per head groups; num_heads % tp == 0).
    """
    b, sq, hdim = q.shape
    if num_heads is None:
        raise ValueError("flash_attention_bsh needs num_heads")
    if causal and sq != k.shape[1]:
        raise ValueError(
            "flash_attention_bsh: causal with sq != skv would be top-left "
            "aligned (use the BHSD kernel with offsets, or equal lengths)")
    d = hdim // num_heads
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if bias is not None:
        bias = bias.reshape(b, 1, k.shape[1]).astype(jnp.float32)

    seed = None
    mask = None
    if dropout_prob > 0.0:
        if dropout_seed is not None:
            seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
        elif dropout_key is not None:
            seed = jax.random.randint(
                dropout_key, (1,), 0, jnp.iinfo(jnp.int32).max,
                dtype=jnp.int32)
        else:
            raise ValueError("dropout needs dropout_key or dropout_seed")
        # interpret mode (the CPU tests' oracle) draws the keep mask
        # with jax.random outside the kernel; on the chip the kernel
        # regenerates it with zero HBM traffic
        if backend.interpret():
            mkey = dropout_key if dropout_key is not None else (
                jax.random.PRNGKey(seed[0]))
            mask = jax.random.bernoulli(
                jax.random.fold_in(mkey, 7), 1.0 - dropout_prob,
                (b, num_heads, sq, k.shape[1]),
            ).astype(jnp.uint8)
            if _bsh_streams(sq, k.shape[1]):
                # the stream kernels hold score tiles keys-major
                mask = mask.swapaxes(2, 3)

    def local(ql, kl, vl, bl, ml, sl, nh_local):
        core = _make_flash_core_bsh(
            sm_scale=float(sm_scale), nh=nh_local, causal=causal,
            dropout_prob=dropout_prob, form=form)
        return core(ql, kl, vl, bl, ml, sl, None)

    axes = [
        ax for ax in (batch_axis, head_axis)
        if mesh is not None and ax in mesh.axis_names and mesh.shape[ax] > 1
    ]
    if not axes:
        return local(q, k, v, bias, mask, seed, num_heads)

    from jax.sharding import PartitionSpec as P

    ba = batch_axis if batch_axis in axes else None
    ha = head_axis if head_axis in axes else None
    nh_local = num_heads // (mesh.shape[ha] if ha else 1)
    qspec = P(ba, None, ha)
    bias_spec = P(ba, None, None) if bias is not None else None
    mask_spec = P(ba, ha, None, None) if mask is not None else None

    def body(ql, kl, vl, bl, ml, sl):
        local_seed = sl
        if sl is not None:
            import jax.lax as lax

            salt = jnp.int32(0)
            if ba:
                salt = salt + lax.axis_index(ba) * jnp.int32(0x632BE59B)
            if ha:
                salt = salt + lax.axis_index(ha) * jnp.int32(0x1B873593)
            local_seed = sl + salt
        return local(ql, kl, vl, bl, ml, local_seed, nh_local)

    in_specs = (qspec, qspec, qspec, bias_spec, mask_spec,
                P() if seed is not None else None)
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=qspec,
        check_vma=False,
    )(q, k, v, bias, mask, seed)


# head widths the kernels' lane layouts take
HEAD_WIDTHS = (64, 128, 256)


def flash_shapes_ok(s, d) -> bool:
    """THE shape/backend/flag gate for every flash dispatch site (the
    attention op, the encoder stack, and the ring path all call this)."""
    from ...fluid.flags import flag

    if not flag("FLAGS_use_flash_attention"):
        return False
    return d in HEAD_WIDTHS and s % MIN_BLOCK == 0 and backend.on_kernels()


flash_block_ok = flash_shapes_ok  # ring-path alias
