"""Paged decode-step attention — one query token per sequence attending
over a paged KV cache.

The serving engine (inference/engine.py) keeps each request's KV history
in fixed-size pages of a preallocated HBM pool (inference/kv_cache.py).
At every decode step each active slot owns one query vector and a page
table naming which physical pages hold its history; this op computes

    o[b] = softmax(q[b] . K[b]^T * sm_scale) V[b]

where K[b]/V[b] are the first ``lengths[b]`` logical positions gathered
through ``page_table[b]``.  Two implementations with identical math:

* ``jnp``   — gather pages into a dense [B, T, H, D] view and run a
  stable fp32 softmax.  Reference semantics; used on CPU and for GQA.
* ``pallas`` — a TPU kernel over grid (batch, pages) that streams one
  KV page per step through VMEM using ``PrefetchScalarGridSpec``: the
  page table and lengths are scalar-prefetched so each k/v BlockSpec
  index map can chase ``table[b, p]`` and DMA the right physical page
  while the previous one computes.  Online softmax state (m, l, acc)
  lives in VMEM scratch and persists across the page dimension, so the
  output block is written once on the last page.

Pages past a sequence's length are fully masked (they contribute
exp(-inf) = 0), so garbage table entries beyond the live range are
harmless as long as they index real pages — the pool reserves physical
page 0 as a trash page for exactly this.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

_NEG_INF = float("-inf")


def _ref_paged_attention(q, k_pages, v_pages, page_table, lengths,
                         sm_scale):
    """Dense-gather reference: exact math of the kernel, any backend."""
    b, h, d = q.shape
    _, page, kh, _ = k_pages.shape
    maxp = page_table.shape[1]
    t = maxp * page
    # [B, maxp, page, KH, D] -> [B, T, KH, D]
    k = k_pages[page_table].reshape(b, t, kh, d)
    v = v_pages[page_table].reshape(b, t, kh, d)
    if kh != h:  # grouped-query: repeat shared KV heads
        rep = h // kh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    pos = jnp.arange(t, dtype=jnp.int32)[None, None, :]
    s = jnp.where(pos < lengths[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bht,bthd->bhd", p / l,
                   v.astype(jnp.float32))
    return o.astype(q.dtype)


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page, heads, sm_scale, maxp):
    """One (slot, page) grid cell. k/v arrive as [page, H*D] — the pool
    row as stored, heads side by side on the lane axis — so each head is
    a STATIC lane slice (the flash BSH idiom) and the page's positions
    lie on the sublane axis: scores are a lane reduce, the online
    softmax state one [1, 1] cell of the [H, 1] scratch per head, the
    p @ v contraction a sublane reduce. All of it is VPU work on
    vectors: decode streams pages from HBM, its arithmetic is noise."""
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = q_ref.shape[-1]
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    live = pos < len_ref[b]                                  # [page, 1]
    for h in range(heads):
        qh = q_ref[0, h:h + 1, :].astype(jnp.float32)        # [1, D]
        kh = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
        vh = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
        s = jnp.sum(kh * qh, axis=-1, keepdims=True) * sm_scale
        s = jnp.where(live, s, _NEG_INF)                     # [page, 1]
        m_prev = m_ref[h:h + 1, :]                           # [1, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        # all-masked page: keep state unchanged (exp(-inf - -inf) trap)
        seen = jnp.isfinite(m_new)
        alpha = jnp.where(seen, jnp.exp(m_prev - m_new), 1.0)
        pw = jnp.where(seen, jnp.exp(s - m_new), 0.0)        # [page, 1]
        m_ref[h:h + 1, :] = m_new
        l_ref[h:h + 1, :] = (l_ref[h:h + 1, :] * alpha
                             + jnp.sum(pw, axis=0, keepdims=True))
        acc_ref[h:h + 1, :] = (acc_ref[h:h + 1, :] * alpha
                               + jnp.sum(pw * vh, axis=0, keepdims=True))

    @pl.when(p == maxp - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)                   # [H, 1]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pallas_paged_attention(q, k_pages, v_pages, page_table, lengths,
                            sm_scale):
    b, h, d = q.shape
    n_pages, page, kh, _ = k_pages.shape
    assert kh == h, "pallas path is MHA-only; GQA uses the jnp path"
    maxp = page_table.shape[1]
    kernel = functools.partial(_paged_kernel, page=page, heads=h,
                               sm_scale=sm_scale, maxp=maxp)
    kv_spec = pl.BlockSpec((1, page, h * d),
                           lambda bi, pi, tbl, lens: (tbl[bi, pi], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, pi, tbl, lens: (bi, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, h, d),
                               lambda bi, pi, tbl, lens: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    # [P, page, H, D] -> [P, page, H*D]: the same bytes, heads on lanes
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        name="paged_attention",
        interpret=_interpret(),
    )(page_table, lengths, q,
      k_pages.reshape(n_pages, page, h * d),
      v_pages.reshape(n_pages, page, h * d))


def paged_attention(q, k_pages, v_pages, page_table, lengths,
                    sm_scale=None, impl=None):
    """Decode-step attention over a paged KV pool.

    Args:
      q:          [B, H, D] one query token per slot.
      k_pages:    [P, page, KH, D] physical key pages (whole pool).
      v_pages:    [P, page, KH, D] physical value pages.
      page_table: [B, maxp] int32 physical page id per logical page.
      lengths:    [B] int32 live KV length per slot (0 => undefined
                  output for that slot; callers mask dead slots).
      sm_scale:   softmax scale; default 1/sqrt(D).
      impl:       'jnp' | 'pallas' | None (env PADDLE_PAGED_ATTN_IMPL,
                  default: pallas when MHA, jnp otherwise).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if impl is None:
        impl = os.environ.get("PADDLE_PAGED_ATTN_IMPL", "auto")
    if impl == "auto":
        # kernel on real TPU backends for MHA; dense-gather reference
        # otherwise (GQA, and CPU tests — interpret mode is for parity
        # checks, not the serving hot loop)
        impl = ("pallas" if not _interpret()
                and q.shape[1] == k_pages.shape[2] else "jnp")
    if impl == "pallas":
        return _pallas_paged_attention(q, k_pages, v_pages,
                                       page_table, lengths, sm_scale)
    if impl == "jnp":
        return _ref_paged_attention(q, k_pages, v_pages,
                                    page_table, lengths, sm_scale)
    raise ValueError(f"unknown paged-attention impl {impl!r}")
