"""`mhc_post` of the hyper-connected residual (ops/latent_ops.py) as two
Pallas kernels for the TPU, each one pass over its operands; below them
`mhc_map`, the three mappings of a sublayer, the same way.

    X'_i = sum_j H_res[i, j] X_j + H_post[i] y        i, j < n streams

X [B, S, n C] holds the n streams side by side on the last axis, y [B, S,
C] is the sublayer's output, H_res [B, S, n n] (row-major) and H_post [B, S,
n] are a token's mappings in float32 (T = B S tokens below). Both kernels
are blocked over rows (tokens) and HBM-bound by design: the forward reads X, y and writes X' (9 C
elements a token), the backward reads dX', X, y and writes dX, dy (14 C),
where XLA's fusions read the streams again for every one of the sixteen
sums over C of dH_res and the four of dH_post.

**What a grid cell does.** A cell holds a block of `br` rows at full
width. Inside it the rows go `_GROUP` at a time through a rolled loop, and
inside that the columns `_chunk(C)` at a time through another: a token's
twenty mappings are spread across the lanes once a row group and stay in
registers while its C columns pass, and the compiled body is one chunk's
whatever the block holds. Everything is float32 from the load to the one
rounding at the store, in `_mhc_post`'s order of additions. The backward
keeps its twenty sums over C as lane-wide partial sums while the columns
pass and folds the lanes once a row group.

**The mappings cross the call with the tokens on the lanes**, H^T [n n +
n, T], and so do their cotangents: that is how `mhc_map` computes them
(Sinkhorn on [n, n, T]), and a call that asked for [T, n n] rows made
XLA carry that layout back into `mhc_map`'s own fusions, sixteen of 128
lanes in use (my chip run, PR 33: `mhc_map` 38.2 -> 54.6 ms a step). A
cell turns its [n n + n, br] block to [br, n n + n] and its sums back by a
product with the identity on the MXU, exact at the highest precision.

Row blocks come from the operands' shapes alone (`default_mhc_rows`, held
to `feasible.mhc_vmem_bytes`): no flag, no environment name, no cache. A
call asks Mosaic for the VMEM its cell needs and a slack, not the core's.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from . import feasible as _feas

# a block of H^T has its rows on the lanes: 128 of them, or all there are
_ROWS = 128
# rows a trip of the row loop holds: the float32 tile, and the row tile of
# XLA's bf16 layout on the TPU (T(8,128)(2,1)), so a load is one vector
# register a stream whatever the dtype
_GROUP = 8
_LANES = 128


def _chunk(c: int) -> int:
    """Columns a trip of the column loop holds: 256 (128 where 256 does not
    divide c). At the Xing4 cell's operands ([8192, 4 x 3584] bf16, row
    block 128; my chip run, PR 33) 128 / 256 / 512 read 0.855 / 0.826 /
    0.825 ms forward and 1.315 / 1.288 / 1.256 backward, where a plain
    copy of the streams runs at the same 640 GB/s: HBM sets the pace, not
    the vector unit (final bundles of the kernels compiled for a described
    v5e at 256: 16.5 a [8, 128] tile of every stream forward, 30.5 backward,
    0.35 and 0.66 ms a call at 1.5 GHz), and 512 is twice the code."""
    return next(w for w in (256, _LANES) if c % w == 0)


def default_mhc_rows(batch: int, s: int, c: int, n: int,
                     itemsize: int) -> Optional[int]:
    """THE row-block chooser of both kernels, from the operands' shapes
    and item size alone, for `batch` sequences of s tokens: 128 rows where
    they tile s (at the Xing4 cell's operands 128 / 256 read 0.821 / 0.827
    ms forward and 1.311 / 1.307 backward; my chip run, PR 33), the whole
    of one short sequence, if the backward cell (the larger of the two)
    fits the budget. None where C is not whole lane tiles or no block
    serves s, and then `_mhc_post` runs."""
    if s % _ROWS == 0:
        rows = _ROWS
    elif batch == 1 and s % _GROUP == 0:
        rows = s
    else:
        return None
    fits = (_feas.mhc_vmem_bytes("bwd", rows, c, n, itemsize)
            <= _feas.MHC_VMEM_BUDGET)
    return rows if c % _LANES == 0 and fits else None


def mhc_rows(x, y, h_res, h_post) -> Optional[int]:
    """THE backend / shape gate of `mhc_post`: the chooser's row block on
    the TPU (or where a test pins the kernels, interpreted) for bf16 or
    float32 streams [..., S, n C], else None."""
    if not backend.on_kernels():
        return None
    if x.dtype != y.dtype or x.dtype not in (jnp.bfloat16, jnp.float32):
        return None
    n, c = h_post.shape[-1], y.shape[-1]
    if x.ndim < 2 or x.shape[-1] != n * c or h_res.shape[-1] != n * n:
        return None
    seq = x.shape[-2]
    return default_mhc_rows(y.size // (seq * c), seq, c, n, x.dtype.itemsize)


def _over(trips: int, body):
    """body(k) for k < trips as a rolled loop (one trip: no loop)."""
    if trips == 1:
        body(0)
        return

    def trip(k, carry):
        body(k)
        return carry

    lax.fori_loop(0, trips, trip, 0)


def _lanes(h, k, width):
    """Column k of h [rows, m] spread across `width` lanes."""
    return lax.broadcast_in_dim(h[:, k], (h.shape[0], width), (0,))


def _turned(a, contract):
    """a^T by a product with the identity on the MXU, exact in float32 at
    the highest precision: a [m, rows] contracted over its lanes gives
    [rows, m], a [rows, m] contracted over its rows gives [m, rows]."""
    ident = backend.identity(a.shape[contract])
    operands = (ident, a) if contract else (a, ident)
    return lax.dot_general(
        *operands, (((contract,), (contract,)), ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _mappings(h_ref, rows, n, width):
    """A row group's n n + n mappings, each spread across the lanes."""
    h = h_ref[rows, :]
    return ([[_lanes(h, i * n + j, width) for j in range(n)]
             for i in range(n)],
            [_lanes(h, n * n + i, width) for i in range(n)])


def _mixed(weights, values):
    """sum_k weights[k] * values[k], added in order."""
    acc = weights[0] * values[0]
    for w, v in zip(weights[1:], values[1:]):
        acc = acc + w * v
    return acc


def _fwd_kernel(x_ref, y_ref, ht_ref, out_ref, h_ref, *, n, c):
    chunk = _chunk(c)
    h_ref[...] = _turned(ht_ref[...], 1)

    def group(g):
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        res, post = _mappings(h_ref, rows, n, chunk)

        def columns(k):
            at = pl.multiple_of(k * chunk, chunk)

            def stream(ref, j):
                return ref[rows, pl.ds(at + j * c, chunk)].astype(jnp.float32)

            xs = [stream(x_ref, j) for j in range(n)] + [stream(y_ref, 0)]
            for i in range(n):
                out_ref[rows, pl.ds(at + i * c, chunk)] = _mixed(
                    res[i] + [post[i]], xs).astype(out_ref.dtype)

        _over(c // chunk, columns)

    _over(x_ref.shape[0] // _GROUP, group)


def _bwd_kernel(g_ref, x_ref, y_ref, ht_ref, dx_ref, dy_ref, dht_ref, h_ref,
                dh_ref, *, n, c):
    chunk = _chunk(c)
    h_ref[...] = _turned(ht_ref[...], 1)

    def group(g):
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        res, post = _mappings(h_ref, rows, n, chunk)

        def columns(k, sums):
            at = pl.multiple_of(k * chunk, chunk)

            def stream(ref, j):
                return ref[rows, pl.ds(at + j * c, chunk)].astype(jnp.float32)

            gs = [stream(g_ref, i) for i in range(n)]
            xs = [stream(x_ref, j) for j in range(n)] + [stream(y_ref, 0)]
            for j in range(n):
                dx_ref[rows, pl.ds(at + j * c, chunk)] = _mixed(
                    [res[i][j] for i in range(n)], gs).astype(dx_ref.dtype)
            dy_ref[rows, pl.ds(at, chunk)] = _mixed(post, gs).astype(
                dy_ref.dtype)
            # the sums over C stay one register wide while the columns
            # pass: row-major dH_res, then dH_post
            sums = list(sums)
            for i in range(n):
                for j in range(n + 1):
                    at_sum = n * n + i if j == n else i * n + j
                    product = gs[i] * xs[j]
                    for tile in range(0, chunk, _LANES):
                        sums[at_sum] = (sums[at_sum]
                                        + product[:, tile:tile + _LANES])
            return tuple(sums)

        sums = lax.fori_loop(
            0, c // chunk, columns,
            (jnp.zeros((_GROUP, _LANES), jnp.float32),) * (n * n + n))
        dh_ref[rows, :] = jnp.concatenate(
            [jnp.sum(s, axis=1, keepdims=True) for s in sums], axis=1)

    _over(x_ref.shape[0] // _GROUP, group)
    dht_ref[...] = _turned(dh_ref[...], 0)


def _specs(br, s, n, c):
    """Blocks of [B, S, width] rows, of H^T [n n + n, B S] and the turned
    copy a cell keeps; the grid is (B, S // br)."""
    def rows(width):
        return pl.BlockSpec((None, br, width), lambda b, i: (b, i, 0),
                            memory_space=pltpu.VMEM)

    return (rows(n * c), rows(c),
            pl.BlockSpec((n * n + n, br), lambda b, i: (0, b * (s // br) + i),
                         memory_space=pltpu.VMEM),
            pltpu.VMEM((br, n * n + n), jnp.float32))


def _params(pass_, br, c, n, itemsize):
    # what the cell needs and a slack: what a call reserves, XLA cannot
    # give to the buffers it keeps on the chip around it
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=(_feas.mhc_vmem_bytes(pass_, br, c, n, itemsize)
                          + _feas.MHC_VMEM_SLACK))


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def _mhc_fwd(x, y, ht, *, br, interpret):
    """x [B, S, n C], y [B, S, C], ht [n n + n, B S] (H_res^T over
    H_post^T) -> x' [B, S, n C]. The streams keep the shape the program
    gave them: a reshape in front of the call made XLA write the first
    sublayer's streams (copies of the embeddings) twice. An inner jit,
    like the grouped matmul's: a step's twenty sublayers (and its check
    program's) share one traced and one lowered body."""
    b, s, c = y.shape
    n = x.shape[2] // c
    streams, one, maps, turned = _specs(br, s, n, c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, c=c),
        grid=(b, s // br),
        in_specs=[streams, one, maps],
        out_specs=streams,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[turned],
        compiler_params=_params("fwd", br, c, n, x.dtype.itemsize),
        name="mhc_post_fwd",
        interpret=interpret,
    )(x, y, ht)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def _mhc_bwd(g, x, y, ht, *, br, interpret):
    """The cotangents of `_mhc_fwd`'s three operands, dht in float32."""
    b, s, c = y.shape
    n = x.shape[2] // c
    streams, one, maps, turned = _specs(br, s, n, c)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, c=c),
        grid=(b, s // br),
        in_specs=[streams, streams, one, maps],
        out_specs=[streams, one, maps],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(y.shape, y.dtype),
            jax.ShapeDtypeStruct(ht.shape, jnp.float32),
        ],
        scratch_shapes=[turned, turned],
        compiler_params=_params("bwd", br, c, n, x.dtype.itemsize),
        name="mhc_post_bwd",
        interpret=interpret,
    )(g, x, y, ht)


def _stacked(*maps):
    """The mappings [B, S, k] one over the other with the tokens on the
    lanes, [sum k, B S]: H_res^T over H_post^T for `mhc_post`'s kernels."""
    h = jnp.concatenate(maps, axis=-1)
    return h.reshape(-1, h.shape[-1]).T


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _core(x, y, h_res, h_post, br):
    return _mhc_fwd(x, y, _stacked(h_res, h_post), br=br,
                    interpret=backend.interpret())


def _core_fwd(x, y, h_res, h_post, br):
    # the residuals are the op's inputs, as jax.checkpoint keeps them
    return _core(x, y, h_res, h_post, br), (x, y, h_res, h_post)


def _core_bwd(br, saved, g):
    x, y, h_res, h_post = saved
    dx, dy, dht = _mhc_bwd(g, x, y, _stacked(h_res, h_post), br=br,
                           interpret=backend.interpret())
    split = h_res.shape[-1]
    return (dx, dy, dht[:split].T.reshape(h_res.shape),
            dht[split:].T.reshape(h_post.shape))


_core.defvjp(_core_fwd, _core_bwd)


def mhc_post(x, y, h_res, h_post, br: int):
    """X' = H_res X + H_post^T y over [..., S, n C] streams at the row
    block `mhc_rows` chose: float32 mixing, one rounding to the streams'
    dtype; dH_res and dH_post leave the backward kernel as float32 sums."""
    def sequences(a):  # [B, S, width]; a no-op where a has three axes
        return a.reshape((-1,) + a.shape[-2:])

    out = _core(sequences(x), sequences(y),
                sequences(h_res.astype(jnp.float32)),
                sequences(h_post.astype(jnp.float32)), br)
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# mhc_map: the three mappings of a sublayer, one pass over the streams
# ---------------------------------------------------------------------------
#
#   inv = rsqrt(mean(vec(X)^2) + eps),  l = a (inv X Phi) + b            [m, T]
#   H_pre = sigmoid(l_pre), H_post = 2 sigmoid(l_post),
#   H_res = Sinkhorn(exp(clip(l_res)))          m = 2 n + n n rows, n = 4
#
# Both kernels hold a block of `br` rows of X [B, S, n C] at full width and
# pass its columns `_map_chunk` at a time through the MXU **with X's tile as
# the stationary operand**, so that every result has the tokens on the
# lanes: H^T [m, T] leaves as `mhc_post`'s kernels take it.
#
# **The same numbers without a float32 copy of X.** A float32 is three
# bf16 terms exactly (`_split3`: 8 + 8 + 8 bits), and a bf16 x bf16 product
# is exact in the MXU's float32 accumulator. Phi^T crosses the call as
# those terms stacked on the rows of one bf16 matrix [160, n C],
# `_phi_stack`: hi, mid, lo, hi, hi, mid, sixteen rows of zeros. The
# projection is the stack's first rows against X's bf16 block, one MXU
# pass, and the sum of the hi, mid and lo rows: what `Precision.HIGHEST`
# computes, X having one term. dPhi^T is d(raw)'s three terms against the
# same block. dX contracts over m, so the terms go on the contraction:
# d(raw)'s [hi, hi, hi, mid, lo, mid] against the stack's [hi, mid, lo, hi,
# hi, mid], the six pairs of the highest precision (those left out are
# 2 ** -24 of the product and less), 144 deep. Float32 streams are split
# inside the kernel, all their terms against all of the stack's.
#
# Sinkhorn's rounds are one rolled loop on [n n, br] (row i n + j of the
# block is entry (i, j), the tokens on the lanes); the sums over j and over
# i are sublane rotations. The backward kernel keeps every round's input in
# VMEM (2 x iters blocks of [n n, br] float32) and walks them back by hand:
# y = m / sum(m) gives dm = (dy - sum(dy y)) / sum(m).

# rows of the stack of Phi^T's terms: six of m = 24 and a bf16 tile of zeros
_STACK = 160
# rows of the stack the projection takes: hi, mid, lo and a bf16 tile's rest
_PROJ_ROWS = 80


def _map_chunk(width: int) -> int:
    """Columns a trip of the column loops holds."""
    return next(w for w in (512, 256, _LANES) if width % w == 0)


def default_map_rows(batch: int, s: int, width: int, n: int, itemsize: int,
                     iters: int) -> Optional[int]:
    """THE row-block chooser of `mhc_map`'s kernels, from the shapes alone:
    the tokens lie on the lanes of every small block, so a block is whole
    lane tiles of rows that tile s: 256 where the backward cell (the larger
    of the two) fits the budget, else 128. At the Xing4 cell's operands
    128 / 256 read 0.474 / 0.377 ms forward and 1.168 / 0.978 backward (my
    chip run, PR 37): a cell's epilogue, Sinkhorn's rounds among it, is a
    chain that no DMA hides, and 256 rows halve its share. None where n is
    not 4 (H_res has to start on a sublane tile and the terms' groups on
    bf16 tiles: m = 24), the width is not whole lane tiles a stream or no
    block serves s; then `_mhc_map` runs."""
    if n != 4 or width % (n * _LANES):
        return None
    for rows in (2 * _ROWS, _ROWS):
        if s % rows == 0 and _feas.mhc_map_vmem_bytes(
                "bwd", rows, width, n, itemsize, iters
        ) <= _feas.MHC_VMEM_BUDGET:
            return rows
    return None


def map_rows(x, phi, n: int, iters: int) -> Optional[int]:
    """THE backend / shape gate of `mhc_map`: the chooser's row block on
    the TPU (or where a test pins the kernels, interpreted) for bf16 or
    float32 streams [B, S, n C] and Phi [n C, 2 n + n n], else None."""
    if not backend.on_kernels():
        return None
    if x.dtype not in (jnp.bfloat16, jnp.float32) or x.ndim != 3:
        return None
    if phi.shape != (x.shape[-1], 2 * n + n * n):
        return None
    return default_map_rows(x.shape[0], x.shape[1], x.shape[2], n,
                            x.dtype.itemsize, iters)


def _split3(a, rounded):
    """Float32 a as three float32 terms, each a bf16 value, hi + mid + lo
    = a exactly; `rounded` rounds a float32 to the nearest bf16 value."""
    hi = rounded(a)
    mid = rounded(a - hi)
    return hi, mid, rounded(a - hi - mid)


def _as_bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _phi_stack(phi):
    """Phi [n C, m] float32 -> the bf16 stack [160, n C] of Phi^T's terms.
    `reduce_precision`, not a pair of converts: XLA may drop those."""
    hi, mid, lo = _split3(
        phi.astype(jnp.float32).T,
        lambda a: lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7))
    rows = jnp.concatenate([hi, mid, lo, hi, hi, mid], axis=0)
    return jnp.pad(rows, ((0, _STACK - rows.shape[0]), (0, 0))).astype(
        jnp.bfloat16)


def _terms(x):
    """A block of the streams as bf16 terms that sum to it exactly."""
    if x.dtype == jnp.bfloat16:
        return (x,)
    return tuple(t.astype(jnp.bfloat16) for t in _split3(x, _as_bf16))


def _dot(a, b, contract):
    return lax.dot_general(a, b, ((contract, ((), ()))),
                           preferred_element_type=jnp.float32)


def _folded(acc, m):
    """hi + mid + lo of a product's stacked rows, the small terms first."""
    return acc[2 * m:3 * m] + acc[m:2 * m] + acc[:m]


def _project(x_ref, p_ref, m):
    """(X Phi)^T [m, br] and the rows' sums of squares [1, br], float32,
    while the held block's columns pass once."""
    br, width = x_ref.shape
    kc = _map_chunk(width)

    def columns(k, carry):
        acc, squares = carry
        at = pl.ds(pl.multiple_of(k * kc, kc), kc)
        x = x_ref[:, at]
        for term in _terms(x):
            acc = acc + _dot(p_ref[:_PROJ_ROWS, at], term, ((1,), (1,)))
        xf = x.astype(jnp.float32)
        xf = xf * xf
        for tile in range(0, kc, _LANES):
            squares = squares + xf[:, tile:tile + _LANES]
        return acc, squares

    acc, squares = lax.fori_loop(
        0, width // kc, columns,
        (jnp.zeros((_PROJ_ROWS, br), jnp.float32),
         jnp.zeros((br, _LANES), jnp.float32)))
    # the fold over the lanes lands the sums on them: ones [8, 128] against
    # the partial sums' lanes, exact at the highest precision
    total = lax.dot_general(
        jnp.ones((_GROUP, _LANES), jnp.float32), squares,
        (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return _folded(acc, m), total[:1]


def _sum_j(a, n, row):
    """[n n, br] -> each row's sum over its j (the n rows of its i)."""
    total, at = a, row % n
    for k in range(1, n):
        total = total + jnp.where(at < n - k,
                                  pltpu.roll(a, n * n - k, 0),
                                  pltpu.roll(a, n - k, 0))
    return total


def _sum_i(a, n, row):
    """[n n, br] -> each row's sum over its i (every n-th row)."""
    total = a
    for k in range(1, n):
        total = total + pltpu.roll(a, k * n, 0)
    return total


_SUMS = (_sum_j, _sum_i)  # a round: over j (a row of H_res), then over i


def _rounds(e, iters, n, row, kept=None):
    """Sinkhorn's rounds on e [n n, br]; every division's input goes to
    kept[2 r], kept[2 r + 1] where the backward pass wants them."""
    def round_(r, a):
        for half, total in enumerate(_SUMS):
            if kept is not None:
                kept[2 * r + half] = a
            a = a / total(a, n, row)
        return a

    return lax.fori_loop(0, iters, round_, e)


def _rounds_back(g, y, iters, n, row, kept):
    """The cotangent of e from g, that of y = `_rounds(e)`."""
    def round_(r, carry):
        g, y = carry
        for half in (1, 0):
            a = kept[2 * (iters - 1 - r) + half]
            total = _SUMS[half]
            g = (g - total(g * y, n, row)) / total(a, n, row)
            y = a
        return g, y

    return lax.fori_loop(0, iters, round_, (g, y))[0]


def _logits(x_ref, p_ref, coef_ref, *, n, eps):
    """raw = (X Phi)^T, inv, proj = raw inv and l = a proj + b."""
    m = 2 * n + n * n
    raw, squares = _project(x_ref, p_ref, m)
    inv = lax.rsqrt(squares / x_ref.shape[1] + eps)
    proj = raw * inv
    return raw, inv, proj, coef_ref[:, 0:1] * proj + coef_ref[:, 1:2]


def _gates(logits, n):
    """sigmoid of the first 2 n rows and the factor (1, 2) of each."""
    gate = jax.nn.sigmoid(logits[:2 * n])
    row = lax.broadcasted_iota(jnp.int32, gate.shape, 0)
    return gate, jnp.where(row < n, 1.0, 2.0)


def _map_fwd_kernel(x_ref, p_ref, coef_ref, ht_ref, *, n, eps, iters, lo, hi):
    _, _, _, logits = _logits(x_ref, p_ref, coef_ref, n=n, eps=eps)
    gate, factor = _gates(logits, n)
    ht_ref[:2 * n, :] = factor * gate
    e = jnp.exp(jnp.clip(logits[2 * n:], lo, hi))
    row = lax.broadcasted_iota(jnp.int32, e.shape, 0)
    ht_ref[2 * n:, :] = _rounds(e, iters, n, row)


def _map_bwd_kernel(x_ref, p_ref, coef_ref, dht_ref, dx_ref, dphi_ref,
                    dcoef_ref, kept, *, n, eps, iters, lo, hi):
    m = 2 * n + n * n
    br, width = x_ref.shape
    kc = _map_chunk(width)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)

    raw, inv, proj, logits = _logits(x_ref, p_ref, coef_ref, n=n, eps=eps)
    gate, factor = _gates(logits, n)
    inside = (logits[2 * n:] > lo) & (logits[2 * n:] < hi)
    e = jnp.exp(jnp.clip(logits[2 * n:], lo, hi))
    row = lax.broadcasted_iota(jnp.int32, e.shape, 0)
    res = _rounds(e, iters, n, row, kept)
    de = _rounds_back(dht_ref[2 * n:, :], res, iters, n, row, kept)
    dl = jnp.concatenate(
        [dht_ref[:2 * n, :] * factor * gate * (1.0 - gate),
         jnp.where(inside, de * e, 0.0)], axis=0)
    dcoef_ref[:m, :] += dl * proj       # -> dAlpha, a sum over rows too
    dcoef_ref[m:, :] += dl              # -> dBias
    dproj = dl * coef_ref[:, 0:1]
    draw = dproj * inv
    # inv = (mean(x^2) + eps)^(-1/2): the norm's term of dX is c x
    c = (jnp.sum(dproj * raw, axis=0, keepdims=True)
         * inv * inv * inv * (-1.0 / width))
    d_hi, d_mid, d_lo = _split3(draw, _as_bf16)
    # d(raw)'s terms, the tokens on the lanes, as dPhi's product takes
    # them (hi, mid, lo from row 2 m) and, turned, as dX's (six groups
    # against the stack's; c rides behind them, against the zeros)
    terms = jnp.concatenate(
        [d_hi, d_hi, d_hi, d_mid, d_lo, d_mid,
         jnp.broadcast_to(c, (_STACK - 6 * m, br))], axis=0)
    turned = terms.T
    c_rows = turned[:, 6 * m:6 * m + 1]
    turned = turned.astype(jnp.bfloat16)
    tail = terms[2 * m:2 * m + _PROJ_ROWS].astype(jnp.bfloat16)

    def columns(k):
        at = pl.ds(pl.multiple_of(k * kc, kc), kc)
        x = x_ref[:, at]
        acc = jnp.zeros((_PROJ_ROWS, kc), jnp.float32)
        for term in _terms(x):
            acc = acc + _dot(tail, term, ((1,), (0,)))
        dphi_ref[:, at] += _folded(acc, m)
        dx = _dot(turned, p_ref[:, at], ((1,), (0,)))
        dx_ref[:, at] = (dx + c_rows * x.astype(jnp.float32)).astype(
            dx_ref.dtype)

    _over(width // kc, columns)


def _map_specs(br, s, width, m):
    """Blocks of [B, S, n C] rows, the stack, the [m, 2] coefficients (a
    and b of each row) and H^T [m, B S]; the grid is (B, S // br)."""
    def whole(*shape):
        return pl.BlockSpec(shape, lambda b, i: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    return (pl.BlockSpec((None, br, width), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            whole, whole(m, 2),
            pl.BlockSpec((m, br), lambda b, i: (0, b * (s // br) + i),
                         memory_space=pltpu.VMEM))


def _map_params(pass_, semantics, br, width, n, itemsize, iters):
    return pltpu.CompilerParams(
        dimension_semantics=(semantics,) * 2,
        vmem_limit_bytes=(_feas.mhc_map_vmem_bytes(
            pass_, br, width, n, itemsize, iters) + _feas.MHC_VMEM_SLACK))


_MAP_STATICS = ("n", "eps", "iters", "lo", "hi", "br", "interpret")


@functools.partial(jax.jit, static_argnames=_MAP_STATICS)
def _map_fwd(x, stack, coef, *, n, eps, iters, lo, hi, br, interpret):
    """x [B, S, n C], `_phi_stack(phi)`, coef [m, 2] -> H^T [m, B S]
    float32: H_pre^T over H_post^T over H_res^T. An inner jit, like
    `_mhc_fwd`: a step's sublayers share one traced and lowered body."""
    b, s, width = x.shape
    m = 2 * n + n * n
    streams, whole, coefs, maps = _map_specs(br, s, width, m)
    return pl.pallas_call(
        functools.partial(_map_fwd_kernel, n=n, eps=eps, iters=iters, lo=lo,
                          hi=hi),
        grid=(b, s // br),
        in_specs=[streams, whole(_PROJ_ROWS, width), coefs],
        out_specs=maps,
        out_shape=jax.ShapeDtypeStruct((m, b * s), jnp.float32),
        compiler_params=_map_params("fwd", "parallel", br, width, n,
                                    x.dtype.itemsize, iters),
        name="mhc_map_fwd",
        interpret=interpret,
    )(x, stack, coef)


@functools.partial(jax.jit, static_argnames=_MAP_STATICS)
def _map_bwd(x, stack, coef, dht, *, n, eps, iters, lo, hi, br, interpret):
    """The cotangents from dht, that of `_map_fwd`'s H^T: dX in x's dtype;
    dPhi^T [m, n C] and [2 m, br] (rows of sum dl proj over rows of sum dl,
    the lanes still to be summed), float32 sums over the row blocks."""
    b, s, width = x.shape
    m = 2 * n + n * n
    streams, whole, coefs, maps = _map_specs(br, s, width, m)
    return pl.pallas_call(
        functools.partial(_map_bwd_kernel, n=n, eps=eps, iters=iters, lo=lo,
                          hi=hi),
        grid=(b, s // br),
        in_specs=[streams, whole(_STACK, width), coefs, maps],
        out_specs=[streams, whole(m, width), whole(2 * m, br)],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((m, width), jnp.float32),
            jax.ShapeDtypeStruct((2 * m, br), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((2 * iters, n * n, br), jnp.float32)],
        # the sums over the row blocks stay in VMEM from cell to cell
        compiler_params=_map_params("bwd", "arbitrary", br, width, n,
                                    x.dtype.itemsize, iters),
        name="mhc_map_bwd",
        interpret=interpret,
    )(x, stack, coef, dht)


def _coef(bias, alpha, n):
    """[m, 2]: each row's a (of its mapping) beside its b."""
    a = jnp.repeat(alpha.astype(jnp.float32), np.array([n, n, n * n]))
    return jnp.stack([a, bias.astype(jnp.float32)], axis=1)


def _map_kwargs(static):
    return dict(zip(_MAP_STATICS, static + (backend.interpret(),)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _map_core(x, phi, bias, alpha, static):
    return _map_fwd(x, _phi_stack(phi), _coef(bias, alpha, static[0]),
                    **_map_kwargs(static))


def _map_core_fwd(x, phi, bias, alpha, static):
    # the residuals are the op's inputs, as jax.checkpoint keeps them
    return _map_core(x, phi, bias, alpha, static), (x, phi, bias, alpha)


def map_cotangents(x, phi, bias, alpha, dht, static):
    """(dX, dPhi, dBias, dAlpha) from dht, the cotangent of H^T, by
    `mhc_map_bwd`: one pass over the streams."""
    n = static[0]
    m = 2 * n + n * n
    dx, dphi_t, dcoef = _map_bwd(x, _phi_stack(phi), _coef(bias, alpha, n),
                                 dht, **_map_kwargs(static))
    dcoef = jnp.sum(dcoef, axis=1)
    dalpha = jnp.stack([jnp.sum(dcoef[:n]), jnp.sum(dcoef[n:2 * n]),
                        jnp.sum(dcoef[2 * n:m])])
    return (dx, dphi_t.T.astype(phi.dtype), dcoef[m:].astype(bias.dtype),
            dalpha.astype(alpha.dtype))


def _composition_t(x, phi, bias, alpha, static):
    """H^T [m, B S] by `latent_ops._mhc_map`."""
    from .. import latent_ops

    n, eps, iters, lo, hi, _ = static
    return _stacked(*latent_ops._mhc_map(
        x, phi, bias, alpha, n=n, eps=eps, iters=iters, clamp_min=lo,
        clamp_max=hi)[:3])


def _map_core_bwd(static, saved, dht):
    # NOT `map_cotangents`, which is 3.4 times as fast (0.98 against 3.36
    # ms a call at the Xing4 cell's operands, and the cell's step 249.4
    # against 275.3 ms; my chip runs, PR 37): with the backward kernel the
    # cell's compiled step reads 14.895 GB where the parent's reads 14.312,
    # four times the benchmark's bound on `peak_hbm_gb`. XLA fits a step
    # under 15.0 GB and no further: the composition's float32 copy of the
    # streams, 0.47 GB in every sublayer's backward pass, is what made it
    # rematerialize a norm's and a projection's output in each layer, and
    # without the copy it keeps them (PERF.md section 6, PR 37). The kernel
    # goes in with a change that lowers what the step keeps from its
    # forward pass (ROADMAP.md S13).
    return jax.vjp(lambda *a: _composition_t(*a, static), *saved)[1](dht)


_map_core.defvjp(_map_core_fwd, _map_core_bwd)


def mhc_map(x, phi, bias, alpha, br: int, *, n, eps, iters, clamp_min,
            clamp_max):
    """`latent_ops._mhc_map` at the row block `map_rows` chose: H_pre,
    H_post [B, S, n], H_res [B, S, n n] and the gap [n], float32, by
    `mhc_map_fwd`; the cotangents by the composition (`_map_core_bwd` says
    why). The mappings leave the kernel as H^T with the tokens on the
    lanes; the `.T.reshape` here and `_stacked`'s in front of `mhc_post`'s
    kernels undo one another."""
    b, s, _ = x.shape
    ht = _map_core(x, phi, bias, alpha,
                   (n, eps, iters, clamp_min, clamp_max, br))
    res = ht[2 * n:]
    by_entry = lax.stop_gradient(res).reshape(n, n, b * s)
    gap = jnp.maximum(
        jnp.max(jnp.abs(jnp.sum(by_entry, axis=1) - 1.0), axis=-1),
        jnp.max(jnp.abs(jnp.sum(by_entry, axis=0) - 1.0), axis=-1))
    return (ht[:n].T.reshape(b, s, n), ht[n:2 * n].T.reshape(b, s, n),
            res.T.reshape(b, s, n * n), gap)
