"""`mhc_post` of the hyper-connected residual (ops/latent_ops.py) as two
Pallas kernels for the TPU, each one pass over its operands.

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
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import feasible as _feas
from .flash_attention import _identity, _interpret

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
    from ..attention import FORCE_PALLAS

    if _interpret() and not FORCE_PALLAS:
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
    ident = _identity(a.shape[contract])
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


def _stacked(h_res, h_post):
    """H_res^T over H_post^T, [n n + n, B S]: the tokens on the lanes."""
    h = jnp.concatenate([h_res, h_post], axis=-1)
    return h.reshape(-1, h.shape[-1]).T


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _core(x, y, h_res, h_post, br):
    return _mhc_fwd(x, y, _stacked(h_res, h_post), br=br,
                    interpret=_interpret())


def _core_fwd(x, y, h_res, h_post, br):
    # the residuals are the op's inputs, as jax.checkpoint keeps them
    return _core(x, y, h_res, h_post, br), (x, y, h_res, h_post)


def _core_bwd(br, saved, g):
    x, y, h_res, h_post = saved
    dx, dy, dht = _mhc_bwd(g, x, y, _stacked(h_res, h_post), br=br,
                           interpret=_interpret())
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
