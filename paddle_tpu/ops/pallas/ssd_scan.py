"""`ssd_scan` of the Mamba-2 mixer (ops/ssm_ops.py) as two Pallas kernels
for the TPU: `ssd_scan_fwd` and `ssd_scan_bwd`, under one `custom_vjp`.

The mathematics is `ssm_ops._ssd_chunked`'s, chunk by chunk (Q positions,
i / j a position in the chunk, cum the running sum of dt A over it):

    y_i  = sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
           + exp(cum_i) C_i S^T + D x_i
    S   <- exp(cum_Q) S + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T

with S [P, N] the state a head carries into the chunk. Both kernels run a
grid over (batch, group, chunk), the chunk axis in order: a group's R heads
share its B and C, so a cell takes the group's [R P, Q] rows of x, its
[N, Q] of B and C, and [R, Q] of dt. **Every per-position array crosses
the call turned, the positions last** (x, dt, B, C, the cotangent, y, dx,
ddt, dB, dC: [B, H P, S], [B, H, S], [B, G N, S]; the turns are
`jnp.swapaxes` outside, which XLA lays into its neighbours' layouts), so a
block arrives with the positions on the lanes. The [Q, Q] matrices (C B^T,
the masked decays, their product with dt) live in VMEM a head at a time
and never reach HBM, and the states travel from chunk to chunk in VMEM
scratch, float32: the [S/Q, S/Q] product over the chunks of the
composition is this carry.

**A group wider than a cell's heads is split** (`_head_block`): a group of
more than `_MAX_HEADS` heads goes in blocks of 16 or 8, one block a cell,
the grid's middle axis then over (group, block); each block reads its
group's B and C, and, backward, writes dB and dC of its own heads as a
float32 partial that `_scan_bwd` sums over the blocks (dB and dC are sums
over a group's heads; no two cells write one block). At Q 256 the [Q, Q]
matrices stay whole: the compiler folds the masked upper half's exps
itself, and 128 x 128 tiles on or below the diagonal ran about as fast
(TPU v5e, one group of 64 heads of 64 at S 4096: a forward pass and VJP
1.224 ms in tiles and 1.250 whole, the forward kernel 0.239 and 0.228).

**Inside a cell the positions lie on the lanes**, as the blocks bring
them: every per-position vector of a head (cum, dt, the decays to the
chunk's end, their cotangents) is one [1, Q] row and the work on x is a
batch of [R, P, Q]; the heads' states stack to [R P, N], so that the
read-out, what a chunk leaves behind and their cotangents are one product
each for the whole group. Only the [Q, Q] matrices go a head at a time,
through an unrolled loop. What a cell still turns is what a product needs
with the contracted axis first: C's block for C B^T, and, backward, the
cotangent (once, for each head's [Q, P] of it), the states and dS for dC
and dB.

**The backward kernel sweeps twice.** Its grid's last axis is 2 S/Q long:
the first half walks the chunks forward and keeps every state that enters
one in VMEM (S/Q x R x [P, N] float32: 8.4 MB a group at the Nemotron
cell, whose cells take two groups, `_cell`), the second walks them back,
carries dS in VMEM and gives dx, ddt, dB and dC a chunk at a time; dA and
dD leave as per-position partial sums, summed outside. The residuals are
the scan's inputs, as `jax.checkpoint` kept them.

Products take x's dtype in and accumulate in float32, as the composition's;
decays, running sums, dt and the carried states are float32; the read-out
takes the state in x's dtype. Cotangents that are float32 products go to
the MXU in x's dtype, as XLA's default precision rounds them on the TPU.
The gate (`kernel_fits`) reads shapes, dtypes and the platform alone.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from . import feasible as _feas

_F32 = jnp.float32
_LANES = 128
# a cell's heads are unrolled: the body is one head's, R times over
_MAX_HEADS = 16


def _widths(r: int, p: int):
    """The heads a cell may take of a group of r, widest first: the whole
    group where r <= `_MAX_HEADS`, else blocks of 16 or 8 that divide it;
    each whole sublane tiles of dt and its rows of x whole lane tiles."""
    return [w for w in ((r,) if r <= _MAX_HEADS else (16, 8))
            if w % 8 == 0 and r % w == 0 and (w * p) % _LANES == 0]


def _head_block(s, r, p, n, q, itemsize) -> Optional[int]:
    """The heads of a group of r that a grid cell takes: the widest of
    `_widths` whose backward cell is within the budget, or None."""
    for w in _widths(r, p):
        if _feas.ssd_scan_vmem_bytes("bwd", s, q, w, p, n, itemsize,
                                     blocks=r // w) <= _feas.SSD_VMEM_BUDGET:
            return w
    return None


def kernel_fits_reason(s: int, heads: int, head_dim: int, groups: int,
                       state: int, chunk: int, dtype) -> Optional[str]:
    """The checks of `kernel_fits`, in order; the first that refuses the
    shapes by its name (`dtype`, `groups`, `lanes`, `heads_per_group`,
    `vmem`), or None where all pass."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return "dtype"
    if groups < 1 or heads % groups:
        return "groups"
    r = heads // groups
    if chunk % _LANES or s % chunk or state % _LANES or head_dim % 8:
        return "lanes"
    if not _widths(r, head_dim):
        return "heads_per_group"
    if _head_block(s, r, head_dim, state, chunk,
                   jnp.dtype(dtype).itemsize) is None:
        return "vmem"
    return None


def kernel_fits(s: int, heads: int, head_dim: int, groups: int, state: int,
                chunk: int, dtype) -> bool:
    """THE shape gate of the kernels, for a row of s positions (a multiple
    of the chunk): the chunk and the state whole lane tiles, a head whole
    sublane tiles, bf16 or float32; a group of 8 or 16 heads, or of more
    that blocks of 16 or 8 divide (a cell's heads whole sublane tiles of dt
    and its rows of x whole lane tiles), and the backward cell (the
    larger) under the budget at that block. Else the composition runs."""
    return kernel_fits_reason(s, heads, head_dim, groups, state, chunk,
                              dtype) is None


def use_kernels(s, heads, head_dim, groups, state, chunk, dtype) -> bool:
    """`kernel_fits` where `backend.on_kernels`."""
    return backend.on_kernels() and kernel_fits(
        s, heads, head_dim, groups, state, chunk, dtype)


# ---------------------------------------------------------------------------
# what a cell computes
# ---------------------------------------------------------------------------


def _operands(a, b):
    """A product's operands: as they are on the TPU; interpreted, in
    float32 (exact for bf16 values: XLA's CPU runtime has no bf16 x bf16 ->
    float32 product of every shape)."""
    if backend.interpret():
        return a.astype(_F32), b.astype(_F32)
    return a, b


def _nt(a, b):
    """a [m, k] . b [n, k]^T, float32 accumulation."""
    return lax.dot_general(*_operands(a, b), (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _dot(a, b):
    return jnp.dot(*_operands(a, b), preferred_element_type=_F32)


def _tn(a, b):
    """a [k, m]^T . b [k, n], float32 accumulation: a turned in the cell,
    as float32, and rounded to b's dtype."""
    return _dot(a.astype(_F32).T.astype(b.dtype), b)


def _iotas(q):
    return (lax.broadcasted_iota(jnp.int32, (q, q), 0),
            lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _running(t, reverse=False):
    """Inclusive running sums of float32 t [R, Q] along the lanes (from the
    last lane back where `reverse`): one bf16 product with a triangle of
    ones, t split into three bf16 terms that sum to it exactly, so every
    product is exact and the sums are float32's."""
    r, q = t.shape
    i, j = _iotas(q)
    tri = ((i >= j) if reverse else (i <= j)).astype(jnp.bfloat16)
    hi = t.astype(jnp.bfloat16)
    mid = (t - hi.astype(_F32)).astype(jnp.bfloat16)
    lo = (t - hi.astype(_F32) - mid.astype(_F32)).astype(jnp.bfloat16)
    sums = _dot(jnp.concatenate([hi, mid, lo], axis=0), tri)
    return sums[:r] + sums[r:2 * r] + sums[2 * r:]


class _Chunk:
    """A cell's per-position rows [R, Q] of one group and chunk, float32:
    dt, cum (the running sum of dt A), cum_Q across the lanes (and
    exp(cum_Q) across the state's n lanes), and w_j = exp(cum_Q - cum_j)
    dt_j, what position j leaves in the state. cum_Q goes through VMEM
    (`last_ref`): a head's row of it then spreads over the sublanes alone
    (Mosaic has no broadcast of one value over both)."""

    def __init__(self, dt_ref, a_ref, last_ref, n):
        self.dt = dt = dt_ref[...]
        q = dt.shape[1]
        self.cum = _running(dt * a_ref[...])
        last_ref[...] = jnp.broadcast_to(self.cum[:, q - 1:q],
                                         last_ref.shape)
        last = last_ref[...]
        self.grow_last = jnp.exp(last[:, :n])
        self.to_end = jnp.exp(last[:, :q] - self.cum)
        self.w = self.to_end * dt


def _stack(a, r, p):
    """[R P, X] <-> [R, P, X]: a cell's heads one over the other."""
    if a.ndim == 2:
        return a.reshape(r, p, a.shape[1])
    return a.reshape(r * p, a.shape[2])


def _columns(ch, crep_ref):
    """Each head's cum as a column spread across the lanes, [R, Q, Q]:
    cum_i of `_decay`, made for the whole cell in front of its heads."""
    q = ch.cum.shape[1]
    cum_t = ch.cum.T
    for r in range(crep_ref.shape[0]):
        crep_ref[r] = jnp.broadcast_to(cum_t[:, r:r + 1], (q, q))


def _decay(cum_ref, crep_ref, r, causal):
    """Head r's masked decays exp(cum_i - cum_j) [i, j], i >= j. The mask
    goes in front of exp: a masked difference is a positive sum of decays'
    logarithms and would overflow."""
    row = cum_ref[pl.ds(r, 1), :]
    return jnp.exp(jnp.where(causal, crep_ref[r] - row, -jnp.inf))


def _causal(q):
    i, j = _iotas(q)
    return i >= j


class _Groups:
    """The cell's `groups` groups of `per` heads (or, where a group is split
    into `blocks` blocks, one block of `per` heads): the [N, Q] rows of a
    B or C block, and a [R P, X] stack's rows, by group."""

    def __init__(self, groups, per, p, n, blocks=1):
        self.groups, self.per, self.p, self.n = groups, per, p, n
        self.blocks = blocks

    def bc(self, a, g):
        return a[g * self.n:(g + 1) * self.n]

    def rows(self, a, g):
        width = self.per * self.p
        return a[g * width:(g + 1) * width]

    def each(self, fn):
        """fn(g) of every group, one over the other."""
        parts = [fn(g) for g in range(self.groups)]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)


def _left(ch, x_t, b, gr, mx):
    """What the chunk leaves in the heads' states, [R P, N]."""
    heads = gr.groups * gr.per
    xw = _stack(_stack(x_t, heads, gr.p) * ch.w[:, None, :], heads, gr.p)
    xw = xw.astype(mx)
    return gr.each(lambda g: _nt(gr.rows(xw, g), gr.bc(b, g)))


def _cb(c, b, gr, cb_ref):
    """C B^T [i, j] of each group into `cb_ref`, from the turned blocks."""
    for g in range(gr.groups):
        cb_ref[g] = _tn(gr.bc(c, g), gr.bc(b, g))


def _over_heads(heads, head):
    """head(r) for each of the cell's heads, unrolled where it lowers: as a
    rolled loop every head waited for its own products and transposes (my
    chip runs, PR 39: the forward kernel 1.34 ms a call rolled, 0.68
    unrolled)."""
    lax.fori_loop(0, heads, head, 0, unroll=True)


def _head_rows(r, p):
    """Head r's rows of a [R P, X] stack (at a multiple of p's largest
    power-of-two factor: bf16 rows pack two to a sublane)."""
    return pl.ds(pl.multiple_of(r * p, p & -p), p)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, s_ref,
                yt_ref, cb_ref, cum_ref, crep_ref, last_ref, *, gr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    mx = x_ref.dtype
    heads, p = gr.groups * gr.per, gr.p
    b, c = b_ref[...], c_ref[...]                      # [G N, Q]
    ch = _Chunk(dt_ref, a_ref, last_ref, gr.n)
    cum_ref[...] = ch.cum
    _columns(ch, crep_ref)
    _cb(c, b, gr, cb_ref)
    causal = _causal(ch.dt.shape[1])

    def head(r, carry):
        m = (cb_ref[r // gr.per] * _decay(cum_ref, crep_ref, r, causal)
             * dt_ref[pl.ds(r, 1), :]).astype(mx)
        rows = _head_rows(r, p)
        # y^T [P, i] = x^T [P, j] M^T
        yt_ref[rows, :] = _nt(x_ref[rows, :], m)
        return carry

    _over_heads(heads, head)
    x_t = x_ref[...].astype(_F32)                      # [R P, Q]
    s = s_ref[...]
    s_rows = _stack(s, heads, p).astype(mx)
    y_in = gr.each(lambda g: _dot(gr.rows(s_rows, g), gr.bc(c, g)))
    y = (_stack(yt_ref[...], heads, p)
         + _stack(y_in, heads, p) * jnp.exp(ch.cum)[:, None, :]
         + d_ref[...][:, None, :] * _stack(x_t, heads, p))
    y_ref[...] = _stack(y, heads, p).astype(y_ref.dtype)
    s_ref[...] = (ch.grow_last[:, None, :] * s
                  + _stack(_left(ch, x_t, b, gr, mx), heads, p))


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, g_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                states, carry, *scratch, gr, chunks):
    k = pl.program_id(2)
    mx = x_ref.dtype
    sc = _BwdScratch(*scratch)

    @pl.when(k == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    @pl.when(k < chunks)
    def _():
        # forward: the states entering chunk k, kept for the way back
        ch = _Chunk(dt_ref, a_ref, sc.last, gr.n)
        s = carry[...]
        states[k] = s
        carry[...] = (ch.grow_last[:, None, :] * s + _stack(
            _left(ch, x_ref[...].astype(_F32), b_ref[...], gr, mx),
            gr.groups * gr.per, gr.p))

    @pl.when(k == chunks)
    def _():
        carry[...] = jnp.zeros_like(carry)

    @pl.when(k >= chunks)
    def _():
        _bwd_chunk(states.at[2 * chunks - 1 - k], x_ref, dt_ref, a_ref,
                   b_ref, c_ref, d_ref, g_ref, dx_ref, ddt_ref, db_ref,
                   dc_ref, da_ref, dd_ref, carry, sc, gr=gr, mx=mx)


class _BwdScratch(NamedTuple):
    """The backward kernel's VMEM beyond the states and dS: each head's
    [Q, P] of the cotangent, dx [R P, Q] from the [Q, Q] matrices, C B^T
    and d(C B^T) a group, cum and its columns, cum_Q, and the per-head rows
    of ddt and d cum from the [Q, Q] matrices."""
    gr: Any
    dxt: Any
    cb: Any
    dcb: Any
    cum: Any
    crep: Any
    last: Any
    ddt_in: Any
    dcum_in: Any


def _bwd_chunk(s_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, g_ref,
               dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref, carry, sc, *,
               gr, mx):
    """A chunk on the way back: `s_ref` the states entering it, `carry` dS
    of those leaving it. With M_ij = (C_i . B_j) L_ij dt_j, L the masked
    decays, and dM = g x^T: dx^T = g^T M, d(C B^T) = dM o L dt_j, and
    through cum the sums of dM o M over j (at i) and over i (at j,
    negative)."""
    heads, p = gr.groups * gr.per, gr.p
    b, c = b_ref[...], c_ref[...]                      # [G N, Q]
    ch = _Chunk(dt_ref, a_ref, sc.last, gr.n)
    q = ch.dt.shape[1]
    sc.cum[...] = ch.cum
    _columns(ch, sc.crep)
    _cb(c, b, gr, sc.cb)
    sc.dcb[...] = jnp.zeros_like(sc.dcb)
    x_t = x_ref[...].astype(_F32)                      # [R P, Q]
    g_t = g_ref[...].astype(_F32)
    # each head's [Q, P] of the cotangent, the operand of dM = g x^T
    g_pos = g_t.T
    for r in range(heads):
        sc.gr[r] = g_pos[:, r * p:(r + 1) * p].astype(mx)
    causal = _causal(q)

    def head(r, carry_):
        decay = _decay(sc.cum, sc.crep, r, causal)
        dt_row = dt_ref[pl.ds(r, 1), :]
        e = sc.cb[r // gr.per] * decay
        rows = _head_rows(r, p)
        sc.dxt[rows, :] = _dot(g_ref[rows, :], (e * dt_row).astype(mx))
        dm = _dot(sc.gr[r], x_ref[rows, :])          # [i, j]
        f = dm * e
        sc.ddt_in[pl.ds(r, 1), :] = jnp.sum(f, axis=0, keepdims=True)
        # sum_j f_ij dt_j, with the positions i on the lanes
        sc.dcum_in[pl.ds(r, 1), :] = jnp.sum((f * dt_row).T, axis=0,
                                              keepdims=True)
        sc.dcb[r // gr.per] += dm * decay * dt_row
        return carry_

    _over_heads(heads, head)
    x3, g3 = _stack(x_t, heads, p), _stack(g_t, heads, p)
    s, ds = s_ref[...], carry[...]                     # [R, P, N]
    s_rows, ds_rows = _stack(s, heads, p), _stack(ds, heads, p)
    y_in = _stack(gr.each(lambda g: _dot(gr.rows(s_rows, g).astype(mx),
                                         gr.bc(c, g))), heads, p)  # [R, P, i]
    dxw = _stack(gr.each(lambda g: _dot(gr.rows(ds_rows, g).astype(mx),
                                        gr.bc(b, g))), heads, p)   # [R, P, j]
    grow = jnp.exp(ch.cum)
    w = ch.w
    dw = jnp.sum(x3 * dxw, axis=1)
    ddt_in = sc.ddt_in[...]
    dcum = (sc.dcum_in[...] - ch.dt * ddt_in
            + grow * jnp.sum(g3 * y_in, axis=1) - w * dw)
    lane = lax.broadcasted_iota(jnp.int32, dcum.shape, 1)
    d_last = (jnp.exp(ch.cum[:, q - 1:q])
              * jnp.sum(jnp.sum(ds * s, axis=1), axis=1, keepdims=True)
              + jnp.sum(w * dw, axis=1, keepdims=True))
    dcum = dcum + jnp.where(lane == q - 1, d_last, 0.0)
    dx = (_stack(sc.dxt[...], heads, p) + dxw * w[:, None, :]
          + d_ref[...][:, None, :] * g3)
    dx_ref[...] = _stack(dx, heads, p).astype(dx_ref.dtype)
    # [R P, Q]: x w and g exp(cum)
    xw = _stack(x3 * w[:, None, :], heads, p).astype(mx)
    dye = _stack(g3 * grow[:, None, :], heads, p).astype(mx)
    for g in range(gr.groups):
        dcb = sc.dcb[g].astype(mx)                     # [i, j]
        # dB^T [N, j] = dS^T (x w) + C^T d(C B^T), dC^T [N, i] likewise
        db = (_tn(gr.rows(ds_rows, g), gr.rows(xw, g))
              + _dot(gr.bc(c, g), dcb))
        dc = (_tn(gr.rows(s_rows, g), gr.rows(dye, g))
              + _nt(gr.bc(b, g), dcb))
        db_ref[g * gr.n:(g + 1) * gr.n] = db.astype(db_ref.dtype)
        dc_ref[g * gr.n:(g + 1) * gr.n] = dc.astype(dc_ref.dtype)
    # dS of the states entering the chunk: (g exp(cum))^T C
    d_in = gr.each(lambda g: _nt(gr.rows(dye, g), gr.bc(c, g)))
    carry[...] = ch.grow_last[:, None, :] * ds + _stack(d_in, heads, p)
    # d(dt A)_k = sum_{i >= k} d cum_i
    d_dta = _running(dcum, reverse=True)
    ddt_ref[...] = ddt_in + ch.to_end * dw + a_ref[...] * d_dta
    da_ref[...] += ch.dt * d_dta
    dd_ref[...] += jnp.sum(g3 * x3, axis=1)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------


def _cell(h, groups, p, n, s, q, itemsize):
    """The groups a grid cell takes: two where they divide the groups and
    the backward cell stays within `_MAX_HEADS` heads and the budget (two
    groups' independent work lets the scheduler hide either's latencies,
    and halves the cells), else one; a block of a group wider than
    `_MAX_HEADS` (`_head_block`). From the shapes alone."""
    per = h // groups
    if per > _MAX_HEADS:
        width = _head_block(s, per, p, n, q, itemsize)
        return _Groups(1, width, p, n, blocks=per // width)
    two = (groups % 2 == 0 and 2 * per <= _MAX_HEADS
           and _feas.ssd_scan_vmem_bytes("bwd", s, q, 2 * per, p, n, itemsize,
                                         groups=2) <= _feas.SSD_VMEM_BUDGET)
    return _Groups(2 if two else 1, per, p, n)


def _specs(q, gr, chunk_of):
    """Blocks of the turned arrays, x [B, H P, S], dt [B, H, S], B / C
    [B, G N, S], and of a [H, 1] and D [H, Q], over the grid (b, cell, k);
    `chunk_of(k)` is the chunk of step k (a pair: that of x, dt, B and of
    C, the cotangent and the outputs). `turned(.., shared=True)` is B's or
    C's: one block for all the cells of a split group."""
    heads = gr.groups * gr.per

    def turned(width, late=False, shared=False):
        blocks = gr.blocks if shared else 1
        return pl.BlockSpec(
            (None, width, q),
            lambda bi, g, k: (bi, g // blocks if blocks > 1 else g,
                              chunk_of(k)[int(late)]),
            memory_space=pltpu.VMEM)

    def per_head(width):
        return pl.BlockSpec((heads, width), lambda bi, g, k: (g, 0),
                            memory_space=pltpu.VMEM)

    return turned, per_head


def _params(pass_, s, q, gr, itemsize):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=(_feas.ssd_scan_vmem_bytes(
            pass_, s, q, gr.groups * gr.per, gr.p, gr.n, itemsize,
            groups=gr.groups, blocks=gr.blocks) + _feas.SSD_VMEM_SLACK))


def _scratch(q, gr):
    """What both kernels keep in VMEM: the states [R, P, N], what the
    [Q, Q] matrices give (y forward, dx backward) [R P, Q], C B^T a group
    [Q, Q], cum [R, Q] and its columns [R, Q, Q], cum_Q across max(Q, N)
    lanes."""
    r, p, n = gr.groups * gr.per, gr.p, gr.n
    return [pltpu.VMEM((r, p, n), _F32), pltpu.VMEM((r * p, q), _F32),
            pltpu.VMEM((gr.groups, q, q), _F32), pltpu.VMEM((r, q), _F32),
            pltpu.VMEM((r, q, q), _F32), pltpu.VMEM((r, max(q, n)), _F32)]


def _shapes(x, dt_t, b, groups, chunk):
    bsz, width, s = x.shape
    h = dt_t.shape[1]
    p, n = width // h, b.shape[1] // groups
    gr = _cell(h, groups, p, n, s, chunk, x.dtype.itemsize)
    return bsz, s, gr, (bsz, groups * gr.blocks // gr.groups)


@functools.partial(jax.jit, static_argnames=("groups", "chunk", "interpret"))
def _scan_fwd(x, dt_t, a, b, c, d, *, groups, chunk, interpret):
    """x [B, H P, S], dt_t [B, H, S], a [H, 1], d [H, Q] float32, b / c
    [B, G N, S] -> y [B, H P, S], every per-position array turned. An
    inner jit: a step's layers (and its check program's) share one traced
    and one lowered body."""
    bsz, s, gr, cells = _shapes(x, dt_t, b, groups, chunk)
    heads = gr.groups * gr.per
    turned, per_head = _specs(chunk, gr, lambda k: (k, k))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, gr=gr),
        grid=cells + (s // chunk,),
        in_specs=[turned(heads * gr.p), turned(heads), per_head(1),
                  turned(gr.groups * gr.n, shared=True),
                  turned(gr.groups * gr.n, shared=True), per_head(chunk)],
        out_specs=turned(heads * gr.p),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=_scratch(chunk, gr),
        compiler_params=_params("fwd", s, chunk, gr, x.dtype.itemsize),
        name="ssd_scan_fwd",
        interpret=interpret,
    )(x, dt_t, a, b, c, d)


@functools.partial(jax.jit, static_argnames=("groups", "chunk", "interpret"))
def _scan_bwd(x, dt_t, a, b, c, d, g, *, groups, chunk, interpret):
    """The cotangents from g [B, H P, S], that of `_scan_fwd`'s y: dx, dB,
    dC turned in their dtypes, ddt_t [B, H, S] and the partial sums of dA
    and dD [B, H, Q] (over the batch and the lanes still to be summed),
    float32. Where a group is split, each block's dB and dC leave as a
    float32 partial [B, G blocks N, S], summed here."""
    bsz, s, gr, cells = _shapes(x, dt_t, b, groups, chunk)
    heads, p = gr.groups * gr.per, gr.p
    nc = s // chunk

    def chunk_of(k):
        back = 2 * nc - 1 - k
        return (jnp.where(k < nc, k, back), jnp.where(k < nc, nc - 1, back))

    turned, per_head = _specs(chunk, gr, chunk_of)
    sums = pl.BlockSpec((None, heads, chunk), lambda bi, gi, k: (bi, gi, 0),
                        memory_space=pltpu.VMEM)
    partial = jax.ShapeDtypeStruct((bsz, dt_t.shape[1], chunk), _F32)
    bc = gr.groups * gr.n
    if gr.blocks > 1:
        d_bc = jax.ShapeDtypeStruct((bsz, groups * gr.blocks * gr.n, s), _F32)
    else:
        d_bc = jax.ShapeDtypeStruct(b.shape, b.dtype)
    carry, dx_t, cb, cum, crep, last = _scratch(chunk, gr)
    dx, ddt_t, db, dc, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, gr=gr, chunks=nc),
        grid=cells + (2 * nc,),
        in_specs=[turned(heads * p), turned(heads), per_head(1),
                  turned(bc, shared=True), turned(bc, True, shared=True),
                  per_head(chunk), turned(heads * p, True)],
        out_specs=[turned(heads * p, True), turned(heads, True),
                   turned(bc, True), turned(bc, True), sums, sums],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt_t.shape, _F32), d_bc, d_bc,
                   partial, partial],
        scratch_shapes=[
            pltpu.VMEM((nc, heads, p, gr.n), _F32), carry,
            pltpu.VMEM((heads, chunk, p), x.dtype), dx_t, cb,
            pltpu.VMEM((gr.groups, chunk, chunk), _F32), cum, crep, last,
            pltpu.VMEM((heads, chunk), _F32),
            pltpu.VMEM((heads, chunk), _F32)],
        compiler_params=_params("bwd", s, chunk, gr, x.dtype.itemsize),
        name="ssd_scan_bwd",
        interpret=interpret,
    )(x, dt_t, a, b, c, d, g)
    if gr.blocks > 1:
        db, dc = (jnp.sum(t.reshape(bsz, groups, gr.blocks, gr.n, s),
                          axis=2).reshape(b.shape).astype(b.dtype)
                  for t in (db, dc))
    return dx, ddt_t, db, dc, da, dd


def _turn(t):
    """[B, S, X] <-> [B, X, S]."""
    return jnp.swapaxes(t, 1, 2)


def _layouts(x, dt, a, b, c, d, chunk):
    """The kernels' layouts: x, dt, B and C turned, [B, H P, S], [B, H, S]
    float32 and [B, G N, S]; a [H, 1] and D across the chunk's lanes
    [H, Q], float32."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    return (_turn(x.reshape(bsz, s, h * p)), _turn(dt.astype(_F32)),
            a.astype(_F32).reshape(h, 1),
            _turn(b.reshape(bsz, s, g * n)), _turn(c.reshape(bsz, s, g * n)),
            jnp.broadcast_to(d.astype(_F32)[:, None], (h, chunk)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, d, chunk):
    y = _scan_fwd(*_layouts(x, dt, a, b, c, d, chunk), groups=b.shape[2],
                  chunk=chunk, interpret=backend.interpret())
    return _turn(y).reshape(x.shape)


def _scan_vjp_fwd(x, dt, a, b, c, d, chunk):
    # the residuals are the scan's inputs, as jax.checkpoint keeps them
    return _scan(x, dt, a, b, c, d, chunk), (x, dt, a, b, c, d)


def _scan_vjp_bwd(chunk, saved, g):
    x, dt, a, b, c, d = saved
    bsz, s, h, p = x.shape
    dx, ddt_t, db, dc, da, dd = _scan_bwd(
        *_layouts(*saved, chunk), _turn(g.reshape(bsz, s, h * p)),
        groups=b.shape[2], chunk=chunk, interpret=backend.interpret())
    return (_turn(dx).reshape(x.shape), _turn(ddt_t).astype(dt.dtype),
            jnp.sum(da, axis=(0, 2)).astype(a.dtype),
            _turn(db).reshape(b.shape), _turn(dc).reshape(c.shape),
            jnp.sum(dd, axis=(0, 2)).astype(d.dtype))


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def ssd_scan(x, dt, a, b, c, d, chunk: int):
    """x [B, S, H, P], dt [B, S, H], a / d [H], b / c [B, S, G, N] -> y
    [B, S, H, P] by the kernels, where `kernel_fits` admitted the shapes
    (S a multiple of the chunk)."""
    return _scan(x, dt, a, b, c, d, int(chunk))
