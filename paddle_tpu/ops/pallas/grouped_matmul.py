"""Grouped matmul over rows sorted by group: the expert products of
`moe_swiglu` (ops/moe_ops.py) as Pallas kernels for the TPU.

Rows of `lhs` lie sorted by group, `group_sizes[g]` of them for group g,
and what lies behind the last group belongs to nobody. Three forms, the
product and its two transposes:

  nn   out[r]   = lhs[r] @ rhs[g(r)]            lhs [m, k], rhs [G, k, n]
  nt   out[r]   = lhs[r] @ rhs[g(r)]^T          lhs [m, n], rhs [G, k, n]
  tn   out[g]   = lhs[rows of g]^T @ rhs[rows of g]
                                                lhs [m, k], rhs [m, n]

`jax.lax.ragged_dot` is the `jnp` composition of all three (XLA lowers it
to a grouped-matmul kernel of its own on the TPU) and what every backend
but the TPU, and every shape the chooser cannot serve, runs.

**The grid follows the rows present** (the design of
`jax.experimental.pallas.ops.tpu.megablox`): the m rows are cut into row
tiles of `tm`, and the grid's last axis walks a list of *visits*, one per
(row tile, group that has rows in it), made on the device from
`group_sizes` (`group_visits`). A tile a group boundary runs through is
visited once a group it holds, consecutively, and each visit stores under
the mask of its group's rows; tiles behind the last group are on nobody's
list, so they cost no DMA and no MXU pass and nobody writes them. The
number of visits is a device scalar and the grid's extent, so a buffer of
65,536 rows that holds 16,384 costs what 16,384 cost. Nothing is padded
into the sorted layout.

**What a grid cell holds.** The contraction is never tiled: a cell of nn
/ nt multiplies a [tm, k] block of rows by a [k, tn] block of its group's
matrix, so that matrix's block stays in VMEM while the visits of one group
pass (its block index does not change, so it is fetched once a group), and
nothing is accumulated across cells. nt reads `rhs[g]` as it lies and
contracts both last axes: no transposed copy of the weights exists. A cell
of tn adds lhs^T @ rhs of one row tile into a float32 [tk, tn] accumulator,
zeroed at a group's first visit and stored at its last; a group without
rows has one visit that stores the zeros.

**What is compiled, and what every process pays.** Inside a cell the
columns go `_chunk` (256) at a time through a rolled loop, so the compiled
body is one chunk's product whatever the block holds: the whole block in
one product is 6 % faster a call and five times the code (0.77 against
0.17 MB a call site, 96 sites a step). A block that is no whole number of
chunks (1856 columns) ends in one static tail, a second and narrower
product in the same body. The three entry points are inner
`jax.jit`s, `interpret` among their static arguments: the call sites of
one signature share one traced and one lowered body, so the step's Mosaic
bodies do not go with its layers (tests/test_moe_gmm_lowering.py). A call
asks Mosaic for the VMEM its cell needs and a slack, not for the core's.

Tiles come from the operands' shapes alone (`default_gmm_tiles`, held to
`feasible.gmm_vmem_bytes`): no flag, no environment name, no cache. An
axis of the matrices is tiled in multiples of 128; one that is a multiple
of 64 only (1856 = 14.5 x 128) is served as one block of its whole
extent, which Mosaic takes whatever the extent; any other axis, and rows
no row tile divides, keep `ragged_dot`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import feasible as _feas
from .flash_attention import _interpret

FORMS = ("nn", "nt", "tn")


class Visits(NamedTuple):
    """The grid's last axis, made on the device by `group_visits`."""
    offsets: jax.Array  # [G + 1] int32: group g is rows offsets[g:g + 2]
    group: jax.Array    # [tiles + G + 1] int32: the group of visit v
    tile: jax.Array     # [tiles + G + 1] int32: its row tile
    count: jax.Array    # [] int32: visits to make


@functools.partial(jax.jit, static_argnames=("rows", "tm"))
def group_visits(group_sizes, rows: int, tm: int) -> Visits:
    """One visit per (row tile, group with rows in it), groups in order and
    tiles in order within a group; a group without rows has one visit (tn
    stores its zeros there; nn / nt store under a mask that holds no row).
    Behind `count` the group reads G, which is nobody's. An inner jit like
    the kernels': a step traces this once a signature and not once a
    product, and XLA makes one list of the identical calls it inlines into
    a block."""
    if rows % tm:
        raise ValueError(f"group_visits: {rows} rows are not whole tiles "
                         f"of {tm}")
    tiles, groups = rows // tm, group_sizes.shape[0]
    length = tiles + groups + 1
    # lax primitives, not jnp: jnp's wrappers cost this trace 0.1 s
    ends = lax.min(lax.cumsum(lax.convert_element_type(group_sizes,
                                                       jnp.int32)), rows)
    offsets = lax.pad(ends, jnp.int32(0), [(1, 0, 0)])
    starts = lax.slice(offsets, (0,), (groups,))
    first = lax.min(lax.div(starts, tm), tiles - 1)
    visits = lax.select(ends > starts,
                        lax.div(ends + (tm - 1), tm) - lax.div(starts, tm),
                        lax.full_like(ends, 1))
    through = lax.cumsum(visits)  # visits up to and including group g

    def per_visit(x):  # [length, groups] from a [groups] table
        return lax.broadcast_in_dim(x, (length, groups), (1,))

    v = lax.iota(jnp.int32, length)
    across = lax.broadcast_in_dim(v, (length, groups), (0,))
    group = lax.reduce(
        lax.convert_element_type(across >= per_visit(through), jnp.int32),
        jnp.int32(0), lax.add, (1,))
    mine = lax.broadcasted_iota(jnp.int32, (length, groups), 1) == (
        lax.broadcast_in_dim(lax.min(group, groups - 1), (length, groups),
                             (0,)))
    # first tile of the visit's group, less the visits before the group
    base = lax.reduce(
        lax.select(mine, per_visit(first - (through - visits)),
                   lax.full((length, groups), 0, jnp.int32)),
        jnp.int32(0), lax.add, (1,))
    tile = lax.clamp(0, base + v, tiles - 1)
    return Visits(offsets, group, tile,
                  lax.index_in_dim(through, groups - 1, keepdims=False))


# The kernel bodies below speak lax, not jnp: every process traces and
# lowers them in front of the compile cache, once a signature, and jnp's
# operator wrappers were most of that (PERF.md, PR 31).


def _row_mask(offsets, group, tile, v, tm, width):
    """[tm, width] mask of the rows of visit v's tile that are its group's."""
    g = group[v]
    start, end = offsets[g], offsets[lax.add(g, 1)]
    rows = lax.add(lax.broadcasted_iota(jnp.int32, (tm, width), 0),
                   lax.mul(tile[v], tm))
    return lax.bitwise_and(lax.ge(rows, start), lax.lt(rows, end))


def _keep(mask, x):
    return lax.select(mask, x, lax.full_like(x, 0))


def _over_chunks(width: int, chunk: int, mask, body):
    """body(cols, mask(columns)) over `width` columns, `chunk` at a time, as
    a rolled loop: the compiled body is one chunk's, whatever the block
    holds. A width that is no multiple of the chunk (1856 = 7 x 256 + 64)
    ends in one static tail behind the whole chunks, at a lane-aligned
    offset, its row mask built at the tail's width: a second, narrower
    product in the same kernel and not a second `pallas_call`."""
    whole, tail = divmod(width, chunk)
    if whole:
        mine = mask(chunk)  # once, in front of the loop
        if whole == 1:
            body(pl.ds(0, chunk), mine)
        else:
            def trip(j, carry):
                body(pl.ds(pl.multiple_of(lax.mul(j, chunk), chunk), chunk),
                     mine)
                return carry

            lax.fori_loop(0, whole, trip, 0)
    if tail:
        body(pl.ds(whole * chunk, tail), mask(tail))


def _gmm_kernel(offsets, group, tile, lhs_ref, rhs_ref, out_ref, *, tm,
                chunk, transposed):
    # no branch on an empty group: its one visit stores under a mask that
    # holds no row
    v = pl.program_id(1)

    def columns(cols, mine):
        product = lax.dot_general(
            lhs_ref[...], rhs_ref[cols, :] if transposed
            else rhs_ref[:, cols],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[:, cols] = lax.select(
            mine, lax.convert_element_type(product, out_ref.dtype),
            out_ref[:, cols])

    _over_chunks(out_ref.shape[1], chunk,
                 functools.partial(_row_mask, offsets, group, tile, v, tm),
                 columns)


def _tgmm_kernel(offsets, group, tile, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                 tm, chunk):
    v = pl.program_id(2)
    g = group[v]
    first = lax.bitwise_or(
        lax.eq(v, 0), lax.ne(group[lax.max(lax.sub(v, 1), 0)], g))
    last = lax.ne(group[lax.add(v, 1)], g)
    # rows of other groups, and what lies behind the last one (which may be
    # anything), leave by select and not by a product with zero; a group
    # without rows keeps none, and its one visit stores the zeros
    mask = functools.partial(_row_mask, offsets, group, tile, v, tm)
    lhs = _keep(mask(lhs_ref.shape[1]), lhs_ref[...])

    def columns(cols, mine):
        product = lax.dot_general(
            lhs, _keep(mine, rhs_ref[:, cols]), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = lax.add(product, lax.select(
            lax.broadcast(first, product.shape), lax.full_like(product, 0),
            acc_ref[:, cols]))
        acc_ref[:, cols] = acc

        @pl.when(last)
        def _():
            out_ref[:, cols] = lax.convert_element_type(acc, out_ref.dtype)

    _over_chunks(rhs_ref.shape[1], chunk, mask, columns)


@functools.partial(jax.jit, static_argnames=(
    "tm", "tn", "chunk", "transposed", "vmem_limit", "interpret"))
def _gmm(lhs, rhs, visits: Visits, *, tm, tn, chunk, transposed, vmem_limit,
         interpret):
    """nn / nt. An inner jit: the call sites of one signature (four layers,
    forward and recomputed) share one traced and one lowered body."""
    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    if transposed:
        rhs_spec = pl.BlockSpec(
            (None, tn, k), lambda j, v, off, grp, til: (grp[v], j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, k, tn), lambda j, v, off, grp, til: (grp[v], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, chunk=chunk,
                          transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, visits.count),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, off, grp, til: (til[v], 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, off, grp, til: (til[v], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="moe_gmm_nt" if transposed else "moe_gmm_nn",
    )(visits.offsets, visits.group, visits.tile, lhs, rhs)


@functools.partial(jax.jit, static_argnames=(
    "groups", "tm", "tk", "tn", "chunk", "vmem_limit", "interpret"))
def _tgmm(lhs, rhs, visits: Visits, *, groups, tm, tk, tn, chunk, vmem_limit,
          interpret):
    """tn, as an inner jit like `_gmm`."""
    k, n = lhs.shape[1], rhs.shape[1]
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(k // tk, n // tn, visits.count),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda i, j, v, off, grp, til: (til[v], i)),
                pl.BlockSpec((tm, tn),
                             lambda i, j, v, off, grp, til: (til[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda i, j, v, off, grp, til: (grp[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="moe_gmm_tn",
    )(visits.offsets, visits.group, visits.tile, lhs, rhs)


# ---------------------------------------------------------------------------
# tiles, the gate, and the product with its transposes
# ---------------------------------------------------------------------------


_ROW_TILES = (256, 128)


def _widths(n: int):
    """Tile widths of an axis of n: the multiples of 128 that divide it,
    widest first (1792 = 14 x 128: 1792, 896, 256, 128). An axis that is
    no multiple of 128 but one of 64, the half lane tile (1856 = 14.5 x
    128), has one, itself: a block whose extent is its array's is legal
    whatever the extent, and 64 keeps bf16's 16-row packing where the axis
    is sliced on the sublanes. Any other axis has none."""
    if n % 128:
        return [] if n % 64 else [n]
    return [n // d for d in range(1, n // 128 + 1)
            if n % d == 0 and (n // d) % 128 == 0]


def default_gmm_tiles(form: str, m: int, k: int, n: int,
                      itemsize: int) -> Optional[Tuple[int, int, int]]:
    """THE grouped-matmul tile chooser, (tm, tk, tn) for `form` over m
    sorted rows and [G, k, n] matrices: a function of the operands' shapes
    and item size and of nothing else. None where no tile serves them,
    and then `jax.lax.ragged_dot` runs.

    tm, the row tile, is 256 (128 where only that divides m): every group
    boundary costs one more visit of a tile, and at ~2,048 rows a group a
    smaller tile wastes less of it than a larger one saves in grid steps.
    At the LFM2 cell's operands ([32768, 2048] x [8, 2048, 1792] and
    [32768, 1792] x [8, 1792, 2048], bf16, 16,3xx rows present; my chip
    run, PR 31) tm 256 / 512 / 1024 read 0.754 / 0.816 / 0.969 ms for nn,
    the same for nt, 0.797 / 0.852 / 1.007 for tn, against 1.27-1.60 for
    XLA's `ragged-dot`.

    nn keeps [k, tn] of its group's matrix in VMEM, nt [tk, n], tn a
    float32 [tk, tn] accumulator: the widest that fits
    (`feasible.gmm_vmem_bytes`), which at those operands is the whole
    matrix for all three (nn: tn 1792 / 896 / 256 read 0.754 / 0.770 /
    1.009 ms; tn: (2048, 1792) / (1024, 1792) / (2048, 896) / (1024, 896)
    read 0.797 / 0.825 / 0.840 / 0.909). A narrower tile re-reads the rows
    once a column block; among tn's tiles of one area the wider tn wins.
    (All read with the block in one product; the loop over `_chunk`
    columns adds 0.05 ms to nn / nt and 0.10 to tn.)

    An axis that is a multiple of 64 and not of 128 has one candidate, its
    whole extent (`_widths`): it is never tiled, and where the block does
    not fit with it whole the chooser refuses. At the Nemotron cell's operands ([6144, 2688] x [8, 2688, 1856]
    and [6144, 1856] x [8, 1856, 2688], bf16, 3,072 rows present; my chip
    run, PR 35) nn / nt / tn read 0.500 / 0.540 / 0.678 ms at the first
    and 0.379 / 0.356 / 0.508 at the second, the same bits as XLA's
    `ragged-dot`, which took 1.68-2.22; tm 128 / 256 / 512 read 0.463 /
    0.506 / 0.593 for nn at the first (at 384 rows a group a 256-row tile
    is visited three times for every two tiles of rows)."""
    tm = next((t for t in _ROW_TILES if m % t == 0), None)
    if tm is None or not (_widths(k) and _widths(n)):
        return None
    if form == "nn":
        cands = [(k, tn) for tn in _widths(n)]
    elif form == "nt":
        cands = [(tk, n) for tk in _widths(k)]
    else:
        cands = sorted(((tk, tn) for tk in _widths(k) for tn in _widths(n)),
                       key=lambda c: (-c[0] * c[1], -c[1]))
    for tk, tn in cands:
        if (_feas.gmm_vmem_bytes(form, tm, tk, tn, k, n, itemsize)
                <= _feas.GMM_VMEM_BUDGET):
            return tm, tk, tn
    return None


def _chunk(width: int) -> int:
    """Columns one trip of a kernel's inner loop multiplies: 256 (128
    where 256 does not divide the block). At the LFM2 cell's operands, tm
    256 (my chip run, PR 31), nn / nt read 0.859-0.898 ms at 128, 0.801-
    0.830 at 256, 0.764-0.768 at half the block (896 / 1024) and 0.753-
    0.759 with the block unrolled whole; tn 1.10-1.15, 0.89-0.91, 0.82-0.83
    and 0.79-0.80. The whole block is 3.5 ms a step faster than 256 and
    +70 MB of executable, +0.5 s of every process's first step: set-up is
    an end-to-end metric, so 256. A block that neither divides (1856) goes
    256 at a time and ends in a tail of 64 (`_over_chunks`): nn / nt / tn
    read 0.518 / 0.377 / 0.741 ms at 128 + 64, 0.503 / 0.354 / 0.682 at
    256 + 64, 0.493 / 0.348 / 0.668 at 512 + 320 and 0.485 / 0.339 with
    the block whole (tn then passes the VMEM it asks for) (the Nemotron
    cell's operands, my chip run, PR 35). The 2688 columns of that cell
    (21 x 128) keep the 128 that every multiple of 128 without a factor
    of 256 has had: nn / nt / tn read 0.376 / 0.540 / 0.508 at 128, 0.359
    / 0.501 / 0.420 at 384, 0.352 / 0.492 / 0.408 at 896: 384 would be
    0.65 ms a step of 285 and a rule of its own."""
    return next((c for c in (256, 128) if width % c == 0), 256)


def gmm_tiles(form, lhs, rhs_shape) -> Optional[Tuple[int, int, int]]:
    """THE backend / shape gate of every grouped product: the chooser's
    tiles on the TPU (or where a test pins the kernels, interpreted) for
    bf16 or float32 rows, else None."""
    from ..attention import FORCE_PALLAS

    if _interpret() and not FORCE_PALLAS:
        return None
    if lhs.dtype not in (jnp.bfloat16, jnp.float32):
        return None
    _, k, n = rhs_shape
    return default_gmm_tiles(form, lhs.shape[0], k, n, lhs.dtype.itemsize)


def _ragged_nt(d_out, rhs, group_sizes, rhs_shape):
    """d_lhs of `jax.lax.ragged_dot`, as autodiff writes it."""
    lhs = jax.ShapeDtypeStruct((d_out.shape[0], rhs_shape[1]), d_out.dtype)
    return jax.linear_transpose(
        lambda lhs: lax.ragged_dot(lhs, rhs, group_sizes), lhs)(d_out)[0]


def _ragged_tn(lhs, d_out, group_sizes, rhs_shape):
    """d_rhs of `jax.lax.ragged_dot`, as autodiff writes it."""
    rhs = jax.ShapeDtypeStruct(rhs_shape, lhs.dtype)
    return jax.linear_transpose(
        lambda rhs: lax.ragged_dot(lhs, rhs, group_sizes), rhs)(d_out)[0]


_RAGGED_DOT = {
    "nn": lambda lhs, rhs, group_sizes, rhs_shape: lax.ragged_dot(
        lhs, rhs, group_sizes),
    "nt": _ragged_nt,
    "tn": _ragged_tn,
}


def _run(form, a, b, group_sizes, rhs_shape, kernels=FORMS):
    """One form over operands (a, b): nn (lhs, rhs), nt (d_out, rhs), tn
    (lhs, d_out); `rhs_shape` is the matrices' [G, k, n]; a form that is
    not among `kernels` keeps `ragged_dot` whatever the gate says."""
    from ...fluid import monitor

    tiles = gmm_tiles(form, a, rhs_shape) if form in kernels else None
    monitor.record_grouped_product_lowering(
        "ragged_dot" if tiles is None else "pallas", form)
    if tiles is None:
        return _RAGGED_DOT[form](a, b, group_sizes, rhs_shape)
    tm, tk, tn = tiles
    _, k, n = rhs_shape
    # jax keys an inner jit's trace on the abstract-mesh context variable,
    # which is unset in a forward pass and set (to the same, empty mesh)
    # inside a custom_vjp's backward rule: named here, to what it already
    # is, so that the backward's recomputation shares the forward's bodies
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return _kernel(form, a, b, group_sizes, rhs_shape, tm, tk, tn, k, n)


def _kernel(form, a, b, group_sizes, rhs_shape, tm, tk, tn, k, n):
    visits = group_visits(group_sizes, a.shape[0], tm)
    # a call asks Mosaic for what its cell needs, not for the core's VMEM:
    # what it reserves, XLA cannot give to the buffers it keeps on the chip
    common = dict(tm=tm, interpret=_interpret(), vmem_limit=(
        _feas.gmm_vmem_bytes(form, tm, tk, tn, k, n, a.dtype.itemsize)
        + _feas.GMM_VMEM_SLACK))
    if form == "tn":
        return _tgmm(a, b, visits, groups=rhs_shape[0], tk=tk, tn=tn,
                     chunk=_chunk(tn), **common)
    width = tn if form == "nn" else tk  # the tile over the result's columns
    return _gmm(a, b, visits, tn=width, chunk=_chunk(width),
                transposed=form == "nt", **common)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, group_sizes, kernels=FORMS):
    """out[r] = lhs[r] @ rhs[g(r)] for rows sorted by group, in `lhs`'
    dtype with float32 accumulation: lhs [m, k], rhs [G, k, n],
    group_sizes [G] int32. Rows behind the last group are neither read
    nor written. Each of the product and its two transposes runs the
    Pallas kernel where it is among `kernels` and `gmm_tiles` serves it,
    and `jax.lax.ragged_dot` where not; autodiff never sees a
    `pallas_call`."""
    return _run("nn", lhs, rhs, group_sizes, rhs.shape, kernels)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, kernels):
    return (grouped_matmul(lhs, rhs, group_sizes, kernels),
            (lhs, rhs, group_sizes))


def _grouped_matmul_bwd(kernels, saved, d_out):
    lhs, rhs, group_sizes = saved
    return (_run("nt", d_out, rhs, group_sizes, rhs.shape, kernels),
            _run("tn", lhs, d_out, group_sizes, rhs.shape, kernels), None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
