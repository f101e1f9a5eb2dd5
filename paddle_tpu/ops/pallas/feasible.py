"""VMEM-footprint models of the Pallas kernels in this package: what
their tile and row-block choosers (flash_attention.default_bsh_block,
add_ln.default_ln_rows, conv_bn.default_conv_bn_rows) and their
dispatch gates hold a candidate to. Calibrated on v5e against Mosaic's
scoped-vmem report — see the per-function notes. All pure stdlib math;
nothing here imports jax.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

# scoped-VMEM budgets (bytes). The BSH flash kernels raise Mosaic's
# scoped limit to 112MB of the 128MB/core (whole-sequence residency is
# the design); the row-blocked kernels stay under the default ~16MB.
BSH_VMEM_LIMIT = 112 * 1024 * 1024
LN_VMEM_BUDGET = 10 * 1024 * 1024
# add_ln's [1, R] f32 row statistics are blocked (1, rows): Mosaic takes
# a lane block only in multiples of 128
LN_ROW_ALIGN = 128
CONV_BN_VMEM_BUDGET = 12 * 1024 * 1024


class NoFeasibleConfig(ValueError):
    """No candidate configuration can serve this kernel shape.

    Subclasses ValueError so pre-existing `except ValueError` dispatch
    guards keep working; carries the candidates that were considered
    and why each was rejected, so 'not tileable' errors name what was
    actually tried instead of a bare complaint."""

    def __init__(self, kernel: str, key: Dict[str, Any],
                 tried: List[Tuple[Any, str]], detail: str = ""):
        self.kernel = kernel
        self.key = dict(key)
        self.tried = list(tried)
        head = f"{kernel}: no feasible kernel config for {key}"
        if detail:
            head += f" ({detail})"
        if tried:
            head += "; tried: " + "; ".join(
                f"{cfg} -> {why}" for cfg, why in tried[:8])
            if len(tried) > 8:
                head += f"; ... {len(tried) - 8} more"
        super().__init__(head)


# ---------------------------------------------------------------------------
# flash attention, BSH layout
# ---------------------------------------------------------------------------


# ops/pallas/flash_attention.py runs its stream kernels from this S on
# and the whole-tile ones below it
FLASH_BSH_STREAM_FROM = 1024


def _flash_bsh_compute_tile(n: int) -> int:
    """The stream kernels' compute-tile extent along n rows a grid cell
    holds (ops/pallas/flash_attention.py:_compute_tile)."""
    return next(c for c in (512, 256, 128) if n % c == 0)


def flash_bsh_fwd_vmem_bytes(sq: int, skv: int, h: int, bq: int,
                             bk: int) -> int:
    """Forward kernel footprint (bf16 operands). Whole-tile kernel
    (S < 1024): k/v whole-sequence resident, double-buffered, q/o
    blocks, and ~40 B per bq*bk tile element of f32 score temporaries.
    Stream kernel: k / v resident in ONE buffer each where a batch row
    has several cells (4 B/elem), V^T beside them (2 B/elem), the
    per-key bias as f32 columns a compute tile wide, the q / o blocks
    double-buffered, a zero-padded q^T per head (two heads share a
    128-lane slab at d = 64, so 4 B per block element), and two slots
    each of f32 scores and bf16 probabilities a compute tile in size.
    Mosaic allocated 34.1 MiB at (s4096, h768, bq1024) where this counts
    39.0 (described-v5e compiles bisected on the limit, PR 27), and
    90.1-90.9 MiB at (s4096, h2560, bq1024), ten heads of 256, where it
    counts 102.0 (the same bisection, PR 38: the widest slab
    `ops/attention.py:latent_head_groups` hands the kernels)."""
    if max(sq, skv) < FLASH_BSH_STREAM_FROM:
        return 8 * skv * h + 8 * bq * h + 40 * bq * bk
    cq, ck = _flash_bsh_compute_tile(bq), _flash_bsh_compute_tile(skv)
    return (6 * skv * h + 4 * skv * cq + 12 * bq * h + 12 * ck * cq
            + 2 ** 20)


def flash_bsh_bwd_vmem_bytes(sq: int, skv: int, h: int, bq: int,
                             bk: int) -> int:
    """Backward kernel footprint. Whole-tile kernel (S < 1024): q/do
    double-buffered bf16 + the dq f32 revisited accumulator (~12 B/elem
    of the full sq*h residency), k/v/dk/dv blocks, score temporaries.
    Stream kernel: a batch row's q^T / do^T (bf16) and the f32 dq^T
    accumulator, 8 B/elem of sq*h (q, do and dq themselves stay in HBM
    and pass through two row windows and 128 rows of staging); the
    k / v / dk / dv blocks, k^T and the per-head zero-padded k / v of a
    block (~22 B/elem of bk*h); two slots each of f32 s^T, dp^T and bf16
    p, ds^T a compute tile in size, and the bias columns. Mosaic
    allocated 55.8 MiB at (s4096, bk1024, h768) where this counts 56.4,
    and at most 69.4 MiB for either pass at s8192 (80.4 here)
    (described-v5e compiles bisected on the limit, PR 27; the
    whole-tile kernel measured 124 MB at (s8192, bq1024)); 101.4-102.3
    MiB at (s4096, bk256, h2560) where this counts 109.5 (PR 38)."""
    if max(sq, skv) < FLASH_BSH_STREAM_FROM:
        return 12 * sq * h + 8 * bk * h + 40 * bq * bk
    cq, ck = _flash_bsh_compute_tile(sq), _flash_bsh_compute_tile(bk)
    return (8 * sq * h + 22 * bk * h + 24 * ck * cq + 4 * bk * cq
            + 4 * cq * h + 512 * h + 6 * 2 ** 20)


def flash_bsh_ok(sq: int, skv: int, h: int, bq: int, bk: int,
                 *, limit: int = BSH_VMEM_LIMIT) -> Tuple[bool, str]:
    """(feasible, reason) for a pair of DMA tiles (bq is the forward's,
    bk the backward's): both passes' footprints must fit."""
    if bq < 128 or bk < 128:
        return False, "block below the 128 tiling minimum"
    if sq % bq or skv % bk:
        return False, f"blocks ({bq},{bk}) do not tile (sq={sq}, skv={skv})"
    f = flash_bsh_fwd_vmem_bytes(sq, skv, h, bq, bk)
    if f > limit:
        return False, f"fwd VMEM estimate {f} > {limit}"
    b = flash_bsh_bwd_vmem_bytes(sq, skv, h, bq, bk)
    if b > limit:
        return False, f"bwd VMEM estimate {b} > {limit}"
    return True, "ok"


# ---------------------------------------------------------------------------
# fused residual-add + LayerNorm
# ---------------------------------------------------------------------------


def ln_vmem_bytes(rows: int, h: int) -> int:
    """x, y, out row blocks double-buffered bf16-worst + ~4 f32
    temporaries per row element (the ops/pallas/add_ln.py model)."""
    return rows * h * (3 * 2 * 2 + 4 * 4)


def ln_rows_ok(r: int, h: int, rows: int,
               *, budget: int = LN_VMEM_BUDGET) -> Tuple[bool, str]:
    if rows < 1 or r % rows:
        return False, f"row block {rows} does not tile r={r}"
    if rows % LN_ROW_ALIGN:
        return False, f"row block {rows} is not a multiple of {LN_ROW_ALIGN}"
    est = ln_vmem_bytes(rows, h)
    if est > budget:
        return False, f"VMEM estimate {est} > {budget}"
    return True, "ok"


# ---------------------------------------------------------------------------
# fused conv + batch-norm
# ---------------------------------------------------------------------------


# bytes per row*width unit of conv_bn's row-blocked passes. 'mm' (the 1x1
# matmul): x + y blocks double-buffered bf16 + the f32 accumulator.
# 'apply' (normalize and the two backward sweeps): three <=2B blocks
# double-buffered + ~4 f32 temporaries, add_ln's model. The temporaries
# count: inside the ResNet-50 b128 step Mosaic allocated 19.94M scoped
# for conv_bn_bwd_dz at [100352, 512] with 2048-row blocks, against the
# 16M limit (v5e, PR 21)
CONV_BN_ROW_UNIT = {"mm": 2 * 2 + 4, "apply": 3 * 2 * 2 + 4 * 4}


def conv_bn_rows_ok(r: int, width: int, rows: int, bytes_per_row_unit: int,
                    *, budget: int = CONV_BN_VMEM_BUDGET) -> Tuple[bool, str]:
    """Row-blocked passes (1x1 matmul / normalize / backward sweeps):
    in+out blocks double-buffered + the f32 accumulator, as bytes per
    row*width unit (CONV_BN_ROW_UNIT)."""
    if rows < 1 or r % rows:
        return False, f"row block {rows} does not tile r={r}"
    est = rows * width * bytes_per_row_unit
    if est > budget:
        return False, f"VMEM estimate {est} > {budget}"
    return True, "ok"


# ---------------------------------------------------------------------------
# grouped matmul (ops/pallas/grouped_matmul.py)
# ---------------------------------------------------------------------------


# the chooser keeps a cell's estimate under this budget, and a call asks
# Mosaic for its estimate and this slack, not for the core's 128 MiB: what
# a call reserves, XLA cannot give to the buffers it keeps on the chip
# around it
GMM_VMEM_BUDGET = 64 * 1024 * 1024
GMM_VMEM_SLACK = 8 * 1024 * 1024


def gmm_vmem_bytes(form: str, tm: int, tk: int, tn: int, k: int, n: int,
                   itemsize: int) -> int:
    """Footprint of one grid cell of ops/pallas/grouped_matmul.py over
    [G, k, n] matrices. nn: a [tm, k] block of rows, [k, tn] of the
    group's matrix and the [tm, tn] result, each double-buffered, and the
    float32 product before its cast; nt the same with [tk, n] of the
    matrix and a [tm, tk] result. tn: [tm, tk] and [tm, tn] row blocks
    and the [tk, tn] result double-buffered, the float32 [tk, tn]
    accumulator, and the masked copies of the row blocks. Mosaic
    allocated, in MiB, with the block in one product (described-v5e
    compiles bisected on the limit, PR 31; this model in brackets): nn
    bf16 (256, 2048, 1792) 19.1 (20.5), (512, 2048, 1792) 24.2 (26.0),
    float32 (256, 2048, 1792) 35.9 (38.2); tn bf16 (256, 2048, 1792) 33.1
    (36.5), (256, 2048, 896) 18.2 (20.8), float32 (256, 2048, 1792) 50.7
    (58.0). An extent that is no multiple of 128 lies in VMEM padded to
    the next one and is counted so (1856 as 1920). With the rolled loop
    and its tail, as the kernels run (the same bisection, PR 35): bf16
    (256, 2688, 1856) nn 21.2 (27.1), nt 22.5 (27.8), tn 44.8 (49.4);
    (256, 1856, 2688) nn 21.8 (27.8), nt 23.2 (27.1), tn 40.1 (49.4);
    float32 nn (256, 2688, 1856) 44.9 (51.2), tn (256, 896, 1856) 23.5
    (31.7). (Bisect at more groups than XLA can hold on the chip: at
    eight it gave the kernel the 80 MB of weights in VMEM and Mosaic
    allocated 4.2.)"""
    tk, tn, k, n = (-(-extent // 128) * 128 for extent in (tk, tn, k, n))
    if form == "tn":
        return (2 * itemsize * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
                + 2 * itemsize * tm * (tk + tn) + 2 ** 20)
    rows_in, out = (k, tn) if form == "nn" else (n, tk)
    return (2 * itemsize * (tm * rows_in + rows_in * out + tm * out)
            + 4 * tm * out + 2 ** 20)


# ---------------------------------------------------------------------------
# the hyper-connections' stream mixing (ops/pallas/mhc.py)
# ---------------------------------------------------------------------------


# as the grouped matmul's: the chooser keeps a cell under the budget, and
# a call asks Mosaic for its estimate and the slack (30 MiB at the Xing4
# cell's bf16 streams; the budget admits float32 streams of that width)
MHC_VMEM_BUDGET = 64 * 1024 * 1024
MHC_VMEM_SLACK = 4 * 1024 * 1024


def mhc_vmem_bytes(pass_: str, rows: int, c: int, n: int,
                   itemsize: int) -> int:
    """Footprint of one grid cell of ops/pallas/mhc.py over `rows` tokens
    of n streams of c columns, every block double-buffered. fwd: the
    [rows, n c] streams in and out and the [rows, c] sublayer output; bwd:
    the cotangent and the streams in, the streams' cotangent out, y in and
    its cotangent out. The float32 mappings (and, bwd, their cotangents)
    are [n n + n, rows] blocks, their turned copies [rows, n n + n] padded
    to 128 lanes, and the identity that turns them [rows, rows]."""
    wide, narrow, maps = {"fwd": (2, 1, 1), "bwd": (3, 2, 2)}[pass_]
    held = -(-(n * n + n) // 8) * 8
    return (2 * itemsize * rows * c * (wide * n + narrow)
            + 4 * maps * (2 * held * rows + rows * 128) + 4 * rows * rows
            + 2 ** 20)


def mhc_map_vmem_bytes(pass_: str, rows: int, width: int, n: int,
                       itemsize: int, iters: int) -> int:
    """Footprint of one grid cell of `mhc_map`'s kernels over `rows` tokens
    of streams `width` = n c columns wide, every block double-buffered.
    fwd: the [rows, width] streams in and 80 rows of the bf16 stack of
    Phi^T's terms; bwd: the streams in and their cotangent out, the
    stack's 160 rows, dPhi^T [m, width] in float32 and the rounds'
    inputs, 2 x iters blocks of [n n, rows]. Both: a column chunk's
    float32 temporaries (512 columns: the block, its square or its
    cotangent, a product) and the small [m, rows] blocks, counted as 64
    of [128, rows]."""
    m = 2 * n + n * n
    streams, stack, sums = {"fwd": (1, 80, 0), "bwd": (2, 160, 1)}[pass_]
    return (2 * itemsize * rows * width * streams + 2 * 2 * stack * width
            + sums * (2 * 4 * m * width + 4 * 2 * iters * n * n * rows)
            + 4 * 4 * rows * 512 + 64 * 4 * 128 * rows + 2 ** 20)


# ---------------------------------------------------------------------------
# the state-space scan (ops/pallas/ssd_scan.py)
# ---------------------------------------------------------------------------


# as the grouped matmul's: the gate keeps a cell under the budget, and a
# call asks Mosaic for its estimate and the slack
SSD_VMEM_BUDGET = 64 * 1024 * 1024
SSD_VMEM_SLACK = 4 * 1024 * 1024


def ssd_scan_vmem_bytes(pass_: str, s: int, q: int, r: int, p: int, n: int,
                        itemsize: int, groups: int = 1,
                        blocks: int = 1) -> int:
    """Footprint of one grid cell of ops/pallas/ssd_scan.py: `groups` groups
    of r heads in all, or, where a group is split into `blocks` blocks, r
    heads of one group; each head of p columns, state n, chunk q, a row of s
    positions; every block double-buffered. fwd: x in and y out [q, r p],
    B and C [q, groups n], dt and D [r, q]; scratch: the states [r, p, n]
    float32, x turned and y^T [r p, q], C B^T a group and each head's cum
    as lane-wide columns, [q, q] float32 each; two float32 values of [r p,
    q] and six [q, q] temporaries. bwd: x and the cotangent in and dx out,
    B and C in and their cotangents out (float32 partials where a group is
    split), five [r, q] float32 blocks; the states entering every chunk and
    dS, (s / q + 1) [r, p, n] float32; x, the cotangent and dx turned,
    their per-head copies (lanes padded to 128), C B^T and d(C B^T) a group
    and the columns of cum; six [r p, q] values and ten [q, q] temporaries.
    Both: 1 MiB."""
    wide_p = -(-p // 128) * 128
    if pass_ == "fwd":
        return (2 * itemsize * q * (2 * r * p + 2 * groups * n)
                + 2 * 4 * 3 * r * q + 4 * r * p * n + 4 * 4 * r * p * q
                + 4 * q * q * (groups + r + 6) + 2 ** 20)
    partials = itemsize if blocks == 1 else 4
    return (2 * q * (itemsize * (3 * r * p + 2 * groups * n)
                     + partials * 2 * groups * n)
            + 2 * 4 * 6 * r * q + 4 * (s // q + 1) * r * p * n
            + 4 * 9 * r * p * q + 2 * itemsize * r * q * wide_p
            + 4 * q * q * (2 * groups + r + 10) + 2 ** 20)
