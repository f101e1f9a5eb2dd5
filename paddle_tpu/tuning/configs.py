"""Candidate config spaces per tunable kernel (pure, deterministic).

Each enumerator returns the ORDERED list of feasible candidate configs
for one (kernel, shape, dtype) key — the order is the deterministic
tie-break the search harness applies when two candidates measure
identically (first enumerated wins), so enumeration order is part of
the reproducibility contract: largest blocks first, axes varied
inner-to-outer, never dependent on dict/hash order.

Infeasible candidates are returned separately with their rejection
reasons (the feasibility gate's audit trail: NoFeasibleConfig carries
them when nothing survives).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from . import feasible

# block-size menu shared by the flash axes (the kernels' tiling minimum
# is 128; 1024 is the largest tile the s4096 hand measurements reached)
_FLASH_BLOCKS = (1024, 512, 256, 128)
_LN_ROWS = (2048, 1024, 512, 256, 128)
_CONV_ROWS = (2048, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
# paged-attention page sizes: fewer grid steps (large pages) first; the
# tuned page doubles as the KV pool page granularity, so small pages
# trade kernel overhead for finer pool packing
_PAGED_PAGES = (64, 32, 16, 8)

Rejects = List[Tuple[Dict[str, Any], str]]


def flash_bsh_candidates(sq: int, skv: int, h: int, dtype: str = "bfloat16",
                         dropout: bool = False,
                         ) -> Tuple[List[Dict[str, Any]], Rejects]:
    """(bq, bk) tile pairs feasible for BOTH passes (one config serves
    fwd and bwd so PRNG dropout regenerates identical masks), plus the
    dropout-mask axis when the target config applies dropout: 'regen'
    (in-kernel PRNG, zero HBM traffic) vs 'materialize' (precomputed
    [B,nh,Sq,Skv] mask in HBM — only ever wins when the HBM gate says
    the mask fits and the VPU PRNG is the bottleneck)."""
    ok: List[Dict[str, Any]] = []
    rejects: Rejects = []
    for bq in _FLASH_BLOCKS:
        for bk in _FLASH_BLOCKS:
            cfg = {"bq": bq, "bk": bk}
            feas, why = feasible.flash_bsh_ok(sq, skv, h, bq, bk)
            if not feas:
                rejects.append((cfg, why))
                continue
            if dropout:
                ok.append({**cfg, "mask": "regen"})
                ok.append({**cfg, "mask": "materialize"})
            else:
                ok.append(cfg)
    return ok, rejects


def add_ln_candidates(r: int, h: int, dtype: str = "float32",
                      ) -> Tuple[List[Dict[str, Any]], Rejects]:
    ok: List[Dict[str, Any]] = []
    rejects: Rejects = []
    for rows in _LN_ROWS:
        cfg = {"block_rows": rows}
        feas, why = feasible.ln_rows_ok(r, h, rows)
        (ok if feas else rejects).append(cfg if feas else (cfg, why))
    return ok, rejects


# bytes-per-row-unit by pass kind, the table ops/pallas/conv_bn.py sizes
# its row blocks with
CONV_BN_ROW_UNIT = feasible.CONV_BN_ROW_UNIT


def conv_bn_candidates(kind: str, r: int, width: int,
                       dtype: str = "float32",
                       ) -> Tuple[List[Dict[str, Any]], Rejects]:
    unit = CONV_BN_ROW_UNIT[kind]
    ok: List[Dict[str, Any]] = []
    rejects: Rejects = []
    for rows in _CONV_ROWS:
        cfg = {"block_rows": rows}
        feas, why = feasible.conv_bn_rows_ok(r, width, rows, unit)
        (ok if feas else rejects).append(cfg if feas else (cfg, why))
    return ok, rejects


def paged_attention_candidates(kv_heads: int, head_dim: int,
                               dtype: str = "float32", max_seq: int = 0,
                               ) -> Tuple[List[Dict[str, Any]], Rejects]:
    """Page-size axis for the serving paged-attention kernel. One page
    of KV streams through VMEM per grid step, so the page size is the
    kernel's block size AND the pool's allocation granularity —
    kv_cache.from_budget consults the tuned winner when no explicit
    page size is configured."""
    ok: List[Dict[str, Any]] = []
    rejects: Rejects = []
    for page in _PAGED_PAGES:
        cfg = {"page_size": page}
        feas, why = feasible.paged_page_ok(page, kv_heads, head_dim,
                                           dtype, max_seq)
        (ok if feas else rejects).append(cfg if feas else (cfg, why))
    return ok, rejects


def conv_bn_s2d_candidates(n: int, hp: int, wp: int, c: int, o: int,
                           kh: int, kw: int, strides: Tuple[int, int],
                           dtype: str = "float32",
                           ) -> Tuple[List[Dict[str, Any]], Rejects]:
    """The space-to-depth axis for kxk stride-2 convs (hp/wp already
    padded): {'space_to_depth': 1} vs the XLA reference lowering
    {'space_to_depth': 0}. Candidates exist only when the rearranged
    stride-1 problem fits the per-image VMEM budget and the output-size
    identity holds (even padded extent, or odd kernel)."""
    rejects: Rejects = []
    if tuple(strides) != (2, 2) or (kh, kw) == (1, 1):
        rejects.append(({"space_to_depth": 1},
                        "only kxk stride-2 convs have an s2d lowering"))
        return [], rejects
    for ext, k in ((hp, kh), (wp, kw)):
        if ext % 2 and k % 2 == 0:
            rejects.append(({"space_to_depth": 1},
                            f"odd padded extent {ext} with even kernel {k} "
                            "changes the output size"))
            return [], rejects
    est = feasible.conv_bn_s2d_per_image_bytes(hp, wp, c, o, kh, kw)
    if est > feasible.CONV_BN_VMEM_BUDGET:
        rejects.append(({"space_to_depth": 1},
                        f"per-image VMEM estimate {est} > "
                        f"{feasible.CONV_BN_VMEM_BUDGET}"))
        return [], rejects
    return [{"space_to_depth": 0}, {"space_to_depth": 1}], rejects
