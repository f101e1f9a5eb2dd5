"""`flash_bsh_causal_fwd` (ops/pallas/flash_attention.py): causal attention
forward on projection-layout tensors, q, k and v [B, S, H], sq = skv.

Operations: Q K^T and P V over the score pairs a causal kernel cannot
avoid: the 128 x 128 MXU tiles on or below the diagonal, S/128 (S/128 + 1)
/ 2 of them (at S = 4096, 528 of 1,024: 51.6 % of the square that
`flash_bsh_fwd` counts). 2 FLOPs a multiply-add, 4 H a pair over all
heads. Softmax and mask are vector work and not counted. Bytes as
`flash_bsh_fwd` counts them. Bound: compute.
"""
BOUND = "compute"
TILE = 128


def causal_pairs(s: int) -> float:
    """(query, key) pairs in the TILE x TILE tiles on or below the diagonal."""
    n = -(-s // TILE)
    return n * (n + 1) / 2.0 * min(TILE, s) ** 2


def work(call):
    """(FLOPs, HBM bytes) of one call, from its shapes in the compiled step."""
    b, s, h = call.operands[0].dims
    return 4.0 * b * causal_pairs(s) * h, call.hbm_bytes
