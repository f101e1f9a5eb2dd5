"""`flash_bsh_causal_bwd` (ops/pallas/flash_attention.py): causal attention
backward, q, k and v [B, S, H], sq = skv.

Operations: the five matrix products of the flash backward (Q K^T again,
dV, dP, dQ, dK) over the pairs `flash_bsh_causal_fwd` counts, 10 H a pair.
Bytes as `flash_bsh_fwd` counts them. Bound: compute.
"""
from benchmark import manifest

BOUND = "compute"


def work(call):
    b, s, h = call.operands[0].dims
    pairs = manifest.load_module(
        "kernels", "flash_bsh_causal_fwd").causal_pairs(s)
    return 10.0 * b * pairs * h, call.hbm_bytes
