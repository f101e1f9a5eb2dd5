"""`flash_mla_wide_causal_fwd` (ops/pallas/flash_attention.py's causal BSH
forward, as `ops/attention.py:latent_attention` calls it on heads that are a
kernel width as they stand: 256-wide q / k, 192 unrotated + 64 rotated,
against 256-wide v): nothing is padded, so a call's shapes are its work.
Twenty such heads at S 4096 go in two calls of ten (the kernels hold a
batch row's K and V of all the heads they are given in VMEM): the
arithmetic is a call's, and the reader sums the calls.

Operations: Q K^T over 256 and P V over 256 columns a head, 2 FLOPs a
multiply-add, over the score pairs a causal kernel cannot avoid (the 128 x
128 tiles on or below the diagonal, `flash_bsh_causal_fwd.causal_pairs`):
4 H a pair over the call's H = heads x 256 columns. Bytes: the call's HBM
operands and results. Bound: compute.
"""
from benchmark import manifest

BOUND = "compute"
PRODUCTS = 2  # Q K^T, P V, each over the call's H columns


def pair_flops(call, products: int) -> float:
    b, s, h = call.operands[0].dims
    pairs = manifest.load_module(
        "kernels", "flash_bsh_causal_fwd").causal_pairs(s)
    return 2.0 * products * b * pairs * h


def work(call):
    """(FLOPs, HBM bytes) of one call, from its shapes in the compiled step."""
    return pair_flops(call, PRODUCTS), call.hbm_bytes
