"""`flash_mla_wide_causal_bwd`: the backward of `flash_mla_wide_causal_fwd`.

Operations: the five matrix products of the flash backward over the pairs
the forward counts, each over the call's H = heads x 256 columns: Q K^T
again, dQ = dS K, dK = dS^T Q, dV = P^T dO and dP = dO V^T. Bytes: the
call's HBM operands and results. Bound: compute.
"""
from benchmark import manifest

BOUND = "compute"
PRODUCTS = 5


def work(call):
    fwd = manifest.load_module("kernels", "flash_mla_wide_causal_fwd")
    return fwd.pair_flops(call, PRODUCTS), call.hbm_bytes
