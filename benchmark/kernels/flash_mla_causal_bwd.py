"""`flash_mla_causal_bwd`: the backward of `flash_mla_causal_fwd`.

Operations: the five matrix products of the flash backward over the pairs
the forward counts, at the unpadded widths: Q K^T again, dQ = dS K and
dK = dS^T Q over 192 columns, dV = P^T dO and dP = dO V^T over 128. Bytes
as the forward counts them. Bound: compute.
"""
from benchmark import manifest

BOUND = "compute"


def work(call):
    fwd = manifest.load_module("kernels", "flash_mla_causal_fwd")
    products = 3 * (fwd.QK_WIDTH,) + 2 * (fwd.V_WIDTH,)
    return fwd.pair_flops(call, products), fwd.unpadded_bytes(call)
