"""`flash_mla_causal_fwd` (ops/pallas/flash_attention.py's causal BSH
forward, as `ops/attention.py:latent_attention` calls it): causal latent
attention on heads zero-padded to a width the kernel runs. q, k and v are
[B, S, heads x KERNEL_WIDTH]; the model's heads are QK_WIDTH wide in q and
k (128 without rotation + 64 with) and V_WIDTH wide in v.

Operations: Q K^T over QK_WIDTH and P V over V_WIDTH columns, 2 FLOPs a
multiply-add, over the score pairs a causal kernel cannot avoid (the 128 x
128 tiles on or below the diagonal, `flash_bsh_causal_fwd.causal_pairs`):
the **unpadded** work, so the share of the roofline charges the padding to
the kernel. Bytes: the call's HBM operands and results at the unpadded
share of their columns. Bound: compute.
"""
from benchmark import manifest

BOUND = "compute"
KERNEL_WIDTH = 256  # what 192 and 128 are padded to (ops/attention.py)
QK_WIDTH = 192
V_WIDTH = 128
PRODUCTS = (QK_WIDTH, V_WIDTH)  # Q K^T, P V


def heads(call) -> int:
    return call.operands[0].dims[2] // KERNEL_WIDTH


def unpadded_bytes(call) -> float:
    """q, k at QK_WIDTH and v, o at V_WIDTH of KERNEL_WIDTH columns."""
    return call.hbm_bytes * (2 * QK_WIDTH + 2 * V_WIDTH) / (4 * KERNEL_WIDTH)


def pair_flops(call, products) -> float:
    b, s, _ = call.operands[0].dims
    pairs = manifest.load_module(
        "kernels", "flash_bsh_causal_fwd").causal_pairs(s)
    return 2.0 * sum(products) * b * heads(call) * pairs


def work(call):
    """(FLOPs, HBM bytes) of one call, from its shapes in the compiled step."""
    return pair_flops(call, PRODUCTS), unpadded_bytes(call)
