"""`flash_bsh_bwd` (ops/pallas/flash_attention.py): attention backward,
q [B, Sq, H], k and v [B, Skv, H].

Operations: the five matrix products the flash backward needs (Q K^T
again, dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q), 2 FLOPs a
multiply-add each: 10 B Sq Skv H. The second Q K^T is part of the
algorithm, which keeps no [S, S] matrix, so it counts here although the
model-FLOP formula of `mfu` leaves it out. Bytes as `flash_bsh_fwd` counts
them. Bound: compute.
"""
BOUND = "compute"


def work(call):
    q, k = call.operands[0], call.operands[1]
    (b, sq, h), skv = q.dims, k.dims[1]
    return 10.0 * b * sq * skv * h, call.hbm_bytes
