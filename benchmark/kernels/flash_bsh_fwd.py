"""`flash_bsh_fwd` (ops/pallas/flash_attention.py): attention forward on
projection-layout tensors, q [B, Sq, H], k and v [B, Skv, H].

Operations: the two matrix products the algorithm needs, Q K^T and P V,
2 FLOPs a multiply-add each: 4 B Sq Skv H. Softmax, mask and in-kernel
dropout are vector work and not counted. Bytes: every operand read once and
every result written once, as far as the compiled step keeps them in HBM
(`hlo_text.MosaicCall.hbm_bytes`). Bound: compute from S = 1024 up; at
S = 512 and H = 768 the two bounds meet (0.26 ms against 0.25 ms for 64
sequences), and the larger is taken call by call.
"""
BOUND = "compute"


def work(call):
    """(FLOPs, HBM bytes) of one call, from its shapes in the compiled step."""
    q, k = call.operands[0], call.operands[1]
    (b, sq, h), skv = q.dims, k.dims[1]
    return 4.0 * b * sq * skv * h, call.hbm_bytes
