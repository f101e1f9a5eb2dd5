"""`add_ln_bwd` (ops/pallas/add_ln.py): backward of LayerNorm(x + y); reads
x, y, the saved statistics and the cotangent, writes one dx that serves
both branches, and per-block partials of dscale and dshift.

Bound: HBM, counted as `add_ln_fwd` counts it. With everything in HBM,
forward and backward of the encoder's [32768, 768] bf16 rows move 0.35 GB
together (3 + 4 tensors of 50.3 MB).
"""
BOUND = "hbm"


def work(call):
    return 0.0, call.hbm_bytes
