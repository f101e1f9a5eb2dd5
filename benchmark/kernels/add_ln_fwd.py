"""`add_ln_fwd` (ops/pallas/add_ln.py): LayerNorm(x + y) over rows of H,
one pass per row block.

Bound: HBM. Bytes: x, y (where there is a residual) and the output, each
[R, H] once, plus the two [1, R] statistics and the scale and shift: every
operand read once and every result written once, as far as the compiled
step keeps them in HBM. XLA's memory-space assignment puts some of the
[32768, 768] activations into the chip's on-chip memory (layout `S(1)`),
and those cost no HBM traffic; counted as if all were in HBM, the call would
read as faster than its roofline. The arithmetic is vector work (about ten
operations an element), for which the bf16 matrix peak is no bound, so no
operations are counted.
"""
BOUND = "hbm"


def work(call):
    return 0.0, call.hbm_bytes
