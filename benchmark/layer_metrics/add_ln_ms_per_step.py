"""Device milliseconds a step in the fused residual + LayerNorm kernels,
forward and backward."""
LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
KERNELS = ("add_ln_fwd", "add_ln_bwd")


def read(run):
    return run.kernel_ms_per_step(KERNELS)
