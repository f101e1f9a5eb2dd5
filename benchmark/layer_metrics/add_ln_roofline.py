"""Share of its HBM roofline the fused LayerNorm kernels reach, per cent."""
LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "%"
SOURCE = "device_trace"
KERNELS = ("add_ln_fwd", "add_ln_bwd")


def read(run):
    return run.kernel_roofline_pct(KERNELS)
