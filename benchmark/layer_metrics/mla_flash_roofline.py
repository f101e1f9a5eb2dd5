"""Share of its roofline latent attention's flash kernels reach: least time
by kernels/flash_mla_causal_{fwd,bwd}.py (the tiles on or below the
diagonal, 192-wide scores and 128-wide values: the unpadded heads) over
the time the trace shows, per cent."""
LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "%"
SOURCE = "device_trace"
KERNELS = ("flash_mla_causal_fwd", "flash_mla_causal_bwd")


def read(run):
    return run.kernel_roofline_pct(KERNELS)
