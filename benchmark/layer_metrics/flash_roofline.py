"""Share of its roofline the flash-attention kernels reach: least time by
kernels/flash_bsh_{fwd,bwd}.py over the time the trace shows, per cent."""
LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "%"
SOURCE = "device_trace"
KERNELS = ("flash_bsh_fwd", "flash_bsh_bwd")


def read(run):
    return run.kernel_roofline_pct(KERNELS)
