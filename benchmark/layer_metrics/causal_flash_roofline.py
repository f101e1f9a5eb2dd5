"""Share of its roofline the causal flash-attention kernels reach: least
time by kernels/flash_bsh_causal_{fwd,bwd}.py (the tiles on or below the
diagonal) over the time the trace shows, per cent."""
LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "%"
SOURCE = "device_trace"
KERNELS = ("flash_bsh_causal_fwd", "flash_bsh_causal_bwd")


def read(run):
    return run.kernel_roofline_pct(KERNELS)
