"""Share of its roofline latent attention's flash kernels reach on heads
of 256 / 256: least time by kernels/flash_mla_wide_causal_{fwd,bwd}.py
(the tiles on or below the diagonal, every product over the call's own
columns: nothing is padded), summed over the calls of all head groups,
over the time the trace shows, per cent."""
LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "%"
SOURCE = "device_trace"
KERNELS = ("flash_mla_wide_causal_fwd", "flash_mla_wide_causal_bwd")


def read(run):
    return run.kernel_roofline_pct(KERNELS)
