"""Device milliseconds a step in the flash kernels latent attention calls
on heads that are a kernel width as they stand (256 / 256, one call a
group of heads), forward and backward, found in the trace by their
pallas_call names through the compiled step's text and summed over the
calls. Absent where the step runs neither."""
LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
KERNELS = ("flash_mla_wide_causal_fwd", "flash_mla_wide_causal_bwd")


def read(run):
    return run.kernel_ms_per_step(KERNELS)
