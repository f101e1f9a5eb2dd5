"""Device milliseconds a step in the flash kernels latent attention calls,
forward and backward, found in the trace by their pallas_call names through
the compiled step's text. Absent where the step runs neither."""
LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
KERNELS = ("flash_mla_causal_fwd", "flash_mla_causal_bwd")


def read(run):
    return run.kernel_ms_per_step(KERNELS)
