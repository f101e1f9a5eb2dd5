"""Device milliseconds a step in the head-major (BHSD) causal
flash-attention kernels, forward and backward, found in the trace by their
pallas_call names through the compiled step's text: where an attention
call's whole-sequence residency sends it past the BSH kernels
(`bsh_dispatch_ok`: H 4096 at S 4096), these run, between head transposes
that are XLA's and not counted here. Absent where the step runs neither."""
LAYER = "kernels"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
KERNELS = ("flash_fwd", "flash_bwd")


def read(run):
    return run.kernel_ms_per_step(KERNELS)
