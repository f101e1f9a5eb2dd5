"""The state-space scan of a Mamba-2 layer (`paddle_tpu/ops/ssm_ops.py`,
scope `ssd_scan` inside `mamba2`): x [T, H, P], dt [T, H], B and C
[T, G, N] -> y [T, H, P], three passes a step (forward, the forward run
again for the backward pass, the backward pass itself counted as one: a
**least**, the transposes do twice the forward's arithmetic).

The count is from the shapes alone and the same whatever implements the
scan. Arithmetic: the chunked form's products at chunk Q (C B^T inside a
chunk, the masked [Q, Q] matrix times x, the state a chunk leaves behind,
the entering state read out); a position-by-position recurrence would do
H P N multiply-adds twice a token, which is less arithmetic and no matrix
product, so the chunked form's is what the MXU can be held to. Bytes: x,
dt, B and C read and y written once a pass; the [Q, Q] matrices and the
chunk states are what a kernel keeps in VMEM, so they are not part of the
least.

Bound: bandwidth at the published sizes (3.4 MFLOP and 20.7 KB a token
and pass: 165 FLOPs a byte against the chip's ridge of 240).
"""
PASSES = 3
ACTIVATION_BYTES = 2  # bf16 under AMP
DT_BYTES = 4


def mamba_layers(config: dict) -> int:
    return config["hybrid_override_pattern"].count("M")


def flops_per_token(config: dict) -> float:
    q, h, p = (config["chunk_size"], config["mamba_num_heads"],
               config["mamba_head_dim"])
    g, n = config["n_groups"], config["ssm_state_size"]
    return 2.0 * (q * g * n + q * h * p + 2 * h * p * n)


def bytes_per_token(config: dict) -> float:
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n = config["n_groups"], config["ssm_state_size"]
    return ACTIVATION_BYTES * (2 * h * p + 2 * g * n) + DT_BYTES * h


def step_work(config: dict, tokens: int):
    """(FLOPs, HBM bytes) of all Mamba-2 layers' scans in one step."""
    calls = PASSES * mamba_layers(config) * tokens
    return calls * flops_per_token(config), calls * bytes_per_token(config)
