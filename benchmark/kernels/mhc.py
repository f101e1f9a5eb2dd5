"""The residual path of manifold-constrained hyper-connections
(`paddle_tpu/ops/latent_ops.py`, scopes `mhc_map` and `mhc_mix`): n streams
of C columns a token, mixed twice a layer (around attention and around the
feed-forward).

Bound: HBM bandwidth. The arithmetic is a few multiply-adds an element
(n + 1 for H_res X + H_post^T y, 1 for H_pre X, 2 x 24 / 4 for xbar phi);
the bytes are the streams themselves, n C bf16 elements a token. The
**least** traffic of one sublayer and pass is the streams read once and
written once: the forward pass reads X and writes X', the backward pass
reads dX' and writes dX. What the program moves beyond that (X read again
for the mappings and for H_pre X, X read again in the backward pass for the
mappings' gradients, the sublayer's own input and output) is what a fused
kernel could save, so it is not part of the least.
"""
BOUND = "hbm"
PASSES = 2  # forward, backward
STREAM_BYTES = 2  # bf16 under AMP


def sublayers(config: dict) -> int:
    return 2 * config["num_hidden_layers"]


def step_bytes(config: dict, tokens: int) -> float:
    """Least HBM bytes of all hyper-connected sublayers in one step."""
    streams = tokens * config["hc_mult"] * config["hidden_size"] * STREAM_BYTES
    return PASSES * sublayers(config) * 2.0 * streams
