"""The grouped expert products of `moe_swiglu` (ops/moe_ops.py, scope
`moe_experts`): three products a layer (W1, W3, W2) over the rows routed to
the experts held, three passes a step (forward, input gradient, weight
gradient). XLA lowers `jax.lax.ragged_dot` to its own grouped-matmul kernel
(`ragged-dot-none`), so there is no `pallas_call` name to find a call by and
no call shape that says how many rows were live: the count rests on the
**expected** rows, tokens x experts per token x held / router width, which
is what uniform routing gives (PERF.md section 7). Recomputation under
`remat_ffn` is not counted.

Bound: compute (2,048 rows an expert against weights of 2048 x 1792: some
2,000 FLOPs a weight byte, ridge 240); the bytes are every expert's weights
once a product and pass, and the rows in and out.
"""
BOUND = "compute"
PASSES = 3
PRODUCTS = 3


def expected_rows(config: dict, tokens: int) -> float:
    return (tokens * config["num_experts_per_tok"] * config["experts_held"]
            / config["num_experts"])


def moe_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def step_work(config: dict, tokens: int):
    """(FLOPs, HBM bytes) of all expert layers in one step."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = expected_rows(config, tokens)
    layers = moe_layers(config)
    flops = PASSES * PRODUCTS * 2.0 * rows * h * f * layers
    nbytes = PASSES * PRODUCTS * 2.0 * layers * (
        config["experts_held"] * h * f + rows * (h + f))
    return flops, nbytes
