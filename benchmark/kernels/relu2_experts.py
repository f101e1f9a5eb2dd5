"""The grouped products of the routed squared-ReLU experts of a
`nemotron_h` configuration (`ops/moe_ops.py:moe_swiglu` built without W3,
scope `moe_experts`): **two** products a layer (W1, W2) over the rows
routed to the experts held, three passes a step (forward, input gradient,
weight gradient), at the unpadded width `moe_intermediate_size`. It rests
on the **expected** rows, tokens x experts per token x held / router
width, which is what uniform routing gives. The shared expert is not part
of it (scope `shared_expert`); recomputation under `remat_ffn` is not
counted.

Bound: the larger of the two. At 384 rows an expert against weights of
2688 x 1856 the products do 384 FLOPs a weight byte around the chip's
ridge of 240, so the bytes (every held expert's weights once a product and
pass, and the rows in and out) stand close to the arithmetic.
"""
PASSES = 3
PRODUCTS = 2
WEIGHT_BYTES = 2  # bf16 under AMP


def expected_rows(config: dict, tokens: int) -> float:
    return (tokens * config["num_experts_per_tok"] * config["experts_held"]
            / config["n_routed_experts"])


def moe_layers(config: dict) -> int:
    return config["hybrid_override_pattern"].count("E")


def step_work(config: dict, tokens: int):
    """(FLOPs, HBM bytes) of all expert layers' routed products in one
    step."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = expected_rows(config, tokens)
    calls = PASSES * PRODUCTS * moe_layers(config)
    return (calls * 2.0 * rows * h * f,
            calls * WEIGHT_BYTES * (config["experts_held"] * h * f
                                    + rows * (h + f)))
