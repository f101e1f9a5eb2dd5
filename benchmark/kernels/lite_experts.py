"""The grouped products of the routed experts of a `glm4_moe_lite`
configuration (`ops/moe_ops.py:moe_swiglu`, scope `moe_experts`; the
`moe_gmm_*` kernels of `ops/pallas/grouped_matmul.py` on the chip): three
products a layer (W1, W3, W2) over the rows routed to the experts held,
three passes a step (forward, input gradient, weight gradient), in every
expert layer: the trunk's `num_hidden_layers - first_k_dense_replace` and
the multi-token-prediction module's `num_nextn_predict_layers`, whose block
is one more. `kernels/moe_experts.py`'s count, handed this family's key
names under the ones it reads, as `kernels/routed_experts.py` does for
`xing4_0`; the shared expert is not part of it (scope `shared_expert`). It
rests on the **expected** rows, tokens x experts per token x held / router
width, which is what uniform routing gives. Recomputation under
`remat_ffn` is not counted.

Bound: the larger of the two, in the reader. At 512 rows an expert against
weights of 2048 x 1536 the products do 512 FLOPs a weight byte around the
chip's ridge of 240, so the bytes (every held expert's weights once a
product and pass, and the rows in and out) are within 2 x of the
arithmetic.
"""
from benchmark import manifest


def _as_moe_experts(config: dict) -> dict:
    """The configuration under the key names `kernels/moe_experts.py`
    reads, the module's expert layer counted with the trunk's."""
    return dict(
        config, num_experts=config["n_routed_experts"],
        num_dense_layers=config["first_k_dense_replace"],
        num_hidden_layers=(config["num_hidden_layers"]
                           + config["num_nextn_predict_layers"]))


def _count():
    return manifest.load_module("kernels", "moe_experts")


def expected_rows(config: dict, tokens: int) -> float:
    return _count().expected_rows(_as_moe_experts(config), tokens)


def moe_layers(config: dict) -> int:
    return _count().moe_layers(_as_moe_experts(config))


def step_work(config: dict, tokens: int):
    """(FLOPs, HBM bytes) of all expert layers' routed products in one
    step."""
    return _count().step_work(_as_moe_experts(config), tokens)
