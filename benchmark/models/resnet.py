"""Model family `resnet`: ImageNet classification training, He et al. 2015.

Same contract as `bert.py`: program through the user's entry points, batch
generator, model-FLOP formula (a copy of `models/resnet.py:
resnet_step_flops`) and the plain float32 reference.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def units_per_step(traffic: dict) -> int:
    """Images in one step."""
    return int(traffic["batch"])


def model_config(config: dict):
    from paddle_tpu.models.resnet import ResNetConfig

    return ResNetConfig(
        depth=config["depth"], num_classes=config["num_classes"],
        blocks=list(config["blocks"]), base_filters=config["base_filters"])


def build_forward(config: dict, traffic: dict, batch: int, dropout: bool,
                  main, startup):
    """Forward graph into `main`/`startup`; returns (loss, feed names).
    The network has no dropout; the argument is the family contract's."""
    from paddle_tpu.models.resnet import build_resnet_train_program

    _, _, feed_names, loss = build_resnet_train_program(
        model_config(config), batch, int(traffic["image_size"]), main,
        startup)
    return loss, feed_names


def optimizer(config: dict, batch: int):
    """Momentum SGD with the linear scaling rule of Goyal et al. 2017: the
    configured rate is for 256 images, so the cell trains at it and the
    small check batch at its share."""
    import paddle_tpu.fluid as fluid

    opt = config["optimizer"]
    return fluid.optimizer.MomentumOptimizer(
        learning_rate=opt["learning_rate_per_256"] * batch / 256.0,
        momentum=opt["momentum"])


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    """Model FLOPs of one step: 2 FLOPs a multiply-add over every
    convolution and the classifier, forward once and backward twice.
    Copy of `models/resnet.py:resnet_step_flops`."""
    base = config["base_filters"]
    bottleneck = config["depth"] >= 50
    h = int(traffic["image_size"]) // 2
    flops = 2.0 * (7 * 7 * 3) * base * h * h
    h //= 2  # max pool
    cin = filters = base
    for stage, n_blocks in enumerate(config["blocks"]):
        for blk in range(n_blocks):
            stride = 2 if (stage > 0 and blk == 0) else 1
            ho = h // stride
            if bottleneck:
                flops += 2 * cin * filters * h * h
                flops += 2 * 9 * filters * filters * ho * ho
                flops += 2 * filters * filters * 4 * ho * ho
                if stride != 1 or cin != filters * 4:
                    flops += 2 * cin * filters * 4 * ho * ho
                cin = filters * 4
            else:
                flops += 2 * 9 * cin * filters * ho * ho
                flops += 2 * 9 * filters * filters * ho * ho
                if stride != 1 or cin != filters:
                    flops += 2 * cin * filters * ho * ho
                cin = filters
            h = ho
        filters *= 2
    flops += 2 * cin * config["num_classes"]
    return 3.0 * flops * batch


def make_batch(config: dict, traffic: dict, batch: int,
               rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """Decoded images as the reference's readers yield them: float32 NCHW
    in [0, 1) from the host, and int64 labels."""
    size = int(traffic["image_size"])
    return {
        "image": rng.random((batch, 3, size, size), dtype=np.float32),
        "label": rng.integers(
            0, config["num_classes"], (batch, 1), dtype=np.int64),
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _last_block(config: dict) -> str:
    stage = len(config["blocks"]) - 1
    return f"s{stage}.b{config['blocks'][-1] - 1}"


# the classifier's bias is the one parameter the program names itself; the
# harness builds every program under a fresh name generator, so it is fc_0
HEAD_BIAS = "fc_0.b_0"


def check_parameters(config: dict) -> List[Tuple[str, str, object]]:
    """(label, parameter, index) of the gradients the check reads. The
    configuration file says which are judged: at random initialisation this
    network's gradients below the classifier are ill-conditioned."""
    last = "c3" if config["depth"] >= 50 else "c2"
    return [
        ("stem.w", "stem.w", None),
        (f"last_block.{last}.w", f"{_last_block(config)}.{last}.w", None),
        ("head.w", "head.w", None),
        ("head.b", HEAD_BIAS, None),
    ]


def reference_loss_and_grads(config: dict, traffic: dict,
                             params: Dict[str, object],
                             batch: Dict[str, np.ndarray]):
    """ResNet v1 forward and loss in float32, plain `jax.numpy`/`lax`, NCHW
    throughout: 7x7/2 stem, 3x3/2 max pool, bottleneck (or basic) blocks
    with the stride on the 3x3 (the v1.5 placement the program uses),
    projection shortcuts, batch norm on batch statistics (biased variance,
    epsilon 1e-5), global average pool, classifier with bias, mean softmax
    cross-entropy."""
    import jax
    import jax.numpy as jnp

    bottleneck = config["depth"] >= 50
    names = sorted({p for _, p, _ in check_parameters(config)})

    def conv(x, w, stride):
        k = w.shape[-1]
        pad = (k - 1) // 2
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def conv_bn(w, x, name, stride=1, relu=False):
        y = conv(x, w[f"{name}.w"], stride)
        mu = jnp.mean(y, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(y - mu), axis=(0, 2, 3), keepdims=True)
        y = ((y - mu) * jax.lax.rsqrt(var + 1e-5)
             * w[f"{name}.bn_s"].reshape(1, -1, 1, 1)
             + w[f"{name}.bn_b"].reshape(1, -1, 1, 1))
        return jnp.maximum(y, 0.0) if relu else y

    def block(w, x, filters, stride, name):
        if bottleneck:
            out = conv_bn(w, x, f"{name}.c1", relu=True)
            out = conv_bn(w, out, f"{name}.c2", stride, relu=True)
            out = conv_bn(w, out, f"{name}.c3")
            width = filters * 4
        else:
            out = conv_bn(w, x, f"{name}.c1", stride, relu=True)
            out = conv_bn(w, out, f"{name}.c2")
            width = filters
        if stride != 1 or x.shape[1] != width:
            x = conv_bn(w, x, f"{name}.proj", stride)
        return jnp.maximum(out + x, 0.0)

    def loss_fn(wrt, rest, batch):
        w = {**rest, **wrt}
        x = conv_bn(w, batch["image"], "stem", 2, relu=True)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
        filters = config["base_filters"]
        for stage, n_blocks in enumerate(config["blocks"]):
            for blk in range(n_blocks):
                stride = 2 if (stage > 0 and blk == 0) else 1
                x = block(w, x, filters, stride, f"s{stage}.b{blk}")
            filters *= 2
        logits = jnp.mean(x, axis=(2, 3)) @ w["head.w"] + w[HEAD_BIAS]
        logp = logits - jax.scipy.special.logsumexp(
            logits, axis=-1, keepdims=True)
        return -jnp.mean(jnp.take_along_axis(logp, batch["label"], axis=-1))

    wrt = {n: params[n] for n in names}
    rest = {n: v for n, v in params.items() if n not in wrt}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(wrt, rest, batch)
    return loss, grads
