"""Op library: JAX emitters registered by op type.

Importing this package registers all built-in ops (the analog of the
reference's static REGISTER_OPERATOR initializers).
"""
from . import registry  # noqa: F401
from . import (  # noqa: F401
    attention,
    collective_ops,
    compare_ops,
    control_flow_ops,
    creation,
    decoder_ops,
    detection2_ops,
    detection3_ops,
    detection_ops,
    encoder_stack,
    latent_ops,
    manipulation,
    math_ops,
    misc_ops,
    moe_ops,
    nn_ops,
    optimizer_ops,
    ps_ops,
    quant_ops,
    recompute,
    reduce_ops,
    sequence_ops,
    ssm_ops,
    vision_ops,
)
from .registry import EmitContext, OpSpec, get, register, registered_ops  # noqa: F401
