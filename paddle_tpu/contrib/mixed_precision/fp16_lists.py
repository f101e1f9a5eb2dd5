"""AMP op lists.

Parity: /root/reference/python/paddle/fluid/contrib/mixed_precision/fp16_lists.py.
White = compute in low precision (MXU ops), black = keep float32
(reductions / loss / normalization statistics), gray = follow neighbors
(here: left untouched; mixed-dtype elementwise promotes to f32 naturally).
"""
from __future__ import annotations


class AutoMixedPrecisionLists:
    def __init__(self, custom_white_list=None, custom_black_list=None):
        self.white_list = set(white_list)
        self.black_list = set(black_list)
        if custom_white_list:
            self.white_list |= set(custom_white_list)
            self.black_list -= set(custom_white_list)
        if custom_black_list:
            self.black_list |= set(custom_black_list)
            self.white_list -= set(custom_black_list)


white_list = {
    "matmul",
    "matmul_v2",
    "mul",
    "conv2d",
    "conv3d",
    "depthwise_conv2d",
    "conv2d_transpose",
    "fused_multihead_attention",
    # the whole fused stack runs in bf16; its emitter keeps layer_norm and
    # softmax internals in f32 (ops/encoder_stack.py) so this is safe
    "fused_encoder_stack",
    "fused_decoder_stack",
    "fc",
    # these emitters compute statistics in f32 internally (ops/nn_ops.py),
    # so bf16 in/out only halves the residual-stream bandwidth
    "layer_norm",
    "batch_norm",
    # fused conv+BN(+relu): conv on the MXU in bf16, statistics and the
    # normalize chain in f32 inside the kernel (ops/pallas/conv_bn.py)
    "fused_conv_bn",
    # decoder blocks (ops/decoder_ops.py, ops/moe_ops.py): products in
    # bf16; norm statistics, conv taps, gates, router scores and the
    # SwiGLU activation in f32 inside the emitters. `rope` is gray: it
    # rotates in f32 and returns its input's dtype
    "rms_norm",
    "short_conv",
    "swiglu_ffn",
    "moe_swiglu",
    # latent attention and the hyper-connection ops (ops/latent_ops.py):
    # products and the residual streams in bf16; the latent norms, the
    # rotation, the three mappings with Sinkhorn and the mixing in f32
    # inside the emitters
    "shared_expert",
    "mla",
    "mhc_map",
    "mhc_pre",
    "mhc_post",
    # the Mamba-2 mixer (ops/ssm_ops.py): both projections and the scan's
    # products in bf16; the convolution, dt, the decays and their running
    # sums, the carried state and the gated norm in f32 inside the emitter
    "mamba2",
}

black_list = {
    "softmax_with_cross_entropy",
    "cross_entropy",
    "cross_entropy2",
    "group_norm",
    "instance_norm",
    "reduce_sum",
    "reduce_mean",
    "mean",
    "sum",
    "softmax",
    "log_softmax",
    "exp",
    "square",
    "sigmoid_cross_entropy_with_logits",
    "bce_loss",
    "squared_l2_norm",
}
