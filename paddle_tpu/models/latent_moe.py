"""The two sublayers the DeepSeek-V3-shaped decoders here share: latent
attention on the heads a program holds, and a feed-forward that is a dense
SwiGLU MLP in the leading layers and after them bias-selected routed
experts beside a shared one. `models/xing4.py` puts them inside
hyper-connections on four residual streams, `models/glm4_moe_lite.py` on a
plain residual, in its trunk and in its multi-token-prediction module.

`cfg` is either model's configuration: the released keys under their own
names, the share held (`heads_held`, `experts_held`, `first_expert`),
`softmax_scale`, `inv_freq` (None: the plain rotary table at
`rope_theta`), `remat_ffn`, `expert_bias_update_rate` and
`initializer_range`.
"""
from __future__ import annotations

from typing import Optional

from ..fluid import layers
from ..fluid.initializer import TruncatedNormalInitializer
from ..fluid.param_attr import ParamAttr


def _attr(cfg, name: Optional[str] = None) -> ParamAttr:
    return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
        scale=cfg.initializer_range))


def attention(cfg, z, name: str):
    return layers.mla(
        z, cfg.heads_held, cfg.q_lora_rank, cfg.kv_lora_rank,
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
        cfg.softmax_scale, epsilon=cfg.rms_norm_eps, theta=cfg.rope_theta,
        inv_freq=cfg.inv_freq, param_attr=_attr(cfg), name=name)


def feed_forward(cfg, z, index: int, name: str, is_test: bool):
    """Dense SwiGLU in the leading layers; after them the held routed
    experts' part plus the shared expert."""
    if index < cfg.first_k_dense_replace:
        return layers.swiglu_ffn(z, cfg.intermediate_size,
                                 remat=cfg.remat_ffn, param_attr=_attr(cfg),
                                 name=name)
    routed, _ = layers.moe_swiglu(
        z, cfg.n_routed_experts, cfg.moe_intermediate_size,
        experts_held=cfg.experts_held, first_expert=cfg.first_expert,
        top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        remat=cfg.remat_ffn, param_attr=_attr(cfg),
        bias_update_rate=0.0 if is_test else cfg.expert_bias_update_rate,
        # started random, as models/lfm2_moe.py does and for its reason:
        # selection by s + b is exercised from the first step
        bias_attr=_attr(cfg), name=name)
    shared = layers.shared_expert(
        z, cfg.moe_intermediate_size * cfg.n_shared_experts,
        remat=cfg.remat_ffn, param_attr=_attr(cfg),
        name=f"{name}.shared_experts")
    return layers.elementwise_add(routed, shared)


def outputs_of(program, op_type: str, slot: str) -> list:
    block = program.global_block()
    return [block.var(n) for op in block.ops if op.type == op_type
            for n in op.outputs.get(slot, [])]


def tokens_per_expert(program) -> list:
    """The `TokensPerExpert` variable of every expert layer, in the order
    the layers were built: fetch them beside the loss to see each held
    expert's load."""
    return outputs_of(program, "moe_swiglu", "TokensPerExpert")
