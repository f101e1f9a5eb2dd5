"""Xing4.0 decoder language model (XingChen-AGI `xing4_0`, e.g.
Xing4.0-29B-A4B) as a training `Program`.

A DeepSeek-V3-shaped sparse decoder on a four-stream residual:

- every sublayer F (attention, feed-forward) sits inside
  manifold-constrained hyper-connections ("mHC", arXiv:2512.24880) on
  n = `hc_mult` residual streams X in R^{n x C}:

      H_pre, H_post, H_res = mhc_map(X)       per token, float32, H_res
                                              doubly stochastic (Sinkhorn)
      X' = H_res X + H_post^T F(RMSNorm(H_pre X))

  and not x + F(norm(x)). The streams start as n copies of the token's
  embedding and are summed before the final norm;
- attention is multi-head latent attention (`layers.mla`): low-rank q and
  kv projections, 128 + 64 wide q / k heads whose 64-wide part is rotated
  at YaRN's frequencies, 128-wide v heads, a softmax scale YaRN multiplies;
- the feed-forward is a dense SwiGLU MLP in the leading
  `first_k_dense_replace` layers and after them `n_routed_experts`
  sigmoid-scored, bias-selected (`noaux_tc`) SwiGLU experts, top-k with
  renormalised gates times `routed_scaling_factor`, beside a shared expert
  that every token passes;
- the head is untied; the loss is mean next-token cross-entropy.

A chip's share of a deployment is part of the configuration: `heads_held`
/ `first_head` say which attention heads' weights this program holds,
`experts_held` / `first_expert` which routed experts', `vocab_rows` how
many rows of the vocabulary it embeds and scores. The router keeps its
published width; what absent heads and experts would add is left out.
The attention and the feed-forward are `models/latent_moe.py`'s, which
`models/glm4_moe_lite.py` shares. Multi-token prediction
(`num_nextn_predict_layers`) is not built on the four-stream residual:
`models/glm4_moe_lite.py` builds the module, on a plain one.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

import numpy as np

from ..fluid import layers
from ..fluid.framework import Program, program_guard
from ..fluid.initializer import NormalInitializer
from ..fluid.param_attr import ParamAttr
from .latent_moe import (_attr, attention, feed_forward,  # noqa: F401
                         outputs_of, tokens_per_expert)


def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """The dim/2 rotary frequencies under YaRN, float64, as the
    DeepSeek-V3 release computes them: pair i turns at theta^(-2i/dim)
    where it makes more than `beta_fast` turns over the original context,
    at 1/`factor` of that where it makes fewer than `beta_slow`, and at a
    linear blend between the two correction dimensions."""
    base, original = float(theta), scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    plain = base ** (-2.0 * i / dim)
    # 0: the pair keeps its own frequency; 1: it turns `factor` times slower
    slowed = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain / scaling["factor"] * slowed + plain * (1.0 - slowed)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass
class Xing4Config:
    """The keys of the released `config.json` under their own names, and
    what this program holds of the model."""
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 262144
    num_nextn_predict_layers: int = 0
    # the share held here: attention heads first_head .. first_head +
    # heads_held - 1 (None: all), routed experts first_expert ..
    # first_expert + experts_held - 1 of every expert layer (None: all),
    # and the first vocab_rows rows of the vocabulary (None: all)
    heads_held: Optional[int] = None
    first_head: int = 0
    experts_held: Optional[int] = None
    first_expert: int = 0
    vocab_rows: Optional[int] = None
    initializer_range: float = 0.02
    # the hyper-connections' start, which the release does not publish:
    # the gating factors a_*, and the standard deviation the static
    # mappings b_* are drawn at
    hc_alpha_init: float = 0.01
    hc_bias_std: float = 1.0
    remat_ffn: bool = False
    # layers.moe_swiglu's balancing rule for the selection bias
    expert_bias_update_rate: float = 0.0

    def __post_init__(self):
        if self.num_nextn_predict_layers:
            raise ValueError(
                "multi-token prediction is not built on the four-stream "
                "residual (models/glm4_moe_lite.py builds the module on a "
                "plain one)")
        if self.rope_scaling is None:
            self.rope_scaling = {
                "type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
                "mscale": 1, "mscale_all_dim": 1,
                "original_max_position_embeddings": 4096}
        if self.rope_scaling.get("type") != "yarn":
            raise ValueError("rope_scaling of another type than yarn")
        if self.rope_scaling["mscale"] != self.rope_scaling["mscale_all_dim"]:
            raise ValueError(
                "mscale != mscale_all_dim scales cos and sin: not built")
        if self.heads_held is None:
            self.heads_held = self.num_attention_heads
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if self.vocab_rows is None:
            self.vocab_rows = self.vocab_size
        if self.first_head + self.heads_held > self.num_attention_heads:
            raise ValueError("heads held beyond num_attention_heads")

    @property
    def softmax_scale(self) -> float:
        """(nope + rope)^(-1/2), times YaRN's mscale squared."""
        m = yarn_mscale(self.rope_scaling["factor"],
                        self.rope_scaling["mscale_all_dim"])
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @property
    def inv_freq(self) -> np.ndarray:
        return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                             self.rope_scaling)

    @staticmethod
    def tiny(**changes) -> "Xing4Config":
        """For tests: one dense layer and two expert layers at toy widths,
        head widths unequal as published (24 + 8 against 16)."""
        return Xing4Config(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=8, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=16, num_experts_per_tok=2,
            max_position_embeddings=4096), **changes})


def hyper_connected(cfg: Xing4Config, streams, sublayer, name: str,
                    norm_name: str):
    """X' = H_res X + H_post^T F(RMSNorm(H_pre X)) for the sublayer F."""
    h_pre, h_post, h_res, _ = layers.mhc_map(
        streams, cfg.hc_mult, epsilon=cfg.hc_eps,
        sinkhorn_iters=cfg.hc_sinkhorn_iters,
        clamp_min=cfg.mhc_h_res_clamp_min, clamp_max=cfg.mhc_h_res_clamp_max,
        alpha_init=cfg.hc_alpha_init, param_attr=_attr(cfg),
        bias_attr=ParamAttr(initializer=NormalInitializer(
            0.0, cfg.hc_bias_std)), name=name)
    z = layers.rms_norm(layers.mhc_pre(streams, h_pre), cfg.rms_norm_eps,
                        param_attr=ParamAttr(name=norm_name))
    return layers.mhc_post(streams, sublayer(z), h_res, h_post)


def decoder_layer(cfg: Xing4Config, streams, index: int, is_test: bool):
    name = f"layers.{index}"
    streams = hyper_connected(
        cfg, streams, lambda z: attention(cfg, z, f"{name}.self_attn"),
        f"{name}.attn_hc", f"{name}.input_layernorm.weight")
    return hyper_connected(
        cfg, streams,
        lambda z: feed_forward(cfg, z, index, f"{name}.mlp", is_test),
        f"{name}.ffn_hc", f"{name}.post_attention_layernorm.weight")


def build_xing4_pretrain_program(
    cfg: Xing4Config,
    batch_size: int,
    seq_len: int,
    is_test: bool = False,
    main_program: Optional[Program] = None,
    startup_program: Optional[Program] = None,
) -> Tuple[Program, Program, List[str], object]:
    """Next-token pre-training graph at static shapes.

    Returns (main_program, startup_program, feed_names, loss_var). Feeds:
    `input_ids` and `labels`, both [B, S] int32 in [0, vocab_rows), labels
    the token that follows each position; positions are 0 .. S-1 in every
    row. `tokens_per_expert(main_program)` and
    `sinkhorn_gaps(main_program)` list what can be fetched beside the
    loss."""
    if seq_len > cfg.max_position_embeddings:
        raise ValueError(
            f"seq_len {seq_len} over max_position_embeddings "
            f"{cfg.max_position_embeddings}")
    main = main_program or Program()
    startup = startup_program or Program()
    with program_guard(main, startup):
        input_ids = layers.data("input_ids", shape=[batch_size, seq_len],
                                dtype="int32", append_batch_size=False)
        labels = layers.data("labels", shape=[batch_size, seq_len],
                             dtype="int32", append_batch_size=False)
        x = layers.embedding(
            input_ids, size=[cfg.vocab_rows, cfg.hidden_size],
            param_attr=_attr(cfg, "embed_tokens.weight"))
        streams = layers.expand(x, [1, 1, cfg.hc_mult])
        for i in range(cfg.num_hidden_layers):
            streams = decoder_layer(cfg, streams, i, is_test)
        x = layers.rms_norm(
            layers.mhc_pre(streams, streams=cfg.hc_mult), cfg.rms_norm_eps,
            param_attr=ParamAttr(name="norm.weight"))
        flat = layers.reshape(x, [batch_size * seq_len, cfg.hidden_size])
        head = layers.create_parameter(
            [cfg.vocab_rows, cfg.hidden_size], "float32",
            attr=_attr(cfg, "lm_head.weight"))
        logits = layers.matmul(flat, head, transpose_y=True)
        loss = layers.reduce_mean(layers.softmax_with_cross_entropy(
            logits, layers.reshape(labels, [batch_size * seq_len, 1])))
    return main, startup, ["input_ids", "labels"], loss


def sinkhorn_gaps(program: Program) -> list:
    """One [hc_mult] float32 variable a sublayer, attention before
    feed-forward in layer order: the worst distance of a row's or a
    column's sum of H_res from 1 over the step's tokens."""
    return outputs_of(program, "mhc_map", "SinkhornGap")
