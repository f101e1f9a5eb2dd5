"""LFM2-MoE decoder language model (LiquidAI `lfm2_moe`, e.g. LFM2-8B-A1B)
as a training `Program`.

The first decoder LM built through the layer DSL: pre-norm residual blocks
whose operator is either a gated short convolution or causal grouped-query
attention with per-head QK-norm and RoPE, and whose feed-forward is a dense
SwiGLU MLP in the leading layers and a dropless, bias-routed mixture of
SwiGLU experts after them:

    h = x + Op(RMSNorm(x));   y = h + FFN(RMSNorm(h))
    model: embedding -> layers -> RMSNorm -> logits = h E^T (tied head)
    loss:  mean next-token cross-entropy

The layers are unlike one another (`layer_types`), so they are built one by
one and not scanned (`fused_encoder_stack` scans identical layers).

A chip's share of a deployment is part of the configuration: `experts_held`
/ `first_expert` say which experts' weights this program holds while the
router keeps its published width (`layers.moe_swiglu`), `vocab_rows` says
how many rows of the vocabulary it embeds and scores: a sliced vocabulary
is a smaller vocabulary, ids and labels come from the slice.

Attention runs `fused_multihead_attention(causal=True)`: K and V are
repeated from `num_key_value_heads` to `num_attention_heads` heads in the
[B, S, H] layout before the op, so that it takes the BSH flash kernels
where its shape gates pass, and the backward pass sums the groups.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from ..fluid import layers
from ..fluid.framework import Program, program_guard
from ..fluid.initializer import TruncatedNormalInitializer
from ..fluid.param_attr import ParamAttr

CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass
class Lfm2MoeConfig:
    """The keys of the released `config.json` under their own names, and
    what this program holds of the model."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    max_position_embeddings: int = 128000
    num_dense_layers: int = 2
    # one of CONV / ATTENTION a layer; None: the released pattern, attention
    # at every fourth layer from the third on
    layer_types: Optional[List[str]] = None
    # the share held here: experts first_expert .. first_expert +
    # experts_held - 1 of every expert layer (None: all), and the first
    # vocab_rows rows of the vocabulary (None: all)
    experts_held: Optional[int] = None
    first_expert: int = 0
    vocab_rows: Optional[int] = None
    initializer_range: float = 0.02
    # compute the dense and expert FFN intermediates again in the backward
    # pass instead of keeping them (BertConfig.remat_ffn)
    remat_ffn: bool = False
    # the trainer's balancing rule for the selection bias, b_e += rate *
    # sign(mean load - load_e) after every step (layers.moe_swiglu); the
    # release does not publish its own. 0: the bias stays as initialised
    expert_bias_update_rate: float = 0.0

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [
                ATTENTION if i >= 2 and (i - 2) % 4 == 0 else CONV
                for i in range(self.num_hidden_layers)]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)} are not built")
        if self.conv_bias or not self.use_expert_bias:
            raise ValueError(
                "conv_bias=True and use_expert_bias=False are not built")
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if self.vocab_rows is None:
            self.vocab_rows = self.vocab_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny() -> "Lfm2MoeConfig":
        """For tests: one dense layer, then attention, conv, conv, conv
        over 8 experts, at toy widths."""
        return Lfm2MoeConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=64, num_hidden_layers=5,
            num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_experts_per_tok=2, num_dense_layers=1,
            layer_types=[CONV, ATTENTION, CONV, CONV, CONV],
            max_position_embeddings=128)


def _attr(cfg: Lfm2MoeConfig, name: Optional[str] = None) -> ParamAttr:
    return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
        scale=cfg.initializer_range))


def _linear(cfg, x, size, name):
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=_attr(cfg, f"{name}.weight"), bias_attr=False)


def _repeat_kv(x, kv_heads: int, groups: int, head_dim: int):
    """[B, S, kv_heads * d] -> [B, S, kv_heads * groups * d], KV head j
    serving query heads j * groups .. (j + 1) * groups - 1."""
    if groups == 1:
        return x
    b, s, _ = x.shape
    x = layers.reshape(x, [b, s, kv_heads, 1, head_dim])
    x = layers.expand(x, [1, 1, 1, groups, 1])
    return layers.reshape(x, [b, s, kv_heads * groups * head_dim])


def gqa_attention(cfg: Lfm2MoeConfig, z, name: str, is_test: bool):
    """Causal grouped-query attention with per-head RMSNorm on q and k
    before RoPE; no bias anywhere; scale 1 / sqrt(head_dim)."""
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _linear(cfg, z, nh * d, f"{name}.q_proj")
    k = _linear(cfg, z, nkv * d, f"{name}.k_proj")
    v = _linear(cfg, z, nkv * d, f"{name}.v_proj")
    q = layers.rms_norm(q, cfg.norm_eps, group_size=d,
                        param_attr=ParamAttr(name=f"{name}.q_layernorm.weight"))
    k = layers.rms_norm(k, cfg.norm_eps, group_size=d,
                        param_attr=ParamAttr(name=f"{name}.k_layernorm.weight"))
    q = layers.rope(q, d, cfg.rope_theta)
    k = layers.rope(k, d, cfg.rope_theta)
    k = _repeat_kv(k, nkv, nh // nkv, d)
    v = _repeat_kv(v, nkv, nh // nkv, d)
    ctx = layers.fused_multihead_attention(
        q, k, v, None, num_heads=nh, causal=True, is_test=is_test)
    return _linear(cfg, ctx, cfg.hidden_size, f"{name}.out_proj")


def decoder_layer(cfg: Lfm2MoeConfig, x, index: int, is_test: bool):
    """One pre-norm block."""
    name = f"layers.{index}"
    z = layers.rms_norm(x, cfg.norm_eps, param_attr=ParamAttr(
        name=f"{name}.operator_norm.weight"))
    if cfg.layer_types[index] == ATTENTION:
        op = gqa_attention(cfg, z, f"{name}.self_attn", is_test)
    else:
        op = layers.short_conv(z, cfg.conv_L_cache, param_attr=_attr(cfg),
                               name=f"{name}.conv")
    h = layers.elementwise_add(x, op)
    z = layers.rms_norm(h, cfg.norm_eps, param_attr=ParamAttr(
        name=f"{name}.ffn_norm.weight"))
    if index < cfg.num_dense_layers:
        ffn = layers.swiglu_ffn(z, cfg.intermediate_size, remat=cfg.remat_ffn,
                                param_attr=_attr(cfg),
                                name=f"{name}.feed_forward")
    else:
        ffn, _ = layers.moe_swiglu(
            z, cfg.num_experts, cfg.moe_intermediate_size,
            experts_held=cfg.experts_held, first_expert=cfg.first_expert,
            top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            remat=cfg.remat_ffn, param_attr=_attr(cfg),
            bias_update_rate=0.0 if is_test else cfg.expert_bias_update_rate,
            # the released buffer starts at zero and is moved by the
            # trainer's balancing rule; a program that starts from random
            # weights starts it random too, so that the selection by
            # s + b is exercised from the first step
            bias_attr=_attr(cfg), name=f"{name}.feed_forward")
    return layers.elementwise_add(h, ffn)


def build_lfm2_moe_pretrain_program(
    cfg: Lfm2MoeConfig,
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
    row. `tokens_per_expert(main_program)` lists the counters of the
    expert layers, one [experts_held] int32 variable a layer."""
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
        for i in range(cfg.num_hidden_layers):
            x = decoder_layer(cfg, x, i, is_test)
        x = layers.rms_norm(x, cfg.norm_eps, param_attr=ParamAttr(
            name="embedding_norm.weight"))
        flat = layers.reshape(x, [batch_size * seq_len, cfg.hidden_size])
        embedding = main.global_block().var("embed_tokens.weight")
        logits = layers.matmul(flat, embedding, transpose_y=True)
        loss = layers.reduce_mean(layers.softmax_with_cross_entropy(
            logits, layers.reshape(labels, [batch_size * seq_len, 1])))
    return main, startup, ["input_ids", "labels"], loss


def tokens_per_expert(program: Program) -> list:
    """The `TokensPerExpert` variable of every expert layer, in layer
    order: fetch them beside the loss to see each held expert's load."""
    block = program.global_block()
    return [block.var(n) for op in block.ops if op.type == "moe_swiglu"
            for n in op.outputs.get("TokensPerExpert", [])]
