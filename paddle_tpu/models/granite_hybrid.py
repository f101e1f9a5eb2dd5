"""Granite-4.0-H hybrid decoder language model (IBM `granitemoehybrid`
without experts, e.g. granite-4.0-h-micro) as a training `Program`.

A decoder whose layer is **two** sublayers, each behind its own RMSNorm
and each added to the residual scaled by `residual_multiplier`:

    h_0 = embedding_multiplier * E[x]
    u   = h + residual_multiplier * Mixer_l(RMSNorm(h))
    h'  = u + residual_multiplier * MLP(RMSNorm(u))
    logits = RMSNorm(h_L) E^T / logits_scaling       (tied head)
    loss:  mean next-token cross-entropy

Mixer_l by `layer_types[l]`:

- `mamba`, a Mamba-2 state-space mixer (`layers.mamba2`, "Transformers are
  SSMs", arXiv:2405.21060): `mamba_n_heads` heads of `mamba_d_head`,
  `mamba_n_groups` groups of B and C of `mamba_d_state` (one group: every
  head reads the same B and C, and the gated norm runs over all of d_in),
  a causal depthwise convolution of `mamba_d_conv` taps with bias and
  SiLU, the recurrence in chunks of `mamba_chunk_size`;
- `attention`, causal grouped-query attention without bias and **without
  a position term** (`position_embedding_type` "nope"), scores scaled by
  `attention_multiplier` and not by 1 / sqrt(head width), K and V repeated
  to the query heads in front of `fused_multihead_attention`, as
  `models/lfm2_moe.py` does.

MLP is the dense SwiGLU of `shared_intermediate_size` (`layers.swiglu_ffn`;
the release's `input_linear` is [W_gate; W_up], its `output_linear` W_o).
The MLP sublayer, its norm and its scaled residual add lower under the
scope `block_mlp` (`fluid.name_scope`).

A chip's share of a deployment is part of the configuration: `vocab_rows`
says how many rows of the vocabulary it embeds and scores; the layers are
held whole.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from ..fluid import layers
from ..fluid.framework import Program, name_scope, program_guard
from ..fluid.initializer import TruncatedNormalInitializer
from ..fluid.param_attr import ParamAttr
from .lfm2_moe import _repeat_kv

MAMBA, ATTENTION = "mamba", "attention"


def _published_layer_types() -> List[str]:
    """granite-4.0-h-micro's 40 layers: attention at 5, 15, 25 and 35."""
    return [ATTENTION if i % 10 == 5 else MAMBA for i in range(40)]


@dataclasses.dataclass
class GraniteHybridConfig:
    """The keys of the released `config.json` under their own names, and
    what this program holds of the model."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: List[str] = dataclasses.field(
        default_factory=_published_layer_types)
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    max_position_embeddings: int = 131072
    # the share held here: the first vocab_rows rows of the vocabulary
    # (None: all)
    vocab_rows: Optional[int] = None
    initializer_range: float = 0.02
    # compute the MLP's intermediates again in the backward pass instead of
    # keeping them
    remat_ffn: bool = False

    def __post_init__(self):
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"{self.num_hidden_layers} layers")
        unknown = set(self.layer_types) - {MAMBA, ATTENTION}
        if unknown:
            raise ValueError(f"mixers {sorted(unknown)} are not built")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is no multiple of the heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are no multiple of the KV heads")
        if self.vocab_rows is None:
            self.vocab_rows = self.vocab_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**changes) -> "GraniteHybridConfig":
        """For tests: mamba, attention, mamba at toy widths; one group of B
        and C over eight Mamba heads, and a chunk a quarter of a 32-token
        row."""
        return GraniteHybridConfig(**{**dict(
            vocab_size=256, hidden_size=64, shared_intermediate_size=128,
            num_hidden_layers=3, layer_types=[MAMBA, ATTENTION, MAMBA],
            num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
            mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16,
            mamba_chunk_size=8, max_position_embeddings=4096), **changes})


def _attr(cfg: GraniteHybridConfig, name: Optional[str] = None) -> ParamAttr:
    return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
        scale=cfg.initializer_range))


def _linear(cfg, x, size, name):
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=_attr(cfg, f"{name}.weight"), bias_attr=False)


def _norm(cfg, x, name):
    return layers.rms_norm(x, cfg.rms_norm_eps,
                           param_attr=ParamAttr(name=f"{name}.weight"))


def _scaled_add(cfg, x, branch):
    return layers.elementwise_add(
        x, layers.scale(branch, cfg.residual_multiplier))


def mamba_mixer(cfg: GraniteHybridConfig, z, name: str):
    """`layers.mamba2` at the configuration's sizes. The released config
    has no time_step_* keys: dt_bias starts at the layer's defaults, the
    Mamba-2 release's. The out_proj starts at the model's initializer like
    every other matrix."""
    out, _ = layers.mamba2(
        z, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
        cfg.mamba_d_state, conv_kernel=cfg.mamba_d_conv,
        chunk_size=cfg.mamba_chunk_size, epsilon=cfg.rms_norm_eps,
        param_attr=_attr(cfg), name=name)
    return out


def attention_mixer(cfg: GraniteHybridConfig, z, name: str, is_test: bool):
    """Causal grouped-query attention, no bias and no rotation; scores
    scaled by attention_multiplier."""
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _linear(cfg, z, nh * d, f"{name}.q_proj")
    k = _repeat_kv(_linear(cfg, z, nkv * d, f"{name}.k_proj"), nkv,
                   nh // nkv, d)
    v = _repeat_kv(_linear(cfg, z, nkv * d, f"{name}.v_proj"), nkv,
                   nh // nkv, d)
    ctx = layers.fused_multihead_attention(
        q, k, v, None, num_heads=nh, causal=True, is_test=is_test,
        softmax_scale=cfg.attention_multiplier)
    return _linear(cfg, ctx, cfg.hidden_size, f"{name}.o_proj")


def decoder_layer(cfg: GraniteHybridConfig, x, index: int, is_test: bool):
    """The mixer `layer_types` names, then the MLP, each behind its norm on
    a scaled residual."""
    name = f"layers.{index}"
    z = _norm(cfg, x, f"{name}.input_layernorm")
    if cfg.layer_types[index] == MAMBA:
        out = mamba_mixer(cfg, z, f"{name}.mamba")
    else:
        out = attention_mixer(cfg, z, f"{name}.self_attn", is_test)
    x = _scaled_add(cfg, x, out)
    with name_scope("block_mlp"):
        z = _norm(cfg, x, f"{name}.post_attention_layernorm")
        return _scaled_add(cfg, x, layers.swiglu_ffn(
            z, cfg.shared_intermediate_size, remat=cfg.remat_ffn,
            param_attr=_attr(cfg), name=f"{name}.shared_mlp"))


def build_granite_hybrid_pretrain_program(
    cfg: GraniteHybridConfig,
    batch_size: int,
    seq_len: int,
    is_test: bool = False,
    main_program: Optional[Program] = None,
    startup_program: Optional[Program] = None,
) -> Tuple[Program, Program, List[str], object]:
    """Next-token pre-training graph at static shapes.

    Returns (main_program, startup_program, feed_names, loss_var). Feeds:
    `input_ids` and `labels`, both [B, S] int32 in [0, vocab_rows), labels
    the token that follows each position. Every row starts from a zero
    state at position 0 and carries it to its end, across any document
    boundary packed into the row. The table `embed_tokens.weight` is one
    parameter that the look-up and the head both use."""
    if seq_len > cfg.max_position_embeddings:
        raise ValueError(
            f"seq_len {seq_len} over max_position_embeddings "
            f"{cfg.max_position_embeddings}")
    main = main_program or Program()
    startup = startup_program or Program()
    with program_guard(main, startup):
        input_ids, labels = (
            layers.data(name, shape=[batch_size, seq_len], dtype="int32",
                        append_batch_size=False)
            for name in ("input_ids", "labels"))
        x = layers.scale(layers.embedding(
            input_ids, size=[cfg.vocab_rows, cfg.hidden_size],
            param_attr=_attr(cfg, "embed_tokens.weight")),
            cfg.embedding_multiplier)
        for i in range(cfg.num_hidden_layers):
            x = decoder_layer(cfg, x, i, is_test)
        x = _norm(cfg, x, "norm")
        tokens = batch_size * seq_len
        table = main.global_block().var("embed_tokens.weight")
        logits = layers.scale(layers.matmul(
            layers.reshape(x, [tokens, cfg.hidden_size]), table,
            transpose_y=True), 1.0 / cfg.logits_scaling)
        loss = layers.reduce_mean(layers.softmax_with_cross_entropy(
            logits, layers.reshape(labels, [tokens, 1])))
    return main, startup, ["input_ids", "labels"], loss
