"""Nemotron-H hybrid decoder language model (NVIDIA `nemotron_h`, e.g.
NVIDIA-Nemotron-3-Nano-30B-A3B) as a training `Program`.

A decoder whose layer is **one** mixer behind one norm, and not attention
plus a feed-forward:

    x' = x + Mixer_l(RMSNorm_l(x)),   Mixer_l by hybrid_override_pattern[l]
    model: embedding -> layers -> RMSNorm -> logits over an untied head
    loss:  mean next-token cross-entropy

- `M`, a Mamba-2 state-space mixer (`layers.mamba2`, "Transformers are
  SSMs", arXiv:2405.21060): `mamba_num_heads` heads of `mamba_head_dim`
  (that product, not `expand`, sizes the layer), `n_groups` groups of B
  and C of `ssm_state_size`, a causal depthwise convolution of
  `conv_kernel` taps with bias and SiLU, the recurrence in chunks of
  `chunk_size`, a gated RMSNorm over groups of d_in / n_groups;
- `*`, causal grouped-query attention without bias and **without a
  position term** (the Mamba layers carry position; `rope_theta` stands in
  the released config unused by this `model_type`), K and V repeated to
  the query heads in front of `fused_multihead_attention`, as
  `models/lfm2_moe.py` does;
- `E`, `n_routed_experts` sigmoid-scored, bias-selected experts of two
  matrices, W2 relu(W1 x)^2, top-k with renormalised gates times
  `routed_scaling_factor` (`layers.moe_swiglu(activation="relu2")`),
  beside a shared expert of `moe_shared_expert_intermediate_size` that
  every token passes.

The family's dense two-matrix MLP (`-` in a pattern) is not built: the
30B-A3B pattern has none.

A chip's share of a deployment is part of the configuration:
`experts_held` / `first_expert` say which routed experts' weights this
program holds while the router keeps its published width, `vocab_rows` how
many rows of the vocabulary it embeds and scores. The mixers, the shared
expert and the norms are held whole.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from ..fluid import layers
from ..fluid.framework import Program, program_guard
from ..fluid.initializer import TruncatedNormalInitializer
from ..fluid.param_attr import ParamAttr
from .latent_moe import outputs_of, tokens_per_expert  # noqa: F401
from .lfm2_moe import _repeat_kv

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


@dataclasses.dataclass
class NemotronHConfig:
    """The keys of the released `config.json` under their own names, and
    what this program holds of the model."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    rescale_prenorm_residual: bool = True
    max_position_embeddings: int = 262144
    # the share held here: routed experts first_expert .. first_expert +
    # experts_held - 1 of every expert layer (None: all), and the first
    # vocab_rows rows of the vocabulary (None: all)
    experts_held: Optional[int] = None
    first_expert: int = 0
    vocab_rows: Optional[int] = None
    initializer_range: float = 0.02
    # rescale_prenorm_residual divides the start of the Mamba mixers'
    # out_proj by the root of the model's depth: the published one where
    # this program is a cut of it (None: num_hidden_layers)
    residual_scale_layers: Optional[int] = None
    # compute the experts' (routed and shared) intermediates again in the
    # backward pass instead of keeping them
    remat_ffn: bool = False
    # layers.moe_swiglu's balancing rule for the selection bias
    expert_bias_update_rate: float = 0.0

    def __post_init__(self):
        if len(self.hybrid_override_pattern) != self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern has "
                f"{len(self.hybrid_override_pattern)} letters for "
                f"{self.num_hidden_layers} layers")
        unknown = set(self.hybrid_override_pattern) - {MAMBA, ATTENTION,
                                                       EXPERTS}
        if unknown:
            raise ValueError(f"mixers {sorted(unknown)} are not built")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are no multiple of the KV heads")
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if self.vocab_rows is None:
            self.vocab_rows = self.vocab_size
        if self.residual_scale_layers is None:
            self.residual_scale_layers = self.num_hidden_layers

    @staticmethod
    def tiny(**changes) -> "NemotronHConfig":
        """For tests: `MEM*E` at toy widths; two Mamba heads a group, and
        a chunk a quarter of a 32-token row."""
        return NemotronHConfig(**{**dict(
            vocab_size=256, hidden_size=64,
            num_hidden_layers=5, hybrid_override_pattern="MEM*E",
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            mamba_num_heads=8, mamba_head_dim=8, n_groups=4,
            ssm_state_size=16, chunk_size=8, n_routed_experts=16,
            num_experts_per_tok=2, moe_intermediate_size=48,
            moe_shared_expert_intermediate_size=96,
            max_position_embeddings=4096), **changes})


def _attr(cfg: NemotronHConfig, name: Optional[str] = None,
          scale: float = 1.0) -> ParamAttr:
    return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
        scale=cfg.initializer_range * scale))


def _linear(cfg, x, size, name):
    return layers.fc(x, size, num_flatten_dims=2,
                     param_attr=_attr(cfg, f"{name}.weight"), bias_attr=False)


def mamba_mixer(cfg: NemotronHConfig, z, name: str):
    """(out, min_decay): `layers.mamba2` at the configuration's sizes."""
    scale = (cfg.residual_scale_layers ** -0.5
             if cfg.rescale_prenorm_residual else 1.0)
    return layers.mamba2(
        z, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
        cfg.ssm_state_size, conv_kernel=cfg.conv_kernel,
        chunk_size=cfg.chunk_size, epsilon=cfg.layer_norm_epsilon,
        dt_min=cfg.time_step_min, dt_max=cfg.time_step_max,
        dt_floor=cfg.time_step_floor,
        param_attr=_attr(cfg), out_attr=_attr(cfg, scale=scale), name=name)


def attention_mixer(cfg: NemotronHConfig, z, name: str, is_test: bool):
    """Causal grouped-query attention, no bias, no norm on q and k and no
    rotation; scale 1 / sqrt(head_dim)."""
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _linear(cfg, z, nh * d, f"{name}.q_proj")
    k = _repeat_kv(_linear(cfg, z, nkv * d, f"{name}.k_proj"), nkv,
                   nh // nkv, d)
    v = _repeat_kv(_linear(cfg, z, nkv * d, f"{name}.v_proj"), nkv,
                   nh // nkv, d)
    ctx = layers.fused_multihead_attention(
        q, k, v, None, num_heads=nh, causal=True, is_test=is_test)
    return _linear(cfg, ctx, cfg.hidden_size, f"{name}.o_proj")


def experts_mixer(cfg: NemotronHConfig, z, name: str, is_test: bool):
    """The held routed experts' part plus the shared expert."""
    routed, _ = layers.moe_swiglu(
        z, cfg.n_routed_experts, cfg.moe_intermediate_size,
        experts_held=cfg.experts_held, first_expert=cfg.first_expert,
        top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        remat=cfg.remat_ffn, param_attr=_attr(cfg),
        bias_update_rate=0.0 if is_test else cfg.expert_bias_update_rate,
        # started random, as models/lfm2_moe.py does and for its reason:
        # selection by s + b is exercised from the first step
        bias_attr=_attr(cfg), name=name, activation="relu2")
    shared = layers.shared_expert(
        z, cfg.moe_shared_expert_intermediate_size * cfg.n_shared_experts,
        remat=cfg.remat_ffn, param_attr=_attr(cfg),
        name=f"{name}.shared_experts", activation="relu2")
    return layers.elementwise_add(routed, shared)


def decoder_layer(cfg: NemotronHConfig, x, index: int, is_test: bool):
    """One pre-norm block around the mixer the pattern names."""
    name = f"layers.{index}"
    kind = cfg.hybrid_override_pattern[index]
    z = layers.rms_norm(x, cfg.layer_norm_epsilon, param_attr=ParamAttr(
        name=f"{name}.norm.weight"))
    if kind == MAMBA:
        out, _ = mamba_mixer(cfg, z, f"{name}.mixer")
    elif kind == ATTENTION:
        out = attention_mixer(cfg, z, f"{name}.mixer", is_test)
    else:
        out = experts_mixer(cfg, z, f"{name}.mixer", is_test)
    return layers.elementwise_add(x, out)


def build_nemotron_h_pretrain_program(
    cfg: NemotronHConfig,
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
    boundary packed into the row. `tokens_per_expert(main_program)` and
    `min_decays(main_program)` list what can be fetched beside the
    loss (the first is `models/xing4.py`'s: one [experts_held] int32
    variable an expert layer)."""
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
            param_attr=_attr(cfg, "embeddings.weight"))
        for i in range(cfg.num_hidden_layers):
            x = decoder_layer(cfg, x, i, is_test)
        x = layers.rms_norm(x, cfg.layer_norm_epsilon, param_attr=ParamAttr(
            name="norm_f.weight"))
        flat = layers.reshape(x, [batch_size * seq_len, cfg.hidden_size])
        head = layers.create_parameter(
            [cfg.vocab_rows, cfg.hidden_size], "float32",
            attr=_attr(cfg, "lm_head.weight"))
        logits = layers.matmul(flat, head, transpose_y=True)
        loss = layers.reduce_mean(layers.softmax_with_cross_entropy(
            logits, layers.reshape(labels, [batch_size * seq_len, 1])))
    return main, startup, ["input_ids", "labels"], loss


def min_decays(program: Program) -> list:
    """One [mamba_num_heads] float32 variable a Mamba-2 layer, in layer
    order: each head's smallest per-step decay exp(dt A) over the step's
    tokens. A head near 0 forgets its state inside a chunk; a head near 1
    never forgets."""
    return outputs_of(program, "mamba2", "MinDecay")
