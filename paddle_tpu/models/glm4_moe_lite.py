"""GLM-4.7-Flash decoder language model (zai-org `glm4_moe_lite`) as a
training `Program`, with its multi-token-prediction module.

A DeepSeek-V3-shaped sparse decoder on a plain residual. With d the hidden
size, E the embedding table, RMS an RMSNorm with a learned weight and
`rms_norm_eps`, t_i the token at position i:

- trunk: h = E[t_i]; for each layer l: h += MLA_l(RMS(h)); h +=
  FFN_l(RMS(h)). MLA is `layers.mla`: q latent `q_lora_rank` and kv latent
  `kv_lora_rank` each behind an RMSNorm, a head's q / k = [`qk_nope_head_dim`
  unrotated | `qk_rope_head_dim` rotated (rotate-half) at `rope_theta`, one
  rotated key part for all heads], v `v_head_dim` wide, causal softmax at
  scale (nope + rope)^(-1/2), W_o over all heads (held whole). FFN_l is a
  SwiGLU MLP at `intermediate_size` in the leading `first_k_dense_replace`
  layers and after them `routed_scaling_factor` x the sum over the
  top-`num_experts_per_tok` of sigmoid(score) + bias (selection by s + b,
  gates s renormalised over the picks) of the **held** experts' SwiGLU at
  `moe_intermediate_size`, plus the shared expert every token passes;
- main loss: L_main = mean_i CE(W_head RMS_f(h_i), t_{i+1});
- the multi-token-prediction module of depth 1 (DeepSeek-V3,
  arXiv:2412.19437, section 2.2; a `glm4_moe` checkpoint carries it as
  layer index `num_hidden_layers` with `enorm`, `hnorm`, `eh_proj`, a
  decoder block and `shared_head`; here its parameters are `mtp.*`):
  u_i = W_eh [RMS_e(E[t_{i+1}]) ; RMS_h(h_i)] with W_eh in R^{2d x d}, the
  **same** table E and h_i the trunk's last hidden state in front of RMS_f;
  h'_i = Block(u_i), one more expert layer exactly as the trunk's (its own
  MLA, router, held experts and shared expert, positions 0 .. S-1);
  L_mtp = mean_i CE(W_head RMS_m(h'_i), t_{i+2}) with the **same** W_head.
  The gradient of L_mtp reaches the trunk through h_i, and E and W_head
  through both of their uses;
- L = L_main + `mtp_loss_weight` L_mtp.

A chip's share of a deployment is part of the configuration:
`experts_held` / `first_expert` say which routed experts' weights this
program holds, `vocab_rows` how many rows of the vocabulary it embeds and
scores; attention heads are held whole. The router keeps its published
width; what absent experts would add is left out.

Scopes in the compiled step (`fluid.name_scope`): `lm_head` around the
trunk's final norm, logits and loss; `mtp` around the whole module and
inside it `mtp_combine` (the two norms, the second look-up in E, W_eh) and
`mtp_head` (RMS_m, the logits, the loss); the module's block lowers under
`mtp` and its ops' own part scopes (`mtp/mla`, `mtp/moe_experts`).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from ..fluid import layers
from ..fluid.framework import Program, name_scope, program_guard
from ..fluid.monitor import record_mtp_module_built
from ..fluid.param_attr import ParamAttr
from .latent_moe import (_attr, attention, feed_forward, outputs_of,
                         tokens_per_expert)  # noqa: F401


@dataclasses.dataclass
class Glm4MoeLiteConfig:
    """The keys of the released `config.json` under their own names, and
    what this program holds of the model."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 202752
    num_nextn_predict_layers: int = 1
    # the share held here: routed experts first_expert .. first_expert +
    # experts_held - 1 of every expert layer (None: all), and the first
    # vocab_rows rows of the vocabulary (None: all)
    experts_held: Optional[int] = None
    first_expert: int = 0
    vocab_rows: Optional[int] = None
    initializer_range: float = 0.02
    # lambda, the weight of the module's loss, which the release does not
    # publish
    mtp_loss_weight: float = 0.3
    remat_ffn: bool = False
    # layers.moe_swiglu's balancing rule for the selection bias
    expert_bias_update_rate: float = 0.0

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                "multi-token prediction is built at depth 1 (or left out)")
        if self.rope_scaling is not None:
            raise ValueError(
                "the plain rotary table is built; a rope_scaling is not")
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if self.vocab_rows is None:
            self.vocab_rows = self.vocab_size

    @property
    def heads_held(self) -> int:
        return self.num_attention_heads

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    inv_freq = None  # the plain table: pair i turns at rope_theta^(-2i/d)

    @staticmethod
    def tiny(**changes) -> "Glm4MoeLiteConfig":
        """For tests: one dense layer, two expert layers and the module at
        toy widths, a head's q / k as wide as its v as published (24 + 8
        against 32)."""
        return Glm4MoeLiteConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
            v_head_dim=32, n_routed_experts=16, num_experts_per_tok=2,
            max_position_embeddings=4096), **changes})


def decoder_layer(cfg: Glm4MoeLiteConfig, x, index: int, name: str,
                  is_test: bool):
    """h += MLA(RMS(h)); h += FFN(RMS(h)); `index` says whether the
    feed-forward is the dense MLP or the experts."""
    z = layers.rms_norm(x, cfg.rms_norm_eps, param_attr=ParamAttr(
        name=f"{name}.input_layernorm.weight"))
    x = layers.elementwise_add(x, attention(cfg, z, f"{name}.self_attn"))
    z = layers.rms_norm(x, cfg.rms_norm_eps, param_attr=ParamAttr(
        name=f"{name}.post_attention_layernorm.weight"))
    return layers.elementwise_add(
        x, feed_forward(cfg, z, index, f"{name}.mlp", is_test))


def _head_loss(cfg: Glm4MoeLiteConfig, x, labels, norm_name: str):
    """mean CE(W_head RMS(x), labels) over the rows held, W_head the one
    parameter `lm_head.weight` whoever asks."""
    tokens = x.shape[0] * x.shape[1]
    x = layers.rms_norm(x, cfg.rms_norm_eps,
                        param_attr=ParamAttr(name=norm_name))
    head = layers.create_parameter(
        [cfg.vocab_rows, cfg.hidden_size], "float32",
        attr=_attr(cfg, "lm_head.weight"))
    logits = layers.matmul(layers.reshape(x, [tokens, cfg.hidden_size]),
                           head, transpose_y=True)
    return layers.reduce_mean(layers.softmax_with_cross_entropy(
        logits, layers.reshape(labels, [tokens, 1])))


def _embed(cfg: Glm4MoeLiteConfig, ids):
    return layers.embedding(
        ids, size=[cfg.vocab_rows, cfg.hidden_size],
        param_attr=_attr(cfg, "embed_tokens.weight"))


def mtp_module(cfg: Glm4MoeLiteConfig, hidden, next_ids, labels_next,
               is_test: bool):
    """L_mtp from the trunk's last hidden state (in front of the final
    norm), the ids of the tokens that follow (t_{i+1}: the trunk's labels)
    and of those after them (t_{i+2})."""
    record_mtp_module_built()
    with name_scope("mtp"):
        with name_scope("mtp_combine"):
            e = layers.rms_norm(
                _embed(cfg, next_ids), cfg.rms_norm_eps,
                param_attr=ParamAttr(name="mtp.enorm.weight"))
            h = layers.rms_norm(
                hidden, cfg.rms_norm_eps,
                param_attr=ParamAttr(name="mtp.hnorm.weight"))
            u = layers.fc(
                layers.concat([e, h], axis=2), cfg.hidden_size,
                num_flatten_dims=2, bias_attr=False,
                param_attr=_attr(cfg, "mtp.eh_proj.weight"))
        # an expert layer whatever the trunk's depth
        x = decoder_layer(cfg, u, cfg.first_k_dense_replace, "mtp", is_test)
        with name_scope("mtp_head"):
            return _head_loss(cfg, x, labels_next,
                              "mtp.shared_head.norm.weight")


def build_glm4_moe_lite_pretrain_program(
    cfg: Glm4MoeLiteConfig,
    batch_size: int,
    seq_len: int,
    is_test: bool = False,
    main_program: Optional[Program] = None,
    startup_program: Optional[Program] = None,
) -> Tuple[Program, Program, List[str], object]:
    """Next-token (and, with the module, next-next-token) pre-training
    graph at static shapes.

    Returns (main_program, startup_program, feed_names, loss_var). Feeds,
    all [B, S] int32 in [0, vocab_rows): `input_ids`; `labels`, the token
    that follows each position (t_{i+1}: the main loss's target and what
    the module embeds); and, with `num_nextn_predict_layers` 1,
    `labels_next`, the token after that (t_{i+2}). Positions are 0 .. S-1
    in every row. The loss is L_main + `mtp_loss_weight` L_mtp;
    `part_losses(main_program)` gives the two parts and
    `tokens_per_expert(main_program)` the expert layers' counters (the
    module's last), all fetchable beside it."""
    if seq_len > cfg.max_position_embeddings:
        raise ValueError(
            f"seq_len {seq_len} over max_position_embeddings "
            f"{cfg.max_position_embeddings}")
    main = main_program or Program()
    startup = startup_program or Program()
    feed_names = ["input_ids", "labels"]
    with program_guard(main, startup):
        input_ids, labels = (
            layers.data(name, shape=[batch_size, seq_len], dtype="int32",
                        append_batch_size=False) for name in feed_names)
        x = _embed(cfg, input_ids)
        for i in range(cfg.num_hidden_layers):
            x = decoder_layer(cfg, x, i, f"layers.{i}", is_test)
        with name_scope("lm_head"):
            loss = _head_loss(cfg, x, labels, "norm.weight")
        if cfg.num_nextn_predict_layers:
            feed_names.append("labels_next")
            labels_next = layers.data(
                "labels_next", shape=[batch_size, seq_len], dtype="int32",
                append_batch_size=False)
            loss = layers.elementwise_add(loss, layers.scale(
                mtp_module(cfg, x, labels, labels_next, is_test),
                cfg.mtp_loss_weight))
    return main, startup, feed_names, loss


def part_losses(program: Program) -> dict:
    """{`main_loss`: L_main, `mtp_loss`: L_mtp (where the module is
    built)}: the variables the program's loss is made of, the only means
    the builder takes."""
    return dict(zip(("main_loss", "mtp_loss"),
                    outputs_of(program, "reduce_mean", "Out")))
