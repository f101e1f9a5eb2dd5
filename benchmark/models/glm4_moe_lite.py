"""Model family `glm4_moe_lite`: next-token pre-training of a GLM-4.7-Flash
decoder (zai-org, `model_type` `glm4_moe_lite`) with its
multi-token-prediction module, on one chip's share of its routed experts
and vocabulary.

One file holds what belongs to the family and to no cell: how the program
is built from a configuration file through the entry points a user calls,
the batch generator, the model-FLOP formula and the plain float32 reference
the program is compared with. `harness.py` finds it by the `family` key of
the configuration file.

The reference stands here and nowhere else: plain functions in
`jax.numpy` (`mla`, `routed_experts`, `shared_expert`, `mtp_module`,
`reference_loss`) that share no code with `paddle_tpu/ops`; the tests
import them from this file.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import numpy as np

QUERY_BLOCK = 512  # the reference's attention, in blocks of queries
# what the reference can be made to get wrong, to show that the check's
# limits refuse it (PERF.md): each is one of the model's terms
FAULTS = (
    "no_mtp_loss",         # lambda = 0
    "mtp_detached",        # h_i cut from the trunk in front of the module
    "mtp_own_table",       # the module embeds from a table of its own:
                           # E's second gradient is missing
    "labels_shift_one",    # labels_next = labels
    "no_rotary",
    "scale_nope_only",     # softmax scale 192^(-1/2), the unrotated part's
    "gates_unscaled",      # gates not multiplied by routed_scaling_factor
    "no_shared_expert",
)


def units_per_step(traffic: dict) -> int:
    """Tokens in one step; packed documents, so every one is real."""
    return int(traffic["batch"]) * int(traffic["seq_len"])


# ---------------------------------------------------------------------------
# the program, through the user's entry points
# ---------------------------------------------------------------------------

PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
    "rms_norm_eps", "rope_theta", "rope_scaling", "max_position_embeddings",
    "num_nextn_predict_layers")
SHARE_KEYS = ("experts_held", "first_expert", "vocab_rows",
              "initializer_range")
# what the program has one way of doing: any other value is another model
FIXED = {"attention_bias": False, "hidden_act": "silu", "n_group": 1,
         "topk_group": 1, "topk_method": "noaux_tc",
         "tie_word_embeddings": False, "partial_rotary_factor": 1}


def model_config(config: dict):
    """`Glm4MoeLiteConfig` from the configuration file: the published keys
    under their own names, and the chip's share."""
    from paddle_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig

    if not config["program"]["use_flash_attention"]:
        raise ValueError("the family builds the fused attention op only")
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r} is not built")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has one key head a query head")
    return Glm4MoeLiteConfig(
        **{k: config[k] for k in PUBLISHED_KEYS + SHARE_KEYS},
        mtp_loss_weight=config["mtp_loss_weight"],
        remat_ffn=config["program"]["remat_ffn"],
        expert_bias_update_rate=config["optimizer"]["expert_bias_update_rate"])


def build_forward(config: dict, traffic: dict, batch: int, dropout: bool,
                  main, startup):
    """Forward graph into `main`/`startup`; returns (loss, feed names). The
    model has no dropout, so the check program is the cell's own at the
    check's batch."""
    from paddle_tpu.models.glm4_moe_lite import (
        build_glm4_moe_lite_pretrain_program)

    _, _, feed_names, loss = build_glm4_moe_lite_pretrain_program(
        model_config(config), batch, int(traffic["seq_len"]),
        main_program=main, startup_program=startup)
    return loss, feed_names


def part_losses(main) -> dict:
    """`main_loss` and `mtp_loss` of a program `build_forward` made,
    fetchable beside its loss (a check, never the timed window)."""
    from paddle_tpu.models import glm4_moe_lite

    return glm4_moe_lite.part_losses(main)


def optimizer(config: dict, batch: int):
    import paddle_tpu.fluid as fluid

    return fluid.optimizer.AdamOptimizer(
        learning_rate=config["optimizer"]["learning_rate"])


def forward_flops_per_token(config: dict, seq_len: int) -> Dict[str, float]:
    """Model FLOPs a token of one forward pass of what this chip computes,
    by part, 2 FLOPs a multiply-add: every attention block's projections
    and the causal triangle of its scores (nope + rope wide) and values
    (the trunk's layers and the module's one), the dense MLP, the routed
    experts at the expected share of the picks (experts per token x held /
    router width) and the shared expert and the router of every expert
    layer (the module's among them), the module's W_eh, and the head over
    the held rows of the vocabulary once for the trunk and once for the
    module. Norms, rotations and gates are vector work and not counted."""
    c = config["hidden_size"]
    n_mtp = config["num_nextn_predict_layers"]
    n_dense = config["first_k_dense_replace"]
    blocks = config["num_hidden_layers"] + n_mtp
    n_moe = blocks - n_dense
    nh = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    share = (config["num_experts_per_tok"] * config["experts_held"]
             / config["n_routed_experts"])
    f = config["moe_intermediate_size"]
    return {
        "mla_projections": blocks * 2.0 * (
            c * config["q_lora_rank"] + config["q_lora_rank"] * nh * qk
            + c * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * nh * (config["qk_nope_head_dim"] + dv)
            + nh * dv * c),
        "mla_scores": blocks * 2.0 * nh * (qk + dv) * (seq_len + 1) / 2,
        "dense_mlp": n_dense * 6.0 * c * config["intermediate_size"],
        "routed_experts": n_moe * share * 6.0 * c * f,
        "shared_expert": n_moe * 6.0 * c * f * config["n_shared_experts"],
        "router": n_moe * 2.0 * c * config["n_routed_experts"],
        "mtp_eh_proj": n_mtp * 2.0 * 2 * c * c,
        "heads": (1 + n_mtp) * 2.0 * c * config["vocab_rows"],
    }


def mtp_flops_share(config: dict, seq_len: int) -> float:
    """The module's share of a step's model FLOPs: its block, W_eh and its
    head over everything."""
    whole = forward_flops_per_token(config, seq_len)
    without = forward_flops_per_token(
        dict(config, num_nextn_predict_layers=0), seq_len)
    return 1.0 - sum(without.values()) / sum(whole.values())


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    """Model FLOPs of one step: forward once and backward twice that, the
    trunk, the module and both heads once each. Recomputation
    (`remat_ffn`, the flash backward's second Q K^T) is not counted."""
    seq = int(traffic["seq_len"])
    return 3.0 * sum(forward_flops_per_token(config, seq).values()) * batch * seq


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def make_batch(config: dict, traffic: dict, batch: int,
               rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """Packed batch with both labels: S + 2 token ids a row, uniform over
    the vocabulary rows held; `input_ids` the first S, `labels` the S from
    the second on, `labels_next` the S from the third on. Every position is
    real, predicts its successor and the token after it."""
    s = int(traffic["seq_len"])
    ids = rng.integers(0, config["vocab_rows"], (batch, s + 2)).astype(np.int32)
    return {"input_ids": np.ascontiguousarray(ids[:, :s]),
            "labels": np.ascontiguousarray(ids[:, 1:s + 1]),
            "labels_next": np.ascontiguousarray(ids[:, 2:])}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def check_parameters(config: dict) -> List[Tuple[str, str, object]]:
    """(label, parameter, index): the two parameters with two uses (E and
    W_head), the module's own W_eh and W_kvb (which only L_mtp reaches),
    the first layer's W_kvb (the deepest attention under both losses), and
    of the first expert layer the routed experts' W1, the shared expert's
    W1 and the router."""
    moe = config["first_k_dense_replace"]
    return [
        ("embedding", "embed_tokens.weight", None),
        ("lm_head", "lm_head.weight", None),
        ("mtp.eh_proj", "mtp.eh_proj.weight", None),
        ("mtp.kv_b_proj", "mtp.self_attn.kv_b_proj", None),
        ("first.kv_b_proj", "layers.0.self_attn.kv_b_proj", None),
        ("first_moe.w1", f"layers.{moe}.mlp.w1", None),
        ("first_moe.shared_w1", f"layers.{moe}.mlp.shared_experts.w1", None),
        ("first_moe.gate", f"layers.{moe}.mlp.gate", None),
    ]


def _rounder(products_in):
    """mm(a, b) with both operands rounded to `products_in` first: how the
    reference reads in a precision below the program's."""
    import jax.numpy as jnp

    def r(a):
        return a if products_in is None else a.astype(products_in).astype(
            jnp.float32)

    return r


def rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rope_table(config: dict, seq_len: int):
    """cos, sin [S, rot/2] of position x theta^(-2i/rot), float64
    arithmetic."""
    rot, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    i = np.arange(rot // 2, dtype=np.float64)
    angle = (np.arange(seq_len, dtype=np.float64)[:, None]
             * theta ** (-2 * i / rot)[None, :])
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def mla(config: dict, z, p, products_in=None, faults=()):
    """DeepSeek-V2/V3's latent attention on all heads: c_q = RMS(z W_qa),
    [q_nope, q_rope] = c_q W_qb a head, [c_kv, k_rope] = z W_kva, [k_nope,
    v] = RMS(c_kv) W_kvb a head, the rope parts rotated (rotate-half, pairs
    (i, i + rot/2)) at theta^(-2i/rot), the one k_rope for every head,
    causal softmax of q . k (nope + rope)^(-1/2), W_o. Attention in blocks
    of QUERY_BLOCK queries, each under its own `jax.checkpoint`, one rolled
    loop over the blocks."""
    import jax
    import jax.numpy as jnp

    r = _rounder(products_in)
    nope, rot, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    lora, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    scale = (nope if "scale_nope_only" in faults else nope + rot) ** -0.5
    b, s, _ = z.shape
    nh = p["o_proj"].shape[0] // dv
    cos, sin = rope_table(config, s)

    def rotate(x):  # [B, S, heads, rot]
        if "no_rotary" in faults:
            return x
        x1, x2 = x[..., : rot // 2], x[..., rot // 2:]
        c, sn = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)

    c_q = rms(r(z) @ r(p["q_a_proj"]), p["q_a_layernorm"], eps)
    q = (r(c_q) @ r(p["q_b_proj"])).reshape(b, s, nh, nope + rot)
    kv_a = r(z) @ r(p["kv_a_proj"])
    c_kv = rms(kv_a[..., :lora], p["kv_a_layernorm"], eps)
    k_rope = rotate(kv_a[..., lora:][:, :, None, :])
    kv = (r(c_kv) @ r(p["kv_b_proj"])).reshape(b, s, nh, nope + dv)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, nh, rot))], -1)
    v = kv[..., nope:]
    pos = jnp.arange(s)

    @jax.checkpoint
    def block(q_blk, q_pos):
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(q_blk), r(k)) * scale
        scores = jnp.where(q_pos[:, None] >= pos[None, :], scores, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          r(jax.nn.softmax(scores, axis=-1)), r(v))

    size = min(QUERY_BLOCK, s)
    ctx = jax.lax.map(
        lambda blk: block(*blk),
        (jnp.moveaxis(q.reshape(b, s // size, size, nh, nope + rot), 1, 0),
         pos.reshape(s // size, size)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, nh * dv)
    return r(ctx) @ r(p["o_proj"])


def swiglu(z, w1, w3, w2, products_in=None):
    import jax

    r = _rounder(products_in)
    return r(jax.nn.silu(r(z) @ r(w1)) * (r(z) @ r(w3))) @ r(w2)


def routed_experts(config: dict, z, p, experts: Optional[Tuple[int, int]],
                   products_in=None, faults=()):
    """s = sigmoid(W_g z) in float32, the top-k of s + b picked, gates the
    picks' own scores over their sum + 1e-6 times routed_scaling_factor,
    and the sum over the picks of the experts `experts = (first, count)`
    holds (scored, picked and normalised over the whole router all the
    same; None: all of them): a dense loop over the held experts, every
    token through each, weighed by a gate that is zero where the token did
    not pick it."""
    import jax
    import jax.numpy as jnp

    first, count = experts if experts is not None else (0, p["gate"].shape[1])
    s = jax.nn.sigmoid(z @ p["gate"])
    _, picks = jax.lax.top_k(s + p["expert_bias"],
                             config["num_experts_per_tok"])
    gates = jnp.take_along_axis(s, picks, axis=-1)
    if config["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    if "gates_unscaled" not in faults:
        gates = gates * config["routed_scaling_factor"]

    def add_expert(out, held):  # held: expert first + e and its weights
        e, w1, w3, w2 = held
        weight = jnp.sum(jnp.where(picks == e, gates, 0.0), -1)
        return out + weight[..., None] * jax.checkpoint(
            functools.partial(swiglu, products_in=products_in))(
                z, w1, w3, w2), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(z), (
        first + jnp.arange(count), p["w1"], p["w3"], p["w2"]))
    return out


def shared_expert(z, p, products_in=None):
    return swiglu(z, p["shared_experts.w1"], p["shared_experts.w3"],
                  p["shared_experts.w2"], products_in)


def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def block(config: dict, x, lp, dense: bool, experts, products_in=None,
          faults=()):
    """h += MLA(RMS(h)); h += FFN(RMS(h)) from the layer's own parameters
    (its prefix taken off), each sublayer under `jax.checkpoint`."""
    import jax

    eps = config["rms_norm_eps"]
    ffn = _sub(lp, "mlp.")

    @jax.checkpoint
    def attend(x):
        return x + mla(config, rms(x, lp["input_layernorm.weight"], eps),
                       _sub(lp, "self_attn."), products_in, faults)

    @jax.checkpoint
    def feed(x):
        z = rms(x, lp["post_attention_layernorm.weight"], eps)
        if dense:
            return x + swiglu(z, ffn["w1"], ffn["w3"], ffn["w2"], products_in)
        y = routed_experts(config, z, ffn, experts, products_in, faults)
        if "no_shared_expert" not in faults:
            y = y + shared_expert(z, ffn, products_in)
        return x + y

    return feed(attend(x))


def mtp_combine(config: dict, params, hidden, next_ids, products_in=None,
                table=None):
    """u = W_eh [RMS_e(E[t_{i+1}]) ; RMS_h(h)]: the embedding's half of
    W_eh's rows first. `table` stands in for E where a fault asks."""
    import jax.numpy as jnp

    r = _rounder(products_in)
    eps = config["rms_norm_eps"]
    table = params["embed_tokens.weight"] if table is None else table
    e = rms(table[next_ids], params["mtp.enorm.weight"], eps)
    h = rms(hidden, params["mtp.hnorm.weight"], eps)
    return r(jnp.concatenate([e, h], -1)) @ r(params["mtp.eh_proj.weight"])


def cross_entropy(config: dict, params, x, norm, labels, products_in=None):
    """mean CE(W_head RMS(x), labels) over the rows held."""
    import jax
    import jax.numpy as jnp

    r = _rounder(products_in)
    x = rms(x, params[norm], config["rms_norm_eps"])
    logits = r(x) @ r(params["lm_head.weight"].T)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def mtp_module(config: dict, params, hidden, next_ids, labels_next, experts,
               products_in=None, faults=()):
    """L_mtp: the combine, one expert layer from the parameters `mtp.*`,
    RMS_m and the trunk's own head against t_{i+2}."""
    import jax

    if "mtp_detached" in faults:
        hidden = jax.lax.stop_gradient(hidden)
    table = (jax.lax.stop_gradient(params["embed_tokens.weight"])
             if "mtp_own_table" in faults else None)
    u = mtp_combine(config, params, hidden, next_ids, products_in, table)
    x = block(config, u, _sub(params, "mtp."), False, experts, products_in,
              faults)
    return cross_entropy(config, params, x, "mtp.shared_head.norm.weight",
                         labels_next, products_in)


def reference_loss(config: dict, params: Dict[str, object], batch: dict,
                   experts: Optional[Tuple[int, int]], products_in=None,
                   faults=()):
    """(L, L_main, L_mtp) in plain `jax.numpy`, float32:

    trunk h = E[t_i], then for each layer h += MLA(RMS(h)), h +=
    FFN(RMS(h)) with FFN the dense SwiGLU in the leading layers and after
    them the routed experts' sum plus the shared expert; L_main = mean
    CE(W_head RMS_f(h), t_{i+1}); the module u = W_eh [RMS_e(E[t_{i+1}]) ;
    RMS_h(h)] on the trunk's last hidden state in front of RMS_f, one more
    expert layer, L_mtp = mean CE(W_head RMS_m(h'), t_{i+2}); L = L_main +
    mtp_loss_weight L_mtp.

    Departures, the program's too: `experts = (first, count)` leaves out
    what routed experts outside first .. first + count - 1 would add; the
    vocabulary is the rows held; float32 throughout; packed rows with
    positions 0 .. S-1 and no boundary mask; what `assumed` of the
    configuration file lists.

    `products_in` rounds both operands of every matrix product to that
    dtype first (the router's stays float32, as the program's); `faults`
    gets one of the model's terms wrong (`FAULTS`). The check's limits
    have to refuse each (PERF.md)."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    x = params["embed_tokens.weight"][batch["input_ids"]]
    for i in range(config["num_hidden_layers"]):
        x = block(config, x, _sub(params, f"layers.{i}."),
                  i < config["first_k_dense_replace"], experts, products_in,
                  faults)
    main = cross_entropy(config, params, x, "norm.weight", batch["labels"],
                         products_in)
    if not config["num_nextn_predict_layers"]:
        return main, main, 0.0 * main
    labels_next = batch["labels" if "labels_shift_one" in faults
                        else "labels_next"]
    mtp = mtp_module(config, params, x, batch["labels"], labels_next,
                     experts, products_in, faults)
    weight = 0.0 if "no_mtp_loss" in faults else config["mtp_loss_weight"]
    return main + weight * mtp, main, mtp


def reference_loss_and_grads(config: dict, traffic: dict,
                             params: Dict[str, object],
                             batch: Dict[str, np.ndarray], products_in=None,
                             faults=(), parts=False):
    """Loss and the gradients of `check_parameters`' parameters (whole; the
    harness takes the named index), in float32 with
    `jax.default_matmul_precision("highest")`, one compile. With `parts`
    the loss is (L, L_main, L_mtp)."""
    import jax
    import jax.numpy as jnp

    names = sorted({p for _, p, _ in check_parameters(config)})
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    rest = {k: v for k, v in params.items() if k not in names}
    experts = (int(config["first_expert"]), int(config["experts_held"]))

    def loss_of(wrt, rest, batch):
        loss, main, mtp = reference_loss(
            config, {**rest, **wrt}, batch, experts, products_in, faults)
        return loss, (loss, main, mtp)

    # everything that is an array goes in as an argument: a closed-over
    # parameter would be a constant of gigabytes for XLA to fold
    with jax.default_matmul_precision("highest"):
        (loss, losses), grads = jax.jit(
            jax.value_and_grad(loss_of, has_aux=True))(
                {k: params[k] for k in names}, rest,
                {k: jnp.asarray(v) for k, v in batch.items()})
    return (losses if parts else loss), grads
