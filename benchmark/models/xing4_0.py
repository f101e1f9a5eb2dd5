"""Model family `xing4_0`: next-token pre-training of a Xing4.0 decoder
(XingChen-AGI, `model_type` `xing4_0`) on one chip's share of its attention
heads, routed experts and vocabulary.

One file holds what belongs to the family and to no cell: how the program
is built from a configuration file through the entry points a user calls,
the batch generator, the model-FLOP formula and the plain float32 reference
the program is compared with. `harness.py` finds it by the `family` key of
the configuration file.

The reference is this file's own copy of
`paddle_tpu/models/xing4_reference.py`: later PRs may edit the original,
and the yardstick has to stay put.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

QUERY_BLOCK = 512  # the reference's attention, in blocks of queries
# what the reference can be made to leave out, to show that the check's
# limits refuse it (PERF.md): each is one of the model's terms
FAULTS = ("sinkhorn_one_round", "no_shared_expert", "no_rotary",
          "unscaled_softmax")


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
    "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
    "mhc_h_res_clamp_max", "rms_norm_eps", "rope_theta",
    "max_position_embeddings", "num_nextn_predict_layers")
SHARE_KEYS = ("heads_held", "first_head", "experts_held", "first_expert",
              "vocab_rows", "initializer_range")
# what the program has one way of doing: any other value is another model
FIXED = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
         "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "tie_word_embeddings": False, "ep_size": 1}


def model_config(config: dict):
    """`Xing4Config` from the configuration file: the published keys under
    their own names, and the chip's share."""
    from paddle_tpu.models.xing4 import Xing4Config

    if not config["program"]["use_flash_attention"]:
        raise ValueError("the family builds the fused attention op only")
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r} is not built")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has one key head a query head")
    return Xing4Config(
        **{k: config[k] for k in PUBLISHED_KEYS + SHARE_KEYS},
        rope_scaling=dict(config["rope_scaling"]),
        hc_alpha_init=config["hyper_connections"]["alpha_init"],
        hc_bias_std=config["hyper_connections"]["bias_std"],
        remat_ffn=config["program"]["remat_ffn"],
        expert_bias_update_rate=config["optimizer"]["expert_bias_update_rate"])


def build_forward(config: dict, traffic: dict, batch: int, dropout: bool,
                  main, startup):
    """Forward graph into `main`/`startup`; returns (loss, feed names). The
    model has no dropout, so the check program is the cell's own at the
    check's batch."""
    from paddle_tpu.models.xing4 import build_xing4_pretrain_program

    _, _, feed_names, loss = build_xing4_pretrain_program(
        model_config(config), batch, int(traffic["seq_len"]),
        main_program=main, startup_program=startup)
    return loss, feed_names


def optimizer(config: dict, batch: int):
    import paddle_tpu.fluid as fluid

    return fluid.optimizer.AdamOptimizer(
        learning_rate=config["optimizer"]["learning_rate"])


def forward_flops_per_token(config: dict, seq_len: int) -> Dict[str, float]:
    """Model FLOPs a token of one forward pass of what this chip computes,
    by part, 2 FLOPs a multiply-add: the held heads' projections and the
    causal triangle of their scores (192-wide) and values (128-wide), the
    routed experts at the expected share of the picks (experts per token x
    held / router width), the shared expert for every token, the head over
    the held rows of the vocabulary, and the hyper-connections' one matrix
    product (xbar phi). Norms, rotations, gates, Sinkhorn and the mixing
    of the streams are vector work and not counted."""
    c = config["hidden_size"]
    layers = config["num_hidden_layers"]
    n_dense = config["first_k_dense_replace"]
    n_moe = layers - n_dense
    nh = config["heads_held"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    n = config["hc_mult"]
    share = (config["num_experts_per_tok"] * config["experts_held"]
             / config["n_routed_experts"])
    f = config["moe_intermediate_size"]
    return {
        "mla_projections": layers * 2.0 * (
            c * config["q_lora_rank"] + config["q_lora_rank"] * nh * qk
            + c * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * nh * (config["qk_nope_head_dim"] + dv)
            + nh * dv * c),
        "mla_scores": layers * 2.0 * nh * (qk + dv) * (seq_len + 1) / 2,
        "dense_mlp": n_dense * 6.0 * c * config["intermediate_size"],
        "routed_experts": n_moe * share * 6.0 * c * f,
        "shared_expert": n_moe * 6.0 * c * f * config["n_shared_experts"],
        "router": n_moe * 2.0 * c * config["n_routed_experts"],
        "mhc_map": 2 * layers * 2.0 * n * c * (2 * n + n * n),
        "head": 2.0 * c * config["vocab_rows"],
    }


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    """Model FLOPs of one step: forward once and backward twice that.
    Recomputation (`remat_ffn`, the flash backward's second Q K^T, the
    mappings run again in the backward pass) is not counted."""
    seq = int(traffic["seq_len"])
    return 3.0 * sum(forward_flops_per_token(config, seq).values()) * batch * seq


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def make_batch(config: dict, traffic: dict, batch: int,
               rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """Packed next-token batch: S + 1 token ids a row, uniform over the
    vocabulary rows held; `input_ids` the first S, `labels` the last S.
    Every position is real and predicts its successor."""
    s = int(traffic["seq_len"])
    ids = rng.integers(0, config["vocab_rows"], (batch, s + 1)).astype(np.int32)
    return {"input_ids": np.ascontiguousarray(ids[:, :-1]),
            "labels": np.ascontiguousarray(ids[:, 1:])}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def check_parameters(config: dict) -> List[Tuple[str, str, object]]:
    """(label, parameter, index): the embedding (the deepest under the
    loss), the first layer's W_kvb and the phi_res of its feed-forward
    sublayer (the columns of `phi` behind the 2n of H_pre and H_post; in
    front of the very first sublayer the streams are still copies of one
    another, H_res X = X whatever H_res is and that phi_res has no
    gradient), and of the first expert layer the shared expert's W1, the
    routed experts' W1 and the router."""
    n = config["hc_mult"]
    moe = config["first_k_dense_replace"]
    return [
        ("embedding", "embed_tokens.weight", None),
        ("first.phi_res", "layers.0.ffn_hc.phi",
         (slice(None), slice(2 * n, None))),
        ("first.kv_b_proj", "layers.0.self_attn.kv_b_proj", None),
        ("first_moe.shared_w1", f"layers.{moe}.mlp.shared_experts.w1", None),
        ("first_moe.w1", f"layers.{moe}.mlp.w1", None),
        ("first_moe.gate", f"layers.{moe}.mlp.gate", None),
    ]


def yarn_table(config: dict, seq_len: int):
    """cos, sin [S, rot/2] of position x YaRN's blended frequency, float64
    arithmetic: f_i = (1 - m_i) theta^(-2i/d) / factor + m_i theta^(-2i/d),
    m_i = 1 - clamp((i - low) / (high - low), 0, 1), low / high the floor /
    ceiling of d ln(original / (2 pi beta)) / (2 ln theta) at beta_fast /
    beta_slow."""
    d, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    y = config["rope_scaling"]

    def dim_of(turns):
        return d * math.log(y["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    m = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    plain = theta ** (-2 * i / d)
    f = (1.0 - m) * plain / y["factor"] + m * plain
    angle = np.arange(seq_len, dtype=np.float64)[:, None] * f[None, :]
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def softmax_scale(config: dict) -> float:
    y = config["rope_scaling"]
    m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    return (config["qk_nope_head_dim"]
            + config["qk_rope_head_dim"]) ** -0.5 * m * m


def reference_loss(config: dict, params: Dict[str, object], input_ids, labels,
                   experts: Optional[Tuple[int, int]], products_in=None,
                   faults=()):
    """Mean next-token cross-entropy in plain `jax.numpy`.

    Residual path ("mHC: Manifold-Constrained Hyper-Connections",
    arXiv:2512.24880; n = hc_mult streams X [n, C] a token): xbar = vec(X) /
    sqrt(mean(vec(X)^2) + hc_eps); H_pre = sigmoid(a_pre xbar phi_pre +
    b_pre), H_post = 2 sigmoid(..), H_res = Sinkhorn(exp(clamp(a_res
    mat(xbar phi_res) + b_res))) with hc_sinkhorn_iters rounds of rows over
    their sums, then columns over theirs; X' = H_res X + H_post^T
    F(RMSNorm(H_pre X)) for F the attention and then the feed-forward of a
    layer. The streams start as n copies of the embedding and are summed
    before the final norm and the untied head.

    Attention (DeepSeek-V2/V3's MLA): c_q = RMSNorm(x W_qa), [q_nope, q_rope]
    = c_q W_qb a head, [c_kv, k_rope] = x W_kva, [k_nope, v] = RMSNorm(c_kv)
    W_kvb a head, the 64-wide parts rotated (rotate-half) at YaRN's
    frequencies, the one k_rope for every head, causal softmax of q . k
    times (nope + rope)^(-1/2) (0.1 mscale_all_dim ln factor + 1)^2, W_o.

    Feed-forward: SwiGLU at intermediate_size in the leading layers; after
    them s = sigmoid(W_g z), the top-k of s + b picked, gates the picks'
    own scores over their sum + 1e-6 times routed_scaling_factor, plus the
    shared expert for every token.

    Departures, the program's too: the parameters hold the held heads'
    columns and rows, so the attention output is their part of the sum over
    heads; `experts = (first, count)` leaves out what routed experts
    outside first .. first + count - 1 would add (they are scored, picked
    and normalised over all the same); the vocabulary is the rows held;
    float32 throughout; packed rows with positions 0 .. S-1 and no boundary
    mask; what `assumed` of the configuration file lists.

    No kernel, no sort, no padding: every sublayer under `jax.checkpoint`,
    attention in blocks of QUERY_BLOCK queries (each under its own, one
    rolled loop over the blocks), the experts a dense loop (`jax.lax.scan`)
    over the experts held, every token through each, weighed by a gate that
    is zero where the token did not pick it.

    `products_in` rounds both operands of every matrix product to that
    dtype first: how the reference reads in a precision below the
    program's. `faults` leaves out terms (`FAULTS`). The check's limits
    have to refuse each (PERF.md)."""
    import jax
    import jax.numpy as jnp

    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")

    def r(a):
        return a if products_in is None else a.astype(products_in).astype(
            jnp.float32)

    def mm(a, b):
        return r(a) @ r(b)

    n, eps = config["hc_mult"], config["rms_norm_eps"]
    nope, rot, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    lora = config["kv_lora_rank"]
    rounds = (1 if "sinkhorn_one_round" in faults
              else config["hc_sinkhorn_iters"])
    scale = ((nope + rot) ** -0.5 if "unscaled_softmax" in faults
             else softmax_scale(config))

    def rms(x, w):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def hyper(streams, p, norm, fn):
        # a sublayer keeps its input streams and nothing else for the
        # backward pass, so that S 4096 fits beside the check program
        b, s = streams.shape[:2]
        vec = streams.reshape(b, s, -1)
        xbar = vec * jax.lax.rsqrt(
            jnp.mean(jnp.square(vec), axis=-1, keepdims=True)
            + config["hc_eps"])
        t = xbar @ p["phi"]  # the mappings stay float32, as the routing
        a, bias = p["alpha"], p["b"]
        h_pre = jax.nn.sigmoid(a[0] * t[..., :n] + bias[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * t[..., n:2 * n] + bias[n:2 * n])
        m = jnp.exp(jnp.clip(
            (a[2] * t[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n),
            config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]))
        for _ in range(rounds):
            m = m / jnp.sum(m, axis=-1, keepdims=True)
            m = m / jnp.sum(m, axis=-2, keepdims=True)
        u = jnp.einsum("bsn,bsnc->bsc", h_pre, streams)
        y = fn(rms(u, norm))
        return (jnp.einsum("bsij,bsjc->bsic", m, streams)
                + h_post[..., None] * y[:, :, None, :])

    def mla(z, p):
        b, s, _ = z.shape
        nh = p["o_proj"].shape[0] // dv
        cos, sin = yarn_table(config, s)

        def rotate(x):  # [B, S, heads, rot]; pairs (i, i + rot/2)
            if "no_rotary" in faults:
                return x
            x1, x2 = x[..., : rot // 2], x[..., rot // 2:]
            c, sn = cos[None, :, None, :], sin[None, :, None, :]
            return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)

        c_q = rms(mm(z, p["q_a_proj"]), p["q_a_layernorm"])
        q = mm(c_q, p["q_b_proj"]).reshape(b, s, nh, nope + rot)
        kv_a = mm(z, p["kv_a_proj"])
        c_kv = rms(kv_a[..., :lora], p["kv_a_layernorm"])
        k_rope = rotate(kv_a[..., lora:][:, :, None, :])
        kv = mm(c_kv, p["kv_b_proj"]).reshape(b, s, nh, nope + dv)
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

        # one rolled loop over the blocks: the body is compiled once
        size = min(QUERY_BLOCK, s)
        ctx = jax.lax.map(
            lambda blk: block(*blk),
            (jnp.moveaxis(q.reshape(b, s // size, size, nh, nope + rot), 1, 0),
             pos.reshape(s // size, size)))
        ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, nh * dv)
        return mm(ctx, p["o_proj"])

    def swiglu(z, w1, w3, w2):
        return mm(jax.nn.silu(mm(z, w1)) * mm(z, w3), w2)

    def moe(z, p):
        n_experts = p["gate"].shape[1]
        first, count = experts if experts is not None else (0, n_experts)
        s = jax.nn.sigmoid(z @ p["gate"])  # the router stays float32
        _, picks = jax.lax.top_k(s + p["expert_bias"],
                                 config["num_experts_per_tok"])
        gates = jnp.take_along_axis(s, picks, axis=-1)
        if config["norm_topk_prob"]:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
        gates = gates * config["routed_scaling_factor"]
        def add_expert(out, held):  # held: expert first + e and its weights
            e, w1, w3, w2 = held
            weight = jnp.sum(jnp.where(picks == e, gates, 0.0), -1)
            return out + weight[..., None] * jax.checkpoint(swiglu)(
                z, w1, w3, w2), None

        out, _ = jax.lax.scan(add_expert, jnp.zeros_like(z), (
            first + jnp.arange(count), p["w1"], p["w3"], p["w2"]))
        if "no_shared_expert" in faults:
            return out
        return out + swiglu(z, p["shared_experts.w1"], p["shared_experts.w3"],
                            p["shared_experts.w2"])

    def sub(prefix):
        return {k[len(prefix):]: v for k, v in params.items()
                if k.startswith(prefix)}

    def layer(streams, lp, dense):
        """One layer from its own parameters, `layers.<i>.` taken off."""
        def part(prefix):
            return {k[len(prefix):]: v for k, v in lp.items()
                    if k.startswith(prefix)}

        ffn = part("mlp.")
        streams = hyper(streams, part("attn_hc."),
                        lp["input_layernorm.weight"],
                        lambda z: mla(z, part("self_attn.")))
        return hyper(
            streams, part("ffn_hc."), lp["post_attention_layernorm.weight"],
            (lambda z: swiglu(z, ffn["w1"], ffn["w3"], ffn["w2"])) if dense
            else (lambda z: moe(z, ffn)))

    x = params["embed_tokens.weight"][input_ids]
    streams = jnp.broadcast_to(x[:, :, None, :],
                               x.shape[:2] + (n, x.shape[-1]))
    for i in range(config["num_hidden_layers"]):
        streams = layer(streams, sub(f"layers.{i}."),
                        i < config["first_k_dense_replace"])
    x = rms(jnp.sum(streams, axis=2), params["norm.weight"])
    logits = mm(x, params["lm_head.weight"].T)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def reference_loss_and_grads(config: dict, traffic: dict,
                             params: Dict[str, object],
                             batch: Dict[str, np.ndarray], products_in=None,
                             faults=()):
    """Loss and the gradients of `check_parameters`' parameters (whole; the
    harness takes the named index), in float32 with
    `jax.default_matmul_precision("highest")`, one compile."""
    import jax
    import jax.numpy as jnp

    names = sorted({p for _, p, _ in check_parameters(config)})
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    rest = {k: v for k, v in params.items() if k not in names}
    experts = (int(config["first_expert"]), int(config["experts_held"]))

    def loss_of(wrt, rest, batch):
        return reference_loss(config, {**rest, **wrt}, batch["input_ids"],
                              batch["labels"], experts, products_in, faults)

    # everything that is an array goes in as an argument: a closed-over
    # parameter would be a constant of gigabytes for XLA to fold
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss_of))(
            {k: params[k] for k in names}, rest,
            {k: batch[k] for k in ("input_ids", "labels")})
