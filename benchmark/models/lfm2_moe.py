"""Model family `lfm2_moe`: next-token pre-training of an LFM2-MoE decoder
(LiquidAI, `model_type` `lfm2_moe`) on one chip's share of its experts and
vocabulary.

One file holds what belongs to the family and to no cell: how the program
is built from a configuration file through the entry points a user calls,
the batch generator, the model-FLOP formula and the plain float32 reference
the program is compared with. `harness.py` finds it by the `family` key of
the configuration file.

The reference is this file's own copy of
`paddle_tpu/models/lfm2_moe_reference.py`: later PRs may edit the original,
and the yardstick has to stay put.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

ATTENTION = "full_attention"
QUERY_BLOCK = 512  # the reference's attention, in blocks of queries


def units_per_step(traffic: dict) -> int:
    """Tokens in one step; packed documents, so every one is real."""
    return int(traffic["batch"]) * int(traffic["seq_len"])


# ---------------------------------------------------------------------------
# the program, through the user's entry points
# ---------------------------------------------------------------------------


def model_config(config: dict):
    """`Lfm2MoeConfig` from the configuration file: the published keys
    under their own names, and the chip's share."""
    from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig

    if not config["program"]["use_flash_attention"]:
        raise ValueError("the family builds the fused attention op only")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "use_expert_bias", "norm_eps", "rope_theta", "conv_L_cache",
            "conv_bias", "max_position_embeddings", "num_dense_layers",
            "experts_held", "first_expert", "vocab_rows",
            "initializer_range")
    return Lfm2MoeConfig(
        **{k: config[k] for k in keys},
        layer_types=list(config["layer_types"]),
        remat_ffn=config["program"]["remat_ffn"],
        expert_bias_update_rate=config["optimizer"]["expert_bias_update_rate"])


def build_forward(config: dict, traffic: dict, batch: int, dropout: bool,
                  main, startup):
    """Forward graph into `main`/`startup`; returns (loss, feed names). The
    model has no dropout, so the check program is the cell's own at the
    check's batch."""
    from paddle_tpu.models.lfm2_moe import build_lfm2_moe_pretrain_program

    _, _, feed_names, loss = build_lfm2_moe_pretrain_program(
        model_config(config), batch, int(traffic["seq_len"]),
        main_program=main, startup_program=startup)
    return loss, feed_names


def optimizer(config: dict, batch: int):
    import paddle_tpu.fluid as fluid

    return fluid.optimizer.AdamOptimizer(
        learning_rate=config["optimizer"]["learning_rate"])


def forward_flops_per_token(config: dict, seq_len: int) -> Dict[str, float]:
    """Model FLOPs a token of one forward pass of what this chip computes,
    by part, 2 FLOPs a multiply-add: the causal triangle of attention and
    not the square, the experts at the expected share of the picks
    (experts per token x held / router width), the head over the held rows
    of the vocabulary. Norms, rotations, gates and the convolution's three
    taps are vector work and not counted."""
    h = config["hidden_size"]
    kinds = config["layer_types"]
    n_attn = sum(k == ATTENTION for k in kinds)
    n_conv = len(kinds) - n_attn
    n_dense = config["num_dense_layers"]
    n_moe = len(kinds) - n_dense
    kv = h // config["num_attention_heads"] * config["num_key_value_heads"]
    share = (config["num_experts_per_tok"] * config["experts_held"]
             / config["num_experts"])
    return {
        "short_conv": n_conv * (2.0 * h * 3 * h + 2.0 * h * h),
        "attention": n_attn * (2.0 * h * (2 * h + 2 * kv)  # q, o, k, v
                               + 4.0 * h * (seq_len + 1) / 2),  # QK^T, PV
        "dense_mlp": n_dense * 6.0 * h * config["intermediate_size"],
        "experts": n_moe * share * 6.0 * h * config["moe_intermediate_size"],
        "router": n_moe * 2.0 * h * config["num_experts"],
        "head": 2.0 * h * config["vocab_rows"],
    }


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    """Model FLOPs of one step: forward once and backward twice that.
    Recomputation (`remat_ffn`, the flash backward's second Q K^T) is not
    counted."""
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
    """(label, parameter, index): the embedding, the first attention
    layer's W_q, the first conv layer's W_in (the deepest under the loss),
    the first and last expert layers' W1 and the first expert layer's
    router."""
    kinds = config["layer_types"]
    attn = kinds.index(ATTENTION)
    conv = next(i for i, k in enumerate(kinds) if k != ATTENTION)
    first_moe, last_moe = config["num_dense_layers"], len(kinds) - 1
    return [
        ("embedding", "embed_tokens.weight", None),
        ("attention.q_proj", f"layers.{attn}.self_attn.q_proj.weight", None),
        ("conv.in_proj", f"layers.{conv}.conv.in_proj", None),
        ("first_moe.w1", f"layers.{first_moe}.feed_forward.w1", None),
        ("last_moe.w1", f"layers.{last_moe}.feed_forward.w1", None),
        ("first_moe.gate", f"layers.{first_moe}.feed_forward.gate", None),
    ]


def reference_loss(config: dict, params: Dict[str, object], input_ids, labels,
                   held: Optional[Tuple[int, int]], products_in=None):
    """(mean next-token cross-entropy, the picks of every expert layer
    [B, S, k]) in plain `jax.numpy`. Follows the released
    `modeling_lfm2_moe.py`: pre-norm blocks h = x + Op(RMSNorm(x)),
    y = h + FFN(RMSNorm(h)); Op a gated short convolution (in_proj -> B, C,
    x chunks, causal depthwise conv of B * x with L taps, C * conv,
    out_proj; no bias, no activation) or causal grouped-query attention
    with RMSNorm over each head of q and k before rotate-half RoPE; FFN a
    dense SwiGLU MLP in the leading layers and after them sigmoid-scored
    experts, the top-k of score + bias picked, gates the picks' own scores
    over their sum + 1e-6, times the scaling factor.

    Departures, which are the program's and are kept so that the comparison
    sees arithmetic only: the head is the embedding matrix; `held = (first,
    count)` leaves out what experts outside first .. first + count - 1
    would add (they are scored, picked and normalised over all the same);
    the vocabulary is the rows held; RoPE angles from a float64 table;
    float32 throughout where the release autocasts to bf16; packed rows
    with positions 0 .. S-1 and no boundary mask.

    No kernel, no scan, no sort: attention in blocks of QUERY_BLOCK queries
    (each under `jax.checkpoint`, so that S = 4096 at 32 heads keeps one
    block's scores alive), the experts a dense loop over the experts held,
    every token through each, weighed by a gate that is zero where the token
    did not pick it.

    `products_in` rounds both operands of every matrix product to that
    dtype first: how the reference reads in a precision below the
    program's, which the check's limits have to refuse (PERF.md)."""
    import jax
    import jax.numpy as jnp

    def r(a):
        return a if products_in is None else a.astype(products_in).astype(
            jnp.float32)

    def mm(a, b):
        return r(a) @ r(b)

    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    eps = config["norm_eps"]
    top_k = config["num_experts_per_tok"]
    all_picks = []

    def rms(x, w):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w

    def short_conv(z, p):
        bg, cg, u = jnp.split(mm(z, p["in_proj"]), 3, axis=-1)
        bu = bg * u
        taps = p["conv"]  # [L, H]; the last tap weighs the current position
        n_taps, s = taps.shape[0], z.shape[1]
        c = sum(taps[j] * jnp.pad(
            bu, ((0, 0), (n_taps - 1 - j, 0), (0, 0)))[:, :s]
            for j in range(n_taps))
        return mm(cg * c, p["out_proj"])

    def rope(x):
        # x [B, S, heads, d]; pairs (i, i + d/2), angle = t * theta^(-2i/d)
        s, d = x.shape[1], x.shape[-1]
        freq = float(config["rope_theta"]) ** (
            -np.arange(0, d, 2, dtype=np.float64) / d)
        angle = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
        cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
        sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(z, p):
        b, s, h = z.shape
        d = h // nh
        q = mm(z, p["q_proj.weight"]).reshape(b, s, nh, d)
        k = mm(z, p["k_proj.weight"]).reshape(b, s, nkv, d)
        v = mm(z, p["v_proj.weight"]).reshape(b, s, nkv, d)
        q = rope(rms(q, p["q_layernorm.weight"]))
        k = rope(rms(k, p["k_layernorm.weight"]))
        q = q.reshape(b, s, nkv, nh // nkv, d)  # KV head j serves a group
        pos = jnp.arange(s)

        @jax.checkpoint
        def block(q_blk, q_pos):
            scores = jnp.einsum("bqjgd,bkjd->bjgqk", r(q_blk), r(k)) / math.sqrt(d)
            scores = jnp.where(q_pos[:, None] >= pos[None, :], scores, -1e30)
            return jnp.einsum("bjgqk,bkjd->bqjgd",
                              r(jax.nn.softmax(scores, axis=-1)), r(v))

        size = min(QUERY_BLOCK, s)
        ctx = jnp.concatenate(
            [block(q[:, i:i + size], pos[i:i + size])
             for i in range(0, s, size)], axis=1)
        return mm(ctx.reshape(b, s, h), p["out_proj.weight"])

    def swiglu(z, w1, w3, w2):
        return mm(jax.nn.silu(mm(z, w1)) * mm(z, w3), w2)

    def moe(z, p):
        n_experts = p["gate"].shape[1]
        first, count = held if held is not None else (0, n_experts)
        s = jax.nn.sigmoid(z @ p["gate"])  # the router stays float32
        _, picks = jax.lax.top_k(s + p["expert_bias"], top_k)
        all_picks.append(picks)
        gates = jnp.take_along_axis(s, picks, axis=-1)
        if config["norm_topk_prob"]:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
        gates = gates * config["routed_scaling_factor"]
        out = jnp.zeros_like(z)
        for e in range(count):  # p["w1"][e] is expert first + e
            weight = jnp.sum(jnp.where(picks == first + e, gates, 0.0), -1)
            out = out + weight[..., None] * jax.checkpoint(swiglu)(
                z, p["w1"][e], p["w3"][e], p["w2"][e])
        return out

    def sub(prefix):
        return {k[len(prefix):]: v for k, v in params.items()
                if k.startswith(prefix)}

    x = params["embed_tokens.weight"][input_ids]
    for i, kind in enumerate(config["layer_types"]):
        name = f"layers.{i}."
        z = rms(x, params[name + "operator_norm.weight"])
        x = x + (attention(z, sub(name + "self_attn.")) if kind == ATTENTION
                 else short_conv(z, sub(name + "conv.")))
        z = rms(x, params[name + "ffn_norm.weight"])
        ffn = sub(name + "feed_forward.")
        x = x + (swiglu(z, ffn["w1"], ffn["w3"], ffn["w2"])
                 if i < config["num_dense_layers"] else moe(z, ffn))
    x = rms(x, params["embedding_norm.weight"])
    logits = mm(x, params["embed_tokens.weight"].T)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return loss, all_picks


def reference_loss_and_grads(config: dict, traffic: dict,
                             params: Dict[str, object],
                             batch: Dict[str, np.ndarray],
                             with_picks: bool = False, products_in=None):
    """Loss and the gradients of `check_parameters`, in float32 with
    `jax.default_matmul_precision("highest")`, one compile. `with_picks`
    also returns every expert layer's picks, for whoever counts how many
    fall differently in the bf16 program."""
    import jax
    import jax.numpy as jnp

    names = sorted({p for _, p, _ in check_parameters(config)})
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    rest = {k: v for k, v in params.items() if k not in names}
    held = (int(config["first_expert"]), int(config["experts_held"]))

    def loss_of(wrt, rest, batch):
        return reference_loss(config, {**rest, **wrt}, batch["input_ids"],
                              batch["labels"], held, products_in)

    # everything that is an array goes in as an argument: a closed-over
    # parameter would be a 2 GB constant for XLA to fold
    with jax.default_matmul_precision("highest"):
        (loss, picks), grads = jax.jit(
            jax.value_and_grad(loss_of, has_aux=True))(
                {k: params[k] for k in names}, rest,
                {k: batch[k] for k in ("input_ids", "labels")})
    return (loss, grads, picks) if with_picks else (loss, grads)
