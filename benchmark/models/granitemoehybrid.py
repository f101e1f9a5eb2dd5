"""Model family `granitemoehybrid`: next-token pre-training of a
Granite-4.0-H hybrid decoder (IBM, `model_type` `granitemoehybrid` without
experts: every layer a Mamba-2 or attention mixer and then a dense SwiGLU
MLP, each on a residual scaled by `residual_multiplier`; a tied head) on
one chip's share of its vocabulary.

One file holds what belongs to the family and to no cell: how the program
is built from a configuration file through the entry points a user calls,
the batch generator, the model-FLOP formula and the plain float32 reference
the program is compared with. `harness.py` finds it by the `family` key of
the configuration file.

The reference's Mamba-2 mixer and recurrence are `models/nemotron_h.py`'s,
by import: one plain Mamba-2 in the benchmark, read here through this
family's keys (`scan_keys`).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmark.models import nemotron_h

QUERY_BLOCK = 512  # the reference's attention, in blocks of queries
# what the reference can be made to do wrong, to show that the check's
# limits refuse it (PERF.md): each is one term of the model
FAULTS = ("no_residual_multiplier", "no_embedding_multiplier",
          "no_logits_scaling", "sqrt_attention_scale",
          "state_dropped_at_chunks", "no_d_skip", "no_block_mlp")

units_per_step = nemotron_h.units_per_step
make_batch = nemotron_h.make_batch


# ---------------------------------------------------------------------------
# the program, through the user's entry points
# ---------------------------------------------------------------------------

PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "shared_intermediate_size",
    "num_hidden_layers", "layer_types", "num_attention_heads",
    "num_key_value_heads", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
    "mamba_d_state", "mamba_d_conv", "mamba_chunk_size", "rms_norm_eps",
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "max_position_embeddings")
SHARE_KEYS = ("vocab_rows", "initializer_range")
# what the program has one way of doing: any other value is another model
FIXED = {"num_local_experts": 0, "position_embedding_type": "nope",
         "tie_word_embeddings": True, "mamba_proj_bias": False,
         "mamba_conv_bias": True, "attention_bias": False,
         "hidden_act": "silu", "normalization_function": "rmsnorm"}


def model_config(config: dict):
    """`GraniteHybridConfig` from the configuration file: the published
    keys under their own names, and the chip's share."""
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig

    if not config["program"]["use_flash_attention"]:
        raise ValueError("the family builds the fused attention op only")
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r} is not built")
    if (config["mamba_n_heads"] * config["mamba_d_head"]
            != config["mamba_expand"] * config["hidden_size"]):
        raise ValueError("a Mamba-2 layer of another width than expand x "
                         "hidden_size")
    return GraniteHybridConfig(
        **{k: config[k] for k in PUBLISHED_KEYS + SHARE_KEYS},
        remat_ffn=config["program"]["remat_ffn"])


def build_forward(config: dict, traffic: dict, batch: int, dropout: bool,
                  main, startup):
    """Forward graph into `main`/`startup`; returns (loss, feed names). The
    model has no dropout, so the check program is the cell's own at the
    check's batch."""
    from paddle_tpu.models.granite_hybrid import (
        build_granite_hybrid_pretrain_program)

    _, _, feed_names, loss = build_granite_hybrid_pretrain_program(
        model_config(config), batch, int(traffic["seq_len"]),
        main_program=main, startup_program=startup)
    return loss, feed_names


def optimizer(config: dict, batch: int):
    import paddle_tpu.fluid as fluid

    return fluid.optimizer.AdamOptimizer(
        learning_rate=config["optimizer"]["learning_rate"])


def scan_keys(config: dict) -> dict:
    """The Mamba-2 sizes under `nemotron_h`'s and `kernels/ssd_scan.py`'s
    names, with a pattern that holds one `M` a Mamba-2 layer."""
    return {"chunk_size": config["mamba_chunk_size"],
            "mamba_num_heads": config["mamba_n_heads"],
            "mamba_head_dim": config["mamba_d_head"],
            "n_groups": config["mamba_n_groups"],
            "ssm_state_size": config["mamba_d_state"],
            "conv_kernel": config["mamba_d_conv"],
            "layer_norm_epsilon": config["rms_norm_eps"],
            "hybrid_override_pattern": "M" * config["layer_types"].count(
                "mamba")}


def forward_flops_per_token(config: dict, seq_len: int) -> Dict[str, float]:
    """Model FLOPs a token of one forward pass, by part, 2 FLOPs a
    multiply-add: the Mamba-2 layers' two projections and the chunked
    scan's products (`nemotron_h.scan_flops_per_token`), the attention
    layers' projections and the causal triangle of their scores and values,
    every layer's MLP, and the head over the held rows of the vocabulary.
    The convolution, norms, gates, decays and scalings are vector work and
    not counted."""
    c = config["hidden_size"]
    mamba = config["layer_types"].count("mamba")
    attention = config["layer_types"].count("attention")
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    d_in = h * p
    bc = 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = c // nh
    return {
        "mamba_projections": mamba * 2.0 * c * (2 * d_in + bc + h + d_in),
        "ssd_scan": mamba * nemotron_h.scan_flops_per_token(scan_keys(config)),
        "attention_projections": attention * 2.0 * c * d * (2 * nh + 2 * nkv),
        "attention_scores": attention * 2.0 * nh * 2 * d * (seq_len + 1) / 2,
        "mlp": config["num_hidden_layers"] * 6.0 * c
        * config["shared_intermediate_size"],
        "head": 2.0 * c * config["vocab_rows"],
    }


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    """Model FLOPs of one step: forward once and backward twice that.
    Recomputation (`remat_ffn`, the scan run again in the backward pass,
    the flash backward's second Q K^T) is not counted."""
    seq = int(traffic["seq_len"])
    return 3.0 * sum(forward_flops_per_token(config, seq).values()) * batch * seq


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _first(config: dict, kind: str) -> int:
    return config["layer_types"].index(kind)


def check_parameters(config: dict) -> List[Tuple[str, str, object]]:
    """(label, parameter, index): the tied table (its gradient the sum of
    the look-up's and the head's); of the first Mamba-2 layer A_log and
    dt_bias (reached through the decays and dt alone), the convolution's
    taps, in_proj and the gated norm's weight; of the attention layer W_k;
    of the first layer's MLP W_gate and W_o."""
    m = f"layers.{_first(config, 'mamba')}"
    a = f"layers.{_first(config, 'attention')}.self_attn"
    return [
        ("embedding", "embed_tokens.weight", None),
        ("mamba.A_log", f"{m}.mamba.A_log", None),
        ("mamba.dt_bias", f"{m}.mamba.dt_bias", None),
        ("mamba.conv1d", f"{m}.mamba.conv1d.weight", None),
        ("mamba.in_proj", f"{m}.mamba.in_proj", None),
        ("mamba.norm", f"{m}.mamba.norm.weight", None),
        ("attention.k_proj", f"{a}.k_proj.weight", None),
        ("mlp.w_gate", "layers.0.shared_mlp.w1", None),
        ("mlp.w_o", "layers.0.shared_mlp.w2", None),
    ]


def attention(z, p, config: dict, r=nemotron_h._same, faults=()):
    """Causal grouped-query attention, no position term, scores scaled by
    attention_multiplier (1 / sqrt(head width) under the fault
    `sqrt_attention_scale`), in blocks of QUERY_BLOCK queries."""
    import jax
    import jax.numpy as jnp

    bsz, s, c = z.shape
    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = c // nh
    scale = (d ** -0.5 if "sqrt_attention_scale" in faults
             else config["attention_multiplier"])
    q = (r(z) @ r(p["q_proj.weight"])).reshape(bsz, s, nkv, nh // nkv, d)
    k = (r(z) @ r(p["k_proj.weight"])).reshape(bsz, s, nkv, d)
    v = (r(z) @ r(p["v_proj.weight"])).reshape(bsz, s, nkv, d)
    pos = jnp.arange(s)

    @jax.checkpoint
    def block(q_blk, q_pos):
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", r(q_blk), r(k)) * scale
        scores = jnp.where(q_pos[:, None] >= pos[None, :], scores, -1e30)
        return jnp.einsum("bgrqk,bkgd->bqgrd",
                          r(jax.nn.softmax(scores, axis=-1)), r(v))

    # one rolled loop over the blocks: the body is compiled once
    size = min(QUERY_BLOCK, s)
    ctx = jax.lax.map(
        lambda blk: block(*blk),
        (jnp.moveaxis(
            q.reshape(bsz, s // size, size, nkv, nh // nkv, d), 1, 0),
         pos.reshape(s // size, size)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(bsz, s, c)
    return r(ctx) @ r(p["o_proj.weight"])


def mlp(z, p, r=nemotron_h._same):
    """W_o (silu(W_gate z) * W_up z)."""
    import jax

    return r(jax.nn.silu(r(z) @ r(p["w1"])) * (r(z) @ r(p["w3"]))) @ r(p["w2"])


def reference_loss(config: dict, params: Dict[str, object], input_ids, labels,
                   products_in=None, faults=()):
    """Mean next-token cross-entropy in plain `jax.numpy`.

    h_0 = embedding_multiplier E[x]; layer l: u = h + m Mixer_l(RMSNorm(h)),
    h' = u + m W_o (silu(W_gate n) * W_up n), n = RMSNorm(u), with m the
    residual_multiplier and the mixer by `layer_types[l]`; logits =
    RMSNorm(h_L) E^T / logits_scaling over the rows held.

    `mamba`: `nemotron_h.mamba2` under `scan_keys` (one group of B and C for
    all heads, the gated norm over all of d_in), its recurrence **position
    by position** from S = 0. `attention`: query head i reads KV head
    i // (heads / KV heads), causal softmax of q . k x attention_multiplier,
    no bias and no position term.

    Departures, the program's too: the vocabulary is the rows held; float32
    throughout; packed rows without a boundary mask, the state zero at
    position 0 and carried to the row's end; what `assumed` of the
    configuration file lists. Every layer keeps its input and nothing else
    for the backward pass (`jax.checkpoint`).

    `products_in`: `nemotron_h._rounded`. `faults` breaks terms (`FAULTS`).
    The check's limits have to refuse each (PERF.md)."""
    table = params["embed_tokens.weight"]
    return loss_of_rows(config, params, table[input_ids], table, labels,
                        products_in, faults)


def loss_of_rows(config: dict, params: Dict[str, object], rows, head, labels,
                 products_in=None, faults=()):
    """`reference_loss` from the rows the look-up read and the table the
    head scores against: the tied table's two uses, apart."""
    import jax
    import jax.numpy as jnp

    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    r = nemotron_h._rounded(products_in)
    eps = config["rms_norm_eps"]
    scan = scan_keys(config)
    m = (1.0 if "no_residual_multiplier" in faults
         else config["residual_multiplier"])
    mixers = {"mamba": lambda z, p: nemotron_h.mamba2(z, p, scan, r, faults),
              "attention": lambda z, p: attention(z, p, config, r, faults)}
    rms = nemotron_h.rms

    x = rows
    if "no_embedding_multiplier" not in faults:
        x = x * config["embedding_multiplier"]
    for i, kind in enumerate(config["layer_types"]):
        prefix = f"layers.{i}."
        mixer = prefix + ("mamba." if kind == "mamba" else "self_attn.")
        p = {k[len(mixer):]: v for k, v in params.items()
             if k.startswith(mixer)}
        ffn = {k[len(prefix + "shared_mlp."):]: v for k, v in params.items()
               if k.startswith(prefix + "shared_mlp.")}

        @jax.checkpoint
        def layer(x, p, ffn, norm1, norm2, mixer=mixers[kind]):
            u = x + m * mixer(rms(x, norm1, eps), p)
            if "no_block_mlp" in faults:
                return u
            return u + m * mlp(rms(u, norm2, eps), ffn, r)

        x = layer(x, p, ffn, params[prefix + "input_layernorm.weight"],
                  params[prefix + "post_attention_layernorm.weight"])
    x = rms(x, params["norm.weight"], eps)
    logits = r(x) @ r(head.T)
    if "no_logits_scaling" not in faults:
        logits = logits / config["logits_scaling"]
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

    def loss_of(wrt, rest, batch):
        return reference_loss(config, {**rest, **wrt}, batch["input_ids"],
                              batch["labels"], products_in, faults)

    # everything that is an array goes in as an argument: a closed-over
    # parameter would be a constant of gigabytes for XLA to fold
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss_of))(
            {k: params[k] for k in names}, rest,
            {k: batch[k] for k in ("input_ids", "labels")})
