"""Plain float32 reference of the LFM2-MoE language model: forward, loss and
`jax.grad`, in `jax.numpy` under `jax.default_matmul_precision("highest")`.

No kernel, no scan, no sort and no grouped product: the experts are a dense
loop over the experts given, every token through every one of them, weighed
by a gate that is zero where the token did not pick it. It follows the
released `modeling_lfm2_moe.py` (`Lfm2MoeDecoderLayer`, `Lfm2MoeShortConv`,
`Lfm2MoeAttention`, `Lfm2MoeSparseMoeBlock`); parameters are taken by the
names `models/lfm2_moe.py` gives them, so that a program's own weights can
be handed over as they lie in its scope.

Departures from the released model, each one the program's too:

- the output head is the embedding matrix (`tie_embedding` of the LFM2
  family; the catalog's `config.json` keys do not say);
- `held = (first, count)`: only experts first .. first + count - 1 add to an
  expert layer's output. The router scores all `num_experts`, picks its
  top-k among all of them and normalises the gates over all k picks; what
  the absent experts would have added is left out and that partial result
  goes on to the next layer. `held=None` is the whole model;
- a vocabulary of fewer rows than published is a smaller vocabulary: the
  embedding has `vocab_rows` rows and the loss is over them;
- RoPE angles come from a float64 table (released: float32 arithmetic);
- every product, norm and gate in float32 (released: bf16 autocast, the
  norms casting back to bf16 before their weight);
- documents are packed end to end without a boundary mask, positions run
  0 .. S-1 in every row, and nothing is dropped out (the model has no
  dropout).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import numpy as np

QUERY_BLOCK = 512  # attention in blocks of queries: [B, heads, 512, S] scores


def reference_settings(cfg) -> dict:
    """What the reference needs of a `Lfm2MoeConfig` (or of a dict with
    the same keys), as plain numbers."""
    c = dataclasses.asdict(cfg) if dataclasses.is_dataclass(cfg) else dict(cfg)
    return {k: c[k] for k in (
        "num_attention_heads", "num_key_value_heads", "norm_eps",
        "rope_theta", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor", "layer_types", "num_dense_layers")}


def lfm2_moe_loss(params: Dict[str, object], input_ids, labels, cfg: dict,
                  held: Optional[Tuple[int, int]] = None):
    """Mean next-token cross-entropy of the model `params` describe."""
    import jax
    import jax.numpy as jnp

    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["norm_eps"]
    top_k = cfg["num_experts_per_tok"]

    def rms(x, w):
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w

    def short_conv(z, p):
        bg, cg, u = jnp.split(z @ p["in_proj"], 3, axis=-1)
        bu = bg * u
        taps = p["conv"]  # [L, H]; the last tap weighs the current position
        n_taps, s = taps.shape[0], z.shape[1]
        c = sum(taps[j] * jnp.pad(
            bu, ((0, 0), (n_taps - 1 - j, 0), (0, 0)))[:, :s]
            for j in range(n_taps))
        return (cg * c) @ p["out_proj"]

    def rope(x):
        # x [B, S, heads, d]; pairs (i, i + d/2), angle = position * theta^(-2i/d)
        s, d = x.shape[1], x.shape[-1]
        freq = float(cfg["rope_theta"]) ** (
            -np.arange(0, d, 2, dtype=np.float64) / d)
        angle = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
        cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
        sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(z, p):
        b, s, h = z.shape
        d = h // nh
        q = (z @ p["q_proj.weight"]).reshape(b, s, nh, d)
        k = (z @ p["k_proj.weight"]).reshape(b, s, nkv, d)
        v = (z @ p["v_proj.weight"]).reshape(b, s, nkv, d)
        q = rope(rms(q, p["q_layernorm.weight"]))
        k = rope(rms(k, p["k_layernorm.weight"]))
        q = q.reshape(b, s, nkv, nh // nkv, d)  # KV head j serves a group
        pos = jnp.arange(s)

        @jax.checkpoint
        def block(q_blk, q_pos):
            scores = jnp.einsum("bqjgd,bkjd->bjgqk", q_blk, k) / math.sqrt(d)
            scores = jnp.where(q_pos[:, None] >= pos[None, :], scores, -1e30)
            return jnp.einsum("bjgqk,bkjd->bqjgd",
                              jax.nn.softmax(scores, axis=-1), v)

        size = min(QUERY_BLOCK, s)
        ctx = jnp.concatenate(
            [block(q[:, i:i + size], pos[i:i + size])
             for i in range(0, s, size)], axis=1)
        return ctx.reshape(b, s, h) @ p["out_proj.weight"]

    def swiglu(z, w1, w3, w2):
        return (jax.nn.silu(z @ w1) * (z @ w3)) @ w2

    def moe(z, p):
        n_experts = p["gate"].shape[1]
        first, count = held if held is not None else (0, n_experts)
        s = jax.nn.sigmoid(z @ p["gate"])
        _, picks = jax.lax.top_k(s + p["expert_bias"], top_k)
        gates = jnp.take_along_axis(s, picks, axis=-1)
        if cfg["norm_topk_prob"]:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
        gates = gates * cfg["routed_scaling_factor"]
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
    for i, kind in enumerate(cfg["layer_types"]):
        name = f"layers.{i}."
        z = rms(x, params[name + "operator_norm.weight"])
        x = x + (attention(z, sub(name + "self_attn."))
                 if kind == "full_attention" else
                 short_conv(z, sub(name + "conv.")))
        z = rms(x, params[name + "ffn_norm.weight"])
        ffn = sub(name + "feed_forward.")
        x = x + (swiglu(z, ffn["w1"], ffn["w3"], ffn["w2"])
                 if i < cfg["num_dense_layers"] else moe(z, ffn))
    x = rms(x, params["embedding_norm.weight"])
    logits = x @ params["embed_tokens.weight"].T
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def lfm2_moe_loss_and_grads(params: Dict[str, object], input_ids, labels,
                            cfg: dict, held: Optional[Tuple[int, int]] = None):
    """(loss, {name: gradient}) for every parameter but the expert bias,
    which the loss does not train, in float32 at the highest matmul
    precision."""
    import jax
    import jax.numpy as jnp

    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    names = [k for k in params if not k.endswith("expert_bias")]
    rest = {k: v for k, v in params.items() if k not in names}

    def loss_of(chosen, rest):
        return lfm2_moe_loss({**rest, **chosen}, input_ids, labels, cfg, held)

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss_of))(
            {k: params[k] for k in names}, rest)
