"""Plain float32 reference of the Xing4.0 language model: forward, loss and
`jax.grad`, in `jax.numpy` under `jax.default_matmul_precision("highest")`.

No kernel, no sort, no grouped product, no padding (`jax.checkpoint` around
every sublayer, every block of queries and every expert, so that S 4096
fits): the residual streams
are a [B, S, n, C] array mixed by einsums, Sinkhorn is a loop over small
matrices, attention runs in blocks of queries over heads of 192 against
128, the experts are a dense loop over the experts given, every token
through every one of them, weighed by a gate that is zero where the token
did not pick it. Parameters are taken by the names `models/xing4.py` gives
them, so that a program's own weights can be handed over as they lie in
its scope.

It follows, for what the catalog's `config.json` keys name: DeepSeek-V2/V3's
published attention (`DeepseekV3Attention`: low-rank q and kv, a shared
rotated key part, YaRN frequencies, `mscale_all_dim` squared into the
softmax scale) and routing (`noaux_tc`: sigmoid scores, selection by score
+ bias, gates the picks' own scores over their sum, times
`routed_scaling_factor`, one shared expert added for every token); and
"mHC: Manifold-Constrained Hyper-Connections" (arXiv:2512.24880) for the
residual path, with `hc_mult` streams, `hc_sinkhorn_iters` rounds and
`hc_eps`.

Departures from a release, each one the program's too:

- `heads = (first, count)`: only attention heads first .. first + count - 1
  add to a layer's attention output; the parameters given hold those
  heads' columns of W_qb and W_kvb and rows of W_o. `experts = (first,
  count)` likewise for the routed experts: the router scores all of them,
  picks its top-k among all and normalises the gates over all k picks; the
  shared expert is always computed. What absent heads and experts would
  add is left out and that partial result goes on;
- a vocabulary of fewer rows than published is a smaller vocabulary;
- the stream norm has no learned weight, `hc_eps` enters there only
  (Sinkhorn divides by plain sums: the entries are positive), the streams
  start as copies of the embedding and are summed at the end;
- rotate-half pairing inside the rotary part, angles from a float64 table;
- the gates' denominator carries + 1e-6 (`moe_swiglu`'s; the DeepSeek-V3
  release: 1e-20);
- every product, norm and gate in float32; documents packed end to end
  without a boundary mask, positions 0 .. S-1 in every row.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import numpy as np

QUERY_BLOCK = 512  # attention in blocks of queries: [B, heads, 512, S] scores

SETTINGS = (
    "first_k_dense_replace", "num_hidden_layers", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "hc_mult", "hc_sinkhorn_iters",
    "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max", "rms_norm_eps",
    "rope_theta", "rope_scaling")


def reference_settings(cfg) -> dict:
    """What the reference needs of a `Xing4Config` (or of a dict with the
    same keys), as plain numbers."""
    c = dataclasses.asdict(cfg) if dataclasses.is_dataclass(cfg) else dict(cfg)
    return {k: c[k] for k in SETTINGS}


def yarn_table(cfg: dict, seq_len: int):
    """cos, sin [S, rot/2] of position x YaRN's blended frequency, float64
    arithmetic: f_i = (1 - m_i) theta^(-2i/d) / factor + m_i theta^(-2i/d),
    m_i = 1 - clamp((i - low) / (high - low), 0, 1)."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    y = cfg["rope_scaling"]

    def dim_of(turns):
        return d * math.log(y["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    m = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    f = (1.0 - m) * theta ** (-2 * i / d) / y["factor"] + m * theta ** (-2 * i / d)
    angle = np.arange(seq_len, dtype=np.float64)[:, None] * f[None, :]
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    y = cfg["rope_scaling"]
    m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def hyper_maps(streams, p, cfg: dict, iters: Optional[int] = None):
    """(H_pre [B, S, n], H_post [B, S, n], H_res [B, S, n, n]) of the
    streams [B, S, n, C] under the parameters `phi`, `b`, `alpha`."""
    import jax
    import jax.numpy as jnp

    n = cfg["hc_mult"]
    b, s = streams.shape[:2]
    vec = streams.reshape(b, s, -1)
    xbar = vec * jax.lax.rsqrt(
        jnp.mean(jnp.square(vec), axis=-1, keepdims=True) + cfg["hc_eps"])
    t = xbar @ p["phi"]
    a, bias = p["alpha"], p["b"]
    h_pre = jax.nn.sigmoid(a[0] * t[..., :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * t[..., n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(
        (a[2] * t[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n),
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"] if iters is None else iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)  # each row by its sum
        m = m / jnp.sum(m, axis=-2, keepdims=True)  # each column by its sum
    return h_pre, h_post, m


def mla(z, p, cfg: dict):
    """The attention output of the heads whose weights `p` holds."""
    import jax
    import jax.numpy as jnp

    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    eps, lora = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    b, s, _ = z.shape
    nh = p["o_proj"].shape[0] // dv
    cos, sin = yarn_table(cfg, s)

    def rotate(x):  # [B, S, heads, rot]; pairs (i, i + rot/2)
        x1, x2 = x[..., : rot // 2], x[..., rot // 2:]
        c, sn = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)

    c_q = rms(z @ p["q_a_proj"], p["q_a_layernorm"], eps)
    q = (c_q @ p["q_b_proj"]).reshape(b, s, nh, nope + rot)
    kv_a = z @ p["kv_a_proj"]
    c_kv = rms(kv_a[..., :lora], p["kv_a_layernorm"], eps)
    k_rope = rotate(kv_a[..., lora:][:, :, None, :])
    kv = (c_kv @ p["kv_b_proj"]).reshape(b, s, nh, nope + dv)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, nh, rot))], -1)
    v = kv[..., nope:]
    pos = jnp.arange(s)
    scale = softmax_scale(cfg)

    @jax.checkpoint
    def block(q_blk, q_pos):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * scale
        scores = jnp.where(q_pos[:, None] >= pos[None, :], scores, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    # one rolled loop over the blocks: the body is compiled once
    size = min(QUERY_BLOCK, s)
    ctx = jax.lax.map(
        lambda blk: block(*blk),
        (jnp.moveaxis(q.reshape(b, s // size, size, nh, nope + rot), 1, 0),
         pos.reshape(s // size, size)))
    return jnp.moveaxis(ctx, 0, 1).reshape(b, s, nh * dv) @ p["o_proj"]


def swiglu(z, w1, w3, w2):
    import jax

    return (jax.nn.silu(z @ w1) * (z @ w3)) @ w2


def routed_experts(z, p, cfg: dict, experts: Optional[Tuple[int, int]]):
    """The part of the expert layer that the experts first .. first +
    count - 1 give; p["w1"][e] is expert first + e."""
    import jax
    import jax.numpy as jnp

    n_experts = p["gate"].shape[1]
    first, count = experts if experts is not None else (0, n_experts)
    s = jax.nn.sigmoid(z @ p["gate"])
    _, picks = jax.lax.top_k(s + p["expert_bias"], cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(s, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    gates = gates * cfg["routed_scaling_factor"]
    def add_expert(out, held):  # held: expert first + e and its weights
        e, w1, w3, w2 = held
        weight = jnp.sum(jnp.where(picks == e, gates, 0.0), -1)
        return out + weight[..., None] * jax.checkpoint(swiglu)(
            z, w1, w3, w2), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(z), (
        first + jnp.arange(count), p["w1"], p["w3"], p["w2"]))
    return out


def shared_expert(z, p):
    return swiglu(z, p["shared_experts.w1"], p["shared_experts.w3"],
                  p["shared_experts.w2"])


def xing4_loss(params: Dict[str, object], input_ids, labels, cfg: dict,
               experts: Optional[Tuple[int, int]] = None):
    """Mean next-token cross-entropy of the model `params` describe; the
    heads held are those `params` has weights for."""
    import jax
    import jax.numpy as jnp

    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]

    def sub(prefix):
        return {k[len(prefix):]: v for k, v in params.items()
                if k.startswith(prefix)}

    @functools.partial(jax.checkpoint, static_argnums=(1, 2, 3))
    def hyper(streams, name, norm, fn):
        # a sublayer keeps its input streams and nothing else for the
        # backward pass, so that S 4096 fits one chip
        h_pre, h_post, h_res = hyper_maps(streams, sub(name), cfg)
        u = jnp.einsum("bsn,bsnc->bsc", h_pre, streams)
        y = fn(rms(u, params[norm], eps))
        return (jnp.einsum("bsij,bsjc->bsic", h_res, streams)
                + h_post[..., None] * y[:, :, None, :])

    def ffn(z, i):
        p = sub(f"layers.{i}.mlp.")
        if i < cfg["first_k_dense_replace"]:
            return swiglu(z, p["w1"], p["w3"], p["w2"])
        return routed_experts(z, p, cfg, experts) + shared_expert(z, p)

    x = params["embed_tokens.weight"][input_ids]
    streams = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n, x.shape[-1]))
    for i in range(cfg["num_hidden_layers"]):
        name = f"layers.{i}."
        streams = hyper(
            streams, name + "attn_hc.", name + "input_layernorm.weight",
            lambda z: mla(z, sub(name + "self_attn."), cfg))
        streams = hyper(
            streams, name + "ffn_hc.",
            name + "post_attention_layernorm.weight", lambda z: ffn(z, i))
    x = rms(jnp.sum(streams, axis=2), params["norm.weight"], eps)
    logits = x @ params["lm_head.weight"].T
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def xing4_loss_and_grads(params: Dict[str, object], input_ids, labels,
                         cfg: dict, experts: Optional[Tuple[int, int]] = None):
    """(loss, {name: gradient}) for every parameter but the expert bias,
    which the loss does not train, in float32 at the highest matmul
    precision."""
    import jax
    import jax.numpy as jnp

    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    names = [k for k in params if not k.endswith("expert_bias")]
    rest = {k: v for k, v in params.items() if k not in names}

    def loss_of(chosen, rest):
        return xing4_loss({**rest, **chosen}, input_ids, labels, cfg, experts)

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss_of))(
            {k: params[k] for k in names}, rest)
