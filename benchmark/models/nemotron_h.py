"""Model family `nemotron_h`: next-token pre-training of a Nemotron-H hybrid
decoder (NVIDIA, `model_type` `nemotron_h`: Mamba-2 mixers, squared-ReLU
experts beside a shared one, attention without a position term, one mixer
a layer) on one chip's share of its routed experts and vocabulary.

One file holds what belongs to the family and to no cell: how the program
is built from a configuration file through the entry points a user calls,
the batch generator, the model-FLOP formula and the plain float32 reference
the program is compared with. `harness.py` finds it by the `family` key of
the configuration file.

The reference stands here and nowhere else: the yardstick lies under the
benchmark's paths, and `tests/test_nemotron_ops.py` and
`tests/test_nemotron_model.py` judge the program by its pieces (`mamba2`,
`routed_experts`, `shared_expert`, `reference_loss`).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import numpy as np

QUERY_BLOCK = 512  # the reference's attention, in blocks of queries
SCAN_BLOCK = 64    # its recurrence keeps one state every so many positions
# what the reference can be made to do wrong, to show that the check's
# limits refuse it (PERF.md): each is one term of the model
FAULTS = ("state_dropped_at_chunks", "group_zero_for_all", "no_d_skip",
          "no_softplus", "relu_not_squared", "no_shared_expert",
          "norm_over_all")


def units_per_step(traffic: dict) -> int:
    """Tokens in one step; packed documents, so every one is real."""
    return int(traffic["batch"]) * int(traffic["seq_len"])


# ---------------------------------------------------------------------------
# the program, through the user's entry points
# ---------------------------------------------------------------------------

PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "num_attention_heads", "num_key_value_heads",
    "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
    "ssm_state_size", "conv_kernel", "chunk_size", "time_step_min",
    "time_step_max", "time_step_floor", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "norm_topk_prob",
    "routed_scaling_factor", "layer_norm_epsilon", "rescale_prenorm_residual",
    "max_position_embeddings")
SHARE_KEYS = ("experts_held", "first_expert", "vocab_rows",
              "initializer_range")
# what the program has one way of doing: any other value is another model
FIXED = {"attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
         "use_bias": False, "use_conv_bias": True, "mamba_hidden_act": "silu",
         "mlp_hidden_act": "relu2", "n_group": 1, "topk_group": 1,
         "tie_word_embeddings": False, "residual_in_fp32": False,
         "sliding_window": None}


def model_config(config: dict):
    """`NemotronHConfig` from the configuration file: the published keys
    under their own names, and the chip's share."""
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    if not config["program"]["use_flash_attention"]:
        raise ValueError("the family builds the fused attention op only")
    for key, value in FIXED.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r} is not built")
    if config["norm_eps"] != config["layer_norm_epsilon"]:
        raise ValueError("the gated norm's epsilon is the block norm's")
    if (config["mamba_num_heads"] * config["mamba_head_dim"]
            < config["hidden_size"]):
        raise ValueError("a Mamba-2 layer narrower than the residual")
    return NemotronHConfig(
        **{k: config[k] for k in PUBLISHED_KEYS + SHARE_KEYS},
        residual_scale_layers=config.get("published", {}).get(
            "num_hidden_layers"),
        remat_ffn=config["program"]["remat_ffn"],
        expert_bias_update_rate=config["optimizer"]["expert_bias_update_rate"])


def build_forward(config: dict, traffic: dict, batch: int, dropout: bool,
                  main, startup):
    """Forward graph into `main`/`startup`; returns (loss, feed names). The
    model has no dropout, so the check program is the cell's own at the
    check's batch."""
    from paddle_tpu.models.nemotron_h import build_nemotron_h_pretrain_program

    _, _, feed_names, loss = build_nemotron_h_pretrain_program(
        model_config(config), batch, int(traffic["seq_len"]),
        main_program=main, startup_program=startup)
    return loss, feed_names


def optimizer(config: dict, batch: int):
    import paddle_tpu.fluid as fluid

    return fluid.optimizer.AdamOptimizer(
        learning_rate=config["optimizer"]["learning_rate"])


def kinds(config: dict) -> Dict[str, int]:
    """How many layers of each kind the pattern names."""
    pattern = config["hybrid_override_pattern"]
    return {k: pattern.count(k) for k in "M*E"}


def scan_flops_per_token(config: dict) -> float:
    """The chunked scan's products a token of one forward pass, 2 FLOPs a
    multiply-add, at chunk Q over H heads of P, G groups of N: C B^T inside
    a chunk (Q G N), the masked matrix times x (Q H P), the state a chunk
    leaves (H P N) and the entering state read out (H P N). The product
    over the chunks that carries the state is 1 / Q of the last and is not
    counted."""
    q, h, p = (config["chunk_size"], config["mamba_num_heads"],
               config["mamba_head_dim"])
    g, n = config["n_groups"], config["ssm_state_size"]
    return 2.0 * (q * g * n + q * h * p + 2 * h * p * n)


def forward_flops_per_token(config: dict, seq_len: int) -> Dict[str, float]:
    """Model FLOPs a token of one forward pass of what this chip computes,
    by part, 2 FLOPs a multiply-add: the Mamba-2 layers' two projections
    and the chunked scan's products, the attention layers' projections and
    the causal triangle of their scores and values, the routed experts at
    the expected share of the picks (experts per token x held / router
    width), the shared expert for every token, the router, and the head
    over the held rows of the vocabulary. The convolution, norms, gates and
    decays are vector work and not counted."""
    c = config["hidden_size"]
    count = kinds(config)
    d_in = config["mamba_num_heads"] * config["mamba_head_dim"]
    bc = 2 * config["n_groups"] * config["ssm_state_size"]
    nh, nkv, d = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    share = (config["num_experts_per_tok"] * config["experts_held"]
             / config["n_routed_experts"])
    return {
        "mamba_projections": count["M"] * 2.0 * c * (
            2 * d_in + bc + config["mamba_num_heads"] + d_in),
        "ssd_scan": count["M"] * scan_flops_per_token(config),
        "attention_projections": count["*"] * 2.0 * c * d * (2 * nh + 2 * nkv),
        "attention_scores": count["*"] * 2.0 * nh * 2 * d * (seq_len + 1) / 2,
        "routed_experts": count["E"] * share * 4.0 * c
        * config["moe_intermediate_size"],
        "shared_expert": count["E"] * 4.0 * c
        * config["moe_shared_expert_intermediate_size"]
        * config["n_shared_experts"],
        "router": count["E"] * 2.0 * c * config["n_routed_experts"],
        "head": 2.0 * c * config["vocab_rows"],
    }


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    """Model FLOPs of one step: forward once and backward twice that.
    Recomputation (`remat_ffn`, the scan run again in the
    backward pass, the flash backward's second Q K^T) is not counted."""
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


def _first(config: dict, kind: str) -> int:
    return config["hybrid_override_pattern"].index(kind)


def check_parameters(config: dict) -> List[Tuple[str, str, object]]:
    """(label, parameter, index): the embedding (the deepest under the
    loss); of the first Mamba-2 layer A_log and dt_bias (reached through
    the decays and dt alone), the convolution's taps, in_proj and the
    gated norm's weight; of the first expert layer the routed experts' W1,
    the shared expert's W1 and the router; of the attention layer W_k."""
    m, e, a = (f"layers.{_first(config, k)}.mixer" for k in "ME*")
    return [
        ("embedding", "embeddings.weight", None),
        ("mamba.A_log", f"{m}.A_log", None),
        ("mamba.dt_bias", f"{m}.dt_bias", None),
        ("mamba.conv1d", f"{m}.conv1d.weight", None),
        ("mamba.in_proj", f"{m}.in_proj", None),
        ("mamba.norm", f"{m}.norm.weight", None),
        ("first_moe.w1", f"{e}.w1", None),
        ("first_moe.shared_w1", f"{e}.shared_experts.w1", None),
        ("first_moe.gate", f"{e}.gate", None),
        ("attention.k_proj", f"{a}.k_proj.weight", None),
    ]


def _same(a):
    return a


def _rounded(products_in):
    """Both operands of a product rounded to `products_in` first (the
    router's scores and the recurrence's decays stay float32, as the
    program keeps them): how the reference reads in a precision below the
    program's."""
    import jax.numpy as jnp

    if products_in is None:
        return _same
    return lambda a: a.astype(products_in).astype(jnp.float32)


def rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def recurrence(x, dt, a, b, c, d, r=_same, dropped_every=0, skip=True):
    """S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D_h x_t
    from S = 0, **one position after another**: x [B, S, H, P], dt
    [B, S, H], a / d [H], b / c [B, S, H, N] (a head's own copy of its
    group's). A `lax.scan` over the row in blocks of SCAN_BLOCK positions
    under `jax.checkpoint`, so that the backward pass keeps one state a
    block. Faults: the state dropped at every `dropped_every`-th position
    (where the program's chunks meet); D x left out (`skip` false)."""
    import jax
    import jax.numpy as jnp

    bsz, s, h, p = x.shape
    block = min(SCAN_BLOCK, s)
    if s % block:
        raise ValueError(f"a row of {s} positions in blocks of {block}")
    keeps = jnp.ones((s,), jnp.float32)
    if dropped_every:
        keeps = (jnp.arange(s) % dropped_every != 0).astype(jnp.float32)

    def position(state, at):
        x_t, dt_t, b_t, c_t, keep = at
        # the products of the recurrence take the rounding too: the
        # update dt x B^T and the read-out S C
        state = (jnp.exp(dt_t * a)[..., None, None] * state * keep
                 + r(dt_t[..., None] * x_t)[..., None]
                 * r(b_t)[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", r(state), r(c_t))

    @jax.checkpoint
    def positions(state, ats):
        return jax.lax.scan(position, state, ats)

    def blocks(t):  # [B, S, ...] -> [S / block, block, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((s // block, block) + t.shape[1:])

    start = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(
        positions, start,
        tuple(map(blocks, (x, dt, b, c))) + (keeps.reshape(-1, block),))
    y = jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)
    return y + d[:, None] * x if skip else y


def mamba2(z, p, config: dict, r=_same, faults=()):
    """The Mamba-2 mixer on z [B, S, C] from its own parameters."""
    import jax
    import jax.numpy as jnp

    bsz, s, _ = z.shape
    h, hd = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n = config["n_groups"], config["ssm_state_size"]
    taps, d_in = config["conv_kernel"], h * hd
    proj = r(z) @ r(p["in_proj"])
    gate = proj[..., :d_in]
    xbc = proj[..., d_in:2 * d_in + 2 * g * n]
    dt = proj[..., 2 * d_in + 2 * g * n:] + p["dt_bias"]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(
        sum(p["conv1d.weight"][j] * padded[:, j:j + s] for j in range(taps))
        + p["conv1d.bias"])
    x = xbc[..., :d_in].reshape(bsz, s, h, hd)
    b = xbc[..., d_in:d_in + g * n].reshape(bsz, s, g, n)
    c = xbc[..., d_in + g * n:].reshape(bsz, s, g, n)
    if "group_zero_for_all" in faults:
        b, c = (jnp.broadcast_to(t[:, :, :1], (bsz, s, h, n)) for t in (b, c))
    else:
        b, c = (jnp.repeat(t, h // g, axis=2) for t in (b, c))
    if "no_softplus" not in faults:
        dt = jax.nn.softplus(dt)
    y = recurrence(
        x, dt, -jnp.exp(p["A_log"]), b, c, p["D"], r,
        dropped_every=(config["chunk_size"]
                       if "state_dropped_at_chunks" in faults else 0),
        skip="no_d_skip" not in faults)
    y = y.reshape(bsz, s, d_in) * jax.nn.silu(gate)
    groups = 1 if "norm_over_all" in faults else g
    y = rms(y.reshape(bsz, s, groups, d_in // groups), 1.0,
            config["layer_norm_epsilon"]).reshape(bsz, s, d_in)
    return r(y * p["norm.weight"]) @ r(p["out_proj"])


def attention(z, p, config: dict, r=_same):
    """Causal grouped-query attention, no position term, in blocks of
    QUERY_BLOCK queries."""
    import jax
    import jax.numpy as jnp

    bsz, s, _ = z.shape
    nh, nkv, d = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    q = (r(z) @ r(p["q_proj.weight"])).reshape(bsz, s, nkv, nh // nkv, d)
    k = (r(z) @ r(p["k_proj.weight"])).reshape(bsz, s, nkv, d)
    v = (r(z) @ r(p["v_proj.weight"])).reshape(bsz, s, nkv, d)
    pos = jnp.arange(s)

    @jax.checkpoint
    def block(q_blk, q_pos):
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", r(q_blk), r(k)) * d ** -0.5
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
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(bsz, s, nh * d)
    return r(ctx) @ r(p["o_proj.weight"])


def relu2(z, w1, w2, r=_same, faults=()):
    import jax
    import jax.numpy as jnp

    a = jax.nn.relu(r(z) @ r(w1))
    return r(a if "relu_not_squared" in faults else jnp.square(a)) @ r(w2)


def routed_experts(z, p, config: dict, experts: Optional[Tuple[int, int]],
                   r=_same, faults=()):
    """What routed experts first .. first + count - 1 (`experts`; None:
    all) add: a dense loop over them, every token through each, weighed by
    a gate that is zero where the token did not pick it."""
    import jax
    import jax.numpy as jnp

    n_experts = p["gate"].shape[1]
    first, count = experts if experts is not None else (0, n_experts)
    s = jax.nn.sigmoid(z @ p["gate"])  # the router stays float32
    _, picks = jax.lax.top_k(s + p["expert_bias"],
                             config["num_experts_per_tok"])
    gates = jnp.take_along_axis(s, picks, axis=-1)
    if config["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    gates = gates * config["routed_scaling_factor"]
    expert = jax.checkpoint(functools.partial(relu2, r=r, faults=faults))

    def add_expert(out, held):  # held: expert first + e and its weights
        e, w1, w2 = held
        weight = jnp.sum(jnp.where(picks == e, gates, 0.0), -1)
        return out + weight[..., None] * expert(z, w1, w2), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(z), (
        first + jnp.arange(count), p["w1"], p["w2"]))
    return out


def shared_expert(z, p, r=_same, faults=()):
    return relu2(z, p["shared_experts.w1"], p["shared_experts.w2"], r, faults)


def reference_loss(config: dict, params: Dict[str, object], input_ids, labels,
                   experts: Optional[Tuple[int, int]], products_in=None,
                   faults=()):
    """Mean next-token cross-entropy in plain `jax.numpy`.

    Block: x' = x + Mixer_l(RMSNorm_l(x)), the mixer by
    `hybrid_override_pattern[l]`; embedding -> layers -> RMSNorm -> an
    untied head over the rows held.

    `M`, Mamba-2 ("Transformers are SSMs", arXiv:2405.21060): [z, xBC, dt]
    = u W_in; xBC = silu(causal depthwise conv of conv_kernel taps + bias);
    x in mamba_num_heads heads of mamba_head_dim, B and C in n_groups
    groups of ssm_state_size, head h reading group h // (heads / groups);
    dt = softplus(dt + dt_bias), A = -exp(A_log); **position by position**
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T from S = 0, y_t = S_t C_t
    + D_h x_t (`recurrence`); y = RMSNorm over groups of d_in / n_groups of
    y * silu(z), times a learned weight; W_out.

    `*`, attention: query head i reads KV head i // (heads / KV heads),
    causal softmax of q . k / sqrt(head_dim), no bias and no position term.

    `E`, experts: s = sigmoid(W_g z), the top-k of s + b picked, gates the
    picks' own scores over their sum + 1e-6 times routed_scaling_factor,
    experts W2 relu(W1 z)^2, plus the shared expert for every token.

    Departures, the program's too: `experts = (first, count)` leaves out
    what routed experts outside first .. first + count - 1 would add (they
    are scored, picked and normalised over all the same); the vocabulary
    is the rows held; float32 throughout; packed rows without a boundary
    mask, the state zero at position 0 and carried to the row's end; what
    `assumed` of the configuration file lists. Every layer keeps its input
    and nothing else for the backward pass (`jax.checkpoint`), so that
    S 4096 fits beside the check program.

    `products_in`: `_rounded`. `faults` breaks terms (`FAULTS`). The
    check's limits have to refuse each (PERF.md)."""
    import jax
    import jax.numpy as jnp

    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    r = _rounded(products_in)
    eps = config["layer_norm_epsilon"]

    def moe(z, p):
        out = routed_experts(z, p, config, experts, r, faults)
        if "no_shared_expert" in faults:
            return out
        return out + shared_expert(z, p, r, faults)

    mixers = {"M": lambda z, p: mamba2(z, p, config, r, faults),
              "*": lambda z, p: attention(z, p, config, r),
              "E": moe}

    x = params["embeddings.weight"][input_ids]
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        prefix = f"layers.{i}.mixer."
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}

        @jax.checkpoint
        def layer(x, p, norm, mixer=mixers[kind]):
            return x + mixer(rms(x, norm, eps), p)

        x = layer(x, p, params[f"layers.{i}.norm.weight"])
    x = rms(x, params["norm_f.weight"], eps)
    logits = r(x) @ r(params["lm_head.weight"].T)
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
