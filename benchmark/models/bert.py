"""Model family `bert`: BERT pre-training (MLM + NSP), Devlin et al. 2018.

One file holds what belongs to the family and to no cell: how the program
is built from a configuration file through the entry points a user calls,
the batch generator, the model-FLOP formula and the plain float32
reference the program is compared with. `harness.py` finds it by the
`family` key of the configuration file.

The generator and the FLOP formula are copies (`models/bert.py:
random_pretrain_batch`, extended; `bench.py:_bert_step_flops`): later PRs
may edit the originals, and the yardstick has to stay put.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np


def units_per_step(traffic: dict) -> int:
    """Tokens in one step, padding included: the step computes them."""
    return int(traffic["batch"]) * int(traffic["seq_len"])


# ---------------------------------------------------------------------------
# the program, through the user's entry points
# ---------------------------------------------------------------------------


def model_config(config: dict, traffic: dict, dropout: bool):
    """`BertConfig` from the configuration file. The position table grows to
    the cell's sequence length where that is longer than the published 512
    (the file lists it under `changed`); `dropout=False` is the check
    program, whose reference cannot replay the in-kernel masks."""
    from paddle_tpu.models.bert import BertConfig

    program = config["program"]
    return BertConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        hidden_act=config["hidden_act"],
        hidden_dropout_prob=(
            config["hidden_dropout_prob"] if dropout else 0.0),
        attention_probs_dropout_prob=(
            config["attention_probs_dropout_prob"] if dropout else 0.0),
        max_position_embeddings=max(
            config["max_position_embeddings"], int(traffic["seq_len"])),
        type_vocab_size=config["type_vocab_size"],
        initializer_range=config["initializer_range"],
        use_flash_attention=program["use_flash_attention"],
        remat_ffn=program["remat_ffn"],
        fuse_stack=program["fuse_stack"],
    )


def build_forward(config: dict, traffic: dict, batch: int, dropout: bool,
                  main, startup):
    """Forward graph into `main`/`startup`; returns (loss, feed names)."""
    from paddle_tpu.models.bert import build_bert_pretrain_program

    cfg = model_config(config, traffic, dropout)
    _, _, feed_names, loss = build_bert_pretrain_program(
        cfg, batch, int(traffic["seq_len"]), int(traffic["max_preds"]),
        main_program=main, startup_program=startup)
    return loss, feed_names


def optimizer(config: dict, batch: int):
    import paddle_tpu.fluid as fluid

    return fluid.optimizer.AdamOptimizer(
        learning_rate=config["optimizer"]["learning_rate"])


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    """Model FLOPs of one step, forward + backward: 6 N per token for the
    matmul parameters (forward 2 N, backward 4 N) plus 12 L S H per token
    for attention scores and context. Recomputation (remat_ffn, the flash
    backward's second QK^T) is not counted. Copy of
    `bench.py:_bert_step_flops`."""
    h, L = config["hidden_size"], config["num_hidden_layers"]
    ff, v = config["intermediate_size"], config["vocab_size"]
    seq = int(traffic["seq_len"])
    n_matmul = L * (4 * h * h + 2 * h * ff) + v * h
    per_token = 6 * n_matmul + 12 * L * seq * h
    return float(per_token * batch * seq)


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def make_batch(config: dict, traffic: dict, batch: int,
               rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """One pre-training batch as `create_pretraining_data.py` of the BERT
    release shapes it, with uniform token ids in place of a corpus:

    - `short_seq_prob` of the sequences have a length uniform in [2, S],
      the rest fill S; `input_mask` is 0 on the padding, ids there are 0;
    - two segments split at a uniform point, `token_type_ids` 0 then 1;
    - `masked_lm_prob` of the real tokens (at least 1, at most `max_preds`)
      are predicted, never position 0 (the [CLS] slot); unused slots point
      at the row's position 0 with `mask_weights` 0;
    - `nsp_labels` uniform in {0, 1}.

    `mask_positions` are flat indices into [B*S], as the program gathers.
    """
    b, s, mp = batch, int(traffic["seq_len"]), int(traffic["max_preds"])
    vocab = config["vocab_size"]
    short = rng.random(b) < traffic["short_seq_prob"]
    lengths = np.where(short, rng.integers(2, s + 1, b), s)
    pos = np.arange(s, dtype=np.int32)
    real = pos[None, :] < lengths[:, None]
    split = (1 + np.floor(rng.random(b) * (lengths - 1))).astype(np.int64)

    ids = np.where(real, rng.integers(0, vocab, (b, s)), 0).astype(np.int32)
    types = ((pos[None, :] >= split[:, None]) & real).astype(np.int32)

    n_pred = np.clip(np.rint(lengths * traffic["masked_lm_prob"]), 1, mp)
    n_pred = np.minimum(n_pred, lengths - 1).astype(np.int64)
    # a random order of the positions 1..len-1 in every row: rank random
    # keys, with position 0 and the padding pushed to the end
    keys = rng.random((b, s))
    keys[:, 0] = 2.0
    keys[~real] = 2.0
    order = np.argsort(keys, axis=1)[:, :mp]
    used = np.arange(mp)[None, :] < n_pred[:, None]
    picked = np.sort(np.where(used, order, s), axis=1)  # unused last
    picked = np.where(picked == s, 0, picked)
    flat = (picked + np.arange(b)[:, None] * s).astype(np.int32)
    labels = np.where(used, rng.integers(0, vocab, (b, mp)), 0)

    return {
        "input_ids": ids,
        "token_type_ids": types,
        "position_ids": np.tile(pos, (b, 1)),
        "input_mask": real.astype(np.float32),
        "mask_positions": flat.reshape(-1),
        "mask_labels": labels.reshape(-1, 1).astype(np.int32),
        "mask_weights": used.reshape(-1, 1).astype(np.float32),
        "nsp_labels": rng.integers(0, 2, (b, 1)).astype(np.int32),
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

# (label, parameter, index into its leading axis or None): the gradients the
# check compares. The scan-fused stack keeps its layers stacked on axis 0,
# so "first layer" and "last layer" are slices of one parameter.
def check_parameters(config: dict) -> List[Tuple[str, str, object]]:
    last = config["num_hidden_layers"] - 1
    return [
        ("first_layer.qkv_w", "encoder_stack.qkv_w", 0),
        ("last_layer.qkv_w", "encoder_stack.qkv_w", last),
        ("last_layer.ffn_w2", "encoder_stack.ffn_w2", last),
        ("word_embedding", "word_embedding", None),
    ]


def reference_loss_and_grads(config: dict, traffic: dict,
                             params: Dict[str, object],
                             batch: Dict[str, np.ndarray]):
    """Loss and the gradients of `check_parameters`, in float32 with
    `jax.default_matmul_precision("highest")`, from plain `jax.numpy`: no
    kernel, no AMP, no dropout. Follows Devlin et al. and the released
    `modeling.py` (post-LN blocks, tanh-approximated GELU, MLM head tied to
    the word embedding, NSP on the pooled [CLS]). Departures, which are the
    program's and are kept so that the comparison sees arithmetic only:
    LayerNorm epsilon 1e-5 (released: 1e-12), erf GELU in the MLM transform,
    -1e4 additive mask, MLM mean over `sum(weights) + 1e-5`.

    The layers run under `lax.scan` with a per-layer `jax.checkpoint`, so
    that S = 4096 keeps one layer's [B, heads, S, S] scores alive at a time.
    """
    import jax
    import jax.numpy as jnp

    nh = config["num_attention_heads"]
    eps = config["layer_norm_eps"]
    names = sorted({p for _, p, _ in check_parameters(config)})

    def ln(x, scale, bias):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias

    def xent(logits, labels):
        logp = logits - jax.scipy.special.logsumexp(
            logits, axis=-1, keepdims=True)
        return -jnp.take_along_axis(logp, labels, axis=-1)

    def layer(x, p, bias):
        b, s, h = x.shape
        dh = h // nh
        qkv = x @ p["qkv_w"] + p["qkv_b"]
        q, k, v = (t.reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(dh) + bias
        ctx = jax.nn.softmax(scores, axis=-1) @ v
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
        x = ln(x + ctx @ p["out_w"] + p["out_b"],
               p["ln1_scale"], p["ln1_bias"])
        inter = jax.nn.gelu(x @ p["ffn_w1"] + p["ffn_b1"], approximate=True)
        return ln(x + inter @ p["ffn_w2"] + p["ffn_b2"],
                  p["ln2_scale"], p["ln2_bias"])

    def loss_fn(wrt, rest, batch):
        w = {**rest, **wrt}
        x = (w["word_embedding"][batch["input_ids"]]
             + w["pos_embedding"][batch["position_ids"]]
             + w["sent_embedding"][batch["token_type_ids"]])
        x = ln(x, w["pre_encoder_ln_scale"], w["pre_encoder_ln_bias"])
        bias = (1e4 * (batch["input_mask"] - 1.0))[:, None, None, :]
        stack = {k[len("encoder_stack."):]: v for k, v in w.items()
                 if k.startswith("encoder_stack.")}
        body = jax.checkpoint(lambda c, p: (layer(c, p, bias), None))
        x, _ = jax.lax.scan(body, x, stack)

        pooled = jnp.tanh(x[:, 0] @ w["pooled_fc.w_0"] + w["pooled_fc.b_0"])
        picked = x.reshape(-1, x.shape[-1])[batch["mask_positions"]]
        trans = jax.nn.gelu(
            picked @ w["mask_lm_trans_fc.w_0"] + w["mask_lm_trans_fc.b_0"],
            approximate=False)
        trans = ln(trans, w["mask_lm_trans_ln_scale"],
                   w["mask_lm_trans_ln_bias"])
        logits = trans @ w["word_embedding"].T + w["mask_lm_out_fc.b_0"]
        weights = batch["mask_weights"]
        mlm = (jnp.sum(xent(logits, batch["mask_labels"]) * weights)
               / (jnp.sum(weights) + 1e-5))
        nsp_logits = pooled @ w["next_sent_fc.w_0"] + w["next_sent_fc.b_0"]
        nsp = jnp.mean(xent(nsp_logits, batch["nsp_labels"]))
        return mlm + nsp

    wrt = {n: params[n] for n in names}
    rest = {n: v for n, v in params.items() if n not in wrt}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(wrt, rest, batch)
    return loss, grads
