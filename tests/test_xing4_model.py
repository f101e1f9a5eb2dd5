"""The tiny Xing4.0 program (one dense layer, two expert layers, four
residual streams, latent attention with unequal head widths) against the
plain float32 reference on seeded weights: loss and gradients in float32
and under bf16 AMP, the whole model and a held share of it; the share test
(head shares and expert shares add up to the uncut sublayers, the shared
expert counted once); a few optimizer steps; what can be fetched beside
the loss."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.executor import Scope
from model_checks import rel, run_layer
from paddle_tpu.models import xing4_reference as ref
from paddle_tpu.models.xing4 import (Xing4Config,
                                     build_xing4_pretrain_program,
                                     sinkhorn_gaps, tokens_per_expert)

BATCH, SEQ = 2, 32
# what `benchmark/models/xing4_0.py:check_parameters` names, at the tiny
# model's layer indices
NAMED = ("embed_tokens.weight", "layers.0.ffn_hc.phi",
         "layers.0.self_attn.kv_b_proj", "layers.1.mlp.shared_experts.w1",
         "layers.1.mlp.w1", "layers.1.mlp.gate")


def _built(cfg, amp):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard():
        _, _, feeds, loss = build_xing4_pretrain_program(
            cfg, BATCH, SEQ, main_program=main, startup_program=startup)
        with fluid.program_guard(main, startup):
            opt = fluid.optimizer.AdamOptimizer(learning_rate=1e-3)
            if amp:
                opt = mixed_precision.decorate(opt, use_bf16=True)
            _, pgs = opt.minimize(loss, startup_program=startup)
    assert feeds == ["input_ids", "labels"]
    exe, scope = fluid.Executor(), Scope()
    exe.run(startup, scope=scope)
    ids = np.random.default_rng(3).integers(
        0, cfg.vocab_rows, (BATCH, SEQ + 1)).astype(np.int32)
    feed = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    return main, loss, {p.name: g.name for p, g in pgs if g is not None}, \
        exe, scope, feed


@pytest.mark.parametrize("share, amp, tol", [
    (None, False, 5e-5),                                  # the whole model
    (dict(heads_held=2, first_head=4, experts_held=4, first_expert=8,
          vocab_rows=128), False, 5e-5),                  # a share, float32
    (dict(heads_held=4, experts_held=8, vocab_rows=128), True, 8e-2),
])
def test_program_against_the_reference(share, amp, tol):
    cfg = Xing4Config.tiny(remat_ffn=True, **(share or {}))
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp)
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in main.all_parameters()}
    assert params["embed_tokens.weight"].shape == (cfg.vocab_rows, 64)
    assert params["lm_head.weight"].shape == (cfg.vocab_rows, 64)
    assert params["layers.1.mlp.gate"].shape == (64, 16)  # published width
    assert params["layers.1.mlp.w1"].shape == (cfg.experts_held, 64, 32)
    assert params["layers.1.mlp.shared_experts.w1"].shape == (64, 32)
    assert params["layers.0.mlp.w1"].shape == (64, 128)   # the dense layer
    assert params["layers.0.self_attn.q_b_proj"].shape == (
        48, cfg.heads_held * 32)
    assert params["layers.0.self_attn.o_proj"].shape == (
        cfg.heads_held * 16, 64)
    assert params["layers.2.ffn_hc.phi"].shape == (4 * 64, 24)
    want_loss, want = ref.xing4_loss_and_grads(
        params, feed["input_ids"], feed["labels"],
        ref.reference_settings(cfg),
        experts=(cfg.first_expert, cfg.experts_held))
    # every trainable parameter has a gradient; the selection bias has none
    assert set(grad_of) == set(want)
    assert not any(n.endswith("expert_bias") for n in grad_of)
    names = sorted(grad_of) if not amp else list(NAMED)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[loss] + [
        grad_of[n] for n in names] + tokens_per_expert(main)
        + sinkhorn_gaps(main))
    assert abs(float(got[0][0]) - float(want_loss)) < tol * float(want_loss)
    for name, g in zip(names, got[1:1 + len(names)]):
        if name == "layers.0.attn_hc.phi":
            # in front of the first sublayer the streams are copies of one
            # another: H_res X = X whatever H_res, and phi_res's gradient
            # is rounding noise around zero in both
            g, w = g[:, :8], want[name][:, :8]
            assert np.abs(np.asarray(want[name])[:, 8:]).max() < 1e-6
        else:
            w = want[name]
        assert rel(g, w) < tol, name
    rest = got[1 + len(names):]
    counts, gaps = rest[:2], rest[2:]
    assert all(c.shape == (cfg.experts_held,) for c in counts)
    if share is None:  # all experts held: every pick lands somewhere
        assert [int(c.sum()) for c in counts] == [BATCH * SEQ * 2] * 2
    # one gap vector a sublayer; 20 rounds bring every sum within 1e-4 of 1
    assert len(gaps) == 6 and all(g.shape == (4,) for g in gaps)
    assert max(float(g.max()) for g in gaps) < 1e-4


# ---------------------------------------------------------------------------
# the share test
# ---------------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_sublayers():
    """At 8 heads and 16 experts: the attention parts of the four head
    shares add up to the uncut attention, and the routed parts of the four
    expert shares plus the shared expert, counted once, to the uncut
    feed-forward. The replicated parts (the low-rank projections, the
    latent norms, the router, the selection bias) go to every share whole."""
    cfg = Xing4Config.tiny()
    settings = ref.reference_settings(cfg)
    rng = np.random.default_rng(11)
    x = rng.standard_normal((2, 16, 64)).astype(np.float32)

    def w(*shape):
        return (rng.standard_normal(shape) * 0.2).astype(np.float32)

    # ---- attention: heads are columns of W_qb / W_kvb and rows of W_o
    attn = {"q_a_proj": w(64, 48), "q_a_layernorm": 1 + w(48),
            "q_b_proj": w(48, 8 * 32), "kv_a_proj": w(64, 40),
            "kv_a_layernorm": 1 + w(32), "kv_b_proj": w(32, 8 * 40),
            "o_proj": w(8 * 16, 64)}
    whole = np.asarray(ref.mla(jnp.asarray(x), attn, settings))

    def mla_share(first, count):
        held = dict(attn)
        held["q_b_proj"] = attn["q_b_proj"][:, first * 32:(first + count) * 32]
        held["kv_b_proj"] = attn["kv_b_proj"][
            :, first * 40:(first + count) * 40]
        held["o_proj"] = attn["o_proj"][first * 16:(first + count) * 16]
        return run_layer(lambda v: layers.mla(
            v, count, 48, 32, 24, 8, 16, cfg.softmax_scale,
            theta=cfg.rope_theta, inv_freq=cfg.inv_freq, name="a"),
            x, {f"a.{k}": val for k, val in held.items()})

    parts = [mla_share(first, 2) for first in (0, 2, 4, 6)]
    assert rel(sum(parts), whole) < 1e-5
    assert rel(parts[0], whole) > 0.3  # a share is not the whole
    assert rel(mla_share(0, 8), whole) < 1e-5

    # ---- feed-forward: routed experts split, the shared expert whole
    ffn = {"gate": w(64, 16) * 5, "expert_bias": w(16) * 0.1,
           "w1": w(16, 64, 32), "w3": w(16, 64, 32), "w2": w(16, 32, 64),
           "shared_experts.w1": w(64, 32), "shared_experts.w3": w(64, 32),
           "shared_experts.w2": w(32, 64)}
    z = jnp.asarray(x)
    whole = np.asarray(ref.routed_experts(z, ffn, settings, None)
                       + ref.shared_expert(z, ffn))

    def routed_share(first, count):
        held = {k: (val[first:first + count] if k in ("w1", "w3", "w2")
                    else val)
                for k, val in ffn.items() if not k.startswith("shared")}
        return run_layer(lambda v: layers.moe_swiglu(
            v, 16, 32, experts_held=count, first_expert=first, top_k=2,
            norm_topk_prob=True, routed_scaling_factor=2.0, name="m")[0],
            x, {f"m.{k}": val for k, val in held.items()})

    shared = run_layer(
        lambda v: layers.shared_expert(v, 32, name="m.shared_experts"), x,
        {f"m.{k}": val for k, val in ffn.items() if k.startswith("shared")})
    routed = [routed_share(first, 4) for first in (0, 4, 8, 12)]
    assert rel(sum(routed) + shared, whole) < 1e-5
    # counted with every share it would stand four times
    assert rel(sum(routed) + 4 * shared, whole) > 0.1
    assert rel(shared, np.asarray(ref.shared_expert(z, ffn))) < 1e-5


# ---------------------------------------------------------------------------
# training, and what is refused
# ---------------------------------------------------------------------------


def test_a_few_adam_steps_lower_the_loss():
    cfg = Xing4Config.tiny(heads_held=4, experts_held=8, remat_ffn=True,
                           expert_bias_update_rate=0.002)
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp=True)
    bias = np.asarray(scope.find_var("layers.2.mlp.expert_bias"))
    assert bias.any()  # started random, so that s + b selects from step one
    alpha = np.asarray(scope.find_var("layers.0.attn_hc.alpha"))
    np.testing.assert_allclose(alpha, 0.01)
    assert np.asarray(scope.find_var("layers.0.attn_hc.b")).std() > 0.5
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)[0][0]) for _ in range(5)]
    assert all(b < a for a, b in zip(losses, losses[1:])), losses
    # the balancing rule moves the buffer, one rate an entry a step
    moved = np.asarray(scope.find_var("layers.2.mlp.expert_bias"))
    assert 0 < np.abs(moved - bias).max() <= 5 * 0.002 + 1e-6
    assert np.asarray(scope.find_var("embed_tokens.weight")).dtype == (
        np.float32)  # master weights stay float32 under AMP


def test_the_published_settings_and_what_is_refused():
    cfg = Xing4Config()
    assert (cfg.heads_held, cfg.experts_held, cfg.vocab_rows) == (
        32, 64, 131072)
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert cfg.inv_freq.shape == (32,)
    with pytest.raises(ValueError, match="multi-token prediction"):
        Xing4Config(num_nextn_predict_layers=1)
    with pytest.raises(ValueError, match="yarn"):
        Xing4Config(rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(ValueError, match="beyond num_attention_heads"):
        Xing4Config(heads_held=8, first_head=28)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        build_xing4_pretrain_program(
            Xing4Config.tiny(max_position_embeddings=16), 1, 32)
