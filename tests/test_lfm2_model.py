"""The five-layer LFM2-MoE program (one dense layer, then attention, conv,
conv, conv over experts) against the plain float32 reference on seeded
weights: loss and gradients in float32 and under bf16 AMP, the whole model
and a held share of it, and a few optimizer steps."""
import dataclasses

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.fluid.executor import Scope
from paddle_tpu.models import lfm2_moe_reference as ref
from paddle_tpu.models.lfm2_moe import (Lfm2MoeConfig,
                                        build_lfm2_moe_pretrain_program,
                                        tokens_per_expert)

BATCH, SEQ = 2, 32
# what `benchmark/models/lfm2_moe.py:check_parameters` names, at the tiny
# model's layer indices
NAMED = ("embed_tokens.weight", "layers.1.self_attn.q_proj.weight",
         "layers.0.conv.in_proj", "layers.1.feed_forward.w1",
         "layers.4.feed_forward.w1", "layers.1.feed_forward.gate")


def _built(cfg, amp):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard():
        _, _, feeds, loss = build_lfm2_moe_pretrain_program(
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


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("held, amp, tol", [
    (None, False, 2e-5),       # the whole model, float32
    ((2, 4), False, 2e-5),     # experts 2-5 of 8, float32
    ((0, 4), True, 5e-2),      # a share under bf16 AMP
])
def test_program_against_the_reference(held, amp, tol):
    cfg = dataclasses.replace(Lfm2MoeConfig.tiny(), remat_ffn=True)
    if held:
        cfg = dataclasses.replace(cfg, first_expert=held[0],
                                  experts_held=held[1], vocab_rows=128)
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp)
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in main.all_parameters()}
    assert params["embed_tokens.weight"].shape == (cfg.vocab_rows, 64)
    assert params["layers.1.feed_forward.gate"].shape == (64, 8)
    assert params["layers.1.feed_forward.w1"].shape == (cfg.experts_held,
                                                        64, 64)
    assert params["layers.1.self_attn.k_proj.weight"].shape == (64, 32)
    want_loss, want = ref.lfm2_moe_loss_and_grads(
        params, feed["input_ids"], feed["labels"],
        ref.reference_settings(cfg), held=held)
    # every trainable parameter has a gradient; the selection bias has none
    assert set(grad_of) == set(want)
    assert not any(n.endswith("expert_bias") for n in grad_of)
    names = sorted(grad_of) if not amp else list(NAMED)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[loss] + [
        grad_of[n] for n in names] + tokens_per_expert(main))
    assert abs(float(got[0][0]) - float(want_loss)) < tol * float(want_loss)
    for name, g in zip(names, got[1:1 + len(names)]):
        assert _rel(g, want[name]) < tol, name
    counts = got[1 + len(names):]
    assert len(counts) == 4 and all(c.shape == (cfg.experts_held,)
                                    for c in counts)
    if held is None:  # all experts held: every pick lands somewhere
        assert [int(c.sum()) for c in counts] == [BATCH * SEQ * 2] * 4


def test_a_few_adam_steps_lower_the_loss_and_leave_the_bias_alone():
    cfg = dataclasses.replace(Lfm2MoeConfig.tiny(), experts_held=4,
                              remat_ffn=True)
    main, loss, _, exe, scope, feed = _built(cfg, amp=True)
    bias = np.asarray(scope.find_var("layers.2.feed_forward.expert_bias"))
    assert bias.any()  # started random, so that s + b selects from step one
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)[0][0]) for _ in range(5)]
    assert all(b < a for a, b in zip(losses, losses[1:])), losses
    np.testing.assert_array_equal(
        bias, np.asarray(scope.find_var("layers.2.feed_forward.expert_bias")))
    assert np.asarray(scope.find_var("embed_tokens.weight")).dtype == (
        np.float32)  # master weights stay float32 under AMP
    # with the balancing rule on, the program moves the buffer itself, by
    # one rate an entry a step, and the loss has no say in it
    cfg = dataclasses.replace(cfg, expert_bias_update_rate=0.002)
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp=True)
    assert not any(n.endswith("expert_bias") for n in grad_of)
    bias = np.asarray(scope.find_var("layers.2.feed_forward.expert_bias"))
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    moved = np.asarray(scope.find_var("layers.2.feed_forward.expert_bias"))
    np.testing.assert_allclose(np.abs(moved - bias).max(), 0.002, rtol=1e-4)


def test_remat_is_the_same_arithmetic():
    outs = []
    for remat in (False, True):
        cfg = dataclasses.replace(Lfm2MoeConfig.tiny(), experts_held=4,
                                  remat_ffn=remat)
        main, loss, grad_of, exe, scope, feed = _built(cfg, amp=False)
        outs.append(exe.run(main, feed=feed, scope=scope, fetch_list=[
            loss, grad_of["layers.4.feed_forward.w1"],
            grad_of["layers.0.feed_forward.w1"]]))
    for a, b in zip(*outs):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)


def test_the_released_layer_pattern_and_what_is_refused():
    cfg = Lfm2MoeConfig()
    assert cfg.layer_types.count("full_attention") == 6
    assert cfg.layer_types[:7] == ["conv", "conv", "full_attention", "conv",
                                   "conv", "conv", "full_attention"]
    assert (cfg.head_dim, cfg.experts_held, cfg.vocab_rows) == (64, 32, 65536)
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(num_hidden_layers=3, layer_types=["conv"])
    with pytest.raises(ValueError, match="not built"):
        Lfm2MoeConfig(conv_bias=True)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        build_lfm2_moe_pretrain_program(Lfm2MoeConfig.tiny(), 1, 256)
