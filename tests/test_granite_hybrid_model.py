"""The tiny Granite-4.0-H program (mamba, attention, mamba: one group of B
and C over eight Mamba-2 heads and four chunks a row, one NoPE attention
layer at its own scale, a dense SwiGLU MLP in every layer, residuals scaled
by 0.22, a tied head) against the plain float32 reference on seeded
weights: loss and every gradient in float32 (AMP left out); the reference
under each fault away from the program; the tied table's gradient the sum
of both of its uses; a few optimizer steps under AMP; what is refused."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.models import granitemoehybrid as ref
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.fluid.executor import Scope
from model_checks import reference_value_and_grad, rel
from paddle_tpu.models.granite_hybrid import (
    ATTENTION, MAMBA, GraniteHybridConfig,
    build_granite_hybrid_pretrain_program)

BATCH, SEQ = 2, 32


def _built(cfg, amp):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard():
        _, _, feeds, loss = build_granite_hybrid_pretrain_program(
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


def _settings(cfg):
    """The configuration as the family file reads it."""
    import dataclasses

    return dict(dataclasses.asdict(cfg), mamba_expand=2)


def _reference(cfg, params, feed, **how):
    """The benchmark family's reference on the program's own weights: the
    loss and every parameter's gradient, float32 at `highest`."""
    def loss_of(p):
        return ref.reference_loss(_settings(cfg), p, feed["input_ids"],
                                  feed["labels"], **how)

    return reference_value_and_grad(loss_of, params)


@pytest.fixture(scope="module")
def float32_run():
    """The program in float32 and the reference on its weights."""
    cfg = GraniteHybridConfig.tiny(remat_ffn=True, initializer_range=0.1,
                                   vocab_rows=128)
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp=False)
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in main.all_parameters()}
    names = sorted(grad_of)
    got = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[loss] + [grad_of[n] for n in names])
    return cfg, params, feed, float(got[0][0]), dict(zip(names, got[1:]))


def test_program_against_the_reference(float32_run):
    cfg, params, feed, loss, grads = float32_run
    # one table, no head of its own; two norms a layer; one group of B, C
    assert params["embed_tokens.weight"].shape == (128, 64)
    assert not any("lm_head" in n for n in params)
    assert sum(n.endswith("layernorm.weight") for n in params) == 2 * 3
    assert params["layers.0.mamba.in_proj"].shape == (64, 128 + 128 + 32 + 8)
    assert params["layers.0.mamba.norm.weight"].shape == (128,)
    assert params["layers.1.self_attn.k_proj.weight"].shape == (64, 2 * 16)
    assert params["layers.1.shared_mlp.w1"].shape == (64, 128)
    assert params["layers.1.shared_mlp.w2"].shape == (128, 64)
    assert not any(n.startswith("layers.1.mamba") for n in params)
    want_loss, want = _reference(cfg, params, feed)
    assert set(grads) == set(want)
    assert abs(loss - float(want_loss)) < 5e-5 * float(want_loss)
    for name, g in grads.items():
        assert rel(g, want[name]) < 5e-5, name


def test_each_fault_is_away_from_the_program(float32_run):
    """Every term the family can break moves the loss or a checked
    gradient far beyond the float32 agreement above; the unknown fault is
    refused by name."""
    cfg, params, feed, loss, grads = float32_run
    settings = _settings(cfg)
    checked = ref.check_parameters(settings)
    assert {label for label, _, _ in checked} == {
        "embedding", "mamba.A_log", "mamba.dt_bias", "mamba.conv1d",
        "mamba.in_proj", "mamba.norm", "attention.k_proj", "mlp.w_gate",
        "mlp.w_o"}
    assert len(ref.FAULTS) == 7
    for fault in ref.FAULTS:
        other_loss, other = _reference(cfg, params, feed, faults=(fault,))
        worst = max(rel(other[name], grads[name]) for _, name, _ in checked)
        assert max(worst, abs(float(other_loss) - loss) / loss) > 0.05, fault
    with pytest.raises(ValueError, match="unknown faults"):
        ref.reference_loss(settings, params, feed["input_ids"],
                           feed["labels"], faults=("no_such",))


def test_the_tied_table_takes_the_gradient_of_both_uses(float32_run):
    """The table's gradient is the look-up's part plus the head's: the
    reference with one use held constant (`stop_gradient`) gives each part,
    and the program's gradient is their sum and neither alone."""
    cfg, params, feed, _, grads = float32_run
    params = {k: jnp.asarray(v) for k, v in params.items()}
    table = params["embed_tokens.weight"]
    ids, labels = feed["input_ids"], feed["labels"]
    hold = jax.lax.stop_gradient

    def part(through_lookup):
        def loss_of(t):
            rows, head = (t, hold(t)) if through_lookup else (hold(t), t)
            return ref.loss_of_rows(_settings(cfg), params, rows[ids], head,
                                    labels)

        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.grad(loss_of)(table))

    lookup, head = part(True), part(False)
    got = grads["embed_tokens.weight"]
    assert rel(lookup + head, got) < 5e-5
    assert rel(head, got) > 0.01 and rel(lookup, got) > 0.01


def test_a_few_adam_steps_under_amp_lower_the_loss():
    cfg = GraniteHybridConfig.tiny(vocab_rows=128, remat_ffn=True,
                                   initializer_range=0.1)
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp=True)
    assert "embed_tokens.weight" in grad_of  # one table, one gradient
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)[0][0]) for _ in range(5)]
    assert all(b < a for a, b in zip(losses, losses[1:])), losses
    assert np.asarray(scope.find_var("embed_tokens.weight")).dtype == (
        np.float32)  # master weights stay float32 under AMP


def test_the_published_settings_and_what_is_refused():
    cfg = GraniteHybridConfig()
    assert (cfg.vocab_rows, cfg.head_dim, cfg.mamba_n_groups,
            cfg.mamba_chunk_size) == (100352, 64, 1, 256)
    kinds = cfg.layer_types
    assert (kinds.count(MAMBA), kinds.count(ATTENTION)) == (36, 4)
    assert [i for i, k in enumerate(kinds) if k == ATTENTION] == [
        5, 15, 25, 35]
    with pytest.raises(ValueError, match="40 entries for 10 layers"):
        GraniteHybridConfig(num_hidden_layers=10)
    with pytest.raises(ValueError, match="are not built"):
        GraniteHybridConfig.tiny(layer_types=[MAMBA, "sliding", MAMBA])
    with pytest.raises(ValueError, match="no multiple of the KV heads"):
        GraniteHybridConfig.tiny(num_key_value_heads=3)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        build_granite_hybrid_pretrain_program(
            GraniteHybridConfig.tiny(max_position_embeddings=16), 1, 32)
