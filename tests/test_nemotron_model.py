"""The tiny Nemotron-H program (`MEM*E`: two Mamba-2 layers with two heads
a group and four chunks a row, two expert layers of squared-ReLU experts
beside a shared one, one attention layer without a position term) against
the plain float32 reference on seeded weights: loss and every gradient in
float32 (AMP left out), the whole model and a held share of it; the share
test (the routed parts of all the shares plus the shared expert, counted
once, add up to the uncut expert layer); a few optimizer steps under AMP;
what can be fetched beside the loss."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.models import nemotron_h as ref
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.executor import Scope
from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                          build_nemotron_h_pretrain_program,
                                          min_decays, tokens_per_expert)

BATCH, SEQ = 2, 32


def _built(cfg, amp):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard():
        _, _, feeds, loss = build_nemotron_h_pretrain_program(
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


def _reference_loss_and_grads(cfg, params, feed):
    """The benchmark family's reference on the program's own weights: the
    loss and every trainable parameter's gradient, float32 at `highest`."""
    params = {k: jnp.asarray(v) for k, v in params.items()}
    bias = {k: v for k, v in params.items() if k.endswith("expert_bias")}

    def loss_of(trained):
        return ref.reference_loss(
            dataclasses.asdict(cfg), {**bias, **trained}, feed["input_ids"],
            feed["labels"], (cfg.first_expert, cfg.experts_held))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_of)(
            {k: v for k, v in params.items() if k not in bias})


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("share", [
    None,                                                  # the whole model
    dict(experts_held=4, first_expert=8, vocab_rows=128),  # a share
], ids=["whole", "share"])
def test_program_against_the_reference(share):
    cfg = NemotronHConfig.tiny(remat_ffn=True, initializer_range=0.1,
                               **(share or {}))
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp=False)
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in main.all_parameters()}
    assert params["embeddings.weight"].shape == (cfg.vocab_rows, 64)
    assert params["lm_head.weight"].shape == (cfg.vocab_rows, 64)
    assert params["layers.1.mixer.gate"].shape == (64, 16)  # published width
    assert params["layers.1.mixer.w1"].shape == (cfg.experts_held, 64, 48)
    assert params["layers.1.mixer.shared_experts.w2"].shape == (96, 64)
    assert not any(n.endswith(".w3") for n in params)       # two matrices
    assert params["layers.0.mixer.in_proj"].shape == (64, 64 + 64 + 128 + 8)
    assert params["layers.3.mixer.k_proj.weight"].shape == (64, 2 * 16)
    # one norm a layer and no second one
    assert sum(n.endswith(".norm.weight") and "mixer" not in n
               for n in params) == 5
    # rescale_prenorm_residual: the Mamba mixers' out_proj starts smaller by
    # the root of the depth
    assert (params["layers.0.mixer.out_proj"].std()
            < 0.6 * params["layers.0.mixer.in_proj"].std())
    want_loss, want = _reference_loss_and_grads(cfg, params, feed)
    # every trainable parameter has a gradient; the selection bias has none
    assert set(grad_of) == set(want)
    assert not any(n.endswith("expert_bias") for n in grad_of)
    names = sorted(grad_of)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[loss] + [
        grad_of[n] for n in names] + tokens_per_expert(main)
        + min_decays(main))
    assert abs(float(got[0][0]) - float(want_loss)) < 5e-5 * float(want_loss)
    for name, g in zip(names, got[1:1 + len(names)]):
        assert _rel(g, want[name]) < 5e-5, name
    counts, decays = got[1 + len(names):][:2], got[1 + len(names):][2:]
    assert all(c.shape == (cfg.experts_held,) for c in counts)
    if share is None:  # all experts held: every pick lands somewhere
        assert [int(c.sum()) for c in counts] == [BATCH * SEQ * 2] * 2
    # one vector of decays a Mamba-2 layer, each a probability-like number
    assert len(decays) == 2 and all(d.shape == (8,) for d in decays)
    assert all(0.0 < float(d.min()) and float(d.max()) < 1.0 for d in decays)


# ---------------------------------------------------------------------------
# the share test
# ---------------------------------------------------------------------------


def _run_layer(build, x, weights):
    """out = build(x) with the named parameters set to `weights`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        v = layers.data("x", shape=list(x.shape), dtype="float32",
                        append_batch_size=False)
        out = build(v)
    exe, scope = fluid.Executor(), Scope()
    exe.run(startup, scope=scope)
    names = {p.name for p in main.all_parameters()}
    assert names == set(weights), names ^ set(weights)
    for name, w in weights.items():
        assert scope.find_var(name).shape == w.shape, name
        scope.set_var(name, jnp.asarray(w))
    return exe.run(main, feed={"x": x}, fetch_list=[out], scope=scope)[0]


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """At 16 experts: the routed parts of the four shares plus the shared
    expert, counted once, add up to the uncut expert layer. The replicated
    parts (the router, the selection bias, the shared expert) go to every
    share whole."""
    cfg = NemotronHConfig.tiny()
    settings = dataclasses.asdict(cfg)
    rng = np.random.default_rng(11)
    x = rng.standard_normal((2, 16, 64)).astype(np.float32)

    def w(*shape):
        return (rng.standard_normal(shape) * 0.2).astype(np.float32)

    ffn = {"gate": w(64, 16) * 5, "expert_bias": w(16) * 0.1,
           "w1": w(16, 64, 48), "w2": w(16, 48, 64),
           "shared_experts.w1": w(64, 96), "shared_experts.w2": w(96, 64)}
    z = jnp.asarray(x)
    whole = np.asarray(ref.routed_experts(z, ffn, settings, None)
                       + ref.shared_expert(z, ffn))

    def routed_share(first, count):
        held = {k: (val[first:first + count] if k in ("w1", "w2") else val)
                for k, val in ffn.items() if not k.startswith("shared")}
        return _run_layer(lambda v: layers.moe_swiglu(
            v, 16, 48, experts_held=count, first_expert=first, top_k=2,
            norm_topk_prob=True, routed_scaling_factor=2.5, name="m",
            activation="relu2")[0],
            x, {f"m.{k}": val for k, val in held.items()})

    shared = _run_layer(
        lambda v: layers.shared_expert(v, 96, name="m.shared_experts",
                                       activation="relu2"), x,
        {f"m.{k}": val for k, val in ffn.items() if k.startswith("shared")})
    routed = [routed_share(first, 4) for first in (0, 4, 8, 12)]
    assert _rel(sum(routed) + shared, whole) < 1e-5
    assert _rel(routed[0] + shared, whole) > 0.1  # a share is not the whole
    # counted with every share it would stand four times
    assert _rel(sum(routed) + 4 * shared, whole) > 0.1
    assert _rel(shared, np.asarray(ref.shared_expert(z, ffn))) < 1e-5
    assert _rel(routed_share(0, 16) + shared, whole) < 1e-5


# ---------------------------------------------------------------------------
# training, and what is refused
# ---------------------------------------------------------------------------


def test_a_few_adam_steps_under_amp_lower_the_loss():
    cfg = NemotronHConfig.tiny(experts_held=8, vocab_rows=128, remat_ffn=True,
                               initializer_range=0.1)
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp=True)
    bias = np.asarray(scope.find_var("layers.1.mixer.expert_bias"))
    assert bias.any()  # started random, so that s + b selects from step one
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)[0][0]) for _ in range(5)]
    assert all(b < a for a, b in zip(losses, losses[1:])), losses
    assert np.asarray(scope.find_var("embeddings.weight")).dtype == (
        np.float32)  # master weights stay float32 under AMP
    # the two drawn vectors follow the program's seed and the layer's name
    a0, a2 = (np.asarray(scope.find_var(f"layers.{i}.mixer.A_log"))
              for i in (0, 2))
    assert not np.allclose(a0, a2)


def test_the_balancing_rule_moves_the_selection_bias():
    cfg = NemotronHConfig.tiny(experts_held=8, expert_bias_update_rate=0.002)
    main, loss, _, exe, scope, feed = _built(cfg, amp=True)
    bias = np.asarray(scope.find_var("layers.4.mixer.expert_bias"))
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    moved = np.asarray(scope.find_var("layers.4.mixer.expert_bias"))
    assert 0 < np.abs(moved - bias).max() <= 3 * 0.002 + 1e-6


def test_the_published_settings_and_what_is_refused():
    cfg = NemotronHConfig()
    assert (cfg.experts_held, cfg.vocab_rows, cfg.residual_scale_layers) == (
        128, 131072, 52)
    pattern = cfg.hybrid_override_pattern
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        23, 23, 6)
    assert pattern[:9] == "MEMEM*EME"
    with pytest.raises(ValueError, match="letters for"):
        NemotronHConfig(num_hidden_layers=9)
    with pytest.raises(ValueError, match="are not built"):
        NemotronHConfig.tiny(hybrid_override_pattern="MEMXE")
    with pytest.raises(ValueError, match="max_position_embeddings"):
        build_nemotron_h_pretrain_program(
            NemotronHConfig.tiny(max_position_embeddings=16), 1, 32)
    # the family's dense two-matrix MLP is not built: the pattern has none
    with pytest.raises(ValueError, match="are not built"):
        NemotronHConfig.tiny(hybrid_override_pattern="M-M*-")
