"""The tiny GLM-4.7-Flash program (one dense layer, two expert layers and
the multi-token-prediction module, latent attention on heads of equal
widths, a plain residual) against the plain float32 reference of
`benchmark/models/glm4_moe_lite.py` on seeded weights: loss, both of its
parts and the gradients, in float32 and under bf16 AMP, the whole model
and a held share of it; every fault the reference can be given fails the
limits the unfaulted program meets; the gradients of the embedding table
and of the head are the sums over their two uses; the shares add up to
the uncut layer, in the trunk and in the module; what the batch holds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.models import glm4_moe_lite as ref
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.executor import Scope
from paddle_tpu.models.glm4_moe_lite import (
    Glm4MoeLiteConfig, build_glm4_moe_lite_pretrain_program, decoder_layer,
    part_losses, tokens_per_expert)
from model_checks import reference_value_and_grad, rel, run_layer

BATCH, SEQ = 2, 32
TRAFFIC = {"seq_len": SEQ}
SHARE = dict(experts_held=4, first_expert=8, vocab_rows=128)


def _config(cfg: Glm4MoeLiteConfig) -> dict:
    """The configuration file a tiny `cfg` would be, as far as the
    reference reads it."""
    keys = ref.PUBLISHED_KEYS + ref.SHARE_KEYS + ("mtp_loss_weight",)
    return {k: getattr(cfg, k) for k in keys}


def _built(cfg, amp, rate=1e-3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard():
        _, _, feeds, loss = build_glm4_moe_lite_pretrain_program(
            cfg, BATCH, SEQ, main_program=main, startup_program=startup)
        with fluid.program_guard(main, startup):
            opt = fluid.optimizer.AdamOptimizer(learning_rate=rate)
            if amp:
                opt = mixed_precision.decorate(opt, use_bf16=True)
            _, pgs = opt.minimize(loss, startup_program=startup)
    exe, scope = fluid.Executor(), Scope()
    exe.run(startup, scope=scope)
    feed = ref.make_batch(_config(cfg), TRAFFIC, BATCH,
                          np.random.default_rng(3))
    assert feeds == list(feed)
    return main, loss, {p.name: g.name for p, g in pgs if g is not None}, \
        exe, scope, feed


def _params(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name))
            for p in main.all_parameters()}


def _reference(cfg, params, feed, **kw):
    """((L, L_main, L_mtp), gradients of every trainable parameter)."""
    config = _config(cfg)
    params = {k: jnp.asarray(v) for k, v in params.items()}
    wrt = {k: v for k, v in params.items() if not k.endswith("expert_bias")}
    rest = {k: v for k, v in params.items() if k not in wrt}

    def loss_of(wrt):
        losses = ref.reference_loss(
            config, {**rest, **wrt}, {k: jnp.asarray(v)
                                      for k, v in feed.items()},
            (cfg.first_expert, cfg.experts_held), **kw)
        return losses[0], losses

    (_, losses), grads = reference_value_and_grad(loss_of, wrt, has_aux=True)
    return [float(v) for v in losses], grads


def test_the_batch_holds_the_token_after_the_label():
    feed = ref.make_batch({"vocab_rows": 128}, TRAFFIC, BATCH,
                          np.random.default_rng(5))
    assert list(feed) == ["input_ids", "labels", "labels_next"]
    assert all(v.shape == (BATCH, SEQ) and v.dtype == np.int32
               for v in feed.values())
    # one stream of S + 2 tokens a row: each feed is the one before it,
    # a position on
    np.testing.assert_array_equal(feed["labels"][:, :-1],
                                  feed["input_ids"][:, 1:])
    np.testing.assert_array_equal(feed["labels_next"][:, :-1],
                                  feed["labels"][:, 1:])
    assert (feed["labels_next"] != feed["labels"]).mean() > 0.9


@pytest.mark.parametrize("share, amp, tol", [
    (None, False, 5e-5),           # the whole model
    (SHARE, False, 5e-5),          # a share, float32
    (dict(experts_held=8, vocab_rows=128), True, 8e-2),
], ids=["whole", "share", "share_amp"])
def test_program_against_the_reference(share, amp, tol):
    cfg = Glm4MoeLiteConfig.tiny(remat_ffn=True, **(share or {}))
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp)
    # one multi-token-prediction module is built into the program: its
    # projection W_eh is read by one forward op (the product, or under AMP
    # the cast in front of it)
    assert len([op for op in main.global_block().ops
                if "mtp.eh_proj.weight" in op.input_names()
                and op.role == "forward"]) == 1
    params = _params(main, scope)
    assert params["embed_tokens.weight"].shape == (cfg.vocab_rows, 64)
    assert params["lm_head.weight"].shape == (cfg.vocab_rows, 64)
    assert params["layers.1.mlp.gate"].shape == (64, 16)  # published width
    assert params["layers.1.mlp.w1"].shape == (cfg.experts_held, 64, 32)
    assert params["mtp.mlp.w1"].shape == (cfg.experts_held, 64, 32)
    assert params["mtp.mlp.shared_experts.w1"].shape == (64, 32)
    assert params["layers.0.mlp.w1"].shape == (64, 128)   # the dense layer
    assert params["mtp.eh_proj.weight"].shape == (128, 64)
    assert params["mtp.self_attn.q_b_proj"].shape == (48, 4 * 32)
    assert params["mtp.self_attn.o_proj"].shape == (4 * 32, 64)
    want_losses, want = _reference(cfg, params, feed)
    # every trainable parameter has a gradient; the selection bias has none
    assert set(grad_of) == set(want)
    names = sorted(grad_of)
    parts = part_losses(main)
    assert list(parts) == ["main_loss", "mtp_loss"]
    got = exe.run(main, feed=feed, scope=scope, fetch_list=[
        loss, parts["main_loss"], parts["mtp_loss"]] + [
        grad_of[n] for n in names] + tokens_per_expert(main))
    for g, w in zip(got[:3], want_losses):
        assert abs(float(g[0]) - w) < tol * w
    assert want_losses[0] == pytest.approx(
        want_losses[1] + 0.3 * want_losses[2], rel=1e-6)
    for name, g in zip(names, got[3:3 + len(names)]):
        assert rel(g, want[name]) < tol, name
    counts = got[3 + len(names):]
    assert len(counts) == 3  # two trunk expert layers, the module's last
    assert all(c.shape == (cfg.experts_held,) for c in counts)
    if share is None:  # all experts held: every pick lands somewhere
        assert [int(c.sum()) for c in counts] == [BATCH * SEQ * 2] * 3


@pytest.fixture(scope="module")
def checked():
    """The bf16-AMP program's readings at the labels the benchmark's check
    names, and what it was compared on."""
    cfg = Glm4MoeLiteConfig.tiny(
        remat_ffn=True, initializer_range=0.1, experts_held=8,
        vocab_rows=128)
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp=True)
    params = _params(main, scope)
    wanted = ref.check_parameters(_config(cfg))
    got = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[loss] + [grad_of[n] for _, n, _ in wanted])
    return cfg, params, feed, wanted, float(got[0][0]), got[1:]


def _readings(checked, **kw):
    cfg, params, feed, wanted, loss, grads = checked
    losses, want = _reference(cfg, params, feed, **kw)
    return abs(loss - losses[0]) / losses[0], {
        label: rel(g, want[name])
        for (label, name, _), g in zip(wanted, grads)}


# the limits the unfaulted program meets here (64 tokens, an expert holding
# some sixteen rows: it reads embedding 10.5 %, the routed W1 18.3 %, the
# router 14.5 %, 4.8-9.1 % elsewhere), and what each fault has to fail at
# least one of. The narrowest is the module's look-up in a table of its
# own: only E's gradient sees it, 18.2 % against the limit's 14
LIMITS = {"embedding": 0.14, "lm_head": 0.1, "mtp.eh_proj": 0.1,
          "mtp.kv_b_proj": 0.1, "first.kv_b_proj": 0.14,
          "first_moe.w1": 0.25, "first_moe.shared_w1": 0.1,
          "first_moe.gate": 0.2}


def test_the_program_meets_the_limits(checked):
    loss_err, errors = _readings(checked)
    assert loss_err < 1e-2
    assert all(errors[k] < v for k, v in LIMITS.items()), errors


@pytest.mark.parametrize("fault", ref.FAULTS + ("e4m3",))
def test_each_fault_fails_the_limits(checked, fault):
    kw = (dict(products_in=jnp.float8_e4m3fn) if fault == "e4m3"
          else dict(faults=(fault,)))
    _, errors = _readings(checked, **kw)
    assert any(errors[k] > 1.25 * v for k, v in LIMITS.items()), errors


def test_the_reference_in_bf16_reads_the_programs_level(checked):
    _, errors = _readings(checked, products_in=jnp.bfloat16)
    assert all(errors[k] < v for k, v in LIMITS.items()), errors


def test_unknown_faults_are_refused():
    with pytest.raises(ValueError, match="unknown faults"):
        ref.reference_loss({}, {}, {}, None, faults=("no_such",))


# ---------------------------------------------------------------------------
# two uses of one parameter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name, without_the_second_use", [
    ("embed_tokens.weight", "mtp_own_table"),
    ("lm_head.weight", "no_mtp_loss")])
def test_a_shared_parameters_gradient_is_the_sum_of_its_uses(
        name, without_the_second_use):
    """E is looked up by the trunk and by the module, W_head scores both
    hidden states: each is ONE parameter, initialised once, and its
    gradient is the sum of one partial a use. A `backward` that kept one
    use would give the reference's gradient with the other use cut off:
    the module embedding from a table of its own, or lambda = 0."""
    cfg = Glm4MoeLiteConfig.tiny(**SHARE)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard():
        _, _, _, loss = build_glm4_moe_lite_pretrain_program(
            cfg, BATCH, SEQ, main_program=main, startup_program=startup)
    block = main.global_block()
    uses = [op for op in block.ops if name in op.input_names()]
    assert len(uses) == 2 and {op.scope[:1] for op in uses} == {
        ("mtp",), ("lm_head",) if name.startswith("lm_head") else ()}
    made = [op for op in startup.global_block().ops
            if name in op.output_names()]
    assert len(made) == 1  # and not once a use
    with fluid.program_guard(main, startup):
        pgs = fluid.append_backward(loss)
    grad = {p.name: g for p, g in pgs}[name]
    sums = [op for op in block.ops
            if op.type == "sum" and grad.name in op.output_names()]
    assert len(sums) == 1 and len(sums[0].input("X")) == 2
    # the sum is written over the partial of the use met first (walking
    # backwards: the module's); the trunk's use keeps a name of its own
    trunk_part = [n for n in sums[0].input("X") if n != grad.name]
    assert len(trunk_part) == 1
    exe, scope = fluid.Executor(), Scope()
    exe.run(startup, scope=scope)
    feed = ref.make_batch(_config(cfg), TRAFFIC, BATCH,
                          np.random.default_rng(3))
    both, trunk = exe.run(main, feed=feed, scope=scope,
                          fetch_list=[grad.name] + trunk_part)
    params = _params(main, scope)
    _, want = _reference(cfg, params, feed)
    _, one_use = _reference(cfg, params, feed,
                            faults=(without_the_second_use,))
    assert rel(both, want[name]) < 5e-5
    assert rel(trunk, one_use[name]) < 5e-5
    assert rel(both, one_use[name]) > 0.05
    assert rel(both - trunk, want[name] - one_use[name]) < 5e-4


def test_a_named_parameter_asked_for_again_must_agree():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = layers.data("ids", shape=[2, 4], dtype="int32",
                          append_batch_size=False)
        first = layers.embedding(ids, size=[16, 8], param_attr="table")
        again = layers.embedding(ids, size=[16, 8], param_attr="table")
        assert first.name != again.name
        assert [p.name for p in main.all_parameters()] == ["table"]
        with pytest.raises(ValueError, match="asked for again"):
            layers.embedding(ids, size=[16, 4], param_attr="table")
        # another startup program has to initialise it too
        with fluid.program_guard(main, fluid.Program()):
            layers.embedding(ids, size=[16, 8], param_attr="table")
            assert "table" in fluid.default_startup_program(
                ).global_block().vars


# ---------------------------------------------------------------------------
# the share test
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["trunk", "mtp"])
def test_the_shares_add_up_to_the_uncut_layer(where):
    """At 16 experts: the routed parts of the four expert shares, with the
    residual, the attention and the shared expert (and, in the module, the
    combine) counted once, give the uncut reference's layer output. Every
    share's program computes x + attention + routed share + shared expert:
    what all compute alike is taken off three of the four."""
    cfg = Glm4MoeLiteConfig.tiny()
    config = _config(cfg)
    rng = np.random.default_rng(11)
    x = rng.standard_normal((2, 16, 64)).astype(np.float32)

    def w(*shape):
        return (rng.standard_normal(shape) * 0.2).astype(np.float32)

    name = "layers.1" if where == "trunk" else "mtp"
    layer = {
        "input_layernorm.weight": 1 + w(64),
        "post_attention_layernorm.weight": 1 + w(64),
        "self_attn.q_a_proj": w(64, 48), "self_attn.q_a_layernorm": 1 + w(48),
        "self_attn.q_b_proj": w(48, 4 * 32), "self_attn.kv_a_proj": w(64, 40),
        "self_attn.kv_a_layernorm": 1 + w(32),
        "self_attn.kv_b_proj": w(32, 4 * 56), "self_attn.o_proj": w(4 * 32, 64),
        "mlp.gate": w(64, 16) * 5, "mlp.expert_bias": w(16) * 0.1,
        "mlp.w1": w(16, 64, 32), "mlp.w3": w(16, 64, 32),
        "mlp.w2": w(16, 32, 64), "mlp.shared_experts.w1": w(64, 32),
        "mlp.shared_experts.w3": w(64, 32), "mlp.shared_experts.w2": w(32, 64)}
    if where == "mtp":
        # the module's block reads the combine's output: computed once,
        # from the reference, and handed to every share alike
        table = w(256, 64)
        ids = rng.integers(0, 256, (2, 16))
        x = np.asarray(ref.mtp_combine(
            config, {"embed_tokens.weight": jnp.asarray(table),
                     "mtp.enorm.weight": 1 + w(64),
                     "mtp.hnorm.weight": 1 + w(64),
                     "mtp.eh_proj.weight": w(128, 64)}, jnp.asarray(x), ids))
    whole = np.asarray(ref.block(
        config, jnp.asarray(x), {k: jnp.asarray(v) for k, v in layer.items()},
        False, None))

    def share(first, count):
        held = {k: (v[first:first + count]
                    if k in ("mlp.w1", "mlp.w3", "mlp.w2") else v)
                for k, v in layer.items()}
        part = Glm4MoeLiteConfig.tiny(experts_held=count, first_expert=first)
        return run_layer(
            lambda v: decoder_layer(part, v, 1, name, is_test=True), x,
            {f"{name}.{k}": v for k, v in held.items()})

    parts = [share(first, 4) for first in (0, 4, 8, 12)]
    # x + attention + shared expert: a layer that holds no routed pick's
    # expert... is what the four have in common; taken from the reference
    z = jnp.asarray(x)
    attended = z + ref.mla(config, ref.rms(
        z, layer["input_layernorm.weight"], cfg.rms_norm_eps), {
            k[len("self_attn."):]: jnp.asarray(v) for k, v in layer.items()
            if k.startswith("self_attn.")})
    common = np.asarray(attended + ref.shared_expert(
        ref.rms(attended, layer["post_attention_layernorm.weight"],
                cfg.rms_norm_eps),
        {k[len("mlp."):]: jnp.asarray(v) for k, v in layer.items()}))
    assert rel(sum(parts) - 3 * common, whole) < 1e-5
    # counted with every share, what is common would stand four times
    assert rel(sum(parts), whole) > 0.5
    assert rel(parts[0], whole) > 0.05  # a share is not the whole
    assert rel(share(0, 16), whole) < 1e-5


# ---------------------------------------------------------------------------
# training, and what is refused
# ---------------------------------------------------------------------------


def test_a_few_adam_steps_lower_both_losses():
    cfg = Glm4MoeLiteConfig.tiny(experts_held=8, remat_ffn=True,
                                 expert_bias_update_rate=0.002)
    main, loss, grad_of, exe, scope, feed = _built(cfg, amp=True)
    bias = np.asarray(scope.find_var("mtp.mlp.expert_bias"))
    assert bias.any()  # started random, so that s + b selects from step one
    parts = part_losses(main)
    losses = np.array([[float(v[0]) for v in exe.run(
        main, feed=feed, scope=scope,
        fetch_list=[loss, parts["main_loss"], parts["mtp_loss"]])]
        for _ in range(5)])
    assert (np.diff(losses, axis=0) < 0).all(), losses
    np.testing.assert_allclose(losses[:, 0],
                               losses[:, 1] + 0.3 * losses[:, 2], rtol=1e-5)
    moved = np.asarray(scope.find_var("mtp.mlp.expert_bias"))
    assert 0 < np.abs(moved - bias).max() <= 5 * 0.002 + 1e-6


def test_without_the_module_the_program_is_the_trunk():
    cfg = Glm4MoeLiteConfig.tiny(num_nextn_predict_layers=0, **SHARE)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        _, _, feeds, _ = build_glm4_moe_lite_pretrain_program(
            cfg, BATCH, SEQ, main_program=main, startup_program=startup)
    assert feeds == ["input_ids", "labels"]
    assert list(part_losses(main)) == ["main_loss"]
    assert not any(p.name.startswith("mtp.") for p in main.all_parameters())
    assert not any("mtp" in op.scope for op in main.global_block().ops)


def test_the_published_settings_and_what_is_refused():
    cfg = Glm4MoeLiteConfig()
    assert (cfg.heads_held, cfg.experts_held, cfg.vocab_rows) == (
        20, 64, 154880)
    assert cfg.softmax_scale == 1 / 16 and cfg.inv_freq is None
    assert cfg.num_nextn_predict_layers == 1
    with pytest.raises(ValueError, match="depth 1"):
        Glm4MoeLiteConfig(num_nextn_predict_layers=2)
    with pytest.raises(ValueError, match="rope_scaling"):
        Glm4MoeLiteConfig(rope_scaling={"type": "yarn", "factor": 2})
    with pytest.raises(ValueError, match="max_position_embeddings"):
        build_glm4_moe_lite_pretrain_program(
            Glm4MoeLiteConfig.tiny(max_position_embeddings=16), 1, 32)
