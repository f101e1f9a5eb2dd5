"""What Xing4.0 forced of the ops, through Program -> append_backward ->
Executor against plain numpy / jnp compositions on seeded values: the
attention op with value heads of another width than its query / key heads
and a given softmax scale (the composition and, in interpret mode, the
padded flash calls under their own names), the rotary part at a frequency
table given by the model (YaRN's, by hand), the three mappings and the
mixing of manifold-constrained hyper-connections with Sinkhorn, the shared
expert, and the whole `mla` sublayer."""
import math
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.models import xing4_reference as ref
from paddle_tpu.models.xing4 import Xing4Config, yarn_inv_freq
from paddle_tpu.ops import attention, latent_ops
from paddle_tpu.telemetry import get_registry
from test_lfm2_ops import INIT, _assert_close, _ref_grads, _rel, _run

YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096}


# ---------------------------------------------------------------------------
# the attention op: unequal widths, a given scale
# ---------------------------------------------------------------------------


def _attention_ref(nh, dqk, dv, scale):
    def fn(x, p):
        b, s, _ = x.shape
        q = x[..., :nh * dqk].reshape(b, s, nh, dqk)
        k = x[..., nh * dqk:2 * nh * dqk].reshape(b, s, nh, dqk)
        v = x[..., 2 * nh * dqk:].reshape(b, s, nh, dv)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        scores = jnp.where(np.tril(np.ones((s, s), bool)), scores, -1e30)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        return out.reshape(b, s, nh * dv)
    return fn


def _attention_build(nh, dqk, dv, scale):
    def build(x):
        q = layers.slice(x, [2], [0], [nh * dqk])
        k = layers.slice(x, [2], [nh * dqk], [2 * nh * dqk])
        v = layers.slice(x, [2], [2 * nh * dqk], [2 * nh * dqk + nh * dv])
        return layers.fused_multihead_attention(
            q, k, v, num_heads=nh, causal=True, softmax_scale=scale), []
    return build


def _lowerings(impl, form):
    return get_registry().counter("attention_lowerings_total", impl=impl,
                                  form=form).value


@pytest.mark.parametrize("dqk, dv, scale", [
    (24, 16, 0.31),    # MLA's shape: wider q / k than v, a given scale
    (16, 16, 0.31),    # one width, a given scale
    (16, 32, None),    # wider v, the default scale of the q / k width
])
def test_attention_with_unequal_widths_and_a_given_scale(dqk, dv, scale):
    nh = 2
    x = np.random.RandomState(1).randn(2, 12, nh * (2 * dqk + dv)).astype(
        np.float32)
    before = _lowerings("jnp", "mla")
    got = _run(_attention_build(nh, dqk, dv, scale), {"x": x})
    assert got[0].shape == (2, 12, nh * dv)
    assert _lowerings("jnp", "mla") > before
    want = _ref_grads(_attention_ref(nh, dqk, dv, scale or dqk ** -0.5),
                      x, {}, got[3])
    _assert_close(got[0], got[1], *want, tol=1e-5)


def test_attention_at_equal_widths_is_what_it_was():
    """No scale given and one width: the op takes its old path, counted as
    `mha`, its lowered text has no trace of the latent form, and the result
    is the composition at 1/sqrt(d)."""
    nh, d = 2, 16
    x = np.random.RandomState(2).randn(2, 12, nh * 3 * d).astype(np.float32)
    lowered = []
    before = _lowerings("jnp", "mha"), _lowerings("jnp", "mla")
    got = _run(_attention_build(nh, d, d, None), {"x": x}, lowered=lowered)
    assert _lowerings("jnp", "mha") > before[0]
    assert _lowerings("jnp", "mla") == before[1]
    want = _ref_grads(_attention_ref(nh, d, d, d ** -0.5), x, {}, got[3])
    _assert_close(got[0], got[1], *want, tol=1e-5)
    with pytest.raises(RuntimeError, match="neither BiasQK nor dropout"):
        _run(lambda v: (layers.fused_multihead_attention(
            v, v, v, num_heads=nh, dropout_prob=0.1, softmax_scale=0.5), []),
            {"x": x[..., :nh * d]})


def test_the_padded_flash_calls_carry_their_own_names():
    """192 / 128 wide heads padded to 256 run the causal BSH bodies: in
    interpret mode they give what the composition gives; lowered for the
    TPU from this CPU process they are `flash_mla_causal_fwd` / `_bwd`."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    nh, dqk, dv, s = 2, 192, 128, 128
    x = (np.random.RandomState(3).randn(1, s, nh * (2 * dqk + dv)) * 0.5
         ).astype(np.float32)
    scale = Xing4Config().softmax_scale
    assert scale == pytest.approx(0.14468, rel=1e-4)
    before = _lowerings("pallas", "mla")
    with mock.patch.object(attention, "FORCE_PALLAS", True):
        got = _run(_attention_build(nh, dqk, dv, scale), {"x": x})
    assert _lowerings("pallas", "mla") > before
    want = _ref_grads(_attention_ref(nh, dqk, dv, scale), x, {}, got[3])
    _assert_close(got[0], got[1], *want, tol=2e-5)

    def loss(q, k, v):
        return attention.latent_attention(
            q, k, v, nh, scale, True).astype(jnp.float32).sum()

    q = jnp.zeros((1, s, nh * dqk), jnp.bfloat16)
    v = jnp.zeros((1, s, nh * dv), jnp.bfloat16)
    fa._make_flash_core_bsh.cache_clear()
    try:
        with mock.patch.object(fa, "_interpret", lambda: False):
            text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
                q, q, v).lower(lowering_platforms=("tpu",)).as_text()
    finally:
        fa._make_flash_core_bsh.cache_clear()
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert kernels == {"flash_mla_causal_fwd", "flash_mla_causal_bwd"}
    assert fa._bsh_kernel_name("fwd", True) == "flash_bsh_causal_fwd"


# ---------------------------------------------------------------------------
# the rotary part at YaRN's frequencies
# ---------------------------------------------------------------------------


def test_yarn_frequencies_by_hand():
    """The published keys: 64-wide rotary part, theta 1e4, factor 64,
    beta_fast 32, beta_slow 1 over 4,096 original positions."""
    def correction(turns):
        return 64 * math.log(4096 / (turns * 2 * math.pi)) / (
            2 * math.log(10000.0))

    assert correction(32) == pytest.approx(10.47, abs=0.01)
    assert correction(1) == pytest.approx(22.51, abs=0.01)
    low, high = 10, 23  # floor and ceiling of the two
    f = yarn_inv_freq(64, 10000.0, YARN)
    assert f.dtype == np.float64 and f.shape == (32,)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:low + 1], plain[:low + 1], rtol=1e-15)
    np.testing.assert_allclose(f[high:], plain[high:] / 64, rtol=1e-15)
    # pair 16: (16 - 10) / 13 of the way from its own frequency to 1/64 of it
    ramp = (16 - low) / (high - low)
    assert f[16] == pytest.approx(
        plain[16] * (1 - ramp) + plain[16] / 64 * ramp, rel=1e-14)
    # the reference's own table agrees
    cos, _ = ref.yarn_table(ref.reference_settings(Xing4Config()), 8)
    np.testing.assert_allclose(cos[5], np.cos(5 * f), atol=1e-7)


def test_rope_at_a_table_given_by_the_model():
    d, heads = 8, 3
    table = [1.0, 0.37, 0.011, 0.0007]
    x = np.random.RandomState(4).randn(2, 10, heads * d).astype(np.float32)
    got = _run(lambda v: (layers.rope(v, d, inv_freq=table), []), {"x": x})
    # by hand, in float64
    xs = x.astype(np.float64).reshape(2, 10, heads, 2, d // 2)
    angle = np.arange(10)[:, None] * np.asarray(table)[None, :]
    c, s = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
    want = np.stack([xs[..., 0, :] * c - xs[..., 1, :] * s,
                     xs[..., 1, :] * c + xs[..., 0, :] * s], -2)
    assert _rel(got[0], want.reshape(x.shape)) < 1e-6
    with pytest.raises(RuntimeError, match="frequencies"):
        _run(lambda v: (layers.rope(v, d, inv_freq=table[:3]), []), {"x": x})


# ---------------------------------------------------------------------------
# hyper-connections: the mappings, Sinkhorn, the mixing
# ---------------------------------------------------------------------------

N, C = 4, 16


def _maps_numpy(x, phi, b, alpha, iters, eps=1e-6, lo=-30.0, hi=30.0):
    """The three mappings a token at a time, float64 numpy."""
    x = x.astype(np.float64)
    xbar = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps)
    t = xbar @ phi.astype(np.float64)
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    pre = sig(alpha[0] * t[..., :N] + b[:N])
    post = 2.0 * sig(alpha[1] * t[..., N:2 * N] + b[N:2 * N])
    m = np.exp(np.clip(alpha[2] * t[..., 2 * N:] + b[2 * N:], lo, hi)
               ).reshape(x.shape[:-1] + (N, N))
    for _ in range(iters):
        m = m / m.sum(-1, keepdims=True)
        m = m / m.sum(-2, keepdims=True)
    return pre, post, m


def _hc_attrs():
    return dict(
        param_attr=fluid.ParamAttr(initializer=INIT),
        bias_attr=fluid.ParamAttr(
            initializer=fluid.initializer.NormalInitializer(0.0, 1.0)))


def _mhc_map_lowerings(impl):
    return get_registry().counter("mhc_map_lowerings_total",
                                  impl=impl).value


@pytest.mark.parametrize("iters, bound, B, S, C, impl", [
    (20, 1e-4, 2, 5, 16, "jnp"), (1, 1e-2, 2, 5, 16, "jnp"),
    # whole lane tiles and 128 rows, the kernels pinned (the worst of 128
    # tokens at 512 columns starts further from doubly stochastic)
    (20, 0.1, 1, 128, 128, "pallas"), (1, 0.5, 1, 128, 128, "pallas"),
], ids=["jnp-20", "jnp-1", "pallas-20", "pallas-1"])
def test_mhc_map_against_numpy_and_sinkhorn(iters, bound, B, S, C, impl):
    x = np.random.RandomState(6).randn(B, S, N * C).astype(np.float32)
    before = _mhc_map_lowerings(impl)

    def build(v):
        pre, post, res, gap = layers.mhc_map(
            v, N, sinkhorn_iters=iters, alpha_init=0.5, name="hc",
            **_hc_attrs())
        return layers.concat([pre, post, res], axis=2), [gap]

    with mock.patch.object(attention, "FORCE_PALLAS", impl == "pallas"):
        out, grads, params, w, (gap,) = _run(build, {"x": x})
    assert _mhc_map_lowerings(impl) > before
    assert set(params) == {"hc.phi", "hc.b", "hc.alpha"}
    assert params["hc.phi"].shape == (N * C, 2 * N + N * N)
    pre, post, res = _maps_numpy(x, params["hc.phi"], params["hc.b"],
                                 params["hc.alpha"], iters)
    want = np.concatenate([pre, post, res.reshape(B, S, N * N)], -1)
    assert _rel(out, want) < 1e-5
    assert (0 < pre).all() and (pre < 1).all() and (post < 2).all()
    # H_res after the last round: columns exact, rows as far as the rounds
    # brought them; the op reports the worst of both, stream by stream
    h_res = out[..., 2 * N:].reshape(B, S, N, N)
    rows = np.abs(h_res.sum(-1) - 1).max((0, 1))
    cols = np.abs(h_res.sum(-2) - 1).max((0, 1))
    assert gap.shape == (N,)
    np.testing.assert_allclose(gap, np.maximum(rows, cols), atol=1e-6)
    if iters == 20:        # doubly stochastic
        assert gap.max() < bound
    else:                  # one round is not enough
        assert gap.max() > bound
    # gradients, through Sinkhorn too, against jax on the same function
    settings = {"hc_mult": N, "hc_eps": 1e-6, "hc_sinkhorn_iters": iters,
                "mhc_h_res_clamp_min": -30.0, "mhc_h_res_clamp_max": 30.0}

    def fn(xv, p):
        a, b_, m = ref.hyper_maps(
            xv.reshape(B, S, N, C),
            {"phi": p["hc.phi"], "b": p["hc.b"], "alpha": p["hc.alpha"]},
            settings)
        return jnp.concatenate([a, b_, m.reshape(B, S, N * N)], -1)

    _assert_close(out, grads, *_ref_grads(fn, x, params, w), tol=2e-5)


def _mhc_post_lowerings(impl):
    return get_registry().counter("mhc_post_lowerings_total",
                                  impl=impl).value


@pytest.mark.parametrize("B, S, C, impl", [
    (2, 5, 16, "jnp"),      # the composition, as every CPU run has it
    (1, 16, 128, "pallas"),  # whole lane tiles, the kernels pinned
], ids=["jnp", "pallas"])
def test_mhc_mix_against_numpy(B, S, C, impl):
    rng = np.random.RandomState(7)
    x = rng.randn(B, S, N * C + C + 2 * N + N * N).astype(np.float32)
    before = _mhc_post_lowerings(impl)

    def build(v):
        streams = layers.slice(v, [2], [0], [N * C])
        y = layers.slice(v, [2], [N * C], [N * C + C])
        at = N * C + C
        h_pre = layers.slice(v, [2], [at], [at + N])
        h_post = layers.slice(v, [2], [at + N], [at + 2 * N])
        h_res = layers.slice(v, [2], [at + 2 * N], [at + 2 * N + N * N])
        u = layers.mhc_pre(streams, h_pre)
        readout = layers.mhc_pre(streams, streams=N)
        mixed = layers.mhc_post(streams, y, h_res, h_post)
        return layers.concat([u, readout, mixed], axis=2), []

    def fn(v, p):
        s = v[..., :N * C].reshape(B, S, N, C)
        y = v[..., N * C:N * C + C]
        at = N * C + C
        h_pre, h_post = v[..., at:at + N], v[..., at + N:at + 2 * N]
        h_res = v[..., at + 2 * N:].reshape(B, S, N, N)
        u = jnp.einsum("bsn,bsnc->bsc", h_pre, s)
        mixed = (jnp.einsum("bsij,bsjc->bsic", h_res, s)
                 + h_post[..., None] * y[:, :, None, :])
        return jnp.concatenate(
            [u, s.sum(2), mixed.reshape(B, S, N * C)], -1)

    with mock.patch.object(attention, "FORCE_PALLAS", impl == "pallas"):
        got = _run(build, {"x": x})
    assert _mhc_post_lowerings(impl) > before
    # by hand for one token: stream 1 of X' = sum_j H_res[1, j] X_j + H_post[1] y
    s0 = x[0, 0, :N * C].reshape(N, C).astype(np.float64)
    at = N * C + C
    hr = x[0, 0, at + 2 * N:].reshape(N, N).astype(np.float64)
    want1 = hr[1] @ s0 + x[0, 0, at + N + 1] * x[0, 0, N * C:at]
    np.testing.assert_allclose(got[0][0, 0, 2 * C + C:2 * C + 2 * C], want1,
                               rtol=1e-5, atol=1e-6)
    _assert_close(got[0], got[1], *_ref_grads(fn, x, {}, got[3]), tol=1e-5)


def test_the_mappings_stay_float32_under_amp():
    """bf16 streams in, float32 H out and into the mixing; the streams
    come back in bf16."""
    from paddle_tpu.contrib import mixed_precision

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[2, 5, N * C], dtype="float32",
                        append_batch_size=False)
        pre, post, res, _ = layers.mhc_map(x, N, name="hc", **_hc_attrs())
        z = layers.rms_norm(layers.mhc_pre(x, pre))
        y = layers.shared_expert(z, 8, param_attr=fluid.ParamAttr(
            initializer=INIT), name="se")
        out = layers.mhc_post(x, y, res, post)
        loss = layers.reduce_mean(layers.cast(out, "float32"))
        mixed_precision.decorate(fluid.optimizer.SGD(0.0),
                                 use_bf16=True).minimize(loss, startup)
    block = main.global_block()
    by_type = {op.type: op for op in block.ops if not op.type.endswith("grad")}
    dtype_of = lambda n: np.dtype(block.var(n).dtype).name

    def ins(op, slot):
        return dtype_of(by_type[op].inputs[slot][0])

    assert ins("mhc_map", "X") == "bfloat16"
    assert {ins("mhc_map", s) for s in ("Phi", "Bias", "Alpha")} == {"float32"}
    assert ins("mhc_pre", "HPre") == "float32"
    assert {ins("mhc_post", s) for s in ("HRes", "HPost")} == {"float32"}
    assert ins("mhc_post", "Y") == "bfloat16"
    assert ins("shared_expert", "W1") == "bfloat16"
    assert dtype_of(by_type["mhc_post"].outputs["Out"][0]) == "bfloat16"


# ---------------------------------------------------------------------------
# the shared expert and the scopes the benchmark reads
# ---------------------------------------------------------------------------


def _parts(lowered_text):
    """The part scopes the op_names of a lowered step carry."""
    from benchmark import part_scopes

    names = set(re.findall(r'"(jit\([a-z_]+\)/[^"]*)"', lowered_text))
    return {part_scopes.part_of(n) for n in names} - {None}


def test_the_shared_expert_is_a_swiglu_under_its_own_scope():
    x = np.random.RandomState(8).randn(2, 6, 16).astype(np.float32)
    lowered = []
    got = _run(lambda v: (layers.shared_expert(
        v, 24, remat=True, param_attr=fluid.ParamAttr(initializer=INIT),
        name="se"), []), {"x": x}, lowered=lowered)

    def fn(v, p):
        return (jax.nn.silu(v @ p["se.w1"]) * (v @ p["se.w3"])) @ p["se.w2"]

    _assert_close(got[0], got[1], *_ref_grads(fn, x, got[2], got[3]),
                  tol=1e-5)
    assert _parts(lowered[0]) == {"shared_expert"}


@pytest.mark.parametrize("width, seq, impl, map_impl", [
    (64, 8, "jnp", "jnp"), (128, 8, "pallas", "jnp"),
    (128, 128, "pallas", "pallas")], ids=["jnp", "pallas", "pallas-s128"])
def test_every_new_op_lowers_under_its_part_scope(width, seq, impl, map_impl):
    """At 128 columns a stream with the kernels pinned, `mhc_post` is the
    two kernels of ops/pallas/mhc.py: they lower under `mhc_mix` like the
    composition, the backward one under the role `backward`. At 128 rows
    too, `mhc_map`'s forward pass is its kernel, under `mhc_map`; its
    backward pass is the composition's (`mhc._map_core_bwd` says why),
    under the same part and the role `backward`."""
    cfg = Xing4Config.tiny(heads_held=4)
    x = np.random.RandomState(9).randn(1, seq, width).astype(np.float32)
    lowered = []
    before = _mhc_post_lowerings(impl), _mhc_map_lowerings(map_impl)

    def build(v):
        streams = layers.expand(v, [1, 1, 4])
        pre, post, res, _ = layers.mhc_map(streams, 4, name="hc",
                                           **_hc_attrs())
        y = layers.mla(
            layers.mhc_pre(streams, pre), 4, 48, 32, 24, 8, 16,
            cfg.softmax_scale, inv_freq=yarn_inv_freq(8, 1e4, YARN),
            param_attr=fluid.ParamAttr(initializer=INIT), name="attn")
        return layers.mhc_post(streams, y, res, post), []

    with mock.patch.object(attention, "FORCE_PALLAS", impl == "pallas"):
        _run(build, {"x": x}, lowered=lowered)
    assert _mhc_post_lowerings(impl) > before[0]
    assert _mhc_map_lowerings(map_impl) > before[1]
    assert _parts(lowered[0]) == {"mla", "mhc_map", "mhc_mix"}
    backward = "\n".join(line for line in lowered[0].splitlines()
                         if "/backward/" in line)
    assert _parts(backward) == {"mla", "mhc_map", "mhc_mix"}
    from benchmark import part_scopes, roles

    names = set(re.findall(r'"(jit\([a-z_]+\)/[^"]*)"', lowered[0]))
    # the kernels are inner jits: the call sites carry the scopes, and XLA
    # prefixes them to the `mhc_*/pallas_call` it inlines
    for entry, role, part, on in (
            ("_mhc_fwd", "forward", "mhc_mix", impl),
            ("_mhc_bwd", "backward", "mhc_mix", impl),
            ("_map_fwd", "forward", "mhc_map", map_impl),
            ("_map_bwd", "backward", "mhc_map", None)):
        calls = {n for n in names if n.endswith(f"/jit({entry})")}
        assert bool(calls) == (on == "pallas"), (entry, calls)
        for n in calls:
            assert part_scopes.part_of(n) == part, n
            assert roles.role_of(n) == role, n


# ---------------------------------------------------------------------------
# the whole latent-attention sublayer
# ---------------------------------------------------------------------------


def test_mla_against_the_reference_sublayer():
    cfg = Xing4Config.tiny()
    settings = ref.reference_settings(cfg)
    x = np.random.RandomState(10).randn(2, 16, 64).astype(np.float32)
    got = _run(lambda v: (layers.mla(
        v, 8, 48, 32, 24, 8, 16, cfg.softmax_scale, theta=cfg.rope_theta,
        inv_freq=cfg.inv_freq, param_attr=fluid.ParamAttr(initializer=INIT),
        name="a"), []), {"x": x})
    assert {k: v.shape for k, v in got[2].items()} == {
        "a.q_a_proj": (64, 48), "a.q_a_layernorm": (48,),
        "a.q_b_proj": (48, 8 * 32), "a.kv_a_proj": (64, 40),
        "a.kv_a_layernorm": (32,), "a.kv_b_proj": (32, 8 * 40),
        "a.o_proj": (8 * 16, 64)}

    def fn(v, p):
        return ref.mla(v, {k[2:]: w for k, w in p.items()}, settings)

    _assert_close(got[0], got[1], *_ref_grads(fn, x, got[2], got[3]),
                  tol=2e-5)
