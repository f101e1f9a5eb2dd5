"""What Nemotron-H forced of the ops, on seeded values at toy widths: the
state-space scan in chunks against the recurrence computed position by
position in float64 numpy (forward, and every input's gradient by central
differences of that recurrence), the four-tap convolution with bias and
SiLU beside LFM2's three-tap call, the gated grouped norm, the whole
`mamba2` op through Program -> append_backward -> Executor against the
reference's mixer, the two-matrix squared-ReLU experts against a loop over
experts with `moe_swiglu`'s three-matrix lowering left as the parent had
it, and the scopes and the counter the benchmark reads."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from benchmark.models import nemotron_h as ref
from paddle_tpu.fluid import layers
from paddle_tpu.ops import decoder_ops, ssm_ops
from paddle_tpu.telemetry import get_registry
from test_lfm2_ops import INIT, _assert_close, _ref_grads, _rel, _run

# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------


def _recurrence64(x, dt, a, b, c, d):
    """S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D_h x_t,
    float64, one position after another; head h reads group h // (H / G)."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    y = np.zeros((bsz, s, h, p))
    for i in range(bsz):
        state = np.zeros((h, p, n))
        for t in range(s):
            bt = np.repeat(b[i, t], h // g, axis=0)
            ct = np.repeat(c[i, t], h // g, axis=0)
            state = (np.exp(dt[i, t] * a)[:, None, None] * state
                     + (dt[i, t, :, None] * x[i, t])[:, :, None]
                     * bt[:, None, :])
            y[i, t] = np.einsum("hpn,hn->hp", state, ct) + d[:, None] * x[i, t]
    return y


def _scan_inputs(seq, seed=0):
    """Two batch rows, 4 heads of 3 in 2 groups (two heads a group) of
    state 5; head 0 hardly decays (exp(dt A) ~ 1), head 1 forgets within a
    step (exp(dt A) ~ 0)."""
    rng = np.random.default_rng(seed)
    a = -rng.uniform(0.5, 8.0, 4)
    a[0], a[1] = -1e-3, -80.0
    return dict(
        x=rng.normal(size=(2, seq, 4, 3)), dt=rng.uniform(0.05, 0.5, (2, seq, 4)),
        a=a, b=rng.normal(size=(2, seq, 2, 5)), c=rng.normal(size=(2, seq, 2, 5)),
        d=rng.normal(size=4))


@pytest.mark.parametrize("seq, chunk", [(16, 4), (4, 4), (10, 4), (6, 128)],
                         ids=["four_chunks", "one_chunk", "ragged_row",
                              "row_shorter_than_a_chunk"])
def test_ssd_scan_against_the_recurrence_forward_and_every_gradient(seq, chunk):
    ins = _scan_inputs(seq)
    names = list(ins)
    want = _recurrence64(**ins)
    decay = np.exp(ins["dt"] * ins["a"])
    assert decay[..., 0].min() > 0.999 and decay[..., 1].max() < 0.02
    f32 = [jnp.asarray(ins[k], jnp.float32) for k in names]
    with jax.default_matmul_precision("highest"):
        got = ssm_ops.ssd_scan(*f32, chunk)
        w = np.random.default_rng(1).uniform(0.5, 1.5, want.shape)
        grads = jax.grad(lambda *t: jnp.sum(ssm_ops.ssd_scan(*t, chunk) * w),
                         argnums=tuple(range(6)))(*f32)
    assert _rel(got, want) < 2e-6
    # every input's gradient along a random direction, against the central
    # difference of the float64 recurrence
    rng = np.random.default_rng(2)
    for name, g in zip(names, grads):
        assert np.all(np.isfinite(np.asarray(g))), name
        v = rng.normal(size=ins[name].shape)
        eps = 1e-5
        plus = np.sum(_recurrence64(**{**ins, name: ins[name] + eps * v}) * w)
        minus = np.sum(_recurrence64(**{**ins, name: ins[name] - eps * v}) * w)
        want_dir = (plus - minus) / (2 * eps)
        got_dir = float(np.sum(np.asarray(g, np.float64) * v))
        assert abs(got_dir - want_dir) < 2e-4 * max(abs(want_dir), 1.0), name


def test_the_state_is_carried_across_chunks_and_heads_read_their_group():
    """With the state dropped where chunks meet, or group 0's B and C for
    every head, the result is another one: both show at these sizes."""
    ins = _scan_inputs(16)
    want = _recurrence64(**ins)
    cut = np.concatenate([
        _recurrence64(**{k: (v[:, z:z + 4] if v.ndim > 1 else v)
                         for k, v in ins.items()}) for z in range(0, 16, 4)],
        axis=1)
    assert _rel(cut, want) > 0.05
    zero = {**ins, "b": np.repeat(ins["b"][:, :, :1], 2, axis=2),
            "c": np.repeat(ins["c"][:, :, :1], 2, axis=2)}
    assert _rel(_recurrence64(**zero), want) > 0.3
    got = ssm_ops.ssd_scan(*(jnp.asarray(ins[k], jnp.float32) for k in ins), 4)
    assert _rel(got, want) < 1e-5


# ---------------------------------------------------------------------------
# the convolution, the gated norm
# ---------------------------------------------------------------------------


def _conv64(x, taps):
    n_taps, s = taps.shape[0], x.shape[1]
    padded = np.pad(x.astype(np.float64), ((0, 0), (n_taps - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + s] for j in range(n_taps))


@pytest.mark.parametrize("n_taps", [3, 4])
def test_the_causal_convolution_at_three_and_at_four_taps(n_taps):
    """LFM2's three-tap call and Mamba-2's four taps are one function; with
    a bias and SiLU behind it, it is what `mamba2` applies to xBC."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 9, 6)).astype(np.float32)
    taps = rng.normal(size=(n_taps, 6)).astype(np.float32)
    bias = rng.normal(size=6).astype(np.float32)
    want = _conv64(x, taps)
    got = decoder_ops.causal_depthwise_conv(jnp.asarray(x), jnp.asarray(taps))
    assert _rel(got, want) < 1e-6
    # the first position sees the last tap alone
    np.testing.assert_allclose(got[:, 0], taps[-1] * x[:, 0], rtol=1e-6)
    z = want + bias
    assert _rel(jax.nn.silu(got + bias), z / (1 + np.exp(-z))) < 1e-6


def test_the_gated_norm_is_over_groups_and_behind_the_gate():
    rng = np.random.default_rng(4)
    y, z = rng.normal(size=(2, 5, 12)), rng.normal(size=(2, 5, 12))
    w = rng.uniform(0.5, 1.5, 12)
    gated = (y * z / (1 + np.exp(-z))).reshape(2, 5, 3, 4)
    want = (gated / np.sqrt(np.mean(gated ** 2, -1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 12) * w
    got = ssm_ops.gated_group_norm(*(jnp.asarray(t, jnp.float32)
                                     for t in (y, z, w)), 4, 1e-5)
    assert _rel(got, want) < 1e-6
    over_all = ssm_ops.gated_group_norm(*(jnp.asarray(t, jnp.float32)
                                          for t in (y, z, w)), 12, 1e-5)
    assert _rel(over_all, want) > 0.1


# ---------------------------------------------------------------------------
# the mamba2 op through the Program
# ---------------------------------------------------------------------------

MAMBA = dict(mamba_num_heads=4, mamba_head_dim=4, n_groups=2,
             ssm_state_size=6, conv_kernel=4, layer_norm_epsilon=1e-5)


def _mamba_layer(v, chunk=4):
    out, decay = layers.mamba2(
        v, 4, 4, 2, 6, conv_kernel=4, chunk_size=chunk,
        param_attr=fluid.ParamAttr(initializer=INIT), name="m")
    return out, [decay]


def _scan_lowerings():
    return get_registry().counter("ssd_scan_lowerings_total", impl="jnp").value


def test_mamba2_against_the_reference_mixer():
    x = np.random.RandomState(6).randn(2, 16, 12).astype(np.float32)
    before = _scan_lowerings()
    out, grads, params, w, extras = _run(_mamba_layer, {"x": x})
    assert _scan_lowerings() > before
    assert {k: v.shape for k, v in params.items()} == {
        "m.in_proj": (12, 16 + 16 + 24 + 4), "m.conv1d.weight": (4, 40),
        "m.conv1d.bias": (40,), "m.dt_bias": (4,), "m.A_log": (4,),
        "m.D": (4,), "m.norm.weight": (16,), "m.out_proj": (16, 12)}
    # what the release's layer starts at
    assert np.abs(params["m.conv1d.weight"]).max() <= 0.5
    dt = np.log1p(np.exp(params["m.dt_bias"]))
    assert np.all((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001))
    a = np.exp(params["m.A_log"])
    assert np.all((a >= 1) & (a <= 16)) and np.all(params["m.D"] == 1)

    def fn(v, p):
        return ref.mamba2(v, {k[2:]: t for k, t in p.items()}, MAMBA)

    _assert_close(out, grads, *_ref_grads(fn, x, params, w), tol=2e-5)
    # each head's smallest decay exp(dt A) over the step's tokens
    proj = x @ params["m.in_proj"]
    step = np.log1p(np.exp(proj[..., -4:] + params["m.dt_bias"]))
    np.testing.assert_allclose(
        extras[0], np.exp(-step * a).min(axis=(0, 1)), rtol=1e-5)


def test_mamba2_keeps_its_small_vectors_float32_under_amp():
    from paddle_tpu.contrib import mixed_precision

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[2, 8, 12], dtype="float32",
                        append_batch_size=False)
        out, decay = _mamba_layer(layers.rms_norm(x))
        loss = layers.reduce_mean(layers.cast(out, "float32"))
        mixed_precision.decorate(fluid.optimizer.SGD(0.0),
                                 use_bf16=True).minimize(loss, startup)
    block = main.global_block()
    op = next(op for op in block.ops if op.type == "mamba2")
    dtype_of = lambda n: np.dtype(block.var(n).dtype).name
    assert {dtype_of(op.inputs[s][0]) for s in ("X", "InW", "OutW")} == {
        "bfloat16"}
    assert {dtype_of(op.inputs[s][0]) for s in (
        "ConvW", "ConvB", "DtBias", "ALog", "D", "NormW")} == {"float32"}
    assert dtype_of(op.outputs["Out"][0]) == "bfloat16"
    assert dtype_of(op.outputs["MinDecay"][0]) == "float32"


def test_the_scan_lowers_under_its_role_and_both_part_scopes():
    """`ssd_scan` lies inside `mamba2`: among the accepted part names plus
    `mamba2` the scan's instructions are the mixer's, plus `ssd_scan` the
    scan's own, forward and backward, through `jax.checkpoint`."""
    from benchmark import part_scopes, roles

    x = np.random.RandomState(7).randn(1, 8, 12).astype(np.float32)
    lowered = []
    _run(_mamba_layer, {"x": x}, lowered=lowered)
    names = set(re.findall(r'"(jit\([a-z_]+\)/[^"]*)"', lowered[0]))
    scan = {n for n in names if "ssd_scan" in n}
    assert scan and all("mamba2" in n.split("ssd_scan")[0] for n in scan)
    assert {roles.role_of(n) for n in scan} == {"forward", "backward"}
    mixer = part_scopes.PARTS + ("mamba2",)
    alone = part_scopes.PARTS + ("ssd_scan",)
    assert {part_scopes.part_of(n, mixer) for n in scan} == {"mamba2"}
    assert {part_scopes.part_of(n, alone) for n in scan} == {"ssd_scan"}
    rest = {n for n in names if "mamba2" in n} - scan
    assert rest and {part_scopes.part_of(n, alone) for n in rest} == {None}
    # the masked [Q, Q] product and the product over the chunks are there
    assert any(n.endswith("dot_general") for n in scan)


# ---------------------------------------------------------------------------
# the two-matrix experts
# ---------------------------------------------------------------------------


def _relu2_layer(held, first, remat=False):
    def build(v):
        out, counts = layers.moe_swiglu(
            v, 16, 24, experts_held=held, first_expert=first, top_k=2,
            routed_scaling_factor=2.5, remat=remat,
            param_attr=fluid.ParamAttr(initializer=INIT),
            bias_attr=fluid.ParamAttr(initializer=INIT), name="m",
            activation="relu2")
        return out, [counts]
    return build


@pytest.mark.parametrize("held, first, remat", [(16, 0, False), (16, 0, True),
                                                (4, 8, False)])
def test_relu2_experts_against_a_loop_over_experts(held, first, remat):
    x = np.random.RandomState(8).randn(2, 16, 32).astype(np.float32)
    out, grads, params, w, extras = _run(_relu2_layer(held, first, remat),
                                         {"x": x})
    assert set(params) == {"m.gate", "m.expert_bias", "m.w1", "m.w2"}
    assert params["m.w1"].shape == (held, 32, 24)
    assert params["m.gate"].shape == (32, 16)  # the router's whole width
    settings = dict(num_experts_per_tok=2, norm_topk_prob=True,
                    routed_scaling_factor=2.5)

    def fn(v, p):
        return ref.routed_experts(
            v, {"expert_bias": params["m.expert_bias"],
                **{k[2:]: t for k, t in p.items()}}, settings, (first, held))

    trained = {k: v for k, v in params.items() if k != "m.expert_bias"}
    _assert_close(out, grads, *_ref_grads(fn, x, trained, w), tol=2e-5)
    if held == 16:  # every pick lands on a held expert
        assert int(extras[0].sum()) == 2 * 16 * 2


def test_the_shared_expert_at_relu2_and_what_is_refused():
    x = np.random.RandomState(9).randn(2, 6, 16).astype(np.float32)
    lowered = []
    got = _run(lambda v: (layers.shared_expert(
        v, 24, remat=True, param_attr=fluid.ParamAttr(initializer=INIT),
        name="se", activation="relu2"), []), {"x": x}, lowered=lowered)
    assert set(got[2]) == {"se.w1", "se.w2"}

    def fn(v, p):
        return jnp.square(jax.nn.relu(v @ p["se.w1"])) @ p["se.w2"]

    _assert_close(got[0], got[1], *_ref_grads(fn, x, got[2], got[3]),
                  tol=1e-5)
    from benchmark import part_scopes

    names = set(re.findall(r'"(jit\([a-z_]+\)/[^"]*)"', lowered[0]))
    assert {part_scopes.part_of(n) for n in names} - {None} == {
        "shared_expert"}
    with pytest.raises(ValueError, match="'swiglu' or 'relu2'"):
        _run(lambda v: (layers.shared_expert(v, 24, activation="gelu"), []),
             {"x": x})


@pytest.mark.parametrize("held, remat, output", [
    (4, False, "8adc173f924c30a9"), (16, True, "61d593e226bb485f")])
def test_moe_swiglu_at_its_defaults_gives_the_parents_bits(held, remat,
                                                           output):
    """The three-matrix layer through the generalised block: the output's
    bytes are those the same script read on the parent commit a623c15, for
    a held quarter (two lowerings under a conditional) and for a whole
    router with recomputation. (The lowered steps of the accepted cells are
    compared tree against tree, PERF.md section 6.)"""
    x = np.random.RandomState(2).randn(2, 16, 32).astype(np.float32)
    got = _run(lambda v: (lambda o: (o[0], [o[1]]))(layers.moe_swiglu(
        v, 16, 24, experts_held=held, first_expert=4 if held == 4 else 0,
        top_k=2, routed_scaling_factor=2.0, remat=remat,
        bias_update_rate=0.01, param_attr=fluid.ParamAttr(initializer=INIT),
        bias_attr=fluid.ParamAttr(initializer=INIT), name="m")), {"x": x})
    assert set(got[2]) == {"m.gate", "m.expert_bias", "m.w1", "m.w3", "m.w2"}
    assert hashlib.sha256(np.asarray(got[0]).tobytes()).hexdigest()[:16] == (
        output)


def test_the_grouped_product_counter_counts_the_two_product_form():
    def count():
        reg = get_registry()
        return sum(reg.counter("moe_grouped_product_lowerings_total",
                               impl="ragged_dot", form=f).value
                   for f in ("nn", "nt", "tn"))

    x = np.random.RandomState(10).randn(1, 8, 32).astype(np.float32)
    before = count()
    _run(_relu2_layer(16, 0), {"x": x})
    two = count() - before
    before = count()
    _run(lambda v: (lambda o: (o[0], [o[1]]))(layers.moe_swiglu(
        v, 16, 24, top_k=2, param_attr=fluid.ParamAttr(initializer=INIT),
        name="m")), {"x": x})
    three = count() - before
    assert two > 0 and three * 2 == two * 3
