"""The decoder ops (rms_norm, rope, short_conv, swiglu_ffn, moe_swiglu)
through Program -> append_backward -> Executor against plain numpy / jnp
references on seeded weights: forward and gradients of each op alone, in
float32 and under bf16 AMP; what the held-expert share means; the scopes
the benchmark reads; and the BSH flash kernels' names by mode."""
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.executor import Scope
from paddle_tpu.ops import decoder_ops, moe_ops
from paddle_tpu.ops.pallas import backend
from model_checks import INIT, assert_close, ref_grads, rel, run_op

# ---------------------------------------------------------------------------
# rms_norm, rope
# ---------------------------------------------------------------------------


def _rms_ref(group):
    def fn(x, p):
        (w,) = p.values()
        xs = x.reshape(x.shape[:-1] + (-1, group))
        y = xs * jax.lax.rsqrt(jnp.mean(xs * xs, -1, keepdims=True) + 1e-5) * w
        return y.reshape(x.shape)
    return fn


@pytest.mark.parametrize("group", [None, 8])
@pytest.mark.parametrize("amp", [False, True])
def test_rms_norm_whole_axis_and_per_head(group, amp):
    x = np.random.RandomState(0).randn(2, 6, 32).astype(np.float32)
    attr = fluid.ParamAttr(
        name="n.w", initializer=fluid.initializer.UniformInitializer(0.5, 1.5))
    got = run_op(lambda v: (layers.rms_norm(
        v, 1e-5, group_size=group, param_attr=attr), []), {"x": x}, amp)
    want = ref_grads(_rms_ref(group or 32), x, got[2], got[3])
    assert_close(got[0], got[1], *want, tol=2e-2 if amp else 1e-5)


def _rope_closed_form(x, head_dim, theta):
    """float64: y_i = x_i cos - x_{i+d/2} sin, y_{i+d/2} = x_{i+d/2} cos +
    x_i sin, angle = t * theta^(-2i/d)."""
    b, s, h = x.shape
    half = head_dim // 2
    xs = x.astype(np.float64).reshape(b, s, h // head_dim, 2, half)
    angle = (np.arange(s, dtype=np.float64)[:, None]
             * theta ** (-2.0 * np.arange(half) / head_dim))[None, :, None, :]
    y = np.stack([xs[..., 0, :] * np.cos(angle) - xs[..., 1, :] * np.sin(angle),
                  xs[..., 1, :] * np.cos(angle) + xs[..., 0, :] * np.sin(angle)],
                 axis=-2)
    return y.reshape(b, s, h)


def test_rope_against_the_closed_form_at_positions_0_and_4095():
    x = np.random.RandomState(1).randn(1, 4096, 32).astype(np.float32)
    out, grads, _, w, _ = run_op(
        lambda v: (layers.rope(v, head_dim=16, theta=1e6), []), {"x": x})
    want = _rope_closed_form(x, 16, 1e6)
    np.testing.assert_array_equal(out[:, 0], x[:, 0])  # angle 0
    for t in (0, 1, 4095):
        np.testing.assert_allclose(out[:, t], want[:, t], atol=2e-6)
    assert rel(out, want) < 1e-6
    # a rotation: norms of every pair are kept, and the gradient is the
    # inverse rotation of the cotangent
    np.testing.assert_allclose(
        np.linalg.norm(out, axis=-1), np.linalg.norm(x, axis=-1), rtol=1e-5)
    back = _rope_closed_form(grads["x"], 16, 1e6)
    assert rel(back, w) < 1e-6


def test_rope_under_amp_keeps_the_input_dtype():
    x = np.random.RandomState(2).randn(2, 16, 32).astype(np.float32)

    def build(v):
        q = layers.fc(v, 32, num_flatten_dims=2, bias_attr=False,
                      param_attr=fluid.ParamAttr(name="q", initializer=INIT))
        return layers.rope(q, head_dim=8, theta=1e4), []

    out, grads, params, w, _ = run_op(build, {"x": x}, amp=True)
    want = _rope_closed_form(x @ params["q"], 8, 1e4)
    assert rel(out, want) < 2e-2


# ---------------------------------------------------------------------------
# short_conv, swiglu_ffn
# ---------------------------------------------------------------------------


def _short_conv_ref(x, p):
    bg, cg, u = jnp.split(x @ p["c.in_proj"], 3, axis=-1)
    bu, taps = bg * u, p["c.conv"]
    s = x.shape[1]
    c = sum(taps[j] * jnp.pad(bu, ((0, 0), (taps.shape[0] - 1 - j, 0),
                                   (0, 0)))[:, :s]
            for j in range(taps.shape[0]))
    return (cg * c) @ p["c.out_proj"]


@pytest.mark.parametrize("amp", [False, True])
def test_short_conv_against_the_reference(amp):
    x = np.random.RandomState(3).randn(2, 12, 16).astype(np.float32)
    got = run_op(lambda v: (layers.short_conv(
        v, 3, param_attr=fluid.ParamAttr(initializer=INIT), name="c"), []),
        {"x": x}, amp)
    assert sorted(got[2]) == ["c.conv", "c.in_proj", "c.out_proj"]
    assert got[2]["c.conv"].shape == (3, 16)
    want = ref_grads(_short_conv_ref, x, got[2], got[3])
    assert_close(got[0], got[1], *want, tol=3e-2 if amp else 1e-5)


def test_short_conv_is_causal_and_three_taps_long():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 10, 8).astype(np.float32))
    ins = {"X": [x], "InW": [jnp.asarray(rng.randn(8, 24), jnp.float32)],
           "Filter": [jnp.asarray(rng.randn(3, 8), jnp.float32)],
           "OutW": [jnp.asarray(rng.randn(8, 8), jnp.float32)]}
    base = decoder_ops.short_conv(None, ins, {})["Out"][0]
    t = 6
    later = x.at[:, t + 1:].set(99.0)
    moved = decoder_ops.short_conv(None, {**ins, "X": [later]}, {})["Out"][0]
    # the output at t and before is unchanged by inputs after t
    np.testing.assert_array_equal(moved[:, :t + 1], base[:, :t + 1])
    assert not np.allclose(moved[:, t + 1:], base[:, t + 1:])
    # and an input at t reaches t, t+1 and t+2, no further
    bumped = x.at[:, t].add(1.0)
    moved = decoder_ops.short_conv(None, {**ins, "X": [bumped]}, {})["Out"][0]
    changed = np.abs(np.asarray(moved - base)).max(axis=(0, 2)) > 0
    assert changed.tolist() == [False] * t + [True] * 3 + [False]
    # zeros stand before t = 0: the first output sees the last tap only
    taps = ins["Filter"][0]
    proj = x[:, 0] @ ins["InW"][0]
    bg, cg, u = jnp.split(proj, 3, axis=-1)
    np.testing.assert_allclose(
        base[:, 0], (cg * (taps[2] * bg * u)) @ ins["OutW"][0], rtol=1e-5)


@pytest.mark.parametrize("remat", [False, True])
def test_swiglu_ffn_against_the_reference(remat):
    x = np.random.RandomState(5).randn(2, 6, 16).astype(np.float32)
    got = run_op(lambda v: (layers.swiglu_ffn(
        v, 24, remat=remat, param_attr=fluid.ParamAttr(initializer=INIT),
        name="f"), []), {"x": x})
    want = ref_grads(
        lambda x, p: (jax.nn.silu(x @ p["f.w1"]) * (x @ p["f.w3"])) @ p["f.w2"],
        x, got[2], got[3])
    assert_close(got[0], got[1], *want, tol=1e-5)


# ---------------------------------------------------------------------------
# moe_swiglu
# ---------------------------------------------------------------------------

E, K, H, F = 32, 4, 16, 24


def _moe_ref(held):
    """The reference's expert layer alone, through its own code path: a
    model of one MoE layer would drag the rest along, so the block is
    written out once more, dense over the experts given."""
    first, count = held

    def fn(x, p):
        s = jax.nn.sigmoid(x @ p["m.gate"])
        _, picks = jax.lax.top_k(s + p["m.expert_bias"], K)
        gates = jnp.take_along_axis(s, picks, axis=-1)
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-6)
        out = jnp.zeros_like(x)
        for e in range(count):
            weight = jnp.sum(jnp.where(picks == first + e, gates, 0.0), -1)
            out = out + weight[..., None] * (
                (jax.nn.silu(x @ p["m.w1"][e]) * (x @ p["m.w3"][e]))
                @ p["m.w2"][e])
        return out
    return fn


def _moe_layer(held, remat=False, experts=E, bias=INIT, inter=F):
    first, count = held
    return lambda v: (lambda r: (r[0], [r[1]]))(layers.moe_swiglu(
        v, experts, inter, experts_held=count, first_expert=first, top_k=K,
        remat=remat, param_attr=fluid.ParamAttr(initializer=INIT),
        bias_attr=fluid.ParamAttr(initializer=bias), name="m"))


@pytest.mark.parametrize("held, remat", [((0, 8), False), ((8, 8), True),
                                         ((0, 32), True)])
def test_moe_swiglu_against_the_dense_loop(held, remat):
    x = np.random.RandomState(6).randn(2, 24, H).astype(np.float32)
    got = run_op(_moe_layer(held, remat), {"x": x})
    params = got[2]
    assert params["m.gate"].shape == (H, E)  # the router's own width
    assert params["m.w1"].shape == (held[1], H, F)
    want_out, want = ref_grads(_moe_ref(held), x, params, got[3])
    want.pop("m.expert_bias")  # a buffer: the program makes it no gradient
    assert "m.expert_bias" not in got[1]
    assert_close(got[0], got[1], want_out, want, tol=2e-5)
    # the counter: the rows each held expert received
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(jnp.asarray(x) @ params["m.gate"])
    _, picks = jax.lax.top_k(s + params["m.expert_bias"], K)
    rows = [(np.asarray(picks) == held[0] + e).sum() for e in range(held[1])]
    assert got[4][0].tolist() == rows
    assert got[4][0].dtype == np.int32


def test_moe_swiglu_under_bf16_amp():
    x = np.random.RandomState(7).randn(2, 24, H).astype(np.float32)
    got = run_op(_moe_layer((0, 8)), {"x": x}, amp=True)
    want_out, want = ref_grads(_moe_ref((0, 8)), x, got[2], got[3])
    want.pop("m.expert_bias")
    # the router scores in float32 over the bf16 tokens, so a near-tie can
    # fall the other way: a few per cent, not the 1e-5 of float32
    assert_close(got[0], got[1], want_out, want, tol=8e-2)


@pytest.mark.parametrize("seq", [24, 128])
def test_the_four_shares_add_up_to_the_uncut_layer(seq):
    """first_expert 0, 8, 16, 24 of one layer: the shares' outputs sum to
    what the reference gives for the whole layer, and so do the loads. At
    256 tokens each share's sorted block is bounded (512 rows of 1,024)."""
    assert (moe_ops.sorted_rows(2 * seq * K, 8, E) < 2 * seq * K) == (
        seq == 128)
    x = np.random.RandomState(8).randn(2, seq, H).astype(np.float32)
    whole = run_op(_moe_layer((0, 32)), {"x": x})
    params = whole[2]
    with jax.default_matmul_precision("highest"):
        uncut = _moe_ref((0, E))(jnp.asarray(x), {
            k: jnp.asarray(v) for k, v in params.items()})
    np.testing.assert_allclose(whole[0], uncut, rtol=2e-5, atol=2e-6)
    total, loads = np.zeros_like(uncut), []
    for first in (0, 8, 16, 24):
        ins = {"X": [jnp.asarray(x)], "GateW": [params["m.gate"]],
               "ExpertBias": [params["m.expert_bias"]],
               "W1": [params["m.w1"][first:first + 8]],
               "W3": [params["m.w3"][first:first + 8]],
               "W2": [params["m.w2"][first:first + 8]]}
        outs = moe_ops.moe_swiglu(None, ins, {"top_k": K,
                                              "first_expert": first})
        total += np.asarray(outs["Out"][0])
        loads += outs["TokensPerExpert"][0].tolist()
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-6)
    assert sum(loads) == x.shape[0] * x.shape[1] * K  # every pick, once
    assert loads == whole[4][0].tolist()


def _ref_params(ins):
    """An op's inputs under the names `_moe_ref` reads."""
    return {"m.gate": ins["GateW"][0], "m.expert_bias": ins["ExpertBias"][0],
            "m.w1": ins["W1"][0], "m.w3": ins["W3"][0], "m.w2": ins["W2"][0]}


def _moe_ins(rng, tokens, bias):
    return {"X": [jnp.asarray(rng.randn(1, tokens, H), jnp.float32)],
            "GateW": [jnp.asarray(rng.randn(H, E) * 0.3, jnp.float32)],
            "ExpertBias": [jnp.asarray(bias, jnp.float32)],
            "W1": [jnp.asarray(rng.randn(8, H, F) * 0.3, jnp.float32)],
            "W3": [jnp.asarray(rng.randn(8, H, F) * 0.3, jnp.float32)],
            "W2": [jnp.asarray(rng.randn(8, F, H) * 0.3, jnp.float32)]}


def test_nothing_is_dropped_when_every_token_picks_the_same_expert():
    """A bias of +10 on held expert 3 puts it into every token's top-k:
    it receives all T rows (any capacity factor would have dropped most),
    and every token's output holds its full gate for that expert."""
    tokens = 96
    bias = np.zeros(E, np.float32)
    bias[3] = 10.0
    ins = _moe_ins(np.random.RandomState(9), tokens, bias)
    outs = moe_ops.moe_swiglu(None, ins, {"top_k": K, "first_expert": 0})
    counts = outs["TokensPerExpert"][0]
    assert int(counts[3]) == tokens
    want = _moe_ref((0, 8))(ins["X"][0], _ref_params(ins))
    np.testing.assert_allclose(outs["Out"][0], want, rtol=2e-5, atol=2e-6)
    # ... and when every pick of every token falls on held experts, the
    # row buffer is full to its last row: T * k rows, none lost
    bias = np.full(E, -10.0, np.float32)
    bias[:4] = 10.0
    ins = _moe_ins(np.random.RandomState(10), tokens, bias)
    outs = moe_ops.moe_swiglu(None, ins, {"top_k": K, "first_expert": 0})
    assert outs["TokensPerExpert"][0].tolist() == [tokens] * 4 + [0] * 4


# the sorted block's row bound: a share of 2 of 16 experts over 1,024 tokens
# expects 512 of the 4,096 pairs and has room for 1,024

NARROW, SHARE, TOKENS = 16, (0, 2), 1024
BOUND = 1024


@pytest.mark.parametrize("pairs, held, experts, rows", [
    (65536, 8, 32, 32768),  # the LFM2 cell
    (16384, 8, 32, 8192),  # its check program
    (4096, 2, 16, BOUND), (4096, 1, 16, 512), (4096, 3, 32, 1024),
    (192, 8, 32, 192),  # too few pairs for a multiple of 512 to bound
    (4096, 8, 16, 4096), (4096, 16, 16, 4096)])  # half, whole: no bound
def test_the_sorted_block_has_twice_the_expected_rows(pairs, held, experts,
                                                      rows):
    assert moe_ops.sorted_rows(pairs, held, experts) == rows


def _share_against_the_dense_loop(bias, lowered=None):
    x = np.random.RandomState(14).randn(1, TOKENS, H).astype(np.float32)
    got = run_op(_moe_layer(SHARE, remat=True, experts=NARROW,
                          bias=fluid.initializer.NumpyArrayInitializer(bias)),
               {"x": x}, lowered=lowered)
    want_out, want = ref_grads(_moe_ref(SHARE), x, got[2], got[3])
    want.pop("m.expert_bias")
    assert_close(got[0], got[1], want_out, want, tol=2e-5)
    return got[4][0]


def test_a_share_that_receives_more_than_its_bound_falls_back_and_drops_nothing():
    """+10 on both held experts puts them into every token's top-k: 2,048
    rows against a bound of 1,024, four times the expectation. Output,
    every gradient and the counter are the dense loop's."""
    bias = np.zeros(NARROW, np.float32)
    bias[:2] = 10.0
    counts = _share_against_the_dense_loop(bias)
    assert counts.tolist() == [TOKENS, TOKENS]
    assert counts.sum() > BOUND == moe_ops.sorted_rows(TOKENS * K, 2, NARROW)


def test_a_balanced_share_runs_the_bounded_block_and_lowers_both():
    lowered = []
    counts = _share_against_the_dense_loop(
        np.random.RandomState(15).randn(NARROW).astype(np.float32) * 0.05,
        lowered)
    assert 0.5 * 512 < counts.sum() < BOUND
    (text,) = lowered
    # the block's [rows, F] buffers at the bound and, under the fallback's
    # scope, at T * k rows
    assert f"tensor<{BOUND}x{F}xf32>" in text
    assert f"tensor<{TOKENS * K}x{F}xf32>" in text
    assert "stablehlo.case" in text
    names = set(re.findall(r'"(jit\(step\)[^"]*)"', text))
    for part in ("moe_dispatch", "moe_experts", "moe_combine"):
        for role in ("forward", "backward"):
            assert any(n.startswith(f"jit(step)/{role}/") and part in n
                       and "moe_full_width" not in n for n in names)
            # the scope stands outside the part's, so the part is still
            # the first that `benchmark/scopes.py` meets
            assert any(n.startswith(f"jit(step)/{role}/")
                       and "moe_full_width" in n
                       and n.index("moe_full_width") < n.index(part)
                       for n in names if part in n)


@pytest.mark.parametrize("fallen_back", [False, True])
def test_the_grouped_matmul_kernels_agree_with_ragged_dot_through_the_op(
        fallen_back):
    """`moe_swiglu` through Program -> Executor at widths the kernels'
    gate serves (128 lanes), the Pallas kernels pinned on (interpreted)
    against the `ragged_dot` path: output, every gradient and
    `TokensPerExpert`, in the bounded block and in the fallback."""
    wide, inter = 128, 256
    bias = np.random.RandomState(16).randn(NARROW).astype(np.float32) * 0.05
    if fallen_back:
        bias[:2] = 10.0
    x = np.random.RandomState(17).randn(1, TOKENS, wide).astype(np.float32)
    layer = _moe_layer(SHARE, remat=True, experts=NARROW, inter=inter,
                       bias=fluid.initializer.NumpyArrayInitializer(bias))
    lowered = []
    want = run_op(layer, {"x": x})
    with mock.patch.object(backend, "FORCE_PALLAS", True):
        got = run_op(layer, {"x": x}, lowered=lowered)
    assert {"moe_gmm_nn", "moe_gmm_nt", "moe_gmm_tn"} <= set(
        re.findall(r"moe_gmm_[a-z]+", lowered[0]))
    assert (got[4][0].sum() > BOUND) == fallen_back
    assert got[4][0].tolist() == want[4][0].tolist()
    assert set(got[1]) == set(want[1]) == {"m.gate", "m.w1", "m.w2", "m.w3",
                                           "x"}
    assert_close(got[0], got[1], want[0], want[1], tol=1e-5)


def _typed_tokens(rng, both, one):
    """Tokens whose picks are known: `both` of them pick held experts 0 and
    1 (and 4, 5), `one` picks held expert 0 alone (and 4, 5, 6), the rest
    pick 4-7; small noise keeps every output and gradient alive."""
    kinds = np.asarray([0] * both + [1] * one
                       + [2] * (TOKENS - both - one))
    rng.shuffle(kinds)
    x = rng.randn(TOKENS, H).astype(np.float32) * 0.05
    x[np.arange(TOKENS), kinds] += 4.0
    gate = rng.randn(H, NARROW).astype(np.float32) * 0.05
    for kind, picked in enumerate(([0, 1, 4, 5], [0, 4, 5, 6], [4, 5, 6, 7])):
        gate[kind, picked] += 2.0
    return {"X": [jnp.asarray(x[None])], "GateW": [jnp.asarray(gate)],
            "ExpertBias": [jnp.zeros((NARROW,), jnp.float32)],
            "W1": [jnp.asarray(rng.randn(2, H, F) * 0.3, jnp.float32)],
            "W3": [jnp.asarray(rng.randn(2, H, F) * 0.3, jnp.float32)],
            "W2": [jnp.asarray(rng.randn(2, F, H) * 0.3, jnp.float32)]}


def _through_the_op(ins):
    names = ("X", "GateW", "W1", "W3", "W2")

    def loss(*trained):
        outs = moe_ops.moe_swiglu(
            None, dict(ins, **{n: [v] for n, v in zip(names, trained)}),
            {"top_k": K, "first_expert": 0, "remat": True})
        return (outs["Out"][0] ** 2).sum(), outs

    (_, outs), grads = jax.value_and_grad(loss, argnums=range(5),
                                          has_aux=True)(
        *(ins[n][0] for n in names))
    return outs["Out"][0], grads, outs["TokensPerExpert"][0]


@pytest.mark.parametrize("present", [BOUND, BOUND + 1])
def test_both_sides_of_the_bound(present):
    """Exactly `rows` pairs on held experts fill the bounded block to its
    last row; one more and the layer runs at full width. With the fallback
    made to answer zero, the first still gives the dense loop's result and
    the second gives zero: that is which block ran."""
    ins = _typed_tokens(np.random.RandomState(16), BOUND // 2,
                        present - BOUND)
    out, grads, counts = _through_the_op(ins)
    assert int(counts.sum()) == present
    params = _ref_params(ins)
    with jax.default_matmul_precision("highest"):
        want_out = _moe_ref(SHARE)(ins["X"][0], params)
        want = jax.grad(lambda x, p: (_moe_ref(SHARE)(x, p) ** 2).sum(),
                        argnums=(0, 1))(ins["X"][0], params)
    assert rel(out, want_out) < 2e-5
    for got, ref in zip(grads, (want[0], *(want[1][n] for n in (
            "m.gate", "m.w1", "m.w3", "m.w2")))):
        assert rel(got, ref) < 2e-5
    with mock.patch.object(moe_ops, "_full_width",
                           lambda *operands: jnp.zeros_like(operands[0])):
        zeroed = _through_the_op(ins)[0]
    if present <= BOUND:
        np.testing.assert_array_equal(zeroed, out)
    else:
        assert not np.asarray(zeroed).any() and np.asarray(out).any()


@pytest.mark.parametrize("held, conditional", [(8, False), (16, False),
                                               (2, True)])
def test_half_or_all_of_the_router_lowers_without_a_conditional(
        held, conditional):
    rng = np.random.RandomState(17)
    ins = _typed_tokens(rng, 8, 0)
    for name in ("W1", "W3", "W2"):
        ins[name] = [jnp.concatenate([ins[name][0]] * (held // 2))]

    def loss(x, w1):
        return moe_ops.moe_swiglu(
            None, dict(ins, X=[x], W1=[w1]),
            {"top_k": K, "first_expert": 0, "remat": True})["Out"][0].sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        ins["X"][0], ins["W1"][0]).as_text(debug_info=True)
    assert ("stablehlo.case" in text or "stablehlo.if" in text) == conditional
    assert ("moe_full_width" in text) == conditional


def test_the_expert_bias_changes_the_selection_and_not_the_gates():
    rng = np.random.RandomState(11)
    x2 = jnp.asarray(rng.randn(64, H), jnp.float32)
    gate_w = jnp.asarray(rng.randn(H, E) * 0.3, jnp.float32)
    zero = jnp.zeros((E,), jnp.float32)
    bias = jnp.asarray(rng.randn(E) * 0.2, jnp.float32)
    picks0, gates0 = moe_ops.route_sigmoid_topk(x2, gate_w, zero, K, True, 1.0)
    picks1, gates1 = moe_ops.route_sigmoid_topk(x2, gate_w, bias, K, True, 1.0)
    assert (np.sort(picks0, -1) != np.sort(picks1, -1)).any()
    s = jax.nn.sigmoid(x2 @ gate_w)
    for picks, gates in ((picks0, gates0), (picks1, gates1)):
        own = jnp.take_along_axis(s, picks, axis=-1)
        # the gates are the picks' own scores over their sum: no bias in them
        np.testing.assert_allclose(
            gates, own / (own.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # without renormalisation the gate is the raw score, times the factor
    _, raw = moe_ops.route_sigmoid_topk(x2, gate_w, bias, K, False, 2.0)
    np.testing.assert_allclose(
        raw, 2.0 * jnp.take_along_axis(s, picks1, axis=-1), rtol=1e-6)
    # and the bias takes no gradient
    g = jax.grad(lambda b: moe_ops.route_sigmoid_topk(
        x2, gate_w, b, K, True, 1.0)[1].sum())(bias)
    assert not np.asarray(g).any()


def test_the_balancing_rule_moves_every_bias_one_rate_towards_the_mean():
    picks = jnp.asarray([[0, 1], [0, 2], [0, 1], [0, 3]], jnp.int32)
    bias = jnp.asarray([0.5, 0.0, -0.1, 0.2, 0.0, 0.0, 0.0, 0.0])
    # loads 4, 2, 1, 1, 0, 0, 0, 0; mean 1: over, over, at, at, under x 4
    load = moe_ops.expert_load(picks, 8)
    assert load.tolist() == [4, 2, 1, 1, 0, 0, 0, 0]
    got = moe_ops.balance_bias(bias, load, 0.01)
    np.testing.assert_allclose(got, bias + 0.01 * np.asarray(
        [-1, -1, 0, 0, 1, 1, 1, 1]), rtol=1e-6)
    np.testing.assert_array_equal(moe_ops.balance_bias(bias, load, 0.0), bias)


def test_the_layer_balances_its_experts_through_the_buffer():
    """With a rate the buffer is updated in place every step, every entry
    by exactly one rate, and the loads of a lopsided start even out; the
    held experts' rows then sit near tokens * k * held / router width."""
    rate, tokens = 0.02, 512
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[1, tokens, H], dtype="float32",
                        append_batch_size=False)
        out, counts = layers.moe_swiglu(
            x, E, F, experts_held=8, first_expert=8, top_k=K,
            bias_update_rate=rate,
            param_attr=fluid.ParamAttr(initializer=INIT),
            bias_attr=fluid.ParamAttr(initializer=INIT), name="m")
    exe, scope = fluid.Executor(), Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(13)
    # a component all tokens share makes some experts everybody's favourite
    xs = rng.randn(1, tokens, H).astype(np.float32) + 1.5 * rng.randn(H).astype(
        np.float32)
    before = np.asarray(scope.find_var("m.expert_bias")).copy()
    first = exe.run(main, feed={"x": xs}, fetch_list=[counts], scope=scope)[0]
    after = np.asarray(scope.find_var("m.expert_bias"))
    assert set(np.round(np.abs(after - before) / rate, 3)) <= {0.0, 1.0}
    assert (after != before).any()
    for _ in range(80):
        last = exe.run(main, feed={"x": xs}, fetch_list=[counts],
                       scope=scope)[0]
    expected = tokens * K * 8 / E
    assert abs(first.sum() - expected) > 0.15 * expected  # lopsided
    assert abs(last.sum() - expected) < 0.08 * expected  # balanced
    assert last.std() < 0.5 * first.std()


def test_a_share_outside_the_router_is_refused():
    ins = _moe_ins(np.random.RandomState(12), 8, np.zeros(E))
    with pytest.raises(ValueError, match="of a router that is 32 wide"):
        moe_ops.moe_swiglu(None, ins, {"top_k": K, "first_expert": 28})


# ---------------------------------------------------------------------------
# what the benchmark reads: scopes and kernel names
# ---------------------------------------------------------------------------


def _decoder_step(platforms=None, seq=16, heads=(4, 2), hidden=64):
    from paddle_tpu.models.lfm2_moe import (Lfm2MoeConfig,
                                            build_lfm2_moe_pretrain_program)
    import dataclasses

    cfg = dataclasses.replace(
        Lfm2MoeConfig.tiny(), hidden_size=hidden,
        num_attention_heads=heads[0], num_key_value_heads=heads[1],
        experts_held=4, remat_ffn=True, max_position_embeddings=seq)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        _, _, _, loss = build_lfm2_moe_pretrain_program(
            cfg, 2, seq, main_program=main, startup_program=startup)
        with fluid.program_guard(main, startup):
            mixed_precision.decorate(
                fluid.optimizer.AdamOptimizer(1e-3), use_bf16=True).minimize(
                    loss, startup_program=startup)
    exe, scope = fluid.Executor(), Scope()
    exe.run(startup, scope=scope)
    ids = np.zeros((2, seq), np.int32)
    return exe._lower_step(main, feed={"input_ids": ids, "labels": ids},
                           fetch_list=[loss], scope=scope,
                           platforms=platforms)


def test_the_part_scopes_appear_beneath_the_roles_in_the_lowered_step():
    text = _decoder_step().as_text(debug_info=True)
    names = set(re.findall(r'"(jit\(step\)[^"]*)"', text))
    for part in ("rms_norm", "rope", "short_conv", "swiglu_ffn", "moe_route",
                 "moe_dispatch", "moe_experts", "moe_combine"):
        assert any(n.startswith(f"jit(step)/forward/jvp({part})/")
                   for n in names), part
        assert any(n.startswith(f"jit(step)/backward/") and part in n
                   for n in names), part
    # recomputation is emitted at the grad op: backward first, the part after
    assert any(n.startswith("jit(step)/backward/")
               and "moe_experts" in n and "rematted_computation" in n
               for n in names)


def test_the_bsh_flash_calls_are_named_by_mode(described_tpu):
    """Causal calls are `flash_bsh_causal_fwd` / `_bwd`, the others keep
    `flash_bsh_fwd` / `_bwd`: lowered for the TPU from this CPU process,
    heads of 64 at S 128 so that the shape gates pass."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    assert fa._bsh_kernel_name("fwd", False) == "flash_bsh_fwd"
    assert fa._bsh_kernel_name("bwd", False) == "flash_bsh_bwd"
    text = _decoder_step(("tpu",), seq=128, heads=(2, 1),
                         hidden=128).as_text()
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert {"flash_bsh_causal_fwd", "flash_bsh_causal_bwd"} <= kernels
    assert not {"flash_bsh_fwd", "flash_bsh_bwd"} & kernels

    def loss(q, k, v):
        return fa.flash_attention_bsh(
            q, k, v, num_heads=2, causal=False).astype(jnp.float32).sum()

    x = jnp.zeros((2, 128, 128), jnp.bfloat16)
    fa._make_flash_core_bsh.cache_clear()
    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            x, x, x).lower(lowering_platforms=("tpu",)).as_text()
    finally:
        fa._make_flash_core_bsh.cache_clear()
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert kernels == {"flash_bsh_fwd", "flash_bsh_bwd"}
