"""The one-pass kernels of `mhc_post` (ops/pallas/mhc.py), interpreted on
the CPU: the forward against `latent_ops._mhc_post` and the four cotangents
against `jax.vjp` of it, over dtypes, stream counts, widths and row counts
that take more than one row block; the twenty sums over C as float32 against
a float64 sum; the row-block chooser at the Xing4 cell's shapes and its
refusals; the op's gate and the kernels its traced gradient calls; what the
kernel path keeps for the backward pass. Below them the same for `mhc_map`'s two kernels against
`latent_ops._mhc_map`: the three mappings, the gap and the four cotangents
to float32 rounding, with logits beyond both clamps, one round and twenty,
H^T with the tokens on the lanes, the gate, the kernels called, the
residuals."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lowered import calls_in
from paddle_tpu.ops import latent_ops
from paddle_tpu.ops.pallas import backend, feasible, mhc
from paddle_tpu.ops.registry import EmitContext


def _operands(t, n, c, dtype, seed=0, lead=None):
    rng = np.random.RandomState(seed)
    lead = (t,) if lead is None else lead

    def arr(width, dt, lo=None):
        a = (rng.randn(*lead, width) if lo is None
             else rng.uniform(lo, 1.0, lead + (width,)))
        return jnp.asarray(a.astype(np.float32), dt)

    # x, y, h_res, h_post and the cotangent of x'
    return (arr(n * c, dtype), arr(c, dtype), arr(n * n, jnp.float32, 0.0),
            arr(n, jnp.float32, 0.0), arr(n * c, dtype))


def _f64(a):
    return np.asarray(a.astype(jnp.float32), np.float64)


def _close(got, want, dtype):
    """Within a rounding of `dtype` of the composition (whose float32 sums
    XLA's CPU code may contract differently)."""
    eps = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6
    got, want = _f64(got), _f64(want)
    np.testing.assert_allclose(got, want, rtol=2 * eps,
                               atol=2 * eps * np.abs(want).max())


@pytest.mark.parametrize("c", [128, 384, 3584])
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
def test_forward_and_cotangents_against_the_composition(pinned, dtype, n, c):
    x, y, h_res, h_post, g = _operands(16, n, c, dtype)
    rows = mhc.mhc_rows(x, y, h_res, h_post)
    assert rows == 16
    out, vjp = jax.vjp(lambda *a: mhc.mhc_post(*a, rows), x, y, h_res, h_post)
    want_out, want_vjp = jax.vjp(latent_ops._mhc_post, x, y, h_res, h_post)
    assert out.dtype == dtype and out.shape == x.shape
    _close(out, want_out, dtype)
    got, want = vjp(g), want_vjp(g)
    assert [a.dtype for a in got] == [dtype, dtype, jnp.float32, jnp.float32]
    for a, b, dt in zip(got, want, (dtype, dtype, jnp.float32, jnp.float32)):
        assert a.shape == b.shape
        _close(a, b, dt)


@pytest.mark.parametrize("t, c, blocks", [(256, 128, 2), (384, 256, 3),
                                          (256, 384, 2), (40, 128, 1)])
def test_more_than_one_row_block(pinned, t, c, blocks):
    n = 4
    x, y, h_res, h_post, g = _operands(t, n, c, jnp.bfloat16, seed=t)
    rows = mhc.mhc_rows(x, y, h_res, h_post)
    assert rows == min(t, 128) and t // rows == blocks
    out, vjp = jax.vjp(lambda *a: mhc.mhc_post(*a, rows), x, y, h_res, h_post)
    want_out, want_vjp = jax.vjp(latent_ops._mhc_post, x, y, h_res, h_post)
    _close(out, want_out, jnp.bfloat16)
    for a, b, dt in zip(vjp(g), want_vjp(g), (jnp.bfloat16,) * 2
                        + (jnp.float32,) * 2):
        _close(a, b, dt)


@pytest.mark.parametrize("lead, rows", [((2, 128), 128), ((1, 16), 16),
                                        ((3, 2, 128), 128), ((2, 256), 128)])
def test_the_leading_axes_are_sequences_of_rows(pinned, lead, rows):
    n, c = 4, 128
    x, y, h_res, h_post, g = _operands(None, n, c, jnp.bfloat16, lead=lead)
    assert mhc.mhc_rows(x, y, h_res, h_post) == rows
    out, vjp = jax.vjp(lambda *a: mhc.mhc_post(*a, rows), x, y, h_res, h_post)
    want_out, want_vjp = jax.vjp(latent_ops._mhc_post, x, y, h_res, h_post)
    assert out.shape == lead + (n * c,)
    _close(out, want_out, jnp.bfloat16)
    for a, b in zip(vjp(g), want_vjp(g)):
        assert a.shape == b.shape
        _close(a, b, a.dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
def test_the_sums_over_c_are_float32_sums(pinned, dtype):
    """dH_res[i, j] = sum_c dX'_i X_j and dH_post[i] = sum_c dX'_i y over
    3,584 columns: float32 out, and within float32's rounding of the
    float64 sum of the same products, which bf16's is 2 ** 16 times from."""
    t, n, c = 16, 4, 3584
    x, y, h_res, h_post, g = _operands(t, n, c, dtype, seed=3)
    _, _, dh_res, dh_post = jax.vjp(
        lambda *a: mhc.mhc_post(*a, t), x, y, h_res, h_post)[1](g)
    assert dh_res.dtype == dh_post.dtype == jnp.float32
    gs = _f64(g).reshape(t, n, 1, c)
    xs = np.concatenate([_f64(x).reshape(t, 1, n, c),
                         _f64(y).reshape(t, 1, 1, c)], axis=2)
    want = (gs * xs).sum(-1)            # [t, i, j] with j == n for y
    scale = (np.abs(gs) * np.abs(xs)).sum(-1)
    got = np.concatenate([_f64(dh_res).reshape(t, n, n),
                          _f64(dh_post).reshape(t, n, 1)], axis=2)
    assert np.abs(got - want).max() > 0  # not the float64 sum itself
    assert (np.abs(got - want) <= 4e-6 * scale).all()


@pytest.mark.parametrize("t", [4096, 8192])
def test_the_chooser_answers_for_the_cell_from_shapes_alone(t):
    """The cell's step is two sequences of 4,096 tokens, its check program
    one; 8,192 stands for a batch flattened by the caller."""
    rows = mhc.default_mhc_rows(8192 // t, t, 3584, 4, 2)
    assert rows == 128 and t % rows == 0
    # the larger pass decides, and a call asks for its cell and a slack:
    # a quarter of the core's 128 MiB at the cell's bf16 streams
    bwd = feasible.mhc_vmem_bytes("bwd", rows, 3584, 4, 2)
    assert feasible.mhc_vmem_bytes("fwd", rows, 3584, 4, 2) < bwd
    assert bwd + feasible.MHC_VMEM_SLACK < 32 * 2 ** 20
    # float32 streams of that width fit the budget too
    assert mhc.default_mhc_rows(8192 // t, t, 3584, 4, 4) == rows
    assert feasible.mhc_vmem_bytes("bwd", rows, 3584, 4, 4) <= (
        feasible.MHC_VMEM_BUDGET)


@pytest.mark.parametrize("batch, t, c", [
    (2, 4096, 64), (2, 4096, 3584 + 64),  # a width that is not whole lane tiles
    (1, 10, 128), (1, 4, 128),     # rows that are not whole row groups
    (1, 8192 + 64, 3584),          # 128 does not tile them, all are too many
    (2, 64, 128),                  # short sequences, but more than one
])
def test_the_chooser_returns_nothing(batch, t, c):
    assert mhc.default_mhc_rows(batch, t, c, 4, 2) is None
    assert mhc.default_mhc_rows(1, 64, 128, 4, 2) == 64


@pytest.mark.parametrize("c, chunk", [(3584, 256), (384, 128), (128, 128),
                                      (512, 256)])
def test_the_column_loop_takes_256_where_they_divide(c, chunk):
    assert mhc._chunk(c) == chunk


def _op_jaxpr(x, y, h_res, h_post):
    """The op as the Executor lowers it, forward and backward."""
    def op(x, y, h_res, h_post):
        return latent_ops.mhc_post(
            EmitContext(), {"X": [x], "Y": [y], "HRes": [h_res],
                            "HPost": [h_post]}, {})["Out"][0]

    return jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(op(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3)))(x, y, h_res, h_post)


@pytest.mark.parametrize("c, force", [(64, False), (128, False), (64, True)])
def test_off_the_tpu_or_at_64_columns_the_op_is_the_composition(c, force):
    operands = _operands(16, 4, c, jnp.bfloat16)[:4]
    with mock.patch.object(backend, "FORCE_PALLAS", force):
        assert mhc.mhc_rows(*operands) is None
        jaxpr = _op_jaxpr(*operands)
    assert calls_in(jaxpr.jaxpr) == {}


def test_pinned_the_op_is_the_two_kernels_once_each(pinned):
    operands = _operands(16, 4, 128, jnp.bfloat16)[:4]
    assert calls_in(_op_jaxpr(*operands).jaxpr) == {"mhc_post_fwd": 1,
                                                     "mhc_post_bwd": 1}


def test_over_a_mesh_the_op_is_the_composition(pinned):
    from jax.sharding import Mesh

    x, y, h_res, h_post = _operands(16, 4, 128, jnp.bfloat16)[:4]
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    out = latent_ops.mhc_post(
        EmitContext(mesh=mesh), {"X": [x], "Y": [y], "HRes": [h_res],
                                 "HPost": [h_post]}, {})["Out"][0]
    _close(out, latent_ops._mhc_post(x, y, h_res, h_post), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda *a: latent_ops.mhc_post(
        EmitContext(mesh=mesh), {"X": [a[0]], "Y": [a[1]], "HRes": [a[2]],
                                 "HPost": [a[3]]}, {})["Out"][0])(
                                     x, y, h_res, h_post))
    assert "pallas_call" not in text


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.int8])
def test_the_gate_takes_bf16_and_float32_streams_only(pinned, dtype):
    x, y, h_res, h_post = _operands(16, 4, 128, jnp.float32)[:4]
    assert mhc.mhc_rows(x, y, h_res, h_post) == 16
    assert mhc.mhc_rows(x.astype(dtype), y.astype(dtype), h_res,
                        h_post) is None
    assert mhc.mhc_rows(x.astype(jnp.bfloat16), y, h_res, h_post) is None


def test_the_kernel_path_keeps_the_four_inputs_and_nothing_else(pinned):
    """The residuals of the custom_vjp are the op's own operands: every
    value the backward pass is handed is an input of the traced function,
    none is made by the forward pass (no float32 copy of the streams)."""
    operands = _operands(None, 4, 128, jnp.bfloat16, lead=(1, 16))[:4]
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *b: mhc.mhc_post(*b, 16), *a))(*operands).jaxpr
    out, *kept = jaxpr.outvars
    assert 0 < len(kept) <= 4
    assert set(kept) <= set(jaxpr.invars), (kept, jaxpr.invars)
    assert out not in jaxpr.invars
    # and the forward pass is one kernel call
    assert str(jaxpr).count("pallas_call") == 1


# ---------------------------------------------------------------------------
# mhc_map: the three mappings of a sublayer
# ---------------------------------------------------------------------------

MAP = dict(n=4, eps=1e-6, clamp_min=-30.0, clamp_max=30.0)


def _map_operands(b, s, c, dtype, seed=0, spread=1.0):
    """x, phi, bias, alpha and the cotangents of H_pre, H_post, H_res."""
    rng = np.random.RandomState(seed)
    n = MAP["n"]
    m = 2 * n + n * n
    return ((jnp.asarray(rng.randn(b, s, n * c), dtype),
             jnp.asarray(0.05 * rng.randn(n * c, m), jnp.float32),
             jnp.asarray(spread * rng.randn(m), jnp.float32),
             jnp.asarray([0.5, 0.7, 0.9], jnp.float32)),
            tuple(jnp.asarray(rng.randn(b, s, k), jnp.float32)
                  for k in (n, n, n * n)))


def _static(rows, iters):
    return (MAP["n"], MAP["eps"], iters, MAP["clamp_min"], MAP["clamp_max"],
            rows)


def _map_both(operands, cotangents, iters, rows):
    """((values, gap), cotangents) of the kernels and of the composition:
    the op's four outputs (`mhc_map_fwd` behind `mhc.mhc_map`), and the
    cotangents of the four inputs by `mhc_map_bwd` (`mhc.map_cotangents`)
    against `jax.vjp` of `latent_ops._mhc_map`."""
    n = MAP["n"]
    assert mhc.map_rows(operands[0], operands[1], n, iters) == rows
    out = mhc.mhc_map(*operands, rows, iters=iters, **MAP)
    dht = mhc._stacked(*cotangents)
    grads = mhc.map_cotangents(*operands, dht, _static(rows, iters))
    want_out, vjp = jax.vjp(
        lambda *a: latent_ops._mhc_map(*a, iters=iters, **MAP), *operands)
    return (out, grads), (want_out, vjp(cotangents + (jnp.zeros(n),)))


def _map_close(got, want, names, dtype=jnp.float32):
    """To float32 rounding (the streams' own for their cotangent) of the
    array's largest entry: both sides sum thousands of float32 products
    in their own order."""
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        eps = 2.0 ** -8 if (name, dtype) == ("dx", jnp.bfloat16) else 2e-6
        a, b = _f64(a), _f64(b)
        assert np.isfinite(b).all(), name
        # the gap is a distance of sums from 1
        scale = 1.0 if name == "gap" else np.abs(b).max()
        np.testing.assert_allclose(a, b, rtol=0, err_msg=name,
                                   atol=2 * eps * scale)


VALUES = ("pre", "post", "res", "gap")
COTANGENTS = ("dx", "dphi", "dbias", "dalpha")


@pytest.mark.parametrize("b, s, rows", [(1, 128, 128), (2, 256, 256),
                                        (1, 384, 128)],
                         ids=["one_block", "a_block_a_sequence",
                              "three_blocks"])
@pytest.mark.parametrize("c", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
def test_the_mappings_and_their_cotangents_against_the_composition(
        pinned, dtype, c, b, s, rows):
    operands, cotangents = _map_operands(b, s, c, dtype, seed=s + c)
    (out, grads), (want_out, want_grads) = _map_both(operands, cotangents, 20,
                                                     rows)
    n = MAP["n"]
    assert [a.shape for a in out] == [(b, s, n), (b, s, n), (b, s, n * n),
                                      (n,)]
    assert {a.dtype for a in out} == {jnp.dtype(jnp.float32)}
    _map_close(out, want_out, VALUES)
    assert [a.dtype for a in grads] == [dtype] + [jnp.float32] * 3
    _map_close(grads, want_grads, COTANGENTS, dtype)
    # the last division is by the sums over i: those are 1, and the gap
    # is what twenty rounds left of the sums over j
    res = _f64(out[2]).reshape(b, s, n, n)
    assert np.abs(res.sum(-2) - 1).max() < 1e-6
    np.testing.assert_allclose(
        _f64(out[3]), np.abs(res.sum(-1) - 1).max((0, 1)), atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
def test_one_round_is_one_round(pinned, dtype):
    operands, cotangents = _map_operands(1, 128, 128, dtype, seed=5)
    (out, grads), (want_out, want_grads) = _map_both(operands, cotangents, 1,
                                                     128)
    _map_close(out, want_out, VALUES)
    _map_close(grads, want_grads, COTANGENTS, dtype)
    assert float(out[3].max()) > 1e-2     # one round is not enough


def test_logits_beyond_both_clamps(pinned):
    """A bias of +-36 on two entries of H_res' logits each: exp(30) and
    exp(-30) go into the rounds, and nothing comes back through a clamped
    entry."""
    (x, phi, bias, alpha), cotangents = _map_operands(
        1, 128, 128, jnp.float32, seed=9)
    n = MAP["n"]
    bias = bias.at[2 * n + 1].set(36.0).at[2 * n + 6].set(36.5)
    bias = bias.at[2 * n + 8].set(-36.0).at[2 * n + 15].set(-37.0)
    (out, grads), (want_out, want_grads) = _map_both(
        (x, phi, bias, alpha), cotangents, 20, 128)
    logits = float(alpha[2]) * _f64(jnp.einsum(
        "km,tk->mt", phi, x[0] * jax.lax.rsqrt(jnp.mean(x[0] ** 2, -1)
                                               + 1e-6)[:, None])
    )[2 * n:] + _f64(bias)[2 * n:, None]
    assert (logits[[1, 6]] > 30).all() and (logits[[8, 15]] < -30).all()
    _map_close(out, want_out, VALUES)
    _map_close(grads, want_grads, COTANGENTS)
    dbias = np.asarray(grads[2])
    assert (dbias[2 * n:][[1, 6, 8, 15]] == 0).all()
    assert np.count_nonzero(dbias) == dbias.size - 4


def test_the_op_s_cotangents_are_the_composition_s(pinned):
    """`mhc.mhc_map` is `mhc_map_fwd` with the composition's backward pass
    behind it (the backward kernel raises the Xing4 step's compiled peak:
    `mhc._map_core_bwd`): one kernel call forward, none backward."""
    operands, cotangents = _map_operands(1, 128, 128, jnp.bfloat16, seed=2)
    fn = lambda *a: mhc.mhc_map(*a, 128, iters=2, **MAP)[:3]
    ref = lambda *a: latent_ops._mhc_map(*a, iters=2, **MAP)[:3]
    got = jax.vjp(fn, *operands)[1](cotangents)
    want = jax.vjp(ref, *operands)[1](cotangents)
    _map_close(got, want, COTANGENTS, jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(fn, *a)[1](cotangents))(
        *operands))
    assert text.count("name=mhc_map_fwd") == 1 and "mhc_map_bwd" not in text


def test_the_mappings_leave_the_kernel_with_the_tokens_on_the_lanes(pinned):
    (x, phi, bias, alpha), _ = _map_operands(2, 128, 128, jnp.bfloat16)
    kw = dict(n=4, eps=1e-6, iters=2, lo=-30.0, hi=30.0, br=128,
              interpret=True)
    ht = mhc._map_fwd(x, mhc._phi_stack(phi), mhc._coef(bias, alpha, 4), **kw)
    assert ht.shape == (24, 256) and ht.dtype == jnp.float32
    pre, post, res, _ = mhc.mhc_map(x, phi, bias, alpha, 128, iters=2, **MAP)
    np.testing.assert_array_equal(ht[:4].T.reshape(2, 128, 4), pre)
    np.testing.assert_array_equal(ht[8:].T.reshape(2, 128, 16), res)
    # and so do their cotangents, and the sums `mhc_map_bwd` keeps in VMEM
    dx, dphi_t, dcoef = mhc._map_bwd(
        x, mhc._phi_stack(phi), mhc._coef(bias, alpha, 4), jnp.ones_like(ht),
        **kw)
    assert (dx.shape, dx.dtype) == (x.shape, x.dtype)
    assert dphi_t.shape == (24, 512) and dcoef.shape == (48, 128)


def test_the_stack_of_phi_is_its_three_bf16_terms_exactly():
    phi = jnp.asarray(np.random.RandomState(4).randn(512, 24), jnp.float32)
    stack = mhc._phi_stack(phi)
    assert stack.shape == (160, 512) and stack.dtype == jnp.bfloat16
    hi, mid, lo = (_f64(stack[k * 24:(k + 1) * 24]) for k in range(3))
    np.testing.assert_array_equal(hi + mid + lo, _f64(phi).T)
    # the pairs of dX's contraction, and zeros behind them
    for at, term in ((3, hi), (4, hi), (5, mid)):
        np.testing.assert_array_equal(_f64(stack[at * 24:(at + 1) * 24]), term)
    assert not np.asarray(stack[144:]).any()


@pytest.mark.parametrize("batch, s", [(2, 4096), (1, 4096)],
                         ids=["step", "check"])
def test_the_map_chooser_answers_for_the_cell_from_shapes_alone(batch, s):
    assert mhc.default_map_rows(batch, s, 4 * 3584, 4, 2, 20) == 256
    bwd = feasible.mhc_map_vmem_bytes("bwd", 256, 4 * 3584, 4, 2, 20)
    assert feasible.mhc_map_vmem_bytes("fwd", 256, 4 * 3584, 4, 2, 20) < bwd
    assert bwd <= feasible.MHC_VMEM_BUDGET
    # float32 streams of that width: the backward cell at 256 rows is over
    # the budget, at 128 under it
    assert feasible.mhc_map_vmem_bytes("bwd", 256, 4 * 3584, 4, 4, 20) > (
        feasible.MHC_VMEM_BUDGET)
    assert mhc.default_map_rows(batch, s, 4 * 3584, 4, 4, 20) == 128


@pytest.mark.parametrize("s, width, n", [
    (4096, 4 * 64, 4),          # a stream that is not whole lane tiles
    (4096, 4 * 3584 + 128, 4),  # nor is this one
    (4096, 2 * 3584, 2),        # two streams: m = 8
    (64, 512, 4), (4096 + 64, 512, 4),  # 128 rows do not tile them
    (4096, 4 * 128 * 320, 4),   # over the budget at 128 rows too
])
def test_the_map_chooser_returns_nothing(s, width, n):
    assert mhc.default_map_rows(2, s, width, n, 2, 20) is None
    assert mhc.default_map_rows(2, 128, 512, 4, 2, 20) == 128
    assert mhc.default_map_rows(2, 384, 512, 4, 2, 20) == 128
    assert mhc.default_map_rows(2, 512, 512, 4, 2, 20) == 256


def _map_op(ctx, x, phi, bias, alpha, iters=2):
    out = latent_ops.mhc_map(
        ctx, {"X": [x], "Phi": [phi], "Bias": [bias], "Alpha": [alpha]},
        {"streams": 4, "epsilon": 1e-6, "sinkhorn_iters": iters,
         "clamp_min": -30.0, "clamp_max": 30.0})
    return tuple(out[k][0] for k in ("HPre", "HPost", "HRes", "SinkhornGap"))


def _map_op_jaxpr(ctx, operands):
    """The op as the Executor lowers it, forward and backward."""
    return jax.make_jaxpr(jax.grad(
        lambda *a: sum(jnp.sum(h) for h in _map_op(ctx, *a)[:3]),
        argnums=(0, 1, 2, 3)))(*operands)


@pytest.mark.parametrize("c, s, force", [(128, 128, False), (64, 128, True),
                                         (128, 64, True)])
def test_off_the_tpu_at_64_columns_or_rows_the_map_is_the_composition(
        c, s, force):
    operands, _ = _map_operands(1, s, c, jnp.bfloat16)
    with mock.patch.object(backend, "FORCE_PALLAS", force):
        assert mhc.map_rows(operands[0], operands[1], 4, 2) is None
        jaxpr = _map_op_jaxpr(EmitContext(), operands)
    assert calls_in(jaxpr.jaxpr) == {}


def test_pinned_the_map_is_the_forward_kernel_alone(pinned):
    """The forward kernel, once; the backward pass is the composition's
    (`mhc._map_core_bwd` says why), so `mhc_map_bwd` is not called."""
    operands, _ = _map_operands(1, 128, 128, jnp.bfloat16)
    jaxpr = _map_op_jaxpr(EmitContext(), operands)
    assert calls_in(jaxpr.jaxpr) == {"mhc_map_fwd": 1}


def test_over_a_mesh_the_map_is_the_composition(pinned):
    from jax.sharding import Mesh

    operands, _ = _map_operands(1, 128, 128, jnp.bfloat16)
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    jaxpr = _map_op_jaxpr(EmitContext(mesh=mesh), operands)
    assert calls_in(jaxpr.jaxpr) == {}
    got = _map_op(EmitContext(mesh=mesh), *operands)
    want = latent_ops._mhc_map(*operands, iters=2, **MAP)
    _map_close(got, want, VALUES)


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.int8])
def test_the_map_gate_takes_bf16_and_float32_streams_only(pinned, dtype):
    (x, phi, _, _), _ = _map_operands(1, 128, 128, jnp.float32)
    assert mhc.map_rows(x, phi, 4, 20) == 128
    assert mhc.map_rows(x.astype(jnp.bfloat16), phi, 4, 20) == 128
    assert mhc.map_rows(jnp.tile(x, (1, 2, 1)), phi, 4, 20) == 256
    assert mhc.map_rows(x.astype(dtype), phi, 4, 20) is None
    assert mhc.map_rows(x, phi[:, :20], 4, 20) is None
    assert mhc.map_rows(x[0], phi, 4, 20) is None


def test_the_map_kernels_keep_the_four_inputs_and_nothing_else(pinned):
    """As `mhc_post`'s: every value the backward pass is handed is an input
    of the traced function (no float32 copy of the streams, no stack of
    Phi, no round of Sinkhorn), and the forward pass is one kernel call."""
    operands, _ = _map_operands(1, 128, 128, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *b: mhc.mhc_map(*b, 128, iters=2, **MAP), *a))(*operands).jaxpr
    kept = jaxpr.outvars[4:]   # behind the op's four outputs
    assert 0 < len(kept) <= 4
    assert set(kept) <= set(jaxpr.invars), (kept, jaxpr.invars)
    assert str(jaxpr).count("pallas_call") == 1
