"""The one-pass kernels of `mhc_post` (ops/pallas/mhc.py), interpreted on
the CPU: the forward against `latent_ops._mhc_post` and the four cotangents
against `jax.vjp` of it, over dtypes, stream counts, widths and row counts
that take more than one row block; the twenty sums over C as float32 against
a float64 sum; the row-block chooser at the Xing4 cell's shapes and its
refusals; the op's gate and its counter; what the kernel path keeps for the
backward pass."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention, latent_ops
from paddle_tpu.ops.pallas import feasible, mhc
from paddle_tpu.ops.registry import EmitContext
from paddle_tpu.telemetry import get_registry


@pytest.fixture
def pinned():
    with mock.patch.object(attention, "FORCE_PALLAS", True):
        yield


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


def _lowerings(impl):
    return get_registry().counter("mhc_post_lowerings_total",
                                  impl=impl).value


def _op_jaxpr(x, y, h_res, h_post):
    """The op as the Executor lowers it, forward and backward."""
    def op(x, y, h_res, h_post):
        return latent_ops.mhc_post(
            EmitContext(), {"X": [x], "Y": [y], "HRes": [h_res],
                            "HPost": [h_post]}, {})["Out"][0]

    return str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(op(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3)))(x, y, h_res, h_post))


@pytest.mark.parametrize("c, force", [(64, False), (128, False), (64, True)])
def test_off_the_tpu_or_at_64_columns_the_op_is_the_composition(c, force):
    operands = _operands(16, 4, c, jnp.bfloat16)[:4]
    before = _lowerings("jnp"), _lowerings("pallas")
    with mock.patch.object(attention, "FORCE_PALLAS", force):
        assert mhc.mhc_rows(*operands) is None
        text = _op_jaxpr(*operands)
    assert "pallas_call" not in text
    assert (_lowerings("jnp"), _lowerings("pallas")) == (before[0] + 1,
                                                         before[1])


def test_pinned_the_op_is_the_two_kernels_and_the_counter_says_so(pinned):
    operands = _operands(16, 4, 128, jnp.bfloat16)[:4]
    before = _lowerings("jnp"), _lowerings("pallas")
    text = _op_jaxpr(*operands)
    assert "name=mhc_post_fwd" in text and "name=mhc_post_bwd" in text
    assert (_lowerings("jnp"), _lowerings("pallas")) == (before[0],
                                                         before[1] + 1)


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
