"""The grouped-matmul kernels of `moe_swiglu`'s expert products
(ops/pallas/grouped_matmul.py), interpreted on the CPU: each of the three
forms against a dense loop over the groups, at group layouts that put a
boundary inside a row tile, leave a group empty, fill the buffer or leave
rows behind the last group (NaN going in: nobody may read them); the list
of visits the grid walks; the chooser's tiles at the LFM2 cell's operands
and its refusals; the gate; the counter."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention
from paddle_tpu.ops.pallas import feasible
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.telemetry import get_registry

ROWS, K, N = 1024, 384, 512  # four row tiles of 256; the kernels loop over 128 or 256 columns

LAYOUTS = {
    "uneven": [100, 333, 290, 61],
    "an_empty_group": [100, 0, 333, 290],
    "empty_first_and_last": [0, 500, 300, 0],
    "a_boundary_inside_every_tile": [130, 260, 250, 270],
    "boundaries_on_the_tiles": [256, 512, 0, 256],
    "all_rows_in_one_group": [0, 0, 700, 0],
    "three_groups_in_one_tile": [10, 20, 30, 400],
    "the_buffer_full": [300, 200, 500, 24],
    "no_rows_at_all": [0, 0, 0, 0],
}


@pytest.fixture
def pinned():
    with mock.patch.object(attention, "FORCE_PALLAS", True):
        yield


def _operands(sizes, dtype, seed=0):
    rng = np.random.RandomState(seed)
    present = sum(sizes)

    def rows(width):
        a = rng.randn(ROWS, width).astype(np.float32)
        a[present:] = np.nan  # behind the last group: nobody's to read
        return jnp.asarray(a, dtype)

    return (rows(K), rows(N), jnp.asarray(rng.randn(len(sizes), K, N), dtype),
            jnp.asarray(sizes, jnp.int32))


def _dense(form, lhs, d_out, rhs, sizes):
    """The form by a loop over the groups, float64 on the host; rows behind
    the last group stay NaN."""
    lhs, d_out, rhs = (np.asarray(a, np.float64) for a in (lhs, d_out, rhs))
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    if form == "tn":
        return np.stack([lhs[lo:hi].T @ d_out[lo:hi]
                         for lo, hi in zip(bounds, bounds[1:])])
    out = np.full((ROWS, N if form == "nn" else K), np.nan)
    for g, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
        out[lo:hi] = (lhs[lo:hi] @ rhs[g] if form == "nn"
                      else d_out[lo:hi] @ rhs[g].T)
    return out


def _form(form, lhs, d_out, rhs, sizes):
    a, b = {"nn": (lhs, rhs), "nt": (d_out, rhs), "tn": (lhs, d_out)}[form]
    return gm._run(form, a, b, sizes, rhs.shape)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("form", gm.FORMS)
def test_a_form_against_the_dense_loop(pinned, form, layout, dtype):
    sizes = LAYOUTS[layout]
    lhs, d_out, rhs, group_sizes = _operands(sizes, dtype)
    assert gm.gmm_tiles(form, lhs, rhs.shape) is not None
    got = np.asarray(_form(form, lhs, d_out, rhs, group_sizes), np.float64)
    want = _dense(form, lhs, d_out, rhs, sizes)
    present = sum(sizes)
    if form != "tn":
        got, want = got[:present], want[:present]
    assert np.isfinite(got).all()  # no NaN row was read
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    scale = max(np.abs(want).max(initial=0.0), 1.0)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale)


@pytest.mark.parametrize("form", gm.FORMS)
def test_a_form_agrees_with_ragged_dot_where_both_run(pinned, form):
    lhs, d_out, rhs, group_sizes = _operands(LAYOUTS["the_buffer_full"],
                                             jnp.bfloat16, seed=1)
    a, b = {"nn": (lhs, rhs), "nt": (d_out, rhs), "tn": (lhs, d_out)}[form]
    got = gm._run(form, a, b, group_sizes, rhs.shape)
    want = gm._RAGGED_DOT[form](a, b, group_sizes, rhs.shape)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=0.25)


@pytest.mark.parametrize("layout", ["uneven", "an_empty_group"])
def test_the_gradients_are_the_two_transposes(pinned, layout):
    """`jax.grad` through the custom_vjp, kernels pinned, against autodiff
    of `jax.lax.ragged_dot`."""
    sizes = LAYOUTS[layout]
    lhs, d_out, rhs, group_sizes = _operands(sizes, jnp.float32, seed=2)
    present = sum(sizes)
    lhs, w = jnp.nan_to_num(lhs), jnp.nan_to_num(d_out)

    def loss(product):
        return lambda l, r: jnp.sum((product(l, r, group_sizes) * w)[:present])

    got = jax.grad(loss(gm.grouped_matmul), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(jax.lax.ragged_dot), argnums=(0, 1))(lhs, rhs)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g[:present] if g.ndim == 2 else g,
                                   r[:present] if r.ndim == 2 else r,
                                   rtol=1e-5, atol=1e-4)


def _visits_by_hand(sizes, rows, tm):
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    tiles = rows // tm
    out = []
    for g, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
        if hi == lo:
            out.append((g, min(lo // tm, tiles - 1)))
        else:
            out += [(g, t) for t in range(lo // tm, -(-hi // tm))]
    return out


@pytest.mark.parametrize("tm", [128, 256])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_visits_follow_the_rows_present(layout, tm):
    sizes = LAYOUTS[layout]
    v = gm.group_visits(jnp.asarray(sizes, jnp.int32), ROWS, tm)
    want = _visits_by_hand(sizes, ROWS, tm)
    count = int(v.count)
    assert count == len(want) <= ROWS // tm + len(sizes) - 1
    assert list(zip(v.group[:count].tolist(), v.tile[:count].tolist())) == want
    assert v.offsets.tolist() == np.concatenate(
        [[0], np.cumsum(sizes)]).tolist()
    # tiles are revisited consecutively only, and nothing behind the last
    # group is on the list
    assert (np.diff(v.tile[:count]) >= 0).all()
    assert max(t for _, t in want) <= max(sum(sizes) - 1, 0) // tm
    # behind the list the group is nobody's, so the last visit ends its group
    assert (np.asarray(v.group[count:]) == len(sizes)).all()
    assert v.group.shape == v.tile.shape == (ROWS // tm + len(sizes) + 1,)


def test_the_buffer_does_not_add_visits():
    sizes = jnp.asarray([2000, 2100, 1990, 2050], jnp.int32)
    counts = [int(gm.group_visits(sizes, rows, 256).count)
              for rows in (8192, 32768, 65536)]
    assert counts[0] == counts[1] == counts[2] == len(
        _visits_by_hand(sizes.tolist(), 8192, 256))


@pytest.mark.parametrize("form, k, n, want", [
    # the LFM2 cell: [rows, 2048] x [8, 2048, 1792] (W1, W3) and
    # [rows, 1792] x [8, 1792, 2048] (W2), bf16: the whole matrix
    ("nn", 2048, 1792, (256, 2048, 1792)),
    ("nn", 1792, 2048, (256, 1792, 2048)),
    ("nt", 2048, 1792, (256, 2048, 1792)),
    ("nt", 1792, 2048, (256, 1792, 2048)),
    ("tn", 2048, 1792, (256, 2048, 1792)),
    ("tn", 1792, 2048, (256, 1792, 2048)),
])
@pytest.mark.parametrize("rows", [32768, 65536, 8192])
def test_the_chooser_at_the_cells_operands(form, k, n, want, rows):
    assert gm.default_gmm_tiles(form, rows, k, n, 2) == want
    assert feasible.gmm_vmem_bytes(form, *want, k, n, 2) <= (
        feasible.GMM_VMEM_BUDGET)


@pytest.mark.parametrize("form", gm.FORMS)
@pytest.mark.parametrize("rows, k, n", [
    (32768, 2048, 1800),  # a lane dimension that is no multiple of 128
    (32768, 2000, 1792),
    (32768, 64, 24),
    (192, 2048, 1792),  # rows that no row tile divides
])
def test_the_chooser_refuses_what_it_cannot_tile(form, rows, k, n):
    assert gm.default_gmm_tiles(form, rows, k, n, 2) is None


@pytest.mark.parametrize("form", gm.FORMS)
def test_the_chooser_narrows_the_tile_until_it_fits(form):
    """float32 matrices of 4096 x 4096 do not fit whole: the tile is the
    widest that does, every extent a multiple of 128 that divides its
    axis."""
    tm, tk, tn = gm.default_gmm_tiles(form, 16384, 4096, 4096, 4)
    assert tm == 256 and 4096 % tk == 0 and 4096 % tn == 0
    assert tk % 128 == 0 and tn % 128 == 0 and tk * tn < 4096 * 4096
    assert feasible.gmm_vmem_bytes(form, tm, tk, tn, 4096, 4096, 4) <= (
        feasible.GMM_VMEM_BUDGET)
    assert {"nn": tk, "nt": tn}.get(form, 4096) == 4096  # never the contraction
    # rows that only 128 divides take the smaller row tile
    assert gm.default_gmm_tiles(form, 384, 256, 128, 2)[0] == 128


def test_the_widths_of_1792():
    assert gm._widths(1792) == [1792, 896, 256, 128]
    assert gm._widths(2048) == [2048, 1024, 512, 256, 128]


@pytest.mark.parametrize("width, chunk", [(1792, 256), (2048, 256),
                                          (896, 128), (384, 128), (128, 128)])
def test_the_inner_loop_takes_256_columns_where_they_divide(width, chunk):
    assert gm._chunk(width) == chunk


def _lowerings():
    series = get_registry().snapshot().get(
        "moe_grouped_product_lowerings_total", {"series": []})["series"]
    return {(s["labels"]["impl"], s["labels"]["form"]): s["value"]
            for s in series}


def test_off_the_tpu_every_form_is_ragged_dot_and_the_counter_says_so():
    lhs, d_out, rhs, group_sizes = _operands(LAYOUTS["uneven"], jnp.float32)
    for form in gm.FORMS:
        assert gm.gmm_tiles(form, lhs, rhs.shape) is None
    before = _lowerings()
    text = str(jax.make_jaxpr(jax.grad(
        lambda l, r: jnp.sum(gm.grouped_matmul(l, r, group_sizes)[:10]),
        argnums=(0, 1)))(jnp.nan_to_num(lhs), rhs))
    assert text.count("= ragged_dot_general[") == 3
    assert "pallas_call" not in text
    after = _lowerings()
    for form in gm.FORMS:
        assert after[("ragged_dot", form)] == before.get(
            ("ragged_dot", form), 0) + 1
        assert after.get(("pallas", form), 0) == before.get(
            ("pallas", form), 0)


def test_pinned_the_counter_counts_the_kernel(pinned):
    lhs, _, rhs, group_sizes = _operands(LAYOUTS["uneven"], jnp.float32)
    before = _lowerings().get(("pallas", "nn"), 0)
    jax.jit(gm.grouped_matmul).lower(lhs, rhs, group_sizes)
    assert _lowerings()[("pallas", "nn")] == before + 1


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.int8])
def test_the_gate_takes_bf16_and_float32_rows_only(pinned, dtype):
    lhs = jnp.zeros((ROWS, K), dtype)
    assert gm.gmm_tiles("nn", lhs, (4, K, N)) is None


@pytest.mark.parametrize("kernels", [("nt",), ("nn", "tn"), ()])
def test_a_form_that_is_not_named_keeps_ragged_dot(pinned, kernels):
    """`moe_swiglu`'s dropless fallback names the forms that may take the
    kernel; the others are `ragged_dot` whatever the gate says."""
    lhs, d_out, rhs, group_sizes = _operands(LAYOUTS["uneven"], jnp.float32)
    lhs = jnp.nan_to_num(lhs)
    text = str(jax.make_jaxpr(jax.grad(
        lambda l, r: jnp.sum(
            gm.grouped_matmul(l, r, group_sizes, kernels)[:10]),
        argnums=(0, 1)))(lhs, rhs))
    assert text.count("= ragged_dot_general[") == 3 - len(kernels)
    for form in gm.FORMS:
        assert (f"moe_gmm_{form}" in text) == (form in kernels)
