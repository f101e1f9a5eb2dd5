"""The grouped-matmul kernels of `moe_swiglu`'s expert products
(ops/pallas/grouped_matmul.py), interpreted on the CPU: each of the three
forms against a dense loop over the groups, at group layouts that put a
boundary inside a row tile, leave a group empty, fill the buffer or leave
rows behind the last group (NaN going in: nobody may read them); the list
of visits the grid walks; the same forms at matrices one of whose axes is
a multiple of 64 and not of 128 (one whole block, the inner loop's tail);
the chooser's tiles at the three decoder cells' operands and its refusals;
the gate; the counter."""
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


def _operands(sizes, dtype, seed=0, k=K, n=N):
    rng = np.random.RandomState(seed)
    present = sum(sizes)

    def rows(width):
        a = rng.randn(ROWS, width).astype(np.float32)
        a[present:] = np.nan  # behind the last group: nobody's to read
        return jnp.asarray(a, dtype)

    return (rows(k), rows(n), jnp.asarray(rng.randn(len(sizes), k, n), dtype),
            jnp.asarray(sizes, jnp.int32))


def _dense(form, lhs, d_out, rhs, sizes):
    """The form by a loop over the groups, float64 on the host; rows behind
    the last group stay NaN."""
    lhs, d_out, rhs = (np.asarray(a, np.float64) for a in (lhs, d_out, rhs))
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    if form == "tn":
        return np.stack([lhs[lo:hi].T @ d_out[lo:hi]
                         for lo, hi in zip(bounds, bounds[1:])])
    out = np.full((ROWS, rhs.shape[2 if form == "nn" else 1]), np.nan)
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


# matrices with one axis that is a multiple of 64 and not of 128, as the
# Nemotron cell's 1856: on the result's columns and on the contraction of
# each form; 576 = 2 x 256 + 64 runs the rolled loop and then the tail,
# 192 the tail alone
RAGGED = {"k256_n192": (256, 192), "k192_n256": (192, 256),
          "k384_n576": (384, 576), "k576_n384": (576, 384)}


@pytest.mark.parametrize("layout", ["uneven", "an_empty_group"])
@pytest.mark.parametrize("widths", sorted(RAGGED))
@pytest.mark.parametrize("form", gm.FORMS)
def test_a_form_at_an_axis_of_half_lane_tiles(pinned, form, widths, layout):
    """One whole block at the axis no 128 divides, against the dense loop
    and against `ragged_dot`; rows behind the last group hold NaN."""
    k, n = RAGGED[widths]
    sizes = LAYOUTS[layout]
    lhs, d_out, rhs, group_sizes = _operands(sizes, jnp.bfloat16, seed=3,
                                             k=k, n=n)
    a, b = {"nn": (lhs, rhs), "nt": (d_out, rhs), "tn": (lhs, d_out)}[form]
    assert gm.gmm_tiles(form, a, rhs.shape) == (256, k, n)
    got = gm._run(form, a, b, group_sizes, rhs.shape)
    xla = gm._RAGGED_DOT[form](a, b, group_sizes, rhs.shape)
    assert got.dtype == xla.dtype and got.shape == xla.shape
    got, xla = (np.asarray(x, np.float64) for x in (got, xla))
    want = _dense(form, lhs, d_out, rhs, sizes)
    if form != "tn":
        present = sum(sizes)
        got, xla, want = got[:present], xla[:present], want[:present]
    assert np.isfinite(got).all()  # no NaN row was read
    scale = max(np.abs(want).max(initial=0.0), 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2 * scale)
    np.testing.assert_allclose(got, xla, rtol=2e-2, atol=0.25)


@pytest.mark.parametrize("layout", ["uneven", "an_empty_group"])
@pytest.mark.parametrize("widths", ["k256_n192", "k192_n256"])
def test_the_gradients_at_an_axis_of_half_lane_tiles(pinned, widths, layout):
    """`jax.grad` through the custom_vjp at such matrices, against autodiff
    of `jax.lax.ragged_dot`."""
    k, n = RAGGED[widths]
    sizes = LAYOUTS[layout]
    lhs, d_out, rhs, group_sizes = _operands(sizes, jnp.float32, seed=4,
                                             k=k, n=n)
    present = sum(sizes)
    lhs, w = jnp.nan_to_num(lhs), jnp.nan_to_num(d_out)

    def loss(product):
        return lambda l, r: jnp.sum((product(l, r, group_sizes) * w)[:present])

    text = str(jax.make_jaxpr(jax.grad(loss(gm.grouped_matmul),
                                       argnums=(0, 1)))(lhs, rhs))
    assert all(f"moe_gmm_{form}" in text for form in gm.FORMS)
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


def _cell_cases(widths, row_counts):
    return [pytest.param(form, k, n, rows, id=f"{form}-{k}x{n}-{rows}")
            for k, n in widths for form in gm.FORMS for rows in row_counts]


@pytest.mark.parametrize("form, k, n, rows", (
    # the LFM2 cell: [rows, 2048] x [8, 2048, 1792] (W1, W3) and
    # [rows, 1792] x [8, 1792, 2048] (W2)
    _cell_cases([(2048, 1792), (1792, 2048)], [32768, 65536, 8192])
    # the Xing4 cell: 3584 x 1024 and back, 8,192 rows bounded, 65,536 in
    # the fallback, 4,096 in the check program
    + _cell_cases([(3584, 1024), (1024, 3584)], [8192, 65536, 4096])
    # the Nemotron cell: 2688 x 1856 (W1) and 1856 x 2688 (W2), 1856 =
    # 14.5 x 128 as one block: 6,144 rows bounded, 49,152 in the fallback,
    # 3,072 in the check program
    + _cell_cases([(2688, 1856), (1856, 2688)], [6144, 49152, 3072])))
def test_the_chooser_at_the_cells_operands(form, k, n, rows):
    """bf16, every form: the group's whole matrix, at a row tile of 256."""
    want = (256, k, n)
    assert gm.default_gmm_tiles(form, rows, k, n, 2) == want
    assert feasible.gmm_vmem_bytes(form, *want, k, n, 2) <= (
        feasible.GMM_VMEM_BUDGET)


def test_the_vmem_model_counts_a_ragged_lane_extent_padded():
    """1856 columns lie in 15 lane tiles: the model counts 1920, so that it
    stays over what Mosaic allocates, and a multiple of 128 as it is."""
    for form in gm.FORMS:
        assert feasible.gmm_vmem_bytes(form, 256, 2688, 1856, 2688, 1856, 2) \
            == feasible.gmm_vmem_bytes(form, 256, 2688, 1920, 2688, 1920, 2)
        assert feasible.gmm_vmem_bytes(form, 256, 1856, 2688, 1856, 2688, 2) \
            == feasible.gmm_vmem_bytes(form, 256, 1920, 2688, 1920, 2688, 2)
    # Mosaic's own, bisected for a described v5e (PR 35), in MiB
    for form, tiles, mosaic in [("nn", (256, 2688, 1856), 21.2),
                                ("nt", (256, 2688, 1856), 22.5),
                                ("tn", (256, 2688, 1856), 44.8),
                                ("nn", (256, 1856, 2688), 21.8),
                                ("nt", (256, 1856, 2688), 23.2),
                                ("tn", (256, 1856, 2688), 40.1)]:
        model = feasible.gmm_vmem_bytes(form, *tiles, *tiles[1:], 2) / 2 ** 20
        assert mosaic <= model <= 1.35 * mosaic, (form, tiles, model)


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


@pytest.mark.parametrize("n, want", [
    (2688, [2688, 896, 384, 128]),  # 21 x 128
    (1856, [1856]),  # 14.5 x 128: the whole axis or nothing
    (192, [192]), (64, [64]),
    (1800, []), (2000, []), (24, []), (96, []),
])
def test_the_widths_of_an_axis_no_128_divides(n, want):
    assert gm._widths(n) == want


@pytest.mark.parametrize("form", gm.FORMS)
def test_a_ragged_axis_is_never_tiled(form):
    """float32 matrices of 4096 x 4032 do not fit whole: the axis of 4096
    narrows, the one of 63 x 64 stays whole or the chooser refuses."""
    for k, n in [(4096, 4032), (4032, 4096)]:
        tiles = gm.default_gmm_tiles(form, 16384, k, n, 4)
        if tiles is None:
            continue
        tm, tk, tn = tiles
        assert (tk if k == 4032 else tn) == 4032
        assert feasible.gmm_vmem_bytes(form, tm, tk, tn, k, n, 4) <= (
            feasible.GMM_VMEM_BUDGET)


@pytest.mark.parametrize("width, chunk", [(1792, 256), (2048, 256),
                                          (896, 128), (384, 128), (128, 128)])
def test_the_inner_loop_takes_256_columns_where_they_divide(width, chunk):
    assert gm._chunk(width) == chunk


@pytest.mark.parametrize("width, chunk, whole, tail", [
    (1856, 256, 7, 64),   # the tail starts at the lane-aligned 1792
    (2688, 128, 21, 0),
    (1792, 256, 7, 0),
    (576, 256, 2, 64),
    (256, 256, 1, 0),     # one chunk: no loop
    (192, 256, 0, 192),   # the tail alone
])
def test_the_inner_loop_and_its_tail(width, chunk, whole, tail):
    """`_over_chunks` hands the body every column once: `whole` chunks
    through one rolled trip, then one static tail under a mask built at
    the tail's width."""
    assert gm._chunk(width) == chunk
    seen = []

    def body(cols, mask):
        seen.append((cols.start, cols.size, mask))

    jaxpr = jax.make_jaxpr(lambda: gm._over_chunks(
        width, chunk, lambda columns: ("mask", columns), body))()
    loops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in ("scan",
                                                                 "while")]
    assert len(loops) == (1 if whole > 1 else 0)
    if whole > 1:
        assert loops[0].params.get("length", whole) == whole
    # inside the rolled trip the chunk's start is the loop's counter
    got = [(start if isinstance(start, int) else "rolled", size, mask)
           for start, size, mask in seen]
    want = [("rolled" if whole > 1 else 0, chunk, ("mask", chunk))][:whole]
    if tail:
        want.append((whole * chunk, tail, ("mask", tail)))
    assert got == want


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
