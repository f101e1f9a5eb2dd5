"""The state-space scan's two kernels (ops/pallas/ssd_scan.py), interpreted
on the CPU: the forward pass and the cotangents of x, dt, A, B, C and D
against the chunked composition `ssm_ops._ssd_chunked` and against the
recurrence computed position by position in float64 (`_recurrence64` of
test_nemotron_ops), over three chunks (the state carried forward and, in
the backward kernel, back), two groups of eight heads (each head reads its
own group), one group split into blocks of sixteen heads (at chunk 128
and 256), a row continued with dt = 0, bf16 and float32; the gate's
refusals and the composition they leave bit for bit; the scopes, roles and
counters of a `mamba2` step with the kernels pinned; the VMEM model at the
Nemotron and Granite cells' shapes."""
import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.executor import Scope
from paddle_tpu.ops import attention, ssm_ops
from paddle_tpu.ops.pallas import feasible
from paddle_tpu.ops.pallas import ssd_scan as ssd
from paddle_tpu.telemetry import get_registry
from test_lfm2_ops import INIT, _rel, _run
from test_nemotron_ops import _recurrence64

NAMES = ("x", "dt", "a", "b", "c", "d")
# the smallest shapes the gate admits: chunk and state one lane tile, two
# groups of eight heads of 16 columns (a group's x one lane tile)
GROUPS, PER, P, N, Q = 2, 8, 16, 128, 128


@pytest.fixture
def pinned():
    with mock.patch.object(attention, "FORCE_PALLAS", True):
        yield


def _inputs(seq, seed=0, batch=2, p=P, h=GROUPS * PER, groups=GROUPS):
    """Two batch rows; head 0 hardly decays, head 1 forgets within a few
    positions, the rest in between; dt as softplus gives it in the cell."""
    rng = np.random.default_rng(seed)
    a = -rng.uniform(1.0, 16.0, h)
    a[0], a[1] = -1e-3, -40.0
    return dict(
        x=rng.normal(size=(batch, seq, h, p)),
        dt=np.log1p(np.exp(rng.normal(-3.0, 1.0, (batch, seq, h)))),
        a=a, b=rng.normal(size=(batch, seq, groups, N)) / np.sqrt(N),
        c=rng.normal(size=(batch, seq, groups, N)) / np.sqrt(N),
        d=rng.normal(size=h))


def _arrays(ins, dtype):
    """x, B and C in `dtype`, the rest float32, as `mamba2` hands them."""
    return [jnp.asarray(ins[k], dtype if k in ("x", "b", "c") else jnp.float32)
            for k in NAMES]


def _vjp(fn, args, g):
    y, back = jax.vjp(fn, *args)
    return [y] + list(back(g))


def _composition(chunk=Q):
    return jax.checkpoint(functools.partial(ssm_ops._ssd_chunked,
                                            chunk=chunk))


def _kernels(*args):
    return ssd.ssd_scan(*args, Q)


def test_forward_and_every_cotangent_against_the_composition_float32(pinned):
    ins = _inputs(3 * Q)
    args = _arrays(ins, jnp.float32)
    g = jnp.asarray(np.random.default_rng(1).normal(size=ins["x"].shape),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _vjp(_composition(), args, g)
    got = _vjp(_kernels, args, g)
    for name, u, v in zip(("y",) + NAMES, got, want):
        assert u.dtype == v.dtype and u.shape == v.shape, name
        assert _rel(u, v) < 2e-5, (name, _rel(u, v))


@pytest.mark.parametrize("heads, chunk, seq, blocks", [
    (32, Q, 3 * Q, 2), (64, 2 * Q, 4 * Q, 4)], ids=["32_heads_q128",
                                                   "64_heads_q256"])
def test_a_split_group_against_the_composition(pinned, heads, chunk, seq,
                                               blocks):
    """One group of B and C over more heads than a cell takes: blocks of
    sixteen heads, each reading the group's B and C; dB and dC are the sums
    of the blocks' partials (a block left out reads (blocks - 1) / blocks
    off)."""
    ins = _inputs(seq, seed=13, batch=1, h=heads, groups=1)
    args = _arrays(ins, jnp.float32)
    assert ssd.head_blocks(seq, heads, P, 1, N, chunk, jnp.float32) == blocks
    g = jnp.asarray(np.random.default_rng(14).normal(size=ins["x"].shape),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _vjp(_composition(chunk), args, g)
    got = _vjp(lambda *t: ssd.ssd_scan(*t, chunk), args, g)
    for name, u, v in zip(("y",) + NAMES, got, want):
        assert u.dtype == v.dtype and u.shape == v.shape, name
        assert _rel(u, v) < 2e-5, (name, _rel(u, v))


def test_forward_and_every_cotangent_against_the_recurrence(pinned):
    """Forward against the float64 recurrence; each cotangent along a
    random direction against the recurrence's central difference."""
    ins = _inputs(3 * Q, seed=3, batch=1)
    want = _recurrence64(**ins)
    w = np.random.default_rng(4).uniform(0.5, 1.5, want.shape)
    args = _arrays(ins, jnp.float32)
    got = _vjp(_kernels, args, jnp.asarray(w, jnp.float32))
    assert _rel(got[0], want) < 1e-5
    rng = np.random.default_rng(5)
    for name, grad in zip(NAMES, got[1:]):
        assert np.all(np.isfinite(np.asarray(grad))), name
        v = rng.normal(size=ins[name].shape)
        eps = 1e-6
        plus = np.sum(_recurrence64(**{**ins, name: ins[name] + eps * v}) * w)
        minus = np.sum(_recurrence64(**{**ins, name: ins[name] - eps * v}) * w)
        want_dir = (plus - minus) / (2 * eps)
        got_dir = float(np.sum(np.asarray(grad, np.float64) * v))
        assert abs(got_dir - want_dir) < 2e-4 * max(abs(want_dir), 1.0), name


def test_bf16_is_as_close_to_float32_as_the_composition(pinned):
    """bf16 x, B and C: the kernels' forward pass and cotangents lie as near
    the float32 answer for the same inputs as the composition's bf16 pass,
    within a rounding of bf16 either way. Heads of 64 columns, as the
    Nemotron cell's (XLA's CPU runtime takes no bf16 x bf16 -> float32
    product of 16 columns)."""
    ins = _inputs(3 * Q, seed=6, batch=1, p=64)
    args16 = _arrays(ins, jnp.bfloat16)
    g = jnp.asarray(np.random.default_rng(7).normal(size=ins["x"].shape),
                    jnp.bfloat16)
    exact = [a.astype(jnp.float32) for a in args16]
    # (nor one of the composition's at the default precision; its products
    # of bf16 values are exact at either)
    with jax.default_matmul_precision("highest"):
        want = _vjp(_composition(), exact, g.astype(jnp.float32))
        comp = _vjp(_composition(), args16, g)
    got = _vjp(_kernels, args16, g)
    for name, u, c, v in zip(("y",) + NAMES, got, comp, want):
        assert u.dtype == c.dtype, name
        assert _rel(u, v) < max(2 * _rel(c, v), 2.0 ** -8), (
            name, _rel(u, v), _rel(c, v))


def test_a_ragged_row_is_continued_with_dt_zero(pinned):
    """ssm_ops.ssd_scan pads 300 positions to three chunks with dt = 0: the
    kernels run (the gate sees whole chunks) and the answer is the
    recurrence's over the 300."""
    ins = _inputs(300, seed=8, batch=1)
    want = _recurrence64(**ins)
    args = _arrays(ins, jnp.float32)
    assert ssm_ops.scan_kernels((1, 3 * Q, GROUPS * PER, P),
                                (1, 3 * Q, GROUPS, N), Q, jnp.float32)
    got = ssm_ops.ssd_scan(*args, Q)
    assert got.shape == want.shape and _rel(got, want) < 1e-5
    w = np.random.default_rng(9).uniform(0.5, 1.5, want.shape)
    grads = jax.grad(lambda *t: jnp.sum(ssm_ops.ssd_scan(*t, Q) * w),
                     argnums=tuple(range(6)))(*args)
    with jax.default_matmul_precision("highest"):
        plain = jax.grad(
            lambda *t: jnp.sum(ssm_ops._ssd_chunked(
                *[jnp.pad(u, ((0, 0), (0, 84)) + ((0, 0),) * (u.ndim - 2))
                  if u.ndim > 1 else u for u in t], chunk=Q)[:, :300] * w),
            argnums=tuple(range(6)))(*args)
    for name, u, v in zip(NAMES, grads, plain):
        assert _rel(u, v) < 2e-5, name


def test_the_state_is_carried_and_each_head_reads_its_group(pinned):
    """With the groups' B and C swapped, or the rows cut where chunks meet,
    the kernels' answer is another one: both show at these sizes."""
    ins = {**_inputs(3 * Q, seed=10, batch=1), "d": np.zeros(GROUPS * PER)}
    want = _recurrence64(**ins)
    got = np.asarray(_kernels(*_arrays(ins, jnp.float32)))
    assert _rel(got, want) < 1e-5
    swapped = {**ins, "b": ins["b"][:, :, ::-1], "c": ins["c"][:, :, ::-1]}
    assert _rel(_recurrence64(**swapped), want) > 0.1
    cut = np.concatenate([
        _recurrence64(**{k: (v[:, z:z + Q] if v.ndim > 1 else v)
                         for k, v in ins.items()}) for z in range(0, 3 * Q, Q)],
        axis=1)
    assert _rel(cut, want) > 0.05


@pytest.mark.parametrize("shape, why", [
    (dict(chunk=64), "chunk not a lane tile"),
    (dict(state=64), "state not a lane tile"),
    (dict(head_dim=12), "head not whole sublane tiles"),
    (dict(head_dim=8), "a group's x not a lane tile"),
    (dict(heads=8, groups=2), "four heads a group"),
    (dict(heads=40, groups=2), "20 heads a group"),
    (dict(s=192), "row not whole chunks"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_gate_refuses_what_it_cannot_tile(shape, why):
    sizes = dict(s=3 * Q, heads=GROUPS * PER, head_dim=P, groups=GROUPS,
                 state=N, chunk=Q, dtype=jnp.bfloat16)
    assert ssd.kernel_fits(**sizes)
    assert not ssd.kernel_fits(**{**sizes, **shape}), why
    assert not ssd.kernel_fits(**{**sizes, "dtype": jnp.float16})


def test_a_refused_shape_runs_the_composition_bit_for_bit():
    """At a state of 64 the gate refuses: with the kernels pinned the scan
    is the composition's, to the bit, forward and backward."""
    rng = np.random.default_rng(11)
    args = [jnp.asarray(rng.normal(size=(1, 256, 16, 16)), jnp.float32),
            jnp.asarray(rng.uniform(0.01, 0.1, (1, 256, 16)), jnp.float32),
            jnp.asarray(-rng.uniform(1, 4, 16), jnp.float32),
            jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32),
            jnp.asarray(rng.normal(size=16), jnp.float32)]

    def run():
        return jax.grad(lambda *t: jnp.sum(ssm_ops.ssd_scan(*t, Q) ** 2),
                        argnums=tuple(range(6)))(*args)

    plain = run()
    with mock.patch.object(attention, "FORCE_PALLAS", True):
        assert not ssm_ops.scan_kernels((1, 256, 16, 16), (1, 256, 2, 64), Q,
                                        jnp.float32)
        pinned_ = run()
    for u, v in zip(pinned_, plain):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def test_off_the_tpu_the_gate_leaves_the_composition():
    assert not ssd.use_kernels(3 * Q, 64, 64, 8, 128, 128, jnp.bfloat16)
    assert ssd.kernel_fits(3 * Q, 64, 64, 8, 128, 128, jnp.bfloat16)


# ---------------------------------------------------------------------------
# a mamba2 step with the kernels: scopes, roles, counter
# ---------------------------------------------------------------------------


def _lowerings(impl):
    return get_registry().counter("ssd_scan_lowerings_total",
                                  impl=impl).value


def test_a_mamba2_step_lowers_the_kernels_under_their_scopes(pinned):
    """`ssd_scan_fwd` under the role `forward`, `ssd_scan_bwd` under
    `backward`; among the accepted part names plus `ssd_scan` both are the
    scan's, plus `mamba2` the mixer's; the counter counts one `pallas` a
    `mamba2` op and no `jnp`."""
    from benchmark import part_scopes, roles

    def build(v):
        out, decay = layers.mamba2(
            v, GROUPS * PER, P, GROUPS, N, conv_kernel=4, chunk_size=Q,
            param_attr=fluid.ParamAttr(initializer=INIT), name="m")
        return out, [decay]

    x = np.random.RandomState(12).randn(1, 2 * Q, 32).astype(np.float32)
    before = _lowerings("pallas"), _lowerings("jnp")
    lowered = []
    _run(build, {"x": x}, lowered=lowered)
    assert _lowerings("pallas") - before[0] >= 1
    assert _lowerings("jnp") == before[1]
    names = set(re.findall(r'"(jit\([a-z_]+\)/[^"]*)"', lowered[0]))
    for entry, role in (("_scan_fwd", "forward"), ("_scan_bwd", "backward")):
        calls = {n for n in names if n.endswith(f"/jit({entry})")}
        assert calls, entry
        for n in calls:
            assert roles.role_of(n) == role, n
            assert part_scopes.part_of(
                n, part_scopes.PARTS + ("ssd_scan",)) == "ssd_scan", n
            assert part_scopes.part_of(
                n, part_scopes.PARTS + ("mamba2",)) == "mamba2", n


def test_the_counter_counts_one_pallas_a_mamba2_op(pinned):
    """Two `mamba2` ops in one program: two `pallas` lowerings a trace."""
    def build(v):
        for name in ("m0", "m1"):
            v, _ = layers.mamba2(
                v, GROUPS * PER, P, GROUPS, N, conv_kernel=4, chunk_size=Q,
                param_attr=fluid.ParamAttr(initializer=INIT), name=name)
        return v, []

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[1, Q, 32], dtype="float32",
                        append_batch_size=False)
        out, _ = build(x)
        loss = layers.reduce_mean(out)
        fluid.optimizer.SGD(0.0).minimize(loss, startup)
    exe = fluid.Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    before = _lowerings("pallas")
    exe._lower_step(main, feed={"x": np.zeros((1, Q, 32), np.float32)},
                    fetch_list=[loss], scope=scope)
    assert _lowerings("pallas") - before == 2


def test_the_vmem_model_admits_the_nemotron_cell_with_margin():
    """The Nemotron cell: 64 heads of 64 in 8 groups at state 128, chunk
    128, rows of 4,096, bf16; two groups a cell, both passes well under the
    budget the stream kernels share."""
    r, s = 2 * 8, 4096
    for pass_ in ("fwd", "bwd"):
        est = feasible.ssd_scan_vmem_bytes(pass_, s, Q, r, 64, 128, 2,
                                           groups=2)
        assert est <= 0.5 * feasible.SSD_VMEM_BUDGET, (pass_, est)
    # the backward cell keeps every chunk's entering states: 64 chunks'
    # worth of 16 heads' [64, 128] float32 states alone is 33.6 MB
    assert feasible.ssd_scan_vmem_bytes(
        "bwd", 8 * s, Q, r, 64, 128, 2, groups=2) > feasible.SSD_VMEM_BUDGET
    assert ssd.kernel_fits(s, 64, 64, 8, 128, 128, jnp.bfloat16)
    assert not ssd.kernel_fits(16 * s, 64, 64, 8, 128, 128, jnp.bfloat16)
    cell = ssd._cell(64, 8, 64, 128, s, Q, 2)
    assert (cell.groups, cell.per, cell.blocks) == (2, 8, 1)
    assert ssd.head_blocks(s, 64, 64, 8, 128, 128, jnp.bfloat16) == 1


def test_the_vmem_model_admits_the_granite_cell_in_blocks_of_sixteen():
    """The Granite cell: one group of 64 heads of 64 at state 128, chunk
    256, rows of 4,096, bf16. All 64 heads in one cell want ~116 MB, over
    the budget; a block of sixteen is within it (~33 MB), so a group goes
    in four blocks, one a cell."""
    s, q = 4096, 2 * Q
    whole = feasible.ssd_scan_vmem_bytes("bwd", s, q, 64, 64, 128, 2)
    assert 1.1e8 < whole and whole > feasible.SSD_VMEM_BUDGET
    for pass_ in ("fwd", "bwd"):
        est = feasible.ssd_scan_vmem_bytes(pass_, s, q, 16, 64, 128, 2,
                                           blocks=4)
        assert est <= 0.6 * feasible.SSD_VMEM_BUDGET, (pass_, est)
    cell = ssd._cell(64, 1, 64, 128, s, q, 2)
    assert (cell.groups, cell.per, cell.blocks) == (1, 16, 4)
    assert ssd.head_blocks(s, 64, 64, 1, 128, q, jnp.bfloat16) == 4


# ---------------------------------------------------------------------------
# why the gate refused: the first check that failed, counted
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sizes, reason", [
    # the Nemotron cell: eight groups of eight heads at chunk 128
    (dict(s=4096, heads=64, head_dim=64, groups=8, state=128, chunk=128),
     None),
    # the Granite cell: one group of all 64 heads at chunk 256, in blocks
    (dict(s=4096, heads=64, head_dim=64, groups=1, state=128, chunk=256),
     None),
    # twenty heads in one group: neither 16 nor 8 divides them
    (dict(s=4096, heads=20, head_dim=64, groups=1, state=128, chunk=256),
     "heads_per_group"),
    (dict(s=4096, heads=64, head_dim=64, groups=8, state=128, chunk=128,
          dtype=jnp.float16), "dtype"),
    (dict(s=4096, heads=64, head_dim=64, groups=3, state=128, chunk=128),
     "groups"),
    (dict(s=4096, heads=64, head_dim=64, groups=8, state=64, chunk=128),
     "lanes"),
    (dict(s=16 * 4096, heads=64, head_dim=64, groups=8, state=128,
          chunk=128), "vmem"),
], ids=["nemotron", "granite", "heads_per_group", "dtype", "groups", "lanes",
        "vmem"])
def test_the_gate_names_the_first_check_that_refused(sizes, reason):
    sizes = {"dtype": jnp.bfloat16, **sizes}
    assert ssd.kernel_fits_reason(**sizes) == reason
    assert ssd.kernel_fits(**sizes) == (reason is None)


def _refusals(reason):
    return get_registry().counter("ssd_scan_gate_refusals_total",
                                  reason=reason).value


def _trace_scan(heads, groups):
    """`ssm_ops.ssd_scan` traced once at two chunks of Q, heads of P."""
    x = jax.ShapeDtypeStruct((1, 2 * Q, heads, P), jnp.float32)
    dt = jax.ShapeDtypeStruct((1, 2 * Q, heads), jnp.float32)
    vec = jax.ShapeDtypeStruct((heads,), jnp.float32)
    bc = jax.ShapeDtypeStruct((1, 2 * Q, groups, N), jnp.float32)
    jax.eval_shape(functools.partial(ssm_ops.ssd_scan, chunk=Q),
                   x, dt, vec, bc, bc, vec)


def test_a_refusal_is_counted_where_the_kernels_would_run():
    """Twenty heads in one group: refused by `heads_per_group` and
    counted once a trace where the kernels would run (pinned here, as on
    the TPU); off the TPU the composition is the path and nothing is
    counted; a shape the gate admits is no refusal."""
    before = _refusals("heads_per_group")
    _trace_scan(20, 1)
    assert _refusals("heads_per_group") == before
    with mock.patch.object(attention, "FORCE_PALLAS", True):
        _trace_scan(20, 1)
        assert _refusals("heads_per_group") == before + 1
        counted = {r: _refusals(r) for r in ("dtype", "groups", "lanes",
                                              "heads_per_group", "vmem")}
        _trace_scan(GROUPS * PER, GROUPS)
        assert {r: _refusals(r) for r in counted} == counted


def _head_blocks(blocks):
    return get_registry().counter("ssd_scan_head_blocks_total",
                                  blocks=blocks).value


def test_a_split_group_is_counted_by_its_blocks():
    """Thirty-two heads in one group where the kernels run: one `pallas`
    lowering, no refusal, and one count under `blocks="2"`; two groups of
    eight heads, which a cell takes whole, count no blocks; off the TPU
    nothing is counted."""
    before = _head_blocks("2")
    _trace_scan(32, 1)
    assert _head_blocks("2") == before
    with mock.patch.object(attention, "FORCE_PALLAS", True):
        counted = _lowerings("pallas"), _refusals("heads_per_group")
        _trace_scan(32, 1)
        assert (_lowerings("pallas"), _refusals("heads_per_group")) == (
            counted[0] + 1, counted[1])
        assert _head_blocks("2") == before + 1
        _trace_scan(GROUPS * PER, GROUPS)
        assert _head_blocks("2") == before + 1
