"""A kernel's tile is a function of its operands' shapes, computed in the
kernel's own module: the choosers' answers at the shapes the benchmark's
cells run (which until PR 30 only a chip number pinned), the BSH dispatch
gate, the head-group calibration, and the Executor's step key, which
holds nothing from the kernels."""
import importlib.util
import os
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.ops.pallas import add_ln, backend, conv_bn, feasible
from paddle_tpu.ops.pallas import flash_attention as fa


class _SpiedEnviron(dict):
    """os.environ's stand-in that records which names are asked for."""

    def __init__(self, *a):
        super().__init__(*a)
        self.asked = []

    def get(self, key, default=None):
        self.asked.append(key)
        return super().get(key, default)

    def __getitem__(self, key):
        self.asked.append(key)
        return super().__getitem__(key)

    def __contains__(self, key):
        self.asked.append(key)
        return super().__contains__(key)


@pytest.fixture
def spied_environ(monkeypatch):
    spy = _SpiedEnviron(os.environ)
    monkeypatch.setattr(os, "environ", spy)
    return spy


# (sq, skv, h) -> DMA tile of the forward, of the backward
@pytest.mark.parametrize("s,h,fwd,bwd", [
    (512, 768, 512, 512),      # bert-base.s512, .dp4: whole-tile kernels
    (4096, 768, 1024, 1024),   # bert-base.s4096: the largest that fits
    (4096, 2048, 1024, 512),   # the LFM2 cell: q^T / do^T / dq^T of H 2048
    (2048, 768, 512, 512),
    (1024, 768, 512, 512),
    (8192, 768, 1024, 1024),
    (256, 512, 256, 256),
])
def test_bsh_tiles_at_the_shapes_the_cells_run(s, h, fwd, bwd):
    assert fa.default_bsh_block(s, s, h) == fwd
    assert fa.default_bsh_block(s, s, h, bwd=True) == bwd
    assert fa._resolve_bsh_blocks(s, s, h) == (
        fwd, fwd, feasible.BSH_VMEM_LIMIT)
    assert fa._resolve_bsh_blocks(s, s, h, bwd=True) == (
        bwd, bwd, feasible.BSH_VMEM_LIMIT)
    # what is chosen serves the pass it was chosen for
    assert feasible.flash_bsh_fwd_vmem_bytes(
        s, s, h, fwd, fwd) <= feasible.BSH_VMEM_LIMIT
    assert feasible.flash_bsh_bwd_vmem_bytes(
        s, s, h, bwd, bwd) <= feasible.BSH_VMEM_LIMIT


def test_bsh_tiles_of_a_length_no_block_divides():
    for s in (200, 1100):
        with pytest.raises(feasible.NoFeasibleConfig):
            fa.default_bsh_block(s, s, 768)


def test_bsh_tiles_of_cross_attention_follow_each_length():
    bq, bk, _ = fa._resolve_bsh_blocks(512, 4096, 768)
    assert (bq, bk) == (512, 1024)


def test_choosers_ask_the_environment_nothing(spied_environ):
    assert (fa.default_bsh_block(4096, 4096, 768), fa._pick_block(512),
            fa._pick_group(96, 4096, 512, 64, False),
            fa._pick_group_bwd(96, 512, 512, 64, False),
            add_ln.default_ln_rows(32768, 768),
            conv_bn.default_conv_bn_rows(100352, 512, 28)) == (
        1024, 512, 2, 6, 256, 512)
    assert spied_environ.asked == []
    # and no package of tile caches stands above the kernels
    assert importlib.util.find_spec("paddle_tpu.tuning") is None


def _bias(*shape):
    return np.zeros(shape, np.float32)


# (sq, skv, h, heads, bias, batch, causal) -> dispatched to the BSH kernels
DISPATCH = {
    "no bias": ((512, 512, 768, 12, None, 8, False), True),
    "per-key bias [B,1,1,Skv]": (
        (512, 512, 768, 12, _bias(8, 1, 1, 512), 8, False), True),
    "per-key bias [B,1,Skv]": (
        (512, 512, 768, 12, _bias(8, 1, 512), 8, False), True),
    "cross attention, per-key bias": (
        (512, 1024, 768, 12, _bias(8, 1, 1, 1024), 8, False), True),
    "causal, square": ((4096, 4096, 2048, 32, None, 4, True), True),
    "head width 128": ((512, 512, 1024, 8, None, 8, False), True),
    "full bias [B,nh,S,S]": (
        (512, 512, 768, 12, _bias(2, 12, 512, 512), 2, False), False),
    "per-query bias [B,1,S,S]": (
        (512, 512, 768, 12, _bias(2, 1, 512, 512), 2, False), False),
    "bias of another batch": (
        (512, 512, 768, 12, _bias(4, 1, 1, 512), 8, False), False),
    "bias of another length": (
        (512, 512, 768, 12, _bias(8, 1, 1, 256), 8, False), False),
    "bias of rank 2": (
        (512, 512, 768, 12, _bias(8, 512), 8, False), False),
    "causal with sq != skv": ((512, 1024, 768, 12, None, 8, True), False),
    "head width 32": ((512, 512, 384, 12, None, 8, False), False),
    "S not a multiple of 128": ((200, 200, 768, 12, None, 8, False), False),
    "s8192 / h2048 (R8)": ((8192, 8192, 2048, 32, None, 1, True), False),
}


@pytest.mark.parametrize("case", sorted(DISPATCH))
def test_bsh_dispatch_truth_table(pinned, case):
    (sq, skv, h, heads, bias, batch, causal), want = DISPATCH[case]
    # on the CPU the gate is closed unless a test forces the kernels
    assert fa.bsh_dispatch_ok(sq, skv, h, heads, bias=bias, batch=batch,
                              causal=causal) is want


def test_bsh_dispatch_gate_is_closed_by_flag_and_off_the_chip(monkeypatch):
    args = (512, 512, 768, 12)
    # interpret mode and nothing forced: the jnp composition runs
    assert backend.interpret() and not backend.FORCE_PALLAS
    assert not fa.bsh_dispatch_ok(*args)
    monkeypatch.setattr(backend, "FORCE_PALLAS", True)
    assert fa.bsh_dispatch_ok(*args)
    fluid.flags.set_flags({"FLAGS_use_flash_attention": False})
    try:
        assert not fa.bsh_dispatch_ok(*args)
    finally:
        fluid.flags.set_flags({"FLAGS_use_flash_attention": True})


# (bh, s, bq, d) -> G of the BHSD forward, of its fused backward
@pytest.mark.parametrize("bh,s,bq,d,fwd,bwd", [
    # the calibration the comment above _VMEM_BUDGET names: G = 3 at
    # s4096 / bq 512 allocated 16.98M of the 16M scoped limit, G = 2 fits
    (96, 4096, 512, 64, 2, 1),
    (12, 4096, 512, 64, 2, 1),
    # G = 8 at s512 allocated 16.97M in the fused backward: 6 is kept
    (768, 512, 512, 64, 8, 6),
    (48, 1024, 512, 128, 4, 2),
    (16, 8192, 512, 64, 1, 1),
    (7, 512, 512, 64, 1, 1),   # G divides bh
])
def test_head_groups_hold_the_v5e_calibration(bh, s, bq, d, fwd, bwd):
    assert fa._pick_group(bh, s, bq, d, False) == fwd
    assert fa._pick_group_bwd(bh, s, bq, d, False) == bwd
    assert bh % fwd == 0 and bh % bwd == 0
    # a full bias is indexed per bh row: one row a cell
    assert fa._pick_group(bh, s, bq, d, True) == 1
    assert fa._pick_group_bwd(bh, s, bq, d, True) == 1


def test_head_group_of_three_is_refused_at_s4096():
    # bh = 3 leaves only G = 3 and G = 1 to choose from
    assert fa._pick_group(3, 4096, 512, 64, False) == 1
    assert fa._pick_group(3, 2048, 512, 64, False) == 3


# (rows, h) -> row block: the s512 / s4096 cells' [B x S, 768] and the
# LFM2 cell's [B x S, 2048]
@pytest.mark.parametrize("r,h,rows", [
    (32768, 768, 256), (16384, 2048, 128), (640, 768, 128), (256, 128, 256)])
def test_ln_rows_at_the_cells_shapes(r, h, rows):
    assert add_ln.default_ln_rows(r, h) == rows
    # PR 21's repair: the [1, R] statistics take lane blocks in
    # multiples of 128 only
    assert rows % 128 == 0
    assert feasible.ln_rows_ok(r, h, rows) == (True, "ok")
    assert add_ln.ln_shapes_ok(r, h)


def test_ln_rows_pad_what_128_does_not_divide():
    # the MLM head's batch x 76 masked positions
    assert add_ln.default_ln_rows(608, 768) is None
    assert add_ln._padded_rows(608) == 640 and add_ln.ln_shapes_ok(608, 768)
    # a row of 128 x 4096 floats and its temporaries passes the budget
    assert add_ln.default_ln_rows(32768, 4096) is None
    assert not add_ln.ln_shapes_ok(32768, 4096)
    assert not add_ln.ln_shapes_ok(256, 100)


@pytest.mark.parametrize("r,width,kind,rows", [
    (100352, 64 + 256, "mm", 2048),   # ResNet-50 b128, a 1x1 of stage 1
    (100352, 512, "apply", 512),      # PR 21: 2048 rows allocated 19.94M
    (25, 8, "apply", 1),              # 25 has no larger divisor in the menu
])
def test_conv_bn_rows_fit_their_budget(r, width, kind, rows):
    unit = conv_bn._ROW_UNIT[kind]
    assert conv_bn.default_conv_bn_rows(r, width, unit) == rows
    assert feasible.conv_bn_rows_ok(r, width, rows, unit) == (True, "ok")
    assert rows * width * unit <= feasible.CONV_BN_VMEM_BUDGET
    bigger = [c for c in conv_bn._ROW_CANDIDATES if c > rows and r % c == 0]
    assert all(not feasible.conv_bn_rows_ok(r, width, c, unit)[0]
               for c in bigger)


def test_conv_bn_rows_none_when_one_row_does_not_fit():
    unit = conv_bn._ROW_UNIT["mm"]
    assert conv_bn.default_conv_bn_rows(7, 10**7, unit) is None
    assert not conv_bn.conv_bn_shapes_ok(
        (1, 1, 7, 5 * 10**6), (5 * 10**6, 5 * 10**6, 1, 1), (1, 1),
        ((0, 0), (0, 0)))


def test_the_step_key_holds_nothing_from_the_kernels(spied_environ):
    main = fluid.Program()
    feed = {"x": np.zeros((2, 3), np.float32)}
    before = set(sys.modules)
    key = fluid.Executor._cache_key(main, feed, ("loss",), False)
    # program serial and version, feeds, fetches, donation, four flags:
    # the same tuple whatever the environment holds, built without
    # importing anything
    assert len(key) == 9
    assert key[:5] == (main._serial, main._version,
                       (("x", (2, 3), "float32"),), ("loss",), False)
    assert all(isinstance(f, bool) for f in key[5:])
    assert spied_environ.asked == []
    assert set(sys.modules) == before
