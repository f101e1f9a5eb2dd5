"""ops/pallas/feasible.py: the VMEM models the kernels' choosers and gates
hold a tile to, against what Mosaic was seen to allocate on v5e, and the
error a kernel raises when nothing tiles."""
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import feasible


def test_flash_candidates_feasibility():
    # the tiles the s4096 cell runs serve both passes
    assert feasible.flash_bsh_ok(4096, 4096, 768, 1024, 1024) == (True, "ok")
    # the bwd's residency is q^T / do^T / dq^T, 8 B/elem of sq*h: the
    # model must admit what Mosaic was seen to allocate (69.4 MiB at
    # s8192/h768) and reject a batch row that cannot fit
    assert feasible.flash_bsh_bwd_vmem_bytes(
        8192, 8192, 768, 1024, 1024) <= feasible.BSH_VMEM_LIMIT
    assert feasible.flash_bsh_bwd_vmem_bytes(
        8192, 8192, 768, 1024, 1024) >= 69.4 * 2**20
    assert feasible.flash_bsh_bwd_vmem_bytes(
        32768, 32768, 768, 128, 128) > feasible.BSH_VMEM_LIMIT
    ok, why = feasible.flash_bsh_ok(32768, 32768, 768, 128, 128)
    assert not ok and "VMEM estimate" in why
    # at H 2048 a whole batch row of 8k passes 112 MiB in either pass (R8)
    ok, why = feasible.flash_bsh_ok(8192, 8192, 2048, 128, 128)
    assert not ok and "VMEM estimate" in why
    # below S 1024 the whole-tile kernels' model applies: a [1024, 1024]
    # score tile at ~40 B an element does not fit beside the operands
    # there, which is why _pick_block stops at 512
    assert feasible.flash_bsh_fwd_vmem_bytes(
        512, 512, 768, 512, 512) < feasible.BSH_VMEM_LIMIT
    assert 40 * 1024 * 1024 <= feasible.flash_bsh_fwd_vmem_bytes(
        512, 512, 768, 1024, 1024)
    # tiles that do not tile, or lie below Mosaic's minimum, say why
    assert "do not tile" in feasible.flash_bsh_ok(512, 512, 768, 384, 512)[1]
    assert "128" in feasible.flash_bsh_ok(512, 512, 768, 64, 64)[1]


def test_no_feasible_config_from_kernels():
    from paddle_tpu.ops.pallas import add_ln
    from paddle_tpu.ops.pallas.flash_attention import _pick_block

    with pytest.raises(feasible.NoFeasibleConfig) as ei:
        _pick_block(130)
    assert ei.value.tried  # carries what was considered
    x = jnp.zeros((4, 100), jnp.float32)  # h % 128 != 0
    with pytest.raises(ValueError) as ei2:  # legacy contract intact
        add_ln.fused_add_ln(x, None, jnp.ones(100), jnp.zeros(100))
    assert isinstance(ei2.value, feasible.NoFeasibleConfig)
    assert ei2.value.kernel == "add_ln"
