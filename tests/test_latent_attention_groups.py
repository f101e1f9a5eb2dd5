"""Latent attention on more heads than the BSH stream kernels hold at once
(`ops/attention.py:latent_attention`, `latent_head_groups`): the heads go
in the fewest equal groups whose slab passes the kernels' own VMEM model,
one flash call a group. The grouped path in interpret mode against the
`jnp` composition, forward and backward; what the chooser picks at the
benchmark's shapes; the names the calls carry."""
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lowered import pallas_calls
from paddle_tpu.ops import attention
from paddle_tpu.ops.pallas import backend, feasible
from paddle_tpu.ops.pallas import flash_attention as fa


def _holds(heads, width):
    """A kernel that holds `heads` heads of `width` and no more."""
    return mock.patch.object(
        fa, "bsh_shapes_ok", lambda sq, skv, h: h <= heads * width)


def _loss(nh, scale):
    def loss(q, k, v):
        out = attention.latent_attention(q, k, v, nh, scale, True)
        # weighed, so that every output column has a cotangent of its own
        return (out.astype(jnp.float32)
                * jnp.linspace(0.5, 1.5, out.shape[-1])).sum()

    return loss


def _loss_and_grads(q, k, v, nh, scale, pallas):
    with mock.patch.object(backend, "FORCE_PALLAS", pallas):
        fa._make_flash_core_bsh.cache_clear()
        return jax.value_and_grad(_loss(nh, scale), argnums=(0, 1, 2))(
            q, k, v)


@pytest.mark.parametrize("nh, dqk, dv, held, groups, form", [
    (20, 64, 64, 10, 2, "mla_wide"),  # the cell's: twenty heads, ten fit
    (6, 64, 64, 4, 2, "mla_wide"),    # four fit and do not divide six
    (6, 48, 32, 2, 3, "mla"),         # padded to 64 and grouped
    (5, 64, 64, 3, 5, "mla_wide"),    # a prime count: one head a call
], ids=["20_by_10", "6_by_3", "6_padded_by_2", "5_by_1"])
def test_the_grouped_flash_path_is_the_composition(nh, dqk, dv, held, groups,
                                                   form):
    s = 128
    rng = np.random.RandomState(nh)
    q, k = (jnp.asarray(rng.randn(2, s, nh * dqk) * 0.5, jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(2, s, nh * dv) * 0.5, jnp.float32)
    scale = 0.7 * dqk ** -0.5
    with _holds(held, 64), mock.patch.object(backend, "FORCE_PALLAS", True):
        assert attention.latent_head_groups(s, s, nh, 64, batch=2) == groups
        # one forward and one backward kernel call a group, under the
        # form's own names, and nothing of the composition
        fa._make_flash_core_bsh.cache_clear()
        assert pallas_calls(jax.grad(_loss(nh, scale), argnums=(0, 1, 2)),
                            q, k, v) == {
            fa._bsh_kernel_name("fwd", True, form): groups,
            fa._bsh_kernel_name("bwd", True, form): groups}
    want, want_grads = _loss_and_grads(q, k, v, nh, scale, pallas=False)
    with _holds(held, 64):
        got, got_grads = _loss_and_grads(q, k, v, nh, scale, pallas=True)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for g, w in zip(got_grads, want_grads):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("s, nh, width, batch, groups", [
    (4096, 4, 256, 2, 1),     # the Xing4 cell's four padded heads
    (4096, 20, 256, 2, 2),    # this model's twenty: two calls of ten
    (4096, 20, 256, 1, 2),
    (512, 12, 64, 64, 1),     # BERT-base's shapes, were they latent
    (4096, 12, 64, 8, 1),
    (8192, 20, 256, 1, 4),    # a longer row holds fewer heads
], ids=["xing4", "glm_step", "glm_check", "bert_s512", "bert_s4096", "s8192"])
def test_what_the_chooser_picks(s, nh, width, batch, groups):
    """The fewest equal groups `feasible.py`'s model admits: a function of
    the shapes. Where all heads pass, one call, as before the grouping."""
    with mock.patch.object(backend, "FORCE_PALLAS", True):
        assert attention.latent_head_groups(
            s, s, nh, width, batch=batch) == groups
        held = nh // groups
        assert feasible.flash_bsh_ok(s, s, held * width, 128, 128)[0]
        wider = next((nh // g for g in range(groups - 1, 0, -1)
                      if nh % g == 0), None)
        assert wider is None or not feasible.flash_bsh_ok(
            s, s, wider * width, 128, 128)[0]
    # off the TPU and unforced, no kernel: the composition
    assert attention.latent_head_groups(s, s, nh, width, batch=batch) is None


def test_no_group_where_not_one_head_passes():
    with _holds(0, 64), mock.patch.object(backend, "FORCE_PALLAS", True):
        assert attention.latent_head_groups(128, 128, 4, 64) is None
        q = jnp.ones((1, 128, 4 * 64), jnp.float32)
        # the composition: no kernel
        assert pallas_calls(lambda q: attention.latent_attention(
            q, q, q, 4), q) == {}


def test_the_wide_calls_carry_their_own_names(described_tpu):
    """Lowered for the TPU from this CPU process, twenty heads of 256 / 256
    at S 4096 are two `flash_mla_wide_causal_fwd` calls and two `_bwd`, on
    [B, 4096, 2560] slabs; four heads of 192 / 128 stay one
    `flash_mla_causal_*` pair on the padded [B, 4096, 1024]."""
    def text(nh, dqk, dv):
        def loss(q, k, v):
            return attention.latent_attention(
                q, k, v, nh, 1 / 16, True).astype(jnp.float32).sum()

        q = jax.ShapeDtypeStruct((1, 4096, nh * dqk), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, 4096, nh * dv), jnp.bfloat16)
        fa._make_flash_core_bsh.cache_clear()
        try:
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
                q, q, v).lower(lowering_platforms=("tpu",)).as_text()
        finally:
            fa._make_flash_core_bsh.cache_clear()

    wide = re.findall(r'kernel_name = "([^"]+)"', text(20, 256, 256))
    assert sorted(wide) == (["flash_mla_wide_causal_bwd"] * 2
                            + ["flash_mla_wide_causal_fwd"] * 2)
    padded = re.findall(r'kernel_name = "([^"]+)"', text(4, 192, 128))
    assert sorted(padded) == ["flash_mla_causal_bwd", "flash_mla_causal_fwd"]
    assert fa._bsh_kernel_name("fwd", True, "mla_wide") == (
        "flash_mla_wide_causal_fwd")
