"""Fused residual+LayerNorm Pallas kernel (ops/pallas/add_ln.py) vs the
jnp oracle — forward and gradients, interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _oracle(x, y, scale, shift, eps=1e-5):
    s = (x + y if y is not None else x).astype(jnp.float32)
    mu = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mu), axis=-1, keepdims=True)
    out = (s - mu) * jax.lax.rsqrt(var + eps) * scale + shift
    return out.astype(x.dtype)


@pytest.mark.parametrize("shape", [(4, 32, 128), (8, 256)])
@pytest.mark.parametrize("with_y", [True, False])
def test_fused_add_ln_matches_oracle(shape, with_y):
    from paddle_tpu.ops.pallas.add_ln import fused_add_ln

    rng = np.random.RandomState(0)
    h = shape[-1]
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    y = jnp.asarray(rng.randn(*shape).astype(np.float32)) if with_y else None
    scale = jnp.asarray(rng.rand(h).astype(np.float32) + 0.5)
    shift = jnp.asarray(rng.randn(h).astype(np.float32))

    out = fused_add_ln(x, y, scale, shift)
    ref = _oracle(x, y, scale, shift)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_fused(*args):
        if with_y:
            x_, y_, sc, sh = args
            o = fused_add_ln(x_, y_, sc, sh)
        else:
            x_, sc, sh = args
            o = fused_add_ln(x_, None, sc, sh)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(*args):
        if with_y:
            x_, y_, sc, sh = args
            o = _oracle(x_, y_, sc, sh)
        else:
            x_, sc, sh = args
            o = _oracle(x_, None, sc, sh)
        return jnp.sum(o * jnp.cos(o))

    args = (x, y, scale, shift) if with_y else (x, scale, shift)
    g_fused = jax.grad(loss_fused, argnums=tuple(range(len(args))))(*args)
    g_ref = jax.grad(loss_ref, argnums=tuple(range(len(args))))(*args)
    for gf, gr in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)
    if with_y:
        # the residual add distributes the cotangent: dx == dy exactly
        np.testing.assert_array_equal(np.asarray(g_fused[0]),
                                      np.asarray(g_fused[1]))


def test_fused_add_ln_bf16():
    from paddle_tpu.ops.pallas.add_ln import fused_add_ln

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 128).astype(np.float32)).astype(jnp.bfloat16)
    y = jnp.asarray(rng.randn(8, 128).astype(np.float32)).astype(jnp.bfloat16)
    scale = jnp.ones((128,), jnp.float32)
    shift = jnp.zeros((128,), jnp.float32)
    out = fused_add_ln(x, y, scale, shift)
    assert out.dtype == jnp.bfloat16
    ref = _oracle(x, y, scale, shift)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_encoder_stack_dispatches_fused_ln(monkeypatch):
    """FORCE_PALLAS: the fused stack must route its residual+LN pairs
    through the kernel and still match the jnp composition."""
    from paddle_tpu.ops.pallas import backend
    from paddle_tpu.ops.pallas.add_ln import fused_ln_dispatch_ok

    assert not fused_ln_dispatch_ok((4, 32, 128))  # interpret off by default
    monkeypatch.setattr(backend, "FORCE_PALLAS", True)
    assert fused_ln_dispatch_ok((4, 32, 128))
    assert not fused_ln_dispatch_ok((4, 32, 96))  # H % 128 != 0
