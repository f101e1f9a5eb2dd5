"""BSH (transpose-free) flash attention vs the jnp oracle — interpret
mode on CPU. Covers square + rectangular (cross-attention) shapes,
causal, per-key bias, the host-mask dropout path, and gradients."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

B, NH, D = 2, 4, 64
H = NH * D


def _oracle(q, k, v, bias=None, causal=False, mask=None, keep=1.0):
    b, sq, _ = q.shape
    skv = k.shape[1]

    def heads(t, s):
        return t.reshape(b, s, NH, D).transpose(0, 2, 1, 3)

    qh, kh, vh = heads(q, sq), heads(k, skv), heads(v, skv)
    s = jnp.einsum("bnqd,bnkd->bnqk", qh, kh,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    if bias is not None:
        s = s + bias.reshape(b, 1, 1, skv)
    if causal:
        cm = jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :]
        s = jnp.where(cm, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pn = p / l
    if mask is not None:
        pn = jnp.where(mask != 0, pn / keep, 0.0)
    o = jnp.einsum("bnqk,bnkd->bnqd", pn.astype(q.dtype), vh)
    return o.transpose(0, 2, 1, 3).reshape(b, sq, H)


def _mk(sq, skv, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, sq, H).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, skv, H).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, skv, H).astype(np.float32) * 0.3)
    return q, k, v


pytestmark = pytest.mark.usefixtures("pinned")


@pytest.mark.parametrize("sq,skv", [(128, 128), (256, 128), (128, 384)])
@pytest.mark.parametrize("causal", [False, True])
def test_bsh_forward(sq, skv, causal):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bsh

    if causal and sq != skv:
        # rectangular causal is rejected (top-left vs bottom-right mask
        # alignment is ambiguous) — assert the loud failure and stop
        q, k, v = _mk(sq, skv)
        with pytest.raises(ValueError, match="causal"):
            flash_attention_bsh(q, k, v, num_heads=NH, causal=True)
        return
    q, k, v = _mk(sq, skv)
    out = flash_attention_bsh(q, k, v, num_heads=NH, causal=causal)
    ref = _oracle(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bsh_bias_and_grads():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bsh

    sq = skv = 128
    q, k, v = _mk(sq, skv, seed=3)
    rng = np.random.RandomState(4)
    bias = jnp.asarray((rng.rand(B, 1, 1, skv) > 0.2) * 0.0
                       - (rng.rand(B, 1, 1, skv) <= 0.2) * 1e4,
                       dtype=jnp.float32)

    def loss_bsh(q_, k_, v_):
        o = flash_attention_bsh(q_, k_, v_, bias=bias, num_heads=NH)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q_, k_, v_):
        o = _oracle(q_, k_, v_, bias=bias)
        return jnp.sum(o * jnp.cos(o))

    np.testing.assert_allclose(float(loss_bsh(q, k, v)),
                               float(loss_ref(q, k, v)), rtol=1e-5)
    g1 = jax.grad(loss_bsh, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_bsh_rectangular_grads():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bsh

    sq, skv = 128, 256
    q, k, v = _mk(sq, skv, seed=5)

    def loss_bsh(q_, k_, v_):
        o = flash_attention_bsh(q_, k_, v_, num_heads=NH)
        return jnp.sum(jnp.square(o))

    def loss_ref(q_, k_, v_):
        return jnp.sum(jnp.square(_oracle(q_, k_, v_)))

    g1 = jax.grad(loss_bsh, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_bsh_causal_grads():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bsh

    sq = skv = 256
    q, k, v = _mk(sq, skv, seed=6)

    def loss_bsh(q_, k_, v_):
        o = flash_attention_bsh(q_, k_, v_, num_heads=NH, causal=True)
        return jnp.sum(jnp.square(o))

    def loss_ref(q_, k_, v_):
        return jnp.sum(jnp.square(_oracle(q_, k_, v_, causal=True)))

    g1 = jax.grad(loss_bsh, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_bsh_dropout_mask_path():
    """Interpret mode draws the mask host-side; fwd and bwd must use the
    identical mask (numerator-only dropout) — check against the oracle
    given the same mask."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    sq = skv = 128
    q, k, v = _mk(sq, skv, seed=7)
    key = jax.random.PRNGKey(11)
    prob = 0.3

    out = fa.flash_attention_bsh(q, k, v, num_heads=NH, dropout_prob=prob,
                                 dropout_key=key)
    # regenerate the same host-side mask the wrapper drew
    mask = jax.random.bernoulli(
        jax.random.fold_in(key, 7), 1.0 - prob, (B, NH, sq, skv)
    ).astype(jnp.uint8)
    ref = _oracle(q, k, v, mask=mask, keep=1.0 - prob)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bsh_matches_bhsd_kernel():
    """The two layouts must agree (same math, different plumbing)."""
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention,
        flash_attention_bsh,
    )

    s = 128
    q, k, v = _mk(s, s, seed=8)

    def heads(t):
        return t.reshape(B, s, NH, D).transpose(0, 2, 1, 3)

    o_bsh = flash_attention_bsh(q, k, v, num_heads=NH, causal=True)
    o_bhsd = flash_attention(heads(q), heads(k), heads(v), causal=True)
    o_bhsd = o_bhsd.transpose(0, 2, 1, 3).reshape(B, s, H)
    np.testing.assert_allclose(np.asarray(o_bsh), np.asarray(o_bhsd),
                               rtol=1e-6, atol=1e-6)


def _grads(loss, *args):
    return jax.grad(loss, argnums=(0, 1, 2))(*args)


# The stream kernels (the BSH kernels from S = 1024 on), steered down to
# small S here. (sq, skv, causal, bias, DMA tile, compute tile): S 512
# under DMA tiles of 256 and 512 with 128 x 128 compute tiles has two and
# four of them in both directions of a DMA tile, and more key windows
# than one trip of the kernels' stream holds; the unpatched tiles (512)
# run the same shapes as one step a head
TILED = [
    (512, 512, False, False, 256, 128),
    (512, 512, False, True, 512, 128),
    (512, 512, True, False, 256, 128),
    (512, 512, True, True, 512, 128),
    (256, 128, False, True, 128, 128),
    (128, 384, False, True, 128, 128),
    (512, 512, False, True, 512, 512),
    (512, 512, True, False, 256, 512),
    (384, 384, True, True, 128, 512),
]


@pytest.mark.parametrize("sq,skv,causal,with_bias,block,tile", TILED)
def test_bsh_compute_tiles_forward_and_grads(monkeypatch, sq, skv, causal,
                                             with_bias, block, tile):
    from paddle_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "default_bsh_block",
                        lambda s, skv_, h, bwd=False: block)
    monkeypatch.setattr(fa, "_STREAM_FROM", 128)
    monkeypatch.setattr(fa, "_CQ", tile)
    monkeypatch.setattr(fa, "_CK", tile)
    q, k, v = _mk(sq, skv, seed=9)
    rng = np.random.RandomState(10)
    bias = None
    if with_bias:
        bias = jnp.asarray((rng.rand(B, 1, 1, skv) <= 0.2) * -1e4,
                           dtype=jnp.float32)
    w = jnp.asarray(rng.randn(B, sq, H).astype(np.float32))

    def loss_bsh(q_, k_, v_):
        return jnp.sum(w * fa.flash_attention_bsh(
            q_, k_, v_, bias=bias, num_heads=NH, causal=causal))

    def loss_ref(q_, k_, v_):
        return jnp.sum(w * _oracle(q_, k_, v_, bias=bias, causal=causal))

    np.testing.assert_allclose(float(loss_bsh(q, k, v)),
                               float(loss_ref(q, k, v)), rtol=1e-5)
    for a, b_ in zip(_grads(loss_bsh, q, k, v), _grads(loss_ref, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_bsh_streams_from_s1024():
    """S = 1024 takes the stream kernels by itself: two compute tiles in
    both directions of its DMA tile, per-key bias, gradients."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    s = 1024
    assert fa._bsh_streams(s, s) and not fa._bsh_streams(512, 512)
    q, k, v = _mk(s, s, seed=15)
    rng = np.random.RandomState(16)
    bias = jnp.asarray((rng.rand(B, 1, 1, s) <= 0.2) * -1e4,
                       dtype=jnp.float32)
    w = jnp.asarray(rng.randn(B, s, H).astype(np.float32))

    def loss_bsh(q_, k_, v_):
        return jnp.sum(w * fa.flash_attention_bsh(
            q_, k_, v_, bias=bias, num_heads=NH))

    def loss_ref(q_, k_, v_):
        return jnp.sum(w * _oracle(q_, k_, v_, bias=bias))

    for a, b_ in zip(_grads(loss_bsh, q, k, v), _grads(loss_ref, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def _position_mask(fa, seed, sq, skv, prob):
    """The in-kernel dropout mask of the whole [B, NH, sq, skv] matrix,
    from the kernels' own draw a 128-key group at a time."""
    rows = []
    for bh in range(B * NH):
        rows.append(jnp.concatenate([
            fa._dropout_keep_t(seed[0], jnp.int32(bh), jnp.int32(k0),
                               jnp.int32(0), 1.0 - prob, 128, sq)
            for k0 in range(0, skv, 128)], axis=0).T)
    return jnp.stack(rows).reshape(B, NH, sq, skv)


@pytest.mark.parametrize("fwd_block,bwd_block,tile", [
    (256, 128, 128), (128, 512, 128), (512, 256, 512)])
def test_bsh_dropout_mask_is_the_same_under_any_tiles(
        monkeypatch, fwd_block, bwd_block, tile):
    """The forward under one DMA tile and the backward under another
    (and both whatever their compute tiles are) draw the same mask: it
    is a function of the seed and of (head-row, key, query) alone. The
    kernels run their in-kernel draw here, in interpret mode."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    sq = skv = 512
    prob = 0.1
    monkeypatch.setattr(fa, "_STREAM_FROM", 128)
    monkeypatch.setattr(fa, "_CQ", tile)
    monkeypatch.setattr(fa, "_CK", tile)
    blocks = fa._resolve_bsh_blocks

    def resolve(sq_, skv_, h, *, bwd=False):
        block = bwd_block if bwd else fwd_block
        return block, block, blocks(sq_, skv_, h, bwd=bwd)[2]

    monkeypatch.setattr(fa, "_resolve_bsh_blocks", resolve)
    q, k, v = _mk(sq, skv, seed=12)
    rng = np.random.RandomState(13)
    bias = jnp.asarray((rng.rand(B, 1, skv) <= 0.2) * -1e4,
                       dtype=jnp.float32)
    w = jnp.asarray(rng.randn(B, sq, H).astype(np.float32))
    seed = jnp.asarray([1234567], jnp.int32)
    core = fa._make_flash_core_bsh.__wrapped__(
        sm_scale=1.0 / math.sqrt(D), nh=NH, causal=False,
        dropout_prob=prob)

    keep = fa._dropout_quantized_keep(1.0 - prob)
    mask = _position_mask(fa, seed, sq, skv, prob)
    # the quantized keep probability, 230 / 256, within 4 sigma
    sigma = math.sqrt(keep * (1 - keep) / mask.size)
    assert abs(float(mask.mean()) - keep) < 4 * sigma

    def loss_bsh(q_, k_, v_):
        return jnp.sum(w * core(q_, k_, v_, bias, None, seed, None))

    def loss_ref(q_, k_, v_):
        return jnp.sum(w * _oracle(q_, k_, v_, bias=bias, mask=mask,
                                   keep=keep))

    np.testing.assert_allclose(
        np.asarray(core(q, k, v, bias, None, seed, None)),
        np.asarray(_oracle(q, k, v, bias=bias, mask=mask, keep=keep)),
        rtol=2e-5, atol=2e-5)
    for a, b_ in zip(_grads(loss_bsh, q, k, v), _grads(loss_ref, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_bsh_odd_head_count_runs_static_lane_groups(monkeypatch):
    """Three heads of 64 do not pair up into 128-lane groups: every head
    is then its own group of the stream kernels, at a static lane
    offset."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bsh

    monkeypatch.setattr(fa, "_STREAM_FROM", 128)
    nh, s = 3, 256
    rng = np.random.RandomState(14)
    q, k, v = (jnp.asarray(rng.randn(B, s, nh * D).astype(np.float32) * 0.3)
               for _ in range(3))

    def oracle(q_, k_, v_):
        def heads(t):
            return t.reshape(B, s, nh, D).transpose(0, 2, 1, 3)

        sc = jnp.einsum("bnqd,bnkd->bnqk", heads(q_), heads(k_)) / math.sqrt(D)
        sc = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(s)[None, :],
                       sc, -1e30)
        o = jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(sc, -1), heads(v_))
        return o.transpose(0, 2, 1, 3).reshape(B, s, nh * D)

    def loss_bsh(q_, k_, v_):
        return jnp.sum(jnp.square(flash_attention_bsh(
            q_, k_, v_, num_heads=nh, causal=True)))

    def loss_ref(q_, k_, v_):
        return jnp.sum(jnp.square(oracle(q_, k_, v_)))

    for a, b_ in zip(_grads(loss_bsh, q, k, v), _grads(loss_ref, q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_bsh_s8192_dropout_grads_match_interpret_oracle():
    """End-to-end at a mixed-tile S (fwd could take 1024, bwd cannot):
    with PRNG dropout the fwd/bwd masks must agree, so
    grad(sum(out*cot)) via the kernel pair equals recomputing the same
    masked softmax — checked by the kernel's own fwd determinism:
    out2 == out1 and the vjp runs without block-partition mismatch."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa

    B, S, H, NH = 1, 5120, 128, 2
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H).astype("f4") * 0.1)
    k = jnp.asarray(rng.randn(B, S, H).astype("f4") * 0.1)
    v = jnp.asarray(rng.randn(B, S, H).astype("f4") * 0.1)
    key = jax.random.PRNGKey(3)

    def loss(q, k, v):
        o = fa.flash_attention_bsh(q, k, v, None, num_heads=NH,
                                   dropout_prob=0.5, dropout_key=key)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    (l1, o1), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    (l2, o2), _ = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                     has_aux=True)(q, k, v)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).sum()) > 0
