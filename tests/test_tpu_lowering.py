"""Pre-flight for chip time: lower the real programs for the TPU platform
from this CPU process and check that the Mosaic calls are there.

With `_interpret` patched to False the Pallas modules take their TPU
branches (in-kernel PRNG, no interpreter), and
`lower(lowering_platforms=("tpu",))` runs Pallas' TPU lowering with its
block-shape checks and jax's "Mosaic kernels cannot be automatically
partitioned" check. Mosaic itself lives in libtpu and only runs when the
chip compiles, so a kernel that lowers here can still be refused there —
but everything this file catches costs no chip time.
"""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
import paddle_tpu.fluid as fluid
from paddle_tpu.models.bert import BertConfig, random_pretrain_batch
from paddle_tpu.ops.pallas import add_ln, conv_bn, flash_attention
from paddle_tpu.ops.pallas import paged_attention as paged

@pytest.fixture(autouse=True)
def _tpu_branches():
    with mock.patch.object(flash_attention, "_interpret", lambda: False), \
            mock.patch.object(add_ln, "_interpret", lambda: False), \
            mock.patch.object(conv_bn, "_interpret", lambda: False), \
            mock.patch.object(paged, "_interpret", lambda: False):
        yield


def _kernel_names(text):
    """Mosaic calls of a lowered module, by pallas_call name=."""
    import re

    assert "tpu_custom_call" in text
    return set(re.findall(r'kernel_name = "([^"]+)"', text))


def _tpu_text(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _bert_step_text(mesh_axes):
    """chip_smoke.py's own step at a size whose shapes still pass every
    kernel gate (hidden a multiple of 128, head width 64, s a multiple
    of 128)."""
    cfg = BertConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=256,
        max_position_embeddings=128, fuse_stack=True, remat_ffn=True)
    batch, seq, max_preds = 8, 128, 20
    main, startup, loss = chip_smoke.build_step(
        cfg, batch, seq, max_preds, mesh_axes)
    exe = fluid.Executor()
    exe.run(startup)
    feed = random_pretrain_batch(cfg, batch, seq, max_preds, seed=0)
    return exe._lower_step(main, feed=feed, fetch_list=[loss],
                           platforms=("tpu",)).as_text()


def test_bert_step_lowers_on_one_device():
    assert set(chip_smoke.STEP_KERNELS) <= _kernel_names(_bert_step_text(None))


def test_bert_step_lowers_on_a_dp_mesh():
    # every Mosaic call must sit inside a shard_map here, or jax refuses
    # the whole step; the virtual CPU mesh alone never notices, because
    # off the TPU the kernel gates route to the jnp compositions
    text = _bert_step_text({"dp": 4})
    assert set(chip_smoke.STEP_KERNELS) <= _kernel_names(text)
    assert "num_partitions = 4" in text


@pytest.mark.parametrize("rows", [608, 3648])
def test_add_ln_lowers_at_mlm_head_rows(rows):
    """batch x 76 masked positions: 608 = 19*32 (b8), 3648 = 57*64 (b48)
    have no row block that is a multiple of 128."""
    x = jnp.zeros((rows, 768), jnp.bfloat16)
    scale = jnp.ones((768,), jnp.float32)
    shift = jnp.zeros((768,), jnp.float32)

    def loss(x, scale, shift):
        out = add_ln.fused_add_ln(x, None, scale, shift)
        return out.astype(jnp.float32).sum()

    text = _tpu_text(jax.grad(loss, argnums=(0, 1, 2)), x, scale, shift)
    assert {"add_ln_fwd", "add_ln_bwd"} <= _kernel_names(text)


def test_paged_attention_lowers_at_h16_d128():
    b, h, d, pages, page, maxp = 4, 16, 128, 32, 16, 8
    q = jnp.zeros((b, h, d), jnp.float32)
    kv = jnp.zeros((pages, page, h, d), jnp.float32)
    table = jnp.zeros((b, maxp), jnp.int32)
    lengths = np.full((b,), 5, np.int32)
    text = _tpu_text(
        lambda *a: paged.paged_attention(*a, impl="pallas"),
        q, kv, kv, table, lengths)
    assert _kernel_names(text) == {"paged_attention"}
