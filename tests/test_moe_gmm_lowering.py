"""The LFM2 decoder step lowered for the TPU from this CPU process, with the
grouped-matmul kernels on their TPU branch: the `moe_gmm_*` calls stand
under `moe_experts`, all three forms in the bounded block, forward and
backward, and in the dropless fallback (`moe_full_width`) the input
gradient alone, beside XLA's `ragged_dot`; call sites of one signature
share one lowered body, so the module's Mosaic bodies do not go with the
number of layers; and the traced size of each kernel is a few dozen
equations whatever the tile.

What this guards is set-up: every process traces and lowers the step in
front of the compile cache (PR 25 paid 30 s there for an unrolled body;
PR 31 measured ~0.1 s a distinct kernel body on the benchmark's host).
The same for the Nemotron family's two-matrix experts at an intermediate
width that is a multiple of 64 and not of 128 (the published 1856 = 14.5
x 128; here 192): one whole block, the inner loop's tail in the same
body.

No wall clock is read; nothing is compiled."""
import dataclasses
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.fluid.executor import Scope
from paddle_tpu.models.lfm2_moe import (ATTENTION, CONV, Lfm2MoeConfig,
                                        build_lfm2_moe_pretrain_program)
from lowered import kernel_sizes
from paddle_tpu.ops.pallas import backend
from paddle_tpu.ops.pallas import grouped_matmul as gm

KERNELS = {"moe_gmm_nn", "moe_gmm_nt", "moe_gmm_tn"}
BATCH, SEQ = 2, 256  # 512 tokens, 1,024 pairs: a share of 2 of 8 is bounded
# equations of a kernel's traced body (its nested bodies included) at
# (256, 2048, 1792): nn and nt 23, tn 54, the loops over 256 columns
# rolled; where a 64-column tail stands behind the loop (the result's
# 1856 columns) nn and nt 41, tn 79; the ceiling leaves room for an
# epilogue, not for a loop unrolled in Python (seven trips of nn's would
# be ~110)
EQN_CEILING = 100


pytestmark = pytest.mark.usefixtures("described_tpu")


def _step_text(expert_layers):
    """One dense layer and `expert_layers` expert layers at widths the gate
    serves (128 lanes), every expert layer holding 2 of 8 experts."""
    kinds = [CONV, ATTENTION, CONV, CONV][:1 + expert_layers]
    cfg = dataclasses.replace(
        Lfm2MoeConfig.tiny(), hidden_size=128, moe_intermediate_size=256,
        num_attention_heads=2, num_key_value_heads=1, experts_held=2,
        num_hidden_layers=len(kinds), layer_types=kinds, remat_ffn=True,
        max_position_embeddings=SEQ)
    return _lowered_step(build_lfm2_moe_pretrain_program, cfg)


def _lowered_step(build, cfg):
    """The bf16-AMP Adam step of `build(cfg, ...)`, lowered for the TPU."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        _, _, _, loss = build(cfg, BATCH, SEQ, main_program=main,
                              startup_program=startup)
        with fluid.program_guard(main, startup):
            mixed_precision.decorate(
                fluid.optimizer.AdamOptimizer(1e-3), use_bf16=True).minimize(
                    loss, startup_program=startup)
    exe, scope = fluid.Executor(), Scope()
    exe.run(startup, scope=scope)
    ids = np.zeros((BATCH, SEQ), np.int32)
    return exe._lower_step(
        main, feed={"input_ids": ids, "labels": ids}, fetch_list=[loss],
        scope=scope, platforms=("tpu",)).as_text(debug_info=True)


@pytest.fixture(scope="module")
def texts():
    # module-scoped: the `described_tpu` fixture is not yet in place
    with mock.patch.object(backend, "interpret", lambda: False):
        return {n: _step_text(n) for n in (1, 3)}


def _bodies(text):
    """Mosaic bodies of a lowered module, by pallas_call name=."""
    return re.findall(r'kernel_name = "([^"]+)"', text)


def _call_sites(text):
    """The shared grouped-matmul functions of a lowered module, once a
    call: the private function's name, e.g. `_gmm_341`."""
    return re.findall(r"call @(_t?gmm(?:_[0-9]+)?)\(", text)


def _scopes(text):
    """The distinct op_names under which those functions are called."""
    return [m for m in set(re.findall(r'loc\("(jit\(step\)[^"]*)"', text))
            if m.endswith(("jit(_gmm)", "jit(_tgmm)"))]


def test_the_kernels_stand_under_moe_experts_in_both_branches(texts):
    text = texts[1]
    assert KERNELS <= set(_bodies(text))
    scopes = _scopes(text)
    assert scopes and all("moe_experts" in s for s in scopes)
    bounded = [s for s in scopes if "moe_full_width" not in s]
    for role in ("forward", "backward"):
        assert any(s.startswith(f"jit(step)/{role}/") for s in bounded), role
    # the fallback: the input gradient's kernel, in the backward pass, with
    # the scope outside the part's; its other products are XLA's own
    fallback = [s for s in scopes if "moe_full_width" in s]
    assert fallback and all(
        s.startswith("jit(step)/backward/") and s.endswith("jit(_gmm)")
        and s.index("moe_full_width") < s.index("moe_experts")
        for s in fallback)
    ragged = [m for m in re.findall(r'loc\("(jit\(step\)[^"]*)"', text)
              if "ragged_dot" in m]
    assert ragged and all("moe_full_width" in m and "moe_experts" in m
                          for m in ragged)
    # one expert layer, the bounded block: three products forward, and in
    # the backward pass three recomputed, three input gradients and three
    # weight gradients (_tgmm); the fallback: three input gradients
    sites = _call_sites(text)
    assert len(sites) == 12 + 3
    assert sum(s.startswith("_tgmm") for s in sites) == 3


def test_the_lowered_bodies_do_not_go_with_the_layers(texts):
    one, three = (sorted(b for b in _bodies(texts[n]) if b in KERNELS)
                  for n in (1, 3))
    assert len(_call_sites(texts[3])) == 3 * len(_call_sites(texts[1]))
    assert one == three
    # a form lowers once a signature: two operand shapes (W1 / W3 and W2),
    # the input gradient at two row counts (the bound, and T * k under
    # moe_full_width); the backward's recomputation shares the forward
    # pass's nn bodies
    assert one == sorted(["moe_gmm_nn"] * 2 + ["moe_gmm_nt"] * 4
                         + ["moe_gmm_tn"] * 2)


def _nemotron_step_text(expert_layers):
    """`expert_layers` layers of two-matrix experts behind one Mamba-2
    layer, 2 of 16 experts held, the experts 192 wide: no multiple of 128."""
    from paddle_tpu.models.nemotron_h import (
        NemotronHConfig, build_nemotron_h_pretrain_program)

    cfg = NemotronHConfig.tiny(
        hidden_size=128, moe_intermediate_size=192,
        moe_shared_expert_intermediate_size=256, experts_held=2,
        num_hidden_layers=1 + expert_layers,
        hybrid_override_pattern="M" + "E" * expert_layers, remat_ffn=True,
        max_position_embeddings=SEQ)
    return _lowered_step(build_nemotron_h_pretrain_program, cfg)


@pytest.fixture(scope="module")
def nemotron_texts():
    with mock.patch.object(backend, "interpret", lambda: False):
        return {n: _nemotron_step_text(n) for n in (1, 2)}


def test_two_matrix_experts_of_192_columns_take_the_kernels(nemotron_texts):
    text = nemotron_texts[1]
    assert KERNELS <= set(_bodies(text))
    scopes = _scopes(text)
    assert scopes and all("moe_experts" in s for s in scopes)
    bounded = [s for s in scopes if "moe_full_width" not in s]
    for role in ("forward", "backward"):
        assert any(s.startswith(f"jit(step)/{role}/") for s in bounded), role
    fallback = [s for s in scopes if "moe_full_width" in s]
    assert fallback and all(
        s.startswith("jit(step)/backward/") and s.endswith("jit(_gmm)")
        for s in fallback)
    ragged = [m for m in re.findall(r'loc\("(jit\(step\)[^"]*)"', text)
              if "ragged_dot" in m]
    assert ragged and all("moe_full_width" in m for m in ragged)
    # two products a block: two forward, and in the backward pass two
    # recomputed, two input gradients and two weight gradients; the
    # fallback: two input gradients
    sites = _call_sites(text)
    assert len(sites) == 8 + 2
    assert sum(s.startswith("_tgmm") for s in sites) == 2


def test_the_two_matrix_bodies_do_not_go_with_the_layers(nemotron_texts):
    one, two = (sorted(b for b in _bodies(nemotron_texts[n]) if b in KERNELS)
                for n in (1, 2))
    assert len(_call_sites(nemotron_texts[2])) == 2 * len(
        _call_sites(nemotron_texts[1]))
    # one lowered body a signature, the tail inside it and not a second
    # `pallas_call`: W1 and W2, the input gradient at the bound and at T * k
    assert one == two == sorted(["moe_gmm_nn"] * 2 + ["moe_gmm_nt"] * 4
                                + ["moe_gmm_tn"] * 2)


@pytest.mark.parametrize("k, n, rows", [
    (2048, 1792, 8192), (2048, 1792, 65536),  # the LFM2 cell
    (2688, 1856, 6144), (2688, 1856, 49152),  # the Nemotron cell, W1
    (1856, 2688, 6144), (1856, 2688, 49152),  # and W2
])
@pytest.mark.parametrize("form", gm.FORMS)
def test_the_traced_body_is_small_and_does_not_go_with_the_rows(form, k, n,
                                                                rows):
    a = jax.ShapeDtypeStruct((rows, n if form == "nt" else k), jnp.bfloat16)
    b = (jax.ShapeDtypeStruct((rows, n), jnp.bfloat16) if form == "tn"
         else jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16))
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda a, b, s: gm._run(form, a, b, s, (8, k, n)))(a, b, sizes)
    (name, eqns), = kernel_sizes(jaxpr.jaxpr).items()
    assert name == f"moe_gmm_{form}"
    assert eqns <= EQN_CEILING, eqns
