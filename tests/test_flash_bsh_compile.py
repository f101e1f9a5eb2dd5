"""The BSH flash kernels, traced, lowered and compiled for a described
TPU v5e at the benchmark's shapes, from this CPU process and at no chip
time (`on-chip-measurement` guide, section 2, third rehearsal).

What it guards is the cost every process pays in front of the compile
cache: the traced size of the stream kernels (S >= 1024) must not go with
the DMA tile. PR 25 unrolled its compute tiles in Python; the kernels were
16 % faster and `first_step_s` went from 7 s to 29 s. No wall clock is read
here: the equation count of each `pallas_call`'s jaxpr is the measure.
"""
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import flash_attention as fa

NUM_HEADS, HIDDEN = 12, 768
# [B, S, H] of `bert-base.s512` and `bert-base.s4096`, and the shortest S
# that streams, at the same tokens a step
SHAPES = {"s512": (64, 512), "s1024": (32, 1024), "s4096": (8, 4096)}
# the whole-tile kernels of the parent commit (61ef225), counted the same
# way at S 512 and S 4096, where they took 10 s and 27 s to compile: the
# stream kernels' ceiling is 1.5 x these
PARENT_EQNS = {"flash_bsh_fwd": 846, "flash_bsh_bwd": 929}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _count_eqns(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_eqns(sub)
    return n


def _pallas_calls(jaxpr, found):
    """{kernel name: equations in its body}, nested bodies included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = _count_eqns(eqn.params["jaxpr"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


def _traced_grad(one_chip, batch, seq):
    """jax.grad of the attention as the BERT cells call it: per-key
    bias, in-kernel dropout 0.1, 12 heads of 64."""
    act = jax.ShapeDtypeStruct(
        (batch, seq, HIDDEN), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct(
        (batch, 1, 1, seq), jnp.float32, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)

    def loss(q, k, v, bias, seed):
        out = fa.flash_attention_bsh(
            q, k, v, bias=bias, num_heads=NUM_HEADS, dropout_prob=0.1,
            dropout_seed=seed)
        return out.astype(jnp.float32).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    # off the TPU the kernels would take their interpreter branch
    with mock.patch.object(fa, "_interpret", lambda: False):
        fa._make_flash_core_bsh.cache_clear()
        try:
            return grad.trace(act, act, act, bias, seed)
        finally:
            fa._make_flash_core_bsh.cache_clear()


@pytest.fixture(scope="module")
def traced(one_chip):
    return {name: _traced_grad(one_chip, *shape)
            for name, shape in SHAPES.items()}


@pytest.mark.parametrize("cell", ["s512", "s4096"])
def test_both_kernels_compile_for_the_v5e(traced, cell, no_compile_cache):
    compiled = traced[cell].lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "flash_bsh_fwd" in text and "flash_bsh_bwd" in text
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("kernel", sorted(PARENT_EQNS))
def test_traced_size_does_not_go_with_the_tile(traced, kernel):
    small = _pallas_calls(traced["s1024"].jaxpr.jaxpr, {})[kernel]
    large = _pallas_calls(traced["s4096"].jaxpr.jaxpr, {})[kernel]
    # the DMA tile is 512 at S 1024 and 1024 at S 4096, a head's stream
    # 4 and 16 (forward), 2 and 16 (backward) steps long: the body that
    # is traced is one trip's
    assert abs(large - small) <= 0.03 * small, (small, large)
    assert large <= 1.5 * PARENT_EQNS[kernel], (large, PARENT_EQNS[kernel])
    # below S 1024 the whole-tile kernels run, as they were
    assert _pallas_calls(
        traced["s512"].jaxpr.jaxpr, {})[kernel] == PARENT_EQNS[kernel]


# ---------------------------------------------------------------------------
# latent attention's widest group: ten heads of 256 at S 4096
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("passes, mosaic_mib", [
    ("fwd", (90.06, 90.88)), ("fwd_bwd", (101.43, 102.25))])
def test_the_vmem_model_bounds_mosaic_at_ten_heads_of_256(
        one_chip, no_compile_cache, passes, mosaic_mib):
    """`latent_head_groups` trusts `feasible.py`'s model of the stream
    kernels' residency: twenty heads of 256 at S 4096 (5,120 columns) are
    over it and ten (2,560) under. Mosaic's own scoped allocation at
    [1, 4096, 2560], causal, bisected on the limit by described-v5e
    compiles (PR 38): the forward 90.06-90.88 MiB at a 1,024-row tile
    where the model counts 102.0, forward and backward 101.43-102.25 MiB
    (the backward at 256 rows, the largest tile the model admits) where it
    counts 109.5. Here: the calls compile under the model's own estimate as
    the limit, and not under 85 % of it, so the model is an upper bound
    and within a fifth of Mosaic."""
    from paddle_tpu.ops.pallas import feasible

    s, nh, d = 4096, 10, 256
    h = nh * d
    assert not feasible.flash_bsh_ok(s, s, 2 * h, 128, 128)[0]
    assert feasible.flash_bsh_ok(s, s, h, 128, 128)[0]
    bq = fa.default_bsh_block(s, s, h)
    bk = fa.default_bsh_block(s, s, h, bwd=True)
    assert (bq, bk) == (1024, 256)
    model = feasible.flash_bsh_fwd_vmem_bytes(s, s, h, bq, bq)
    if passes == "fwd_bwd":
        model = max(model, feasible.flash_bsh_bwd_vmem_bytes(s, s, h, bk, bk))
    assert mosaic_mib[1] * 2 ** 20 <= model <= feasible.BSH_VMEM_LIMIT
    x = jax.ShapeDtypeStruct((1, s, h), jnp.bfloat16, sharding=one_chip)

    def attend(q, k, v):
        return fa.flash_attention_bsh(q, k, v, None, num_heads=nh,
                                      sm_scale=1 / 16, causal=True,
                                      form="mla_wide")

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    def compile_under(limit):
        blocks = fa._resolve_bsh_blocks

        def resolve(sq, skv, hdim, *, bwd=False):
            return blocks(sq, skv, hdim, bwd=bwd)[:2] + (int(limit),)

        fn = attend if passes == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
        with mock.patch.object(fa, "_interpret", lambda: False), \
                mock.patch.object(fa, "_resolve_bsh_blocks", resolve):
            fa._make_flash_core_bsh.cache_clear()
            try:
                # a new function each time: jit's trace cache would hand
                # back the kernels traced under another limit
                return jax.jit(lambda *a: fn(*a)).trace(x, x, x).lower(
                    lowering_platforms=("tpu",)).compile().as_text()
            finally:
                fa._make_flash_core_bsh.cache_clear()

    text = compile_under(model)
    assert "flash_mla_wide_causal_fwd" in text
    assert ("flash_mla_wide_causal_bwd" in text) == (passes == "fwd_bwd")
    with pytest.raises(Exception, match="(?i)vmem"):
        compile_under(0.85 * model)


# ---------------------------------------------------------------------------
# the grouped-matmul kernels of moe_swiglu (ops/pallas/grouped_matmul.py),
# in this file because one process of a test run may describe the topology
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows, k, n", [
    (32768, 2048, 1792), (32768, 1792, 2048),
    (6144, 2688, 1856), (6144, 1856, 2688)],
    ids=["w1_w3", "w2", "nemotron_w1", "nemotron_w2"])
@pytest.mark.parametrize("form", ["nn", "nt", "tn"])
def test_the_grouped_matmul_compiles_for_the_v5e(one_chip, no_compile_cache,
                                                 form, rows, k, n):
    """The LFM2 cell's operands: 32,768 sorted rows against eight
    [2048, 1792] (W1, W3) or [1792, 2048] (W2) matrices in bf16, at the
    chooser's tiles: Mosaic takes the blocks, the transposed contractions
    and the scoped-VMEM limit. The Nemotron cell's: 6,144 rows against
    eight [2688, 1856] (W1) or [1856, 2688] (W2), where 1856 = 14.5 x 128
    is one block of the whole axis, on the result's lanes (a 64-column
    tail at lane 1792), on the contraction, and on the sublanes of nt's
    and tn's blocks."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    groups = 8

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    a = shaped(rows, n if form == "nt" else k)
    b = shaped(rows, n) if form == "tn" else shaped(groups, k, n)
    with mock.patch.object(gm, "_interpret", lambda: False):
        assert gm.gmm_tiles(form, a, (groups, k, n)) == (256, k, n)
        compiled = jax.jit(
            lambda a, b, sizes: gm._run(form, a, b, sizes, (groups, k, n))
        ).lower(a, b, shaped(groups, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert f"moe_gmm_{form}" in text and "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# the stream-mixing kernels of mhc_post (ops/pallas/mhc.py), in this file
# because one process of a test run may describe the topology
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tokens", [8192, 4096], ids=["step", "check"])
@pytest.mark.parametrize("kernel", ["mhc_post_fwd", "mhc_post_bwd"])
def test_the_mhc_post_kernels_compile_for_the_v5e(one_chip, no_compile_cache,
                                                  kernel, tokens):
    """The Xing4 cell's operands (two sequences of 4,096 tokens a step, one
    in its check program, four streams of 3,584 bf16 columns, float32
    mappings with the tokens on the lanes) at the chooser's row block:
    Mosaic takes the blocks, the lane offsets of the streams, the products
    with the identity and the scoped-VMEM limit the byte model asks for.
    The traced body is one chunk's: its equation count does not go with the
    rows."""
    from paddle_tpu.ops.pallas import mhc

    n, c = 4, 3584

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lead = (tokens // 4096, 4096)
    x, y = shaped(*lead, n * c), shaped(*lead, c)
    maps = (shaped(*lead, n * n, dtype=jnp.float32),
            shaped(*lead, n, dtype=jnp.float32))
    turned = shaped(n * n + n, tokens, dtype=jnp.float32)
    with mock.patch.object(mhc, "_interpret", lambda: False):
        rows = mhc.mhc_rows(x, y, *maps)
        assert rows == 128
        if kernel == "mhc_post_fwd":
            traced = jax.jit(lambda *a: mhc._mhc_fwd(
                *a, br=rows, interpret=False)).trace(x, y, turned)
        else:
            traced = jax.jit(lambda *a: mhc._mhc_bwd(
                *a, br=rows, interpret=False)).trace(x, x, y, turned)
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert kernel in text and "tpu_custom_call" in text
    assert _pallas_calls(traced.jaxpr.jaxpr, {})[kernel] <= {
        "mhc_post_fwd": 250, "mhc_post_bwd": 500}[kernel]


@pytest.mark.parametrize("batch", [2, 1], ids=["step", "check"])
@pytest.mark.parametrize("kernel", ["mhc_map_fwd", "mhc_map_bwd"])
def test_the_mhc_map_kernels_compile_for_the_v5e(one_chip, no_compile_cache,
                                                 kernel, batch):
    """The Xing4 cell's operands (two sequences of 4,096 tokens a step, one
    in its check program, four streams of 3,584 bf16 columns, twenty rounds)
    at the chooser's row block: Mosaic takes the bf16 products with the
    streams' tile stationary, the 144-deep contraction of dX, the sublane
    rotations of Sinkhorn's sums, the turned block of d(raw)'s terms and the
    scoped-VMEM limit the byte model asks for. Sinkhorn's rounds are rolled
    loops: the traced bodies do not go with their number."""
    from paddle_tpu.ops.pallas import feasible, mhc

    n, width, iters = 4, 4 * 3584, 20

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = shaped(batch, 4096, width)
    with mock.patch.object(mhc, "_interpret", lambda: False):
        rows = mhc.map_rows(x, shaped(width, 24, dtype=jnp.float32), n, iters)
    assert rows == 256
    assert feasible.mhc_map_vmem_bytes(
        kernel[-3:], rows, width, n, 2, iters) <= feasible.MHC_VMEM_BUDGET
    operands = [x, shaped(160, width), shaped(24, 2, dtype=jnp.float32)]
    call = {"mhc_map_fwd": mhc._map_fwd, "mhc_map_bwd": mhc._map_bwd}[kernel]
    if kernel == "mhc_map_bwd":
        operands.append(shaped(24, batch * 4096, dtype=jnp.float32))

    def traced(rounds):
        return jax.jit(lambda *a: call(
            *a, n=n, eps=1e-6, iters=rounds, lo=-30.0, hi=30.0, br=rows,
            interpret=False)).trace(*operands)

    twenty = traced(iters)
    text = twenty.lower(lowering_platforms=("tpu",)).compile().as_text()
    assert kernel in text and "tpu_custom_call" in text
    eqns = _pallas_calls(twenty.jaxpr.jaxpr, {})[kernel]
    assert eqns == _pallas_calls(traced(2).jaxpr.jaxpr, {})[kernel]
    assert eqns <= {"mhc_map_fwd": 400, "mhc_map_bwd": 900}[kernel]


@pytest.mark.parametrize("batch", [2, 1], ids=["step", "check"])
def test_the_ssd_scan_kernels_compile_for_the_v5e(one_chip, no_compile_cache,
                                                  batch):
    """The Nemotron cell's scan (two rows of 4,096 positions a step, one in
    its check program; 64 heads of 64 in 8 groups at state 128, chunk 128,
    bf16), its gradient through the custom VJP: Mosaic takes both kernels'
    blocks, the turned x and cotangent, the heads' rows at sublane offsets,
    the per-group lane slices of B and C and the scoped-VMEM limit the byte
    model asks for. The heads are one loop body in the traced jaxpr,
    unrolled where it lowers: the equation count does not go with them."""
    from paddle_tpu.ops.pallas import ssd_scan as ssd

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, bc = shaped(batch, 4096, 64, 64), shaped(batch, 4096, 8, 128)
    dt = shaped(batch, 4096, 64, dtype=jnp.float32)
    vec = shaped(64, dtype=jnp.float32)

    def loss(*args):
        return ssd.ssd_scan(*args, 128).astype(jnp.float32).sum()

    with mock.patch.object(ssd, "_interpret", lambda: False):
        assert ssd.use_kernels(4096, 64, 64, 8, 128, 128, jnp.bfloat16)
        traced = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)))).trace(x, dt, vec, bc, bc, vec)
    text = traced.lower(lowering_platforms=("tpu",)).compile().as_text()
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    eqns = _pallas_calls(traced.jaxpr.jaxpr, {})
    assert eqns["ssd_scan_fwd"] <= 250 and eqns["ssd_scan_bwd"] <= 650


# ---------------------------------------------------------------------------
# a whole step at published widths, in this file because one process of a
# test run may describe the topology
# ---------------------------------------------------------------------------


def test_the_xing4_cell_step_compiles_and_fits_the_v5e(one_chip,
                                                       no_compile_cache):
    """The step of `xing4.0-29b-a4b.tp8ep8share.s4096` as the harness
    builds it, at the cell's batch and the published widths, compiled for
    the described chip with its state given as shapes (656 M parameters
    are not allocated here): Mosaic takes the padded latent-attention
    flash calls, the grouped products at 3584 x 1024, the stream-mixing
    kernels and the mappings' forward kernel, the step holds every call the
    configuration lists, and XLA's
    buffer assignment reads no more than the `peak_hbm_gb` the configuration
    states."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from benchmark import harness, hlo_text, manifest
    from paddle_tpu.fluid.executor import Scope
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import mhc

    cell = manifest.load_cell(manifest.load_manifest(),
                              "xing4.0-29b-a4b.tp8ep8share.s4096")
    batch = int(cell.traffic["batch"])
    built = harness.build_program(cell, batch, dropout=True, seed=1)
    exe, scope = fluid.Executor(), Scope()
    for program in (built.startup, built.main):
        for v in program.global_block().vars.values():
            if v.persistable and v.shape is not None:
                scope.set_var(v.name, jax.ShapeDtypeStruct(
                    tuple(v.shape), np.dtype(v.dtype)))
    feed = cell.family.make_batch(cell.config, cell.traffic, batch,
                                  harness.batch_rng(1, 1, 0))
    with mock.patch.object(fa, "_interpret", lambda: False), \
            mock.patch.object(gm, "_interpret", lambda: False), \
            mock.patch.object(mhc, "_interpret", lambda: False):
        fa._make_flash_core_bsh.cache_clear()
        try:
            compiled = exe._lower_step(
                built.main, feed=feed, fetch_list=[built.loss], scope=scope,
                platforms=("tpu",), sharding=one_chip).compile()
        finally:
            fa._make_flash_core_bsh.cache_clear()
    present = hlo_text.read_step(compiled.as_text()).kernels
    assert set(cell.config["mosaic_calls"]) <= set(present), present
    assert {"mhc_post_fwd", "mhc_post_bwd"} <= set(present), present
    # `mhc_map`'s forward pass is its kernel; its backward pass stays the
    # composition's, whose float32 copy of the streams this peak rests on
    # (with `mhc_map_bwd` in its place the step read 14.890 GB, PR 37)
    assert "mhc_map_fwd" in present and "mhc_map_bwd" not in present
    mem = compiled.memory_analysis()
    peak_gb = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 1e9
    # one-sided since PR 33: the stream-mixing kernels took the peak 2 %
    # under what the configuration (a benchmark file) states
    stated = cell.config["stated"]["peak_hbm_gb"]
    assert peak_gb <= 1.01 * stated, (peak_gb, stated)
    assert 4.0 < peak_gb < 15.75


def test_the_nemotron_cell_step_compiles_and_fits_the_v5e(one_chip,
                                                          no_compile_cache):
    """The step of `nemotron-3-nano-30b-a3b.ep16share.s4096` as the harness
    builds it, at the cell's batch and the published widths, compiled for
    the described chip with its state given as shapes (667 M parameters
    are not allocated here): the chunked state-space scan, the two-matrix
    experts at 2688 x 1856 and the attention call at H 4096 compile, the
    step holds every Mosaic call the configuration lists (the BHSD flash
    kernels: at S 4096 and H 4096 the BSH kernels' whole-sequence residency
    is over their gate) and, since PR 35, the three grouped-matmul kernels
    with 1856 as one whole block, and XLA's buffer assignment reads no more
    than the `peak_hbm_gb` the configuration states."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from benchmark import harness, hlo_text, manifest
    from paddle_tpu.fluid.executor import Scope
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import ssd_scan as ssd

    cell = manifest.load_cell(manifest.load_manifest(),
                              "nemotron-3-nano-30b-a3b.ep16share.s4096")
    batch = int(cell.traffic["batch"])
    built = harness.build_program(cell, batch, dropout=True, seed=1)
    exe, scope = fluid.Executor(), Scope()
    for program in (built.startup, built.main):
        for v in program.global_block().vars.values():
            if v.persistable and v.shape is not None:
                scope.set_var(v.name, jax.ShapeDtypeStruct(
                    tuple(v.shape), np.dtype(v.dtype)))
    feed = cell.family.make_batch(cell.config, cell.traffic, batch,
                                  harness.batch_rng(1, 1, 0))
    with mock.patch.object(fa, "_interpret", lambda: False), \
            mock.patch.object(gm, "_interpret", lambda: False), \
            mock.patch.object(ssd, "_interpret", lambda: False):
        compiled = exe._lower_step(
            built.main, feed=feed, fetch_list=[built.loss], scope=scope,
            platforms=("tpu",), sharding=one_chip).compile()
    text = compiled.as_text()
    present = hlo_text.read_step(text).kernels
    assert set(cell.config["mosaic_calls"]) <= set(present), present
    # 1856 = 14.5 x 128 is served as one block (PR 35; before it the gate
    # refused and every product was XLA's): the bounded block's products
    # are the kernels, and XLA's `ragged-dot` stays for the dropless
    # fallback's product and weight gradient
    assert {"moe_gmm_nn", "moe_gmm_nt", "moe_gmm_tn"} <= set(present), present
    # since PR 39 the state-space scan is its two kernels (14.421 GB here,
    # 14.502 with the composition)
    assert {"ssd_scan_fwd", "ssd_scan_bwd"} <= set(present), present
    assert "ragged-dot" in text
    mem = compiled.memory_analysis()
    peak_gb = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 1e9
    # one-sided since PR 35, as the Xing4 cell's: the configuration (a
    # benchmark file) states the peak of the step with XLA's products
    stated = cell.config["stated"]["peak_hbm_gb"]
    assert peak_gb <= 1.01 * stated, (peak_gb, stated)
    assert 4.0 < peak_gb < 15.2


def test_the_glm_cell_step_compiles_and_fits_the_v5e(one_chip,
                                                     no_compile_cache):
    """The step of `glm-4.7-flash.ep8share.mtp.s4096` as the harness builds
    it, at the cell's batch and the published widths, compiled for the
    described chip with its state given as shapes (707 M parameters are not
    allocated here): the trunk and the multi-token-prediction module, the
    embedding table and the head used twice each. Mosaic takes latent
    attention's twenty heads of 256 / 256 as two calls of ten a block under
    a name of their own (six blocks: twelve forward calls), and the grouped
    products at 2048 x 1536; the step holds every call the configuration
    lists, no attention call is the composition's, XLA rematerializes
    nothing the program did not ask for, and its buffer assignment reads no
    more than the `peak_hbm_gb` the configuration states."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from benchmark import harness, hlo_text, manifest
    from paddle_tpu.fluid.executor import Scope
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.telemetry import get_registry

    cell = manifest.load_cell(manifest.load_manifest(),
                              "glm-4.7-flash.ep8share.mtp.s4096")
    batch = int(cell.traffic["batch"])
    built = harness.build_program(cell, batch, dropout=True, seed=1)
    assert sum(int(np.prod(p.shape)) for p in built.main.all_parameters()
               ) == cell.config["stated"]["parameters"]
    exe, scope = fluid.Executor(), Scope()
    for program in (built.startup, built.main):
        for v in program.global_block().vars.values():
            if v.persistable and v.shape is not None:
                scope.set_var(v.name, jax.ShapeDtypeStruct(
                    tuple(v.shape), np.dtype(v.dtype)))
    feed = cell.family.make_batch(cell.config, cell.traffic, batch,
                                  harness.batch_rng(1, 1, 0))

    def traced(impl, form):
        return get_registry().counter(
            "attention_lowerings_total", impl=impl, form=form).value

    before = traced("pallas", "mla_wide"), traced("jnp", "mla")
    with mock.patch.object(fa, "_interpret", lambda: False), \
            mock.patch.object(gm, "_interpret", lambda: False):
        fa._make_flash_core_bsh.cache_clear()
        try:
            compiled = exe._lower_step(
                built.main, feed=feed, fetch_list=[built.loss], scope=scope,
                platforms=("tpu",), sharding=one_chip).compile()
        finally:
            fa._make_flash_core_bsh.cache_clear()
    # five trunk blocks and the module's: six attention calls, all kernels
    assert traced("pallas", "mla_wide") == before[0] + 6
    assert traced("jnp", "mla") == before[1]
    text = compiled.as_text()
    step = hlo_text.read_step(text)
    assert set(cell.config["mosaic_calls"]) <= set(step.kernels), step.kernels
    forward = [c for c in step.calls.values()
               if c.kernel == "flash_mla_wide_causal_fwd"]
    assert {c.operands[0].dims for c in forward} == {(batch, 4096, 2560)}
    assert len({c.instruction for c in forward}) >= 12
    assert ".remat" not in text
    mem = compiled.memory_analysis()
    peak_gb = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 1e9
    stated = cell.config["stated"]["peak_hbm_gb"]
    assert peak_gb <= 1.01 * stated, (peak_gb, stated)
    assert 4.0 < peak_gb <= 15.2


def test_the_granite_cell_step_compiles_and_fits_the_v5e(one_chip,
                                                         no_compile_cache):
    """The step of `granite-4.0-h-micro.vocab8.s4096` as the harness builds
    it, at the cell's batch and the published widths, compiled for the
    described chip with its state given as shapes (772 M parameters are not
    allocated here): the one NoPE attention layer is the latent form's wide
    kernels on 32 heads of 64 at the given scale, and all nine state-space
    scans are the scan's kernels, a group of 64 heads in four blocks of
    sixteen: the lowering counts nine `pallas` scans, nine splits into four
    blocks and no refusal by `heads_per_group`. Nothing is rematerialized,
    and XLA's buffer assignment reads no more than the `peak_hbm_gb` the
    configuration states."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from benchmark import harness, hlo_text, manifest
    from paddle_tpu.fluid.executor import Scope
    from paddle_tpu.ops.pallas import ssd_scan as ssd
    from paddle_tpu.telemetry import get_registry

    cell = manifest.load_cell(manifest.load_manifest(),
                              "granite-4.0-h-micro.vocab8.s4096")
    batch = int(cell.traffic["batch"])
    built = harness.build_program(cell, batch, dropout=True, seed=1)
    exe, scope = fluid.Executor(), Scope()
    for program in (built.startup, built.main):
        for v in program.global_block().vars.values():
            if v.persistable and v.shape is not None:
                scope.set_var(v.name, jax.ShapeDtypeStruct(
                    tuple(v.shape), np.dtype(v.dtype)))
    feed = cell.family.make_batch(cell.config, cell.traffic, batch,
                                  harness.batch_rng(1, 1, 0))

    def counted(name, **labels):
        return get_registry().counter(name, **labels).value

    before = (counted("ssd_scan_gate_refusals_total",
                      reason="heads_per_group"),
              counted("ssd_scan_lowerings_total", impl="pallas"),
              counted("attention_lowerings_total", impl="pallas",
                      form="mla_wide"),
              counted("ssd_scan_head_blocks_total", blocks="4"))
    with mock.patch.object(fa, "_interpret", lambda: False), \
            mock.patch.object(ssd, "_interpret", lambda: False):
        fa._make_flash_core_bsh.cache_clear()
        try:
            compiled = exe._lower_step(
                built.main, feed=feed, fetch_list=[built.loss], scope=scope,
                platforms=("tpu",), sharding=one_chip).compile()
        finally:
            fa._make_flash_core_bsh.cache_clear()
    assert (counted("ssd_scan_gate_refusals_total", reason="heads_per_group"),
            counted("ssd_scan_lowerings_total", impl="pallas"),
            counted("attention_lowerings_total", impl="pallas",
                    form="mla_wide"),
            counted("ssd_scan_head_blocks_total", blocks="4")) == (
        before[0], before[1] + 9, before[2] + 1, before[3] + 9)
    text = compiled.as_text()
    step = hlo_text.read_step(text)
    assert set(step.kernels) == set(cell.config["mosaic_calls"]) | {
        "ssd_scan_fwd", "ssd_scan_bwd"}, step.kernels
    forward = [c for c in step.calls.values()
               if c.kernel == "flash_mla_wide_causal_fwd"]
    assert {c.operands[0].dims for c in forward} == {(batch, 4096, 2048)}
    assert ".remat" not in text
    mem = compiled.memory_analysis()
    peak_gb = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 1e9
    stated = cell.config["stated"]["peak_hbm_gb"]
    assert peak_gb <= 1.01 * stated, (peak_gb, stated)
    assert 4.0 < peak_gb <= 15.2
