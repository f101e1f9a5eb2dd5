"""Pallas kernel autotuner (ISSUE 13): cache round-trip + invalidation,
deterministic mocked-timer search (winner selection, tie-break
stability), feasibility-gate rejection paths, flag-off bit-identity of
the emitted HLO, empty-cache fallback (no behavior cliff), the
space-to-depth conv variant's parity, and the op_bench/cost.py
measurement plumbing the searcher consumes."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import paddle_tpu.fluid as fluid
from paddle_tpu import tuning
from paddle_tpu.tuning import configs, feasible
from paddle_tpu.tuning.cache import TuningCache, canonical_key
from paddle_tpu.tuning.search import Searcher, SearchTarget, mock_measure


@pytest.fixture
def autotune_on():
    fluid.flags.set_flags({"FLAGS_kernel_autotune": True})
    tuning.clear_choices()
    yield
    fluid.flags.set_flags({"FLAGS_kernel_autotune": False})


def _target(kernel="k", key=None, candidates=None, **kw):
    return SearchTarget(
        kernel=kernel, key=key or {"s": 1},
        candidates=candidates if candidates is not None
        else [{"a": 1}, {"a": 2}], **kw)


# ---------------------------------------------------------------------------
# cache layer
# ---------------------------------------------------------------------------


def test_canonical_key_is_sorted_and_dtype_normalized():
    a = canonical_key({"h": 128, "sq": 512, "dtype": jnp.float32})
    b = canonical_key({"dtype": np.dtype("float32"), "sq": 512, "h": 128})
    c = canonical_key({"dtype": "float32", "h": 128, "sq": 512})
    assert a == b == c == "dtype=float32,h=128,sq=512"


def test_cache_round_trip(tmp_path):
    cache = TuningCache("cpu")
    cache.put("flash_bsh", "sq=256", {"config": {"bq": 128}, "us": 5.0})
    path = cache.save(str(tmp_path / "cpu.json"))
    loaded, reason = TuningCache.load(path, expect_chip="cpu")
    assert reason is None
    assert loaded.get("flash_bsh", "sq=256")["config"] == {"bq": 128}
    assert loaded.fingerprint() == cache.fingerprint()
    # canonical blob is byte-stable across a load/save cycle
    path2 = loaded.save(str(tmp_path / "again.json"))
    assert open(path).read() == open(path2).read()


def test_cache_version_and_chip_invalidation(tmp_path):
    cache = TuningCache("v5e")
    cache.put("add_ln", "r=8", {"config": {"block_rows": 8}})
    path = cache.save(str(tmp_path / "c.json"))
    # chip mismatch: a v5e cache must never feed configs to a cpu run
    loaded, reason = TuningCache.load(path, expect_chip="cpu")
    assert loaded is None and "chip mismatch" in reason
    # version mismatch: stale schema is ignored wholesale
    raw = json.load(open(path))
    raw["version"] = 999
    json.dump(raw, open(path, "w"))
    loaded, reason = TuningCache.load(path, expect_chip="v5e")
    assert loaded is None and "version mismatch" in reason
    # unreadable file is a reason, not a crash
    open(path, "w").write("{not json")
    loaded, reason = TuningCache.load(path)
    assert loaded is None and "unreadable" in reason


def test_env_cache_overrides_user_layer(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_AUTOTUNE_CHIP", "cpu")
    user_dir = tmp_path / "xdg"
    monkeypatch.setenv("XDG_CACHE_HOME", str(user_dir))
    user = TuningCache("cpu")
    user.put("add_ln", "r=64", {"config": {"block_rows": 8}})
    user.put("add_ln", "r=128", {"config": {"block_rows": 16}})
    user.save(tuning.user_cache_path("cpu"))
    env = TuningCache("cpu")
    env.put("add_ln", "r=64", {"config": {"block_rows": 32}})
    env_path = tmp_path / "env.json"
    env.save(str(env_path))
    monkeypatch.setenv("PADDLE_AUTOTUNE_CACHE", str(env_path))
    merged = tuning.load_active_cache("cpu")
    # env layer wins where it speaks; user layer fills the rest
    assert merged.get("add_ln", "r=64")["config"] == {"block_rows": 32}
    assert merged.get("add_ln", "r=128")["config"] == {"block_rows": 16}


# ---------------------------------------------------------------------------
# search harness
# ---------------------------------------------------------------------------


def test_mock_search_is_deterministic(tmp_path):
    t = _target(candidates=[{"a": 1}, {"a": 2}, {"a": 3}])
    results = []
    for _ in range(2):
        cache = TuningCache("cpu")
        s = Searcher(cache, mock_measure, log=lambda m: None)
        results.append(s.search(t))
    assert results[0].winner == results[1].winner
    assert results[0].us == results[1].us


def test_search_winner_selection_and_tie_break():
    # deliberate tie between candidates 0 and 2: the FIRST enumerated
    # wins (enumeration order is the documented deterministic tie-break)
    times = {1: 7.0, 2: 9.0, 3: 7.0}

    def measure(target, cfg):
        return times[cfg["a"]]

    cache = TuningCache("cpu")
    s = Searcher(cache, measure, log=lambda m: None)
    res = s.search(_target(candidates=[{"a": 1}, {"a": 2}, {"a": 3}]))
    assert res.winner == {"a": 1} and res.us == 7.0
    # winner persisted under the canonical key with its objective
    entry = cache.get("k", "s=1")
    assert entry["config"] == {"a": 1} and entry["us"] == 7.0


def test_search_cache_hit_skips_measurement():
    calls = []

    def measure(target, cfg):
        calls.append(cfg)
        return 1.0

    cache = TuningCache("cpu")
    s = Searcher(cache, measure, log=lambda m: None)
    first = s.search(_target())
    assert not first.cache_hit and calls
    calls.clear()
    second = s.search(_target())
    assert second.cache_hit and second.winner == first.winner
    assert calls == []  # 100% cache hit: zero re-measurement


def test_search_no_feasible_candidates_raises_with_audit():
    t = _target(candidates=[],
                rejected=[({"a": 9}, "VMEM estimate over budget")])
    s = Searcher(TuningCache("cpu"), mock_measure, log=lambda m: None)
    with pytest.raises(feasible.NoFeasibleConfig) as ei:
        s.search(t)
    assert ei.value.tried == [({"a": 9}, "VMEM estimate over budget")]
    assert isinstance(ei.value, ValueError)  # legacy except-clauses hold


def test_search_hbm_gate_rejects_oversized_candidates():
    t = _target(candidates=[{"mask": "materialize"}, {"mask": "regen"}],
                hbm_bytes=lambda c: 10**9 if c["mask"] == "materialize"
                else 0)
    cache = TuningCache("cpu")
    s = Searcher(cache, lambda target, cfg: 1.0,
                 hbm_budget_bytes=10**6, log=lambda m: None)
    res = s.search(t)
    assert res.winner == {"mask": "regen"}
    assert any("HBM gate" in why for _c, why in res.rejected)


# ---------------------------------------------------------------------------
# candidate enumeration + feasibility models
# ---------------------------------------------------------------------------


def test_flash_candidates_feasibility():
    ok, rejects = configs.flash_bsh_candidates(4096, 4096, 768, "bfloat16")
    assert {"bq": 1024, "bk": 1024} in ok  # the hand-measured winner
    # nothing infeasible leaks through
    for cfg in ok:
        feas, _ = feasible.flash_bsh_ok(4096, 4096, 768,
                                        cfg["bq"], cfg["bk"])
        assert feas
    # the bwd's residency is q^T / do^T / dq^T, 8 B/elem of sq*h: the
    # model must admit what Mosaic was seen to allocate (69.4 MiB at
    # s8192/h768) and reject a batch row that cannot fit
    assert feasible.flash_bsh_bwd_vmem_bytes(
        8192, 8192, 768, 1024, 1024) <= feasible.BSH_VMEM_LIMIT
    assert feasible.flash_bsh_bwd_vmem_bytes(
        8192, 8192, 768, 1024, 1024) >= 69.4 * 2**20
    assert feasible.flash_bsh_bwd_vmem_bytes(
        32768, 32768, 768, 128, 128) > feasible.BSH_VMEM_LIMIT
    # dropout doubles the space with the mask axis
    okd, _ = configs.flash_bsh_candidates(512, 512, 768, "bfloat16",
                                          dropout=True)
    assert {"bq": 512, "bk": 512, "mask": "regen"} in okd
    assert {"bq": 512, "bk": 512, "mask": "materialize"} in okd


def test_ln_and_conv_candidates():
    ok, _ = configs.add_ln_candidates(256, 128)
    # the [1, R] stat rows are blocked (1, rows): multiples of 128 only
    assert ok == [{"block_rows": 256}, {"block_rows": 128}]
    ok, rej = configs.conv_bn_candidates("apply", 25, 8)
    assert ok == [{"block_rows": 1}]  # 25 has no larger divisor in menu
    assert rej  # and the audit trail names the non-divisors


def test_s2d_candidates_structural_gates():
    # stride-1 and 1x1 have no s2d lowering
    ok, rej = configs.conv_bn_s2d_candidates(1, 8, 8, 4, 4, 3, 3, (1, 1))
    assert ok == [] and "stride-2" in rej[0][1]
    ok, _ = configs.conv_bn_s2d_candidates(1, 8, 8, 4, 4, 1, 1, (2, 2))
    assert ok == []
    # odd padded extent with an EVEN kernel changes the output size
    ok, rej = configs.conv_bn_s2d_candidates(1, 9, 9, 4, 4, 2, 2, (2, 2))
    assert ok == [] and "even kernel" in rej[0][1]
    # the eligible case offers both lowerings, reference first
    ok, _ = configs.conv_bn_s2d_candidates(1, 10, 10, 4, 4, 3, 3, (2, 2))
    assert ok == [{"space_to_depth": 0}, {"space_to_depth": 1}]


# ---------------------------------------------------------------------------
# kernel resolvers: fallback, validation, NoFeasibleConfig
# ---------------------------------------------------------------------------


def test_resolvers_flag_off_never_touch_the_cache():
    from paddle_tpu.ops.pallas import add_ln
    from paddle_tpu.ops.pallas import flash_attention as fa

    assert not tuning.enabled()
    key = canonical_key({"r": 256, "h": 128, "dtype": "float32"})
    with tuning.override({"add_ln": {key: {"block_rows": 64}}}):
        # flag off: the override must be invisible
        assert add_ln._resolve_ln_rows(256, 128, "float32") == 256
    assert fa._resolve_bsh_blocks(512, 512, 256, "float32")[0] == 512


def test_resolvers_empty_cache_fall_back_to_hand_picked(autotune_on):
    from paddle_tpu.ops.pallas import add_ln, conv_bn
    from paddle_tpu.ops.pallas import flash_attention as fa

    with tuning.override({}):
        assert add_ln._resolve_ln_rows(256, 128, "float32") == \
            add_ln.default_ln_rows(256, 128)
        assert fa._resolve_bsh_blocks(512, 512, 256, "float32")[:2] == (
            fa.default_bsh_block(512, 512, 256),
            fa.default_bsh_block(512, 512, 256))
        assert conv_bn._resolve_rows(64, 16, 8, "mm", "float32") == \
            conv_bn.default_conv_bn_rows(64, 16, 8)
        # the fallback decision is recorded for bench reproducibility
        chosen = tuning.chosen_configs()
        assert any(v["source"] == "default" for v in chosen.values())


def test_resolvers_use_cache_entry_and_validate(autotune_on):
    from paddle_tpu.ops.pallas import add_ln
    from paddle_tpu.ops.pallas import flash_attention as fa

    lnkey = canonical_key({"r": 256, "h": 128, "dtype": "float32"})
    with tuning.override({"add_ln": {lnkey: {"block_rows": 128}}}):
        assert add_ln._resolve_ln_rows(256, 128, "float32") == 128
        assert any(v["source"] == "cache"
                   for v in tuning.chosen_configs().values())
    # a non-dividing row block, or one the TPU cannot tile the stat
    # rows with, is REJECTED -> hand-picked fallback
    for bad in (100, 64):
        with tuning.override({"add_ln": {lnkey: {"block_rows": bad}}}):
            assert add_ln._resolve_ln_rows(256, 128, "float32") == 256
    fkey = canonical_key({"sq": 512, "skv": 512, "h": 256,
                          "dtype": "float32"})
    with tuning.override({"flash_bsh": {fkey: {"bq": 256, "bk": 128}}}):
        assert fa._resolve_bsh_blocks(512, 512, 256, "float32")[:2] == \
            (256, 128)
    # an over-budget tile pair is rejected by the footprint model
    with tuning.override({"flash_bsh": {fkey: {"bq": 999999,
                                               "bk": 999999}}}):
        assert fa._resolve_bsh_blocks(512, 512, 256, "float32")[:2] == \
            (512, 512)


def test_env_block_override_beats_cache(autotune_on, monkeypatch):
    from paddle_tpu.ops.pallas import flash_attention as fa

    fkey = canonical_key({"sq": 512, "skv": 512, "h": 256,
                          "dtype": "float32"})
    monkeypatch.setenv("PADDLE_FLASH_BLOCK", "128")
    with tuning.override({"flash_bsh": {fkey: {"bq": 256, "bk": 256}}}):
        assert fa._resolve_bsh_blocks(512, 512, 256, "float32")[:2] == \
            (128, 128)


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


def test_mask_materialize_axis(autotune_on):
    from paddle_tpu.ops.pallas import flash_attention as fa

    key = canonical_key({"sq": 256, "skv": 256, "h": 128,
                         "dtype": "float32"})
    with tuning.override({"flash_bsh": {key: {"bq": 128, "bk": 128,
                                              "mask": "materialize"}}}):
        assert fa._bsh_mask_materialize(256, 256, 128, "float32")
    with tuning.override({"flash_bsh": {key: {"bq": 128, "bk": 128}}}):
        assert not fa._bsh_mask_materialize(256, 256, 128, "float32")


# ---------------------------------------------------------------------------
# flag-off bit-identity + compile-cache key
# ---------------------------------------------------------------------------


def _lowered_ln_text():
    from paddle_tpu.ops.pallas.add_ln import fused_add_ln

    x = jnp.ones((256, 128), jnp.float32)
    sc = jnp.ones((128,), jnp.float32)
    sh = jnp.zeros((128,), jnp.float32)

    def f(x, sc, sh):
        return fused_add_ln(x, None, sc, sh)

    return jax.jit(f).lower(x, sc, sh).as_text()


def test_flag_off_emitted_hlo_bit_identical():
    key = canonical_key({"r": 256, "h": 128, "dtype": "float32"})
    baseline = _lowered_ln_text()
    # flag OFF + a cache entry that WOULD change the block size: the
    # lowered computation must be byte-identical to the no-cache build
    with tuning.override({"add_ln": {key: {"block_rows": 128}}}):
        assert _lowered_ln_text() == baseline
    # flag ON + empty cache: still byte-identical (no behavior cliff)
    fluid.flags.set_flags({"FLAGS_kernel_autotune": True})
    try:
        with tuning.override({}):
            assert _lowered_ln_text() == baseline
        # flag ON + a real entry: the block size actually moves
        with tuning.override({"add_ln": {key: {"block_rows": 128}}}):
            assert _lowered_ln_text() != baseline
    finally:
        fluid.flags.set_flags({"FLAGS_kernel_autotune": False})


def test_executor_cache_key_rides_cache_fingerprint():
    from paddle_tpu.fluid.executor import Executor

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        block = main_p.global_block()
        block.create_var(name="x", shape=(4, 4), dtype=np.float32)
        block.create_var(name="out")
        block.append_op(type="scale", inputs={"X": ["x"]},
                        outputs={"Out": ["out"]}, attrs={"scale": 2.0})
    feeds = {"x": np.zeros((4, 4), np.float32)}

    def key():
        return Executor._cache_key(main_p, feeds, ("out",), False)

    base = key()
    assert base[-1] is None  # flag off: key unchanged vs pre-autotune
    with tuning.override({"add_ln": {"r=1": {"block_rows": 8}}}):
        assert key() == base  # flag off: override invisible
    fluid.flags.set_flags({"FLAGS_kernel_autotune": True})
    try:
        k_empty = key()
        assert k_empty[-1] is not None
        with tuning.override({"add_ln": {"r=1": {"block_rows": 8}}}):
            k_a = key()
        with tuning.override({"add_ln": {"r=1": {"block_rows": 16}}}):
            k_b = key()
        assert k_a != k_b != k_empty  # an edited cache must retrace
    finally:
        fluid.flags.set_flags({"FLAGS_kernel_autotune": False})


# ---------------------------------------------------------------------------
# space-to-depth conv variant (the tuned kxk stride-2 lowering)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hw,k,pad", [(9, 3, "SAME"), (10, 3, "VALID")])
def test_conv_bn_s2d_parity(autotune_on, hw, k, pad):
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas import conv_bn as cb

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, hw, hw, 4).astype(np.float32))
    wt = jnp.asarray(rng.randn(6, 4, k, k).astype(np.float32) * 0.1)
    sc = jnp.asarray(rng.rand(6).astype(np.float32) + 0.5)
    bi = jnp.asarray(rng.randn(6).astype(np.float32))
    strides = (2, 2)
    pads = cb._resolve_pads(pad, hw, hw, k, k, strides)
    assert cb.conv_bn_s2d_ok(x.shape, wt.shape, strides, pads)
    key = canonical_key({"n": 2, "h": hw, "w": hw, "c": 4, "o": 6,
                         "kh": k, "kw": k, "sh": 2, "sw": 2,
                         "dtype": "float32"})
    entries = {"conv_bn_s2d": {key: {"space_to_depth": 1}}}
    ref = cb.conv_bn_reference(x, wt, sc, bi, strides=strides, pads=pads,
                               with_relu=True)
    prev = attention.FORCE_PALLAS
    attention.FORCE_PALLAS = True
    try:
        with tuning.override(entries):
            assert cb._s2d_wanted(x.shape, wt.shape, strides, pads,
                                  x.dtype)
            got = cb.fused_conv_bn(x, wt, sc, bi, strides=strides,
                                   pads=pad, with_relu=True)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

        def loss(fn):
            def run(x_, w_, s_, b_):
                y, _m, _v = fn(x_, w_, s_, b_)
                return jnp.sum(y * jnp.cos(y))
            return run

        def fused(x_, w_, s_, b_):
            with tuning.override(entries):
                return cb.fused_conv_bn(x_, w_, s_, b_, strides=strides,
                                        pads=pad, with_relu=True)

        def refc(x_, w_, s_, b_):
            return cb.conv_bn_reference(x_, w_, s_, b_, strides=strides,
                                        pads=pads, with_relu=True)

        gf = jax.grad(loss(fused), argnums=(0, 1, 2, 3))(x, wt, sc, bi)
        gr = jax.grad(loss(refc), argnums=(0, 1, 2, 3))(x, wt, sc, bi)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3)
    finally:
        attention.FORCE_PALLAS = prev


def test_s2d_not_taken_without_cache_entry(autotune_on):
    from paddle_tpu.ops.pallas import conv_bn as cb

    pads = cb._resolve_pads("SAME", 9, 9, 3, 3, (2, 2))
    with tuning.override({}):
        assert not cb._s2d_wanted((2, 9, 9, 4), (6, 4, 3, 3), (2, 2),
                                  pads, jnp.float32)


# ---------------------------------------------------------------------------
# measurement plumbing: op_bench + cost per-op query + CLI round trip
# ---------------------------------------------------------------------------


def test_op_bench_run_case_schema_and_sweep():
    import op_bench

    row = op_bench.run_case("matmul", {"X": (8, 8), "Y": (8, 8)}, {},
                            repeat=2, op_profile=False)
    assert row["op"] == "matmul" and row["fenced"] is True
    assert row["latency_us"] > 0 and row["repeat"] == 2
    combos = list(op_bench.sweep_cases(
        [("X", [(8, 8), (16, 16)]), ("Y", [(8, 8)])]))
    assert combos == [{"X": (8, 8), "Y": (8, 8)},
                      {"X": (16, 16), "Y": (8, 8)}]


def test_op_bench_op_profile_objective():
    import op_bench

    row = op_bench.run_case("matmul", {"X": (32, 32), "Y": (32, 32)}, {},
                            repeat=2, op_profile=True, op_profile_steps=2)
    # the candidate's OWN attributed device time — the autotune objective
    assert row["op_device_us"] > 0
    assert 0 < row["op_profile_coverage"] <= 1.0


def test_cost_report_per_op_query():
    from paddle_tpu.telemetry.cost import CostReport, CostRow

    rows = [
        CostRow(scope="op0:matmul", op_index=0, op_type="matmul",
                device_ms=6.0, share=0.6, count=2, fused=False),
        CostRow(scope="op1:softmax", op_index=1, op_type="softmax",
                device_ms=4.0, share=0.4, count=2, fused=False),
    ]
    rep = CostReport(rows=rows, by_op_type={}, by_layer={}, framework={},
                     unattributed={}, steps=2, total_op_ms=10.0,
                     attributed_ms=10.0, coverage=1.0,
                     device_ms_per_step=5.0)
    assert rep.device_ms_for(op_type="matmul") == 3.0  # per step
    assert rep.device_ms_for(op_type="matmul", per_step=False) == 6.0
    assert rep.device_ms_for(op_index=1) == 2.0
    assert rep.device_ms_for(op_type="missing") == 0.0
    assert len(rep.rows_for(op_type="softmax")) == 1


def test_autotune_cli_mock_search_cache_reuse(tmp_path, monkeypatch):
    """search twice with the deterministic mock: the second run is a
    100% cache hit and the file is byte-identical (the CI lane asserts
    the same over the real CPU-interpret measurement path)."""
    import autotune as at

    cache_path = str(tmp_path / "cpu.json")
    monkeypatch.setenv("PADDLE_AUTOTUNE_CHIP", "cpu")
    argv = ["search", "--ln", "256:128", "--measure", "mock",
            "--cache", cache_path, "--json"]
    assert at.main(argv) == 0
    first = open(cache_path).read()
    blob = json.loads(first)
    assert blob["entries"]["add_ln"]
    assert at.main(argv) == 0
    assert open(cache_path).read() == first
    # and the flag state was restored
    assert not tuning.enabled()


def test_autotune_cli_show_and_diff(tmp_path, capsys):
    import autotune as at

    a = TuningCache("cpu")
    a.put("add_ln", "r=64", {"config": {"block_rows": 8}, "us": 1.0})
    pa = a.save(str(tmp_path / "a.json"))
    b = TuningCache("cpu")
    b.put("add_ln", "r=64", {"config": {"block_rows": 16}, "us": 2.0})
    b.put("conv_bn", "r=8", {"config": {"block_rows": 8}})
    pb = b.save(str(tmp_path / "b.json"))
    assert at.main(["show", "--cache", pa]) == 0
    out = capsys.readouterr().out
    assert "add_ln" in out and "block_rows" in out
    assert at.main(["diff", pa, pb, "--json"]) == 1  # differences found
    diff = json.loads(capsys.readouterr().out)
    assert len(diff["added"]) == 1 and len(diff["changed"]) == 1
    assert at.main(["diff", pa, pa, "--json"]) == 0  # identical
