"""The `lfm2-8b-a1b` configuration and its cell on the CPU: the manifest's
entries against the published config, the family file's arithmetic and
traffic, the program against the reference with the AMP rewrite left out,
the part scopes' rule and split over a hand-made trace, the six new
readers, and a traced rehearsal."""
import json
import os
import shutil
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

from benchmark import harness, hlo_text, manifest, roles, scopes
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event, Line, Plane
from test_bench_roles import _xplane  # the trace file's wire format, by hand

CELL = "lfm2-8b-a1b.ep4share.s4096"
# `config` of LFM2-8B-A1B in the model-configs catalog, which is the
# released config.json without the keys that say nothing about the shape
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
NEW_READERS = {
    "moe_experts_ms_per_step": "experts", "moe_experts_roofline": "experts",
    "moe_route_dispatch_ms_per_step": "experts",
    "short_conv_ms_per_step": "short_conv",
    "causal_flash_ms_per_step": "kernels", "causal_flash_roofline": "kernels"}
MS = 1e6  # ns


@pytest.fixture(scope="module")
def doc():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def cell(doc):
    return manifest.load_cell(doc, CELL)


def test_the_manifest_has_the_cell_and_no_problems(doc, cell):
    assert manifest.problems(doc) == []
    row = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (row["config"], row["traffic"], row["chips"]) == (
        "lfm2-8b-a1b", "pretrain-s4096-packed", 1)
    assert doc["workloads"][-1] is row and doc["configs"][-1]["name"] == (
        "lfm2-8b-a1b")  # appended, nothing put in the middle
    tokens = next(m for m in doc["end_to_end"]
                  if m["name"] == "tokens_per_s_per_chip")
    assert tokens["workloads"][-1] == CELL
    rows = {m["name"]: m for m in doc["per_layer"]}
    for name, layer in NEW_READERS.items():
        reader = manifest.load_module("layer_metrics", name)
        assert rows[name]["workloads"] == [CELL]
        assert (reader.LAYER, reader.MOVES, reader.SOURCE) == (
            layer, "tokens_per_s_per_chip", "device_trace")
        assert reader.UNIT == ("%" if name.endswith("_roofline") else "ms")
    assert [m["name"] for m in doc["per_layer"][-6:]] == list(NEW_READERS)
    # every metric of the cell has a reader, and throughput is among them
    assert {"tokens_per_s_per_chip", "step_ms", "peak_hbm_gb", "setup_s"} == {
        m["name"] for m in cell.end_to_end}
    assert cell.traffic["batch"] * cell.traffic["seq_len"] == 16384
    assert (cell.traffic["log_every"], cell.traffic["pool"],
            cell.traffic["check_batch"], cell.traffic["mesh"]) == (5, 8, 1, None)


def test_every_published_number_stands_unless_reduced(doc, cell):
    entry = doc["configs"][-1]
    config = cell.config
    assert entry["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                               "blob/main/config.json")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            assert config[key] == value, key
    # the cut: leading dense layers once, then one whole period, 1 : 3
    assert config["layer_types"] == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert config["published"]["num_hidden_layers"] == 24
    # the chip's share, a quarter of the experts and of the vocabulary,
    # at or above the guide's floors (8 experts, an eighth of the rows)
    assert (config["experts_held"], config["first_expert"]) == (8, 0)
    assert config["vocab_rows"] * 4 == config["vocab_size"]
    assert "four chips share each layer" in config["deployment"]
    assert set(config["changed"]) >= set(entry["reduced"]) | {
        "experts_held", "vocab_rows"}
    assert set(config["assumed"]) >= {"router_score", "tie_embedding",
                                      "initializer_range", "optimizer"}
    assert config["program"] == {"amp": "bf16", "use_flash_attention": True,
                                 "remat_ffn": True}
    assert config["mosaic_calls"] == ["flash_bsh_causal_fwd",
                                      "flash_bsh_causal_bwd"]
    labels = [label for label, _, _ in cell.family.check_parameters(config)]
    assert set(config["check"]["grad_rel_l2"]) == set(labels)
    # the program that is built has exactly these sizes
    built = cell.family.model_config(config)
    assert (built.hidden_size, built.head_dim, built.num_experts,
            built.experts_held, built.vocab_rows, built.remat_ffn) == (
        2048, 64, 32, 8, 16384, True)


def test_model_flops_are_of_what_the_chip_computes(cell):
    config, traffic = cell.config, cell.traffic
    parts = cell.family.forward_flops_per_token(config, 4096)
    h = 2048
    assert parts["short_conv"] == 4 * (2 * h * 3 * h + 2 * h * h)  # 134 M
    assert parts["dense_mlp"] == 6 * h * 7168  # 88 M
    # a quarter of the four picks falls on the eight experts held
    assert parts["experts"] == 4 * 1 * 6 * h * 1792  # 88 M
    assert parts["head"] == 2 * h * 16384  # 67 M
    # the causal triangle, not the square: (S + 1) / 2 keys a query
    assert parts["attention"] == (2 * h * (2 * h + 2 * 512)
                                  + 4 * h * 4097 / 2)  # 37.8 M
    total = sum(parts.values())
    assert total == pytest.approx(415.8e6, rel=1e-3)
    assert cell.family.step_flops(config, traffic, 4) == pytest.approx(
        3 * total * 16384)
    assert cell.family.step_flops(config, traffic, 4) == pytest.approx(
        20.44e12, rel=1e-3)
    assert cell.family.units_per_step(traffic) == 16384
    params = 507.8e6  # ISSUE 28's count of what the chip holds
    n = (h * 3 * h + 3 * h + h * h) * 4 + (2 * h * h + 2 * h * 512) + (
        3 * h * 7168) + 4 * (h * 32 + 3 * 8 * h * 1792) + 16384 * h
    assert n == pytest.approx(params, rel=2e-3)


def test_kernel_files_count_the_triangle_and_the_expected_rows(cell):
    fwd = manifest.load_module("kernels", "flash_bsh_causal_fwd")
    bwd = manifest.load_module("kernels", "flash_bsh_causal_bwd")
    square = manifest.load_module("kernels", "flash_bsh_fwd")
    q = hlo_text.Shape("bf16", (4, 4096, 2048), 0)
    call = hlo_text.MosaicCall("flash_bsh_causal_fwd.1",
                               "flash_bsh_causal_fwd", (q, q, q), (q,))
    assert fwd.causal_pairs(4096) == 528 * 128 * 128  # 32 * 33 / 2 tiles
    assert fwd.causal_pairs(128) == 128 * 128 and fwd.causal_pairs(64) == 64 * 64
    flops, nbytes = fwd.work(call)
    assert flops == 4.0 * 4 * 528 * 128 * 128 * 2048
    assert flops / square.work(call)[0] == pytest.approx(528 / 1024)
    assert nbytes == 4 * q.nbytes
    assert bwd.work(call)[0] == 2.5 * flops
    moe = manifest.load_module("kernels", "moe_experts")
    assert moe.expected_rows(cell.config, 16384) == 16384  # 2,048 an expert
    assert moe.moe_layers(cell.config) == 4
    flops, nbytes = moe.step_work(cell.config, 16384)
    assert flops == 3 * 3 * 2.0 * 16384 * 2048 * 1792 * 4  # 4.33 TFLOP
    peaks = manifest.load_peaks("TPU v5 lite")
    assert flops / peaks["bf16_flops_per_s"] > nbytes / peaks["hbm_bytes_per_s"]
    assert moe.BOUND == "compute"


def test_packed_batches_are_next_token_pairs_from_the_held_rows(cell):
    config = dict(cell.config, vocab_rows=97)
    traffic = dict(cell.traffic, seq_len=40)
    a = cell.family.make_batch(config, traffic, 3, harness.batch_rng(5, 1, 0))
    b = cell.family.make_batch(config, traffic, 3, harness.batch_rng(5, 1, 0))
    c = cell.family.make_batch(config, traffic, 3,
                               harness.batch_rng(2147483999, 1, 0))
    assert set(a) == {"input_ids", "labels"}
    for name in a:
        assert a[name].shape == (3, 40) and a[name].dtype == np.int32
        np.testing.assert_array_equal(a[name], b[name])
        assert 0 <= a[name].min() and a[name].max() < 97
    np.testing.assert_array_equal(a["labels"][:, :-1], a["input_ids"][:, 1:])
    assert not np.array_equal(a["input_ids"], c["input_ids"])


def test_program_is_the_reference_in_float32():
    """With the AMP rewrite left out, the program the harness builds and
    the family's own copy of the reference are the same arithmetic."""
    from paddle_tpu.contrib import mixed_precision

    small = manifest.load_cell(manifest.load_manifest(), CELL, rehearse=True)
    with mock.patch.object(mixed_precision, "decorate",
                           lambda opt, use_bf16=True: opt):
        check = harness.run_check(small, seed=5)
    assert check["loss_rel_error"] < 1e-5
    assert max(check["grad_rel_l2_error"].values()) < 1e-4
    assert len(check["grad_rel_l2_error"]) == 6 and check["loss_falls"]


def test_the_reference_in_a_lower_precision_is_refused_by_the_limits():
    """The family's reference with its products rounded to an 8-bit float
    lands outside the rehearsal's limits, and with bf16 products inside:
    the check tells the two apart."""
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import Scope

    small = manifest.load_cell(manifest.load_manifest(), CELL, rehearse=True)
    fam, config, traffic = small.family, small.config, small.traffic
    built = harness.build_program(small, 1, dropout=False, seed=9)
    exe, scope = fluid.Executor(), Scope()
    exe.run(built.startup, scope=scope)
    params = {p.name: scope.find_var(p.name)
              for p in built.main.all_parameters()}
    feed = fam.make_batch(config, traffic, 1, harness.batch_rng(9, 2))
    loss, grads, picks = fam.reference_loss_and_grads(
        config, traffic, params, feed, with_picks=True)
    assert len(picks) == 4 and picks[0].shape == (1, 32, 4)
    limits = config["check"]["grad_rel_l2"]

    def worst(products_in):
        _, g = fam.reference_loss_and_grads(config, traffic, params, feed,
                                            products_in=products_in)
        return {label: float(jnp.linalg.norm(g[n] - grads[n])
                             / jnp.linalg.norm(grads[n]))
                for label, n, _ in fam.check_parameters(config)}

    fp8, bf16 = worst(jnp.float8_e4m3fn), worst(jnp.bfloat16)
    assert all(bf16[k] < v for k, v in limits.items() if v is not None), bf16
    assert any(fp8[k] > v for k, v in limits.items() if v is not None), fp8


# ---------------------------------------------------------------------------
# the part scopes
# ---------------------------------------------------------------------------

FWD = "jit(step)/forward/"
BWD = "jit(step)/backward/"


def test_the_part_is_the_first_component_that_names_one():
    assert scopes.part_of(FWD + "jvp(moe_experts)/ragged_dot_general") == (
        "moe_experts")
    assert scopes.part_of(
        BWD + "transpose(jvp(short_conv))/bsh,hk->bsk/dot_general") == (
        "short_conv")
    # a custom_vjp's backward is traced at the forward op
    assert scopes.part_of(
        BWD + "transpose(forward)/jvp(moe_combine)/jit(_take)") == "moe_combine"
    # recomputation: emitted at the grad op, the part stands twice
    assert scopes.part_of(
        BWD + "transpose(jvp(moe_experts))/forward/jvp(moe_experts)/"
        "checkpoint/rematted_computation/mul") == "moe_experts"
    # XLA's own names for the grouped products it makes of ragged_dot
    assert scopes.part_of("ragged-dot-none") == "moe_experts"
    assert scopes.part_of("ragged-dot-metadata") == "moe_experts"
    for none in (FWD + "jvp()/mul", "jit(step)/optimizer/sub", "",
                 FWD + "jvp(moe_expertss)/mul", "donated_vals['w']"):
        assert scopes.part_of(none) is None


def _module():
    ins = roles.Instruction
    return roles.Module("jit_step", {
        1: [ins("fusion.1", "fusion", FWD + "jvp(short_conv)/dot_general", (2,)),
            ins("ragged-dot-none.1", "custom-call", "ragged-dot-none"),
            ins("fusion.2", "fusion", BWD + "transpose(jvp(moe_experts))/mul",
                (3,)),
            ins("fusion.3", "fusion", FWD + "jvp(moe_route)/dot_general", (4,)),
            ins("sort.1", "sort", FWD + "jvp(moe_dispatch)/sort"),
            ins("fusion.4", "fusion", BWD + "transpose(forward)/"
                "jvp(moe_combine)/reduce_sum", (5,)),
            ins("fusion.5", "fusion", FWD + "jvp(rms_norm)/mul", (6,)),
            ins("flash.1", "custom-call",
                FWD + "jvp()/flash_bsh_causal_fwd/pallas_call"),
            ins("fusion.6", "fusion", "jit(step)/optimizer/sub", (7,))],
        2: [ins("dot.1", "dot", FWD + "jvp(short_conv)/dot_general"),
            ins("add.1", "add", FWD + "jvp()/add")],  # the residual: no part
        3: [ins("mul.1", "multiply", BWD + "transpose(jvp(moe_experts))/mul"),
            ins("ragged-dot-none.2", "custom-call", "ragged-dot-none")],
        4: [ins("dot.2", "dot", FWD + "jvp(moe_route)/dot_general")],
        5: [ins("reduce.1", "reduce", BWD + "transpose(forward)/"
                "jvp(moe_combine)/reduce_sum")],
        # the norm's multiply fused into the conv's projection: two parts
        6: [ins("mul.2", "multiply", FWD + "jvp(rms_norm)/mul"),
            ins("dot.3", "dot", FWD + "jvp(short_conv)/dot_general")],
        7: [ins("sub.1", "subtract", "jit(step)/optimizer/sub")],
    })


def _planes():
    names = ["fusion.1", "ragged-dot-none.1", "fusion.2", "fusion.3", "sort.1",
             "fusion.4", "fusion.5", "flash.1", "fusion.6"]
    ends = [20, 50, 70, 74, 80, 90, 96, 116, 120]
    events, start = [], 0
    for name, end in zip(names, ends):
        events.append(Event(name, start * MS, end * MS))
        start = end
    device = Plane("/device:TPU:0", [
        Line(tr.OPS_LINE, events),
        Line(roles.MODULES_LINE, [Event("jit_step(7)", 0, 120 * MS)])])
    host = Plane(tr.HOST_PLANE, [Line("python3", [
        Event(tr.WINDOW_SPAN, 0, 125 * MS)])])
    return [device, host]


@pytest.fixture()
def trace_dir(tmp_path):
    path = tmp_path / "plugins" / "profile" / "2026_01_01" / "hand.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_xplane(_planes(), {"jit_step(7)": _module()}))
    return str(tmp_path)


def test_the_split_by_part_over_a_hand_made_trace(trace_dir):
    found = scopes.split_of_trace(tr.find_xplane(trace_dir), 2)
    carried = found.carried
    assert carried["fusion.1"] == {"short_conv"}  # the residual carries none
    assert carried["fusion.2"] == carried["ragged-dot-none.1"] == {
        "moe_experts"}
    assert carried["fusion.5"] == {"rms_norm", "short_conv"}
    assert carried["flash.1"] == carried["fusion.6"] == frozenset()
    assert found.ms_per_step(("short_conv",)) == pytest.approx(20 / 2)
    assert found.ms_per_step(("moe_experts",)) == pytest.approx(50 / 2)
    assert found.ms_per_step(("moe_route", "moe_dispatch", "moe_combine")) == (
        pytest.approx((4 + 6 + 10) / 2))
    # a fusion that mixes parts is counted under none of them
    assert found.ms_per_step(("rms_norm",)) == 0.0
    listing = scopes.describe(found)
    assert "fusion.5  rms_norm+short_conv" in listing
    assert "moe_experts 25.000" in listing and "mixed 3.000" in listing


def _run_facts(cell, trace_dir, kernel_events):
    """RunFacts as the harness fills it, over the hand-made trace."""
    q = hlo_text.Shape("bf16", (4, 4096, 2048), 0)
    calls = {name: hlo_text.MosaicCall(name, kernel, (q, q, q), (q,))
             for name, kernel, _ in kernel_events}
    device = tr.DeviceReduction(
        ordinal=0, window_ns=125 * MS, busy_ns=120 * MS, self_ns_by_name={},
        calls_by_name={},
        kernel_ns={k: ns for _, k, ns in kernel_events},
        kernel_calls={k: [(name, ns)] for name, k, ns in kernel_events},
        collective_ns=0.0, collective_exposed_ns=0.0, gaps=[])
    return harness.RunFacts(
        cell, manifest.load_peaks("TPU v5 lite"), [], {}, {},
        tr.TraceReduction([device], 0.125, 2, {}), calls, 0)


def test_the_six_readers_over_the_trace_and_without_one(cell, trace_dir,
                                                        monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    readers = {n: manifest.load_module("layer_metrics", n)
               for n in NEW_READERS}
    run = _run_facts(cell, trace_dir, [
        ("flash.1", "flash_bsh_causal_fwd", 8 * MS),
        ("flash.2", "flash_bsh_causal_bwd", 20 * MS)])
    got = {n: r.read(run) for n, r in readers.items()}
    assert got["moe_experts_ms_per_step"] == pytest.approx(25.0)
    assert got["moe_route_dispatch_ms_per_step"] == pytest.approx(10.0)
    assert got["short_conv_ms_per_step"] == pytest.approx(10.0)
    assert got["causal_flash_ms_per_step"] == pytest.approx(14.0)
    # 4.33 TFLOP of expert products at 197 TFLOP/s: 21.98 ms of 25
    assert got["moe_experts_roofline"] == pytest.approx(
        100 * 4.3293e12 / 197e12 / 25e-3, rel=1e-3)
    # 14 B H pairs over both kernels against the 28 ms they took
    least = 14.0 * 4 * 528 * 128 * 128 * 2048 / 197e12
    assert got["causal_flash_roofline"] == pytest.approx(
        100 * least / 28e-3, rel=1e-6)
    assert all(0 < v < 100 for v in got.values())
    # an untraced run, and a step without the kernels or the scopes (the
    # parent of the PR that brought them): nothing to read, nothing raised
    untraced = harness.RunFacts(cell, run.peaks, [], {}, {}, None, {}, 0)
    assert [r.read(untraced) for r in readers.values()] == [None] * 6
    bare = roles.Module("jit_step", {1: [roles.Instruction(
        "fusion.1", "fusion", FWD + "jvp()/dot_general")]})
    path = tr.find_xplane(trace_dir)
    with open(path, "wb") as f:
        f.write(_xplane(_planes(), {"jit_step(7)": bare}))
    scopes.split_of_trace.cache_clear()
    roles.split_of_trace.cache_clear()
    parent = _run_facts(cell, trace_dir, [])
    assert [r.read(parent) for r in readers.values()] == [None] * 6


def test_a_traced_rehearsal_finds_the_readers_and_reports_no_device_number(
        tmp_path):
    """`--rehearse --trace 1` from a copy of the benchmark (its own trace
    directory): `correct`, the new readers found and silent on the CPU,
    and the trace's module carries the part scopes."""
    root = str(tmp_path)
    shutil.copytree(manifest.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seed", "2147483999",
         "--seconds", "1", "--trace", "1"],
        cwd=root, text=True, capture_output=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=manifest.ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert not set(NEW_READERS) & set(result["metrics"])
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    check = json.loads(lines[-2])["details"]["check"]
    assert check["ok"] and check["loss_falls"]
    path = tr.find_xplane(os.path.join(root, ".bench_trace"))
    parts = set()
    for module in roles.modules_in(path).values():
        for carried in scopes.carried_parts(module).values():
            parts |= carried
    assert parts == set(scopes.PARTS)
