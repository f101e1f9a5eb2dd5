"""The `nemotron-3-nano-30b-a3b` configuration and its cell on the CPU: the
manifest's entries, found by name and never by position, against the
catalog row's `config` copied in as a literal; the parameter count of the
program that is built, by part as the issue's table has them; the family
file's arithmetic and the two count files against hand values; the five
new readers over a hand-made trace; the program against the family's
reference with the AMP rewrite left out, and the reference under each
fault against the limits; a traced rehearsal."""
import json
import os
import shutil
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

from benchmark import harness, manifest, part_scopes, roles
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event, Line, Plane
from test_bench_roles import _xplane  # the trace file's wire format, by hand

CELL = "nemotron-3-nano-30b-a3b.ep16share.s4096"
CONFIG = "nemotron-3-nano-30b-a3b"
# `config` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 in the model-configs
# catalog, which is the released config.json without the keys that say
# nothing about the shape
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = ["num_hidden_layers", "hybrid_override_pattern"]
NEW_READERS = {
    "mamba_ms_per_step": "mixer", "ssd_scan_ms_per_step": "kernels",
    "ssd_scan_roofline": "kernels", "relu2_experts_ms_per_step": "experts",
    "relu2_experts_roofline": "experts",
    "expert_layer_rest_ms_per_step": "experts",
    "moe_full_width_ms_per_step": "experts",
    "flash_bhsd_ms_per_step": "kernels"}
LABELS = {"embedding", "mamba.A_log", "mamba.dt_bias", "mamba.conv1d",
          "mamba.in_proj", "mamba.norm", "first_moe.w1",
          "first_moe.shared_w1", "first_moe.gate", "attention.k_proj"}
MS = 1e6  # ns
C = 2688
MAMBA_LAYER = 38_744_896
ATTENTION_LAYER = 23_399_040
EXPERT_LAYER = 100_125_440
VOCABULARY = 88_083_072


@pytest.fixture(scope="module")
def doc():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def cell(doc):
    return manifest.load_cell(doc, CELL)


def _named(rows, name):
    (row,) = [r for r in rows if r["name"] == name]
    return row


def test_the_manifest_has_the_cell_by_name_and_no_problems(doc, cell):
    assert manifest.problems(doc) == []
    row = _named(doc["workloads"], CELL)
    assert (row["config"], row["traffic"], row["chips"]) == (
        CONFIG, "pretrain-s4096-packed-ep16", 1)
    assert len(row["why"]) <= 200
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    tokens = _named(doc["end_to_end"], "tokens_per_s_per_chip")
    assert CELL in tokens["workloads"]
    # the cell reports throughput, and none of another cell's readers that
    # names its own workloads
    assert {"tokens_per_s_per_chip", "step_ms", "peak_hbm_gb", "setup_s"} == {
        m["name"] for m in cell.end_to_end}
    listed = {m["name"] for m in cell.per_layer if "workloads" in m}
    assert listed == set(NEW_READERS)
    assert "mfu" in {m["name"] for m in cell.per_layer}
    # every other cell is as it was: none of them lists a new reader
    for other in doc["workloads"]:
        if other["name"] != CELL:
            names = {m["name"] for m in manifest.metrics_of(
                doc, "per_layer", other["name"])}
            assert not names & set(NEW_READERS), other["name"]
    traffic = cell.traffic
    assert (traffic["seq_len"], traffic["log_every"], traffic["pool"],
            traffic["check_batch"], traffic["mesh"]) == (4096, 5, 8, 1, None)
    assert traffic["batch"] in (1, 2)  # the one the compile allowed


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_new_reader_is_declared_as_its_file_says(doc, name):
    reader = manifest.load_module("layer_metrics", name)
    row = _named(doc["per_layer"], name)
    assert row["workloads"] == [CELL]
    assert (reader.LAYER, reader.MOVES, reader.SOURCE) == (
        NEW_READERS[name], "tokens_per_s_per_chip", "device_trace") == (
        row["layer"], row["moves"], row["source"])
    assert reader.UNIT == row["unit"] == (
        "%" if name.endswith("_roofline") else "ms")
    assert row["better"] == ("higher" if reader.UNIT == "%" else "lower")


def test_every_published_number_stands_unless_reduced(doc, cell):
    entry = _named(doc["configs"], CONFIG)
    config = cell.config
    assert entry["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json"
    assert entry["reduced"] == config["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        assert config["published"].get(key, value) == value, key
    assert set(config["published"]) == set(REDUCED)
    # the cut: published layers 0-8, the pattern's first nine letters
    assert config["num_hidden_layers"] == 9
    assert config["hybrid_override_pattern"] == "MEMEM*EME" == (
        PUBLISHED["hybrid_override_pattern"][:9])
    # the chip's share: a sixteenth of the routed experts, an eighth of the
    # vocabulary, the mixers whole; no width differs
    assert (config["experts_held"], config["first_expert"]) == (8, 0)
    assert config["experts_held"] * 16 == config["n_routed_experts"]
    assert config["vocab_rows"] * 8 == config["vocab_size"]
    assert "sixteen chips share each expert layer" in config["deployment"]
    assert "replicated" in config["deployment"]
    assert set(config["changed"]) == set(REDUCED) | {
        "experts_held", "vocab_rows"}
    assert set(config["assumed"]) >= {
        "no_position_term", "dt_not_clamped", "mamba_init", "gate_before_norm",
        "rescale_prenorm_residual", "initializer_range", "gate_denominator",
        "optimizer", "positions"}
    assert config["program"]["amp"] == "bf16"
    assert config["program"]["use_flash_attention"] is True
    assert set(config["program"]) == {"amp", "use_flash_attention",
                                      "remat_ffn"}
    assert config["mosaic_calls"] == ["flash_fwd", "flash_bwd"]
    assert config["stated"]["parameters"] == 666_963_456
    assert 4.0 < config["stated"]["peak_hbm_gb"] <= 15.2
    labels = [label for label, _, _ in cell.family.check_parameters(config)]
    assert set(config["check"]["grad_rel_l2"]) == set(labels) == LABELS
    assert len(config["check"]["why"]) > 500
    built = cell.family.model_config(config)
    assert (built.hidden_size, built.mamba_num_heads, built.mamba_head_dim,
            built.n_groups, built.ssm_state_size, built.chunk_size,
            built.n_routed_experts, built.experts_held, built.vocab_rows,
            built.residual_scale_layers, built.remat_ffn) == (
        2688, 64, 64, 8, 128, 128, 128, 8, 16384, 52, True)
    with pytest.raises(ValueError, match="not built"):
        cell.family.model_config(dict(config, n_group=8))
    with pytest.raises(ValueError, match="not built"):
        cell.family.model_config(dict(config, mlp_hidden_act="silu"))


def test_the_built_program_has_the_parameters_of_the_issues_table(cell):
    """Shapes only: the program at the published widths is built and
    nothing of its size is allocated."""
    built = harness.build_program(cell, 1, dropout=False, seed=1)
    sizes = {p.name: int(np.prod(p.shape))
             for p in built.main.all_parameters()}

    def total(prefix):
        return sum(n for name, n in sizes.items() if name.startswith(prefix))

    assert sizes["layers.0.mixer.in_proj"] == C * (4096 + 6144 + 64) == (
        27_697_152)
    assert (sizes["layers.0.mixer.conv1d.weight"]
            + sizes["layers.0.mixer.conv1d.bias"]) == 6144 * 4 + 6144
    assert sizes["layers.0.mixer.out_proj"] == 4096 * C == 11_010_048
    assert total("layers.0.") == MAMBA_LAYER
    assert total("layers.5.") == ATTENTION_LAYER
    assert sizes["layers.5.mixer.k_proj.weight"] == C * 256
    assert total("layers.1.mixer.shared_experts.") == 2 * C * 3712
    assert sizes["layers.1.mixer.w1"] == 8 * 9_977_856 // 2
    assert (sizes["layers.1.mixer.gate"]
            + sizes["layers.1.mixer.expert_bias"]) == C * 128 + 128
    assert not any(name.endswith(".w3") for name in sizes)
    assert total("layers.1.") == EXPERT_LAYER
    for i, kind in enumerate("MEMEM*EME"):
        assert total(f"layers.{i}.") == {
            "M": MAMBA_LAYER, "E": EXPERT_LAYER, "*": ATTENTION_LAYER}[kind]
    assert (sizes["embeddings.weight"] + sizes["lm_head.weight"]
            + sizes["norm_f.weight"]) == VOCABULARY
    assert sum(sizes.values()) == (
        4 * MAMBA_LAYER + ATTENTION_LAYER + 4 * EXPERT_LAYER + VOCABULARY
    ) == 666_963_456 == cell.config["stated"]["parameters"]


def test_model_flops_are_of_what_the_chip_computes(cell):
    config, traffic = cell.config, cell.traffic
    parts = cell.family.forward_flops_per_token(config, 4096)
    assert parts["mamba_projections"] == 4 * 2 * C * (10304 + 4096)  # 77.4 M
    # chunk 128: C B^T, the masked matrix times x, the state left behind
    # and the entering state read out
    assert parts["ssd_scan"] == 4 * 2 * (
        128 * 8 * 128 + 128 * 64 * 64 + 2 * 64 * 64 * 128)
    assert parts["ssd_scan"] / 4 == pytest.approx(3.4e6, rel=0.01)
    assert parts["attention_projections"] == 2 * C * 128 * (2 * 32 + 2 * 2)
    # the causal triangle of 32 heads: (S + 1) / 2 keys a query
    assert parts["attention_scores"] == 2 * 32 * 256 * 4097 / 2
    # a sixteenth of the six picks falls on the eight experts held
    assert parts["routed_experts"] == 4 * 0.375 * 4 * C * 1856
    assert parts["shared_expert"] == 4 * 4 * C * 3712
    assert parts["router"] == 4 * 2 * C * 128
    assert parts["head"] == 2 * C * 16384  # 88.1 M
    total = sum(parts.values())
    assert total == pytest.approx(684e6, rel=2e-3)
    mamba = parts["mamba_projections"] + parts["ssd_scan"]
    assert mamba / total == pytest.approx(0.47, abs=0.005)
    batch = traffic["batch"]
    assert cell.family.step_flops(config, traffic, batch) == pytest.approx(
        3 * total * batch * 4096)
    assert cell.family.units_per_step(traffic) == batch * 4096


def test_the_two_count_files_against_hand_values(cell):
    config = cell.config
    scan = manifest.load_module("kernels", "ssd_scan")
    assert scan.mamba_layers(config) == 4
    assert scan.flops_per_token(config) == 2 * (131_072 + 524_288 + 1_048_576)
    # x and y 64 x 64 in bf16, B and C 8 x 128 in bf16, dt 64 in float32
    assert scan.bytes_per_token(config) == 2 * (2 * 4096 + 2 * 1024) + 256
    flops, nbytes = scan.step_work(config, 8192)
    assert flops == 3 * 4 * 8192 * 3_407_872
    assert nbytes == 3 * 4 * 8192 * 20_736
    # bandwidth-bound on the v5e: 2.49 ms against 1.70
    assert nbytes / 819e9 > flops / 197e12
    experts = manifest.load_module("kernels", "relu2_experts")
    assert experts.expected_rows(config, 8192) == 3072  # 384 a held expert
    assert experts.moe_layers(config) == 4
    flops, nbytes = experts.step_work(config, 8192)
    assert flops == 3 * 2 * 4 * 2.0 * 3072 * C * 1856
    assert nbytes == 3 * 2 * 4 * 2 * (8 * C * 1856 + 3072 * (C + 1856))
    assert (experts.PASSES, experts.PRODUCTS) == (3, 2)


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
    the family's own copy of the reference are the same arithmetic: the
    chunked scan and the recurrence computed position by position."""
    from paddle_tpu.contrib import mixed_precision

    small = manifest.load_cell(manifest.load_manifest(), CELL, rehearse=True)
    # two Mamba heads a group, four chunks a row
    assert small.config["mamba_num_heads"] == 2 * small.config["n_groups"]
    assert small.traffic["seq_len"] == 4 * small.config["chunk_size"]
    with mock.patch.object(mixed_precision, "decorate",
                           lambda opt, use_bf16=True: opt):
        check = harness.run_check(small, seed=5)
    assert check["loss_rel_error"] < 1e-5
    assert max(check["grad_rel_l2_error"].values()) < 2e-4
    assert set(check["grad_rel_l2_error"]) == LABELS and check["loss_falls"]


@pytest.mark.parametrize("seed", [5, 2147483999])
def test_faults_and_a_lower_precision_are_refused_by_the_limits(seed):
    """The family's reference with its products rounded to an 8-bit float
    and under each of its faults (the state dropped where chunks meet,
    group 0's B and C for every head, D x left out, softplus left out,
    relu for relu^2, no shared expert, the gated norm over all of d_in)
    lands outside at least one of the rehearsal's limits (a reading that
    is no number is outside, as the harness has it: without softplus dt
    goes negative and the decays overflow); with bf16 products inside
    all."""
    import math

    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import Scope

    small = manifest.load_cell(manifest.load_manifest(), CELL, rehearse=True)
    fam, config, traffic = small.family, small.config, small.traffic
    assert len(fam.FAULTS) == 7
    built = harness.build_program(small, 1, dropout=False, seed=seed)
    exe, scope = fluid.Executor(), Scope()
    exe.run(built.startup, scope=scope)
    params = {p.name: scope.find_var(p.name)
              for p in built.main.all_parameters()}
    feed = fam.make_batch(config, traffic, 1, harness.batch_rng(seed, 2))
    loss, grads = fam.reference_loss_and_grads(config, traffic, params, feed)
    limits = {k: v for k, v in config["check"]["grad_rel_l2"].items()
              if v is not None}

    def errors(**how):
        other, g = fam.reference_loss_and_grads(config, traffic, params, feed,
                                                **how)
        out = {"loss": abs(float(other) - float(loss)) / float(loss)}
        for label, name, index in fam.check_parameters(config):
            a, b = g[name], grads[name]
            out[label] = float(jnp.linalg.norm((a - b).ravel())
                               / jnp.linalg.norm(b.ravel()))
        return out

    def refused(found):
        return (not all(math.isfinite(v) for v in found.values())
                or found["loss"] > config["check"]["loss_rel"]
                or any(found[k] > v for k, v in limits.items()))

    assert not refused(errors(products_in=jnp.bfloat16))
    assert refused(errors(products_in=jnp.float8_e4m3fn))
    for fault in fam.FAULTS:
        assert refused(errors(faults=(fault,))), fault
    with pytest.raises(ValueError, match="unknown faults"):
        fam.reference_loss(config, params, feed["input_ids"], feed["labels"],
                           None, faults=("no_such",))


# ---------------------------------------------------------------------------
# the new readers over a hand-made trace
# ---------------------------------------------------------------------------

FWD = "jit(step)/forward/"
BWD = "jit(step)/backward/"
MIXER = tuple(p for p in part_scopes.PARTS if p != "rms_norm") + ("mamba2",)
SCAN = part_scopes.PARTS + ("ssd_scan",)


def test_the_scan_lies_inside_its_mixer_and_the_names_given_decide():
    part_of = part_scopes.part_of
    proj = FWD + "jvp(mamba2)/bsh,hk->bsk/dot_general"
    scan = (BWD + "transpose(jvp(mamba2))/ssd_scan/checkpoint/"
            "rematted_computation/bzgrij,bzjgrp->bzigrp/dot_general")
    assert part_of(proj, MIXER) == part_of(scan, MIXER) == "mamba2"
    assert part_of(proj, SCAN) is None and part_of(scan, SCAN) == "ssd_scan"
    # among the accepted names alone neither is anybody's
    assert part_of(proj) is None and part_of(scan) is None
    assert part_of("ragged-dot-none", SCAN) == "moe_experts"


FALLBACK = FWD + "cond/branch_1_fun/moe_full_width/moe_dispatch/gather"


def _module():
    ins = roles.Instruction
    scan = "jvp(mamba2)/ssd_scan/checkpoint/dot_general"
    return roles.Module("jit_step", {
        1: [ins("fusion.1", "fusion", FWD + "jvp(mamba2)/dot_general", (2,)),
            ins("fusion.2", "fusion", FWD + scan, (3,)),
            ins("fusion.3", "fusion", BWD + "transpose(jvp(mamba2))/ssd_scan/"
                "checkpoint/rematted_computation/exp", (4,)),
            ins("ragged-dot-none.1", "custom-call", "ragged-dot-none"),
            ins("fusion.4", "fusion", FWD + "jvp(shared_expert)/dot_general",
                (5,)),
            ins("fusion.5", "fusion", FWD + "jvp(rms_norm)/mul", (6,)),
            ins("fusion.6", "fusion", FWD + scan, (7,)),
            ins("fusion.7", "fusion", "jit(step)/optimizer/sub", (8,)),
            ins("fusion.8", "fusion", FALLBACK, (9,)),
            ins("fusion.9", "fusion", FWD + "moe_dispatch/sort", (10,)),
            ins("conditional.1", "conditional", FWD + "cond", (11, 12))],
        2: [ins("dot.1", "dot", FWD + "jvp(mamba2)/dot_general")],
        3: [ins("dot.2", "dot", FWD + scan)],
        4: [ins("exp.1", "exponential", BWD + "transpose(jvp(mamba2))/"
                "ssd_scan/checkpoint/rematted_computation/exp")],
        5: [ins("dot.3", "dot", FWD + "jvp(shared_expert)/dot_general")],
        # the block norm fused into the mixer's first projection
        6: [ins("mul.2", "multiply", FWD + "jvp(rms_norm)/mul"),
            ins("dot.4", "dot", FWD + "jvp(mamba2)/dot_general")],
        # softplus of the mixer fused into the scan's first product: the
        # scan's among the names that leave `mamba2` out
        7: [ins("log.1", "log-plus-one", FWD + "jvp(mamba2)/log1p"),
            ins("dot.5", "dot", FWD + scan)],
        8: [ins("sub.1", "subtract", "jit(step)/optimizer/sub")],
        # what both branches compute alike, moved out of the conditional
        # under the fallback's name: it runs whichever branch does
        9: [ins("convert.1", "convert", FALLBACK)],
        10: [ins("sort.1", "sort", FWD + "moe_dispatch/sort")],
        # the dropless fallback and the bounded block: both branches are in
        # the module, the one that ran is in the trace
        11: [ins("gather.11", "gather", FALLBACK),
             ins("ragged-dot-none.11", "custom-call", "ragged-dot-none")],
        12: [ins("gather.12", "gather",
                 FWD + "cond/branch_1_fun/moe_dispatch/gather"),
             ins("ragged-dot-none.12", "custom-call", "ragged-dot-none")],
    })


def _planes(fell_back=True):
    """Ten instructions back to back; inside the last four milliseconds a
    conditional and, nested in it, the branch that ran."""
    names = ["fusion.1", "fusion.2", "fusion.3", "ragged-dot-none.1",
             "fusion.4", "fusion.5", "fusion.6", "fusion.7", "fusion.8",
             "fusion.9"]
    ends = [20, 26, 40, 60, 70, 76, 80, 90, 96, 100]
    events, start = [], 0
    for name, end in zip(names, ends):
        events.append(Event(name, start * MS, end * MS))
        start = end
    branch = "11" if fell_back else "12"
    events[-1] = Event("fusion.9", 96 * MS, 97 * MS)
    events += [Event("conditional.1", 97 * MS, 100 * MS),
               Event("gather." + branch, 97 * MS, 98 * MS),
               Event("ragged-dot-none." + branch, 98 * MS, 100 * MS)]
    device = Plane("/device:TPU:0", [
        Line(tr.OPS_LINE, events),
        Line(roles.MODULES_LINE, [Event("jit_step(7)", 0, 100 * MS)])])
    host = Plane(tr.HOST_PLANE, [Line("python3", [
        Event(tr.WINDOW_SPAN, 0, 104 * MS)])])
    return [device, host]


@pytest.fixture()
def trace_dir(tmp_path):
    path = tmp_path / "plugins" / "profile" / "2026_01_01" / "hand.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_xplane(_planes(), {"jit_step(7)": _module()}))
    return str(tmp_path)


def _run_facts(cell, kernel_ns=None):
    """RunFacts as the harness fills it, over the hand-made trace."""
    device = tr.DeviceReduction(
        ordinal=0, window_ns=104 * MS, busy_ns=100 * MS, self_ns_by_name={},
        calls_by_name={}, kernel_ns=kernel_ns or {}, kernel_calls={},
        collective_ns=0.0, collective_exposed_ns=0.0, gaps=[])
    return harness.RunFacts(
        cell, manifest.load_peaks("TPU v5 lite"), [], {}, {},
        tr.TraceReduction([device], 0.104, 2, {}), {}, 0)


def _clear():
    part_scopes.split_of_trace.cache_clear()
    roles.split_of_trace.cache_clear()


def test_the_new_readers_over_the_trace_and_without_one(cell, trace_dir,
                                                        monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    _clear()
    found = part_scopes.split_of_trace(tr.find_xplane(trace_dir), 2, MIXER)
    # the block norm fused into the mixer's projection is the mixer's
    assert found.carried["fusion.5"] == found.carried["fusion.6"] == {
        "mamba2"}
    assert manifest.load_module(
        "layer_metrics", "mamba_ms_per_step").AMONG == MIXER
    readers = {n: manifest.load_module("layer_metrics", n)
               for n in NEW_READERS}
    run = _run_facts(cell, {"flash_fwd": 3 * MS, "flash_bwd": 5 * MS,
                            "flash_bsh_causal_fwd": 7 * MS})
    got = {n: r.read(run) for n, r in readers.items()}
    # the projection, the scan forward and backward, the fusion that holds
    # both and the one shared with the block norm
    assert got["mamba_ms_per_step"] == pytest.approx(
        (20 + 6 + 14 + 6 + 4) / 2)
    assert got["ssd_scan_ms_per_step"] == pytest.approx((6 + 14 + 4) / 2)
    assert got["relu2_experts_ms_per_step"] == pytest.approx((20 + 2) / 2)
    # the shared expert, what was moved out of the conditional, the sort
    # and the branch's gather
    assert got["expert_layer_rest_ms_per_step"] == pytest.approx(
        (10 + 6 + 1 + 1) / 2)
    # the fallback's branch, its ragged-dot included, and not what carries
    # its name outside the conditional
    assert got["moe_full_width_ms_per_step"] == pytest.approx((1 + 2) / 2)
    assert got["flash_bhsd_ms_per_step"] == pytest.approx((3 + 5) / 2)
    tokens = cell.traffic["batch"] * 4096
    flops, nbytes = manifest.load_module("kernels", "ssd_scan").step_work(
        cell.config, tokens)
    assert got["ssd_scan_roofline"] == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 12e-3, rel=1e-6)
    flops, nbytes = manifest.load_module(
        "kernels", "relu2_experts").step_work(cell.config, tokens)
    assert got["relu2_experts_roofline"] == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 11e-3, rel=1e-6)
    assert all(0 < v < 100 for v in got.values()), got
    # an untraced run, and a step without the scopes (the parent of this
    # PR, over which the new files are laid): nothing to read, nothing
    # raised
    with open(tr.find_xplane(trace_dir), "wb") as f:
        f.write(_xplane(_planes(fell_back=False), {"jit_step(7)": _module()}))
    _clear()
    # no layer fell back in the traced steps: the scope is in the step and
    # took no time, which is a reading and not a silence
    assert readers["moe_full_width_ms_per_step"].read(run) == 0.0
    assert readers["expert_layer_rest_ms_per_step"].read(run) == (
        pytest.approx((10 + 6 + 1 + 1) / 2))
    untraced = harness.RunFacts(cell, run.peaks, [], {}, {}, None, {}, 0)
    assert [r.read(untraced) for r in readers.values()] == [None] * 8
    bare = roles.Module("jit_step", {1: [
        roles.Instruction("fusion.1", "fusion",
                          FWD + "jvp(rms_norm)/dot_general"),
        roles.Instruction("fusion.2", "fusion", FWD + "jvp()/mul")]})
    with open(tr.find_xplane(trace_dir), "wb") as f:
        f.write(_xplane(_planes(), {"jit_step(7)": bare}))
    _clear()
    assert [r.read(_run_facts(cell)) for r in readers.values()] == [None] * 8
    _clear()


def test_a_traced_rehearsal_finds_the_readers_and_reports_no_device_number(
        tmp_path):
    """`--rehearse --trace 1` from a copy of the benchmark (its own trace
    directory): `correct`, the new readers found and silent on the CPU,
    and the trace's module carries the new part scopes."""
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
        for names in (MIXER, SCAN):
            for carried in part_scopes.carried_parts(module, names).values():
                parts |= carried
    assert parts == {"mamba2", "ssd_scan", "shared_expert", "rms_norm",
                     "moe_route", "moe_dispatch", "moe_experts",
                     "moe_combine"}
