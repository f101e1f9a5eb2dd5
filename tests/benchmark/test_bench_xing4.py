"""The `xing4.0-29b-a4b` configuration and its cell on the CPU: the
manifest's entries, found by name, against the catalog row's `config`; the
parameter count of the program that is built; the family file's arithmetic
and the three count files against hand values; the part split with its
names as an argument; the eight new readers over a hand-made trace; the
program against the family's reference with the AMP rewrite left out, and
the reference under each fault against the limits; a traced rehearsal."""
import json
import os
import shutil
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

from benchmark import harness, hlo_text, manifest, part_scopes, roles, scopes
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event, Line, Plane
from test_bench_roles import _xplane  # the trace file's wire format, by hand

CELL = "xing4.0-29b-a4b.tp8ep8share.s4096"
CONFIG = "xing4.0-29b-a4b"
# `config` of Xing4.0-29B-A4B in the model-configs catalog, which is the
# released config.json without the keys that say nothing about the shape
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}
REDUCED = ["num_hidden_layers", "first_k_dense_replace",
           "num_nextn_predict_layers"]
NEW_READERS = {
    "mhc_ms_per_step": "residual", "mhc_roofline": "residual",
    "mla_ms_per_step": "attention", "mla_flash_ms_per_step": "kernels",
    "mla_flash_roofline": "kernels", "routed_experts_ms_per_step": "experts",
    "routed_experts_roofline": "experts",
    "shared_expert_ms_per_step": "experts"}
MS = 1e6  # ns
C = 3584


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
        CONFIG, "pretrain-s4096-packed-share8", 1)
    assert len(row["why"]) <= 200
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    tokens = _named(doc["end_to_end"], "tokens_per_s_per_chip")
    assert CELL in tokens["workloads"]
    for name, layer in NEW_READERS.items():
        reader = manifest.load_module("layer_metrics", name)
        assert _named(doc["per_layer"], name)["workloads"] == [CELL]
        assert (reader.LAYER, reader.MOVES, reader.SOURCE) == (
            layer, "tokens_per_s_per_chip", "device_trace")
        assert reader.UNIT == ("%" if name.endswith("_roofline") else "ms")
    # the cell reports throughput, and none of another cell's readers that
    # names its own workloads
    assert {"tokens_per_s_per_chip", "step_ms", "peak_hbm_gb", "setup_s"} == {
        m["name"] for m in cell.end_to_end}
    listed = {m["name"] for m in cell.per_layer if "workloads" in m}
    assert listed == set(NEW_READERS)
    assert "mfu" in {m["name"] for m in cell.per_layer}
    traffic = cell.traffic
    assert (traffic["seq_len"], traffic["log_every"], traffic["pool"],
            traffic["check_batch"], traffic["mesh"]) == (4096, 5, 8, 1, None)
    assert traffic["batch"] in (1, 2)  # the one the compile allowed


def test_every_published_number_stands_unless_reduced(doc, cell):
    entry = _named(doc["configs"], CONFIG)
    config = cell.config
    assert entry["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")
    assert entry["file"] == "benchmark/configs/xing4.0-29b-a4b.json"
    assert entry["reduced"] == config["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        assert config["published"].get(key, value) == value, key
    assert set(config["published"]) == set(REDUCED)
    # the cut: the leading dense layers once, four expert layers, no MTP
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_nextn_predict_layers"]) == (5, 1, 0)
    # the chip's share, an eighth of the heads, the experts and the
    # vocabulary, at the guide's floors (8 experts, an eighth of the rows)
    assert (config["heads_held"], config["first_head"]) == (4, 0)
    assert (config["experts_held"], config["first_expert"]) == (8, 0)
    assert config["vocab_rows"] * 8 == config["vocab_size"]
    assert "eight chips share each layer" in config["deployment"]
    assert "see the same tokens" in config["deployment"]
    assert set(config["changed"]) == set(REDUCED) | {
        "heads_held", "experts_held", "vocab_rows"}
    assert set(config["assumed"]) >= {
        "stream_norm", "sinkhorn", "streams_start_and_readout",
        "hyper_connection_init", "rope_pairing", "gate_denominator",
        "initializer_range", "optimizer", "positions"}
    assert config["program"] == {"amp": "bf16", "use_flash_attention": True,
                                 "remat_ffn": True}
    assert config["mosaic_calls"] == [
        "flash_mla_causal_fwd", "flash_mla_causal_bwd", "moe_gmm_nn",
        "moe_gmm_nt", "moe_gmm_tn"]
    labels = [label for label, _, _ in cell.family.check_parameters(config)]
    assert set(config["check"]["grad_rel_l2"]) == set(labels)
    assert len(config["check"]["why"]) > 500
    built = cell.family.model_config(config)
    assert (built.hidden_size, built.heads_held, built.n_routed_experts,
            built.experts_held, built.vocab_rows, built.remat_ffn,
            built.hc_mult, built.hc_sinkhorn_iters) == (
        3584, 4, 64, 8, 16384, True, 4, 20)
    with pytest.raises(ValueError, match="not built"):
        cell.family.model_config(dict(config, n_group=8))


def test_the_built_program_has_the_parameters_of_the_issues_table(cell):
    """Shapes only: the program at the published widths is built and
    nothing of its size is allocated."""
    built = harness.build_program(cell, 1, dropout=False, seed=1)
    sizes = {p.name: int(np.prod(p.shape))
             for p in built.main.all_parameters()}

    def total(prefix):
        return sum(n for name, n in sizes.items() if name.startswith(prefix))

    mla = (C * 768 + 768 * 4 * 192 + C * 576 + 512 * 4 * 256 + 4 * 128 * C
           + 768 + 512)
    assert total("layers.0.self_attn.") == mla == 7_767_296
    hyper = 2 * (4 * C * 24 + 24 + 3)
    assert total("layers.0.attn_hc.") + total("layers.0.ffn_hc.") == (
        hyper) == 688_182
    assert total("layers.1.mlp.shared_experts.") == 3 * C * 1024
    assert sizes["layers.1.mlp.gate"] + sizes["layers.1.mlp.expert_bias"] == (
        229_440)
    assert total("layers.1.") == 107_782_518   # a layer with experts
    assert total("layers.0.") == 107_553_078   # the leading dense layer
    assert sizes["embed_tokens.weight"] + sizes["lm_head.weight"] == (
        117_440_512)
    # 1 dense + 4 expert layers + vocabulary, and the final norm
    assert sum(sizes.values()) == (107_553_078 + 4 * 107_782_518
                                   + 117_440_512 + C) == 656_127_246


def test_model_flops_are_of_what_the_chip_computes(cell):
    config, traffic = cell.config, cell.traffic
    parts = cell.family.forward_flops_per_token(config, 4096)
    assert parts["dense_mlp"] == 6 * C * 9216  # 198 M
    # an eighth of the four picks falls on the eight experts held
    assert parts["routed_experts"] == 4 * 0.5 * 6 * C * 1024  # 44 M
    assert parts["shared_expert"] == 4 * 6 * C * 1024  # 88 M
    assert parts["router"] == 4 * 2 * C * 64
    assert parts["head"] == 2 * C * 16384  # 117 M
    assert parts["mla_projections"] == 5 * 2 * (7_767_296 - 768 - 512)
    # the causal triangle of four heads: (S + 1) / 2 keys a query, 192-wide
    # scores and 128-wide values
    assert parts["mla_scores"] == 5 * 2 * 4 * (192 + 128) * 4097 / 2
    assert parts["mhc_map"] == 10 * 2 * 4 * C * 24
    total = sum(parts.values())
    assert total == pytest.approx(560.3e6, rel=1e-3)
    batch = traffic["batch"]
    assert cell.family.step_flops(config, traffic, batch) == pytest.approx(
        3 * total * batch * 4096)
    assert cell.family.units_per_step(traffic) == batch * 4096


def test_the_count_files_against_hand_values(cell):
    config = cell.config
    # the flash calls: shapes of the padded call, work of the unpadded heads
    fwd = manifest.load_module("kernels", "flash_mla_causal_fwd")
    bwd = manifest.load_module("kernels", "flash_mla_causal_bwd")
    q = hlo_text.Shape("bf16", (2, 4096, 4 * 256), 0)
    call = hlo_text.MosaicCall("flash_mla_causal_fwd.1",
                               "flash_mla_causal_fwd", (q, q, q), (q,))
    pairs = 528 * 128 * 128  # 32 * 33 / 2 tiles on or below the diagonal
    assert fwd.heads(call) == 4
    flops, nbytes = fwd.work(call)
    assert flops == 2.0 * (192 + 128) * 2 * 4 * pairs
    assert nbytes == 4 * q.nbytes * 640 / 1024
    assert bwd.work(call)[0] == 2.0 * (3 * 192 + 2 * 128) * 2 * 4 * pairs
    assert bwd.work(call)[0] / flops == pytest.approx(2.6)
    # padded to 256 the same call would count 1.6 x the forward's work
    padded = manifest.load_module("kernels", "flash_bsh_causal_fwd")
    assert padded.work(call)[0] / flops == pytest.approx(1.6)
    # the routed experts at the expected rows: 512 a held expert at 8,192
    # tokens, 256 at 4,096
    moe = manifest.load_module("kernels", "routed_experts")
    assert moe.expected_rows(config, 8192) == 4096
    assert moe.expected_rows(config, 4096) == 2048
    assert moe.moe_layers(config) == 4
    flops, nbytes = moe.step_work(config, 8192)
    assert flops == 3 * 3 * 2.0 * 4096 * C * 1024 * 4
    assert nbytes == 3 * 3 * 2.0 * 4 * (8 * C * 1024 + 4096 * (C + 1024))
    # the residual path: the streams once in and once out, bf16, ten
    # sublayers, two passes
    mhc = manifest.load_module("kernels", "mhc")
    assert mhc.sublayers(config) == 10
    assert mhc.step_bytes(config, 8192) == 2 * 10 * 2 * 8192 * 4 * C * 2
    assert mhc.BOUND == "hbm"


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
    assert max(check["grad_rel_l2_error"].values()) < 2e-4
    assert len(check["grad_rel_l2_error"]) == 6 and check["loss_falls"]


def test_faults_and_a_lower_precision_are_refused_by_the_limits():
    """The family's reference with its products rounded to an 8-bit float,
    with one Sinkhorn round for twenty, without the shared expert, without
    the rotation and with the unscaled softmax each land outside at least
    one of the rehearsal's limits; with bf16 products inside all."""
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
    loss, grads = fam.reference_loss_and_grads(config, traffic, params, feed)
    limits = {k: v for k, v in config["check"]["grad_rel_l2"].items()
              if v is not None}

    def errors(**how):
        other, g = fam.reference_loss_and_grads(config, traffic, params, feed,
                                                **how)
        out = {"loss": abs(float(other) - float(loss)) / float(loss)}
        for label, name, index in fam.check_parameters(config):
            a, b = g[name], grads[name]
            if index is not None:
                a, b = a[index], b[index]
            out[label] = float(jnp.linalg.norm((a - b).ravel())
                               / jnp.linalg.norm(b.ravel()))
        return out

    def refused(found):
        return (found["loss"] > config["check"]["loss_rel"]
                or any(found[k] > v for k, v in limits.items()))

    assert not refused(errors(products_in=jnp.bfloat16))
    assert refused(errors(products_in=jnp.float8_e4m3fn))
    for fault in fam.FAULTS:
        assert refused(errors(faults=(fault,))), fault
    with pytest.raises(ValueError, match="unknown faults"):
        fam.reference_loss(config, params, feed["input_ids"], feed["labels"],
                           None, faults=("no_such",))


# ---------------------------------------------------------------------------
# the part split with its names as an argument
# ---------------------------------------------------------------------------

FWD = "jit(step)/forward/"
BWD = "jit(step)/backward/"


def test_the_part_is_the_first_component_that_names_one_of_the_names_given():
    part_of = part_scopes.part_of
    assert part_of(FWD + "jvp(mla)/bsh,hk->bsk/dot_general") == "mla"
    # the kernel's own name is one word and no part; the scope decides
    assert part_of(FWD + "jvp(mla)/flash_mla_causal_fwd/pallas_call") == "mla"
    assert part_of(BWD + "transpose(jvp(mhc_mix))/checkpoint/"
                   "rematted_computation/mul") == "mhc_mix"
    assert part_of(BWD + "transpose(jvp(mhc_map))/forward/jvp(mhc_map)/"
                   "checkpoint/div") == "mhc_map"
    assert part_of(FWD + "jvp(shared_expert)/...h,hf->...f/dot_general") == (
        "shared_expert")
    # the accepted names are among the default ones, XLA's own too
    assert part_of(FWD + "jvp(moe_experts)/moe_gmm_nn/pallas_call") == (
        "moe_experts")
    assert part_of("ragged-dot-none") == "moe_experts"
    assert part_of("ragged-dot-none", ("mla",)) is None
    assert part_of(FWD + "jvp(mla)/mul", ("rms_norm",)) is None
    for op_name in (FWD + "jvp(rms_norm)/mul", BWD + "transpose(forward)/"
                    "jvp(moe_combine)/jit(_take)", "ragged-dot-metadata",
                    FWD + "jvp()/mul", "", FWD + "jvp(mlas)/mul"):
        assert part_of(op_name) == scopes.part_of(op_name)
    assert part_scopes.PARTS[:8] == scopes.PARTS


def _module():
    ins = roles.Instruction
    return roles.Module("jit_step", {
        1: [ins("fusion.1", "fusion", FWD + "jvp(mhc_map)/dot_general", (2,)),
            ins("fusion.2", "fusion", BWD + "transpose(jvp(mhc_mix))/mul",
                (3,)),
            ins("fusion.3", "fusion", FWD + "jvp(mla)/dot_general", (4,)),
            ins("flash.1", "custom-call",
                FWD + "jvp(mla)/flash_mla_causal_fwd/pallas_call"),
            ins("flash.2", "custom-call", BWD + "transpose(jvp(mla))/"
                "flash_mla_causal_bwd/pallas_call"),
            ins("gmm.1", "custom-call",
                FWD + "jvp(moe_experts)/moe_gmm_nn/pallas_call"),
            ins("fusion.4", "fusion", FWD + "jvp(shared_expert)/dot_general",
                (5,)),
            ins("fusion.5", "fusion", FWD + "jvp(rms_norm)/mul", (6,)),
            ins("fusion.6", "fusion", "jit(step)/optimizer/sub", (7,))],
        2: [ins("dot.1", "dot", FWD + "jvp(mhc_map)/dot_general")],
        3: [ins("mul.1", "multiply", BWD + "transpose(jvp(mhc_mix))/mul")],
        4: [ins("dot.2", "dot", FWD + "jvp(mla)/dot_general")],
        5: [ins("dot.3", "dot", FWD + "jvp(shared_expert)/dot_general")],
        # the sublayer norm's multiply fused into mla's first projection
        6: [ins("mul.2", "multiply", FWD + "jvp(rms_norm)/mul"),
            ins("dot.4", "dot", FWD + "jvp(mla)/dot_general")],
        7: [ins("sub.1", "subtract", "jit(step)/optimizer/sub")],
    })


def _planes():
    names = ["fusion.1", "fusion.2", "fusion.3", "flash.1", "flash.2",
             "gmm.1", "fusion.4", "fusion.5", "fusion.6"]
    ends = [6, 30, 40, 44, 54, 74, 84, 90, 100]
    events, start = [], 0
    for name, end in zip(names, ends):
        events.append(Event(name, start * MS, end * MS))
        start = end
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


def _run_facts(cell, kernel_events):
    """RunFacts as the harness fills it, over the hand-made trace."""
    q = hlo_text.Shape("bf16", (2, 4096, 1024), 0)
    calls = {name: hlo_text.MosaicCall(name, kernel, (q, q, q), (q,))
             for name, kernel, _ in kernel_events}
    device = tr.DeviceReduction(
        ordinal=0, window_ns=104 * MS, busy_ns=100 * MS, self_ns_by_name={},
        calls_by_name={},
        kernel_ns={k: ns for _, k, ns in kernel_events},
        kernel_calls={k: [(name, ns)] for name, k, ns in kernel_events},
        collective_ns=0.0, collective_exposed_ns=0.0, gaps=[])
    return harness.RunFacts(
        cell, manifest.load_peaks("TPU v5 lite"), [], {}, {},
        tr.TraceReduction([device], 0.104, 2, {}), calls, 0)


def test_the_eight_readers_over_the_trace_and_without_one(cell, trace_dir,
                                                          monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    part_scopes.split_of_trace.cache_clear()
    roles.split_of_trace.cache_clear()
    found = part_scopes.split_of_trace(tr.find_xplane(trace_dir), 2)
    assert found.carried["fusion.5"] == {"rms_norm", "mla"}  # counted nowhere
    assert found.carried["flash.1"] == {"mla"}
    readers = {n: manifest.load_module("layer_metrics", n)
               for n in NEW_READERS}
    run = _run_facts(cell, [
        ("flash.1", "flash_mla_causal_fwd", 4 * MS),
        ("flash.2", "flash_mla_causal_bwd", 10 * MS)])
    got = {n: r.read(run) for n, r in readers.items()}
    assert got["mhc_ms_per_step"] == pytest.approx((6 + 24) / 2)
    # the projections and both flash calls, not the fusion shared with a norm
    assert got["mla_ms_per_step"] == pytest.approx((10 + 4 + 10) / 2)
    assert got["mla_flash_ms_per_step"] == pytest.approx((4 + 10) / 2)
    assert got["routed_experts_ms_per_step"] == pytest.approx(20 / 2)
    assert got["shared_expert_ms_per_step"] == pytest.approx(10 / 2)
    tokens = cell.traffic["batch"] * 4096
    config = cell.config
    least = manifest.load_module("kernels", "mhc").step_bytes(
        config, tokens) / 819e9
    assert got["mhc_roofline"] == pytest.approx(100 * least / 15e-3, rel=1e-6)
    flops, nbytes = manifest.load_module(
        "kernels", "routed_experts").step_work(config, tokens)
    assert got["routed_experts_roofline"] == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 10e-3, rel=1e-6)
    pairs = 528 * 128 * 128
    least = 2.0 * (4 * 192 + 3 * 128) * 2 * 4 * pairs / 197e12
    assert got["mla_flash_roofline"] == pytest.approx(
        100 * least / 14e-3, rel=1e-6)
    assert all(0 < v < 100 for v in got.values()), got
    # an untraced run, and a step without the kernels or the scopes (the
    # parent of this PR, over which the new files are laid): nothing to
    # read, nothing raised
    untraced = harness.RunFacts(cell, run.peaks, [], {}, {}, None, {}, 0)
    assert [r.read(untraced) for r in readers.values()] == [None] * 8
    bare = roles.Module("jit_step", {1: [
        roles.Instruction("fusion.1", "fusion",
                          FWD + "jvp(rms_norm)/dot_general"),
        roles.Instruction("fusion.2", "fusion", FWD + "jvp()/mul")]})
    with open(tr.find_xplane(trace_dir), "wb") as f:
        f.write(_xplane(_planes(), {"jit_step(7)": bare}))
    part_scopes.split_of_trace.cache_clear()
    roles.split_of_trace.cache_clear()
    parent = _run_facts(cell, [])
    assert [r.read(parent) for r in readers.values()] == [None] * 8
    part_scopes.split_of_trace.cache_clear()
    roles.split_of_trace.cache_clear()


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
        for carried in part_scopes.carried_parts(module).values():
            parts |= carried
    assert parts == {"mla", "mhc_map", "mhc_mix", "shared_expert", "rms_norm",
                     "swiglu_ffn", "moe_route", "moe_dispatch", "moe_experts",
                     "moe_combine"}
