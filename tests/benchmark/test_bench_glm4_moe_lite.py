"""The `glm-4.7-flash` configuration and its cell on the CPU: the manifest's
entries, every one found by name, against the catalog row's `config`; the
parameter count of the program that is built; the family file's arithmetic
and the new count files against hand values; the batch with both labels;
the seven new readers over a hand-made trace; the program against the
family's reference with the AMP rewrite left out, and the reference under
each fault against the rehearsal's limits; a traced rehearsal."""
import json
import os
import shutil
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

from benchmark import harness, hlo_text, manifest, part_scopes, roles
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event, Line, Plane
from test_bench_roles import _xplane  # the trace file's wire format, by hand

CELL = "glm-4.7-flash.ep8share.mtp.s4096"
CONFIG = "glm-4.7-flash"
TRAFFIC = "pretrain-s4096-packed-mtp-ep8"
# `config` of GLM-4.7-Flash in the model-configs catalog, which is the
# released config.json without the keys that say nothing about the shape
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
REDUCED = ["num_hidden_layers"]
NEW_READERS = {
    "mtp_ms_per_step": "mtp", "mtp_head_ms_per_step": "mtp",
    "lm_head_ms_per_step": "head", "mla_wide_flash_ms_per_step": "kernels",
    "mla_wide_flash_roofline": "kernels",
    "lite_experts_ms_per_step": "experts",
    "lite_experts_roofline": "experts"}
LABELS = ["embedding", "lm_head", "mtp.eh_proj", "mtp.kv_b_proj",
          "first.kv_b_proj", "first_moe.w1", "first_moe.shared_w1",
          "first_moe.gate"]
MS = 1e6  # ns
C = 2048


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
        CONFIG, TRAFFIC, 1)
    assert len(row["why"]) <= 200
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    assert CELL in _named(doc["end_to_end"],
                          "tokens_per_s_per_chip")["workloads"]
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
    assert {m["name"] for m in cell.per_layer if "workloads" in m} == set(
        NEW_READERS)
    assert {"mfu", "forward_ms_per_step", "backward_ms_per_step",
            "optimizer_ms_per_step"} <= {m["name"] for m in cell.per_layer}
    traffic = cell.traffic
    assert (traffic["seq_len"], traffic["log_every"], traffic["pool"],
            traffic["check_batch"], traffic["mesh"]) == (4096, 5, 8, 1, None)
    assert traffic["batch"] in (1, 2)  # the one the compile allowed


def test_every_published_number_stands_unless_reduced(doc, cell):
    entry = _named(doc["configs"], CONFIG)
    config = cell.config
    assert entry["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/glm-4.7-flash.json"
    assert entry["reduced"] == config["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        assert config["published"].get(key, value) == value, key
    assert set(config["published"]) == set(REDUCED)
    # the cut: the leading dense layer, four expert layers, and the module
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_nextn_predict_layers"]) == (5, 1, 1)
    # the chip's share, an eighth of the experts and of the vocabulary, at
    # the guide's floors; heads are held whole
    assert (config["experts_held"], config["first_expert"]) == (8, 0)
    assert config["vocab_rows"] * 8 == config["vocab_size"]
    assert not any("head" in k or "expert" in k for k in config["reduced"])
    assert "eight chips share each layer" in config["deployment"]
    assert "expert-parallel" in config["deployment"]
    assert set(config["changed"]) == set(REDUCED) | {
        "experts_held", "vocab_rows"}
    assert set(config["assumed"]) >= {
        "mtp_loss_weight", "scoring_func", "eh_proj_order", "mtp_input",
        "rope_pairing", "gate_denominator", "initializer_range", "optimizer",
        "positions"}
    assert config["mtp_loss_weight"] == 0.3
    assert config["program"] == {"amp": "bf16", "use_flash_attention": True,
                                 "remat_ffn": True}
    assert config["mosaic_calls"] == [
        "flash_mla_wide_causal_fwd", "flash_mla_wide_causal_bwd",
        "moe_gmm_nn", "moe_gmm_nt", "moe_gmm_tn"]
    labels = [label for label, _, _ in cell.family.check_parameters(config)]
    assert labels == LABELS
    assert set(config["check"]["grad_rel_l2"]) == set(labels)
    assert len(config["check"]["why"]) > 500
    built = cell.family.model_config(config)
    assert (built.hidden_size, built.heads_held, built.n_routed_experts,
            built.experts_held, built.vocab_rows, built.remat_ffn,
            built.num_nextn_predict_layers, built.mtp_loss_weight,
            built.softmax_scale, built.inv_freq) == (
        2048, 20, 64, 8, 19360, True, 1, 0.3, 1 / 16, None)
    with pytest.raises(ValueError, match="not built"):
        cell.family.model_config(dict(config, n_group=8))


def test_the_built_program_has_the_parameters_of_the_issues_count(cell):
    """Shapes only: the program at the published widths is built and
    nothing of its size is allocated."""
    built = harness.build_program(cell, 1, dropout=False, seed=1)
    assert built.feed_names == ["input_ids", "labels", "labels_next"]
    sizes = {p.name: int(np.prod(p.shape))
             for p in built.main.all_parameters()}

    def total(prefix):
        return sum(n for name, n in sizes.items() if name.startswith(prefix))

    mla = (C * 768 + 768 * 20 * 256 + C * 576 + 512 * 20 * 448 + 20 * 256 * C
           + 768 + 512)
    assert total("layers.0.self_attn.") == total("mtp.self_attn.") == mla
    assert mla == 21_759_232
    expert = 3 * C * 1536
    assert total("layers.1.mlp.shared_experts.") == expert == 9_437_184
    assert sizes["layers.1.mlp.gate"] + sizes["layers.1.mlp.expert_bias"] == (
        C * 64 + 64)
    layer = mla + 9 * expert + C * 64 + 64 + 2 * C
    assert total("layers.1.") == layer == 106_829_120  # a layer with experts
    assert total("layers.0.") == mla + 3 * C * 10240 + 2 * C == 84_677_888
    # the module: one more expert layer, W_eh and three norms
    assert total("mtp.") == layer + 2 * C * C + 3 * C == 115_223_872
    assert sizes["embed_tokens.weight"] == sizes["lm_head.weight"] == (
        19360 * C)
    # each of the two shared parameters once
    assert sum(sizes.values()) == (
        84_677_888 + 4 * layer + 115_223_872 + 2 * 19360 * C + C
    ) == 706_518_848 == cell.config["stated"]["parameters"]


def test_model_flops_are_of_what_the_chip_computes(cell):
    config, traffic = cell.config, cell.traffic
    parts = cell.family.forward_flops_per_token(config, 4096)
    assert parts["dense_mlp"] == 6 * C * 10240
    # an eighth of the four picks falls on the eight experts held, in four
    # trunk layers and the module's
    assert parts["routed_experts"] == 5 * 0.5 * 6 * C * 1536
    assert parts["shared_expert"] == 5 * 6 * C * 1536
    assert parts["router"] == 5 * 2 * C * 64
    assert parts["heads"] == 2 * 2 * C * 19360  # the head scores twice
    assert parts["mtp_eh_proj"] == 2 * 2 * C * C
    assert parts["mla_projections"] == 6 * 2 * (21_759_232 - 768 - 512)
    # the causal triangle of twenty heads: (S + 1) / 2 keys a query,
    # 256-wide scores and 256-wide values, six blocks
    assert parts["mla_scores"] == 6 * 2 * 20 * (256 + 256) * 4097 / 2
    total = sum(parts.values())
    assert total == pytest.approx(956.9e6, rel=1e-3)  # 478 M multiply-adds
    assert parts["mla_scores"] / total == pytest.approx(0.263, abs=2e-3)
    # the module is over a fifth of the step here; of the whole model's
    # (47 layers, all experts, the whole vocabulary) 8 %, three quarters of
    # that its head over 154,880 rows
    assert cell.family.mtp_flops_share(config, 4096) == pytest.approx(
        0.220, abs=2e-3)
    whole = dict(config, num_hidden_layers=47, experts_held=64,
                 vocab_rows=154880)
    assert cell.family.mtp_flops_share(whole, 4096) == pytest.approx(
        0.083, abs=3e-3)
    without = cell.family.forward_flops_per_token(
        dict(config, num_nextn_predict_layers=0), 4096)
    assert without["mtp_eh_proj"] == 0 and without["heads"] == 2 * C * 19360
    batch = traffic["batch"]
    assert cell.family.step_flops(config, traffic, batch) == pytest.approx(
        3 * total * batch * 4096)
    assert cell.family.units_per_step(traffic) == batch * 4096


def test_the_count_files_against_hand_values(cell):
    config = cell.config
    # the flash calls: a group of ten heads of 256, nothing padded
    fwd = manifest.load_module("kernels", "flash_mla_wide_causal_fwd")
    bwd = manifest.load_module("kernels", "flash_mla_wide_causal_bwd")
    q = hlo_text.Shape("bf16", (2, 4096, 10 * 256), 0)
    call = hlo_text.MosaicCall("flash_mla_wide_causal_fwd.1",
                               "flash_mla_wide_causal_fwd", (q, q, q), (q,))
    pairs = 528 * 128 * 128  # 32 * 33 / 2 tiles on or below the diagonal
    flops, nbytes = fwd.work(call)
    assert flops == 2.0 * (256 + 256) * 2 * 10 * pairs
    assert nbytes == 4 * q.nbytes
    assert bwd.work(call) == (2.5 * flops, nbytes)
    assert (fwd.BOUND, bwd.BOUND) == ("compute", "compute")
    # what `flash_bsh_causal_fwd` counts for the same shapes, and 1.6 x
    # what the padded form's file would charge under Xing4's widths
    same = manifest.load_module("kernels", "flash_bsh_causal_fwd")
    assert same.work(call)[0] == flops
    padded = manifest.load_module("kernels", "flash_mla_causal_fwd")
    assert flops / padded.work(call)[0] == pytest.approx(1.6)
    # a buffer XLA keeps on the chip is not HBM traffic
    held = hlo_text.Shape("bf16", (2, 4096, 10 * 256), 1)
    assert fwd.work(hlo_text.MosaicCall("x", "flash_mla_wide_causal_fwd",
                                        (held, q, q), (q,)))[1] == 3 * q.nbytes
    # the routed experts at the expected rows (1,024 of T k = 32,768 picks
    # at 8,192 tokens: 512 a held expert at batch 2, 256 at batch 1) over
    # five expert layers, the module's among them
    moe = manifest.load_module("kernels", "lite_experts")
    assert moe.expected_rows(config, 8192) == 4096
    assert moe.expected_rows(config, 4096) == 2048
    assert moe.moe_layers(config) == 5
    assert moe.moe_layers(dict(config, num_nextn_predict_layers=0)) == 4
    flops, nbytes = moe.step_work(config, 8192)
    assert flops == 3 * 3 * 2.0 * 4096 * C * 1536 * 5
    assert nbytes == 3 * 3 * 2.0 * 5 * (8 * C * 1536 + 4096 * (C + 1536))


def test_packed_batches_hold_both_labels_from_the_held_rows(cell):
    config = dict(cell.config, vocab_rows=97)
    traffic = dict(cell.traffic, seq_len=40)
    a = cell.family.make_batch(config, traffic, 3, harness.batch_rng(5, 1, 0))
    b = cell.family.make_batch(config, traffic, 3, harness.batch_rng(5, 1, 0))
    c = cell.family.make_batch(config, traffic, 3,
                               harness.batch_rng(2147483999, 1, 0))
    assert list(a) == ["input_ids", "labels", "labels_next"]
    for name in a:
        assert a[name].shape == (3, 40) and a[name].dtype == np.int32
        np.testing.assert_array_equal(a[name], b[name])
        assert 0 <= a[name].min() and a[name].max() < 97
    # rows cut from a stream of S + 2 tokens: no position lacks a label
    np.testing.assert_array_equal(a["labels"][:, :-1], a["input_ids"][:, 1:])
    np.testing.assert_array_equal(a["labels_next"][:, :-1], a["labels"][:, 1:])
    assert not np.array_equal(a["input_ids"], c["input_ids"])


def test_program_is_the_reference_in_float32():
    """With the AMP rewrite left out, the program the harness builds and
    the family's reference are the same arithmetic, in the loss, in both of
    its parts and in the eight labelled gradients."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib import mixed_precision
    from paddle_tpu.fluid.executor import Scope

    small = manifest.load_cell(manifest.load_manifest(), CELL, rehearse=True)
    with mock.patch.object(mixed_precision, "decorate",
                           lambda opt, use_bf16=True: opt):
        check = harness.run_check(small, seed=5)
        built = harness.build_program(small, 1, dropout=False, seed=5)
    assert check["loss_rel_error"] < 1e-5
    assert max(check["grad_rel_l2_error"].values()) < 2e-4
    assert list(check["grad_rel_l2_error"]) == LABELS and check["loss_falls"]
    # the parts of the loss, fetched beside it
    fam, config, traffic = small.family, small.config, small.traffic
    exe, scope = fluid.Executor(), Scope()
    exe.run(built.startup, scope=scope)
    params = {p.name: scope.find_var(p.name)
              for p in built.main.all_parameters()}
    feed = fam.make_batch(config, traffic, 1, harness.batch_rng(5, 2))
    want, _ = fam.reference_loss_and_grads(config, traffic, params, feed,
                                           parts=True)
    parts = fam.part_losses(built.main)
    got = exe.run(built.main, feed=feed, scope=scope, fetch_list=[
        built.loss, parts["main_loss"], parts["mtp_loss"]])
    for g, w in zip(got, want):
        assert float(g[0]) == pytest.approx(float(w), rel=1e-5)
    assert float(want[0]) == pytest.approx(
        float(want[1]) + 0.3 * float(want[2]), rel=1e-6)


def test_faults_and_a_lower_precision_are_refused_by_the_limits():
    """The family's reference with its products rounded to an 8-bit float
    and under each of its faults (lambda 0, the trunk cut off in front of
    the module, the module embedding from a table of its own, labels_next =
    labels, no rotation, the scale of the unrotated part alone, gates not
    multiplied by 1.8, no shared expert) lands outside at least one of the
    rehearsal's limits; with bf16 products inside all."""
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
    assert len(limits) >= 6

    def errors(**how):
        other, g = fam.reference_loss_and_grads(config, traffic, params, feed,
                                                **how)
        out = {"loss": abs(float(other) - float(loss)) / float(loss)}
        for label, name, _ in fam.check_parameters(config):
            a, b = g[name], grads[name]
            out[label] = float(jnp.linalg.norm((a - b).ravel())
                               / jnp.linalg.norm(b.ravel()))
        return out

    def refused(found):
        return (found["loss"] > config["check"]["loss_rel"]
                or any(found[k] > v for k, v in limits.items()))

    assert not refused(errors(products_in=jnp.bfloat16))
    assert refused(errors(products_in=jnp.float8_e4m3fn))
    assert len(fam.FAULTS) == 8
    for fault in fam.FAULTS:
        assert refused(errors(faults=(fault,))), fault


# ---------------------------------------------------------------------------
# the seven readers over a hand-made trace
# ---------------------------------------------------------------------------

FWD = "jit(step)/forward/"
BWD = "jit(step)/backward/"


def test_the_modules_scopes_come_first_in_an_op_name():
    part_of = part_scopes.part_of
    heads = ("lm_head", "mtp_head")
    # the module's block lowers under `mtp` and its ops' own scopes
    name = FWD + "mtp/jvp(mla)/flash_mla_wide_causal_fwd/pallas_call"
    assert part_of(name, ("mtp",)) == "mtp"
    assert part_of(name) == "mla"  # among the accepted names, the op's part
    assert part_of(BWD + "mtp/transpose(forward)/mtp/jvp(moe_experts)/"
                   "moe_gmm_tn/pallas_call", ("mtp",)) == "mtp"
    assert part_of(FWD + "mtp/mtp_head/jvp(rms_norm)/mul", heads) == "mtp_head"
    assert part_of(FWD + "mtp/mtp_head/dot_general", ("mtp",)) == "mtp"
    assert part_of(FWD + "lm_head/dot_general", heads) == "lm_head"
    # `mtp_head` and `mtp_combine` are one word each, and no `mtp`
    assert part_of(FWD + "mtp_combine/mul", ("mtp",)) is None
    assert part_of(FWD + "jvp(mla)/mul", ("mtp",)) is None
    assert part_of(FWD + "jvp(mla)/mul", heads) is None


def _module():
    ins = roles.Instruction
    return roles.Module("jit_step", {
        1: [ins("fusion.1", "fusion", FWD + "jvp(mla)/dot_general", (2,)),
            ins("flash.1", "custom-call",
                FWD + "jvp(mla)/flash_mla_wide_causal_fwd/pallas_call"),
            ins("flash.2", "custom-call", BWD + "mtp/transpose(forward)/mtp/"
                "jvp(mla)/flash_mla_wide_causal_bwd/pallas_call"),
            ins("gmm.1", "custom-call",
                FWD + "jvp(moe_experts)/moe_gmm_nn/pallas_call"),
            ins("gmm.2", "custom-call",
                FWD + "mtp/jvp(moe_experts)/moe_gmm_nn/pallas_call"),
            ins("fusion.2", "fusion", FWD + "lm_head/dot_general", (3,)),
            ins("fusion.3", "fusion", FWD + "mtp/mtp_head/dot_general", (4,)),
            ins("fusion.4", "fusion", FWD + "mtp/mtp_combine/dot_general",
                (5,)),
            # both heads' logits in one body: counted under neither
            ins("fusion.5", "fusion", BWD + "lm_head/dot_general", (6,)),
            ins("fusion.6", "fusion", "jit(step)/optimizer/sub", (7,))],
        2: [ins("dot.1", "dot", FWD + "jvp(mla)/dot_general")],
        3: [ins("dot.2", "dot", FWD + "lm_head/dot_general")],
        4: [ins("dot.3", "dot", FWD + "mtp/mtp_head/dot_general")],
        5: [ins("dot.4", "dot", FWD + "mtp/mtp_combine/dot_general")],
        6: [ins("dot.5", "dot", BWD + "lm_head/dot_general"),
            ins("dot.6", "dot", BWD + "mtp/mtp_head/dot_general")],
        7: [ins("sub.1", "subtract", "jit(step)/optimizer/sub")],
    })


def _planes():
    names = ["fusion.1", "flash.1", "flash.2", "gmm.1", "gmm.2", "fusion.2",
             "fusion.3", "fusion.4", "fusion.5", "fusion.6"]
    ends = [10, 14, 24, 36, 40, 50, 58, 62, 90, 100]
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
    q = hlo_text.Shape("bf16", (2, 4096, 2560), 0)
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


def test_the_seven_readers_over_the_trace_and_without_one(cell, trace_dir,
                                                          monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    part_scopes.split_of_trace.cache_clear()
    roles.split_of_trace.cache_clear()
    readers = {n: manifest.load_module("layer_metrics", n)
               for n in NEW_READERS}
    run = _run_facts(cell, [
        ("flash.1", "flash_mla_wide_causal_fwd", 4 * MS),
        ("flash.2", "flash_mla_wide_causal_bwd", 10 * MS)])
    got = {n: r.read(run) for n, r in readers.items()}
    # the module: its flash backward, its experts, its head and its combine;
    # the fusion of both heads' backward mixes module and trunk: neither's
    assert got["mtp_ms_per_step"] == pytest.approx((10 + 4 + 8 + 4) / 2)
    assert got["lm_head_ms_per_step"] == pytest.approx(10 / 2)
    assert got["mtp_head_ms_per_step"] == pytest.approx(8 / 2)
    assert got["mla_wide_flash_ms_per_step"] == pytest.approx((4 + 10) / 2)
    # the experts of the trunk and of the module under one scope name
    assert got["lite_experts_ms_per_step"] == pytest.approx((12 + 4) / 2)
    config, tokens = cell.config, cell.traffic["batch"] * 4096
    flops, nbytes = manifest.load_module(
        "kernels", "lite_experts").step_work(config, tokens)
    assert got["lite_experts_roofline"] == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 8e-3, rel=1e-6)
    pairs = 528 * 128 * 128
    least = 2.0 * 7 * 256 * 2 * 10 * pairs / 197e12
    assert got["mla_wide_flash_roofline"] == pytest.approx(
        100 * least / 14e-3, rel=1e-6)
    assert all(0 < v < 100 for v in got.values()), got
    # an untraced run, and a step without the kernels or the scopes: nothing
    # to read, nothing raised
    untraced = harness.RunFacts(cell, run.peaks, [], {}, {}, None, {}, 0)
    assert [r.read(untraced) for r in readers.values()] == [None] * 7
    bare = roles.Module("jit_step", {1: [
        roles.Instruction("fusion.1", "fusion",
                          FWD + "jvp(rms_norm)/dot_general"),
        roles.Instruction("fusion.2", "fusion", FWD + "jvp()/mul")]})
    with open(tr.find_xplane(trace_dir), "wb") as f:
        f.write(_xplane(_planes(), {"jit_step(7)": bare}))
    part_scopes.split_of_trace.cache_clear()
    roles.split_of_trace.cache_clear()
    parent = _run_facts(cell, [])
    assert [r.read(parent) for r in readers.values()] == [None] * 7
    part_scopes.split_of_trace.cache_clear()
    roles.split_of_trace.cache_clear()


def test_a_traced_rehearsal_finds_the_readers_and_reports_no_device_number(
        tmp_path):
    """`--rehearse --trace 1` from a copy of the benchmark (its own trace
    directory): `correct`, the new readers found and silent on the CPU,
    and the trace's module carries the module's and the heads' scopes."""
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
    assert list(check["grad_rel_l2_error"]) == LABELS
    path = tr.find_xplane(os.path.join(root, ".bench_trace"))
    names = ("mtp", "mtp_combine", "mtp_head", "lm_head")
    found, under_mtp = set(), set()
    for module in roles.modules_in(path).values():
        for carried in part_scopes.carried_parts(module, names).values():
            found |= carried
        for ins in module.instructions():
            if part_scopes.part_of(ins.op_name, ("mtp",)):
                under_mtp.add(part_scopes.part_of(ins.op_name))
    # `mtp_combine` and `mtp_head` nest inside `mtp`, which comes first
    assert found == {"mtp", "lm_head"}
    assert {"mla", "moe_experts", "shared_expert", "rms_norm"} <= under_mtp
