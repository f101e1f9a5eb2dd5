"""The `granite-4.0-h-micro` configuration and its cell, and the
`bert-base.s2048` cell, on the CPU: the manifest's entries, found by name
and never by position, against the catalog row's `config` copied in as a
literal; what `FIXED` refuses; the parameter count of the program that is
built, by part; the family file's arithmetic and the scan's count file
against hand values; the four new readers over a hand-made trace and
without one; the program against the family's reference with the AMP
rewrite left out, and the reference under each fault against the
rehearsal's limits."""
import math
from unittest import mock

import numpy as np
import pytest

from benchmark import harness, manifest, part_scopes, roles
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event, Line, Plane
from test_bench_roles import _xplane  # the trace file's wire format, by hand

CELL = "granite-4.0-h-micro.vocab8.s4096"
CONFIG = "granite-4.0-h-micro"
BERT_CELL = "bert-base.s2048"
# `config` of granite-4.0-h-micro in the model-configs catalog, which is the
# released config.json without the keys that say nothing about the shape
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
REDUCED = ["num_hidden_layers", "layer_types"]
NEW_READERS = {"mamba_g1_ms_per_step": "mixer",
               "ssd_scan_g1_ms_per_step": "kernels",
               "ssd_scan_g1_roofline": "kernels",
               "block_mlp_ms_per_step": "mlp"}
LABELS = {"embedding", "mamba.A_log", "mamba.dt_bias", "mamba.conv1d",
          "mamba.in_proj", "mamba.norm", "attention.k_proj", "mlp.w_gate",
          "mlp.w_o"}
MS = 1e6  # ns
C = 2048
MLP = 2 * C * 8192 + 8192 * C           # W_gate, W_up, W_o
MAMBA_LAYER = 76_182_976
ATTENTION_LAYER = 60_821_504
VOCABULARY = 12544 * C + C              # the tied table and the final norm


@pytest.fixture(scope="module")
def doc():
    return manifest.load_manifest()


@pytest.fixture(scope="module")
def cell(doc):
    return manifest.load_cell(doc, CELL)


def _named(rows, name):
    (row,) = [r for r in rows if r["name"] == name]
    return row


def test_the_manifest_has_both_cells_by_name_and_no_problems(doc, cell):
    assert manifest.problems(doc) == []
    row = _named(doc["workloads"], CELL)
    assert (row["config"], row["traffic"], row["chips"]) == (
        CONFIG, "pretrain-s4096-packed-vocab8", 1)
    bert = _named(doc["workloads"], BERT_CELL)
    assert (bert["config"], bert["traffic"], bert["chips"]) == (
        "bert-base", "pretrain-s2048", 1)
    assert all(len(w["why"]) <= 200 for w in (row, bert))
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    tokens = _named(doc["end_to_end"], "tokens_per_s_per_chip")
    assert {CELL, BERT_CELL} <= set(tokens["workloads"])
    assert {"tokens_per_s_per_chip", "step_ms", "peak_hbm_gb", "setup_s"} == {
        m["name"] for m in cell.end_to_end}
    listed = {m["name"] for m in cell.per_layer if "workloads" in m}
    assert set(NEW_READERS) <= listed
    assert "mfu" in {m["name"] for m in cell.per_layer}
    # the BERT cell reports what its siblings report, the flash and fused
    # LayerNorm kernels among it
    bert_cell = manifest.load_cell(doc, BERT_CELL)
    sibling = manifest.load_cell(doc, "bert-base.s4096")
    assert [m["name"] for m in bert_cell.per_layer] == [
        m["name"] for m in sibling.per_layer]
    assert {"flash_ms_per_step", "flash_roofline", "add_ln_ms_per_step",
            "add_ln_roofline"} <= {m["name"] for m in bert_cell.per_layer}
    traffic = cell.traffic
    assert (traffic["batch"], traffic["seq_len"], traffic["log_every"],
            traffic["pool"], traffic["check_batch"], traffic["mesh"]) == (
        1, 4096, 5, 8, 1, None)
    # the same 32,768 tokens a step as the BERT siblings, and their mix
    assert bert_cell.traffic["batch"] * bert_cell.traffic["seq_len"] == (
        sibling.traffic["batch"] * sibling.traffic["seq_len"]) == 32768
    for key in ("max_preds", "masked_lm_prob", "short_seq_prob", "log_every",
                "pool", "check_batch"):
        assert bert_cell.traffic[key] == sibling.traffic[key], key


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_new_reader_is_declared_as_its_file_says(doc, name):
    reader = manifest.load_module("layer_metrics", name)
    row = _named(doc["per_layer"], name)
    assert CELL in row["workloads"]
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
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    assert entry["file"] == "benchmark/configs/granite-4.0-h-micro.json"
    assert entry["reduced"] == config["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        assert config["published"].get(key, value) == value, key
    assert set(config["published"]) == set(REDUCED)
    # the cut: published layers 0-9, one whole period of the pattern
    assert config["num_hidden_layers"] == 10
    assert config["layer_types"] == PUBLISHED["layer_types"][:10] == (
        PUBLISHED["layer_types"][10:20])
    assert config["layer_types"].index("attention") == 5
    # the chip's share: an eighth of the tied vocabulary; no width differs
    assert config["vocab_rows"] * 8 == config["vocab_size"] == 100352
    assert "vocabulary-parallel" in config["deployment"]
    assert set(config["changed"]) == set(REDUCED) | {"vocab_rows"}
    assert set(config["assumed"]) >= {
        "initializer_range", "mamba_init", "mlp_halves", "optimizer",
        "positions"}
    assert config["program"] == {"amp": "bf16", "use_flash_attention": True,
                                 "remat_ffn": True}
    assert config["mosaic_calls"] == ["flash_mla_wide_causal_fwd",
                                      "flash_mla_wide_causal_bwd"]
    assert config["stated"]["parameters"] == 772_160_448
    assert 0.25 * 16 < config["stated"]["peak_hbm_gb"] <= 15.2
    labels = [label for label, _, _ in cell.family.check_parameters(config)]
    assert set(config["check"]["grad_rel_l2"]) == set(labels) == LABELS
    assert len(config["check"]["why"]) > 500
    built = cell.family.model_config(config)
    assert (built.hidden_size, built.mamba_n_heads, built.mamba_d_head,
            built.mamba_n_groups, built.mamba_d_state, built.mamba_chunk_size,
            built.shared_intermediate_size, built.vocab_rows,
            built.attention_multiplier, built.remat_ffn) == (
        2048, 64, 64, 1, 128, 256, 8192, 12544, 0.015625, True)


@pytest.mark.parametrize("key, other", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"),
    ("tie_word_embeddings", False), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("attention_bias", True),
    ("hidden_act", "gelu"), ("normalization_function", "layernorm")])
def test_fixed_refuses_every_other_value(cell, key, other):
    assert cell.family.FIXED[key] == PUBLISHED[key]
    with pytest.raises(ValueError, match="is not built"):
        cell.family.model_config(dict(cell.config, **{key: other}))


def test_a_pattern_of_the_wrong_length_is_refused(cell):
    with pytest.raises(ValueError, match="10 entries for 9 layers"):
        cell.family.model_config(dict(cell.config, num_hidden_layers=9))
    with pytest.raises(ValueError, match="not built"):
        cell.family.model_config(dict(cell.config, layer_types=[
            "mamba"] * 9 + ["sliding_attention"]))


def test_the_built_program_has_the_parameters_counted_by_part(cell):
    """Shapes only: the program at the published widths is built and
    nothing of its size is allocated."""
    built = harness.build_program(cell, 1, dropout=False, seed=1)
    sizes = {p.name: int(np.prod(p.shape))
             for p in built.main.all_parameters()}

    def total(prefix):
        return sum(n for name, n in sizes.items() if name.startswith(prefix))

    # z 4096, xBC 4096 + 2 x 1 x 128, dt 64
    assert sizes["layers.0.mamba.in_proj"] == C * 8512 == 17_432_576
    assert (sizes["layers.0.mamba.conv1d.weight"]
            + sizes["layers.0.mamba.conv1d.bias"]) == 4352 * 4 + 4352
    assert sizes["layers.0.mamba.norm.weight"] == 4096
    assert sizes["layers.0.mamba.out_proj"] == 4096 * C
    assert total("layers.0.shared_mlp.") == MLP == 50_331_648
    assert total("layers.0.") == MAMBA_LAYER
    assert sizes["layers.5.self_attn.k_proj.weight"] == C * 512
    assert total("layers.5.") == ATTENTION_LAYER
    for i, kind in enumerate(cell.config["layer_types"]):
        assert total(f"layers.{i}.") == {
            "mamba": MAMBA_LAYER, "attention": ATTENTION_LAYER}[kind]
    # one table, looked up and scored against: no head of its own
    assert sizes["embed_tokens.weight"] + sizes["norm.weight"] == VOCABULARY
    assert not any("lm_head" in name for name in sizes)
    assert sum(sizes.values()) == (
        9 * MAMBA_LAYER + ATTENTION_LAYER + VOCABULARY
    ) == 772_160_448 == cell.config["stated"]["parameters"]


def test_model_flops_are_of_what_the_chip_computes(cell):
    config, traffic = cell.config, cell.traffic
    parts = cell.family.forward_flops_per_token(config, 4096)
    assert parts["mamba_projections"] == 9 * 2 * C * (8512 + 4096)
    # chunk 256, one group: C B^T, the masked matrix times x, the state left
    # behind and the entering state read out
    assert parts["ssd_scan"] == 9 * 2 * (
        256 * 1 * 128 + 256 * 64 * 64 + 2 * 64 * 64 * 128)
    assert parts["attention_projections"] == 2 * C * 64 * (2 * 32 + 2 * 8)
    assert parts["attention_scores"] == 2 * 32 * 128 * 4097 / 2
    assert parts["mlp"] == 10 * 2 * MLP
    assert parts["head"] == 2 * C * 12544
    total = sum(parts.values())
    # 4.8 GFLOP a token, forward and backward
    assert 3 * total == pytest.approx(4.80e9, rel=2e-3)
    assert parts["mlp"] / total == pytest.approx(0.63, abs=0.005)
    assert cell.family.step_flops(config, traffic, 1) == pytest.approx(
        3 * total * 4096)
    assert cell.family.units_per_step(traffic) == 4096


def test_the_scan_count_reads_the_family_keys(cell):
    """`kernels/ssd_scan_g1.py` is `kernels/ssd_scan.py`'s count under the
    family's names: one count, nine Mamba-2 layers."""
    config = cell.config
    keys = cell.family.scan_keys(config)
    scan = manifest.load_module("kernels", "ssd_scan")
    assert scan.mamba_layers(keys) == 9
    assert scan.flops_per_token(keys) == 2 * (
        32_768 + 1_048_576 + 1_048_576)
    # x and y 64 x 64 in bf16, B and C 1 x 128 in bf16, dt 64 in float32
    assert scan.bytes_per_token(keys) == 2 * (2 * 4096 + 2 * 128) + 256
    flops, nbytes = manifest.load_module("kernels", "ssd_scan_g1").step_work(
        config, 4096)
    assert (flops, nbytes) == scan.step_work(keys, 4096)
    assert flops == 3 * 9 * 4096 * 4_259_840
    assert nbytes == 3 * 9 * 4096 * 17_152
    # arithmetic-bound on the v5e, barely: 2.39 ms against 2.32
    assert flops / 197e12 > nbytes / 819e9
    assert cell.family.forward_flops_per_token(config, 4096)["ssd_scan"] == (
        9 * scan.flops_per_token(keys))


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
    the family's reference are the same arithmetic: the chunked scan at one
    group and the recurrence computed position by position."""
    from paddle_tpu.contrib import mixed_precision

    small = manifest.load_cell(manifest.load_manifest(), CELL, rehearse=True)
    # one group of B and C for all heads, four chunks a row, both kinds
    assert small.config["mamba_n_groups"] == 1
    assert small.traffic["seq_len"] == 4 * small.config["mamba_chunk_size"]
    assert set(small.config["layer_types"]) == {"mamba", "attention"}
    with mock.patch.object(mixed_precision, "decorate",
                           lambda opt, use_bf16=True: opt):
        check = harness.run_check(small, seed=5)
    assert check["loss_rel_error"] < 1e-5
    assert max(check["grad_rel_l2_error"].values()) < 2e-4
    assert set(check["grad_rel_l2_error"]) == LABELS and check["loss_falls"]


@pytest.mark.parametrize("seed", [5, 2147483999])
def test_faults_and_a_lower_precision_are_refused_by_the_limits(seed):
    """The family's reference with its products rounded to an 8-bit float
    and under each of its faults lands outside at least one of the
    rehearsal's limits; with bf16 products inside all."""
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
    limits = config["check"]["grad_rel_l2"]

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


# ---------------------------------------------------------------------------
# the new readers over a hand-made trace
# ---------------------------------------------------------------------------

FWD = "jit(step)/forward/"
BWD = "jit(step)/backward/"
SCAN = "jvp(mamba2)/ssd_scan/checkpoint/"


def _module():
    ins = roles.Instruction
    return roles.Module("jit_step", {
        1: [ins("fusion.1", "fusion",
                FWD + "block_mlp/jvp(swiglu_ffn)/dot_general", (2,)),
            ins("fusion.2", "fusion", FWD + SCAN + "dot_general", (3,)),
            ins("fusion.3", "fusion", BWD + "block_mlp/transpose(jvp("
                "swiglu_ffn))/dot_general", (4,)),
            ins("fusion.4", "fusion", FWD + "jvp(mamba2)/dot_general", (5,)),
            ins("fusion.5", "fusion", FWD + "block_mlp/add", (6,)),
            ins("fusion.6", "fusion", FWD + "block_mlp/jvp(rms_norm)/mul",
                (7,)),
            ins("fusion.7", "fusion", BWD + "transpose(jvp(mamba2))/"
                "ssd_scan/checkpoint/rematted_computation/exp", (8,)),
            ins("fusion.8", "fusion", "jit(step)/optimizer/sub", (9,))],
        2: [ins("dot.1", "dot", FWD + "block_mlp/jvp(swiglu_ffn)/dot")],
        3: [ins("dot.2", "dot", FWD + SCAN + "dot_general")],
        4: [ins("dot.3", "dot", BWD + "block_mlp/transpose(jvp(swiglu_ffn))"
                "/dot")],
        5: [ins("dot.4", "dot", FWD + "jvp(mamba2)/dot_general")],
        # an MLP's residual add fused with the next mixer's norm and
        # in_proj: the MLP's and the mixer's, so neither's for the MLP
        6: [ins("add.1", "add", FWD + "block_mlp/add"),
            ins("mul.1", "multiply", FWD + "jvp(rms_norm)/mul"),
            ins("dot.5", "dot", FWD + "jvp(mamba2)/dot_general")],
        # the MLP's own norm lies under its scope
        7: [ins("mul.2", "multiply", FWD + "block_mlp/jvp(rms_norm)/mul")],
        8: [ins("exp.1", "exponential", BWD + "transpose(jvp(mamba2))/"
                "ssd_scan/checkpoint/rematted_computation/exp")],
        9: [ins("sub.1", "subtract", "jit(step)/optimizer/sub")],
    })


def _planes():
    names = ["fusion.1", "fusion.2", "fusion.3", "fusion.4", "fusion.5",
             "fusion.6", "fusion.7", "fusion.8"]
    ends = [20, 26, 40, 50, 56, 60, 74, 80]
    events, start = [], 0
    for name, end in zip(names, ends):
        events.append(Event(name, start * MS, end * MS))
        start = end
    device = Plane("/device:TPU:0", [
        Line(tr.OPS_LINE, events),
        Line(roles.MODULES_LINE, [Event("jit_step(7)", 0, 80 * MS)])])
    host = Plane(tr.HOST_PLANE, [Line("python3", [
        Event(tr.WINDOW_SPAN, 0, 84 * MS)])])
    return [device, host]


def _run_facts(cell, trace=True):
    device = tr.DeviceReduction(
        ordinal=0, window_ns=84 * MS, busy_ns=80 * MS, self_ns_by_name={},
        calls_by_name={}, kernel_ns={}, kernel_calls={}, collective_ns=0.0,
        collective_exposed_ns=0.0, gaps=[])
    return harness.RunFacts(
        cell, manifest.load_peaks("TPU v5 lite"), [], {}, {},
        tr.TraceReduction([device], 0.084, 2, {}) if trace else None, {}, 0)


def _clear():
    part_scopes.split_of_trace.cache_clear()
    roles.split_of_trace.cache_clear()


def test_the_new_readers_over_the_trace_and_without_one(cell, tmp_path,
                                                        monkeypatch):
    path = tmp_path / "plugins" / "profile" / "2026_01_01" / "hand.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_xplane(_planes(), {"jit_step(7)": _module()}))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    _clear()
    readers = {n: manifest.load_module("layer_metrics", n)
               for n in NEW_READERS}
    got = {n: r.read(_run_facts(cell)) for n, r in readers.items()}
    # the MLP's products forward and backward and its own norm; not the
    # fusion it shares with the next mixer
    assert got["block_mlp_ms_per_step"] == pytest.approx((20 + 14 + 4) / 2)
    # the projection, the scan forward and backward, and the fusion that
    # holds the next mixer's norm and in_proj beside an MLP's add
    assert got["mamba_g1_ms_per_step"] == pytest.approx((6 + 10 + 6 + 14) / 2)
    assert got["ssd_scan_g1_ms_per_step"] == pytest.approx((6 + 14) / 2)
    # the two are the Nemotron cell's readers under the cell's own names
    for name in ("mamba", "ssd_scan"):
        same = manifest.load_module("layer_metrics", f"{name}_ms_per_step")
        assert same.read(_run_facts(cell)) == got[f"{name}_g1_ms_per_step"]
    flops, nbytes = manifest.load_module("kernels", "ssd_scan_g1").step_work(
        cell.config, 4096)
    assert got["ssd_scan_g1_roofline"] == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 10e-3, rel=1e-6)
    assert all(0 < v < 100 for v in got.values()), got
    # an untraced run, and a step without the scopes (an older program
    # under these reader files): nothing to read, nothing raised
    assert [r.read(_run_facts(cell, trace=False))
            for r in readers.values()] == [None] * 4
    bare = roles.Module("jit_step", {1: [
        roles.Instruction("fusion.1", "fusion",
                          FWD + "jvp(rms_norm)/dot_general"),
        roles.Instruction("fusion.2", "fusion", FWD + "jvp()/mul")]})
    path.write_bytes(_xplane(_planes(), {"jit_step(7)": bare}))
    _clear()
    assert [r.read(_run_facts(cell)) for r in readers.values()] == [None] * 4
    _clear()
